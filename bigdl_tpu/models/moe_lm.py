"""Switch-MoE Transformer language model.

TPU-first addition beyond the reference (BigDL 0.x predates MoE; its
gating ancestor is ``nn/MixtureTable.scala``). Decoder-only causal LM in
the Switch-Transformer layout: every ``moe_every``-th block replaces its
dense FFN with a top-1-routed :class:`bigdl_tpu.nn.MixtureOfExperts`
(capacity + load-balance loss). The summed auxiliary router loss is
surfaced in ``state['aux_loss']`` so training adds
``aux_weight * aux_loss`` to the objective; for the expert-PARALLEL
sharded form of the same math see ``parallel/moe.py`` (used by
``__graft_entry__.dryrun_multichip``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nn.attention import (LayerNormalization, Transformer,
                            TransformerBlock, embed_ids, remat_block)
from ..nn.moe import MixtureOfExperts
from ..nn.module import Module
from ..utils.table import Table


class MoETransformerLM(Module):
    """GPT-style decoder with MoE FFNs on a stride (Switch-Transformer)."""

    pos_encoding = "sinusoidal"   # class default: pre-r4 pickles lack it

    def __init__(self, vocab_size: int, hidden_size: int = 256,
                 num_heads: int = 4, filter_size: int = 1024,
                 num_layers: int = 4, n_experts: int = 4,
                 moe_every: int = 2, capacity_factor: float = 1.25,
                 max_len: int = 2048, use_flash: bool = True,
                 remat: bool = False, num_kv_heads=None,
                 pos_encoding: str = "sinusoidal", name=None):
        super().__init__(name=name)
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.max_len = max_len
        if pos_encoding not in ("sinusoidal", "rope"):
            raise ValueError(f"pos_encoding must be 'sinusoidal' or "
                             f"'rope', got {pos_encoding!r}")
        self.pos_encoding = pos_encoding
        # nn.attention.remat_block per block, as Transformer's remat: the
        # router's dispatch/combine one-hots are (T, E, capacity)-sized
        # residuals — at bench scale ~GBs the backward would otherwise keep
        # live. Kept per layer: the block input and the flash kernel's
        # output and logsumexp; everything else is recomputed
        self.remat = remat
        self.mode = "lm"  # the Transformer inference machinery's guard
        self.blocks = []
        self.moe_idx = set(range(moe_every - 1, num_layers, moe_every))
        for i in range(num_layers):
            if i in self.moe_idx:
                self.blocks.append(_MoEBlock(hidden_size, num_heads,
                                             filter_size, n_experts,
                                             capacity_factor,
                                             use_flash=use_flash,
                                             num_kv_heads=num_kv_heads,
                                             rope=(pos_encoding
                                                   == "rope")))
            else:
                self.blocks.append(TransformerBlock(
                    hidden_size, num_heads, filter_size, causal=True,
                    use_flash=use_flash, num_kv_heads=num_kv_heads,
                    rope=(pos_encoding == "rope")))
        self.ln_f = LayerNormalization(hidden_size)

    def _init_params(self, rng):
        k = jax.random.split(rng, 2 + len(self.blocks))
        p = {"embed": 0.02 * jax.random.normal(
                k[0], (self.vocab_size, self.hidden_size)),
             "ln_f": self.ln_f._init_params(k[1])}
        for i, blk in enumerate(self.blocks):
            p[f"block{i}"] = blk._init_params(k[2 + i])
        return p

    def _init_state(self):
        return {"aux_loss": jnp.zeros(())}

    def _embed(self, params, ids):
        return embed_ids(params["embed"], ids, self.hidden_size,
                         with_pe=self.pos_encoding != "rope")

    def hidden_states(self, params, ids, training=False, rng=None):
        """``(h, aux_loss)`` — final pre-projection hidden states plus the
        summed router auxiliary loss. Mirrors ``Transformer.hidden_states``
        so callers can fuse the tied projection with the loss
        (``models.lm_loss_chunked``) instead of materialising the full
        (B, T, vocab) logits tensor."""
        h = embed_ids(params["embed"], ids, self.hidden_size,
                      with_pe=self.pos_encoding != "rope")
        # causal masking lives inside the blocks (flash-friendly — no
        # materialised (T, T) mask, mirroring Transformer's LM mode)
        mask = None
        aux = jnp.zeros((), h.dtype)
        for i, blk in enumerate(self.blocks):
            r = jax.random.fold_in(rng, i) if rng is not None else None
            if i in self.moe_idx:
                def run(p, hh, blk=blk, r=r):
                    return blk.apply_with_aux(p, hh, mask, training, r)
                if self.remat:
                    run = remat_block(run)
                h, a = run(params[f"block{i}"], h)
                aux = aux + a
            else:
                def run(p, hh, blk=blk, r=r):
                    return blk._apply(p, {}, Table(hh, mask), training, r)
                if self.remat:
                    run = remat_block(run)
                h = run(params[f"block{i}"], h)
        h, _ = self.ln_f.apply(params["ln_f"], {}, h, training, None)
        return h, aux

    def _apply(self, params, state, x, training, rng):
        h, aux = self.hidden_states(params, x, training, rng)
        logits = h @ params["embed"].T  # tied output projection
        return logits, {"aux_loss": aux}

    # ---- autoregressive inference: the shared Transformer machinery,
    # bound as-is (blocks inherit prefill/decode_step; MoE routing is
    # token-level, so cached decode routes each new token normally).
    # Caveat: expert capacity is computed per forward — a full-sequence
    # forward can DROP tokens at tight capacity_factor where one-token
    # decode steps never do, so cached and naive decoding can differ
    # exactly when the full forward would have dropped a token ----
    init_cache = Transformer.init_cache
    prefill = Transformer.prefill
    prefill_chunked = Transformer.prefill_chunked
    _decode_trunk = Transformer._decode_trunk
    decode_one = Transformer.decode_one
    decode_chunk = Transformer.decode_chunk   # decode_one's LM trunk —
    # and the speculative-verify primitive (nn/speculative.py). Caveat
    # (same capacity mechanics as the prefill note above): the verify
    # pass routes S=k+1 tokens per forward, so at tight capacity_factor
    # it can DROP a token that one-token decode steps never drop —
    # speculative output then differs from dense greedy exactly where
    # cached and full-forward decoding already can. A MoE speculative
    # target is exact whenever capacity is not saturated; dense
    # TransformerLM targets are exact unconditionally.
    generate = Transformer.generate


class _MoEBlock(TransformerBlock):
    """TransformerBlock whose FFN slot holds a MixtureOfExperts — the
    attention sublayer, param layout and rng handling are inherited, so
    the two block types cannot drift."""

    def __init__(self, hidden_size: int, num_heads: int, filter_size: int,
                 n_experts: int, capacity_factor: float,
                 use_flash: bool = True, num_kv_heads=None,
                 rope: bool = False, name=None):
        super().__init__(hidden_size, num_heads, filter_size, causal=True,
                         use_flash=use_flash, num_kv_heads=num_kv_heads,
                         rope=rope, name=name)
        self.ffn = MixtureOfExperts(hidden_size, n_experts,
                                    ffn_hidden=filter_size,
                                    capacity_factor=capacity_factor)

    def apply_with_aux(self, params, h, mask, training, rng):
        h = self._attn_sublayer(params, h, mask, training, rng)
        n, _ = self.ln2.apply(params["ln2"], {}, h, training, None)
        f, st = self.ffn.apply(params["ffn"], self.ffn._init_state(), n,
                               training, None)
        return h + f, st["aux_loss"]

    def _apply(self, params, state, x, training, rng):
        h, mask = (x[1], x[2]) if isinstance(x, Table) else (x, None)
        out, _ = self.apply_with_aux(params, h, mask, training, rng)
        return out
