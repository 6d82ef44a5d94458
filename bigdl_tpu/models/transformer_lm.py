"""Transformer language model — the flagship multi-chip model.

Parity: reference ``nn/Transformer.scala`` LM mode (used by the reference's
Transformer example); extended TPU-first with flash attention and
dp×tp×sp sharding hooks (see ``parallel/``). This is the ``__graft_entry__``
model: the driver compile-checks its forward single-chip and its full
sharded train step on an N-device mesh.

TPU memory story (round 3): LM-mode self-attention runs the fused Pallas
flash path (O(T) memory — no (B,H,T,T) score matrix), ``remat=True`` runs
each block under ``jax.checkpoint`` (the backward pass recomputes the block
from its input; only that input and the flash kernel's output and logsumexp
are kept, two (B,T,H) tensors a layer), and :func:`lm_loss_chunked` fuses
the tied vocab projection with the softmax-CE loss in rematerialised
sequence chunks so the (B,T,vocab) logits tensor never exists. Together
these take the B16/T1024 12-layer config from HBM-OOM on a 16 GB v5e to
fitting with room.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nn import Transformer


def TransformerLM(vocab_size: int = 32000, hidden_size: int = 512,
                  num_heads: int = 8, filter_size: int = 2048,
                  num_layers: int = 6, dropout: float = 0.0,
                  max_len: int = 2048, use_flash: bool = True,
                  remat: bool = False, num_kv_heads=None,
                  pos_encoding: str = "sinusoidal",
                  ffn_activation: str = "relu"):
    """``num_kv_heads < num_heads`` turns on grouped-query attention:
    K/V projections and the decode KV caches shrink by the group factor
    — the decode path's HBM-bandwidth lever (each step streams the whole
    cache; see the grouped branch of Attention.decode_chunk).
    ``pos_encoding='rope'`` swaps the
    additive sinusoidal PE for rotary embeddings on q/k (relative
    positions; the KV cache stores rotated keys).
    ``remat=True`` (``nn.attention.remat_block``): the backward pass
    recomputes each block from its input. Kept per layer are that input
    and, where the flash kernel ran, its output and logsumexp, so the
    forward kernel is not run a second time: two (B,T,H) tensors and
    (B,heads,T) f32 a layer, one (B,T,H) on the einsum path."""
    return Transformer(vocab_size=vocab_size, hidden_size=hidden_size,
                       num_heads=num_heads, filter_size=filter_size,
                       num_hidden_layers=num_layers,
                       postprocess_dropout=dropout,
                       attention_dropout=dropout, relu_dropout=dropout,
                       mode="lm", max_len=max_len, use_flash=use_flash,
                       remat=remat, num_kv_heads=num_kv_heads,
                       pos_encoding=pos_encoding,
                       ffn_activation=ffn_activation)


def lm_loss_chunked(h, embed, targets, chunk: int = 128,
                    padding_value: int = 0):
    """Tied-projection softmax cross-entropy over hidden states without
    materialising the full (B, T, vocab) logits.

    Computed as a ``lax.scan`` over sequence chunks whose body is wrapped in
    ``jax.checkpoint``: forward AND backward only ever hold one
    (B, chunk, vocab) logits block (f32), turning the loss head's HBM
    high-water mark from O(T·vocab) into O(chunk·vocab).

    Token-id convention: targets are RAW token ids — 0-based rows of the
    tied embedding, so logits column ``j`` means "next token is ``j``" and
    ``argmax(logits)`` round-trips through ``Transformer.generate``
    directly. (This deliberately differs from the torch-parity
    ``ClassNLLCriterion`` family's 1-based CLASS labels: a 1-based head
    over a tied embedding would train every logit column to mean
    "token j+1" and make greedy decoding off by one — caught by
    ``examples/lm_generate.py``.) ``padding_value`` entries (default 0 —
    reserve id 0 for padding) are excluded; mean over valid positions.

    h: (B, T, H) hidden states; embed: (vocab, H) tied embedding;
    targets: (B, T) token ids (``padding_value`` = ignore).
    """
    B, T, H = h.shape
    if T % chunk != 0:
        # largest divisor of T <= chunk keeps the O(chunk·vocab) bound for
        # every T (falling back to chunk=T would silently reinstate the
        # full-logits high-water mark this function exists to avoid)
        chunk = next(c for c in range(min(chunk, T), 0, -1) if T % c == 0)
    n = T // chunk
    hc = jnp.moveaxis(h.reshape(B, n, chunk, H), 1, 0)        # (n,B,c,H)
    yc = jnp.moveaxis(
        jnp.asarray(targets).astype(jnp.int32).reshape(B, n, chunk),
        1, 0)                                                  # (n,B,c)

    def chunk_loss(hx, emb, yx):
        # bf16 operands, f32 ACCUMULATION — `(hx @ emb.T).astype(f32)`
        # would round the logits to bf16 first and only then upcast
        logits = jax.lax.dot_general(
            hx, emb, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (B,c,V)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        idx = jnp.clip(yx, 0, logits.shape[-1] - 1)  # raw token ids
        gold = jnp.take_along_axis(logits, idx[..., None],
                                   axis=-1)[..., 0]
        valid = (yx != padding_value).astype(jnp.float32)
        return (jnp.sum((lse - gold) * valid), jnp.sum(valid))

    def body(carry, xs):
        hx, yx = xs
        s, c = jax.checkpoint(chunk_loss)(hx, embed, yx)
        return (carry[0] + s, carry[1] + c), None

    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, yc))
    return loss_sum / jnp.maximum(count, 1.0)
