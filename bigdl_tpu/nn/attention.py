"""Attention / transformer layers.

Parity: reference ``nn/Attention.scala`` (multi-head dot-product attention),
``nn/FeedForwardNetwork.scala``, ``nn/Transformer.scala`` (Vaswani-style,
LM and translation modes), ``nn/TransformerOperation.scala`` helpers.

TPU-first: attention is computed as two batched einsums (MXU) with an optional
fused Pallas flash-attention kernel on TPU backends (O(T) memory, tiled over
sequence); the reference has no fused path at all. Ring attention for
sequence parallelism lives in ``bigdl_tpu.parallel.ring_attention``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from .module import Module
from .norm import LayerNormalization, RMSNorm
from ..kernels.flash_attention import FLASH_LSE_NAME, FLASH_OUT_NAME
from ..kernels.sparse_attention import DSA_KEPT_NAME
from ..utils.table import Table


def _glorot(rng, shape):
    fan_in, fan_out = shape[0], shape[-1]
    s = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(rng, shape, minval=-s, maxval=s)


def yarn_inv_freq(dim: int, base: float, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """The ``dim // 2`` inverse frequencies (float64) of YaRN-scaled RoPE
    (Peng et al. 2023, as ``deepseek_v3`` configs use it): a linear
    ramp blends the interpolated frequency ``1 / (factor * base^(2i/dim))``
    into the extrapolated ``1 / base^(2i/dim)`` between the dimensions that
    turn ``beta_fast`` and ``beta_slow`` times over the original length.
    The attention scale's ``mscale`` is the caller's."""
    half = dim // 2
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * math.log(original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / ((high - low) or 0.001), 0.0, 1.0)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_embedding(x, positions, base: float = 10000.0):
    """Rotary position embedding (RoPE, rotate-half convention).

    x: (..., T, D) with D even; positions: (T,) integer global positions,
    or (B, T) PER-ROW positions for x shaped (B, H, T, D) — the paged
    decode layout, where every batch row sits at its own sequence depth.
    Rotation is absolute per position, so attention logits depend only on
    relative distance — the modern alternative to the reference's additive
    sinusoidal PE (``nn/TransformerOperation.scala`` getPositionEncode),
    and the form KV caches prefer (cache entries hold already-rotated K).
    The per-row branch computes cos/sin from the identical ``pos * freqs``
    products, so a given position's rotation is bitwise the same whether
    it arrived via the shared or the per-row path.
    """
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"RoPE needs an even head dim, got {d}")
    half = d // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 2:
        ang = positions.astype(jnp.float32)[..., None] * freqs  # (B,T,half)
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    else:
        ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)      # (T, half)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def dot_product_attention(q, k, v, mask=None, dropout_p=0.0, rng=None,
                          training=False):
    """q,k,v: (B, H, T, D). mask: additive (broadcastable) or None."""
    d = q.shape[-1]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    if mask is not None:
        logits = logits + mask
    w = jax.nn.softmax(logits, axis=-1)
    if training and dropout_p > 0.0 and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_p, w.shape)
        w = jnp.where(keep, w / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def flash_attention(q, k, v, causal=False):
    """Fused attention. Delegates to the ``bigdl_tpu.parallel.flash``
    dispatcher: the custom Pallas kernel on the ``tpu`` platform (a
    kernel failure raises), the einsum path on any other platform."""
    from ..parallel.flash import flash_attention as dispatch
    return dispatch(q, k, v, causal=causal)


def causal_mask(t, dtype=jnp.float32):
    return jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], 0.0,
                     jnp.asarray(-1e9, dtype))


def padding_mask(lengths_or_mask, t):
    """Build additive (B,1,1,T) mask from a (B,T) 0/1 keep-mask."""
    m = lengths_or_mask.astype(jnp.float32)
    return (m[:, None, None, :] - 1.0) * 1e9


class Attention(Module):
    """Multi-head attention (nn/Attention.scala). Input Table(query_seq,
    key_value_seq, additive_mask_or_None) or a single tensor (self-attn)."""

    seq_impl = "ring"     # class defaults: pre-r4 pickles lack the attrs
    num_kv_heads = None   # None → MHA (kv heads == query heads)
    rope = False          # rotary position embedding on q/k
    head_dim = None       # None → hidden_size // num_heads

    def __init__(self, hidden_size: int, num_heads: int,
                 attention_dropout: float = 0.0, use_flash: bool = True,
                 seq_axis=None, causal: bool = False, seq_impl: str = "ring",
                 num_kv_heads=None, rope: bool = False, head_dim=None,
                 name=None):
        """``seq_axis``: name of a mesh axis the sequence dim is sharded
        over — attention then runs sequence-parallel. ``seq_impl``
        picks the scheme: ``"ring"`` (parallel/ring_flash.py: ppermute
        K/V rotation, Pallas blocks, O(T/n) memory, any head count) or
        ``"a2a"`` (parallel/seq_all_to_all.py: Ulysses-style
        head-scatter all_to_all, dense flash locally, needs
        num_heads % axis_size == 0). Only valid inside ``shard_map``
        over that axis; self-attention only, masking via ``causal``
        (additive masks cannot cross devices). ``head_dim``: the width of
        a head where ``num_heads * head_dim`` is not ``hidden_size`` (a
        tensor-parallel share of the heads: q and o are ``num_heads *
        head_dim`` wide)."""
        super().__init__(name=name)
        assert head_dim or hidden_size % num_heads == 0
        if seq_axis is not None and attention_dropout > 0:
            raise ValueError(
                "seq-parallel attention does not support attention "
                "dropout (the ring kernel has no dropout path) — set "
                "attention_dropout=0")
        self.hidden_size, self.num_heads = hidden_size, num_heads
        self.attention_dropout = attention_dropout
        self.use_flash = use_flash
        self.seq_axis = seq_axis
        self.seq_impl = seq_impl
        self.causal = causal
        self.num_kv_heads = num_kv_heads
        self.rope = rope
        self.head_dim = head_dim
        if rope and self._head_dim() % 2:
            raise ValueError("RoPE needs an even head dim")
        if num_kv_heads is not None:
            if num_heads % num_kv_heads:
                raise ValueError(
                    f"num_kv_heads ({num_kv_heads}) must divide "
                    f"num_heads ({num_heads})")
            # GQA composes with the sequence-parallel paths: K/V heads
            # are broadcast up to num_heads BEFORE the ring/a2a exchange
            # (_apply's _expand_kv), so the kernels see equal head
            # counts. The broadcast costs the GQA K/V memory saving on
            # the TRAINING path only — the decode-path win (compact
            # caches) is untouched. r4 rejected this combination; r5
            # lifted it with the ring/a2a-vs-dense GQA oracle test
            # (tests/test_seq_parallel.py).

    def _kvh(self):
        return self.num_kv_heads or self.num_heads

    def _head_dim(self):
        return self.head_dim or self.hidden_size // self.num_heads

    def _init_params(self, rng):
        k = jax.random.split(rng, 4)
        H, d = self.hidden_size, self._head_dim()
        qd, kvd = self.num_heads * d, self._kvh() * d
        return {"wq": _glorot(k[0], (H, qd)), "wk": _glorot(k[1], (H, kvd)),
                "wv": _glorot(k[2], (H, kvd)), "wo": _glorot(k[3], (qd, H))}

    def _split(self, x, heads=None):
        """``[B, T, heads * D]`` → ``[B, heads, T, D]``: the layout of the
        KV caches, the einsum paths and the sequence-parallel exchanges.
        A transposing copy on the device; the flash branch of
        :meth:`_apply` does without it."""
        b, t, _ = x.shape
        return x.reshape(b, t, heads or self.num_heads,
                         -1).transpose(0, 2, 1, 3)

    def _project_fused(self, params, qx, kx=None):
        """Self-attention's q, k and v as ONE matmul writes them, ``[B, T,
        (nH + 2 * kvH) * D]``: one read of the activations and one MXU
        contraction with the concatenated ``[H, H + 2 * kvD]`` weight
        (params stay separate wq/wk/wv, the checkpoint layout; the
        concatenation runs in the step and costs ``3 H^2`` elements
        written and read a layer). None where there is no such matmul:
        cross-attention, and int8 ``QuantizedWeight`` wrappers
        (quantization/lm.py), which dequantize per matmul and cannot be
        concatenated."""
        ws = (params["wq"], params["wk"], params["wv"])
        if (kx is None or kx is qx) and all(
                isinstance(w, jnp.ndarray) for w in ws):
            return qx @ jnp.concatenate(ws, axis=1)
        return None

    def _project(self, params, qx, kx=None):
        """The q, k, v projections as arrays of their own: query ``[B, T,
        nH * D]``, key and value ``[B, T, kvH * D]``: the slices of
        :meth:`_project_fused` (each a copy on the device: the flash
        branch of :meth:`_apply` hands the product over unsliced), or
        three matmuls where there is no fused product."""
        flat = self._project_fused(params, qx, kx)
        if flat is not None:
            qd = self.num_heads * self._head_dim()
            kvd = params["wk"].shape[1]
            return (flat[..., :qd], flat[..., qd:qd + kvd],
                    flat[..., qd + kvd:])
        kx = qx if kx is None else kx
        return qx @ params["wq"], kx @ params["wk"], kx @ params["wv"]

    def qkv(self, params, qx, kx=None):
        """:meth:`_project`, split into heads: query ``[B, nH, T, D]`` and
        key/value ``[B, kvH, T, D]`` — kvH < nH is grouped-query attention
        (GQA: the KV cache and K/V projections shrink by nH/kvH, the
        decode-path HBM lever)."""
        q, k, v = self._project(params, qx, kx)
        kvh = self._kvh()
        return self._split(q), self._split(k, kvh), self._split(v, kvh)

    def _expand_kv(self, k, v):
        """Broadcast kv heads up to the query head count for the dense/
        flash/seq-parallel paths (grouped decode never expands — see the
        grouped branch of :meth:`decode_chunk`)."""
        g = self.num_heads // self._kvh()
        if g == 1:
            return k, v
        return jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)

    def _merge(self, o, params):
        """Split heads ``[B, H, T, D]`` → ``[B, T, H * D]`` (a transposing
        copy) and the output projection."""
        b, h, t, d = o.shape
        return o.transpose(0, 2, 1, 3).reshape(b, t, h * d) @ params["wo"]

    def decode(self, params, x_t, k_cache, v_cache, pos):
        """One autoregressive step: project the current token, write its
        K/V into the cache at ``pos`` (traced scalar), attend over
        positions <= pos. x_t: (B, 1, H); caches: (B, kvH, Tmax, D) —
        kvH = num_kv_heads (== num_heads without GQA; build them with
        Transformer.init_cache). Returns (out (B, 1, H), k_cache,
        v_cache). The S=1 case of :meth:`decode_chunk` — one
        implementation of masked cached-KV attention."""
        return self.decode_chunk(params, x_t, k_cache, v_cache, pos)

    def decode_chunk(self, params, x, k_cache, v_cache, pos):
        """S cached positions in ONE forward (the speculative-decode
        verify primitive, nn/speculative.py): project x (B, S, H), write
        K/V at positions pos..pos+S-1, attend with causal-within-chunk +
        everything-before masking. One pass over the whole cache serves
        all S positions — that amortisation is why verifying k draft
        tokens costs about one decode step, not k. Returns
        (out (B, S, H), k_cache, v_cache)."""
        q, k_t, v_t = self.qkv(params, x)
        S = q.shape[2]
        if self.rope:
            p = pos + jnp.arange(S)
            q = rotary_embedding(q, p)
            k_t = rotary_embedding(k_t, p)   # cache holds rotated K
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_t.astype(k_cache.dtype), (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_t.astype(v_cache.dtype), (0, 0, pos, 0))
        d = q.shape[-1]
        t = k_cache.shape[2]
        groups = self.num_heads // self._kvh()
        if (groups == 1 and self.use_flash and isinstance(pos, int)
                and S >= 8):
            # static offset (chunked prefill: the chunk loop is unrolled
            # with Python-int positions) → the rectangular-causal flash
            # kernel streams the valid cache prefix in tiles instead of
            # materialising (B, H, S, pos+S) logits. The FULL cache is
            # passed with kv_len — the kernel bounds its grid to the
            # valid key blocks, no slice copy. Traced pos (speculative
            # verify, S = k+1 ~ 5) keeps the einsum below — its logits
            # are tiny there.
            from ..parallel.flash import flash_chunk_attention
            o = flash_chunk_attention(q, k_cache, v_cache, q_offset=pos,
                                      kv_len=pos + S)
            return self._merge(o, params), k_cache, v_cache
        keep = (jnp.arange(t)[None, :]
                <= (pos + jnp.arange(S))[:, None])          # (S, T)
        if groups > 1:
            b, h, _, dd = q.shape
            kvh = h // groups
            qg = q.reshape(b, kvh, groups, S, dd)
            logits = jnp.einsum("bkgsd,bktd->bkgst", qg,
                                k_cache) / math.sqrt(d)
            logits = jnp.where(keep[None, None, None], logits, -1e30)
            w = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bkgst,bktd->bkgsd", w,
                           v_cache).reshape(b, h, S, dd)
        else:
            logits = jnp.einsum("bhsd,bhtd->bhst", q,
                                k_cache) / math.sqrt(d)
            logits = jnp.where(keep[None, None], logits, -1e30)
            w = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bhst,bhtd->bhsd", w, v_cache)
        return self._merge(o, params), k_cache, v_cache

    def decode_paged(self, params, x, k_pages, v_pages, block_tables,
                     positions):
        """Cached attention over a PAGED KV store with PER-ROW positions —
        the continuous-batching decode primitive (serving/kv_cache.py,
        serving/decode_scheduler.py). Where :meth:`decode_chunk` indexes
        one dense (B, kvH, Tmax, D) cache at a single shared ``pos``,
        this path lets every batch row sit at its own sequence depth in
        fixed-size HBM blocks shared by the whole engine:

        x: (B, S, H) tokens landing at positions
        ``positions[b] .. positions[b]+S-1`` (S=1 is the decode step,
        S=k+1 the speculative verify chunk);
        k_pages/v_pages: (num_blocks, kvH, block_size, D) — the pooled
        block storage; block_tables: (B, max_blocks) int32 mapping row
        ``b``'s logical block ``i`` to a physical page (0 is the
        engine's reserved null block — padded slots point every entry
        there); positions: (B,) int32.

        Writes the S new K/V entries through the table (scatter), then
        attends. Two implementations of the attention itself, one
        dispatch policy (``parallel.flash.paged_attention``, gated by
        ``BIGDL_TPU_PAGED_ATTN``):

        * the DENSE path (:meth:`_paged_gather_attend` — the non-TPU
          path and the oracle) gathers the logical (B, kvH, T, D) view
          through the tables and einsums over it. The gathered view
          presents logical positions 0..max_blocks*block_size-1 in
          order and masked positions contribute exactly 0 after softmax
          (their logits are -1e30 → exp underflows to +0.0), so the
          unmasked arithmetic is bitwise-identical to
          :meth:`decode_chunk` over a dense cache — the
          continuous-batching correctness gate rests on that;
        * the Pallas KERNEL (``kernels/paged_attention.py``) streams
          the row's physical blocks through VMEM via scalar-prefetched
          tables — no gathered view, no O(T) HBM round-trip. Its
          online-softmax output matches the dense path to ulps (greedy
          argmax absorbs the difference — the kernel-on serving gate).

        Returns (out (B, S, H), k_pages, v_pages)."""
        q, k_t, v_t = self.qkv(params, x)
        S = x.shape[1]
        if self.rope:
            p = positions[:, None] + jnp.arange(S)[None, :]     # (B, S)
            q = rotary_embedding(q, p)
            k_t = rotary_embedding(k_t, p)   # pages hold rotated K
        bs = k_pages.shape[2]
        pos_s = positions[:, None] + jnp.arange(S)[None, :]     # (B, S)
        blk = jnp.take_along_axis(block_tables, pos_s // bs, axis=1)
        off = pos_s % bs
        # k_t (B, kvH, S, D) -> (B, S, kvH, D) rows scattered through the
        # table; duplicate indices only ever occur between padded slots
        # aimed at the null block (garbage either way)
        k_pages = k_pages.at[blk, :, off, :].set(
            jnp.moveaxis(k_t, 1, 2).astype(k_pages.dtype))
        v_pages = v_pages.at[blk, :, off, :].set(
            jnp.moveaxis(v_t, 1, 2).astype(v_pages.dtype))
        from ..parallel.flash import paged_attention
        o = paged_attention(
            q, k_pages, v_pages, block_tables, positions,
            lambda: self._paged_gather_attend(q, k_pages, v_pages,
                                              block_tables, pos_s))
        return self._merge(o, params), k_pages, v_pages

    def _paged_gather_attend(self, q, k_pages, v_pages, block_tables,
                             pos_s):
        """The dense paged-attention path: gather the logical
        (B, kvH, T, D) view through the block tables, einsum over it.
        The non-TPU path and the ORACLE for the Pallas paged kernel — every kernel
        change must keep this path bitwise-stable."""
        B, S = pos_s.shape
        bs = k_pages.shape[2]
        # gather the logical view: (B, nblk, kvH, bs, D) -> (B, kvH, T, D)
        kg = jnp.moveaxis(k_pages[block_tables], 2, 1)
        vg = jnp.moveaxis(v_pages[block_tables], 2, 1)
        t = block_tables.shape[1] * bs
        kg = kg.reshape(B, kg.shape[1], t, -1)
        vg = vg.reshape(B, vg.shape[1], t, -1)
        d = q.shape[-1]
        keep = (jnp.arange(t)[None, None, :] <= pos_s[:, :, None])  # (B,S,T)
        groups = self.num_heads // self._kvh()
        if groups > 1:
            b, h, _, dd = q.shape
            kvh = h // groups
            qg = q.reshape(b, kvh, groups, S, dd)
            logits = jnp.einsum("bkgsd,bktd->bkgst", qg, kg) / math.sqrt(d)
            logits = jnp.where(keep[:, None, None], logits, -1e30)
            w = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("bkgst,bktd->bkgsd", w,
                              vg).reshape(b, h, S, dd)
        logits = jnp.einsum("bhsd,bhtd->bhst", q, kg) / math.sqrt(d)
        logits = jnp.where(keep[:, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhst,bhtd->bhsd", w, vg)

    def _apply(self, params, state, x, training, rng):
        if isinstance(x, Table):
            qx = x[1]
            kx = x[2] if len(x) >= 2 else qx
            mask = x[3] if len(x) >= 3 else None
        else:
            qx, kx, mask = x, x, None
        flash = (self.causal and mask is None and self.use_flash
                 and self.seq_axis is None
                 and not (training and self.attention_dropout > 0.0
                          and rng is not None))
        kvh = self._kvh()
        if flash and not self.rope and kvh == self.num_heads:
            # the fused O(T)-memory path on the projections' own layout:
            # Pallas kernel on TPU backends, einsum+mask elsewhere
            # (parallel/flash dispatcher); no head is split or merged, and
            # self-attention's one projection is not sliced: the kernels
            # read q, k and v out of it
            from ..parallel.flash import (flash_attention_qkv,
                                          flash_attention_rows)
            qkv = self._project_fused(params, qx, kx)
            if qkv is not None:
                o = flash_attention_qkv(qkv, self.num_heads, causal=True)
            else:
                o = flash_attention_rows(*self._project(params, qx, kx),
                                         self.num_heads, causal=True)
            return o @ params["wo"]
        q, k, v = self._project(params, qx, kx)
        q, k, v = self._split(q), self._split(k, kvh), self._split(v, kvh)
        if self.rope:
            if kx is not qx:
                raise ValueError("RoPE supports self-attention only")
            t = q.shape[2]
            pos = jnp.arange(t)
            if self.seq_axis is not None:
                # local block → global positions (runs inside shard_map)
                pos = pos + jax.lax.axis_index(self.seq_axis) * t
            q = rotary_embedding(q, pos)
            k = rotary_embedding(k, pos)
        k, v = self._expand_kv(k, v)
        if self.seq_axis is not None:
            if mask is not None:
                raise ValueError(
                    "seq-parallel attention supports causal masking only "
                    "(set causal=True); additive masks cannot cross the "
                    "ring")
            if self.seq_impl == "a2a":
                from ..parallel.seq_all_to_all import a2a_attention
                o = a2a_attention(q, k, v, axis=self.seq_axis,
                                  causal=self.causal,
                                  use_flash=self.use_flash)
            else:
                from ..parallel.ring_flash import ring_flash_attention
                o = ring_flash_attention(q, k, v, axis=self.seq_axis,
                                         causal=self.causal)
        elif flash:
            # RoPE and grouped K/V are applied to split heads: the fused
            # path through the (B, H, T, D) entry
            o = flash_attention(q, k, v, causal=True)
        else:
            if self.causal and mask is None:
                mask = causal_mask(q.shape[2])
            o = dot_product_attention(q, k, v, mask,
                                      self.attention_dropout, rng, training)
        return self._merge(o, params)


def _rotate_half(x, inv_freq):
    """RoPE over the last dim of ``[B, T, heads, d]`` at positions 0..T-1,
    dim i turning with dim i + d / 2 (:func:`rotary_embedding`'s pairing).
    The positions are static, so cos and sin are tables made in float64 at
    trace time: at position 4095 a float32 angle is already 2e-4 rad off,
    at 16383 2e-3."""
    T, half = x.shape[1], x.shape[-1] // 2
    ang = np.arange(T, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(ang), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), x.dtype)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _inv_freq(dim: int, base: float):
    return base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)


class LatentAttention(Module):
    """Multi-head latent attention (DeepSeek-V2/V3's MLA) without a query
    latent, causal self-attention only:

        q_h           = x Wq                      (nope + rope dims a head)
        [c ; k_pe]    = x Wkva                    (kv_lora_rank + rope)
        [k_nope_h ; v_h] = RMSNorm(c) Wkvb        (nope + v dims a head)
        k_h           = [k_nope_h ; RoPE(k_pe)]   (one k_pe for all heads)
        o_h           = softmax(scale * RoPE(q_h) k_h^T + causal) v_h
        y             = (o * sigmoid(x Wg)) Wo    (``gated``; else o Wo)

    RoPE turns the last ``qk_rope_head_dim`` of a head only, with
    :func:`rotary_embedding`'s pairing; ``rope_scaling`` (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``mscale``, ``mscale_all_dim``) gives YaRN frequencies and multiplies
    the softmax scale by ``mscale(all_dim)^2``. After the up-projection q, k
    and v are ``[B, T, heads * d]`` arrays, so where q/k and v heads are
    equally wide and fill 128-lane blocks the flash kernels of the dense
    decoder run them as they are, with the scale passed in. What decode
    would cache is ``c`` and ``k_pe``; no cached path exists yet."""

    def __init__(self, hidden_size: int, num_heads: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, rope_theta: float = 10000.0,
                 rope_scaling: Optional[dict] = None, norm_eps: float = 1e-6,
                 gated: bool = False, use_flash: bool = True, name=None):
        super().__init__(name=name)
        if qk_rope_head_dim % 2:
            raise ValueError("RoPE needs an even qk_rope_head_dim")
        self.hidden_size, self.num_heads = hidden_size, num_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta, self.rope_scaling = rope_theta, rope_scaling
        self.gated, self.use_flash = gated, use_flash
        self.kv_norm = RMSNorm(kv_lora_rank, norm_eps)
        qk = qk_nope_head_dim + qk_rope_head_dim
        self.scale = qk ** -0.5
        self.inv_freq = None
        if rope_scaling:
            rs = rope_scaling
            self.inv_freq = yarn_inv_freq(
                qk_rope_head_dim, rope_theta, rs["factor"],
                rs["original_max_position_embeddings"],
                rs.get("beta_fast", 32.0), rs.get("beta_slow", 1.0))
            m = yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0.0))
            self.scale *= m * m
            # cos and sin carry mscale / mscale_all_dim; only 1 is built
            if rs.get("mscale", 1.0) != rs.get("mscale_all_dim", 0.0):
                raise NotImplementedError(
                    "YaRN with mscale != mscale_all_dim scales cos and sin")

    def _init_params(self, rng):
        k = jax.random.split(rng, 6)
        H, nh, r = self.hidden_size, self.num_heads, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        p = {"wq": _glorot(k[0], (H, nh * (dn + dr))),
             "wkva": _glorot(k[1], (H, r + dr)),
             "kv_norm": self.kv_norm._init_params(k[2]),
             "wkvb": _glorot(k[3], (r, nh * (dn + dv))),
             "wo": _glorot(k[4], (nh * dv, H))}
        if self.gated:
            p["wg"] = _glorot(k[5], (H, nh * dv))
        return p

    def _rope(self, x):
        """RoPE over ``[B, T, heads, rope]`` (:func:`_rotate_half`), YaRN's
        frequencies where the layer has them."""
        inv = (_inv_freq(x.shape[-1], self.rope_theta)
               if self.inv_freq is None else self.inv_freq)
        return _rotate_half(x, inv)

    def qkv(self, params, x):
        """q, k ``[B, T, heads * (nope + rope)]`` (rotated) and v ``[B, T,
        heads * v]``: what attention reads, nothing transposed. The
        up-projection's weight is cut by use, not its output: k_nope and v
        are two products over the one normed latent."""
        B, T, _ = x.shape
        nh, r = self.num_heads, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        q = (x @ params["wq"]).reshape(B, T, nh, dn + dr)
        kva = x @ params["wkva"]
        c, _ = self.kv_norm.apply(params["kv_norm"], {}, kva[..., :r])
        wkvb = params["wkvb"].reshape(r, nh, dn + dv)
        k_nope = (c @ wkvb[..., :dn].reshape(r, nh * dn)).reshape(B, T, nh, dn)
        v = c @ wkvb[..., dn:].reshape(r, nh * dv)
        k_pe = self._rope(kva[..., r:][:, :, None, :])
        q = jnp.concatenate([q[..., :dn], self._rope(q[..., dn:])], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (B, T, nh, dr))], -1)
        return (q.reshape(B, T, nh * (dn + dr)),
                k.reshape(B, T, nh * (dn + dr)), v)

    def _apply(self, params, state, x, training, rng):
        if isinstance(x, Table):
            if len(x) >= 3 and x[3] is not None:
                raise ValueError("latent attention is causal self-attention: "
                                 "it takes no mask")
            x = x[1]
        q, k, v = self.qkv(params, x)
        nh = self.num_heads
        if self.use_flash and q.shape[-1] == v.shape[-1]:
            from ..parallel.flash import flash_attention_rows
            o = flash_attention_rows(q, k, v, nh, causal=True,
                                     scale=self.scale)
        else:
            B, T, _ = v.shape
            q, k, v = (a.reshape(B, T, nh, -1) for a in (q, k, v))
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * self.scale
            w = jax.nn.softmax(logits + causal_mask(T, logits.dtype), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, T, -1)
        if self.gated:
            o = o * jax.nn.sigmoid(x @ params["wg"])
        return o @ params["wo"]


class SparseAttention(Module):
    """Grouped-query causal self-attention with QK-norm and RoPE over the
    keys a lightning indexer selects (DeepSeek-V3.2's sparse attention,
    DSA):

        q_h = RoPE(RMSNorm_d(x Wq)_h),  k_g = RoPE(RMSNorm_d(x Wk)_g),
        v_g = (x Wv)_g                  (``num_heads`` q, ``num_kv_heads``)
        qI_j = RoPE_half((x' WiQ)_j),  kI = RoPE_half(LayerNorm(x' WiK)),
        w_j = (x' Ww)_j / sqrt(index_heads * index_head_dim)
        I[t, s] = sum_j w_j[t] relu(qI_j[t] . kI[s])          (s <= t)
        S_t = the min(t + 1, topk) keys of largest I[t] (ties: the earlier)
        o_h = softmax_{s in S_t}(q_h . k_g(h) / sqrt(d)) v_g(h);  y = o Wo

    ``x'`` is ``x`` with its gradient stopped, so the indexer learns from
    its own objective alone, ``mean_t KL(p_t || softmax_{S_t}(I_t))`` with
    ``p`` the attention's probabilities averaged over the heads (no
    gradient), which the layer returns in its state as the loss
    ``dsa/kl``; ``Optimizer`` adds it to the criterion's. ``RoPE_half`` turns the first half of the index head's
    dims. The state's counter ``dsa/tiles_visited`` is the share of causal
    ``(block_q, block_k)`` tiles that hold a selected pair.

    On the ``tpu`` platform the kernels of ``kernels/sparse_attention.py``
    run (``BIGDL_TPU_FLASH=interpret``: in the interpreter); elsewhere the
    same mathematics as einsums over ``[B, heads, T, T]``."""

    def __init__(self, hidden_size: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, index_heads: int, index_head_dim: int,
                 topk: int, rope_theta: float = 10000.0,
                 norm_eps: float = 1e-6, block_q: int = 512,
                 block_k: int = 512, name=None):
        super().__init__(name=name)
        if num_heads % num_kv_heads:
            raise ValueError(f"num_kv_heads ({num_kv_heads}) must divide "
                             f"num_heads ({num_heads})")
        self.hidden_size, self.num_heads = hidden_size, num_heads
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.index_heads, self.index_head_dim = index_heads, index_head_dim
        self.topk, self.rope_theta = topk, rope_theta
        self.block_q, self.block_k = block_q, block_k
        self.q_norm = RMSNorm(head_dim, norm_eps)
        self.k_norm = RMSNorm(head_dim, norm_eps)
        self.index_norm = LayerNormalization(index_head_dim, norm_eps)

    def _init_params(self, rng):
        k = jax.random.split(rng, 7)
        H, d, di = self.hidden_size, self.head_dim, self.index_head_dim
        nh, kvh, hi = self.num_heads, self.num_kv_heads, self.index_heads
        return {"wq": _glorot(k[0], (H, nh * d)),
                "wk": _glorot(k[1], (H, kvh * d)),
                "wv": _glorot(k[2], (H, kvh * d)),
                "wo": _glorot(k[3], (nh * d, H)),
                "q_norm": self.q_norm._init_params(None),
                "k_norm": self.k_norm._init_params(None),
                "index": {"wq": _glorot(k[4], (H, hi * di)),
                          "wk": _glorot(k[5], (H, di)),
                          "norm": self.index_norm._init_params(None),
                          "ww": _glorot(k[6], (H, hi))}}

    def _init_state(self):
        return {"losses": {"dsa/kl": jnp.zeros(())},
                "counters": {"dsa/tiles_visited": jnp.zeros(())}}

    def qkv(self, params, x):
        """q ``[B, T, heads * d]``, k and v ``[B, T, kv_heads * d]``: q and
        k normed a head and rotated."""
        B, T, _ = x.shape
        d, inv = self.head_dim, _inv_freq(self.head_dim, self.rope_theta)

        def heads(y, norm, p):
            y, _ = norm.apply(p, {}, y.reshape(B, T, -1, d))
            return _rotate_half(y, inv).reshape(B, T, -1)

        return (heads(x @ params["wq"], self.q_norm, params["q_norm"]),
                heads(x @ params["wk"], self.k_norm, params["k_norm"]),
                x @ params["wv"])

    @jax.named_scope("index")
    def index_inputs(self, params, x):
        """The indexer's queries ``[B, index_heads, T, dI]``, its key
        transposed ``[B, dI, T]`` and the heads' weights ``[B, index_heads,
        T]``, from ``x`` with its gradient stopped."""
        B, T, _ = x.shape
        p, hi, di = params["index"], self.index_heads, self.index_head_dim
        x = jax.lax.stop_gradient(x)
        inv = _inv_freq(di // 2, self.rope_theta)

        def rope(y):      # the first half of the dims turns
            return jnp.concatenate([_rotate_half(y[..., :di // 2], inv),
                                    y[..., di // 2:]], -1)

        qi = rope((x @ p["wq"]).reshape(B, T, hi, di))
        ki, _ = self.index_norm.apply(p["norm"], {}, x @ p["wk"])
        ki = rope(ki[:, :, None, :])[:, :, 0]
        w = (x @ p["ww"]) * (hi * di) ** -0.5
        return (qi.transpose(0, 2, 1, 3), ki.transpose(0, 2, 1),
                w.transpose(0, 2, 1))

    def _apply(self, params, state, x, training, rng):
        from ..parallel.flash import flash_mode
        if isinstance(x, Table):
            if len(x) >= 3 and x[3] is not None:
                raise ValueError("sparse attention is causal self-attention: "
                                 "it takes no mask")
            x = x[1]
        B, T, _ = x.shape
        q, k, v = self.qkv(params, x)
        qi, kit, w = self.index_inputs(params, x)
        mode = flash_mode()
        dims = (self.num_heads, self.num_kv_heads, self.head_dim ** -0.5)
        if mode == "einsum":
            o, kl_sum, tiles = self._einsum(q, k, v, qi, kit, w)
        else:
            from ..kernels import sparse_attention as dsa
            blocks = dict(block_q=self.block_q, block_k=self.block_k,
                          interpret=mode == "interpret")
            with jax.named_scope("index"):
                bits, ilse = dsa.dsa_select(
                    *map(jax.lax.stop_gradient, (qi, kit, w)), self.topk,
                    **blocks)
                bits = checkpoint_name(bits, dsa.DSA_KEPT_NAME)
            o, lse = dsa.dsa_attention(q, k, v, bits, *dims, **blocks)
            with jax.named_scope("index"):
                kl_sum = dsa.dsa_index_loss(
                    qi, kit, w, ilse, bits,
                    *map(jax.lax.stop_gradient, (q, k, lse)), *dims,
                    **blocks)
                tiles = dsa.tiles_visited(bits, self.block_q, self.block_k)
        return o @ params["wo"], {"losses": {"dsa/kl": kl_sum / (B * T)},
                                  "counters": {"dsa/tiles_visited": tiles}}

    def _einsum(self, q, k, v, qi, kit, w):
        """The kernels' mathematics over whole ``[T, T]`` arrays: o, the
        summed KL and the share of tiles visited."""
        B, T, _ = q.shape
        nh, kvh, d = self.num_heads, self.num_kv_heads, self.head_dim
        scores = jnp.einsum("bhtd,bds->bhts", qi, kit)
        index = jnp.sum(jnp.maximum(scores, 0.0) * w[..., None], axis=1)
        causal = jnp.tril(jnp.ones((T, T), bool))
        _, top = jax.lax.top_k(jax.lax.stop_gradient(
            jnp.where(causal, index, -jnp.inf)), min(self.topk, T))
        chosen = jnp.zeros((B, T, T), bool).at[
            jnp.arange(B)[:, None, None], jnp.arange(T)[None, :, None],
            top].set(True) & causal
        qg = q.reshape(B, T, kvh, nh // kvh, d)
        s = jnp.einsum("btkgd,bskd->bkgts", qg, k.reshape(B, T, kvh, d))
        s = jnp.where(chosen[:, None, None], s * d ** -0.5, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgts,bskd->btkgd", p, v.reshape(B, T, kvh, d))
        mean = jax.lax.stop_gradient(jnp.mean(p, axis=(1, 2)))
        log_q = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), -1)
        kl = jnp.where(chosen & (mean > 0),
                       mean * (jnp.log(jnp.where(mean > 0, mean, 1.0))
                               - jnp.where(chosen, log_q, 0.0)), 0.0)
        bq, bk = min(self.block_q, T), min(self.block_k, T)
        if T % bq or T % bk:
            bq = bk = T
        hit = jnp.any(chosen.reshape(B, T // bq, bq, T // bk, bk),
                      axis=(2, 4))
        visits = sum(min((i * bq + bq - 1) // bk + 1, T // bk)
                     for i in range(T // bq))
        tiles = jnp.sum(hit, dtype=jnp.float32) / (B * visits)
        return o.reshape(B, T, nh * d), jnp.sum(kl), tiles


class FeedForwardNetwork(Module):
    """Position-wise FFN (nn/FeedForwardNetwork.scala).

    ``activation``: 'relu' (reference default), 'gelu', 'swiglu'
    (gated: ``(silu(x@w1) * (x@w3)) @ w2`` — the modern LLM default; the
    gate keeps param count comparable by construction since callers
    usually shrink filter_size by 2/3) or 'relu2' (squared ReLU, ungated:
    ``relu(x@w1)^2 @ w2``, as ``nemotron_h`` configs name it)."""

    activation = "relu"   # class default: pre-r4 pickles lack the attr
    bias = True           # likewise

    def __init__(self, hidden_size: int, filter_size: int,
                 relu_dropout: float = 0.0, activation: str = "relu",
                 bias: bool = True, name=None):
        """``bias=False``: ``w1``, ``w2`` (and ``w3``) alone, as the gated
        FFNs of today's decoders are published."""
        super().__init__(name=name)
        self.hidden_size, self.filter_size = hidden_size, filter_size
        self.relu_dropout = relu_dropout
        if activation not in ("relu", "gelu", "swiglu", "relu2"):
            raise ValueError(f"activation must be relu/gelu/swiglu/relu2, "
                             f"got {activation!r}")
        self.activation = activation
        self.bias = bias

    def _init_params(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        p = {"w1": _glorot(k1, (self.hidden_size, self.filter_size)),
             "w2": _glorot(k2, (self.filter_size, self.hidden_size))}
        if self.bias:
            p["b1"] = jnp.zeros((self.filter_size,))
            p["b2"] = jnp.zeros((self.hidden_size,))
        if self.activation == "swiglu":
            p["w3"] = _glorot(k3, (self.hidden_size, self.filter_size))
        return p

    def _apply(self, params, state, x, training, rng):
        act = self.activation
        pre = x @ params["w1"]
        if self.bias:
            pre = pre + params["b1"]
        if act == "swiglu":
            h = jax.nn.silu(pre) * (x @ params["w3"])
        elif act == "gelu":
            h = jax.nn.gelu(pre)
        elif act == "relu2":
            h = jnp.square(jax.nn.relu(pre))
        else:
            h = jax.nn.relu(pre)
        if training and self.relu_dropout > 0 and rng is not None:
            keep = jax.random.bernoulli(rng, 1 - self.relu_dropout, h.shape)
            h = jnp.where(keep, h / (1 - self.relu_dropout), 0.0)
        out = h @ params["w2"]
        return out + params["b2"] if self.bias else out


def position_encoding(length, hidden_size, dtype=jnp.float32):
    """Sinusoidal PE (nn/TransformerOperation.scala getPositionEncode)."""
    pos = np.arange(length)[:, None].astype(np.float64)
    dim = np.arange(hidden_size // 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, 2 * dim / hidden_size)
    pe = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(pe, dtype)


def embed_ids(embed, ids, hidden_size, with_pe: bool = True):
    """Token embedding + sqrt(d) scale + sinusoidal positions (the LM
    input head shared by Transformer and MoETransformerLM). The PE is cast
    to the embedding dtype — an f32 PE added to bf16 embeddings would
    silently promote EVERY downstream activation (and the KV caches) to
    f32, doubling HBM traffic in what looks like a bf16 model.
    ``with_pe=False`` skips the additive PE (RoPE models position inside
    attention instead)."""
    h = jnp.take(embed, ids.astype(jnp.int32), axis=0)
    h = h * math.sqrt(hidden_size)
    if not with_pe:
        return h
    return h + position_encoding(ids.shape[1], hidden_size, h.dtype)


def _make_norm(kind: str, hidden_size: int, eps: float):
    """The block norm of this name: ``layer`` (LayerNorm) or ``rms``."""
    if kind == "rms":
        return RMSNorm(hidden_size, eps)
    if kind != "layer":
        raise ValueError(f"norm must be 'layer' or 'rms', got {kind!r}")
    return LayerNormalization(hidden_size, eps)


class TransformerBlock(Module):
    """Pre-norm transformer block: self-attn (+ optional cross-attn) + FFN.
    ``norm`` picks LayerNorm or RMSNorm; ``attn`` and ``ffn`` take the
    place of the attention and FFN built from the sizes (latent attention,
    a routed-expert layer: any module over ``[B, T, H]``). An attention or
    FFN that returns state (counters, losses: a sparse attention's, an
    expert layer's) makes the block return ``(h, state)``."""

    def __init__(self, hidden_size: int, num_heads: int, filter_size: int,
                 attn_dropout: float = 0.0, ffn_dropout: float = 0.0,
                 with_cross: bool = False, causal: bool = False,
                 use_flash: bool = True, num_kv_heads=None,
                 rope: bool = False, ffn_activation: str = "relu",
                 norm: str = "layer", norm_eps: float = 1e-6,
                 attn: Optional[Module] = None,
                 ffn: Optional[Module] = None, name=None):
        super().__init__(name=name)
        self.attn = attn or Attention(
            hidden_size, num_heads, attn_dropout, use_flash=use_flash,
            causal=causal, num_kv_heads=num_kv_heads, rope=rope)
        self.ffn = ffn or FeedForwardNetwork(
            hidden_size, filter_size, ffn_dropout, activation=ffn_activation)
        if getattr(self.ffn, "bias_update", 0):
            raise ValueError("this block carries no state from step to step:"
                             " a moving bias needs a layer_pattern stack")
        self.ln1 = _make_norm(norm, hidden_size, norm_eps)
        self.ln2 = _make_norm(norm, hidden_size, norm_eps)
        self.with_cross = with_cross
        if with_cross:
            self.cross = Attention(hidden_size, num_heads, attn_dropout)
            self.ln3 = _make_norm(norm, hidden_size, norm_eps)

    def _init_params(self, rng):
        k = jax.random.split(rng, 6)
        p = {"attn": self.attn._init_params(k[0]),
             "ffn": self.ffn._init_params(k[1]),
             "ln1": self.ln1._init_params(k[2]),
             "ln2": self.ln2._init_params(k[3])}
        if self.with_cross:
            p["cross"] = self.cross._init_params(k[4])
            p["ln3"] = self.ln3._init_params(k[5])
        return p

    def _attn_sublayer(self, params, h, mask, training, rng):
        """ln1 → self-attention → residual (shared with MoE blocks)."""
        return self._attn_with_state(params, h, mask, training, rng)[0]

    def _attn_with_state(self, params, h, mask, training, rng):
        """:meth:`_attn_sublayer` and the attention's state (a sparse
        attention's loss and counter; ``{}`` for the others)."""
        r1 = jax.random.fold_in(rng, 1) if rng is not None else None
        n, _ = self.ln1.apply(params["ln1"], {}, h, training, None,
                              scope="ln1")
        a, attn_state = self.attn.apply(params["attn"], {}, Table(n, n, mask),
                                        training, r1, scope="attn")
        return h + a, attn_state

    def _apply(self, params, state, x, training, rng):
        if isinstance(x, Table):
            h, mask = x[1], x[2]
            enc = x[3] if len(x) >= 3 else None
            enc_mask = x[4] if len(x) >= 4 else None
        else:
            h, mask, enc, enc_mask = x, None, None, None
        r1 = jax.random.fold_in(rng, 1) if rng is not None else None
        r2 = jax.random.fold_in(rng, 2) if rng is not None else None
        h, attn_state = self._attn_with_state(params, h, mask, training, rng)
        if self.with_cross and enc is not None:
            n, _ = self.ln3.apply(params["ln3"], {}, h, training, None,
                                  scope="ln3")
            c, _ = self.cross.apply(params["cross"], {},
                                    Table(n, enc, enc_mask), training, r1,
                                    scope="cross")
            h = h + c
        n, _ = self.ln2.apply(params["ln2"], {}, h, training, None,
                              scope="ln2")
        f, ffn_state = self.ffn.apply(params["ffn"], {}, n, training, r2,
                                      scope="ffn")
        from .moe import union_states
        state = union_states(attn_state, ffn_state)
        return (h + f, state) if state else h + f

    def sublayers(self):
        """The modules whose state (counters, losses) the block returns."""
        return (self.attn, self.ffn)

    def _ffn_sublayer(self, params, h):
        n, _ = self.ln2.apply(params["ln2"], {}, h, False, None)
        f, _ = self.ffn.apply(params["ffn"], {}, n, False, None)
        return h + f

    def prefill(self, params, h):
        """Causal forward over a full prompt that also RETURNS the
        projected K/V heads (for the decode cache). (h, (k, v)).
        Honors the block's ``use_flash`` choice exactly like ``_apply``
        (a model configured off the Pallas path must prefill through the
        same attention implementation it trained with)."""
        n, _ = self.ln1.apply(params["ln1"], {}, h, False, None)
        q, k, v = self.attn.qkv(params["attn"], n)
        if self.attn.rope:
            pos = jnp.arange(q.shape[2])
            q = rotary_embedding(q, pos)
            k = rotary_embedding(k, pos)
        # GQA: attention runs over broadcast heads, but the cache keeps
        # the compact kv-head form (that compactness IS the decode win)
        ke, ve = self.attn._expand_kv(k, v)
        if self.attn.use_flash:
            o = flash_attention(q, ke, ve, causal=True)
        else:
            o = dot_product_attention(q, ke, ve, causal_mask(q.shape[2]))
        h = h + self.attn._merge(o, params["attn"])
        return self._ffn_sublayer(params, h), (k, v)

    def cross_kv(self, params, enc):
        """Precompute the cross-attention K/V heads from the encoder
        output (constant across decode steps); the query projection is
        per-step, so only K/V are built here."""
        assert self.with_cross
        p = params["cross"]
        return (self.cross._split(enc @ p["wk"]),
                self.cross._split(enc @ p["wv"]))

    def decode_step(self, params, h_t, kv, pos, cross_kv=None,
                    cross_mask=None):
        """S cached autoregressive positions (S=1 is the classic decode
        step). h_t: (B, S, H) landing at positions pos..pos+S-1;
        kv: (k_cache, v_cache); pos: traced scalar. For translation-mode
        blocks pass the precomputed ``cross_kv`` and the additive
        source-padding ``cross_mask`` (cross-attention reads the full
        encoder output, so it is S-agnostic)."""
        n, _ = self.ln1.apply(params["ln1"], {}, h_t, False, None)
        a, k_cache, v_cache = self.attn.decode(params["attn"], n, kv[0],
                                               kv[1], pos)
        h_t = h_t + a
        if self.with_cross and cross_kv is not None:
            n, _ = self.ln3.apply(params["ln3"], {}, h_t, False, None)
            q = self.cross._split(n @ params["cross"]["wq"])
            o = dot_product_attention(q, cross_kv[0], cross_kv[1],
                                      cross_mask)
            h_t = h_t + self.cross._merge(o, params["cross"])
        return self._ffn_sublayer(params, h_t), (k_cache, v_cache)

    def decode_step_paged(self, params, h_t, k_pages, v_pages,
                          block_tables, positions):
        """The paged-cache analog of :meth:`decode_step` (LM blocks
        only): h_t (B, S, H) lands at per-row positions
        ``positions[b]..positions[b]+S-1`` through the block tables.
        Attention dispatch (dense gather vs the Pallas paged kernel)
        happens inside :meth:`Attention.decode_paged` — this wrapper is
        path-agnostic. Returns (h (B, S, H), k_pages, v_pages)."""
        n, _ = self.ln1.apply(params["ln1"], {}, h_t, False, None)
        a, k_pages, v_pages = self.attn.decode_paged(
            params["attn"], n, k_pages, v_pages, block_tables, positions)
        return self._ffn_sublayer(params, h_t + a), k_pages, v_pages


# a layer pattern's characters (``hybrid_override_pattern`` of nemotron_h
# configs) and the key, and scope, of the sublayer each one builds
LAYER_KINDS = {"M": "ssm", "*": "attn", "E": "ffn"}


class SublayerBlock(Module):
    """One layer of a hybrid stack: ONE sublayer behind its own pre-norm
    and residual, ``h + module(norm(h))``. ``key`` is the sublayer's key in
    the block's parameters and its scope (``ssm``, ``attn``, ``ffn``); the
    norm's is ``ln``. The block's state is the module's: a module that
    returns state (an expert layer's counters and moved bias) makes the
    block return ``(h, state)``."""

    def __init__(self, module: Module, key: str, hidden_size: int,
                 norm: str = "rms", norm_eps: float = 1e-6, name=None):
        super().__init__(name=name)
        self.module, self.key = module, key
        self.ln = _make_norm(norm, hidden_size, norm_eps)

    def _init_params(self, rng):
        k1, k2 = jax.random.split(rng)
        return {"ln": self.ln._init_params(k1),
                self.key: self.module._init_params(k2)}

    def _init_state(self):
        return self.module._init_state()

    def sublayers(self):
        return (self.module,)

    def _apply(self, params, state, x, training, rng):
        h = x[1] if isinstance(x, Table) else x
        n, _ = self.ln.apply(params["ln"], {}, h, training, None, scope="ln")
        y, st = self.module.apply(params[self.key], state, n, training, rng,
                                  scope=self.key)
        return (h + y, st) if st else h + y


def remat_block(run):
    """What ``remat=True`` means in this package: ``run`` (one block) under
    ``jax.checkpoint``. The backward pass recomputes the block from its
    input, except what a Pallas flash forward produced: the kernel's output
    ``o`` and its logsumexp are kept, so the forward kernel runs once a
    layer and not twice. Per layer that keeps the block input and ``o``
    (two ``[B, T, H]`` tensors, was one; ``o`` in the merged form the
    output projection reads) plus the logsumexp as the kernel wrote it
    and the backward kernels read it: ``[B, heads, T]`` f32, one number a
    row. Sparse attention's kernels keep the same two and, under
    ``DSA_KEPT_NAME``, the selection's bitmask and the indexer's gradient
    that its loss made in the forward pass, so no sparse kernel runs
    twice. On the einsum path no residual carries these names, nothing is
    kept, and the whole block is recomputed."""
    return jax.checkpoint(
        run, policy=jax.checkpoint_policies.save_only_these_names(
            FLASH_OUT_NAME, FLASH_LSE_NAME, DSA_KEPT_NAME))


class Transformer(Module):
    """Transformer (nn/Transformer.scala). ``mode='lm'`` (decoder-only causal
    LM over token ids) or ``mode='translation'`` (encoder-decoder; input
    Table(src_ids, tgt_ids)). Returns logits over vocab."""

    # class defaults: pickles from before these options lack the attrs
    embed_scale = True
    tied_head = True
    mtp = None

    def __init__(self, vocab_size: int, hidden_size: int = 256,
                 num_heads: int = 4, filter_size: int = 1024,
                 num_hidden_layers: int = 2, postprocess_dropout: float = 0.0,
                 attention_dropout: float = 0.0, relu_dropout: float = 0.0,
                 mode: str = "lm", max_len: int = 2048,
                 use_flash: bool = True, remat: bool = False,
                 num_kv_heads=None, pos_encoding: str = "sinusoidal",
                 ffn_activation: str = "relu", norm: str = "layer",
                 norm_eps: float = 1e-6, embed_scale: bool = True,
                 tied_head: bool = True, make_attention=None, make_ffn=None,
                 mtp: bool = False, layer_pattern: Optional[str] = None,
                 make_layer=None, name=None):
        """``norm``/``norm_eps``: the blocks' and the final norm
        (``layer`` or ``rms``). ``pos_encoding="none"`` adds no positions
        (an attention that rotates its own q/k, as ``make_attention``'s may)
        and ``embed_scale=False`` leaves the embeddings unscaled.
        ``tied_head=False`` gives the output projection its own ``head``
        ``[H, vocab]``. ``make_attention()`` / ``make_ffn(i)`` return the
        attention and the FFN module of LM block ``i`` in place of the
        dense ones (``make_ffn`` may return None for a dense layer: the FFN
        kind by layer). ``mtp``: one multi-token-prediction module
        (DeepSeek-V3): ``W_eh [norm(Emb(t_{i+1})) ; norm(h_i)]`` through one
        more block (built as the LAST layer's kind) and a final norm of its
        own, embedding and head shared; in training the output is then
        ``Table(logits, mtp_logits)`` with ``mtp_logits[:, i]`` predicting
        the SAME target as ``logits[:, i]`` from one position further back
        (``mtp_logits[:, 0]`` has no prediction: mask its target).
        ``layer_pattern`` (with ``make_layer(kind, i)``): a hybrid stack of
        single-sublayer layers (:class:`SublayerBlock`), one a character:
        ``M`` a state-space mixer, ``*`` attention, ``E`` an expert layer
        (:data:`LAYER_KINDS`); ``make_layer`` returns the
        module of layer ``i``, of that kind. The stack has
        ``len(layer_pattern)`` layers; ``num_hidden_layers``, ``make_attention``,
        ``make_ffn`` and ``mtp`` do not apply.
        ``use_flash``: LM-mode self-attention goes through the fused
        O(T)-memory flash path (Pallas on TPU) instead of materialising the
        (B,H,T,T) score matrix. ``remat``: each block runs under
        :func:`remat_block`, so the backward pass recomputes block internals
        (layer norms, projections, the FFN) instead of storing them. Kept
        per layer: the block input and, where the flash
        kernel ran, its output and logsumexp (the kernel is not run again)
        — activation memory drops from O(layers * intermediates) to two
        (B,T,H) tensors a layer, one on the einsum path."""
        super().__init__(name=name)
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.mode, self.max_len = mode, max_len
        self.dropout_p = postprocess_dropout
        self.remat = remat
        # LM mode: causal masking is a block property (flash-friendly);
        # translation mode keeps additive masks (padding masks cannot be
        # expressed as the flash kernel's static causal pattern)
        if pos_encoding not in ("sinusoidal", "rope", "none"):
            raise ValueError(f"pos_encoding must be 'sinusoidal', 'rope' or "
                             f"'none', got {pos_encoding!r}")
        if pos_encoding == "rope" and mode != "lm":
            raise ValueError("RoPE is LM-mode only (cross-attention has "
                             "no rotary form here)")
        if mode != "lm" and (mtp or make_attention or make_ffn
                             or not tied_head):
            raise ValueError("an untied head, block modules of the caller's "
                             "and the MTP module are LM-mode options")
        self.pos_encoding = pos_encoding
        self.embed_scale, self.tied_head = embed_scale, tied_head
        if layer_pattern is not None:
            if (mode != "lm" or make_layer is None or mtp or make_attention
                    or make_ffn):
                raise ValueError("a layer pattern is an LM-mode stack built "
                                 "by make_layer alone (no MTP module)")
            unknown = set(layer_pattern) - set(LAYER_KINDS)
            if unknown:
                raise ValueError(f"layer pattern characters {sorted(unknown)}"
                                 f" are none of {sorted(LAYER_KINDS)}")

        def block(i):
            return TransformerBlock(
                hidden_size, num_heads, filter_size, attention_dropout,
                relu_dropout, with_cross=(mode == "translation"),
                causal=(mode == "lm"), use_flash=use_flash,
                num_kv_heads=num_kv_heads, rope=(pos_encoding == "rope"),
                ffn_activation=ffn_activation, norm=norm, norm_eps=norm_eps,
                attn=make_attention() if make_attention else None,
                ffn=make_ffn(i) if make_ffn else None)

        if layer_pattern is not None:
            self.blocks = [SublayerBlock(make_layer(kind, i), LAYER_KINDS[kind],
                                         hidden_size, norm, norm_eps)
                           for i, kind in enumerate(layer_pattern)]
        else:
            self.blocks = [block(i) for i in range(num_hidden_layers)]
        if mode == "translation":
            self.enc_blocks = [TransformerBlock(hidden_size, num_heads,
                                                filter_size, attention_dropout,
                                                relu_dropout, norm=norm,
                                                norm_eps=norm_eps)
                               for _ in range(num_hidden_layers)]
        self.ln_f = _make_norm(norm, hidden_size, norm_eps)
        self.mtp = None
        if mtp:
            self.mtp = {"enorm": _make_norm(norm, hidden_size, norm_eps),
                        "hnorm": _make_norm(norm, hidden_size, norm_eps),
                        "block": block(num_hidden_layers - 1),
                        "ln_f": _make_norm(norm, hidden_size, norm_eps)}

    def _init_params(self, rng):
        k = jax.random.split(rng, 4 + len(self.blocks) * 2)
        p = {"embed": 0.02 * jax.random.normal(
                k[0], (self.vocab_size, self.hidden_size)),
             "ln_f": self.ln_f._init_params(k[1])}
        for i, blk in enumerate(self.blocks):
            p[f"block{i}"] = blk._init_params(k[2 + i])
        if self.mode == "translation":
            for i, blk in enumerate(self.enc_blocks):
                p[f"enc_block{i}"] = blk._init_params(
                    k[2 + len(self.blocks) + i])
        if not self.tied_head:
            p["head"] = _glorot(k[-2], (self.hidden_size, self.vocab_size))
        if self.mtp:
            km = jax.random.split(k[-1], 5)
            p["mtp"] = {n: m._init_params(km[j])
                        for j, (n, m) in enumerate(self.mtp.items())}
            p["mtp"]["eh_proj"] = _glorot(
                km[4], (2 * self.hidden_size, self.hidden_size))
        return p

    def _init_state(self):
        """The layers' counters and losses, where a block has any (zeros
        until the first forward), and under a block's key what it carries
        from one step to the next, so that the state a step returns has the
        structure of the state it was given."""
        from .moe import carried, merge_counters, union_states
        blocks = self.blocks + ([self.mtp["block"]] if self.mtp else [])
        state = merge_counters([union_states(*(m._init_state()
                                               for m in b.sublayers()))
                                for b in blocks])
        for i, b in enumerate(self.blocks):
            if carried(b._init_state()):
                state[f"block{i}"] = carried(b._init_state())
        return state

    @jax.named_scope("embed")
    def _embed(self, params, ids):
        if not self.embed_scale:
            if self.pos_encoding != "none":
                raise ValueError("unscaled embeddings take no positions")
            return jnp.take(params["embed"], ids.astype(jnp.int32), axis=0)
        return embed_ids(params["embed"], ids, self.hidden_size,
                         with_pe=getattr(self, "pos_encoding",
                                         "sinusoidal") == "sinusoidal")

    def _stack(self, blocks, prefix, params, h, mask, training, rng,
               enc=None, enc_mask=None, states=None, state=None, kept=None):
        """``h`` through the blocks; a block that returns ``(h, state)``
        (its FFN counts its routing) has the state appended to
        ``states``. A block is given what ``state`` holds under its key,
        and what it carries to the next step goes under that key of
        ``kept``."""
        from .moe import carried
        for i, blk in enumerate(blocks):
            key = f"{prefix}{i}"
            r = jax.random.fold_in(rng, i) if rng is not None else None
            # the block goes through `_apply`, so its scope (the parameter
            # key, as `Module.apply(scope=)` takes it) is put here: inside
            # the checkpoint, so the recomputed forward carries it too
            @jax.named_scope(key)
            def run(p, s, h, enc=enc, blk=blk, r=r):
                arg = Table(h, mask) if enc is None else Table(h, mask, enc,
                                                               enc_mask)
                return blk._apply(p, s, arg, training, r)
            if self.remat:
                run = remat_block(run)
            h = run(params[key], (state or {}).get(key, {}), h)
            if isinstance(h, tuple):
                h, st = h
                states.append(st)
                if carried(st):
                    kept[key] = carried(st)
        return h

    def hidden_states(self, params, x, training=False, rng=None,
                      states=None, trunk=None, state=None, kept=None):
        """Final-LayerNorm hidden states (B, T, H) — the LM trunk without
        the vocab projection, so callers can fuse projection+loss in
        chunks (see models.transformer_lm.lm_loss_chunked) instead of
        materialising (B, T, vocab) logits. ``states`` collects the
        blocks' states, ``trunk`` the last block's output before the final
        norm (what the MTP module reads); ``state`` is the model's, and
        ``kept`` takes what its blocks carry to the next step."""
        assert self.mode == "lm", "hidden_states is the LM-mode trunk"
        h = self._embed(params, x)
        h = self._stack(self.blocks, "block", params, h, None, training, rng,
                        states=[] if states is None else states, state=state,
                        kept={} if kept is None else kept)
        if trunk is not None:
            trunk.append(h)
        h, _ = self.ln_f.apply(params["ln_f"], {}, h, training, None,
                               scope="ln_f")
        return h

    @jax.named_scope("mtp")
    def _mtp_hidden(self, params, ids, h, training, rng, states):
        """The MTP module's normed hidden states, laid one position to the
        right: row ``i`` is made from ``h[i-1]`` and ``Emb(ids[i])`` and
        predicts what the main row ``i`` predicts, ``ids[i+1]``. Row 0
        (rolled round from the last position, whose next token is not in
        ``ids``) predicts nothing. Causal attention and per-token experts
        keep that last position from reaching any other."""
        p, m = params["mtp"], self.mtp
        nxt = jnp.roll(ids.astype(jnp.int32), -1, axis=1)
        e, _ = m["enorm"].apply(p["enorm"], {}, self._embed(params, nxt),
                                scope="enorm")
        n, _ = m["hnorm"].apply(p["hnorm"], {}, h, scope="hnorm")
        x = jnp.concatenate([e, n], axis=-1) @ p["eh_proj"]
        x = self._stack([m["block"]], "block", {"block0": p["block"]}, x,
                        None, training, rng, states=states)
        x, _ = m["ln_f"].apply(p["ln_f"], {}, x, scope="ln_f")
        return jnp.roll(x, 1, axis=1)

    def _apply(self, params, state, x, training, rng):
        if self.mode == "translation":
            src, tgt = x[1], x[2]
            src_mask = padding_mask((src != 0), src.shape[1])
            enc = self._embed(params, src)
            enc = self._stack(self.enc_blocks, "enc_block", params, enc,
                              src_mask, training, rng)
            h = self._embed(params, tgt)
            mask = causal_mask(tgt.shape[1])
            h = self._stack(self.blocks, "block", params, h, mask, training,
                            rng, enc, src_mask)
            h, _ = self.ln_f.apply(params["ln_f"], {}, h, training, None,
                                   scope="ln_f")
            return self._head(params, h)
        # LM mode: causal masking lives inside the blocks (flash path)
        states, trunk, kept = [], [], {}
        out = self._head(params, self.hidden_states(
            params, x, training, rng, states, trunk, state, kept))
        if self.mtp and training:
            r = jax.random.fold_in(rng, len(self.blocks)) \
                if rng is not None else None
            out = Table(out, self._head(params, self._mtp_hidden(
                params, x, trunk[0], training, r, states)))
        if states:
            from .moe import merge_counters
            return out, dict(merge_counters(states), **kept)
        return out

    @jax.named_scope("head")
    def _head(self, params, h):
        if not self.tied_head:
            return h @ params["head"]
        return h @ params["embed"].T  # tied output projection

    # ---- autoregressive inference (KV cache; TPU-first, the reference's
    # Transformer is training-only) --------------------------------------

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """Per-block (k, v) caches shaped (B, kvH, max_len, D) — kvH is
        the (possibly grouped) KV head count, so a GQA model's caches are
        nH/kvH smaller. Positions beyond the current one hold garbage —
        decode masks by position."""
        attn = self.blocks[0].attn
        d = attn._head_dim()
        kvh = attn._kvh()
        return [(jnp.zeros((batch, kvh, max_len, d), dtype),) * 2
                for _ in self.blocks]

    def prefill(self, params, ids, max_len: int):
        """Run the prompt once, returning (last-position logits, caches).
        ids: (B, Tp) with Tp <= max_len."""
        assert self.mode == "lm"
        B, Tp = ids.shape
        h = self._embed(params, ids)
        caches = self.init_cache(B, max_len, h.dtype)
        for i, blk in enumerate(self.blocks):
            h, (k, v) = blk.prefill(params[f"block{i}"], h)
            caches[i] = (jax.lax.dynamic_update_slice(
                caches[i][0], k.astype(caches[i][0].dtype), (0, 0, 0, 0)),
                jax.lax.dynamic_update_slice(
                caches[i][1], v.astype(caches[i][1].dtype), (0, 0, 0, 0)))
        h, _ = self.ln_f.apply(params["ln_f"], {}, h, False, None)
        return h[:, -1] @ params["embed"].T, caches

    def prefill_chunked(self, params, ids, max_len: int,
                        chunk: int = 512):
        """Prompt prefill in fixed-size pieces through the cached decode
        trunk: O(chunk·Tp) attention scratch instead of
        :meth:`prefill`'s O(Tp·Tp) — the long-context serving shape,
        where a 100k-token prompt must not materialise a full
        prompt-wide forward. Only the LAST position is projected to
        vocab (one (B, H)·(H, V) dot total — per-chunk logits would
        often cost more than the transformer itself). Returns
        (last-position logits, caches) like :meth:`prefill`; the chunk
        loop is unrolled at trace time (static shapes per piece; the
        tail piece may compile one extra shape)."""
        assert self.mode == "lm"
        ids = jnp.asarray(ids, jnp.int32)
        B, Tp = ids.shape
        assert Tp <= max_len
        caches = self.init_cache(B, max_len, params["embed"].dtype)
        h = None
        for s in range(0, Tp, chunk):
            h, caches = self._decode_trunk(
                params, ids[:, s:s + chunk], s, caches)
        return h[:, -1] @ params["embed"].T, caches

    def decode_one(self, params, tokens, pos, caches, cross=None,
                   cross_mask=None):
        """One cached step. tokens: (B,) int ids at position ``pos``
        (traced scalar). Returns (logits (B, V), caches). Translation-mode
        callers pass per-block precomputed ``cross`` K/V and the source
        padding ``cross_mask``; the LM path is the S=1 case of
        :meth:`decode_chunk` (one trunk implementation)."""
        if cross is None:
            logits, new_caches = self.decode_chunk(
                params, tokens.astype(jnp.int32)[:, None], pos, caches)
            return logits[:, 0], new_caches
        emb = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
        h = emb * math.sqrt(self.hidden_size)
        if getattr(self, "pos_encoding", "sinusoidal") != "rope":
            pe = position_encoding(self.max_len, self.hidden_size,
                                   emb.dtype)
            h = h + jax.lax.dynamic_slice_in_dim(pe, pos, 1, 0)
        h = h[:, None, :]
        new_caches = []
        for i, blk in enumerate(self.blocks):
            h, kv = blk.decode_step(
                params[f"block{i}"], h, caches[i], pos,
                cross[i], cross_mask)
            new_caches.append(kv)
        h, _ = self.ln_f.apply(params["ln_f"], {}, h, False, None)
        return h[:, 0] @ params["embed"].T, new_caches

    def _decode_trunk(self, params, tokens, pos, caches):
        """Shared cached-decode trunk: embed + PE + block stack + final
        LayerNorm for S tokens landing at positions pos..pos+S-1.
        Returns (hidden (B, S, H), caches) WITHOUT the vocab projection
        — chunked prefill projects only the last position, decode_chunk
        projects all S."""
        assert self.mode == "lm"
        emb = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
        h = emb * math.sqrt(self.hidden_size)
        S = tokens.shape[1]
        if getattr(self, "pos_encoding", "sinusoidal") != "rope":
            pe = position_encoding(self.max_len, self.hidden_size,
                                   emb.dtype)
            h = h + jax.lax.dynamic_slice_in_dim(pe, pos, S, 0)
        new_caches = []
        for i, blk in enumerate(self.blocks):
            h, kvn = blk.decode_step(params[f"block{i}"], h, caches[i],
                                     pos)
            new_caches.append(kvn)
        h, _ = self.ln_f.apply(params["ln_f"], {}, h, False, None)
        return h, new_caches

    def decode_chunk(self, params, tokens, pos, caches):
        """S cached steps in one forward (LM mode): tokens (B, S) land
        at positions pos..pos+S-1; returns (logits (B, S, V), caches).
        ``logits[:, i]`` is the next-token distribution after consuming
        ``tokens[:, :i+1]`` — the speculative-decode verification shape
        (nn/speculative.py)."""
        h, new_caches = self._decode_trunk(params, tokens, pos, caches)
        return h @ params["embed"].T, new_caches

    def decode_paged(self, params, tokens, positions, pages, block_tables):
        """S cached steps over a PAGED KV store with PER-ROW positions —
        the continuous-batching decode step (serving/decode_scheduler.py).
        tokens: (B, S) landing at positions
        ``positions[b]..positions[b]+S-1``; positions: (B,) int32;
        pages: per-block list of (k_pages, v_pages) each
        (num_blocks, kvH, block_size, D); block_tables: (B, max_blocks)
        int32 (see ``Attention.decode_paged``). Returns
        (logits (B, S, V), pages). Row arithmetic is bitwise-identical
        to :meth:`decode_chunk` over a dense cache at the same gemm
        M-class (see serving/kv_cache.py docs for the M=1 caveat)."""
        assert self.mode == "lm"
        emb = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
        h = emb * math.sqrt(self.hidden_size)
        S = tokens.shape[1]
        if getattr(self, "pos_encoding", "sinusoidal") != "rope":
            pe = position_encoding(self.max_len, self.hidden_size,
                                   emb.dtype)
            pos_s = positions[:, None] + jnp.arange(S)[None, :]
            h = h + jnp.take(pe, pos_s, axis=0)   # per-row PE rows
        new_pages = []
        for i, blk in enumerate(self.blocks):
            h, kp, vp = blk.decode_step_paged(
                params[f"block{i}"], h, pages[i][0], pages[i][1],
                block_tables, positions)
            new_pages.append((kp, vp))
        h, _ = self.ln_f.apply(params["ln_f"], {}, h, False, None)
        return h @ params["embed"].T, new_pages

    def generate(self, params, prompt_ids, max_new_tokens: int,
                 temperature: float = 0.0, rng=None, top_k: int = 0,
                 top_p: float = 0.0, eos_id=None):
        """Autoregressive generation with a KV cache: prefill the prompt,
        then ``lax.scan`` one fused decode step per token (greedy when
        ``temperature`` == 0, else temperature / top-k / top-p (nucleus)
        sampling — ``top_p`` keeps the smallest prefix of the sorted
        distribution whose mass reaches p). Returns
        (B, Tp + max_new_tokens) ids; with ``eos_id``, positions after a
        row's first EOS are emitted as 0 (fixed shape — the scan still
        runs max_new_tokens steps). Jit-compatible end to end.

        Token-id convention: logits column ``j`` is taken as token ``j``
        (the tied embedding's own indexing) — train with
        ``models.lm_loss_chunked`` (0-based head). A model trained with
        the torch-parity 1-BASED criteria (``CrossEntropyCriterion`` et
        al. treat target ``t`` as column ``t-1``) would decode off by one
        here."""
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        B, Tp = prompt_ids.shape
        if max_new_tokens <= 0:
            return prompt_ids
        total = Tp + max_new_tokens
        assert total <= self.max_len, (total, self.max_len)
        logits, caches = self.prefill(params, prompt_ids, total)
        if rng is None:
            rng = jax.random.PRNGKey(0)

        def pick(logits, key):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            l = logits / temperature
            if top_k > 0:
                k_eff = min(top_k, l.shape[-1])
                # lax.top_k: O(V) threshold, not a full per-step sort
                kth = jax.lax.top_k(l, k_eff)[0][:, -1:]
                l = jnp.where(l < kth, -1e30, l)
            if top_p > 0.0:
                # nucleus: drop tokens outside the smallest prefix of the
                # sorted distribution with cumulative mass >= p (the
                # highest-probability token always survives)
                srt = jnp.sort(l, axis=-1)[:, ::-1]
                probs = jax.nn.softmax(srt, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                keep_sorted = cum - probs < top_p
                n_keep = jnp.maximum(keep_sorted.sum(-1), 1)
                cutoff = jnp.take_along_axis(srt, n_keep[:, None] - 1, -1)
                l = jnp.where(l < cutoff, -1e30, l)
            return jax.random.categorical(key, l, axis=-1).astype(jnp.int32)

        key0, rng = jax.random.split(rng)
        first = pick(logits, key0)
        done0 = (first == eos_id) if eos_id is not None \
            else jnp.zeros((B,), bool)

        def body(carry, step_key):
            caches, tok, pos, done = carry
            logits, caches = self.decode_one(params, tok, pos, caches)
            nxt = pick(logits, step_key)
            if eos_id is not None:
                nxt = jnp.where(done, 0, nxt)
                new_done = jnp.logical_or(done, nxt == eos_id)
            else:
                new_done = done
            return (caches, nxt, pos + 1, new_done), tok

        keys = jax.random.split(rng, max(max_new_tokens - 1, 1))
        (_, last, _, _), toks = jax.lax.scan(
            body, (caches, first, jnp.int32(Tp), done0),
            keys[:max_new_tokens - 1])
        out = jnp.concatenate(
            [prompt_ids, jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
        return out

    def generate_beam(self, params, prompt_ids, max_new_tokens: int,
                      beam_size: int = 4, eos_id=None,
                      length_penalty: float = 0.0):
        """Beam-search generation for mode='lm' (beyond the reference —
        its Transformer is training-only). Prefill runs ONCE on the
        un-repeated batch; caches are then expanded to the (B*beam)
        layout and beams ride the same cached decode step as greedy.
        Score = sum log-prob / (len ** length_penalty); finished beams
        (emitted ``eos_id``) freeze with their score. Returns
        (B, Tp + max_new_tokens) ids of the best beam (positions after
        eos zeroed). ``beam_size=1`` reproduces greedy :meth:`generate`.
        """
        assert self.mode == "lm"
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        B, Tp = prompt_ids.shape
        K, V = beam_size, self.vocab_size
        if max_new_tokens <= 0:
            return prompt_ids
        total = Tp + max_new_tokens
        assert total <= self.max_len, (total, self.max_len)

        logits, caches = self.prefill(params, prompt_ids, total)
        caches = jax.tree_util.tree_map(
            lambda x: jnp.repeat(x, K, axis=0), caches)
        logp0 = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        scores0, tok0 = jax.lax.top_k(logp0, K)              # (B, K)
        tok = tok0.reshape(-1).astype(jnp.int32)
        done = (tok == eos_id) if eos_id is not None \
            else jnp.zeros((B * K,), bool)

        scores, toks, parents = self._beam_scan(
            lambda t, p, c: self.decode_one(params, t, p, c),
            caches, tok, scores0.reshape(-1), done, jnp.int32(Tp),
            max_new_tokens - 1, B, K, eos_id)
        paths, roots = _beam_backtrack(toks, parents, B, K)
        root_tok = jnp.take_along_axis(tok0, roots, axis=1)  # (B, K)
        paths = jnp.concatenate([root_tok[None], paths], axis=0)
        out = _beam_select(scores, paths, B, K, length_penalty)
        return jnp.concatenate([prompt_ids, out], axis=1)

    def _beam_scan(self, step_fn, caches, tok, scores, done, pos0,
                   steps, B, K, eos_id):
        """Run ``steps`` beam expansions in the flattened (B*K) layout.
        ``step_fn(tok, pos, caches) -> (logits, caches)`` is the cached
        decode step (LM, or a translation closure carrying cross K/V).
        Candidates are (V+1)-wide: the extra column is a frozen beam's
        single "stay" continuation (score unchanged) — vocab column 0
        remains selectable by live beams, preserving exact greedy parity
        at beam_size=1 and eos_id=0 detection. Returns
        (scores (B*K,), toks, parents) with toks/parents shaped
        (steps, B, K) for :func:`_beam_backtrack`."""
        V = self.vocab_size
        neg = jnp.float32(-1e30)

        def gather_beams(tree, idx):
            """idx: (B, K) beam indices into the previous (B*K) layout."""
            flat = (jnp.arange(B)[:, None] * K + idx).reshape(-1)
            return jax.tree_util.tree_map(lambda x: x[flat], tree)

        def body(carry, _):
            caches, tok, pos, scores, done = carry
            logits, new_caches = step_fn(tok, pos, caches)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            live = jnp.where(done[:, None], neg, logp) + scores[:, None]
            stay = jnp.where(done, scores, neg)[:, None]
            cand = jnp.concatenate([live, stay], axis=1)  # (B*K, V+1)
            cand = cand.reshape(B, K * (V + 1))
            top, flat_idx = jax.lax.top_k(cand, K)   # (B, K)
            beam_idx = flat_idx // (V + 1)
            col = (flat_idx % (V + 1)).astype(jnp.int32)
            caches = gather_beams(new_caches, beam_idx)
            done = gather_beams(done, beam_idx)
            col_flat = col.reshape(-1)
            emitted = jnp.where(col_flat == V, 0, col_flat)  # stay → pad
            if eos_id is not None:
                done = jnp.logical_or(done, jnp.logical_and(
                    col_flat != V, emitted == eos_id))
            return (caches, emitted, pos + 1, top.reshape(-1), done), \
                (emitted, beam_idx)

        (_, _, _, scores, _), (toks, parents) = jax.lax.scan(
            body, (caches, tok, pos0, scores, done), None, length=steps)
        return (scores, toks.reshape(steps, B, K),
                parents.reshape(steps, B, K))

    def _encode_src(self, params, src_ids):
        """Shared source-side setup for translate/translate_beam:
        padding mask + encoder stack."""
        src_mask = padding_mask((src_ids != 0), src_ids.shape[1])
        enc = self._embed(params, src_ids)
        enc = self._stack(self.enc_blocks, "enc_block", params, enc,
                          src_mask, False, None)
        return enc, src_mask

    def translate(self, params, src_ids, max_new_tokens: int,
                  bos_id: int = 1, eos_id=None):
        """Greedy encoder-decoder decoding (mode='translation'): encode
        the source once, precompute each block's cross-attention K/V, then
        one cached decode step per target token starting from ``bos_id``.
        Tokens after the first ``eos_id`` (when given) are replaced by 0.
        Returns (B, max_new_tokens) target ids (without the BOS)."""
        assert self.mode == "translation"
        src_ids = jnp.asarray(src_ids, jnp.int32)
        B = src_ids.shape[0]
        assert max_new_tokens + 1 <= self.max_len
        enc, src_mask = self._encode_src(params, src_ids)
        cross = [blk.cross_kv(params[f"block{i}"], enc)
                 for i, blk in enumerate(self.blocks)]
        caches = self.init_cache(B, max_new_tokens + 1, enc.dtype)

        def body(carry, _):
            caches, tok, pos, done = carry
            logits, caches = self.decode_one(params, tok, pos, caches,
                                             cross, src_mask)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            emit = jnp.where(done, 0, nxt)
            if eos_id is not None:
                done = jnp.logical_or(done, nxt == eos_id)
            return (caches, nxt, pos + 1, done), emit

        bos = jnp.full((B,), bos_id, jnp.int32)
        done0 = jnp.zeros((B,), bool)
        (_, _, _, _), toks = jax.lax.scan(
            body, (caches, bos, jnp.int32(0), done0), None,
            length=max_new_tokens)
        return jnp.moveaxis(toks, 0, 1)

    def translate_beam(self, params, src_ids, max_new_tokens: int,
                       beam_size: int = 4, bos_id: int = 1, eos_id=None,
                       length_penalty: float = 0.0):
        """Beam-search decoding for mode='translation' (beyond the
        reference, whose Transformer has no inference path at all).

        Standard fixed-width beam search under ``lax.scan``: beams ride a
        flattened (B*beam) batch through the SAME cached decode step as
        greedy; finished beams (emitted ``eos_id``) are frozen with their
        score. Score = sum log-prob / (len ** length_penalty). Returns
        (B, max_new_tokens) ids of the best beam (BOS excluded, positions
        after eos zeroed). ``beam_size=1`` reproduces :meth:`translate`.
        """
        assert self.mode == "translation"
        src_ids = jnp.asarray(src_ids, jnp.int32)
        B, Ts = src_ids.shape
        K = beam_size
        V = self.vocab_size
        assert max_new_tokens + 1 <= self.max_len

        enc, src_mask = self._encode_src(params, src_ids)
        # project cross K/V ONCE on the un-repeated encoder output, then
        # expand to the (B*K) beam layout
        rep = lambda x: jnp.repeat(x, K, axis=0)
        mask_k = rep(src_mask)
        cross = [tuple(rep(t) for t in
                       blk.cross_kv(params[f"block{i}"], enc))
                 for i, blk in enumerate(self.blocks)]
        caches = self.init_cache(B * K, max_new_tokens + 1, enc.dtype)

        # beam 0 starts live, the rest dead so the first expansion draws
        # K distinct continuations of BOS rather than K copies
        scores0 = jnp.tile(jnp.concatenate(
            [jnp.zeros((1,)), jnp.full((K - 1,), jnp.float32(-1e30))]),
            (B,))
        bos = jnp.full((B * K,), bos_id, jnp.int32)
        done0 = jnp.zeros((B * K,), bool)

        scores, toks, parents = self._beam_scan(
            lambda t, p, c: self.decode_one(params, t, p, c, cross,
                                            mask_k),
            caches, bos, scores0, done0, jnp.int32(0), max_new_tokens,
            B, K, eos_id)
        paths, _ = _beam_backtrack(toks, parents, B, K)
        return _beam_select(scores, paths, B, K, length_penalty)


def _beam_backtrack(toks, parents, B, K):
    """Follow parent pointers from the final beam slots back to step 0.
    Beam slots are physically re-gathered every expansion, so per-slot
    columns of ``toks`` mix hypotheses — both the length penalty and the
    output must walk the parent chain. toks/parents: (steps, B, K).
    Returns (paths (steps, B, K), roots (B, K)) — ``roots[b, k]`` is
    final beam k's slot index at entry to step 0 (LM beam search uses it
    to recover which pre-scan prefill expansion the beam descends
    from)."""
    def walk(beams, inputs):
        tk, pr = inputs
        tok_t = jnp.take_along_axis(tk, beams, axis=1)   # (B, K)
        beams = jnp.take_along_axis(pr, beams, axis=1)
        return beams, tok_t

    init = jnp.tile(jnp.arange(K)[None, :], (B, 1))
    roots, rev = jax.lax.scan(walk, init, (toks[::-1], parents[::-1]))
    return rev[::-1], roots


def _beam_select(scores, paths, B, K, length_penalty):
    """Pick each row's best beam under the length penalty and return its
    token path as (B, T). One implementation of the scoring convention
    (length = count of non-pad tokens, clamped to 1;
    score = sum log-prob / len**penalty) for both LM and translation
    beam search."""
    lens = jnp.sum(paths != 0, axis=0).astype(jnp.float32)  # (B, K)
    norm = jnp.maximum(lens, 1.0) ** length_penalty
    final = scores.reshape(B, K) / norm
    best = jnp.argmax(final, axis=1)                        # (B,)
    out = jnp.take_along_axis(
        paths, best[None, :, None], axis=2)[:, :, 0]        # (T, B)
    return jnp.moveaxis(out, 0, 1)
