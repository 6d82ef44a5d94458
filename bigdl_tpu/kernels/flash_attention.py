"""Fused flash attention as a hand-written Pallas TPU kernel.

Replaces the reference's O(T^2)-memory attention (the reference materialises
the full score matrix — ``nn/Attention.scala`` builds it with two MM layers)
with the online-softmax tiling of FlashAttention: Q/K/V stream through VMEM
in (block x d) tiles, scores never leave VMEM, and the output is rescaled
incrementally — O(T) HBM traffic per head.

Forward and backward are both Pallas kernels wired through ``jax.custom_vjp``
(flash-attention-2 split: the backward recomputes probabilities per tile from
the saved logsumexp; one kernel accumulates dK/dV over query tiles, one
accumulates dQ over key tiles).

Design notes (see /opt/skills/guides/pallas_guide.md):
  * the streaming axis is the innermost grid dimension, so the VMEM scratch
    accumulators persist across its sequential iterations;
  * all matmuls request ``preferred_element_type=float32`` (MXU accumulates
    f32 even for bf16 inputs);
  * sequence lengths are padded to the block size; real lengths are baked in
    statically and masked with ``broadcasted_iota`` (no dynamic shapes);
  * ``interpret=True`` runs the identical kernel on CPU for the test suite.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# ``checkpoint_name``s of the two residuals the forward kernel itself
# computes. A ``jax.checkpoint`` whose policy saves these names
# (nn.attention.remat_block) keeps them, so its backward pass does not run
# the forward kernel a second time only to get them back.
FLASH_OUT_NAME = "flash_attention_out"
FLASH_LSE_NAME = "flash_attention_lse"


def _pick_block(t: int, target: int) -> int:
    """Block size: multiple of 128, capped at the (padded) sequence length."""
    t_pad = (t + 127) // 128 * 128
    return min(target, t_pad)


def _pad_t(x, t_pad):
    t = x.shape[2]
    if t == t_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))


def _mm(a, b, ta=False, tb=False):
    """f32-accumulating matmul on the MXU; optionally transpose operands."""
    ca = 0 if ta else 1
    cb = 1 if tb else 0
    out = jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, block_q, block_k, causal, kv_len, nk,
                q_offset=0):
    i = pl.program_id(2)   # query-block index
    j = pl.program_id(3)   # key-block index (sequential, innermost)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # q_offset: q row r sits at GLOBAL position q_offset + r (chunked
    # prefill over a KV cache — rectangular causal); 0 for self-attention
    q_off = i * block_q + q_offset
    k_off = j * block_k
    # key blocks strictly above the causal diagonal contribute nothing
    needed = (k_off <= q_off + block_q - 1) if causal else (j >= 0)

    @pl.when(needed)
    def _compute():
        # MXU contractions stay in the INPUT dtype (bf16 on the model
        # path) with f32 accumulation from preferred_element_type — f32
        # operands run the MXU at a fraction of bf16 throughput (the
        # round-3 fused-matmul A/B measured the all-f32 form 2.2x slower).
        # f32 is reserved for the softmax statistics math.
        s = _mm(q_ref[0, 0], k_ref[0, 0], tb=True) * scale   # (bq, bk) f32

        col = k_off + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = q_off + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
            mask = jnp.logical_and(mask, col <= row)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                      # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)                     # (bq, bk)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _mm(p.astype(v_ref.dtype),
                                              v_ref[0, 0])
        m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)       # fully-masked rows → 0
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct, carrying varying-mesh-axes when the caller runs
    inside a strict-VMA shard_map (parallel/ring_flash.py)."""
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               vma=None, q_offset=0, kv_len=None):
    b, h, t_q, d = q.shape
    t_kv = k.shape[2]
    # kv_len < t_kv: attend only the first kv_len positions (the VALID
    # prefix of a decode cache — chunked prefill). The GRID is bounded
    # to ceil(kv_len / bk) key blocks, so the garbage tail of the cache
    # is never DMA'd and the caller needs no slice copy of K/V.
    kv_len = t_kv if kv_len is None else int(kv_len)
    bq = _pick_block(t_q, block_q)
    bk = _pick_block(kv_len, block_k)
    tq_pad = (t_q + bq - 1) // bq * bq
    nk = (kv_len + bk - 1) // bk
    tkv_need = nk * bk
    qp = _pad_t(q, tq_pad)
    kp = _pad_t(k, tkv_need) if tkv_need > t_kv else k
    vp = _pad_t(v, tkv_need) if tkv_need > t_kv else v
    nq = tq_pad // bq

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=bq, block_k=bk, causal=causal,
        kv_len=kv_len, nk=nk, q_offset=q_offset)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, 128),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            _sds((b, h, tq_pad, d), q.dtype, vma),
            _sds((b, h, tq_pad, 128), jnp.float32, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qp, kp, vp)
    return o[:, :, :t_q], lse[:, :, :t_q, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc,
                   *, scale, block_q, block_k, causal, kv_len, nq):
    j = pl.program_id(2)   # key-block (parallel)
    i = pl.program_id(3)   # query-block (sequential, innermost)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_off = i * block_q
    k_off = j * block_k
    needed = (k_off <= q_off + block_q - 1) if causal else (i >= 0)

    @pl.when(needed)
    def _compute():
        # bf16-operand MXU contractions with f32 accumulation (see the
        # forward kernel's dtype note); p/ds are computed in f32 and cast
        # back to the wire dtype only as matmul operands
        lse = lse_ref[0, 0][:, :1]                 # (bq, 1)
        delta = delta_ref[0, 0][:, :1]             # (bq, 1)
        dt = q_ref.dtype

        s = _mm(q_ref[0, 0], k_ref[0, 0], tb=True) * scale   # (bq, bk)
        col = k_off + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = q_off + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
            mask = jnp.logical_and(mask, col <= row)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # (bq, bk) f32

        dv_acc[:] += _mm(p.astype(dt), do_ref[0, 0], ta=True)  # (bk, d)
        dp = _mm(do_ref[0, 0], v_ref[0, 0], tb=True)           # (bq, bk)
        ds = p * (dp - delta) * scale
        dk_acc[:] += _mm(ds.astype(dt), q_ref[0, 0], ta=True)  # (bk, d)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_q_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  dq_ref, dq_acc,
                  *, scale, block_q, block_k, causal, kv_len, nk):
    i = pl.program_id(2)   # query-block (parallel)
    j = pl.program_id(3)   # key-block (sequential, innermost)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_off = i * block_q
    k_off = j * block_k
    needed = (k_off <= q_off + block_q - 1) if causal else (j >= 0)

    @pl.when(needed)
    def _compute():
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]

        s = _mm(q_ref[0, 0], k_ref[0, 0], tb=True) * scale
        col = k_off + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = q_off + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
            mask = jnp.logical_and(mask, col <= row)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = _mm(do_ref[0, 0], v_ref[0, 0], tb=True)
        ds = p * (dp - delta) * scale
        dq_acc[:] += _mm(ds.astype(k_ref.dtype), k_ref[0, 0])  # (bq, d)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g,
               delta=None, out_dtype=None, vma=None):
    """``delta``/``out_dtype`` are for block-composed callers
    (parallel/ring_flash.py): a ring backward precomputes the global
    rowsum(dO*O) once and needs f32 gradient outputs so per-hop
    accumulation does not round at the input dtype."""
    q, k, v, o, lse = res
    b, h, t_q, d = q.shape
    t_kv = k.shape[2]
    bq = _pick_block(t_q, block_q)
    bk = _pick_block(t_kv, block_k)
    tq_pad = (t_q + bq - 1) // bq * bq
    tkv_pad = (t_kv + bk - 1) // bk * bk
    nq, nk = tq_pad // bq, tkv_pad // bk

    if delta is None:
        # delta_i = rowsum(dO_i * O_i) — cheap elementwise+reduce; XLA
        # fuses it
        delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)

    qp, kp, vp = _pad_t(q, tq_pad), _pad_t(k, tkv_pad), _pad_t(v, tkv_pad)
    dop = _pad_t(g, tq_pad)
    # lse/delta padded along T and broadcast into 128 lanes so each (bq, 128)
    # tile is layout-friendly
    pad_q = ((0, 0), (0, 0), (0, tq_pad - t_q))
    lsep = jnp.pad(lse, pad_q)[..., None] * jnp.ones((1, 1, 1, 128), jnp.float32)
    deltap = jnp.pad(delta, pad_q)[..., None] * jnp.ones((1, 1, 1, 128),
                                                         jnp.float32)

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, x, y: (b_, h_, y, 0))
    k_spec = pl.BlockSpec((1, 1, bk, d), lambda b_, h_, x, y: (b_, h_, x, 0))
    r_spec = pl.BlockSpec((1, 1, bq, 128),
                          lambda b_, h_, x, y: (b_, h_, y, 0))
    kv_kernel = functools.partial(
        _bwd_kv_kernel, scale=scale, block_q=bq, block_k=bk, causal=causal,
        kv_len=t_kv, nq=nq)
    dk, dv = pl.pallas_call(
        kv_kernel,
        grid=(b, h, nk, nq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[_sds((b, h, tkv_pad, d), out_dtype or k.dtype, vma),
                   _sds((b, h, tkv_pad, d), out_dtype or v.dtype, vma)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lsep, deltap)

    q_spec2 = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, x, y: (b_, h_, x, 0))
    k_spec2 = pl.BlockSpec((1, 1, bk, d), lambda b_, h_, x, y: (b_, h_, y, 0))
    r_spec2 = pl.BlockSpec((1, 1, bq, 128),
                           lambda b_, h_, x, y: (b_, h_, x, 0))
    q_kernel = functools.partial(
        _bwd_q_kernel, scale=scale, block_q=bq, block_k=bk, causal=causal,
        kv_len=t_kv, nk=nk)
    dq = pl.pallas_call(
        q_kernel,
        grid=(b, h, nq, nk),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=q_spec2,
        out_shape=_sds((b, h, tq_pad, d), out_dtype or q.dtype, vma),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lsep, deltap)

    return dq[:, :, :t_q], dk[:, :, :t_kv], dv[:, :, :t_kv]


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return o


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    # `lse` is lane 0 of the kernel's 128-lane output. Tied to `o`, the
    # slice runs before anything reads `o`; left free, XLA may put it off
    # until the backward pass, and what is held meanwhile (by a remat
    # policy that saves `lse`, or as a plain residual) is the 128-lane
    # array. The barrier moves no data.
    o, lse = jax.lax.optimization_barrier((o, lse))
    # The two residuals the kernel itself computed are named for remat
    # policies (q, k, v are not: a checkpointed caller recomputes them from
    # its own input). `o` is named in the merged (B, T, H*D) form, the one
    # the caller's output projection reads: dense in HBM, where a
    # (..., T, D) array with D < 128 is padded to 128 lanes for as long as
    # it is kept. XLA cancels the way back against the caller's own merge.
    b, h, t, d = o.shape
    o = checkpoint_name(o.transpose(0, 2, 1, 3).reshape(b, t, h * d),
                        FLASH_OUT_NAME)
    o = o.reshape(b, t, h, d).transpose(0, 2, 1, 3)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, res, g):
    return _flash_bwd(causal, scale, block_q, block_k, interpret, res, g)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention_fused(q, k, v, causal: bool = False,
                          scale: float | None = None,
                          block_q: int = 512, block_k: int = 512,
                          interpret: bool = False):
    """Fused flash attention. q, k, v: (B, H, T, D); returns (B, H, T, D).

    Matches ``nn.attention.dot_product_attention(q, k, v, causal_mask)``
    numerically (softmax(QK^T / sqrt(D)) V) with O(T) memory. Differentiable
    via the Pallas backward kernels. ``interpret=True`` runs the kernel in
    the Pallas interpreter (CPU tests).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash(q, k, v, bool(causal), float(scale),
                  int(block_q), int(block_k), bool(interpret))


def flash_chunk_attention(q, k, v, q_offset: int, kv_len: int = None,
                          scale: float | None = None,
                          block_q: int = 512, block_k: int = 512,
                          interpret: bool = False):
    """Rectangular-causal flash attention for CHUNKED cached decode:
    q (B, H, S, D) holds positions q_offset..q_offset+S-1; k/v are a KV
    cache whose first ``kv_len`` positions are valid (default: all of
    it) and already contain this chunk's keys. Row r attends columns
    <= q_offset + r. Pass the FULL cache with ``kv_len`` — the grid is
    bounded to the valid key blocks, so the garbage tail is never
    streamed and no slice copy is made. O(S) memory scratch per block
    instead of the einsum path's (B, H, S, kv_len) logits — what makes
    ``Transformer.prefill_chunked`` practical at 100k-token prompts.
    Forward-only (inference path; no vjp)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    o, _ = _flash_fwd(q, k, v, True, float(scale), int(block_q),
                      int(block_k), bool(interpret),
                      q_offset=int(q_offset), kv_len=kv_len)
    return o
