"""Fused flash attention as a hand-written Pallas TPU kernel.

Replaces the reference's O(T^2)-memory attention (the reference materialises
the full score matrix — ``nn/Attention.scala`` builds it with two MM layers)
with the online-softmax tiling of FlashAttention: Q/K/V stream through VMEM
in (block x lanes) tiles, scores never leave VMEM, and the output is rescaled
incrementally — O(T) HBM traffic per head.

Forward and backward are both Pallas kernels wired through ``jax.custom_vjp``
(flash-attention-2 split: the backward recomputes probabilities per tile from
the saved logsumexp; one kernel accumulates dK/dV over query tiles, one
accumulates dQ over key tiles).

Layout. The kernels index their operands as ROWS, ``[N, T, heads * D]``: the
form a projection writes and the output projection reads, so nothing is
copied on either side of the call. A block is ``(1, block, lanes)`` with
``lanes = g * D`` a multiple of 128 holding ``g`` whole heads
(:func:`heads_per_block`: two 64-wide heads, or one head of 128 or 256), and
one grid step does those ``g`` heads' work, one head after the other in a
loop on the device (:func:`_each_head`; unrolled, the two-head bodies of a
24-layer step cost ten seconds of set-up more, measured on the chip's host).
An operand need not be an array of its own: a block is addressed by its
lane-block index, so where self-attention projects q, k and v with ONE
matmul the three are the lane blocks ``p``, ``P + p`` and ``2P + p`` of that
matmul's output ``[N, T, 3 * heads * D]`` (:func:`flash_attention_qkv`), and
no q, k or v array is made; only the gradient is assembled, dq, dk and dv
concatenated.
Inside the block a head is told apart by a lane mask and not by a lane
slice: its q (and dO) are zeroed on the other heads' lanes and contracted
over all of them, and an operand that gives a ``(., D)`` product (v, k, q,
dO) is zeroed likewise, so the product lands on the head's own lanes of the
128-lane accumulator and adds nothing elsewhere. The ``[B, H, T, D]``
entries (decode caches, ring blocks) are the same kernels over ``[B * H, T,
D]``: one head a row, its D the whole minor dimension.

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


def heads_per_block(heads: int, d: int):
    """How many heads one lane block of a ``[.., T, heads * d]`` array
    holds, or None where no block of whole heads is a multiple of 128
    lanes (d = 80 or 96, three heads of 64): such shapes go through the
    ``[B, H, T, D]`` entry."""
    if d % 128 == 0:
        return 1
    g = 128 // d
    return g if 128 % d == 0 and heads % g == 0 else None


def _pick_block(t: int, target: int) -> int:
    """Block size: multiple of 128, capped at the (padded) sequence length."""
    t_pad = (t + 127) // 128 * 128
    return min(target, t_pad)


def _pad_t(x, t_pad, axis=1):
    t = x.shape[axis]
    if t == t_pad:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, t_pad - t)
    return jnp.pad(x, pad)


def _pad_each(xs, t_pads):
    """:func:`_pad_t` of each array to its length. An array given more than
    once (the fused projection, as q, k and v) is padded once a length."""
    done = {}
    for x, t_pad in zip(xs, t_pads):
        if (id(x), t_pad) not in done:
            done[id(x), t_pad] = _pad_t(x, t_pad)
    return [done[id(x), t_pad] for x, t_pad in zip(xs, t_pads)]


def _rows_spec(block, w, t_index, at=0):
    """BlockSpec of a ``(1, block, w)`` tile of a rows operand under the
    kernels' grid ``(b, p, x, y)``: block ``t_index(x, y)`` along T and
    lane block ``at + p``, ``at`` being where the operand starts inside a
    wider array (k and v of the fused projection)."""
    # an index map lowers the addition it is given, of 0 too: an operand
    # that is an array of its own keeps the program it had
    if at == 0:
        return pl.BlockSpec((1, block, w),
                            lambda b_, p_, x, y: (b_, t_index(x, y), p_))
    return pl.BlockSpec((1, block, w),
                        lambda b_, p_, x, y: (b_, t_index(x, y), p_ + at))


def _qkv_specs(bq, bk, w, q_blk, k_blk, at):
    """The :func:`_rows_spec` of q, k and v, each from its own start."""
    return [_rows_spec(bq, w, q_blk, at[0]), _rows_spec(bk, w, k_blk, at[1]),
            _rows_spec(bk, w, k_blk, at[2])]


def _mm(a, b, tb=False):
    """f32-accumulating matmul on the MXU: ``a @ b``, or ``a @ b.T`` (both
    contracted over their lanes) with ``tb``."""
    return jax.lax.dot_general(a, b, (((1,), (1 if tb else 0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _head_lanes(shape, h, d, g):
    """Which lanes of a ``(rows, g * d)`` block are head ``h``'s; None
    where the block is one head's."""
    if g == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jnp.logical_and(lane >= h * d, lane < (h + 1) * d)


def _own(x, lanes):
    """``x`` with the other heads' lanes zeroed."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _each_head(g, head):
    """``head(h)`` for each of the ``g`` heads of a block. Where g > 1 the
    heads are a loop on the device and ``h`` is traced: the body is traced
    and lowered once, which a step of many layers pays for in set-up."""
    if g == 1:
        head(0)
    else:
        jax.lax.fori_loop(0, g, lambda h, carry: (head(h), carry)[1], 0)


def _score_mask(q_off, k_off, block_q, block_k, kv_len, causal,
                key_major=False):
    """Which (query row, key column) pairs of one tile take part; with
    ``key_major`` the tile is ``(block_k, block_q)``, keys down the rows."""
    shape = (block_k, block_q) if key_major else (block_q, block_k)
    col = k_off + jax.lax.broadcasted_iota(jnp.int32, shape,
                                           0 if key_major else 1)
    mask = col < kv_len
    if causal:
        row = q_off + jax.lax.broadcasted_iota(jnp.int32, shape,
                                               1 if key_major else 0)
        mask = jnp.logical_and(mask, col <= row)
    return mask


def _stat_spec(g, block_q, q_blk):
    """BlockSpec of the ``(1, 1, g, block_q)`` tile of a row statistic
    ``[N, heads // g, g, T]`` (:func:`_stat_view`) under the kernels' grid
    ``(b, p, x, y)``: the ``g`` heads of lane block ``p``, one f32 a row,
    the rows of query block ``q_blk(x, y)`` along the lanes."""
    return pl.BlockSpec((1, 1, g, block_q),
                        lambda b_, p_, x, y: (b_, p_, 0, q_blk(x, y)))


def _stat_view(x, g, t_pad):
    """A row statistic ``[N, heads, T]`` as the kernels index it: ``[N,
    heads // g, g, t_pad]``, the heads of one lane block together."""
    n, heads, _ = x.shape
    return _pad_t(x, t_pad, axis=2).reshape(n, heads // g, g, t_pad)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, d, g, scale, block_q, block_k, causal, kv_len, nk,
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
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        mask = _score_mask(q_off, k_off, block_q, block_k, kv_len, causal)

        def head(h):
            lanes = _head_lanes(q.shape, h, d, g)
            s = _mm(_own(q, lanes), k, tb=True) * scale      # (bq, bk) f32
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h, :, :1]                   # (bq, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)                     # (bq, bk)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = jnp.broadcast_to(m_cur, m_ref.shape[1:])
            # (bq, g*d): nothing outside the head's own lanes, which
            # alone are rescaled
            pv = _mm(p.astype(v.dtype),
                     _own(v, _head_lanes(v.shape, h, d, g)))
            keep = alpha if lanes is None else jnp.where(lanes, alpha, 1.0)
            acc_ref[:] = acc_ref[:] * keep + pv

        _each_head(g, head)

    @pl.when(j == nk - 1)
    def _finish():
        def head(h):
            # the head's lanes of the accumulator become its output
            l = l_ref[h, :, :1]
            safe_l = jnp.where(l == 0.0, 1.0, l)       # fully-masked rows → 0
            o_h = acc_ref[:] / safe_l
            lanes = _head_lanes(o_h.shape, h, d, g)
            acc_ref[:] = o_h if lanes is None else jnp.where(lanes, o_h,
                                                             acc_ref[:])

        _each_head(g, head)
        o_ref[0] = acc_ref[:].astype(o_ref.dtype)
        # the statistic leaves as ONE f32 a row, the rows along the lanes:
        # m and l hold it on every lane of a (bq, 128) column, and row 0 of
        # the transpose is the row to write, once a query block
        for h in range(g):
            lse = m_ref[h] + jnp.log(jnp.maximum(l_ref[h], 1e-30))
            lse_ref[0, 0, h:h + 1, :] = lse.T[:1]


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct, carrying varying-mesh-axes when the caller runs
    inside a strict-VMA shard_map (parallel/ring_flash.py)."""
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _block_heads(heads, d):
    """Heads a lane block: :func:`heads_per_block`, and one head where its
    ``d`` is the whole minor dimension (the ``[B * H, T, D]`` callers)."""
    g = 1 if heads == 1 else heads_per_block(heads, d)
    if g is None:
        raise ValueError(
            f"{heads} heads of {d} fill no 128-lane block: split the heads "
            "and use flash_attention_fused")
    return g


def _fwd_rows(q, k, v, heads, causal, scale, block_q, block_k, interpret,
              vma=None, q_offset=0, kv_len=None, at=(0, 0, 0), width=None):
    """q ``[N, Tq, heads*D]``, k/v ``[N, Tkv, heads*D]`` → o ``[N, Tq,
    heads*D]`` and lse ``[N, heads, Tq]``. Where q, k and v are lanes of
    wider arrays (ONE array, the fused projection:
    :func:`flash_attention_qkv`), ``width`` is the ``heads*D`` lanes each
    has and ``at`` the lane block at which each starts."""
    n, t_q = q.shape[:2]
    c = width or q.shape[2]
    d = c // heads
    g = _block_heads(heads, d)
    w = g * d
    t_kv = k.shape[1]
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
    tkv_pad = max(tkv_need, t_kv)
    qp, kp, vp = _pad_each((q, k, v), (tq_pad, tkv_pad, tkv_pad))
    nq = tq_pad // bq

    kernel = functools.partial(
        _fwd_kernel, d=d, g=g, scale=scale, block_q=bq, block_k=bk,
        causal=causal, kv_len=kv_len, nk=nk, q_offset=q_offset)
    q_blk, k_blk = (lambda i, j: i), (lambda i, j: j)
    o, lse = pl.pallas_call(
        kernel,
        grid=(n, heads // g, nq, nk),
        in_specs=_qkv_specs(bq, bk, w, q_blk, k_blk, at),
        out_specs=[_rows_spec(bq, w, q_blk), _stat_spec(g, bq, q_blk)],
        out_shape=[
            _sds((n, tq_pad, c), q.dtype, vma),
            _sds((n, heads // g, g, tq_pad), jnp.float32, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, w), jnp.float32),
            pltpu.VMEM((g, bq, 128), jnp.float32),
            pltpu.VMEM((g, bq, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qp, kp, vp)
    return o[:, :t_q], lse.reshape(n, heads, tq_pad)[:, :, :t_q]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc,
                   *, d, g, scale, block_q, block_k, causal, kv_len, nq):
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
        # back to the wire dtype only as matmul operands.
        # KEY-MAJOR: the tiles are (bk, bq), keys down the rows, so a row
        # statistic is used as it arrives, a (1, bq) row along the lanes (a
        # new one every grid step: the query block is the inner axis), and
        # dV, dK are plain products with no transposed operand
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        dt = q.dtype
        mask = _score_mask(q_off, k_off, block_q, block_k, kv_len, causal,
                           key_major=True)

        def head(h):
            # q and dO on the head's lanes alone: the scores contract
            # over them, and dV, dK land on them
            lanes = _head_lanes(q.shape, h, d, g)
            q_h, do_h = _own(q, lanes), _own(do, lanes)
            lse = lse_ref[0, 0, pl.ds(h, 1), :]        # (1, bq)
            delta = delta_ref[0, 0, pl.ds(h, 1), :]    # (1, bq)

            s = _mm(k, q_h, tb=True) * scale           # (bk, bq)
            p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # (bk, bq) f32

            dv_acc[:] += _mm(p.astype(dt), do_h)       # (bk, g*d)
            dp = _mm(v, do_h, tb=True)                 # (bk, bq)
            ds = p * (dp - delta) * scale
            dk_acc[:] += _mm(ds.astype(dt), q_h)       # (bk, g*d)

        _each_head(g, head)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_q_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  dq_ref, dq_acc,
                  *, d, g, scale, block_q, block_k, causal, kv_len, nk):
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
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        mask = _score_mask(q_off, k_off, block_q, block_k, kv_len, causal)

        def head(h):
            # here k and v carry the head: dQ lands on its lanes
            lanes = _head_lanes(k.shape, h, d, g)
            k_h, v_h = _own(k, lanes), _own(v, lanes)
            # query-major tiles want the statistics down the rows: the
            # head's (bq,) row turned into a (bq, 1) column, in VMEM
            lse = jnp.expand_dims(lse_ref[0, 0, h], -1)
            delta = jnp.expand_dims(delta_ref[0, 0, h], -1)

            s = _mm(q, k_h, tb=True) * scale
            p = jnp.where(mask, jnp.exp(s - lse), 0.0)
            dp = _mm(do, v_h, tb=True)
            ds = p * (dp - delta) * scale
            dq_acc[:] += _mm(ds.astype(k.dtype), k_h)       # (bq, g*d)

        _each_head(g, head)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_rows(heads, causal, scale, block_q, block_k, interpret, res, g,
              delta=None, out_dtype=None, vma=None, at=(0, 0, 0),
              width=None):
    """``res`` = (q, k, v, o, lse) as :func:`_fwd_rows` takes and gives
    them (``at`` and ``width`` too), ``g`` = dO like ``o``; returns dq, dk
    and dv as three arrays of ``heads*D`` lanes. ``delta`` (``[N, heads,
    Tq]``) and ``out_dtype`` are for block-composed callers
    (parallel/ring_flash.py): a ring backward precomputes the global
    rowsum(dO*O) once and needs f32 gradient outputs so per-hop
    accumulation does not round at the input dtype."""
    q, k, v, o, lse = res
    n, t_q = q.shape[:2]
    c = width or q.shape[2]
    d = c // heads
    hb = _block_heads(heads, d)
    w = hb * d
    t_kv = k.shape[1]
    bq = _pick_block(t_q, block_q)
    bk = _pick_block(t_kv, block_k)
    tq_pad = (t_q + bq - 1) // bq * bq
    tkv_pad = (t_kv + bk - 1) // bk * bk
    nq, nk = tq_pad // bq, tkv_pad // bk

    if delta is None:
        # delta_i = rowsum(dO_i * O_i) over each head's own lanes — cheap
        # elementwise+reduce; XLA fuses it
        prod = g.astype(jnp.float32) * o.astype(jnp.float32)
        delta = jnp.sum(prod.reshape(n, t_q, heads, d),
                        axis=-1).transpose(0, 2, 1)

    qp, kp, vp = _pad_each((q, k, v), (tq_pad, tkv_pad, tkv_pad))
    dop = _pad_t(g, tq_pad)
    stats = (_stat_view(lse, hb, tq_pad), _stat_view(delta, hb, tq_pad))

    statics = dict(d=d, g=hb, scale=scale, block_q=bq, block_k=bk,
                   causal=causal, kv_len=t_kv)
    # grid (b, p, key block x, query block y)
    q_blk, k_blk = (lambda x, y: y), (lambda x, y: x)
    q_spec = _rows_spec(bq, w, q_blk)
    k_spec = _rows_spec(bk, w, k_blk)
    r_spec = _stat_spec(hb, bq, q_blk)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, nq=nq, **statics),
        grid=(n, heads // hb, nk, nq),
        in_specs=_qkv_specs(bq, bk, w, q_blk, k_blk, at) + [
            q_spec, r_spec, r_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[_sds((n, tkv_pad, c), out_dtype or k.dtype, vma),
                   _sds((n, tkv_pad, c), out_dtype or v.dtype, vma)],
        scratch_shapes=[pltpu.VMEM((bk, w), jnp.float32),
                        pltpu.VMEM((bk, w), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, *stats)

    # grid (b, p, query block x, key block y)
    q_blk, k_blk = (lambda x, y: x), (lambda x, y: y)
    q_spec2 = _rows_spec(bq, w, q_blk)
    r_spec2 = _stat_spec(hb, bq, q_blk)
    dq = pl.pallas_call(
        functools.partial(_bwd_q_kernel, nk=nk, **statics),
        grid=(n, heads // hb, nq, nk),
        in_specs=_qkv_specs(bq, bk, w, q_blk, k_blk, at) + [
            q_spec2, r_spec2, r_spec2],
        out_specs=q_spec2,
        out_shape=_sds((n, tq_pad, c), out_dtype or q.dtype, vma),
        scratch_shapes=[pltpu.VMEM((bq, w), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, *stats)

    return dq[:, :t_q], dk[:, :t_kv], dv[:, :t_kv]


# ---------------------------------------------------------------------------
# the [B, H, T, D] entries: one head a row of [B * H, T, D]
# ---------------------------------------------------------------------------

def _fold(x):
    """``[B, H, ...]`` → ``[B * H, ...]``: no data moves."""
    return x.reshape((-1,) + x.shape[2:])


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               vma=None, q_offset=0, kv_len=None):
    """q, k, v ``[B, H, T, D]`` → o ``[B, H, Tq, D]``, lse ``[B, H, Tq]``."""
    o, lse = _fwd_rows(_fold(q), _fold(k), _fold(v), 1, causal, scale,
                       block_q, block_k, interpret, vma=vma,
                       q_offset=q_offset, kv_len=kv_len)
    return o.reshape(q.shape), lse.reshape(q.shape[:3])


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g,
               delta=None, out_dtype=None, vma=None):
    """:func:`_bwd_rows` for ``[B, H, T, D]`` operands (``lse`` and
    ``delta`` ``[B, H, Tq]``)."""
    q, k, v, o, lse = res
    if delta is not None:
        delta = delta.reshape(-1, 1, delta.shape[-1])
    dq, dk, dv = _bwd_rows(
        1, causal, scale, block_q, block_k, interpret,
        (_fold(q), _fold(k), _fold(v), _fold(o),
         lse.reshape(-1, 1, lse.shape[-1])),
        _fold(g), delta=delta, out_dtype=out_dtype, vma=vma)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


# ---------------------------------------------------------------------------
# public entries
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, heads, causal, scale, block_q, block_k, interpret):
    o, _ = _fwd_rows(q, k, v, heads, causal, scale, block_q, block_k,
                     interpret)
    return o


def _flash_vjp_fwd(q, k, v, heads, causal, scale, block_q, block_k,
                   interpret):
    o, lse = _fwd_rows(q, k, v, heads, causal, scale, block_q, block_k,
                       interpret)
    o, lse = _kept(o, lse)
    return o, (q, k, v, o, lse)


def _kept(o, lse):
    """The two residuals the forward kernel itself computed, named for
    remat policies (q, k, v and the fused projection are not: a
    checkpointed caller recomputes them from its own input)."""
    # Both are kept as the kernel wrote them. `lse` is one f32 a row, so
    # nothing has to order it before `o`'s readers: what a policy holds
    # until the backward pass is those `[N, heads, T]` numbers whenever
    # XLA chooses to lay them out. `o` is `[B, T, H*D]`, dense in HBM, what
    # the caller's output projection reads and the backward kernels take.
    # (Through the `[B, H, T, D]` entry it is `[B*H, T, D]`, padded to 128
    # lanes for as long as it is kept where D < 128.)
    return (checkpoint_name(o, FLASH_OUT_NAME),
            checkpoint_name(lse, FLASH_LSE_NAME))


def _flash_vjp_bwd(heads, causal, scale, block_q, block_k, interpret, res,
                   g):
    return _bwd_rows(heads, causal, scale, block_q, block_k, interpret, res,
                     g)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _qkv_views(qkv, heads):
    """q, k and v as the kernels find them in the fused projection ``[N,
    T, 3 * heads * D]``: the one array three times, each ``heads * D``
    lanes wide, starting at lane blocks 0, P and 2P (P blocks a view)."""
    c = qkv.shape[2] // 3
    p = heads // _block_heads(heads, c // heads)
    return (qkv, qkv, qkv), dict(at=(0, p, 2 * p), width=c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _flash_qkv(qkv, heads, causal, scale, block_q, block_k, interpret):
    views, where = _qkv_views(qkv, heads)
    o, _ = _fwd_rows(*views, heads, causal, scale, block_q, block_k,
                     interpret, **where)
    return o


def _flash_qkv_vjp_fwd(qkv, heads, causal, scale, block_q, block_k,
                       interpret):
    views, where = _qkv_views(qkv, heads)
    o, lse = _kept(*_fwd_rows(*views, heads, causal, scale, block_q,
                              block_k, interpret, **where))
    return o, (qkv, o, lse)


def _flash_qkv_vjp_bwd(heads, causal, scale, block_q, block_k, interpret,
                       res, g):
    qkv, o, lse = res
    views, where = _qkv_views(qkv, heads)
    # the gradient of the fused projection, as the slices' transposes
    # would assemble it
    return (jnp.concatenate(
        _bwd_rows(heads, causal, scale, block_q, block_k, interpret,
                  (*views, o, lse), g, **where), axis=-1),)


_flash_qkv.defvjp(_flash_qkv_vjp_fwd, _flash_qkv_vjp_bwd)


def flash_attention_rows(q, k, v, num_heads: int, causal: bool = False,
                         scale: float | None = None,
                         block_q: int = 512, block_k: int = 512,
                         interpret: bool = False):
    """Fused flash attention on the activations' own layout. q, k, v:
    ``[B, T, num_heads * D]``, as the q/k/v projections write them;
    returns ``[B, T, num_heads * D]``, as the output projection reads it.
    Needs :func:`heads_per_block` ``(num_heads, D)`` to be a number.

    The same attention as :func:`flash_attention_fused` gives on the split
    heads, with no copy on either side of the kernels."""
    d = q.shape[-1] // num_heads
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return _flash(q, k, v, int(num_heads), bool(causal), float(scale),
                  int(block_q), int(block_k), bool(interpret))


def flash_attention_qkv(qkv, num_heads: int, causal: bool = False,
                        scale: float | None = None,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = False):
    """:func:`flash_attention_rows` of self-attention whose q, k and v are
    ONE matmul's output, ``qkv`` = ``[B, T, 3 * num_heads * D]`` (q's
    lanes, then k's, then v's): the kernels index the three inside it, so
    no q, k or v array is made; returns ``[B, T, num_heads * D]``. The
    gradient is one array like ``qkv``. Needs :func:`heads_per_block`
    ``(num_heads, D)`` to be a number."""
    d = qkv.shape[-1] // (3 * num_heads)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return _flash_qkv(qkv, int(num_heads), bool(causal), float(scale),
                      int(block_q), int(block_k), bool(interpret))


def flash_attention_fused(q, k, v, causal: bool = False,
                          scale: float | None = None,
                          block_q: int = 512, block_k: int = 512,
                          interpret: bool = False):
    """Fused flash attention on SPLIT heads. q, k, v: ``[B, H, T, D]``;
    returns ``[B, H, T, D]`` — the layout of a decode cache, a ring block
    and of any head size that fills no 128-lane block. A caller that holds
    ``[B, T, H * D]`` and can use :func:`flash_attention_rows` saves the
    transposes around this one.

    Matches ``nn.attention.dot_product_attention(q, k, v, causal_mask)``
    numerically (softmax(QK^T / sqrt(D)) V) with O(T) memory. Differentiable
    via the Pallas backward kernels. ``interpret=True`` runs the kernel in
    the Pallas interpreter (CPU tests).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    o = _flash(_fold(q), _fold(k), _fold(v), 1, bool(causal), float(scale),
               int(block_q), int(block_k), bool(interpret))
    return o.reshape(q.shape)


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
