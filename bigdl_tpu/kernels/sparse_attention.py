"""Learned sparse attention (DeepSeek-V3.2's DSA) as Pallas TPU kernels.

A lightning indexer scores every causal (query, key) pair,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])      (s <= t)

and each query attends to the ``min(t + 1, topk)`` keys of largest score
(ties to the earlier key). Five kernels, each with its ``name=``:

``dsa_select``     the indexer's scores tile by tile into VMEM, one query
                   sub-block's whole row at a time, and the EXACT top-k per
                   query by a radix search over the scores' order-preserving
                   32-bit keys (then over the key index, for the tie rule).
                   It writes the selection as a packed bitmask, one bit a
                   pair, and the logsumexp of the scores over the selected
                   keys (the indexer's softmax).
``dsa_fwd``        grouped-query flash attention over the selected pairs:
                   one KV head's ``g`` query heads a grid step, read in the
                   projections' own layout ``[N, T, heads * D]``. Its online
                   softmax keeps a row's running max, its rescale factor
                   and its sum on 128 lanes, the row's value on each, so
                   the only work across lanes a head and tile is the row
                   max and the row sum themselves. It masks a tile's
                   scores ONCE: with the finite ``NEG_INF`` an unselected
                   key's ``exp(NEG_INF - m)`` is 0 once the row has met a
                   selected key; what the row summed before (p = 1 while
                   m is still ``NEG_INF``) its first selected key
                   multiplies by ``alpha = exp(NEG_INF - m) = 0``; and
                   ``dsa_select`` gives every row at least one key. A row
                   given none by hand is written as 0. The scale stays on
                   the scores, as ``_probs`` applies it.
``dsa_bwd_dkv``    dK, dV;  ``dsa_bwd_dq``  dQ.
``dsa_index_bwd``  the indexer's objective, KL(p || softmax_S(I)) with p the
                   main attention's probabilities averaged over all heads
                   (recomputed from q, k and the attention's logsumexp; no
                   gradient), and its gradient dI = softmax_S(I) - p on the
                   selected pairs, taken on to dqI, dkI and dw. It runs in the
                   forward pass, where the loss is made; the backward pass
                   scales what it kept.

Every kernel that uses the selection reads the one bitmask, so the forward
and both backward kernels see the identical set. The four kernels that tile
the (query block, key block) square run its whole grid and skip in two
ways: a step whose tile is not causal neither computes nor fetches (its
index maps return the blocks of the nearest causal step of its grid row,
so the pipeline sees no new block), and a causal tile with no selected
pair skips its compute but still fetches. Nothing of size ``[T, T]`` or
``[T, topk]`` but the bitmask (``T * T`` bits) reaches HBM.

The bitmask is ``[N, (T // block_q) * R, T]`` int32 with ``R = block_q //
32``: bit ``r`` of word ``(i * R + w, s)`` is the pair (query ``i *
block_q + r * R + w``, key ``s``), so a tile unpacks into 32 slabs of ``R``
whole rows. ``T`` must be a multiple of ``block_q`` and ``block_k``.
``interpret=True`` runs the same kernels on the CPU for the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import FLASH_LSE_NAME, FLASH_OUT_NAME, NEG_INF, _mm

# the residuals ``nn.attention.remat_block`` keeps besides the flash ones:
# the selection, and the indexer's gradient made in the forward pass
DSA_KEPT_NAME = "dsa_kept"

INT_MIN = np.int32(-2 ** 31)
SUB = 4                       # query sub-blocks of ``dsa_select`` a block
VMEM_LIMIT = 96 * 2 ** 20     # v5e has 128 MiB of VMEM


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _words(block_q: int) -> int:
    if block_q % 256:
        raise ValueError(f"block_q {block_q}: a multiple of 256")
    return block_q // 32


def _unpack(words):
    """A ``(R, bk)`` tile of the bitmask as the ``(32 * R, bk)`` pairs."""
    return jnp.concatenate([(words >> r) & 1 for r in range(32)],
                           axis=0) != 0


def _row_sum(x):
    """The rows' sums of ``x (rows, cols)`` as a ``(1, rows)`` row: a
    product on the MXU lays the result along the lanes."""
    ones = jnp.ones((8, x.shape[1]), jnp.float32)
    return _mm(ones, x, tb=True)[:1]


def _as_row(col):
    """A ``(rows, 1)`` column as a ``(1, rows)`` row."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1]


def _col(ref_row):
    """A ``(rows,)`` statistic as the ``(rows, 1)`` column a query-major
    tile wants."""
    return jnp.expand_dims(ref_row, -1)


def _index_scores(qi_ref, kit, w_ref, n_index):
    """``I`` of one tile: ``sum_j w_j relu(qI_j kI^T)``, ``(rows, bk)``
    f32, from the query rows' heads ``qi_ref[0, j]`` ``(rows, dI)``, the
    keys ``kit (dI, bk)`` and the weights ``w_ref[0, j]`` ``(rows,)``."""
    out = None
    for j in range(n_index):
        a = _mm(qi_ref[0, j], kit)
        t = jnp.maximum(a, 0.0) * _col(w_ref[0, j])
        out = t if out is None else out + t
    return out


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------

def _order_key(x):
    """int32 keys in the order of the f32 values (-0 is made +0 first)."""
    x = jnp.where(x == 0.0, 0.0, x)
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ np.int32(0x7FFFFFFF), b)


def _from_key(k):
    b = jnp.where(k < 0, k ^ np.int32(0x7FFFFFFF), k)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _bit(b: int):
    return np.int32(((1 << b) + 2 ** 31) % 2 ** 32 - 2 ** 31)


def _select_kernel(qi_ref, kit_ref, w_ref, bits_ref, lse_ref, keys_ref, *,
                   n_index, topk, bs, bk, t_len):
    i, s = pl.program_id(1), pl.program_id(2)
    r0 = (i * SUB + s) * bs
    tiles = jnp.minimum((r0 + bs - 1) // bk + 1, t_len // bk)
    row = r0 + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    cols = lambda c0: c0 + jax.lax.broadcasted_iota(jnp.int32, (bs, bk), 1)

    @pl.when(s == 0)
    def _zero():
        bits_ref[...] = jnp.zeros_like(bits_ref)

    def fill(j, c):
        c0 = pl.multiple_of(j * bk, bk)
        key = _order_key(_index_scores(qi_ref, kit_ref[0, :, pl.ds(c0, bk)],
                                       w_ref, n_index))
        keys_ref[:, pl.ds(c0, bk)] = jnp.where(cols(c0) <= row, key, INT_MIN)
        return c

    jax.lax.fori_loop(0, tiles, fill, 0)

    def total(fn):
        """``sum over the row's tiles of fn(keys, columns)``, ``(bs, 1)``."""
        def body(j, acc):
            c0 = pl.multiple_of(j * bk, bk)
            hit = fn(keys_ref[:, pl.ds(c0, bk)], cols(c0)).astype(jnp.float32)
            for c in range(bk // 128):
                acc = acc + hit[:, c * 128:(c + 1) * 128]
            return acc
        acc = jax.lax.fori_loop(0, tiles, body,
                                jnp.zeros((bs, 128), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    # the k-th largest key: the largest x with #(key >= x) >= k, built bit
    # by bit in the sign-flipped (unsigned) order
    k = jnp.minimum(row + 1, topk).astype(jnp.float32)
    found = jnp.zeros((bs, 1), jnp.int32)
    for b in range(31, -1, -1):
        trial = found | _bit(b)
        n = total(lambda key, _: key >= (trial ^ INT_MIN))
        found = jnp.where(n >= k, trial, found)
    tau = found ^ INT_MIN
    # ties at tau: the earliest ``need`` of them, up to key index ``last``
    need = k - total(lambda key, _: key > tau)
    last = jnp.zeros((bs, 1), jnp.int32)
    for b in range(max(int(t_len - 1).bit_length(), 1) - 1, -1, -1):
        trial = last | np.int32(1 << b)
        n = total(lambda key, col: (key == tau) & (col < trial))
        last = jnp.where(n < need, trial, last)

    def chosen(key, col):
        return (key > tau) | ((key == tau) & (col <= last))

    # the indexer's softmax over the chosen keys: its logsumexp
    def top(j, m):
        c0 = pl.multiple_of(j * bk, bk)
        key = keys_ref[:, pl.ds(c0, bk)]
        x = jnp.where(chosen(key, cols(c0)), _from_key(key), NEG_INF)
        return jnp.maximum(m, jnp.max(x, axis=1, keepdims=True))

    m = jax.lax.fori_loop(0, tiles, top, jnp.full((bs, 1), NEG_INF))
    l = total(lambda key, col: jnp.where(
        chosen(key, col), jnp.exp(_from_key(key) - m), 0.0))
    lse_ref[0] = _as_row(m + jnp.log(l))

    # this sub-block's rows are bits s * rb .. s * rb + rb - 1 of the words
    rb = bs // bits_ref.shape[1]

    def emit(j, c):
        c0 = pl.multiple_of(j * bk, bk)
        sel = chosen(keys_ref[:, pl.ds(c0, bk)], cols(c0)).astype(jnp.int32)
        nw = bits_ref.shape[1]
        word = bits_ref[0, :, pl.ds(c0, bk)]
        for r in range(rb):
            word = word | (sel[r * nw:(r + 1) * nw] << (s * rb + r))
        bits_ref[0, :, pl.ds(c0, bk)] = word
        return c

    jax.lax.fori_loop(0, tiles, emit, 0)


def dsa_select(qi, kit, w, topk: int, block_q: int = 512,
               block_k: int = 512, interpret: bool = False):
    """The exact top-``topk`` keys of every query under the indexer's
    scores. ``qi [N, hI, T, dI]`` (the index heads' queries), ``kit [N, dI,
    T]`` (the one index key, transposed), ``w [N, hI, T]`` (the heads'
    weights, scale included). Returns the bitmask ``[N, T // block_q *
    block_q // 32, T]`` int32 and the logsumexp of the scores over each
    query's selected keys, ``[N, 1, T]`` f32."""
    n, n_index, t, d_index = qi.shape
    nw = _words(block_q)
    bs = block_q // SUB
    if t % block_q or t % block_k:
        raise ValueError(f"T = {t}: a multiple of {block_q} and {block_k}")
    nq = t // block_q
    kernel = functools.partial(_select_kernel, n_index=n_index, topk=topk,
                               bs=bs, bk=block_k, t_len=t)
    sub = lambda b, i, s: i * SUB + s
    return pl.pallas_call(
        kernel,
        grid=(n, nq, SUB),
        in_specs=[
            pl.BlockSpec((1, n_index, bs, d_index),
                         lambda b, i, s: (b, 0, sub(b, i, s), 0)),
            pl.BlockSpec((1, d_index, t), lambda b, i, s: (b, 0, 0)),
            pl.BlockSpec((1, n_index, bs),
                         lambda b, i, s: (b, 0, sub(b, i, s)))],
        out_specs=[pl.BlockSpec((1, nw, t), lambda b, i, s: (b, i, 0)),
                   pl.BlockSpec((1, 1, bs),
                                lambda b, i, s: (b, 0, sub(b, i, s)))],
        out_shape=[jax.ShapeDtypeStruct((n, nq * nw, t), jnp.int32),
                   jax.ShapeDtypeStruct((n, 1, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bs, t), jnp.int32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="dsa_select",
    )(qi, kit, w)


def tiles_visited(bits, block_q: int = 512, block_k: int = 512):
    """The share of causal (query block, key block) tiles that hold a
    selected pair, over the batch."""
    n, rows, t = bits.shape
    nw = _words(block_q)
    nq, nk = rows // nw, t // block_k
    hit = jnp.any(bits.reshape(n, nq, nw, nk, block_k) != 0, axis=(2, 4))
    causal = sum(min((i * block_q + block_q - 1) // block_k + 1, nk)
                 for i in range(nq))
    return jnp.sum(hit, dtype=jnp.float32) / (n * causal)


# ---------------------------------------------------------------------------
# sparse grouped-query attention
# ---------------------------------------------------------------------------

def _visit(i, j, bq, bk, words):
    """A tile is worked on where it is causal and holds a selected pair."""
    return jnp.logical_and(j * bk <= i * bq + bq - 1, jnp.any(words != 0))


def _last_k(i, bq, bk, nk):
    """The last key block query block ``i`` sees: tile ``(i, j)`` is causal
    for ``j <= _last_k(i)``."""
    return jnp.minimum((i * bq + bq - 1) // bk, nk - 1)


def _first_q(j, bq, bk):
    """The first query block that sees key block ``j``: tile ``(i, j)`` is
    causal for ``i >= _first_q(j)``."""
    return j * bk // bq


def _lanes(x, n):
    """A ``(rows, 128)`` block as ``(rows, 128 * n)``: n copies side by
    side, whole registers reused, no data moved."""
    return x if n == 1 else pltpu.repeat(x, n, axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, bits_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, g, d, scale, bq, bk, nk):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    words = bits_ref[0]

    @pl.when(_visit(i, j, bq, bk, words))
    def _compute():
        mask = _unpack(words)
        k, v = k_ref[0], v_ref[0]
        for h in range(g):
            s = _mm(q_ref[0, :, h * d:(h + 1) * d], k, tb=True) * scale
            s = jnp.where(mask, s, NEG_INF)
            # m, alpha and l stay (bq, 128), a row's value on every lane:
            # the row max is broadcast once, and nothing else is a
            # (bq, 1) column to lay out and broadcast again
            m_prev = m_ref[h]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            # no second select: exp(NEG_INF - m_cur) is 0 once the row has
            # met a selected key; before that m_cur is NEG_INF, p is 1 off
            # the selection, and the row's first selected key wipes those
            # finite sums with alpha = exp(NEG_INF - m) = 0
            p = jnp.exp(s - _lanes(m_cur, bk // 128))
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = m_cur
            acc_ref[h] = (acc_ref[h] * _lanes(alpha, d // 128)
                          + _mm(p.astype(v.dtype), v))

    @pl.when(j == nk - 1)
    def _finish():
        for h in range(g):
            # a row that met no selected key (only a hand-made bitmask
            # has one) still holds m = NEG_INF and the sums of its
            # unselected keys: its o is 0, its lse NEG_INF as before
            seen = m_ref[h, :, :1] > NEG_INF
            o = acc_ref[h] / jnp.where(seen, l_ref[h, :, :1], 1.0)
            o_ref[0, :, h * d:(h + 1) * d] = jnp.where(
                seen, o, 0.0).astype(o_ref.dtype)
            lse = m_ref[h] + jnp.log(jnp.maximum(l_ref[h], 1e-30))
            lse_ref[0, h:h + 1, :] = lse.T[:1]


def _specs(g, d, bq, bk, q_blk, k_blk):
    """q-like ``(1, bq, g*d)``, k-like ``(1, bk, d)``, a row statistic
    ``(1, g, bq)`` and the bitmask's ``(1, R, bk)`` under the grid ``(b,
    kv head, x, y)``, with ``q_blk(x, y)`` and ``k_blk(x, y)`` the query
    and key block of a step."""
    nw = _words(bq)
    return dict(
        q=pl.BlockSpec((1, bq, g * d), lambda b, p, x, y: (b, q_blk(x, y), p)),
        k=pl.BlockSpec((1, bk, d), lambda b, p, x, y: (b, k_blk(x, y), p)),
        stat=pl.BlockSpec((1, g, bq), lambda b, p, x, y: (b, p, q_blk(x, y))),
        bits=pl.BlockSpec((1, nw, bk),
                          lambda b, p, x, y: (b, q_blk(x, y), k_blk(x, y))))


def _query_major(g, d, bq, bk, nk):
    """The specs under the grid ``(b, kv head, i, j)``, key block ``j``
    innermost: a step past row ``i``'s last causal key block keeps that
    block, so it fetches nothing."""
    return _specs(g, d, bq, bk, lambda i, j: i,
                  lambda i, j: jnp.minimum(j, _last_k(i, bq, bk, nk)))


def _attn_fwd(q, k, v, bits, heads, kv_heads, scale, bq, bk, interpret):
    n, t, c = q.shape
    d, g = c // heads, heads // kv_heads
    nq, nk = t // bq, t // bk
    sp = _query_major(g, d, bq, bk, nk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, g=g, d=d, scale=scale, bq=bq, bk=bk,
                          nk=nk),
        grid=(n, kv_heads, nq, nk),
        in_specs=[sp["q"], sp["k"], sp["k"], sp["bits"]],
        out_specs=[sp["q"], sp["stat"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((n, heads, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g, bq, d), jnp.float32),
                        pltpu.VMEM((g, bq, 128), jnp.float32),
                        pltpu.VMEM((g, bq, 128), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
        name="dsa_fwd",
    )(q, k, v, bits)


def _probs(q_ref, k, lse_ref, mask, h, d, scale):
    """Head ``h``'s probabilities on a tile, 0 off the selection."""
    s = _mm(q_ref[0, :, h * d:(h + 1) * d], k, tb=True) * scale
    return jnp.where(mask, jnp.exp(s - _col(lse_ref[0, h])), 0.0)


def _bwd_kv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bits_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc, *, g, d, scale, bq, bk,
                   nq):
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    words = bits_ref[0]

    @pl.when(_visit(i, j, bq, bk, words))
    def _compute():
        mask = _unpack(words)
        k, v = k_ref[0], v_ref[0]
        dt = k.dtype
        for h in range(g):
            q_h = q_ref[0, :, h * d:(h + 1) * d]
            do_h = do_ref[0, :, h * d:(h + 1) * d]
            p = _probs(q_ref, k, lse_ref, mask, h, d, scale)   # (bq, bk)
            dv_acc[:] += jax.lax.dot_general(
                p.astype(dt), do_h, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = _mm(do_h, v, tb=True)
            ds = p * (dp - _col(delta_ref[0, h])) * scale
            dk_acc[:] += jax.lax.dot_general(
                ds.astype(dt), q_h, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_q_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bits_ref,
                  dq_ref, dq_acc, *, g, d, scale, bq, bk, nk):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    words = bits_ref[0]

    @pl.when(_visit(i, j, bq, bk, words))
    def _compute():
        mask = _unpack(words)
        k, v = k_ref[0], v_ref[0]
        for h in range(g):
            p = _probs(q_ref, k, lse_ref, mask, h, d, scale)
            dp = _mm(do_ref[0, :, h * d:(h + 1) * d], v, tb=True)
            ds = p * (dp - _col(delta_ref[0, h])) * scale
            dq_acc[h] += _mm(ds.astype(k.dtype), k)

    @pl.when(j == nk - 1)
    def _finish():
        for h in range(g):
            dq_ref[0, :, h * d:(h + 1) * d] = dq_acc[h].astype(dq_ref.dtype)


def _attn_bwd(q, k, v, bits, o, lse, do, heads, kv_heads, scale, bq, bk,
              interpret):
    n, t, c = q.shape
    d, g = c // heads, heads // kv_heads
    nq, nk = t // bq, t // bk
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32)
                     ).reshape(n, t, heads, d), axis=-1).transpose(0, 2, 1)
    statics = dict(g=g, d=d, scale=scale, bq=bq, bk=bk)
    # grid (b, kv head, j, i), query block i innermost: the steps before
    # key block j's first causal query block take that block, which the
    # row's first step fetches once
    sp = _specs(g, d, bq, bk, lambda j, i: jnp.maximum(i, _first_q(j, bq, bk)),
                lambda j, i: j)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, nq=nq, **statics),
        grid=(n, kv_heads, nk, nq),
        in_specs=[sp["q"], sp["k"], sp["k"], sp["q"], sp["stat"],
                  sp["stat"], sp["bits"]],
        out_specs=[sp["k"], sp["k"]],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
        name="dsa_bwd_dkv",
    )(q, k, v, do, lse, delta, bits)
    sp = _query_major(g, d, bq, bk, nk)
    dq = pl.pallas_call(
        functools.partial(_bwd_q_kernel, nk=nk, **statics),
        grid=(n, kv_heads, nq, nk),
        in_specs=[sp["q"], sp["k"], sp["k"], sp["q"], sp["stat"],
                  sp["stat"], sp["bits"]],
        out_specs=sp["q"],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((g, bq, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
        name="dsa_bwd_dq",
    )(q, k, v, do, lse, delta, bits)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _attention(q, k, v, bits, heads, kv_heads, scale, bq, bk, interpret):
    return tuple(_attn_fwd(q, k, v, bits, heads, kv_heads, scale, bq, bk,
                           interpret))


def _attention_fwd(q, k, v, bits, heads, kv_heads, scale, bq, bk, interpret):
    o, lse = _attn_fwd(q, k, v, bits, heads, kv_heads, scale, bq, bk,
                       interpret)
    o = checkpoint_name(o, FLASH_OUT_NAME)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return (o, lse), (q, k, v, bits, o, lse)


def _attention_bwd(heads, kv_heads, scale, bq, bk, interpret, res, ct):
    q, k, v, bits, o, lse = res
    do, _ = ct
    dq, dk, dv = _attn_bwd(q, k, v, bits, o, lse, do, heads, kv_heads, scale,
                           bq, bk, interpret)
    return dq, dk, dv, None


_attention.defvjp(_attention_fwd, _attention_bwd)


def dsa_attention(q, k, v, bits, heads: int, kv_heads: int, scale: float,
                  block_q: int = 512, block_k: int = 512,
                  interpret: bool = False):
    """Grouped-query attention over the selected pairs. q ``[N, T, heads *
    D]``, k and v ``[N, T, kv_heads * D]`` (query head ``h`` reads KV head
    ``h // (heads // kv_heads)``), ``bits`` from :func:`dsa_select`.
    Returns o ``[N, T, heads * D]`` and the logsumexp ``[N, heads, T]``
    (the latter carries no gradient). D is a multiple of 128."""
    if q.shape[-1] // heads % 128:
        raise ValueError("the sparse kernels take heads of 128 lanes")
    return _attention(q, k, v, bits, int(heads), int(kv_heads), float(scale),
                      int(block_q), int(block_k), bool(interpret))


# ---------------------------------------------------------------------------
# the indexer's objective and its gradient
# ---------------------------------------------------------------------------

def _index_kernel(qi_ref, kit_ref, w_ref, ilse_ref, bits_ref, q_ref, k_ref,
                  lse_ref, kl_ref, dqi_ref, dkit_ref, dw_ref, pbar_ref, *,
                  n_index, g, d, heads, scale, bq, bk, kv_heads):
    i, j, p = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when((i == 0) & (j == 0) & (p == 0))
    def _zero_dk():
        dkit_ref[...] = jnp.zeros_like(dkit_ref)

    @pl.when((j == 0) & (p == 0))
    def _zero_rows():
        kl_ref[...] = jnp.zeros_like(kl_ref)
        dqi_ref[...] = jnp.zeros_like(dqi_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    words = bits_ref[0]
    visit = _visit(i, j, bq, bk, words)

    @pl.when(visit)
    def _heads():
        mask = _unpack(words)
        k = k_ref[0]
        part = None
        for h in range(g):
            ph = _probs(q_ref, k, lse_ref, mask, h, d, scale)
            part = ph if part is None else part + ph
        pbar_ref[...] = jnp.where(p == 0, 0.0, pbar_ref[...]) + part

    @pl.when(visit & (p == kv_heads - 1))
    def _index():
        mask = _unpack(words)
        pbar = pbar_ref[...] * (1.0 / heads)
        kit = kit_ref[0]
        ilse = _col(ilse_ref[0, 0])
        x = _index_scores(qi_ref, kit, w_ref, n_index) - ilse
        kl = jnp.where(mask & (pbar > 0.0),
                       pbar * (jnp.log(jnp.maximum(pbar, 1e-30)) - x), 0.0)
        kl_ref[0] += _row_sum(kl)
        di = jnp.where(mask, jnp.exp(x) - pbar, 0.0)
        cols = pl.ds(pl.multiple_of(j * bk, bk), bk)
        for h in range(n_index):
            qi = qi_ref[0, h]
            a = _mm(qi, kit)
            dw_ref[0, h:h + 1, :] += _row_sum(di * jnp.maximum(a, 0.0))
            da = jnp.where(a > 0.0, di * _col(w_ref[0, h]), 0.0)
            dqi_ref[0, h] += _mm(da, kit, tb=True)
            dkit_ref[0, :, cols] += jax.lax.dot_general(
                qi, da, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def _index_call(qi, kit, w, ilse, bits, q, k, lse, heads, kv_heads, scale,
                bq, bk, interpret):
    """Per query row the KL of the indexer's softmax from the attention's
    mean probabilities over the selected keys, ``[N, 1, T]``, and the
    gradient of their SUM with respect to qi, kit and w."""
    n, n_index, t, d_index = qi.shape
    d, g = q.shape[-1] // heads, heads // kv_heads
    nw = _words(bq)
    kernel = functools.partial(
        _index_kernel, n_index=n_index, g=g, d=d, heads=heads, scale=scale,
        bq=bq, bk=bk, kv_heads=kv_heads)
    nk = t // bk
    row = lambda b, i, j, p: (b, 0, i)
    qi_spec = pl.BlockSpec((1, n_index, bq, d_index),
                           lambda b, i, j, p: (b, 0, i, 0))
    kit_all = pl.BlockSpec((1, d_index, t), lambda b, i, j, p: (b, 0, 0))

    def causal(shape, index):
        """``index(b, i, j, p)``'s block, and on a step past row ``i``'s
        last causal key block that of the row's last causal step (KV head
        ``p`` innermost), so such a step fetches nothing."""
        def clamped(b, i, j, p):
            last = _last_k(i, bq, bk, nk)
            past = j > last
            return index(b, i, jnp.where(past, last, j),
                         jnp.where(past, kv_heads - 1, p))
        return pl.BlockSpec(shape, clamped)

    return pl.pallas_call(
        kernel,
        grid=(n, t // bq, nk, kv_heads),
        in_specs=[
            qi_spec,
            causal((1, d_index, bk), lambda b, i, j, p: (b, 0, j)),
            pl.BlockSpec((1, n_index, bq), row),
            pl.BlockSpec((1, 1, bq), row),
            causal((1, nw, bk), lambda b, i, j, p: (b, i, j)),
            causal((1, bq, g * d), lambda b, i, j, p: (b, i, p)),
            causal((1, bk, d), lambda b, i, j, p: (b, j, p)),
            causal((1, g, bq), lambda b, i, j, p: (b, p, i))],
        out_specs=[pl.BlockSpec((1, 1, bq), row), qi_spec, kit_all,
                   pl.BlockSpec((1, n_index, bq), row)],
        out_shape=[jax.ShapeDtypeStruct((n, 1, t), jnp.float32),
                   jax.ShapeDtypeStruct(qi.shape, jnp.float32),
                   jax.ShapeDtypeStruct(kit.shape, jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, bk), jnp.float32)],
        compiler_params=_params("arbitrary", "arbitrary", "arbitrary",
                                "arbitrary"),
        interpret=interpret,
        name="dsa_index_bwd",
    )(qi, kit, w, ilse, bits, q, k, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12, 13))
def _index_loss(qi, kit, w, ilse, bits, q, k, lse, heads, kv_heads, scale,
                bq, bk, interpret):
    kl = _index_call(qi, kit, w, ilse, bits, q, k, lse, heads, kv_heads,
                     scale, bq, bk, interpret)[0]
    return jnp.sum(kl)


def _index_loss_fwd(qi, kit, w, ilse, bits, q, k, lse, heads, kv_heads,
                    scale, bq, bk, interpret):
    kl, dqi, dkit, dw = _index_call(qi, kit, w, ilse, bits, q, k, lse, heads,
                                    kv_heads, scale, bq, bk, interpret)
    kept = tuple(checkpoint_name(x, DSA_KEPT_NAME) for x in (dqi, dkit, dw))
    return jnp.sum(kl), kept


def _index_loss_bwd(heads, kv_heads, scale, bq, bk, interpret, kept, ct):
    # the gradient was made with the loss; what the selection, the
    # attention and its statistics give the indexer carries none back
    return tuple(ct * x for x in kept) + (None,) * 5


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def dsa_index_loss(qi, kit, w, ilse, bits, q, k, lse, heads: int,
                   kv_heads: int, scale: float, block_q: int = 512,
                   block_k: int = 512, interpret: bool = False):
    """``sum over the batch's query rows of KL(p_bar || softmax_S(I))``:
    ``p_bar`` the attention's probabilities (q, k, ``lse`` as
    :func:`dsa_attention` had them) averaged over the heads, ``I`` the
    indexer's scores, ``ilse`` and ``bits`` as :func:`dsa_select` gave
    them. Differentiable in qi, kit and w alone."""
    return _index_loss(qi, kit, w, ilse, bits, q, k, lse, int(heads),
                       int(kv_heads), float(scale), int(block_q),
                       int(block_k), bool(interpret))
