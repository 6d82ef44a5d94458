"""Paged-attention decode kernel: gather-free KV block streaming.

The serving tier's decode hot path is memory-bandwidth-bound, and the
dense ``nn.Attention.decode_paged`` path pays for it twice: every step
it materialises a logical (B, kvH, T, D) view of the paged KV pool
(``k_pages[block_tables]`` — an O(T) HBM gather WRITE, then the
attention reads it back), which defeats the point of the paged layout.
This kernel consumes the paged pool *in place*:

  * the per-row block tables and positions ride SCALAR PREFETCH
    (``pltpu.PrefetchScalarGridSpec``): they are resident before the
    kernel body runs, so each grid step's K/V BlockSpec index map looks
    the row's next physical page up directly — the DMA streams blocks
    HBM -> VMEM straight out of the pool, and the gathered view never
    exists;
  * the grid is (B, kvH, n_logical_blocks) with the block axis
    innermost (sequential), so the online-softmax accumulators
    (``kernels/flash_attention.py``'s tiling) persist in VMEM scratch
    across a row's block stream — scores never leave VMEM either;
  * all matmuls accumulate f32 on the MXU (``preferred_element_type``),
    masked lanes are built from ``broadcasted_iota`` against the
    prefetched positions (static shapes, no dynamic slicing), and
    ``interpret=True`` runs the identical kernel on CPU for CI;
  * the index map CLAMPS past-the-end logical blocks to the row's last
    needed page: consecutive grid steps with identical block indices
    skip the re-fetch, so a short row in a long table does not stream
    garbage blocks (their compute is ``pl.when``-skipped too).

Per decode step per row this reads ``ceil((pos+S)/bs)`` K/V blocks once
— the same bytes the dense path reads, MINUS the O(T) gather write+read
round-trip, which at serving block counts is the majority of decode HBM
traffic (see docs/MFU_ROOFLINE.md "Decode roofline").

GQA: q arrives as (B, nH, S, D); kv heads serve ``G = nH // kvH`` query
heads each, and the kernel folds (G, S) into one (G*S, D) q tile per
(batch row, kv head) — the grouped form never expands K/V (the
decode-path HBM lever), and bigger q tiles pack the MXU better than
S=1 alone.

Forward-only (inference path; no vjp). Dispatch policy and mesh handling
live in ``bigdl_tpu.parallel.flash``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _mm, _sds

# Trace-time spy: bumped every time the kernel is TRACED into a program
# (once per compiled shape). Tests and tools/kernels_smoke.py assert the
# Pallas path actually built the program serving the traffic — execution
# itself never re-enters Python, so the trace is the observable event.
_TRACE_COUNT = 0


def trace_count() -> int:
    return _TRACE_COUNT


def _kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, bs, S, rows, nblk):
    b = pl.program_id(0)
    j = pl.program_id(2)   # logical-block index (sequential, innermost)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    # row b's valid history is 0 .. pos+S-1: later logical blocks hold
    # garbage (their pages were clamped away in the index map too)
    needed = j * bs <= pos + (S - 1)

    @pl.when(needed)
    def _compute():
        s = _mm(q_ref[0, 0], k_ref[0, 0], tb=True) * scale   # (rows, bs)
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        # q row r = g*S + s_off sits at global position pos + s_off —
        # causal-within-chunk + everything-before, per batch row
        s_off = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) % S
        s = jnp.where(col <= pos + s_off, s, NEG_INF)

        m_prev = m_ref[:, :1]                       # (rows, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)                      # (rows, bs)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _mm(p.astype(v_ref.dtype),
                                              v_ref[0, 0])
        m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)

    @pl.when(j == nblk - 1)
    def _finish():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)        # fully-masked rows → 0
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, positions,
                           scale: float | None = None,
                           interpret: bool = False, vma=None):
    """Attention over a paged KV pool, in place.

    q: (B, nH, S, D) queries at per-row positions
    ``positions[b] .. positions[b]+S-1`` (S=1 is the decode step, S>1
    the chunked-prefill / speculative-verify shapes); k_pages/v_pages:
    (num_blocks, kvH, block_size, D) pooled block storage, ALREADY
    holding this chunk's scattered K/V; block_tables: (B, max_blocks)
    int32 (0 = the engine's reserved null block); positions: (B,)
    int32. Returns (B, nH, S, D).

    Matches ``Attention.decode_paged``'s gathered-view einsum
    numerically (same masking domain; online-softmax ordering differs
    in the last ulps — greedy argmax absorbs it, the serving bitwise
    gate measures exactly that). ``vma``: varying mesh axes when the
    call sits inside a strict-VMA shard_map (TP serving)."""
    global _TRACE_COUNT
    B, nH, S, D = q.shape
    kvH, bs = k_pages.shape[1], k_pages.shape[2]
    nblk = block_tables.shape[1]
    if nH % kvH:
        raise ValueError(f"query heads {nH} not a multiple of kv heads "
                         f"{kvH}")
    G = nH // kvH
    rows = G * S
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    # kv-major head order, same as the dense grouped path: query head
    # h = k*G + g -> row g*S + s_off of kv head k's q tile
    qr = q.reshape(B, kvH, G, S, D).reshape(B, kvH, rows, D)
    tables = block_tables.astype(jnp.int32)
    pos = positions.astype(jnp.int32)

    def _k_map(b, h, j, tbl, p):
        # clamp past-the-end blocks to the last needed page: identical
        # consecutive indices skip the DMA re-fetch, so short rows never
        # stream the table's null-padded tail
        last = jnp.maximum(p[b] + (S - 1), 0) // bs
        return (tbl[b, jnp.minimum(j, last)], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, kvH, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, rows, D), lambda b, h, j, tbl, p:
                         (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D), _k_map),
            pl.BlockSpec((1, 1, bs, D), _k_map),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, D), lambda b, h, j, tbl, p:
                               (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, bs=bs, S=S,
                               rows=rows, nblk=nblk)
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_sds((B, kvH, rows, D), q.dtype, vma),
        interpret=interpret,
        name="paged_attention",
    )(tables, pos, qr, k_pages, v_pages)
    # bump only after the pallas trace SUCCEEDED: the spy must not count
    # a program that was never built (bench_serving's kernel arm and
    # chip_smoke.py read exactly this signal)
    _TRACE_COUNT += 1
    return o.reshape(B, kvH, G, S, D).reshape(B, nH, S, D)
