"""Ring attention with flash-kernel local blocks and a hand-derived
ring backward.

The production long-context path: combines the two memory techniques —
sequence sharding over the mesh (``ring_attention.py``) and the Pallas
flash kernel within each block (``kernels/flash_attention.py``). Each
device holds T/n of Q, K, V; K/V blocks rotate via ``ppermute`` while the
per-block (output, logsumexp) pairs merge with the numerically-stable
log-sum-exp combination.

Backward is NOT autodiff-through-scan (which would save every block's
probabilities): it is the flash-attention-2 recomputation written as a
second ring pass — dK/dV accumulators *travel with* their K/V blocks
around the ring and arrive home after n hops, while dQ accumulates
locally (f32 accumulators, cast once on return). Residuals are only
(q, k, v, o, lse). On TPU both passes run the Pallas kernels, so memory
is O(T/n) per device in forward AND backward; the einsum path (CPU, or
``BIGDL_TPU_FLASH=off``) materialises one (T/n)² block at a time.

Dispatch honors the same ``BIGDL_TPU_FLASH`` policy as
``parallel/flash.py``: ``off`` forces einsum, ``interpret`` runs the
Pallas kernels in the interpreter (CPU tests exercise the kernel path),
and any kernel failure falls back to einsum with a logged warning —
never silently.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.compat import axis_size, pvary

# ONE shared dispatch policy (parallel/flash.py) and the kernels' own
# masking constant — no second copy to drift
from .flash import flash_mode as _block_mode
from ..kernels.flash_attention import NEG_INF


# ---------------------------------------------------------------------------
# per-block forward / backward (pluggable kernel)
# ---------------------------------------------------------------------------


def _block_attn_einsum(q, kb, vb, scale, causal_diag):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kb).astype(jnp.float32) * scale
    if causal_diag:
        t, tk = q.shape[-2], kb.shape[-2]
        mask = jnp.arange(t)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd",
                   (p / jnp.maximum(l, 1e-30)).astype(q.dtype), vb)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    return o, lse


def _block_attn(q, kb, vb, scale, diag: bool, causal: bool, axes=None):
    """(o, lse) for one K/V block. ``diag`` — block holds the same global
    positions as q (triangular mask applies). ``axes``: mesh axes the
    blocks vary over (a bare string means one axis)."""
    axes = _as_axes(axes)
    use_causal = causal and diag
    mode = _block_mode()
    if mode in ("pallas", "interpret"):
        from ..kernels.flash_attention import _flash_fwd
        return _flash_fwd(q, kb, vb, use_causal, scale, 512, 512,
                          mode == "interpret",
                          vma=set(axes) if axes else None)
    return _block_attn_einsum(q, kb, vb, scale, use_causal)


def _block_bwd_einsum(q, kb, vb, lse, delta, do, scale, causal_diag):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kb).astype(jnp.float32) * scale
    if causal_diag:
        t, tk = q.shape[-2], kb.shape[-2]
        mask = jnp.arange(t)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jnp.exp(s - lse[..., None])
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, do.astype(jnp.float32))
    dp = jnp.einsum("bhqd,bhkd->bhqk", do.astype(jnp.float32),
                    vb.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kb.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq, dk, dv


def _block_bwd(q, kb, vb, o, lse, delta, do, scale, diag: bool,
               causal: bool, axes=None):
    """One block's (dq, dk, dv) contributions, f32, from GLOBAL (o, lse)
    and precomputed GLOBAL delta = rowsum(dO*O) (hoisted out of the ring
    scan — it is hop-invariant)."""
    axes = _as_axes(axes)
    use_causal = causal and diag
    mode = _block_mode()
    if mode in ("pallas", "interpret"):
        from ..kernels.flash_attention import _flash_bwd
        # out_dtype=f32: per-hop contributions must not round at the
        # input dtype before the ring accumulators sum them
        return _flash_bwd(use_causal, scale, 512, 512,
                          mode == "interpret", (q, kb, vb, o, lse), do,
                          delta=delta, out_dtype=jnp.float32,
                          vma=set(axes) if axes else None)
    return _block_bwd_einsum(q, kb, vb, lse, delta, do, scale, use_causal)


# ---------------------------------------------------------------------------
# ring forward / backward
# ---------------------------------------------------------------------------


def _as_axes(axes):
    """Normalize an axis spec: bare string -> 1-tuple; None/tuple pass."""
    return (axes,) if isinstance(axes, str) else axes


def _vary(x, axes):
    """Mark a fresh constant as varying over ``axes`` (strict-VMA
    shard_map requires cond branches / scan carries to agree)."""
    return pvary(x, axes)


def _vma_axes(x, ring_axis):
    """The FULL set of mesh axes ``x`` varies over inside this shard_map.
    Under a composed mesh (e.g. dp x sp) the blocks vary over more than
    the ring axis, and every fresh constant / kernel output must carry
    the same set or strict-VMA cond/scan typing rejects the program."""
    vma = jax.typeof(x).vma
    if vma:
        return tuple(sorted(vma))
    return (ring_axis,) if ring_axis else ()


def _merge(o, lse, o_i, lse_i):
    new_lse = jnp.logaddexp(lse, lse_i)
    w = jnp.exp(lse - new_lse)[..., None].astype(o.dtype)
    w_i = jnp.exp(lse_i - new_lse)[..., None].astype(o.dtype)
    return o * w + o_i * w_i, new_lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ring_flash_attention(q, k, v, axis: str = "seq",
                         causal: bool = False):
    """q, k, v: (B, H, Tblock, D) local blocks inside ``shard_map``."""
    o, lse = _ring_fwd(q, k, v, axis, causal)
    return o


def _ring_fwd(q, k, v, axis, causal):
    n = axis_size(axis)
    idx = lax.axis_index(axis)
    vaxes = _vma_axes(q, axis)
    scale = 1.0 / math.sqrt(q.shape[-1])
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        k_blk, v_blk, o, lse = carry
        src = (idx - s) % n  # whose block I hold this step
        if causal:
            b, h, tb, d = q.shape
            zeros = (jnp.zeros_like(q),
                     _vary(jnp.full((b, h, tb), NEG_INF, jnp.float32),
                           vaxes))
            # later blocks fully invisible: skip the compute entirely;
            # diagonal needs the triangular mask; earlier fully visible
            o_i, lse_i = lax.cond(
                src > idx,
                lambda: zeros,
                lambda: lax.cond(
                    src == idx,
                    lambda: _block_attn(q, k_blk, v_blk, scale, True,
                                        True, vaxes),
                    lambda: _block_attn(q, k_blk, v_blk, scale, False,
                                        True, vaxes)))
        else:
            o_i, lse_i = _block_attn(q, k_blk, v_blk, scale, False, False,
                                     vaxes)
        o, lse = _merge(o, lse, o_i, lse_i.astype(lse.dtype))
        k_next = lax.ppermute(k_blk, axis, perm)
        v_next = lax.ppermute(v_blk, axis, perm)
        return (k_next, v_next, o, lse), None

    b, h, tb, _ = q.shape
    o0 = jnp.zeros_like(q)
    lse0 = _vary(jnp.full((b, h, tb), NEG_INF, jnp.float32), vaxes)
    (k_f, v_f, o, lse), _ = lax.scan(step, (k, v, o0, lse0),
                                     jnp.arange(n))
    return o, lse


def _ring_vjp_fwd(q, k, v, axis, causal):
    o, lse = _ring_fwd(q, k, v, axis, causal)
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(axis, causal, res, do):
    """Second ring pass: dK/dV ride along with their K/V blocks; dQ stays.

    Flash-attention-2 recomputation from global (o, lse) — each block's
    contribution is independent given them, so on TPU the per-block work
    is the Pallas backward kernels themselves."""
    q, k, v, o, lse = res
    n = axis_size(axis)
    idx = lax.axis_index(axis)
    vaxes = _vma_axes(q, axis)
    scale = 1.0 / math.sqrt(q.shape[-1])
    perm = [(i, (i + 1) % n) for i in range(n)]
    # hop-invariant: compute the global rowsum(dO*O) once, not per hop
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)

    def step(carry, s):
        k_blk, v_blk, dk_blk, dv_blk, dq = carry
        src = (idx - s) % n
        zeros = (jnp.zeros_like(dq), jnp.zeros_like(dk_blk),
                 jnp.zeros_like(dv_blk))
        if causal:
            dq_i, dk_i, dv_i = lax.cond(
                src > idx,
                lambda: zeros,
                lambda: lax.cond(
                    src == idx,
                    lambda: _block_bwd(q, k_blk, v_blk, o, lse, delta, do,
                                       scale, True, True, vaxes),
                    lambda: _block_bwd(q, k_blk, v_blk, o, lse, delta, do,
                                       scale, False, True, vaxes)))
        else:
            dq_i, dk_i, dv_i = _block_bwd(q, k_blk, v_blk, o, lse, delta,
                                          do, scale, False, False, vaxes)
        dq = dq + dq_i
        dk_blk = dk_blk + dk_i
        dv_blk = dv_blk + dv_i
        k_next = lax.ppermute(k_blk, axis, perm)
        v_next = lax.ppermute(v_blk, axis, perm)
        dk_next = lax.ppermute(dk_blk, axis, perm)
        dv_next = lax.ppermute(dv_blk, axis, perm)
        return (k_next, v_next, dk_next, dv_next, dq), None

    init = (k, v, _vary(jnp.zeros(k.shape, jnp.float32), vaxes),
            _vary(jnp.zeros(v.shape, jnp.float32), vaxes),
            _vary(jnp.zeros(q.shape, jnp.float32), vaxes))
    (k_f, v_f, dk, dv, dq), _ = lax.scan(step, init, jnp.arange(n))
    # after n hops every dK/dV block is back on its owner; cast once
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_flash_attention.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def make_ring_flash_attention(mesh, axis: str = "seq",
                              causal: bool = False):
    """shard_mapped ring-flash attention over (B, H, T, D) global arrays."""
    from ..utils.compat import shard_map
    from jax.sharding import PartitionSpec as P
    spec = P(None, None, axis, None)
    return shard_map(
        functools.partial(ring_flash_attention, axis=axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
