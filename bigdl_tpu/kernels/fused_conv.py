"""Fused BN-apply + ReLU + 3x3 conv (+ stats epilogue) Pallas kernel.

The one elementwise HBM pass left inside the fused bottleneck after
``fused_matmul`` (1x1 convs) and ``fused_chain`` (junctions): BN1's
normalize+ReLU must materialise ``xh1`` because the 3x3 conv needs a
spatial tensor (models/resnet.py ``_body``), and BN2's statistics re-read
``z2``. This kernel folds both into the conv itself:

  * prologue: ``xh = relu(x * a + b)`` on the streamed input tile
    (``x`` is conv1's raw output; its BN affine comes from the stats
    epilogue of the producing kernel — the same pipelining contract as
    ``fused_matmul``);
  * 3x3 conv as an in-register im2col: pad H/W by 1 in VMEM, stack the
    9 taps along the channel axis ((rows, 9K) — 9x the contraction
    depth, BETTER MXU lane packing than K=64 alone), one MXU matmul
    against the (9K, N) reshaped weights; stride 2 takes every other
    output row/col at trace time (static shapes);
  * epilogue: per-channel sum / sum-of-squares of ``z2`` accumulated in
    VMEM scratch — BN2's batch statistics without re-reading ``z2``.

Tiles are whole (H, W) planes over a batch sub-block — ResNet's spatial
planes are small (56x56x64 bf16 = 400 KB), so no H halo exchange is
needed and the padding lives entirely in VMEM.

The backward is plain XLA under ``jax.custom_vjp``: it recomputes ``xh``
from the saved ``x`` (one fused elementwise chain) and takes dgrad/wgrad
through ``jax.vjp`` of the reference conv, with the stats-gradient
injection ``dz_eff = dz + ds1 + 2*z*ds2`` applied first — the forward's
HBM savings (no xh1 write, no z2 stats pass) are kept; the backward
matches today's cost. Used by ``models/resnet.py`` FusedBottleneck when
``BIGDL_TPU_FUSED_CONV2=1`` (off by default until an on-chip A/B records
a verdict — ROADMAP Speed item 4).

Reference analog: mkldnn's conv post-ops fuse the PRECEDING conv's
epilogue; fusing the consumer conv's PROLOGUE is the TPU-shaped dual
(the MXU wants deep contractions, so im2col-stacking taps is free win).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_matmul import _mm, _VMEM_BUDGET, _divisors_desc


def _conv_ref(xh, w, stride):
    return lax.conv_general_dilated(
        xh, w, window_strides=(stride, stride), padding=((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def conv3x3_reference(x, w, a, b, stride=1, stats=True):
    """Plain-jnp oracle with identical math."""
    xh = jnp.maximum(x.astype(jnp.float32) * a.astype(jnp.float32)
                     + b.astype(jnp.float32), 0.0).astype(x.dtype)
    z = _conv_ref(xh, w, stride)
    if stats:
        zf = z.astype(jnp.float32)
        return z, jnp.sum(zf, (0, 1, 2)), jnp.sum(zf * zf, (0, 1, 2))
    return z, None, None


def _im2col9(xh, stride):
    """(bb, H+2, W+2, K) padded plane → (bb*H2*W2, 9K) tap stack."""
    bb, Hp, Wp, K = xh.shape
    H, W = Hp - 2, Wp - 2
    H2, W2 = (H + stride - 1) // stride, (W + stride - 1) // stride
    taps = []
    for dy in range(3):
        for dx in range(3):
            win = xh[:, dy:dy + H:stride, dx:dx + W:stride, :]
            taps.append(win.reshape(bb * H2 * W2, K))
    return jnp.concatenate(taps, axis=1), H2, W2


def _cvfwd_kernel(x_ref, w_ref, a_ref, b_ref, z_ref, s1_ref, s2_ref,
                  acc1, acc2, *, nb, stride, stats):
    ib = pl.program_id(0)

    if stats:
        @pl.when(ib == 0)
        def _init():
            acc1[:] = jnp.zeros_like(acc1)
            acc2[:] = jnp.zeros_like(acc2)

    xb = x_ref[...]
    bb, H, W, K = xb.shape
    xh = jnp.maximum(
        xb.astype(jnp.float32) * a_ref[...].reshape(K).astype(jnp.float32)
        + b_ref[...].reshape(K).astype(jnp.float32), 0.0).astype(xb.dtype)
    xh = jnp.pad(xh, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols, H2, W2 = _im2col9(xh, stride)
    z = _mm(cols, w_ref[...])                    # (rows, N) f32 accum
    z_ref[...] = z.reshape(bb, H2, W2, -1).astype(z_ref.dtype)

    if stats:
        acc1[:] += jnp.sum(z, axis=0, keepdims=True)
        acc2[:] += jnp.sum(z * z, axis=0, keepdims=True)

        @pl.when(ib == nb - 1)
        def _finish():
            s1_ref[...] = acc1[:]
            s2_ref[...] = acc2[:]


def _cvfwd(x, w, a, b, stride, stats, block_b, interpret):
    B, H, W, K = x.shape
    N = w.shape[-1]
    H2, W2 = (H + stride - 1) // stride, (W + stride - 1) // stride
    nb = B // block_b
    w9 = w.reshape(9 * K, N)
    a2, b2 = a.reshape(1, K), b.reshape(1, K)

    kernel = functools.partial(_cvfwd_kernel, nb=nb, stride=stride,
                               stats=stats)
    z, s1, s2 = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, H, W, K), lambda ib: (ib, 0, 0, 0)),
            pl.BlockSpec((9 * K, N), lambda ib: (0, 0)),
            pl.BlockSpec((1, K), lambda ib: (0, 0)),
            pl.BlockSpec((1, K), lambda ib: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, H2, W2, N), lambda ib: (ib, 0, 0, 0)),
            pl.BlockSpec((1, N), lambda ib: (0, 0)),
            pl.BlockSpec((1, N), lambda ib: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H2, W2, N), x.dtype),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, N), jnp.float32),
                        pltpu.VMEM((1, N), jnp.float32)],
        interpret=interpret,
    )(x, w9, a2, b2)
    return z, s1[0], s2[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _cv(x, w, a, b, stride, stats, block_b, interpret):
    return _cvfwd(x, w, a, b, stride, stats, block_b, interpret)


def _cv_fwd(x, w, a, b, stride, stats, block_b, interpret):
    z, s1, s2 = _cvfwd(x, w, a, b, stride, stats, block_b, interpret)
    return (z, s1, s2), (x, w, a, b, z if stats else None)


def _cv_bwd(stride, stats, block_b, interpret, res, grads):
    x, w, a, b, z = res
    dz, ds1, ds2 = grads
    af, bf = a.astype(jnp.float32), b.astype(jnp.float32)
    if stats:
        dz = (dz.astype(jnp.float32)
              + ds1.astype(jnp.float32)
              + 2.0 * z.astype(jnp.float32) * ds2.astype(jnp.float32))
    dz = dz.astype(x.dtype)
    u = x.astype(jnp.float32) * af + bf
    xh = jnp.maximum(u, 0.0).astype(x.dtype)
    _, vjp = jax.vjp(lambda xh_, w_: _conv_ref(xh_, w_, stride), xh, w)
    dxh, dw = vjp(dz)
    g = jnp.where(u > 0.0, dxh.astype(jnp.float32), 0.0)
    dx = (g * af).astype(x.dtype)
    da = jnp.sum(g * x.astype(jnp.float32), (0, 1, 2)).astype(a.dtype)
    db = jnp.sum(g, (0, 1, 2)).astype(b.dtype)
    return dx, dw, da, db


_cv.defvjp(_cv_fwd, _cv_bwd)


def _conv_vmem_need(rows, H, W, K, N, eb):
    """x tile + padded xh + 9K im2col + z out (+ double buffering on the
    grid-varying x/z blocks). Channels count at the 128-lane tile width
    they occupy in VMEM: at K=N=64 (ResNet-50 stage 0) the unpadded model
    read 12.9 MB where Mosaic allocated 19.3 MB and refused the kernel."""
    K, N = -(-K // 128) * 128, -(-N // 128) * 128
    xpad = rows // (H * W) * (H + 2) * (W + 2) * K * eb
    return (2 * rows * (K * eb + N * eb) + xpad + rows * 9 * K * eb
            + 9 * K * N * eb + rows * N * 4)


def fused_bn_relu_conv3x3(x, w, scale, bias, *, stride=1, stats=True,
                          interpret=False):
    """relu(x*scale + bias) → 3x3 conv (padding 1) → (z, s1, s2).

    x: (B, H, W, K) NHWC; w: (3, 3, K, N) HWIO; stride 1 or 2. Returns
    None when no batch sub-block fits the VMEM budget — callers fall
    back to the unfused epilogue + lax.conv pair.
    """
    B, H, W, K = x.shape
    N = w.shape[-1]
    eb = x.dtype.itemsize

    pick = None
    for bb in _divisors_desc(B, 32):
        if _conv_vmem_need(bb * H * W, H, W, K, N, eb) <= _VMEM_BUDGET:
            pick = bb
            break
    if pick is None:
        return None
    z, s1, s2 = _cv(x, w, scale, bias, int(stride), bool(stats),
                    int(pick), bool(interpret))
    # stats=False leaves the stat outputs unwritten — never hand callers
    # uninitialized memory (the oracle returns None there too)
    return (z, s1, s2) if stats else (z, None, None)
