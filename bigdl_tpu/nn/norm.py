"""Normalization layers.

Parity: reference ``nn/BatchNormalization.scala``,
``nn/SpatialBatchNormalization.scala``, ``nn/LayerNormalization.scala``,
``nn/SpatialCrossMapLRN.scala``, ``nn/SpatialWithinChannelLRN.scala``,
``nn/Normalize.scala``, ``nn/NormalizeScale.scala``,
``nn/SpatialContrastiveNormalization.scala``,
``nn/SpatialDivisiveNormalization.scala``,
``nn/SpatialSubtractiveNormalization.scala``, ``nn/Masking.scala``.

BatchNorm running stats live in module *state* (non-trainable collection) and
the new state is returned from ``apply`` — the pure-functional analog of the
reference's mutable runningMean/runningVar buffers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .module import Module


class BatchNormalization(Module):
    """BN over (B, C) input; reduce over batch dim (nn/BatchNormalization.scala).

    momentum semantics match the reference: running = (1-m)*running + m*batch.
    """

    _channel_axis = 1

    def __init__(self, n_output: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True, init_weight=None, init_bias=None,
                 name=None):
        super().__init__(name=name)
        self.n_output = n_output
        self.eps, self.momentum, self.affine = eps, momentum, affine
        self.init_weight, self.init_bias = init_weight, init_bias

    def _init_params(self, rng):
        if not self.affine:
            return {}
        w = (jnp.asarray(self.init_weight) if self.init_weight is not None
             else jnp.ones((self.n_output,)))
        b = (jnp.asarray(self.init_bias) if self.init_bias is not None
             else jnp.zeros((self.n_output,)))
        return {"weight": w, "bias": b}

    def _init_state(self):
        return {"running_mean": jnp.zeros((self.n_output,)),
                "running_var": jnp.ones((self.n_output,))}

    def _apply(self, params, state, x, training, rng):
        ch = self._channel_axis % x.ndim  # -1 (NHWC) → last axis
        ax = tuple(i for i in range(x.ndim) if i != ch)
        bshape = [1] * x.ndim
        bshape[ch] = self.n_output
        xf = x.astype(jnp.float32)  # stats always in f32 (bf16-safe)
        if training:
            # shifted one-pass stats: E[(x−s)²]−E[x−s]² with s = the running
            # mean (stop-gradient, free — no extra pass over x). One fused
            # read of the activation, vs jnp.var's two dependent passes (a
            # second full HBM sweep per BN layer, profiled ~20% of the
            # ResNet-50 step); the shift keeps the subtraction from
            # catastrophically cancelling when activation means are large
            # relative to their spread (plain E[x²]−E[x]² loses precision at
            # mean ≫ std even in f32). f32 accumulation keeps it bf16-safe.
            shift = lax.stop_gradient(
                state["running_mean"].astype(jnp.float32))
            xs = xf - shift.reshape(bshape)
            m1 = jnp.mean(xs, axis=ax)
            var = jnp.maximum(jnp.mean(jnp.square(xs), axis=ax)
                              - jnp.square(m1), 0.0)
            mean = m1 + shift
            n = x.size // self.n_output
            unbiased = var * n / max(n - 1, 1)
            new_state = {
                "running_mean": (1 - self.momentum) * state["running_mean"]
                + self.momentum * mean,
                "running_var": (1 - self.momentum) * state["running_var"]
                + self.momentum * unbiased,
            }
        else:
            mean, var = state["running_mean"], state["running_var"]
            new_state = state
        inv = lax.rsqrt(var + self.eps)
        y = (x - mean.reshape(bshape)) * inv.reshape(bshape)
        if self.affine:
            y = y * params["weight"].reshape(bshape) + \
                params["bias"].reshape(bshape)
        # keep activation dtype (bf16 flows through; stats stay f32)
        return y.astype(x.dtype), new_state


class SpatialBatchNormalization(BatchNormalization):
    """Per-channel BN over NCHW or NHWC (nn/SpatialBatchNormalization.scala;
    ``data_format`` mirrors the reference's DataFormat param)."""

    def __init__(self, n_output, eps=1e-5, momentum=0.1, affine=True,
                 init_weight=None, init_bias=None, data_format="NCHW",
                 name=None):
        super().__init__(n_output, eps, momentum, affine, init_weight,
                         init_bias, name=name)
        assert data_format in ("NCHW", "NHWC"), data_format
        if data_format == "NHWC":
            self._channel_axis = -1


class VolumetricBatchNormalization(BatchNormalization):
    """BN over NCDHW, per-channel."""


class LayerNormalization(Module):
    """LayerNorm over the last dim (nn/LayerNormalization.scala)."""

    def __init__(self, hidden_size: int, eps: float = 1e-6, name=None):
        super().__init__(name=name)
        self.hidden_size, self.eps = hidden_size, eps

    def _init_params(self, rng):
        return {"weight": jnp.ones((self.hidden_size,)),
                "bias": jnp.zeros((self.hidden_size,))}

    def _apply(self, params, state, x, training, rng):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        y = (x - mean) * lax.rsqrt(var + self.eps)
        return y * params["weight"] + params["bias"]


class RMSNorm(Module):
    """Root-mean-square norm over the last dim, a weight and no offset
    (Zhang & Sennrich 2019): ``x / sqrt(mean(x^2) + eps) * weight``. The
    statistics are taken in float32 whatever the input's dtype."""

    def __init__(self, hidden_size: int, eps: float = 1e-6, name=None):
        super().__init__(name=name)
        self.hidden_size, self.eps = hidden_size, eps

    def _init_params(self, rng):
        return {"weight": jnp.ones((self.hidden_size,))}

    def _apply(self, params, state, x, training, rng):
        xf = x.astype(jnp.float32)
        y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + self.eps)
        return (y * params["weight"]).astype(x.dtype)


class SpatialCrossMapLRN(Module):
    """AlexNet-style LRN across channels (nn/SpatialCrossMapLRN.scala):
    y = x / (k + alpha/n * sum_{nearby c} x^2)^beta."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75,
                 k: float = 1.0, name=None):
        super().__init__(name=name)
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k

    def _apply(self, params, state, x, training, rng):
        sq = jnp.square(x)
        half = (self.size - 1) // 2
        extra = self.size - 1 - half
        s = lax.reduce_window(sq, 0.0, lax.add, (1, self.size, 1, 1),
                              (1, 1, 1, 1),
                              [(0, 0), (half, extra), (0, 0), (0, 0)])
        denom = jnp.power(self.k + (self.alpha / self.size) * s, self.beta)
        return x / denom


class SpatialWithinChannelLRN(Module):
    """LRN within each channel over a spatial window
    (nn/SpatialWithinChannelLRN.scala)."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75,
                 name=None):
        super().__init__(name=name)
        self.size, self.alpha, self.beta = size, alpha, beta

    def _apply(self, params, state, x, training, rng):
        sq = jnp.square(x)
        half = (self.size - 1) // 2
        extra = self.size - 1 - half
        s = lax.reduce_window(sq, 0.0, lax.add, (1, 1, self.size, self.size),
                              (1, 1, 1, 1),
                              [(0, 0), (0, 0), (half, extra), (half, extra)])
        denom = jnp.power(1.0 + (self.alpha / (self.size * self.size)) * s,
                          self.beta)
        return x / denom


class Normalize(Module):
    """Lp-normalise over feature dim (nn/Normalize.scala)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10, name=None):
        super().__init__(name=name)
        self.p, self.eps = p, eps

    def _norm(self, x):
        if np.isinf(self.p):
            n = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        elif self.p == 2.0:
            n = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True))
        else:
            n = jnp.power(jnp.sum(jnp.power(jnp.abs(x), self.p), axis=1,
                                  keepdims=True), 1.0 / self.p)
        return n

    def _apply(self, params, state, x, training, rng):
        return x / (self._norm(x) + self.eps)


class NormalizeScale(Module):
    """L2-normalise channels then scale by a learnable per-channel weight
    (nn/NormalizeScale.scala — SSD's conv4_3 norm)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10, scale: float = 1.0,
                 size=None, w_regularizer=None, name=None):
        super().__init__(name=name)
        self.p, self.eps, self.scale = p, eps, scale
        self.size = tuple(size) if size is not None else None

    def _init_params(self, rng):
        return {"weight": jnp.full(self.size, self.scale)}

    def _apply(self, params, state, x, training, rng):
        n = jnp.power(jnp.sum(jnp.power(jnp.abs(x), self.p), axis=1,
                              keepdims=True), 1.0 / self.p)
        y = x / (n + self.eps)
        w = params["weight"]
        if w.ndim < x.ndim:
            w = w.reshape((1,) * (x.ndim - w.ndim) + w.shape)
        return y * w


def _gaussian_2d(size):
    k = np.arange(size) - (size - 1) / 2.0
    g = np.exp(-(k ** 2) / (2.0 * (size / 4.0) ** 2))
    g2 = np.outer(g, g)
    return (g2 / g2.sum()).astype(np.float32)


class SpatialSubtractiveNormalization(Module):
    """Subtract weighted local mean (nn/SpatialSubtractiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None, name=None):
        super().__init__(name=name)
        self.n_input_plane = n_input_plane
        self.kernel = (np.asarray(kernel, np.float32) if kernel is not None
                       else _gaussian_2d(9))
        if self.kernel.ndim == 1:
            self.kernel = np.outer(self.kernel, self.kernel)
        self.kernel = self.kernel / self.kernel.sum()

    def _local_mean(self, x):
        kh, kw = self.kernel.shape
        w = jnp.asarray(self.kernel)[None, None].repeat(self.n_input_plane, 0)
        mean = lax.conv_general_dilated(
            x, w, (1, 1), [(kh // 2, (kh - 1) // 2), (kw // 2, (kw - 1) // 2)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=self.n_input_plane)
        # edge correction: divide by the actual kernel mass inside the image
        ones = jnp.ones_like(x[:, :1])
        mass = lax.conv_general_dilated(
            ones, jnp.asarray(self.kernel)[None, None], (1, 1),
            [(kh // 2, (kh - 1) // 2), (kw // 2, (kw - 1) // 2)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return mean / mass

    def _apply(self, params, state, x, training, rng):
        return x - self._local_mean(x)


class SpatialDivisiveNormalization(Module):
    """Divide by local std (nn/SpatialDivisiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4, name=None):
        super().__init__(name=name)
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.threshold, self.thresval = threshold, thresval

    def _apply(self, params, state, x, training, rng):
        local_var = self.sub._local_mean(jnp.square(x))
        local_std = jnp.sqrt(jnp.maximum(local_var, 0.0))
        mean_std = jnp.mean(local_std, axis=(2, 3), keepdims=True)
        denom = jnp.maximum(local_std, mean_std)
        denom = jnp.where(denom < self.threshold, self.thresval, denom)
        return x / denom


class SpatialContrastiveNormalization(Module):
    """Subtractive then divisive (nn/SpatialContrastiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4, name=None):
        super().__init__(name=name)
        self.subn = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.divn = SpatialDivisiveNormalization(n_input_plane, kernel,
                                                 threshold, thresval)

    def _apply(self, params, state, x, training, rng):
        y, _ = self.subn.apply({}, {}, x, training, rng)
        y, _ = self.divn.apply({}, {}, y, training, rng)
        return y


class Masking(Module):
    """Zero out timesteps equal to mask_value (nn/Masking.scala)."""

    def __init__(self, mask_value: float = 0.0, name=None):
        super().__init__(name=name)
        self.mask_value = mask_value

    def _apply(self, params, state, x, training, rng):
        keep = jnp.any(x != self.mask_value, axis=-1, keepdims=True)
        return x * keep.astype(x.dtype)
