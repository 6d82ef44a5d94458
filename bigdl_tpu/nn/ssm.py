"""State-space layers: Mamba-2's mixer and its chunked scan (SSD).

TPU-first addition beyond the reference (BigDL 0.x has no state-space
layer). The scan is Mamba-2's state-space duality (Dao & Gu 2024): inside
a chunk of ``chunk_size`` positions the recurrence is a masked quadratic
form computed as batched matrix products on the MXU; between chunks the
states pass through the same recurrence over the chunks, a parallel
(associative) scan of ``T / chunk_size`` steps in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .module import Module


def ssd_scan(x, dt, A, B, C, chunk: int):
    """The selective scan of Mamba-2 by chunks, for heads in groups.

    x ``[b, T, heads, P]``, dt ``[b, T, heads]`` (positive), A ``[heads]``
    (negative), B and C ``[b, T, groups, N]``; head ``h`` reads group ``h //
    (heads / groups)``. Per head, with a ``[P, N]`` state from zero::

        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,    y_t = S_t C_t

    returns y ``[b, T, heads, P]``. A length that is no multiple of
    ``chunk`` is padded with ``dt = 0`` (no decay, no input)."""
    b, T, nh, P = x.shape
    g, N = B.shape[2], B.shape[3]
    hg = nh // g
    pad = -T % chunk
    if pad:
        widen = lambda a: jnp.pad(a, [(0, 0), (0, pad)]  # noqa: E731
                                  + [(0, 0)] * (a.ndim - 2))
        x, dt, B, C = map(widen, (x, dt, B, C))
    c, L = (T + pad) // chunk, chunk
    x = x.reshape(b, c, L, g, hg, P)
    dt = dt.reshape(b, c, L, g, hg)
    B, C = B.reshape(b, c, L, g, N), C.reshape(b, c, L, g, N)
    xd = x * dt[..., None]
    # log-decays summed along the chunk: [b, c, g, hg, L]
    cs = jnp.cumsum(jnp.moveaxis(dt * A.reshape(g, hg), 2, -1), axis=-1)
    # inside a chunk: (C B^T ∘ exp(cs_l - cs_s), s <= l) X
    causal = np.tril(np.ones((L, L), bool))
    decay = jnp.exp(jnp.where(causal, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))
    cb = jnp.einsum("bclgn,bcsgn->bcgls", C, B)
    y = jnp.einsum("bcghls,bcsghp->bclghp", cb[:, :, :, None] * decay, xd)
    # each chunk's own state at its end, then the states entering the
    # chunks: s_c = exp(cs_c[-1]) s_{c-1} + own_c
    to_end = jnp.moveaxis(jnp.exp(cs[..., -1:] - cs), -1, 2)[..., None]
    own = jnp.einsum("bclgn,bclghp->bcghpn", B, xd * to_end)

    def chain(u, v):
        (a1, s1), (a2, s2) = u, v
        return a1 * a2, a2[..., None, None] * s1 + s2

    _, ends = jax.lax.associative_scan(chain, (jnp.exp(cs[..., -1]), own),
                                       axis=1)
    enter = jnp.concatenate([jnp.zeros_like(ends[:, :1]), ends[:, :-1]], 1)
    from_start = jnp.moveaxis(jnp.exp(cs), -1, 2)[..., None]
    y = y + jnp.einsum("bclgn,bcghpn->bclghp", C, enter) * from_start
    return y.reshape(b, c * L, nh, P)[:, :T]


def causal_conv(x, weight, bias):
    """Depthwise causal convolution over time: x ``[b, T, channels]``,
    weight ``[K, channels]``; ``y_t = sum_k weight_k x_{t - K + 1 + k} +
    bias`` with zeros before the start."""
    K, T = weight.shape[0], x.shape[1]
    xp = jnp.pad(x, [(0, 0), (K - 1, 0), (0, 0)])
    return sum(xp[:, k:k + T] * weight[k] for k in range(K)) + bias


class Mamba2Mixer(Module):
    """Mamba-2's mixer (``nemotron_h`` / ``mamba2`` configs), without
    biases but the convolution's::

        [z, xBC, dt] = x W_in      (z, x: heads * head_dim; B, C: groups * N)
        xBC = silu(causal_conv(xBC) + b_conv)
        dt  = softplus(dt + dt_bias),  A = -exp(A_log)
        y   = SSD(x, dt, A, B, C) + D x              (:func:`ssd_scan`)
        y   = RMSNorm_groups(y * silu(z)) * w_norm   (over each group's
                                                      heads * head_dim / groups)
        out = y W_out

    A share of the heads (a tensor-parallel slice of whole groups) is the
    same layer with fewer heads and groups: its ``out`` is that share's
    part of the sum ``W_out`` makes. The scan runs under the scope
    ``ssd``; the rest under whatever scope the caller applies it in."""

    def __init__(self, hidden_size: int, num_heads: int, head_dim: int,
                 n_groups: int, state_size: int, conv_kernel: int = 4,
                 chunk_size: int = 128, norm_eps: float = 1e-5, name=None):
        super().__init__(name=name)
        if num_heads % n_groups:
            raise ValueError(f"n_groups ({n_groups}) must divide num_heads "
                             f"({num_heads})")
        self.hidden_size, self.num_heads, self.head_dim = (
            hidden_size, num_heads, head_dim)
        self.n_groups, self.state_size = n_groups, state_size
        self.conv_kernel, self.chunk_size = conv_kernel, chunk_size
        self.norm_eps = norm_eps

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.n_groups * self.state_size

    def _init_params(self, rng):
        k = jax.random.split(rng, 5)
        H, nh = self.hidden_size, self.num_heads
        s = 1.0 / math.sqrt(self.conv_kernel)
        dt = jnp.exp(jax.random.uniform(k[3], (nh,), minval=math.log(1e-3),
                                        maxval=math.log(1e-1)))
        return {"in_proj": 0.02 * jax.random.normal(
                    k[0], (H, self.inner + self.conv_dim + nh)),
                "conv_weight": jax.random.uniform(
                    k[1], (self.conv_kernel, self.conv_dim), minval=-s,
                    maxval=s),
                "conv_bias": jnp.zeros((self.conv_dim,)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
                "D": jnp.ones((nh,)),
                "norm": {"weight": jnp.ones((self.inner,))},
                "out_proj": 0.02 * jax.random.normal(k[4], (self.inner, H))}

    def gated_norm(self, y, z, weight):
        """``RMSNorm_groups(y * silu(z)) * weight`` over ``[..., inner]``:
        each group's ``inner / n_groups`` channels normed alone."""
        h = (y * jax.nn.silu(z)).astype(jnp.float32)
        h = h.reshape(*h.shape[:-1], self.n_groups, -1)
        h = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), axis=-1, keepdims=True)
                              + self.norm_eps)
        return (h.reshape(y.shape) * weight).astype(y.dtype)

    def _apply(self, params, state, x, training, rng):
        b, T, _ = x.shape
        nh, P, g, N = (self.num_heads, self.head_dim, self.n_groups,
                       self.state_size)
        zxbcdt = x @ params["in_proj"]
        z = zxbcdt[..., :self.inner]
        xbc = zxbcdt[..., self.inner:self.inner + self.conv_dim]
        dt = zxbcdt[..., self.inner + self.conv_dim:]
        xbc = jax.nn.silu(causal_conv(xbc, params["conv_weight"],
                                      params["conv_bias"]))
        xs = xbc[..., :self.inner].reshape(b, T, nh, P)
        Bm = xbc[..., self.inner:self.inner + g * N].reshape(b, T, g, N)
        Cm = xbc[..., self.inner + g * N:].reshape(b, T, g, N)
        dt = jax.nn.softplus(dt + params["dt_bias"])
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        with jax.named_scope("ssd"):
            y = ssd_scan(xs, dt, A, Bm, Cm, self.chunk_size)
            y = y + params["D"][:, None] * xs
        y = self.gated_norm(y.reshape(b, T, self.inner), z,
                            params["norm"]["weight"])
        return y @ params["out_proj"]
