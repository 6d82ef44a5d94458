"""Mamba-2's mixer and its chunked scan (``nn/ssm.py``) on the CPU against
the state recurrence taken one position at a time, forward and gradients.

The recurrence here is written from the equations (``S_t = exp(dt_t A) S_{t-1}
+ dt_t x_t B_t^T``, ``y_t = S_t C_t``), independent of the chunked
algorithm; both run in float32 at the highest matmul precision. They differ
by float32 rounding alone: the chunked form sums the decays as differences
of cumulative sums and orders its additions otherwise, ~1e-6 of the output's
scale at these sizes. The tolerance, 2e-5 of the scale, leaves ten times
that; the same scan with its inputs rounded to bfloat16 (the precision
below the one the layer states) reads ~1e-3 and fails it, which each test
checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.nn.ssm import causal_conv, ssd_scan

TOL = 2e-5
DT_RANGES = {"small": (1e-4, 1e-3), "large": (0.1, 2.0), "mixed": (1e-4, 2.0)}


def recurrence(x, dt, A, B, C):
    b, T, nh, P = x.shape
    hg = nh // B.shape[2]
    Bh, Ch = jnp.repeat(B, hg, axis=2), jnp.repeat(C, hg, axis=2)

    def step(S, inp):
        x_t, dt_t, b_t, c_t = inp
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t)

    S0 = jnp.zeros((b, nh, P, B.shape[3]))
    _, y = jax.lax.scan(step, S0, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (x, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1)


def _inputs(seed, T=52, dt_range="mixed", b=2, nh=4, P=8, g=2, N=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    lo, hi = DT_RANGES[dt_range]
    dt = jnp.exp(jax.random.uniform(k[1], (b, T, nh), minval=np.log(lo),
                                    maxval=np.log(hi)))
    A = -jnp.exp(jax.random.uniform(k[2], (nh,), maxval=np.log(16.0)))
    return (jax.random.normal(k[0], (b, T, nh, P)), dt, A,
            jax.random.normal(k[3], (b, T, g, N)),
            jax.random.normal(k[4], (b, T, g, N)))


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _bf16(args):
    return [a.astype(jnp.bfloat16).astype(jnp.float32) for a in args]


@pytest.mark.parametrize("dt_range", sorted(DT_RANGES))
def test_chunked_scan_is_the_recurrence_across_chunk_edges(dt_range):
    """52 positions in chunks of 16: four chunks (the last padded), so the
    state crosses three chunk edges."""
    args = _inputs(1, dt_range=dt_range)
    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
        got = ssd_scan(*args, 16)
        low = ssd_scan(*_bf16(args), 16)
    assert got.shape == want.shape
    assert _gap(got, want) <= TOL
    assert _gap(low, want) > TOL


@pytest.mark.parametrize("dt_range", sorted(DT_RANGES))
def test_chunked_scan_gradients_are_the_recurrences(dt_range):
    args = _inputs(2, T=48, dt_range=dt_range)
    w = jax.random.normal(jax.random.PRNGKey(3), args[0].shape)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * w)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(recurrence), argnums=range(5))(*args)
        got = jax.grad(loss(lambda *a: ssd_scan(*a, 16)),
                       argnums=range(5))(*args)
        low = jax.grad(loss(lambda *a: ssd_scan(*a, 16)),
                       argnums=range(5))(*_bf16(args))
    for name, g, h, lo in zip("x dt A B C".split(), got, want, low):
        assert _gap(g, h) <= TOL, name
    assert max(_gap(lo, h) for lo, h in zip(low, want)) > TOL


def test_the_chunk_is_tiling_only():
    """One chunk holding the whole row, or chunks of 8: the same outputs."""
    args = _inputs(4, T=64)
    with jax.default_matmul_precision("highest"):
        whole, tiled = ssd_scan(*args, 64), ssd_scan(*args, 8)
    assert _gap(tiled, whole) <= TOL


def test_causal_conv_is_a_depthwise_convolution_over_the_past():
    x = np.random.default_rng(0).normal(size=(2, 9, 5)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(4, 5)).astype(np.float32)
    bias = np.arange(5, dtype=np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for k in range(4):
            s = t - 3 + k
            if s >= 0:
                want[:, t] += w[k] * x[:, s]
    np.testing.assert_allclose(causal_conv(x, w, bias), want + bias,
                               rtol=1e-6, atol=1e-6)


def _plain_mixer(p, x, layer):
    """The mixer's equations with the recurrence above in place of the
    chunked scan."""
    nh, P, g, N = (layer.num_heads, layer.head_dim, layer.n_groups,
                   layer.state_size)
    b, T, _ = x.shape
    inner = nh * P
    out = x @ p["in_proj"]
    z, xbc, dt = (out[..., :inner], out[..., inner:2 * inner + 2 * g * N],
                  out[..., 2 * inner + 2 * g * N:])
    K = p["conv_weight"].shape[0]
    xp = jnp.pad(xbc, [(0, 0), (K - 1, 0), (0, 0)])
    xbc = jax.nn.silu(sum(xp[:, k:k + T] * p["conv_weight"][k]
                          for k in range(K)) + p["conv_bias"])
    xs = xbc[..., :inner].reshape(b, T, nh, P)
    B = xbc[..., inner:inner + g * N].reshape(b, T, g, N)
    C = xbc[..., inner + g * N:].reshape(b, T, g, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(xs, dt, -jnp.exp(p["A_log"]), B, C) \
        + p["D"][:, None] * xs
    y = (y.reshape(b, T, inner) * jax.nn.silu(z)).reshape(b, T, g, -1)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + 1e-5)
    return (y.reshape(b, T, inner) * p["norm"]["weight"]) @ p["out_proj"]


def test_the_mixer_and_its_gradients_are_the_equations():
    layer = nn.Mamba2Mixer(16, 4, 8, 2, 16, chunk_size=16)
    p = layer._init_params(jax.random.PRNGKey(5))
    p = jax.tree_util.tree_map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(6),
                                               a.shape), p)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 40, 16))
    w = jax.random.normal(jax.random.PRNGKey(8), (2, 40, 16))
    with jax.default_matmul_precision("highest"):
        (got, grads) = jax.value_and_grad(
            lambda p: jnp.sum(layer.apply(p, {}, x)[0] * w))(p)
        (want, wgrads) = jax.value_and_grad(
            lambda p: jnp.sum(_plain_mixer(p, x, layer) * w))(p)
    assert float(got) == pytest.approx(float(want), rel=TOL)
    for (path, g), h in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(wgrads)):
        assert _gap(g, h) <= TOL, path


def test_the_scan_runs_under_its_own_scope():
    layer = nn.Mamba2Mixer(16, 4, 8, 2, 16, chunk_size=8)
    p = layer._init_params(jax.random.PRNGKey(0))
    x = jnp.ones((1, 16, 16))
    text = jax.jit(lambda p, x: layer.apply(p, {}, x, scope="ssm")[0]) \
        .lower(p, x).as_text(debug_info=True)
    assert "ssm/ssd/" in text
    with pytest.raises(ValueError, match="n_groups"):
        nn.Mamba2Mixer(16, 3, 8, 2, 16)
