"""Pallas kernel tests — run the real kernel code via the interpreter on CPU.

The interpret-mode path executes the identical kernel bodies the TPU
compiles, so numerics (online softmax, causal masking, custom VJP) are
covered without hardware.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.kernels import (flash_attention_fused, flash_attention_qkv,
                               flash_attention_rows)
from bigdl_tpu.kernels.flash_attention import flash_chunk_attention
from bigdl_tpu.nn.attention import dot_product_attention
from utils import jaxpr_equations


def _ref(q, k, v, causal):
    mask = None
    if causal:
        t_q, t_kv = q.shape[-2], k.shape[-2]
        mask = jnp.where(np.tril(np.ones((t_q, t_kv), np.bool_))[None, None],
                         0.0, -1e30)
    return dot_product_attention(q, k, v, mask)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [128, 256])
def test_flash_forward_matches_einsum(causal, t):
    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(2, 3, t, 64).astype(np.float32))
               for _ in range(3)]
    out = flash_attention_fused(q, k, v, causal=causal, block_q=128,
                                block_k=128, interpret=True)
    ref = _ref(q, k, v, causal)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()


def test_flash_forward_unpadded_length():
    """T not a multiple of the block: padding + kv_len masking."""
    rng = np.random.RandomState(1)
    t = 200
    q, k, v = [jnp.asarray(rng.randn(1, 2, t, 32).astype(np.float32))
               for _ in range(3)]
    out = flash_attention_fused(q, k, v, causal=False, block_q=128,
                                block_k=128, interpret=True)
    ref = _ref(q, k, v, False)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_cross_attention_kv_longer():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 2, 128, 32).astype(np.float32))
    k, v = [jnp.asarray(rng.randn(1, 2, 384, 32).astype(np.float32))
            for _ in range(2)]
    out = flash_attention_fused(q, k, v, causal=False, block_q=128,
                                block_k=128, interpret=True)
    ref = _ref(q, k, v, False)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_einsum(causal):
    rng = np.random.RandomState(3)
    t = 256
    q, k, v = [jnp.asarray(rng.randn(1, 2, t, 32).astype(np.float32))
               for _ in range(3)]

    def loss_flash(q, k, v):
        o = flash_attention_fused(q, k, v, causal=causal, block_q=128,
                                  block_k=128, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_ref(q, k, v, causal)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        err = np.abs(np.asarray(a) - np.asarray(b)).max()
        assert err < 5e-4, f"d{name} err {err}"


@pytest.mark.parametrize("heads,d,t,rows", [
    (4, 64, 256, True),      # two heads a 128-lane block
    (2, 128, 256, True),     # one head a block
    (4, 64, 200, True),      # unpadded length
    (8, 32, 128, True),      # four heads a block
    (3, 64, 128, False),     # an odd head count: no block of whole heads
    (2, 80, 128, False),     # 80 lanes a head: nor here
])
def test_flash_rows_match_einsum(monkeypatch, heads, d, t, rows):
    """The ``[B, T, H*D]`` entry (``parallel.flash.flash_attention_rows``)
    against the einsum path, forward and the gradients of q, k, v. Where
    whole heads fill 128-lane blocks the kernels index the operands as
    they are (no transpose is traced); other shapes are split, go through
    the ``[B, H, T, D]`` entry and still match."""
    from bigdl_tpu.kernels.flash_attention import heads_per_block
    from bigdl_tpu.parallel import flash
    monkeypatch.setenv("BIGDL_TPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("BIGDL_TPU_FLASH_BLOCK_K", "128")
    rng = np.random.RandomState(6)
    q, k, v = [jnp.asarray(rng.randn(2, t, heads * d).astype(np.float32))
               for _ in range(3)]

    def loss(q, k, v):
        o = flash.flash_attention_rows(q, k, v, heads, causal=True)
        return jnp.sum(jnp.sin(o)), o

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
    monkeypatch.setenv("BIGDL_TPU_FLASH", "off")
    (_, o_ref), g_ref = grad(q, k, v)
    split = lambda x: x.reshape(2, t, heads, d).transpose(0, 2, 1, 3)
    assert np.allclose(np.asarray(split(o_ref)), np.asarray(
        _ref(split(q), split(k), split(v), True)), atol=2e-5)

    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    (_, o), g = grad(q, k, v)
    assert np.allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5), \
        np.abs(np.asarray(o) - np.asarray(o_ref)).max()
    for a, b, name in zip(g, g_ref, "qkv"):
        err = np.abs(np.asarray(a) - np.asarray(b)).max()
        assert err < 5e-4, f"d{name} err {err}"
    assert (heads_per_block(heads, d) is not None) == rows
    forward = jax.make_jaxpr(lambda q, k, v: loss(q, k, v)[1])(q, k, v)
    assert "name=flash_fwd" in str(forward)
    around = {e.primitive.name for e in jaxpr_equations(forward.jaxpr, closed=("pallas_call",))}
    assert ("transpose" not in around) == rows, forward


_THREE = ["flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"]


@pytest.mark.parametrize("attend,shape,stat,kernels", [
    # the fused projection, two 64-wide heads a lane block (g = 2)
    (lambda x: flash_attention_qkv(x, 4, causal=True, interpret=True),
     (2, 256, 3 * 4 * 64), (2, 2, 2, 256), _THREE),
    # rows of 128-wide heads, one a lane block (g = 1)
    (lambda x: flash_attention_rows(x, x, x, 2, causal=True, interpret=True),
     (2, 256, 2 * 128), (2, 2, 1, 256), _THREE),
    # split heads [B, H, T, D]: one head a row of [B * H, T, D]
    (lambda x: flash_attention_fused(x, x, x, causal=True, interpret=True),
     (2, 3, 256, 64), (6, 1, 1, 256), _THREE),
    # the serving prefill's chunk, forward only
    (lambda x: flash_chunk_attention(x[:, :, -128:], x, x, 128,
                                     interpret=True),
     (2, 3, 256, 64), (6, 1, 1, 128), ["flash_fwd"]),
], ids=["qkv", "rows", "heads", "chunk"])
def test_flash_statistics_cross_hbm_one_f32_a_row(attend, shape, stat,
                                                  kernels):
    """``lse`` and ``delta`` enter and leave every kernel of every entry
    as ``[N, heads // g, g, T]``, T on the lanes, and nothing around the
    kernels holds a row statistic repeated over a trailing 128."""
    x = jnp.ones(shape, jnp.float32)
    fn = lambda x: jnp.sum(attend(x))  # noqa: E731
    if len(kernels) > 1:
        fn = jax.value_and_grad(fn)
    eqns = list(jaxpr_equations(jax.make_jaxpr(fn)(x).jaxpr,
                                closed=("pallas_call",)))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == kernels
    for call in calls:
        io = call.outvars[1:] if call.params["name"] == "flash_fwd" \
            else call.invars[4:]
        assert [v.aval.shape for v in io] == [stat] * len(io), call
        assert all(v.aval.dtype == jnp.float32 for v in io)
    t = stat[-1]
    for eqn in eqns:
        for v in eqn.outvars:
            sh = v.aval.shape
            assert not (len(sh) == 4 and sh[-1] == 128 and sh[-2] >= t), eqn


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t_q,t_kv,block", [
    (200, 200, 128),     # T no multiple of the tile, two tiles a side
    (640, 640, 512),     # the step's own tile, T = 1.25 tiles
    (200, 640, 128),     # Tq != Tkv: more key blocks than query blocks
    (640, 200, 512),     # and fewer: one key tile, two query tiles
])
@pytest.mark.parametrize("heads,d", [(2, 64), (1, 128)], ids=["g2", "g1"])
def test_flash_rows_grads_ragged(heads, d, t_q, t_kv, block, causal):
    """Gradients of the rows entry against the einsum path where the
    statistics' tiles are padded (T no multiple of the tile) and where the
    two backward kernels walk different numbers of blocks (Tq != Tkv),
    causal and not, two heads a lane block and one."""
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(1, t_q, heads * d).astype(np.float32))
    k, v = [jnp.asarray(rng.randn(1, t_kv, heads * d).astype(np.float32))
            for _ in range(2)]
    split = lambda x: x.reshape(1, -1, heads, d).transpose(0, 2, 1, 3)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention_rows(
            q, k, v, heads, causal=causal, block_q=block, block_k=block,
            interpret=True)))

    def loss_ref(q, k, v):
        o = _ref(split(q), split(k), split(v), causal)
        return jnp.sum(jnp.sin(o))

    val, g = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    val_ref, g_ref = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(val) - float(val_ref)) < 2e-3 * max(1, abs(float(val_ref)))
    for a, b, name in zip(g, g_ref, "qkv"):
        err = np.abs(np.asarray(a) - np.asarray(b)).max()
        assert err < 5e-4, f"d{name} err {err}"


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [256, 200])
@pytest.mark.parametrize("heads,d", [(16, 64), (4, 64), (2, 128)])
def test_flash_qkv_is_rows_on_the_slices(monkeypatch, heads, d, t, causal):
    """The fused-projection entry (``parallel.flash.flash_attention_qkv``
    on ``[B, T, 3*H*D]``) against ``flash_attention_rows`` on the three
    slices of the same array: the same kernels on the same blocks, so the
    output and the gradient w.r.t. the fused array agree to the last bit;
    and the entry slices nothing."""
    from bigdl_tpu.parallel import flash
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    monkeypatch.setenv("BIGDL_TPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("BIGDL_TPU_FLASH_BLOCK_K", "128")
    c = heads * d
    qkv = jnp.asarray(np.random.RandomState(7).randn(1, t, 3 * c)
                      .astype(np.float32))

    def fused(x):
        o = flash.flash_attention_qkv(x, heads, causal=causal)
        return jnp.sum(jnp.sin(o)), o

    def sliced(x):
        o = flash.flash_attention_rows(x[..., :c], x[..., c:2 * c],
                                       x[..., 2 * c:], heads, causal=causal)
        return jnp.sum(jnp.sin(o)), o

    (_, o), g = jax.value_and_grad(fused, has_aux=True)(qkv)
    (_, o_ref), g_ref = jax.value_and_grad(sliced, has_aux=True)(qkv)
    assert o.shape == (1, t, c) and g.shape == qkv.shape
    assert np.array_equal(np.asarray(o), np.asarray(o_ref))
    assert np.array_equal(np.asarray(g), np.asarray(g_ref))
    assert float(jnp.abs(g[..., 2 * c:]).max()) > 0       # dv is there
    forward = str(jax.make_jaxpr(lambda x: fused(x)[1])(qkv))
    assert "name=flash_fwd" in forward
    assert " slice[" not in forward.split("pallas_call")[0], forward


def test_flash_entry_counters(monkeypatch):
    """``kernels/flash_qkv``, ``kernels/flash_rows`` and
    ``kernels/flash_heads`` count the flash calls BUILT on each entry: one
    bump a traced call, none for a cached program's next run, none on the
    einsum path, none while the observability is off. A self-attention
    ``Attention`` builds on the fused-projection entry alone; cross-
    attention, RoPE and grouped K/V modules never do."""
    from bigdl_tpu import observability as obs
    from bigdl_tpu.parallel import flash
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    rows = jnp.ones((1, 128, 128), jnp.float32)          # 2 heads of 64
    odd = jnp.ones((1, 128, 192), jnp.float32)           # 3 heads of 64
    heads = jnp.ones((1, 2, 128, 64), jnp.float32)
    count = lambda name: getattr(
        obs.registry().get(f"kernels/{name}"), "value", 0)
    flash.flash_attention_rows(rows, rows, rows, 2, causal=True)
    assert (count("flash_rows"), count("flash_heads")) == (0, 0)
    obs.enable()
    try:
        r0, h0 = count("flash_rows"), count("flash_heads")
        fn = jax.jit(lambda x: flash.flash_attention_rows(x, x, x, 2,
                                                          causal=True))
        fn(rows), fn(rows)                       # built once, run twice
        assert (count("flash_rows"), count("flash_heads")) == (r0 + 1, h0)
        flash.flash_attention(heads, heads, heads, causal=True)
        assert (count("flash_rows"), count("flash_heads")) == (r0 + 1,
                                                               h0 + 1)
        # no 128-lane block of whole heads: the (B, H, T, D) entry
        flash.flash_attention_rows(odd, odd, odd, 3, causal=True)
        assert (count("flash_rows"), count("flash_heads")) == (r0 + 1,
                                                               h0 + 2)
        # the modules: which entry an ``Attention`` builds its call on
        from bigdl_tpu import nn
        from bigdl_tpu.utils.table import Table
        x = jnp.ones((1, 128, 128), jnp.float32)
        built = lambda: tuple(count(n) for n in (  # noqa: E731
            "flash_qkv", "flash_rows", "flash_heads"))

        def run(attn, inp):
            params, _ = attn.init(jax.random.PRNGKey(0))
            attn.apply(params, {}, inp, training=False)

        z0, r1, h1 = built()
        run(nn.Attention(128, 2, causal=True), x)
        assert built() == (z0 + 1, r1, h1)
        run(nn.Attention(128, 2, causal=True), Table(x, x + 1.0))  # cross
        assert built() == (z0 + 1, r1 + 1, h1)
        run(nn.Attention(128, 2, causal=True, rope=True), x)
        assert built() == (z0 + 1, r1 + 1, h1 + 1)
        run(nn.Attention(128, 2, causal=True, num_kv_heads=1), x)
        assert built() == (z0 + 1, r1 + 1, h1 + 2)
        # the fused entry where no block of whole heads fills 128 lanes
        # (3 heads of 64): sliced, and through the (B, H, T, D) entry
        run(nn.Attention(192, 3, causal=True), odd)
        assert built() == (z0 + 1, r1 + 1, h1 + 3)
        monkeypatch.setenv("BIGDL_TPU_FLASH", "off")
        flash.flash_attention_rows(rows, rows, rows, 2, causal=True)
        flash.flash_attention_qkv(jnp.ones((1, 128, 384)), 2, causal=True)
        assert built() == (z0 + 1, r1 + 1, h1 + 3)
    finally:
        obs.disable()


def test_flash_bf16_runs():
    rng = np.random.RandomState(4)
    q, k, v = [jnp.asarray(rng.randn(1, 2, 128, 64)).astype(jnp.bfloat16)
               for _ in range(3)]
    out = flash_attention_fused(q, k, v, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _ref(q.astype(jnp.float32), k.astype(jnp.float32),
               v.astype(jnp.float32), True)
    assert np.allclose(np.asarray(out, np.float32), np.asarray(ref),
                       atol=5e-2)


def test_flash_dispatcher_interpret_env(monkeypatch):
    from bigdl_tpu.parallel import flash
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    rng = np.random.RandomState(5)
    q, k, v = [jnp.asarray(rng.randn(1, 1, 128, 16).astype(np.float32))
               for _ in range(3)]
    out = flash.flash_attention(q, k, v, causal=True)
    ref = _ref(q, k, v, True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_dispatcher_raises_when_the_kernel_throws(monkeypatch):
    """With the mode resolved to ``pallas`` (what the tpu platform
    resolves to) a kernel failure propagates: the dispatcher never logs
    and returns the einsum path under the kernel's name."""
    from bigdl_tpu.kernels import flash_attention as fk
    from bigdl_tpu.parallel import flash

    def boom(*a, **kw):
        raise RuntimeError("injected kernel failure")

    monkeypatch.setattr(flash, "flash_mode", lambda: "pallas")
    monkeypatch.setattr(fk, "flash_attention_fused", boom)
    monkeypatch.setattr(fk, "flash_chunk_attention", boom)
    monkeypatch.setattr(flash, "_einsum_attention",
                        lambda *a: pytest.fail("einsum ran behind a "
                                               "kernel failure"))
    monkeypatch.setattr(flash, "_einsum_chunk_attention",
                        lambda *a: pytest.fail("einsum ran behind a "
                                               "kernel failure"))
    q = k = v = jnp.ones((1, 1, 128, 16), jnp.float32)
    with pytest.raises(RuntimeError, match="injected kernel failure"):
        flash.flash_attention(q, k, v, causal=True)
    with pytest.raises(RuntimeError, match="injected kernel failure"):
        flash.flash_chunk_attention(q, k, v, q_offset=0)


def test_fused_matmul_forward_and_grads():
    from bigdl_tpu.kernels.fused_matmul import fused_bn_relu_matmul
    rng = np.random.RandomState(0)
    M, K, N = 160, 48, 72  # deliberately unpadded sizes
    x = jnp.asarray(rng.randn(M, K).astype(np.float32))
    w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.1)
    a = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(K).astype(np.float32))

    def ref(x, w, a, b):
        xh = jnp.maximum(x * a + b, 0.0)
        z = xh @ w
        return z, jnp.sum(z, 0), jnp.sum(z * z, 0)

    z, s1, s2 = fused_bn_relu_matmul(x, w, a, b, interpret=True)
    zr, s1r, s2r = ref(x, w, a, b)
    assert np.allclose(z, zr, atol=1e-4)
    assert np.allclose(s1, s1r, atol=1e-3)
    assert np.allclose(s2, s2r, atol=1e-2)

    def mk_loss(fwd):
        def loss(x, w, a, b):
            z, s1, s2 = fwd(x, w, a, b)
            mean = s1 / z.shape[0]
            var = s2 / z.shape[0] - mean ** 2
            zh = (z - mean) * jax.lax.rsqrt(var + 1e-5)
            return jnp.sum(jnp.tanh(zh * 0.3))
        return loss

    gf = jax.grad(mk_loss(lambda *aa: fused_bn_relu_matmul(
        *aa, interpret=True)), argnums=(0, 1, 2, 3))(x, w, a, b)
    gr = jax.grad(mk_loss(ref), argnums=(0, 1, 2, 3))(x, w, a, b)
    for name, f, r in zip("xwab", gf, gr):
        rel = float(jnp.abs(f - r).max()) / (float(jnp.abs(r).max()) + 1e-9)
        assert rel < 2e-4, (name, rel)


def test_fused_matmul_nhwc_forward_and_grads():
    """Layout-preserving (B,H,W,K) kernel == last-axis dot_general math —
    values, stats, and grads through the same BN-normalize loss as the
    flattened kernel's test."""
    from bigdl_tpu.kernels.fused_matmul import fused_bn_relu_matmul_nhwc
    rng = np.random.RandomState(0)
    B, H, W, K, N = 4, 6, 8, 16, 32
    x = jnp.asarray(rng.randn(B, H, W, K).astype(np.float32))
    w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.1)
    a = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(K).astype(np.float32))

    def ref(x, w, a, b):
        xh = jnp.maximum(x * a + b, 0.0)
        z = jax.lax.dot_general(xh, w, (((3,), (0,)), ((), ())))
        return z, jnp.sum(z, (0, 1, 2)), jnp.sum(z * z, (0, 1, 2))

    kern = lambda *aa: fused_bn_relu_matmul_nhwc(*aa, interpret=True)
    z, s1, s2 = kern(x, w, a, b)
    zr, s1r, s2r = ref(x, w, a, b)
    assert z.shape == (B, H, W, N)
    assert np.allclose(z, zr, atol=1e-4)
    assert np.allclose(s1, s1r, atol=1e-3)
    assert np.allclose(s2, s2r, atol=1e-2)

    def mk_loss(fwd):
        def loss(x, w, a, b):
            z, s1, s2 = fwd(x, w, a, b)
            m = B * H * W
            mean = s1 / m
            var = s2 / m - mean ** 2
            zh = (z - mean) * jax.lax.rsqrt(var + 1e-5)
            return jnp.sum(jnp.tanh(zh * 0.3))
        return loss

    gf = jax.grad(mk_loss(kern), argnums=(0, 1, 2, 3))(x, w, a, b)
    gr = jax.grad(mk_loss(ref), argnums=(0, 1, 2, 3))(x, w, a, b)
    for name, f, r in zip("xwab", gf, gr):
        rel = float(jnp.abs(f - r).max()) / (float(jnp.abs(r).max()) + 1e-9)
        assert rel < 2e-4, (name, rel)
    # non-dividing N falls back (caller handles None)
    wbad = jnp.asarray(rng.randn(K, 24).astype(np.float32))
    assert fused_bn_relu_matmul_nhwc(x, wbad, block_n=16,
                                     interpret=True) is None

    # genuinely multi-tile grid (nb=2, nh=2, nn=2): covers the cross-tile
    # accumulator init/finish guards (ib==0&&ih==0 / last-tile writes)
    # that the auto-fitted single-tile call above never exercises
    from bigdl_tpu.kernels.fused_matmul import _fused4
    zm, s1m, s2m = _fused4(x, w, a, b, True, True, B // 2, H // 2, N // 2,
                           True)
    assert np.allclose(zm, zr, atol=1e-4)
    assert np.allclose(s1m, s1r, atol=1e-3)
    assert np.allclose(s2m, s2r, atol=1e-2)
    gm = jax.grad(mk_loss(lambda *aa: _fused4(
        *aa, True, True, B // 2, H // 2, N // 2, True)),
        argnums=(0, 1, 2, 3))(x, w, a, b)
    for name, f, r in zip("xwab", gm, gr):
        rel = float(jnp.abs(f - r).max()) / (float(jnp.abs(r).max()) + 1e-9)
        assert rel < 2e-4, ("multi-tile", name, rel)


@pytest.mark.parametrize("B,H,W,K,N", [
    (1, 3, 5, 8, 16),     # tiny, odd spatial dims
    (2, 7, 7, 32, 8),     # stage-3-like spatial, N < K
    (3, 4, 1, 16, 32),    # W=1 (degenerate inner row)
    (5, 2, 6, 24, 48),    # B prime vs divisor search
])
def test_fused_matmul_nhwc_shape_matrix(B, H, W, K, N):
    """NHWC kernel == last-axis dot across a shape matrix (values only;
    grads covered by the dedicated test). Catches block-fit/index-map
    regressions the two fixed-shape tests can't."""
    from bigdl_tpu.kernels.fused_matmul import fused_bn_relu_matmul_nhwc
    rng = np.random.RandomState(B * 100 + N)
    x = jnp.asarray(rng.randn(B, H, W, K).astype(np.float32))
    w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.1)
    a = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(K).astype(np.float32))
    out = fused_bn_relu_matmul_nhwc(x, w, a, b, relu=True, stats=True,
                                    interpret=True)
    # every shape in the matrix tiles: a None here IS the fitter
    # regression this test exists to catch
    assert out is not None
    z, s1, s2 = out
    xh = jnp.maximum(x * a + b, 0.0)
    zr = jax.lax.dot_general(xh, w, (((3,), (0,)), ((), ())))
    assert np.allclose(z, zr, atol=1e-4), np.abs(z - zr).max()
    assert np.allclose(s1, jnp.sum(zr, (0, 1, 2)), atol=1e-3)
    assert np.allclose(s2, jnp.sum(zr * zr, (0, 1, 2)), atol=1e-2)


def test_fused_matmul_vmem_overflow_fallback(monkeypatch):
    """When even the smallest block size exceeds the VMEM footprint model,
    fused_bn_relu_matmul warns and computes the same math unfused (XLA) —
    values, stats, grads, dtype, and the stats=False tuple all match the
    kernel contract."""
    import warnings
    import bigdl_tpu.kernels.fused_matmul as fm
    rng = np.random.RandomState(3)
    M, K, N = 32, 16, 24
    x = jnp.asarray(rng.randn(M, K).astype(np.float32))
    w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.1)
    a = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(K).astype(np.float32))

    zk, s1k, s2k = fm.fused_bn_relu_matmul(x, w, a, b, interpret=True)

    def grads(fwd):
        def loss(x, w, a, b):
            z, s1, s2 = fwd(x, w, a, b)
            return (z * z).sum() + s1.sum() + (s2 * 0.1).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, a, b)

    gk = grads(lambda *t: fm.fused_bn_relu_matmul(*t, interpret=True))

    monkeypatch.setattr(fm, "_VMEM_BUDGET", 1)  # force the overflow branch
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        zf, s1f, s2f = fm.fused_bn_relu_matmul(x, w, a, b)
    assert any("falling" in str(r.message) for r in rec)
    assert zf.dtype == x.dtype and s1f.dtype == jnp.float32
    assert np.allclose(zf, zk, atol=1e-4)
    assert np.allclose(s1f, s1k, atol=1e-3)
    assert np.allclose(s2f, s2k, atol=1e-2)
    gf = grads(fm.fused_bn_relu_matmul)
    for gi, gj in zip(gk, gf):
        assert np.allclose(gi, gj, atol=1e-3), np.abs(gi - gj).max()

    # stats=False keeps the (z, zeros, zeros) tuple shape
    z0, s10, s20 = fm.fused_bn_relu_matmul(x, w, a, b, stats=False)
    assert s10.shape == (N,) and not s10.any() and not s20.any()

    # bf16 compute dtype stays bf16 through the fallback (f32 scale/bias)
    zb, s1b, _ = fm.fused_bn_relu_matmul(x.astype(jnp.bfloat16), w.astype(
        jnp.bfloat16), a, b)
    assert zb.dtype == jnp.bfloat16 and s1b.dtype == jnp.float32


def test_fused_matmul_nhwc_h_split_path(monkeypatch):
    """When no whole-batch block fits the VMEM budget the fitter splits H
    — force that path with a tiny budget and check values still match."""
    import bigdl_tpu.kernels.fused_matmul as fm
    B, H, W, K, N = 2, 6, 4, 16, 32
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, H, W, K).astype(np.float32))
    w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.1)
    # budget EXACTLY the (bb=1, bh=2) footprint — the fitter's _fits
    # compares with <=, so the search lands there and nowhere larger
    need = fm._vmem_need(1 * 2 * W, K, N, min(512, N), 4)
    monkeypatch.setattr(fm, "_VMEM_BUDGET", need)
    out = fm.fused_bn_relu_matmul_nhwc(x, w, relu=False, stats=True,
                                       interpret=True)
    assert out is not None     # None here = the fitter regressed
    z, s1, s2 = out
    zr = jax.lax.dot_general(x, w, (((3,), (0,)), ((), ())))
    assert np.allclose(z, zr, atol=1e-4)
    assert np.allclose(s1, jnp.sum(zr, (0, 1, 2)), atol=1e-3)
    assert np.allclose(s2, jnp.sum(zr * zr, (0, 1, 2)), atol=1e-2)


def test_fused_bottleneck_matches_reference_block(monkeypatch):
    """FusedBottleneck == the Sequential bottleneck with identical weights
    (fwd train+eval, running stats), and the interpret-mode Pallas path ==
    the jnp fallback in values and grads."""
    from bigdl_tpu.models.resnet import FusedBottleneck, bottleneck
    rng = np.random.RandomState(0)
    B, H, W, C = 2, 8, 8, 16
    x = jnp.asarray(rng.randn(B, H, W, C).astype(np.float32))
    monkeypatch.setenv("BIGDL_TPU_FLASH", "off")  # jnp fallback path

    for stride, nmid in ((1, 8), (2, 8)):
        fb = FusedBottleneck(C, nmid, stride)
        params, state = fb.init(jax.random.PRNGKey(0))
        ref = bottleneck(C, nmid, stride, 4, "B", False, "NHWC")
        rp, rs = ref.init(jax.random.PRNGKey(1))
        main_p, sc_p = rp["0"]["0"], rp["0"]["1"]

        def oihw(hwio):
            return jnp.asarray(np.transpose(hwio, (3, 2, 0, 1)))
        main_p["0"]["weight"] = oihw(params["w1"].reshape(1, 1, C, nmid))
        main_p["3"]["weight"] = oihw(np.asarray(params["w2"]))
        main_p["6"]["weight"] = oihw(params["w3"].reshape(1, 1, nmid,
                                                          4 * nmid))
        sc_p["0"]["weight"] = oihw(params["proj_w"].reshape(1, 1, C,
                                                            4 * nmid))
        for training in (True, False):
            out_f, st_f = fb.apply(params, state, x, training=training)
            out_r, st_r = ref.apply(rp, rs, x, training=training)
            assert np.allclose(np.asarray(out_f), np.asarray(out_r),
                               atol=2e-4)
            if training:
                assert np.allclose(
                    np.asarray(st_f["bn1"]["running_mean"]),
                    np.asarray(st_r["0"]["0"]["1"]["running_mean"]),
                    atol=1e-4)

    fb = FusedBottleneck(C, 8, 1)
    params, state = fb.init(jax.random.PRNGKey(0))

    def loss(p):
        out, _ = fb.apply(p, state, x, training=True)
        return jnp.sum(out * out) * 0.01

    l_jnp, g_jnp = jax.value_and_grad(loss)(params)
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")  # real kernel
    l_krn, g_krn = jax.value_and_grad(loss)(params)
    assert abs(float(l_jnp) - float(l_krn)) < 1e-3
    for va, vb in zip(jax.tree_util.tree_leaves(g_jnp),
                      jax.tree_util.tree_leaves(g_krn)):
        assert np.allclose(np.asarray(va), np.asarray(vb), atol=1e-3)


def test_fused_chain_kernel_forward_and_grads():
    """Cross-layer junction kernel (kernels/fused_chain.py) vs the jnp
    oracle: h/z_out/stats values and all five gradients, through a loss
    touching every output (interpret mode runs the real kernel bodies)."""
    from bigdl_tpu.kernels.fused_chain import (fused_residual_matmul_nhwc,
                                               residual_chain_reference)
    rng = np.random.RandomState(0)
    B, H, W, K, N = 2, 4, 4, 48, 24
    z = jnp.asarray(rng.randn(B, H, W, K).astype(np.float32))
    r = jnp.asarray(rng.randn(B, H, W, K).astype(np.float32))
    a = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(K).astype(np.float32))
    w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.1)

    h, zo, s1, s2 = fused_residual_matmul_nhwc(z, r, w, a, b,
                                               interpret=True)
    hr, zor, s1r, s2r = residual_chain_reference(z, r, a, b, w)
    assert np.allclose(h, hr, atol=1e-5)
    assert np.allclose(zo, zor, atol=1e-4)
    assert np.allclose(s1, s1r, atol=1e-3)
    assert np.allclose(s2, s2r, atol=1e-2)

    def mk_loss(fn):
        def loss(z, r, a, b, w):
            h, zo, s1, s2 = fn(z, r, a, b, w)
            m = B * H * W
            mean = s1 / m
            var = s2 / m - mean ** 2
            zh = (zo - mean) * jax.lax.rsqrt(var + 1e-5)
            return jnp.sum(jnp.tanh(zh * 0.3)) + 0.5 * jnp.sum(jnp.sin(h))
        return loss

    gk = jax.grad(mk_loss(lambda z, r, a, b, w: fused_residual_matmul_nhwc(
        z, r, w, a, b, interpret=True)), argnums=(0, 1, 2, 3, 4))(
            z, r, a, b, w)
    gr = jax.grad(mk_loss(residual_chain_reference),
                  argnums=(0, 1, 2, 3, 4))(z, r, a, b, w)
    for name, f, x in zip("zrabw", gk, gr):
        rel = float(jnp.abs(f - x).max()) / (float(jnp.abs(x).max()) + 1e-9)
        assert rel < 2e-4, (name, rel)


def test_fused_bottleneck_chain_matches_sequential_blocks(monkeypatch):
    """FusedBottleneckChain == the same FusedBottleneck blocks run
    sequentially with identical params (train+eval values, running
    stats, grads); the interpret-mode chain kernel == the jnp fallback."""
    from bigdl_tpu.models.resnet import (FusedBottleneck,
                                         FusedBottleneckChain)
    rng = np.random.RandomState(0)
    B, H, W, C, nmid = 2, 8, 8, 16, 8
    x = jnp.asarray(rng.randn(B, H, W, C).astype(np.float32))
    blocks = [FusedBottleneck(C, nmid, stride=2),
              FusedBottleneck(4 * nmid, nmid),
              FusedBottleneck(4 * nmid, nmid)]
    chain = FusedBottleneckChain(blocks)
    params, state = chain.init(jax.random.PRNGKey(0))

    def sequential(params, state, x, training):
        h, sts = x, {}
        for i, blk in enumerate(blocks):
            h, sts[str(i)] = blk.apply(params[str(i)], state[str(i)], h,
                                       training=training)
        return h, sts

    monkeypatch.setenv("BIGDL_TPU_FLASH", "off")   # jnp composition
    for training in (True, False):
        out_c, st_c = chain.apply(params, state, x, training=training)
        out_s, st_s = sequential(params, state, x, training)
        assert np.allclose(np.asarray(out_c), np.asarray(out_s),
                           atol=2e-4), training
        if training:
            assert np.allclose(
                np.asarray(st_c["1"]["bn1"]["running_mean"]),
                np.asarray(st_s["1"]["bn1"]["running_mean"]), atol=1e-4)

    def loss(p, training=True):
        out, _ = chain.apply(p, state, x, training=training)
        return jnp.sum(out * out) * 0.01

    l_jnp, g_jnp = jax.value_and_grad(loss)(params)
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")  # real kernels
    l_krn, g_krn = jax.value_and_grad(loss)(params)
    assert abs(float(l_jnp) - float(l_krn)) < 1e-3
    for va, vb in zip(jax.tree_util.tree_leaves(g_jnp),
                      jax.tree_util.tree_leaves(g_krn)):
        assert np.allclose(np.asarray(va), np.asarray(vb), atol=1e-3)
    # eval-mode interpret path (stats=False arm of the kernel)
    out_e, _ = chain.apply(params, state, x, training=False)
    monkeypatch.setenv("BIGDL_TPU_FLASH", "off")
    out_o, _ = chain.apply(params, state, x, training=False)
    assert np.allclose(np.asarray(out_e), np.asarray(out_o), atol=2e-4)


def test_resnet50_fused_chain_builds_and_runs(monkeypatch):
    """ResNet(fused='pallas') assembles FusedBottleneckChain stages by
    default; BIGDL_TPU_FUSED_CHAIN=0 (the A/B control arm) keeps
    per-block modules; BOTH run (jnp fallback) and agree with the same
    weights."""
    from bigdl_tpu.models.resnet import ResNet, FusedBottleneckChain
    monkeypatch.setenv("BIGDL_TPU_FLASH", "off")
    m = ResNet(10, 50, format="NHWC", fused="pallas")
    chains = [mod for mod in m.modules
              if isinstance(mod, FusedBottleneckChain)]
    assert len(chains) == 4 and [len(c.blocks) for c in chains] == \
        [3, 4, 6, 3]
    monkeypatch.setenv("BIGDL_TPU_FUSED_CHAIN", "0")
    m0 = ResNet(10, 50, format="NHWC", fused="pallas")
    assert not any(isinstance(mod, FusedBottleneckChain)
                   for mod in m0.modules)

    x = jnp.asarray(
        np.random.RandomState(0).randn(1, 64, 64, 3).astype(np.float32))
    params, state = m.init(jax.random.PRNGKey(0))
    # remap the chained trees (stage chains hold {j: block}) onto the
    # flat per-block Sequential of the control arm
    p0, s0, k = {}, {}, 0
    for i, mod in enumerate(m.modules):
        if isinstance(mod, FusedBottleneckChain):
            for j in range(len(mod.blocks)):
                p0[str(k)] = params[str(i)][str(j)]
                s0[str(k)] = state[str(i)][str(j)]
                k += 1
        else:
            p0[str(k)] = params[str(i)]
            s0[str(k)] = state[str(i)]
            k += 1
    assert k == len(m0.modules)
    out, _ = m.apply(params, state, x, training=False)
    out0, _ = m0.apply(p0, s0, x, training=False)
    assert out.shape == (1, 10)
    assert np.allclose(np.asarray(out), np.asarray(out0), atol=2e-4)


def test_fused_conv3x3_kernel_forward_and_grads():
    """Fused BN+ReLU+3x3-conv+stats kernel (kernels/fused_conv.py) vs the
    jnp oracle at strides 1 and 2 — values and all four gradients."""
    from bigdl_tpu.kernels.fused_conv import (fused_bn_relu_conv3x3,
                                              conv3x3_reference)
    rng = np.random.RandomState(0)
    for stride in (1, 2):
        B, H, W, K, N = 2, 8, 8, 16, 24
        x = jnp.asarray(rng.randn(B, H, W, K).astype(np.float32))
        w = jnp.asarray(rng.randn(3, 3, K, N).astype(np.float32) * 0.1)
        a = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(K).astype(np.float32))
        z, s1, s2 = fused_bn_relu_conv3x3(x, w, a, b, stride=stride,
                                          interpret=True)
        zr, s1r, s2r = conv3x3_reference(x, w, a, b, stride)
        assert np.allclose(z, zr, atol=1e-4)
        assert np.allclose(s1, s1r, atol=1e-3)
        assert np.allclose(s2, s2r, atol=1e-2)

        def mk_loss(fn):
            def loss(x, w, a, b):
                z, s1, s2 = fn(x, w, a, b)
                m = z.shape[0] * z.shape[1] * z.shape[2]
                mean = s1 / m
                var = s2 / m - mean ** 2
                zh = (z - mean) * jax.lax.rsqrt(var + 1e-5)
                return jnp.sum(jnp.tanh(zh * 0.3))
            return loss

        gk = jax.grad(mk_loss(
            lambda x, w, a, b: fused_bn_relu_conv3x3(
                x, w, a, b, stride=stride, interpret=True)),
            argnums=(0, 1, 2, 3))(x, w, a, b)
        gr = jax.grad(mk_loss(
            lambda x, w, a, b: conv3x3_reference(x, w, a, b, stride)),
            argnums=(0, 1, 2, 3))(x, w, a, b)
        for name, f, r in zip("xwab", gk, gr):
            rel = (float(jnp.abs(f - r).max())
                   / (float(jnp.abs(r).max()) + 1e-9))
            assert rel < 2e-4, (stride, name, rel)


def test_fused_bottleneck_conv2_arm_matches(monkeypatch):
    """BIGDL_TPU_FUSED_CONV2=1 routes conv2 through the fused kernel with
    identical results (fwd train+eval, grads) vs the default path."""
    from bigdl_tpu.models.resnet import FusedBottleneck
    from bigdl_tpu.kernels.fused_conv import fused_bn_relu_conv3x3
    rng = np.random.RandomState(0)
    B, H, W, C, nmid = 2, 8, 8, 16, 8
    x = jnp.asarray(rng.randn(B, H, W, C).astype(np.float32))
    # guard against vacuous pass: the kernel must actually ENGAGE at the
    # bottleneck's z1 shape (a VMEM-fitter regression returning None
    # would silently compare the default path with itself)
    probe = fused_bn_relu_conv3x3(
        jnp.zeros((B, H, W, nmid), jnp.float32),
        jnp.zeros((3, 3, nmid, nmid), jnp.float32),
        jnp.ones((nmid,), jnp.float32), jnp.zeros((nmid,), jnp.float32),
        stride=1, interpret=True)
    assert probe is not None
    for stride in (1, 2):
        fb = FusedBottleneck(C, nmid, stride)
        params, state = fb.init(jax.random.PRNGKey(0))
        monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
        monkeypatch.delenv("BIGDL_TPU_FUSED_CONV2", raising=False)

        def loss(p):
            out, _ = fb.apply(p, state, x, training=True)
            return jnp.sum(out * out) * 0.01

        out_d, st_d = fb.apply(params, state, x, training=True)
        l_d, g_d = jax.value_and_grad(loss)(params)
        monkeypatch.setenv("BIGDL_TPU_FUSED_CONV2", "1")
        out_f, st_f = fb.apply(params, state, x, training=True)
        l_f, g_f = jax.value_and_grad(loss)(params)
        assert np.allclose(np.asarray(out_d), np.asarray(out_f),
                           atol=2e-4)
        assert np.allclose(
            np.asarray(st_d["bn2"]["running_mean"]),
            np.asarray(st_f["bn2"]["running_mean"]), atol=1e-4)
        assert abs(float(l_d) - float(l_f)) < 1e-3
        for va, vb in zip(jax.tree_util.tree_leaves(g_d),
                          jax.tree_util.tree_leaves(g_f)):
            assert np.allclose(np.asarray(va), np.asarray(vb), atol=1e-3)
        # eval arm
        oe_f, _ = fb.apply(params, state, x, training=False)
        monkeypatch.delenv("BIGDL_TPU_FUSED_CONV2")
        oe_d, _ = fb.apply(params, state, x, training=False)
        assert np.allclose(np.asarray(oe_f), np.asarray(oe_d), atol=2e-4)


@pytest.mark.parametrize("q_offset,s,t", [
    (0, 128, 128),      # degenerate: plain causal self-attention
    (128, 128, 256),    # mid-cache chunk, aligned
    (100, 60, 160),     # ragged chunk and offset (padding + iota masks)
])
def test_flash_chunk_attention_matches_einsum(q_offset, s, t):
    """Rectangular-causal chunk kernel (prefill_chunked's attention):
    q rows at global positions q_offset.. over a t-long valid cache
    prefix, row r attending cols <= q_offset + r."""
    from bigdl_tpu.kernels.flash_attention import flash_chunk_attention

    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 2, s, 64).astype(np.float32))
    k, v = [jnp.asarray(rng.randn(2, 2, t, 64).astype(np.float32))
            for _ in range(2)]
    out = flash_chunk_attention(q, k, v, q_offset, block_q=128,
                                block_k=128, interpret=True)
    mask = jnp.where(
        jnp.arange(t)[None, :] <= q_offset + jnp.arange(s)[:, None],
        0.0, -1e30)[None, None]
    ref = dot_product_attention(q, k, v, mask)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()


def test_prefill_chunked_uses_chunk_kernel(monkeypatch):
    """Integration: prefill_chunked through the interpret-mode Pallas
    chunk kernel equals one-shot prefill (the flash path engages at
    S >= 8 with static offsets) — and a spy proves the kernel path
    actually ran (a dispatch-guard regression falling back to einsum
    would otherwise pass silently)."""
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.parallel import flash as flash_mod

    calls = []
    real = flash_mod.flash_chunk_attention
    monkeypatch.setattr(
        flash_mod, "flash_chunk_attention",
        lambda *a, **kw: (calls.append(1), real(*a, **kw))[1])
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    model = TransformerLM(vocab_size=43, hidden_size=32, num_heads=2,
                          filter_size=64, num_layers=2, max_len=64)
    params, _ = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(1).randint(1, 43, (2, 24)),
                      jnp.int32)
    lg_a, ca = model.prefill(params, ids, 32)
    lg_b, cb = model.prefill_chunked(params, ids, 32, chunk=8)
    np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b),
                               rtol=2e-4, atol=2e-4)
    nxt = jnp.argmax(lg_a, -1).astype(jnp.int32)
    oa, _ = model.decode_one(params, nxt, 24, ca)
    ob, _ = model.decode_one(params, nxt, 24, cb)
    np.testing.assert_allclose(np.asarray(oa), np.asarray(ob),
                               rtol=2e-4, atol=2e-4)
    # 24 tokens / chunk 8 = 3 chunks x 2 layers dispatched to the kernel
    assert len(calls) == 6, len(calls)
