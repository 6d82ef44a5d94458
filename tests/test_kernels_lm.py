"""LM-level kernel-integration tests (flash path in the model, remat
equivalence, chunked CE loss) — split from test_kernels.py so xdist
loadfile sharding overlaps these compile-heavy checks with the rest."""
import contextlib
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

def _tiny_lm(**kw):
    from bigdl_tpu.models import TransformerLM
    return TransformerLM(vocab_size=97, hidden_size=32, num_heads=2,
                         filter_size=64, num_layers=2, max_len=64, **kw)


def test_lm_flash_path_matches_einsum(monkeypatch):
    """LM logits with the kernel (interpret) == einsum reference path."""
    import jax
    ids = jnp.asarray(np.random.RandomState(0).randint(
        1, 97, size=(2, 64)).astype(np.int32))
    model = _tiny_lm(use_flash=True)
    params, _ = model.init(jax.random.PRNGKey(0))
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    out_kernel, _ = model.apply(params, {}, ids, training=False)
    monkeypatch.setenv("BIGDL_TPU_FLASH", "off")
    out_einsum, _ = model.apply(params, {}, ids, training=False)
    ref_model = _tiny_lm(use_flash=False)
    out_ref, _ = ref_model.apply(params, {}, ids, training=False)
    assert np.allclose(np.asarray(out_kernel), np.asarray(out_ref), atol=2e-4)
    assert np.allclose(np.asarray(out_einsum), np.asarray(out_ref), atol=1e-5)


def test_lm_remat_matches_plain():
    """remat=True changes memory, not values — fwd and grads identical."""
    import jax
    ids = jnp.asarray(np.random.RandomState(1).randint(
        1, 97, size=(2, 32)).astype(np.int32))
    plain = _tiny_lm(use_flash=False, remat=False)
    remat = _tiny_lm(use_flash=False, remat=True)
    params, _ = plain.init(jax.random.PRNGKey(0))

    def loss(m):
        def f(p):
            out, _ = m.apply(p, {}, ids, training=False)
            return jnp.sum(jnp.tanh(out * 0.01))
        return f

    l0, g0 = jax.value_and_grad(loss(plain))(params)
    l1, g1 = jax.value_and_grad(loss(remat))(params)
    assert np.allclose(float(l0), float(l1), atol=1e-6)
    flat0 = jax.tree_util.tree_leaves(g0)
    flat1 = jax.tree_util.tree_leaves(g1)
    for a, b in zip(flat0, flat1):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_moe_lm_remat_matches_plain():
    """MoE LM remat=True changes memory, not values — fwd (incl. the
    router aux loss) and grads identical through BOTH block types."""
    import jax
    from bigdl_tpu.models import MoETransformerLM
    ids = jnp.asarray(np.random.RandomState(1).randint(
        1, 67, size=(2, 16)).astype(np.int32))

    def build(remat):
        return MoETransformerLM(vocab_size=67, hidden_size=32, num_heads=2,
                                filter_size=64, num_layers=2, n_experts=4,
                                moe_every=2, capacity_factor=4.0,
                                max_len=16, use_flash=False, remat=remat)

    plain, remat = build(False), build(True)
    params, _ = plain.init(jax.random.PRNGKey(0))

    def loss(m):
        def f(p):
            h, aux = m.hidden_states(p, ids, training=False)
            return jnp.sum(jnp.tanh(h * 0.01)) + 0.1 * aux
        return f

    l0, g0 = jax.value_and_grad(loss(plain))(params)
    l1, g1 = jax.value_and_grad(loss(remat))(params)
    assert np.allclose(float(l0), float(l1), atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _kernel_calls(jaxpr_text):
    """How often each flash kernel appears in a jaxpr's text."""
    return {k: len(re.findall(rf"name={k}\b", jaxpr_text))
            for k in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")}


def _flash_lm(family, remat, layers):
    from bigdl_tpu.models import MoETransformerLM, TransformerLM
    kw = dict(vocab_size=67, hidden_size=32, num_heads=2, filter_size=64,
              num_layers=layers, max_len=128, use_flash=True, remat=remat)
    if family == "moe":
        return MoETransformerLM(n_experts=4, moe_every=2,
                                capacity_factor=4.0, **kw)
    return TransformerLM(**kw)


@pytest.mark.parametrize("family,replicated", [
    ("dense", False), ("moe", False), ("dense", True)])
def test_remat_keeps_what_the_flash_kernel_made(monkeypatch, family,
                                                replicated):
    """With the flash kernel, ``remat=True`` gives the loss and every
    gradient leaf of ``remat=False``, and the backward pass does not run
    the forward kernel again: ``o`` and ``lse`` are kept by name
    (``nn.attention.remat_block``), the rest of the block is recomputed.
    ``replicated``: traced as ``DistriOptimizer``'s replicated mode does,
    under ``data_parallel_context`` — the kernel and its named residuals
    sit in a ``shard_map`` of their own, and JAX takes the policy into
    it."""
    from bigdl_tpu.parallel.flash import data_parallel_context
    from bigdl_tpu.parallel.mesh import data_parallel_mesh
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    layers = 2
    ids = jnp.asarray(np.random.RandomState(1).randint(
        1, 67, size=(2, 128)).astype(np.int32))
    plain, remat = (_flash_lm(family, r, layers) for r in (False, True))
    params, _ = plain.init(jax.random.PRNGKey(0))

    def loss(m):
        def f(p):
            if family == "moe":
                h, aux = m.hidden_states(p, ids, training=False)
                return jnp.sum(jnp.tanh(h * 0.01)) + 0.1 * aux
            out, _ = m.apply(p, {}, ids, training=False)
            return jnp.sum(jnp.tanh(out * 0.01))
        return jax.jit(jax.value_and_grad(f))

    ctx = data_parallel_context(data_parallel_mesh(2), "data") \
        if replicated else contextlib.nullcontext()
    with ctx:
        (l0, g0), (l1, g1) = loss(plain)(params), loss(remat)(params)
        texts = [str(jax.make_jaxpr(loss(m))(params))
                 for m in (plain, remat)]
    assert np.allclose(float(l0), float(l1), atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    want = {"flash_fwd": layers, "flash_bwd_dkv": layers,
            "flash_bwd_dq": layers}
    assert [_kernel_calls(t) for t in texts] == [want, want]
    # each block is still recomputed: remat did not become a no-op
    assert len(re.findall(r"= remat\w*\[", texts[1])) == layers


@pytest.mark.parametrize("heads,hidden", [(2, 128), (4, 256), (1, 128)])
def test_attention_fused_projection_matches_einsum(monkeypatch, heads,
                                                   hidden):
    """A flash self-attention layer hands its ONE q/k/v matmul to the
    kernels unsliced (``parallel.flash.flash_attention_qkv``). Under the
    Pallas interpreter it gives the einsum path's output and the same
    gradients for wq, wk, wv, wo and the input; and under ``jax.checkpoint``
    with ``remat_block``'s policy the forward kernel is still built once a
    layer: ``o`` and ``lse`` are kept, the fused projection is recomputed."""
    from bigdl_tpu import nn
    from bigdl_tpu.nn.attention import remat_block
    monkeypatch.setenv("BIGDL_TPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("BIGDL_TPU_FLASH_BLOCK_K", "128")
    attn = nn.Attention(hidden, heads, causal=True)
    params, _ = attn.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(3).randn(2, 200, hidden)
                    .astype(np.float32))

    def loss(run):
        def f(params, x):
            o = run(params, x)
            return jnp.sum(jnp.sin(o)), o
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)

    layer = lambda p, x: attn.apply(p, {}, x, training=True)[0]  # noqa: E731
    monkeypatch.setenv("BIGDL_TPU_FLASH", "off")
    (_, o_ref), (gp_ref, gx_ref) = loss(layer)(params, x)
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    for run in (layer, remat_block(layer)):
        (_, o), (gp, gx) = loss(run)(params, x)
        assert np.allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5), \
            np.abs(np.asarray(o) - np.asarray(o_ref)).max()
        for name, a, b in [("x", gx, gx_ref)] + [
                (k, gp[k], gp_ref[k]) for k in ("wq", "wk", "wv", "wo")]:
            err = np.abs(np.asarray(a) - np.asarray(b)).max()
            assert err < 5e-4, f"d{name} err {err}"
        text = str(jax.make_jaxpr(loss(run))(params, x))
        assert _kernel_calls(text) == {"flash_fwd": 1, "flash_bwd_dkv": 1,
                                       "flash_bwd_dq": 1}
        # q, k and v are read where the one matmul wrote them
        assert f"f32[2,200,{3 * hidden}]" in text
        assert f"f32[2,256,{3 * hidden}]" in text       # padded once
    assert len(re.findall(r"= remat\w*\[", text)) == 1


def test_lm_loss_chunked_matches_full_logits():
    """lm_loss_chunked == full-logits softmax-CE with RAW (0-based) token
    ids, values AND gradients (through a scan-of-checkpoint body). The
    0-based head is what makes argmax(logits) round-trip through
    generate(); the torch-parity criteria stay 1-based — the identity is
    chunked(y) == TimeDistributedMaskCriterion(CE)(logits, y+1)."""
    import jax
    from bigdl_tpu.models import lm_loss_chunked
    from bigdl_tpu.nn import (CrossEntropyCriterion,
                              TimeDistributedMaskCriterion)
    rng = np.random.RandomState(2)
    B, T, H, V = 2, 64, 16, 53
    h = jnp.asarray(rng.randn(B, T, H).astype(np.float32))
    emb = jnp.asarray(0.1 * rng.randn(V, H).astype(np.float32))
    y = rng.randint(1, V - 1, size=(B, T)).astype(np.int32)
    y[0, :5] = 0  # padding positions excluded
    y = jnp.asarray(y)

    def ref(h, emb):
        logits = (h @ emb.T).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        valid = (y != 0).astype(jnp.float32)
        return jnp.sum((lse - gold) * valid) / jnp.sum(valid)

    def chunked(h, emb):
        return lm_loss_chunked(h, emb, y, chunk=16)

    l_ref, g_ref = jax.value_and_grad(ref, argnums=(0, 1))(h, emb)
    l_ch, g_ch = jax.value_and_grad(chunked, argnums=(0, 1))(h, emb)
    assert np.allclose(float(l_ref), float(l_ch), rtol=1e-5)
    for a, b in zip(g_ref, g_ch):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    # identity to the 1-based criterion: shift targets up by one (pad
    # positions shift to 1 — give the shifted criterion padding_value=1)
    crit = TimeDistributedMaskCriterion(CrossEntropyCriterion(),
                                        padding_value=1)
    l_crit = crit._forward(h @ emb.T, y + 1)
    assert np.allclose(float(l_crit), float(l_ch), rtol=1e-5)


# ---------------------------------------------------------------------------
# fused BN+ReLU+matmul (+stats) kernel and the FusedBottleneck built on it
# ---------------------------------------------------------------------------


def test_flash_kernel_is_shard_mapped_under_a_data_parallel_trace(
        monkeypatch):
    """Mosaic kernels cannot be partitioned automatically (the four-chip
    TPU refused DistriOptimizer's replicated step with "Please wrap the
    call in a shard_map"), so inside ``data_parallel_context`` the
    dispatcher shard_maps the kernel over the batch axis — values and
    gradients unchanged, and nothing is wrapped outside the context."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bigdl_tpu.parallel import flash
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    rng = np.random.RandomState(3)
    q, k, v = [jax.device_put(
        jnp.asarray(rng.randn(4, 2, 128, 16).astype(np.float32)),
        NamedSharding(mesh, P("data"))) for _ in range(3)]

    def loss(q, k, v):
        return (flash.flash_attention(q, k, v, causal=True) ** 2).sum()

    def in_context(q, k, v):
        with flash.data_parallel_context(mesh, "data"):
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    assert "shard_map" in str(jax.make_jaxpr(in_context)(q, k, v))
    assert "shard_map" not in str(jax.make_jaxpr(
        jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v))
    got, got_g = jax.jit(in_context)(q, k, v)
    monkeypatch.setenv("BIGDL_TPU_FLASH", "off")
    want, want_g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, k, v)
    assert np.allclose(float(got), float(want), rtol=1e-4)
    for a, b in zip(got_g, want_g):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-3)
    # the output keeps the batch sharding the step's other ops expect
    with flash.data_parallel_context(mesh, "data"):
        monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
        out = jax.jit(lambda q, k, v: flash.flash_attention(
            q, k, v, causal=True))(q, k, v)
    assert len({s.device.id for s in out.addressable_shards}) == 4
    assert out.addressable_shards[0].data.shape == (1, 2, 128, 16)
