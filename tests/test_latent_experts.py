"""Squared-ReLU experts in a latent with a shared expert at full width
(``RoutedExperts(activation="relu2", latent=, n_shared=)``, the
``nemotron_h`` LatentMoE layer) and the squared-ReLU FFN, on the CPU against
their equations written as plain loops with masks, forward and gradients;
the selection bias's update from step to step (``bias_update``, noaux_tc)
through the layer, the stack and the trainer.

Both sides run in float32 at the highest matmul precision and sum the same
products in another order: they agree to ~1e-7 of the output's scale. The
tolerance, 1e-5, leaves a hundred times that; the same layer with its
weights rounded to bfloat16 reads ~1e-3 and fails it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn

TOL = 1e-5
H, E, K, F, L = 16, 8, 2, 12, 6


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _layer(held=(2, 4), bias_update=0.0):
    return nn.RoutedExperts(H, E, K, F, held=held, n_shared=2,
                            routed_scale=2.5, activation="relu2", latent=L,
                            bias_update=bias_update)


def _params(layer, seed):
    p = layer._init_params(jax.random.PRNGKey(seed))
    p["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1), (E,))
    return p


def relu2(x, w1, w2):
    return jnp.square(jnp.maximum(x @ w1, 0.0)) @ w2


def plain(p, x, held=(2, 4), scale=2.5):
    """``y = (sum_{i in sel, held} w_i W2_i relu(W1_i x W_down)^2) W_up +
    W2_s relu(W1_s x)^2``: sel the top k of sigmoid(x Wr) + b."""
    s = jax.nn.sigmoid(jnp.dot(x, p["router"],
                               precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(s + jax.lax.stop_gradient(p["bias"]), K)
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * scale
    u = x @ p["latent"]["down"]
    y = jnp.zeros_like(u)
    for i in range(held[1]):
        wi = jnp.sum(jnp.where(sel == held[0] + i, w, 0), axis=-1)
        y = y + wi[..., None] * relu2(u, p["experts"]["w1"][i],
                                      p["experts"]["w2"][i])
    return y @ p["latent"]["up"] + relu2(x, p["shared"]["w1"],
                                         p["shared"]["w2"])


def test_latent_relu2_experts_and_their_gradients_are_the_equations():
    layer = _layer()
    p = _params(layer, 3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, H))
    g = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    with jax.default_matmul_precision("highest"):
        (got, st), grads = jax.jit(jax.value_and_grad(
            lambda p: (lambda o: (jnp.sum(o[0] * g), o[1]))(
                layer.apply(p, {}, x)), has_aux=True))(p)
        want, wgrads = jax.jit(jax.value_and_grad(
            lambda p: jnp.sum(plain(p, x) * g)))(p)
        low = jnp.sum(layer.apply(jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), p),
            {}, x)[0] * g)
    assert float(got) == pytest.approx(float(want), rel=TOL)
    assert abs(float(low) - float(want)) > TOL * abs(float(want))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(wgrads)):
        if "bias" in str(path):
            assert float(jnp.max(jnp.abs(a))) == 0     # selection only
        else:
            assert _gap(a, b) <= TOL, path
    assert 0 < float(st["counters"]["moe/rows_local"]) <= 2 * 24 * K


def test_the_layer_holds_ungated_experts_in_the_latent_and_its_own_shared():
    p = _params(_layer(), 0)
    assert set(p["experts"]) == {"w1", "w2"}                  # no gate
    assert p["experts"]["w1"].shape == (4, L, F)
    assert p["experts"]["w2"].shape == (4, F, L)
    assert p["latent"]["down"].shape == (H, L)
    assert p["latent"]["up"].shape == (L, H)
    assert p["shared"]["w1"].shape == (H, 2 * F)       # two experts' width
    assert set(p["shared"]) == {"w1", "w2"}
    swiglu = nn.RoutedExperts(H, E, K, F, held=(0, 4), n_shared=2)
    q = swiglu._init_params(jax.random.PRNGKey(0))
    assert set(q["experts"]) == {"w1", "w2", "w3"} and "latent" not in q
    assert q["shared"]["w1"].shape == (H, 2 * F)
    with pytest.raises(ValueError, match="activation"):
        nn.RoutedExperts(H, E, K, F, activation="gelu")


def test_the_relu2_ffn_is_the_squared_relu():
    ffn = nn.FeedForwardNetwork(H, F, activation="relu2", bias=False)
    p = ffn._init_params(jax.random.PRNGKey(0))
    assert set(p) == {"w1", "w2"}
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, H))
    with jax.default_matmul_precision("highest"):
        got, _ = ffn.apply(p, {}, x)
        want = relu2(x, p["w1"], p["w2"])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="relu2"):
        nn.FeedForwardNetwork(H, F, activation="relu3")


def _load(p, x, moved=0.0):
    s = jax.nn.sigmoid(jnp.dot(x, p["router"],
                               precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(s + p["bias"] + moved, K)
    return jnp.bincount(sel.reshape(-1), length=E).astype(jnp.float32)


def test_the_bias_follows_the_load_a_training_forward_routed():
    """noaux_tc's update: after a training forward the state holds what
    ``u * sign(mean load - load_i)`` added to ``params["bias"]``; the next
    forward selects with both; an evaluating forward moves nothing."""
    u = 0.05
    layer = _layer(bias_update=u)
    p = _params(layer, 3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, H))
    assert layer._init_state()["bias"].shape == (E,)
    with jax.default_matmul_precision("highest"):
        _, st = layer.apply(p, layer._init_state(), x, training=True)
        load = _load(p, x)
        want = u * jnp.sign(jnp.mean(load) - load)
        np.testing.assert_array_equal(st["bias"], want)
        assert float(jnp.max(jnp.abs(st["bias"]))) == pytest.approx(u)
        y, st2 = layer.apply(p, st, x, training=True)
        load2 = _load(p, x, st["bias"])
        np.testing.assert_allclose(
            st2["bias"], want + u * jnp.sign(jnp.mean(load2) - load2),
            atol=1e-7)
        moved = dict(p, bias=p["bias"] + st["bias"])
        np.testing.assert_allclose(y, plain(moved, x), rtol=TOL, atol=TOL)
        _, st3 = layer.apply(p, st, x, training=False)
        np.testing.assert_array_equal(st3["bias"], st["bias"])
    # without the option the state has no bias and the program none of this
    assert "bias" not in _layer()._init_state()
    with pytest.raises(ValueError, match="softmax"):
        nn.RoutedExperts(H, E, K, F, scoring="softmax", bias_update=u)
    # the attention-plus-FFN block carries no state between steps
    with pytest.raises(ValueError, match="layer_pattern"):
        nn.TransformerBlock(H, 2, F, ffn=layer)


def test_the_trainer_carries_the_moved_bias_from_step_to_step():
    """In a stack (``Transformer`` over a layer pattern) the moved bias is
    the model state's entry under the expert layer's block: each training
    step adds one update of ``u`` to every expert's, and ``Optimizer``
    (``LocalOptimizer``) hands it to the next step and back to the
    model."""
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.optim import Adam, LocalOptimizer, Trigger
    u, V, T = 0.01, 32, 16
    model = nn.Transformer(
        V, hidden_size=H, mode="lm", pos_encoding="none", embed_scale=False,
        norm="rms", tied_head=False, layer_pattern="*E",
        make_layer=lambda kind, i: nn.Attention(H, 2, causal=True)
        if kind == "*" else _layer(held=(0, E), bias_update=u))
    model.state = model._init_state()
    assert set(model.state) == {"counters", "block1"}
    np.testing.assert_array_equal(model.state["block1"]["bias"],
                                  np.zeros(E))
    rows = np.random.default_rng(0).integers(1, V, size=(4, T + 1))
    opt = LocalOptimizer(model=model, training_set=DataSet.array(
        [Sample(r[:-1].astype(np.float32), r[1:].astype(np.float32))
         for r in rows]), criterion=nn.LMCriterion(padding_value=0),
        optim_method=Adam(learningrate=1e-3), batch_size=1)
    opt.set_end_when(Trigger.max_iteration(3))
    opt.optimize()
    b = np.asarray(model.state["block1"]["bias"])
    # three steps of +-u (0 where an expert's load was the mean)
    assert np.all(np.abs(b) <= 3 * u + 1e-7) and np.any(np.abs(b) > 2 * u)
    np.testing.assert_allclose(b / u, np.round(b / u), atol=1e-4)
