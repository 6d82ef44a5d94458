"""The plain reference against the program's ``TransformerLM`` at a tiny
size on the CPU: logits, the loss, the gradients and three Adam steps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_util  # noqa: F401
from benchmark import harness, reference, weights

M = {"vocab_size": 96, "hidden_size": 32, "num_heads": 4, "filter_size": 64,
     "num_layers": 2, "max_len": 24, "layer_norm_eps": 1e-6}
OPT = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}


def program_model(act):
    from bigdl_tpu.models import TransformerLM
    return TransformerLM(vocab_size=96, hidden_size=32, num_heads=4,
                         filter_size=64, num_layers=2, max_len=24,
                         ffn_activation=act)


@pytest.mark.parametrize("act", ["relu", "gelu"])
def test_logits_agree_with_the_program(act):
    m = dict(M, ffn_activation=act)
    params = weights.make_params(m, 2**31 + 5)
    ids = np.random.default_rng(0).integers(1, 96, size=(2, 24))
    got, _ = program_model(act).apply(params, {}, jnp.asarray(ids),
                                      training=False)
    want = reference.logits(reference.stack(params), jnp.asarray(ids), m)
    assert np.allclose(got, want, atol=2e-5)


def test_loss_and_gradients_agree_with_the_program():
    from bigdl_tpu import nn
    m = dict(M, ffn_activation="gelu")
    params = weights.make_params(m, 7)
    rng = np.random.default_rng(1)
    ids, tg = rng.integers(1, 96, (3, 24)), rng.integers(0, 96, (3, 24))
    model, crit = program_model("gelu"), nn.LMCriterion(padding_value=0)

    def prog(p):
        out, _ = model.apply(p, {}, jnp.asarray(ids), training=True)
        return crit._forward(out, jnp.asarray(tg))

    def ref(p):
        s, n = reference.loss_sum(reference.stack(p), jnp.asarray(ids),
                                  jnp.asarray(tg), m, 0, jnp.float32)
        return s / n

    (lp, gp), (lr, gr) = (jax.value_and_grad(f)(params) for f in (prog, ref))
    assert abs(float(lp) - float(lr)) < 1e-5
    gap, leaf = harness.worst_leaf_gap(reference.flat(reference.leaf_norms(gp)),
                                       reference.flat(reference.leaf_norms(gr)))
    assert gap < 1e-4, leaf


def test_no_leaf_has_a_zero_gradient_by_symmetry():
    m = dict(M, ffn_activation="relu")
    rng = np.random.default_rng(2)
    b = [(rng.integers(1, 96, (2, 24)), rng.integers(1, 96, (2, 24)))]
    out = reference.train_steps(weights.make_params(m, 3), b, m, OPT,
                                row_block=1)
    assert not harness.excluded_leaves(out["grad_norms"])
    assert min(out["delta_norms"].values()) > 0


def test_row_blocks_do_not_change_the_reference():
    m = dict(M, ffn_activation="gelu")
    rng = np.random.default_rng(3)
    b = [(rng.integers(1, 96, (4, 24)), rng.integers(0, 96, (4, 24)))
         for _ in range(2)]
    one = reference.train_steps(weights.make_params(m, 3), b, m, OPT,
                                row_block=1)
    four = reference.train_steps(weights.make_params(m, 3), b, m, OPT,
                                 row_block=4)
    assert np.allclose(one["losses"], four["losses"], rtol=1e-6)
    assert harness.worst_leaf_gap(one["grad_norms"], four["grad_norms"])[0] < 1e-5


def test_weights_follow_the_seed_and_take_large_seeds():
    m = dict(M, ffn_activation="relu")
    a, b = weights.make_params(m, 2**31 + 7), weights.make_params(m, 2**31 + 7)
    c = weights.make_params(m, 7)
    flat = lambda t: np.concatenate([np.ravel(x) for x in
                                     jax.tree_util.tree_leaves(t)])
    assert (flat(a) == flat(b)).all() and (flat(a) != flat(c)).any()
    assert a["embed"].dtype == jnp.float32
    assert set(a) == {"embed", "ln_f", "block0", "block1"}


def test_rows_spread_over_devices_do_not_change_the_reference():
    """A cell of several chips runs the reference with each block's rows
    spread over them: the same numbers as on one device."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    rng = np.random.default_rng(5)
    rows = rng.integers(1, M["vocab_size"], size=(8, 17)).astype(np.int32)
    b = [(rows[:, :-1], rows[:, 1:])]
    m = dict(M, ffn_activation="gelu")
    one = reference.train_steps(weights.make_params(m, 5), b, m, OPT,
                                row_block=4)
    four = reference.train_steps(weights.make_params(m, 5), b, m, OPT,
                                 row_block=4, devices=jax.devices()[:4])
    assert four["losses"][0] == pytest.approx(one["losses"][0], rel=1e-6)
    for k, v in one["grad_norms"].items():
        assert four["grad_norms"][k] == pytest.approx(v, rel=1e-4)
    for k, v in one["delta_norms"].items():
        assert four["delta_norms"][k] == pytest.approx(v, rel=1e-4)
