"""The gated-latent-attention expert family (``benchmark/parts/instella_moe``)
at toy sizes on the CPU: the program's layers against the plain reference on
seeded weights, the share of an expert-parallel deployment against the uncut
layer, the calibration of the routing bias, the two heads' loss and two
optimizer steps against the reference's own loop. The tiny cell through the
harness is in ``test_bm_instella_cell.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_checks
import bm_util
from benchmark import harness
from bigdl_tpu import nn

CELL = "tiny_instella"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bm_util.tiny_root(tmp_path_factory.mktemp("instella"))


@pytest.fixture(scope="module")
def cell(root):
    return harness.load_cell(CELL, root)


@pytest.fixture(scope="module")
def model(cell):
    return cell["config_data"]["model"]


@pytest.fixture(scope="module")
def ref(cell):
    return cell["parts"].reference


@pytest.fixture(scope="module")
def params(cell, model):
    return cell["parts"].weights.make_params(model, 11, log=None)


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


def _grads_close(got, want, tol=2e-5):
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) <= tol * scale + 1e-7, path


# ---- the new layers against the reference's equations

def test_rms_norm_is_the_references(ref):
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 7, 32))
    p = {"weight": 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (32,))}
    got, _ = nn.RMSNorm(32, 1e-6).apply(p, {}, x)
    _close(got, ref.rms_norm(x, p, 1e-6), 1e-6)
    assert set(nn.RMSNorm(32)._init_params(None)) == {"weight"}


def test_swiglu_without_biases_is_the_references(ref):
    ffn = nn.FeedForwardNetwork(32, 80, activation="swiglu", bias=False)
    p = ffn._init_params(jax.random.PRNGKey(2))
    assert set(p) == {"w1", "w2", "w3"}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 32))
    _close(ffn.apply(p, {}, x)[0], ref.swiglu(p, x), 1e-6)
    assert "b1" in nn.FeedForwardNetwork(32, 80)._init_params(
        jax.random.PRNGKey(2))


@pytest.mark.parametrize("dim,factor", [(32, 40), (8, 40), (64, 4)])
def test_yarn_frequencies_blend_interpolation_into_extrapolation(
        ref, dim, factor):
    from bigdl_tpu.nn.attention import yarn_inv_freq, yarn_mscale
    m = {"qk_rope_head_dim": dim, "rope_theta": 8e6, "rope_scaling": {
        "factor": factor, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}}
    got = np.asarray(yarn_inv_freq(dim, 8e6, factor, 4096, 32, 1))
    np.testing.assert_allclose(got, ref.yarn_inv_freq(m), rtol=1e-12)
    plain = 8e6 ** (-np.arange(0, dim, 2) / dim)
    # the fastest dims keep their frequency, the slowest are divided
    assert got[0] == pytest.approx(plain[0])
    assert got[-1] == pytest.approx(plain[-1] / factor)
    assert np.all(got <= plain * (1 + 1e-12)) and np.all(
        got >= plain / factor * (1 - 1e-12))
    assert ref.softmax_scale(dict(m, qk_nope_head_dim=96)) == pytest.approx(
        (96 + dim) ** -0.5 * yarn_mscale(factor, 1) ** 2)


def _attention(model):
    return nn.LatentAttention(
        model["hidden_size"], model["num_heads"], model["kv_lora_rank"],
        model["qk_nope_head_dim"], model["qk_rope_head_dim"],
        model["v_head_dim"], rope_theta=model["rope_theta"],
        rope_scaling=model["rope_scaling"], gated=True)


def test_latent_attention_forward_and_gradients_are_the_references(
        model, ref, params):
    att, p = _attention(model), params["block1"]["attn"]
    assert set(att._init_params(jax.random.PRNGKey(0))) == set(p)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, model["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        f = lambda p, x: jnp.sum(jnp.sin(att.apply(p, {}, x)[0]))
        g = lambda p, x: jnp.sum(jnp.sin(ref.attention(p, x, model)))
        _close(att.apply(p, {}, x)[0], ref.attention(p, x, model))
        _grads_close(jax.grad(f, (0, 1))(p, x), jax.grad(g, (0, 1))(p, x))


def test_flash_runs_one_128_wide_head_a_block_with_the_scale_passed_in(
        monkeypatch):
    """The kernels of the dense decoder (``interpret=True``) on latent
    attention's q, k, v after the up-projection: heads of 96 + 32 and of
    128, YaRN's softmax scale, against the einsum path."""
    from bigdl_tpu.kernels.flash_attention import heads_per_block
    assert heads_per_block(16, 128) == 1
    att = nn.LatentAttention(64, 2, 32, 96, 32, 128, rope_theta=8e6,
                             rope_scaling={
                                 "factor": 40, "beta_fast": 32, "beta_slow": 1,
                                 "original_max_position_embeddings": 4096,
                                 "mscale": 1, "mscale_all_dim": 1}, gated=True)
    assert att.scale == pytest.approx(128 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    p = att._init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 64))
    f = lambda p, x: jnp.sum(jnp.sin(att.apply(p, {}, x)[0]))
    monkeypatch.setenv("BIGDL_TPU_FLASH", "off")
    want, gwant = jax.value_and_grad(f, (0, 1))(p, x)
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    got, ggot = jax.value_and_grad(f, (0, 1))(p, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    _grads_close(ggot, gwant, 1e-4)


def _experts(model, held=None, capacity_factor=None):
    return nn.RoutedExperts(
        model["hidden_size"], model["n_experts"], model["top_k"],
        model["expert_width"],
        held=held or (model["held_first"], model["experts_held"]),
        n_shared=model["n_shared"], routed_scale=model["routed_scale"],
        capacity_factor=capacity_factor)


def test_routed_experts_forward_and_gradients_are_the_references(
        model, ref, params):
    layer, p = _experts(model), params["block2"]["ffn"]
    assert jax.tree_util.tree_structure(
        layer._init_params(jax.random.PRNGKey(0))) == \
        jax.tree_util.tree_structure(p)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, model["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        f = lambda p, x: jnp.sum(jnp.sin(layer.apply(p, {}, x)[0]))
        g = lambda p, x: jnp.sum(jnp.sin(ref.experts(p, x, model)))
        y, state = layer.apply(p, {}, x)
        _close(y, ref.experts(p, x, model))
        got, want = jax.grad(f, (0, 1))(p, x), jax.grad(g, (0, 1))(p, x)
        _grads_close(got, want)
    # the selection bias steers the choice and takes no gradient
    assert float(jnp.max(jnp.abs(got[0]["bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(got[0]["router"]))) > 0
    moved = dict(p, bias=p["bias"].at[0].add(10.0))
    assert not np.allclose(np.asarray(layer.apply(moved, {}, x)[0]),
                           np.asarray(y))
    rows = float(state["counters"]["moe/rows_local"])
    sel, _ = ref.choose(ref.router_scores(p, x), p["bias"], model)
    assert rows == float(np.sum(np.asarray(sel) < model["experts_held"]))
    assert float(state["counters"]["moe/load_max_over_mean"]) >= 1.0


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(model, ref):
    """The guide's test of the cut: two chips hold 8 of the 16 experts
    each, every chip computes the shared experts; the routed parts of the
    two shares and the shared experts ONCE are the layer that holds all 16,
    in the program and in the reference alike."""
    E, held = model["n_experts"], model["experts_held"]
    whole = _experts(model, held=(0, E))
    p = whole._init_params(jax.random.PRNGKey(6))
    p["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (E,))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, model["hidden_size"]))
    cut = lambda first: dict(p, experts=jax.tree_util.tree_map(
        lambda a: a[first:first + held], p["experts"]))
    with jax.default_matmul_precision("highest"):
        full = whole.apply(p, {}, x)[0]
        shared = whole.shared.apply(p["shared"], {}, x)[0]
        total = shared
        rows = 0.0
        for first in range(0, E, held):
            y, st = _experts(model, held=(first, held)).apply(cut(first), {}, x)
            mine = ref.experts(cut(first), x, dict(model, held_first=first))
            _close(y, mine)
            total = total + (y - shared)
            rows += float(st["counters"]["moe/rows_local"])
        _close(total, full)
        _close(full, ref.experts(p, x, dict(model, held_first=0,
                                            experts_held=E)))
    assert rows == x.shape[0] * x.shape[1] * model["top_k"]


def test_an_expert_sent_more_rows_than_its_slots_is_an_error_not_a_drop(
        model, params):
    p = params["block2"]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 64, model["hidden_size"]))
    roomy, tight = _experts(model), _experts(model, capacity_factor=0.25)
    assert roomy.capacity(64) == 64 and tight.capacity(64) == 3
    assert bool(jnp.all(jnp.isfinite(roomy.apply(p, {}, x)[0])))
    assert bool(jnp.all(jnp.isnan(tight.apply(p, {}, x)[0])))


def test_the_mtp_head_predicts_the_next_but_one_token(cell, model, ref,
                                                      params):
    """``ParallelCriterion`` over the Table of the two heads' logits with
    ONE label array is the reference's ``CE(main) + 0.3 CE(mtp)``, the MTP
    head's first position masked; in evaluation the model returns the main
    logits alone, and they are the reference's."""
    net, criterion = cell["parts"].builder.build(model, False)
    rows = np.random.default_rng(0).integers(1, model["vocab_size"], (2, 33))
    ids, tg = rows[:, :-1].astype(np.int32), rows[:, 1:].astype(np.int32)
    with jax.default_matmul_precision("highest"):
        out, state = net.apply(params, net.state, jnp.asarray(ids, jnp.float32),
                               training=True)
        loss = criterion._forward(out, jnp.asarray(tg, jnp.float32))
        main, mtp = ref.loss_sums(params, ids, tg, model, 0, jnp.float32)
        plain = net.apply(params, net.state, jnp.asarray(ids, jnp.float32))[0]
        _close(plain, ref.logits(params, ids, model), 1e-4)
    assert len(out) == 2 and out[1].shape == out[2].shape == plain.shape
    want = main / ids.size + model["mtp_loss_weight"] * mtp / (2 * 31)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert float(mtp) > 0 and set(state["counters"]) == {
        "moe/rows_local", "moe/load_max_over_mean"}
    assert jax.tree_util.tree_structure(state) == \
        jax.tree_util.tree_structure(net.state)


def test_two_optimizer_steps_are_the_references_train_steps(cell, model, ref):
    """``LocalOptimizer`` with ``ParallelCriterion`` against the
    reference's own step loop (row blocks, both losses, Adam): losses and
    parameters after two steps, and the routing counters leave through the
    optimizer's metrics."""
    from benchmark import train
    from bigdl_tpu.optim import Trigger
    weights, o = cell["parts"].weights, cell["config_data"]["entry"]["optimizer"]
    net, opt, fed, B = train.build(cell, 11, 1)
    assert type(opt).__name__ == "LocalOptimizer"
    assert type(opt.criterion).__name__ == "ParallelCriterion"
    losses = []

    def end(state):
        if state["neval"] > len(losses):
            losses.append(state["loss"])
        return state["neval"] >= 2

    opt.set_end_when(Trigger(end))
    with jax.default_matmul_precision("highest"):
        opt.optimize()
    # the rows as the shuffling pipeline fed them (the harness's recorder)
    batches = [tuple(np.stack([f(x) for x in fed[i:i + B]]).astype(np.int32)
                     for f in (lambda x: x.feature(), lambda x: x.label()))
               for i in (0, B)]
    start = weights.make_params(model, 11, log=None)
    want = ref.train_steps(weights.make_params(model, 11, log=None), batches,
                           model, o, row_block=1)
    assert losses[:2] == pytest.approx(want["losses"], rel=1e-5)
    delta = ref.flat(ref.leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, net.params, start)))
    gap, where = harness.worst_leaf_gap(
        delta, want["delta_norms"],
        exclude=harness.excluded_leaves(want["grad_norms"]))
    assert gap < 1e-3, where
    assert delta["block2/ffn/bias"] == 0.0 == want["grad_norms"][
        "block2/ffn/bias"]
    assert len(opt.metrics.values["moe/rows_local"]) == 2
    share = opt.metrics.values["moe/rows_local"][-1] / (B * 32 * model["top_k"])
    assert 0.3 < share < 0.7        # 8 of 16 experts held


# ---- the routing bias

@pytest.mark.parametrize("threshold", [1.25, 1.1])
def test_the_calibration_brings_a_skewed_layer_to_its_threshold(
        cell, threshold):
    """A router whose first experts are favoured by a wide margin (the
    fullest takes over three times the mean) is balanced by the published
    sign update to the threshold it is given."""
    balance = cell["parts"].weights.balance_bias
    k = jax.random.split(jax.random.PRNGKey(12), 2)
    skew = jnp.linspace(1.5, -1.5, 16)
    scores = jax.nn.sigmoid(jax.random.normal(k[0], (4096, 16)) + skew)
    b, worst, steps, start = balance(scores, 3, threshold)
    assert float(start) > 3.0
    assert float(worst) <= threshold and 0 < int(steps) < 20000
    # the favoured experts are held back, the others helped
    assert float(b[0]) < 0 < float(b[-1])


def test_the_calibrated_bias_is_part_of_the_tree_and_the_same_on_every_call(
        cell, model, ref, params):
    weights = cell["parts"].weights
    again = weights.make_params(model, 11, log=None)
    for name in ("block1", "block2"):
        b = np.asarray(params[name]["ffn"]["bias"])
        assert np.any(b != 0) and np.array_equal(
            b, np.asarray(again[name]["ffn"]["bias"]))
    assert np.any(np.asarray(params["mtp"]["block"]["ffn"]["bias"]) != 0)
    other = weights.make_params(model, 12, log=None)
    assert not np.array_equal(np.asarray(other["block1"]["ffn"]["bias"]),
                              np.asarray(params["block1"]["ffn"]["bias"]))
    assert "router" not in params["block0"]["ffn"]      # the dense layer
    # on fresh rows of the cell's distribution the calibrated layer is
    # nearer to even than the same layer with b = 0
    cal = model["calibration"]
    ids = np.random.default_rng(5).integers(
        1, model["vocab_size"], (64, cal["seq_len"])).astype(np.int32)

    def worst(p):
        h = ref.trunk(p, ids, model, upto=1)
        blk = p["block1"]
        a = h + ref.attention(blk["attn"], ref.rms_norm(
            h, blk["ln1"], 1e-6), model)
        n = ref.rms_norm(a, blk["ln2"], 1e-6)
        sel, _ = ref.choose(ref.router_scores(blk["ffn"], n),
                            blk["ffn"]["bias"], model)
        load = np.bincount(np.asarray(sel).reshape(-1),
                           minlength=model["n_experts"])
        return load.max() / load.mean()

    unset = dict(params, block1=dict(params["block1"], ffn=dict(
        params["block1"]["ffn"], bias=jnp.zeros(model["n_experts"]))))
    assert worst(params) < worst(unset)


# ---- the configuration, the counts, the readers

def test_the_tiny_configuration_is_the_real_ones_shape_and_cut(root):
    data = harness.load_json(root, "configs", "tiny-instella.json")
    # the toy is cut as the real one is, but for its depth (3 layers: the
    # tests' compile time); with the real depth it keeps to the floors
    with pytest.raises(AssertionError, match="under the floor"):
        bm_checks.check_cut(data)
    bm_checks.check_cut(dict(data, model=dict(data["model"], num_layers=5)))
    real = harness.load_json(harness.HERE, "configs",
                             "instella-moe-16b-a3b.json")
    assert data["entry"]["parts"] == real["entry"]["parts"]
    assert set(data["model"]) == set(real["model"])
    assert set(data["faults"]) == set(real["faults"])
    bm_checks.check_limits(root, CELL)


def test_the_real_configuration_is_the_catalogs_cut_to_a_share():
    data = harness.load_json(harness.HERE, "configs",
                             "instella-moe-16b-a3b.json")
    m, pub = data["model"], data["published"]
    assert data["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    for key, value in pub.items():
        if key not in data["reduced"]:
            assert data[key] == value, key
    assert (data["num_hidden_layers"], data["n_routed_experts"],
            data["vocab_size"]) == (5, 8, 16112)
    assert m["n_experts"] == pub["n_routed_experts"] == 64
    assert m["vocab_size"] * 8 == pub["vocab_size"]
    assert data["deployment"]["chips_per_layer"] * m["experts_held"] == 64
    assert {"farskip", "seq_aux"} <= {d.split()[0].rstrip(":")
                                      for d in data["departures"]}


def test_the_parts_count_the_shares_own_operations(cell):
    real = harness.load_json(harness.HERE, "configs",
                             "instella-moe-16b-a3b.json")["model"]
    ops = cell["parts"].ops
    assert ops.routed_rows_per_token(real) == 0.75
    assert ops.kernel_layers(real, "flash_fwd") == 6
    assert ops.expert_layers(real) == 5
    # 15.53M multiply-adds a token and attention: the parameters of one
    assert ops.attention_matmul_per_token(real) == 15_532_032
    per_token = ops.train_flops_per_sequence(real, 4096) / 4096
    assert per_token == pytest.approx(2.43e9, rel=0.01)
    scores = 3 * ops.attn_flops(real, ops.causal_pairs(4096)) / 4096
    assert scores / per_token == pytest.approx(0.124, abs=0.005)
    flops, nbytes = ops.flash_train_ops_bytes(real, 4, 4096)
    assert flops == 7 * 2 * 2048 * 4 * ops.causal_pairs(4096) * 6
    assert nbytes == 12 * 4 * 4096 * 2048 * 4 * 6
    few, _ = ops.experts_train_ops_bytes(real, 16384, 0)
    more, _ = ops.experts_train_ops_bytes(real, 16384, 12288)
    assert more - few == 5 * 3 * 6 * 2048 * 1408 * 12288


def test_the_counter_reader_reads_what_the_optimizer_wrote_on_the_step_spans(
        cell, root, monkeypatch):
    reader = harness.load_reader(harness.load_json(
        root, "metrics", "moe_rows_local_share.json"), root)
    assert reader({"trace": None, "window": {}}, what="rows_local_share") is None
    mod = reader.__globals__
    ctx = {"trace": {}, "program_trace": {"spans": [], "devices": {"0": []}},
           "window": {"traced_steps": 2, "tokens_per_step": 64},
           "config": cell["config_data"], "parts": cell["parts"],
           "step_stats": [{"moe/rows_local": 90.0,
                           "moe/load_max_over_mean": 1.2},
                          {"moe/rows_local": 102.0,
                           "moe/load_max_over_mean": 1.3}, {"step_num": 3}]}
    assert reader(ctx, what="rows_local_share") == pytest.approx(
        100 * 96 / (64 * 3))
    assert reader(ctx, what="load_max_over_mean") == 1.3
    assert reader(dict(ctx, step_stats=[{"step_num": 1}]),
                  what="rows_local_share") is None     # a program without them
    assert mod["ROWS"] == "moe/rows_local"
