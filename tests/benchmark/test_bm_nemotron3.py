"""The hybrid Mamba-2 / latent-expert family (``benchmark/parts/
nemotron3_super``) at toy sizes on the CPU: the program's whole model
(``Transformer`` over a layer pattern) against the plain reference on seeded
weights, loss and gradients; the shares of the 64-chip deployment against
the uncut layers (mixer heads, attention heads, experts); the configuration's
cut and the parts' counts. The tiny cell through the harness is in
``test_bm_nemotron3_cell.py``.

Program and reference both run in float32 at the highest matmul precision
here and differ by float32 rounding: the chunked scan against the
recurrence position by position, the sorted dispatch against the masked
loop (~1e-6 of a quantity's scale). The tolerances, 2e-5, leave ten times
that; the reference in bfloat16 (the control) reads ~1e-2 and fails
them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_checks
import bm_util
from benchmark import harness
from bigdl_tpu import nn

CELL = "tiny_nemotron3"
REAL = "nvidia-nemotron-3-super-120b-a12b"
TOL = 2e-5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bm_util.tiny_root(tmp_path_factory.mktemp("nemotron3"))


@pytest.fixture(scope="module")
def cell(root):
    return harness.load_cell(CELL, root)


@pytest.fixture(scope="module")
def model(cell):
    return cell["config_data"]["model"]


@pytest.fixture(scope="module")
def ref(cell):
    return cell["parts"].reference


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want))
                                                  + 1e-30))


def _params(cell, seed=3):
    return cell["parts"].weights.make_params(cell["config_data"]["model"],
                                             seed, log=None)


def test_the_whole_model_loss_and_gradients_are_the_references(cell, model,
                                                               ref):
    program, criterion = cell["parts"].builder.build(model, True)
    p = _params(cell)
    rows = np.random.default_rng(4).integers(1, model["vocab_size"],
                                             size=(2, 49))
    ids, tg = rows[:, :-1], rows[:, 1:]

    from bigdl_tpu.optim.optimizer import _loss_fn
    step_loss = _loss_fn(program, criterion)

    def prog(p):
        return step_loss(p, program.state, jnp.asarray(ids, jnp.float32),
                         jnp.asarray(tg, jnp.float32), None)[0]

    def plain(p, dtype=jnp.float32):
        return ref.loss_sum(p, jnp.asarray(ids), jnp.asarray(tg), model, 0,
                            dtype) / tg.size

    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(prog))(p)
        want, wgrads = jax.jit(jax.value_and_grad(plain))(p)
        low = jax.jit(jax.grad(lambda p: plain(p, jnp.bfloat16)))(p)
    assert float(got) == pytest.approx(float(want), rel=TOL)
    gaps = {k: _gap(grads_k, wgrads_k) for (k, grads_k), wgrads_k in zip(
        ref.leaf_arrays(grads).items(), ref.leaf_arrays(wgrads).values())
        if "bias" not in k}
    assert max(gaps.values()) <= TOL, max(gaps.items(), key=lambda kv: kv[1])
    low = ref.leaf_arrays(low)
    assert max(_gap(low[k].astype(jnp.float32), w) for k, w in
               ref.leaf_arrays(wgrads).items() if "bias" not in k) > TOL


def test_the_stack_is_one_sublayer_a_layer_in_the_patterns_order(cell,
                                                                  model):
    program, _ = cell["parts"].builder.build(model, False)
    kinds = [type(b.module).__name__ for b in program.blocks]
    assert kinds == [{"M": "Mamba2Mixer", "E": "RoutedExperts",
                      "*": "Attention"}[c] for c in model["layer_pattern"]]
    p = _params(cell)
    assert [set(p[f"block{i}"]) for i in range(len(kinds))] == [
        {"ln", {"M": "ssm", "E": "ffn", "*": "attn"}[c]}
        for c in model["layer_pattern"]]
    assert set(program.state["counters"]) == {"moe/rows_local",
                                              "moe/load_max_over_mean"}
    # each expert layer's moved bias, under its block's key
    experts = {f"block{i}" for i, c in enumerate(model["layer_pattern"])
               if c == "E"}
    assert set(program.state) == {"counters"} | experts
    assert all(program.state[k]["bias"].shape == (model["n_experts"],)
               for k in experts)
    with pytest.raises(ValueError, match="none of"):
        nn.Transformer(8, hidden_size=8, layer_pattern="MX",
                       make_layer=lambda k, i: None)
    with pytest.raises(ValueError, match="make_layer"):
        nn.Transformer(8, hidden_size=8, layer_pattern="M")


def _mixer_share(p, m, s, n):
    """Share ``s`` of ``n``: its groups' heads, channels and norm."""
    nh, P, g, N = (m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"],
                   m["ssm_state"])
    inner, hs, gs = nh * P, nh // n, g // n
    heads = lambda a, w: a.reshape(*a.shape[:-1], nh, w)[  # noqa: E731
        ..., s * hs:(s + 1) * hs, :].reshape(*a.shape[:-1], hs * w)
    groups = lambda a: a.reshape(*a.shape[:-1], g, N)[  # noqa: E731
        ..., s * gs:(s + 1) * gs, :].reshape(*a.shape[:-1], gs * N)

    def columns(a):     # [.., z | x | B | C | dt] -> the share's columns
        z, x = a[..., :inner], a[..., inner:2 * inner]
        B, C = a[..., 2 * inner:2 * inner + g * N], \
            a[..., 2 * inner + g * N:2 * inner + 2 * g * N]
        dt = a[..., 2 * inner + 2 * g * N:]
        return jnp.concatenate([heads(z, P), heads(x, P), groups(B),
                                groups(C), heads(dt, 1)], -1)

    conv = lambda a: columns(jnp.concatenate(  # noqa: E731
        [jnp.zeros(a.shape[:-1] + (inner,)), a,
         jnp.zeros(a.shape[:-1] + (nh,))], -1))
    xbc = slice(hs * P, 2 * hs * P + 2 * gs * N)
    return {"in_proj": columns(p["in_proj"]),
            "conv_weight": conv(p["conv_weight"])[..., xbc],
            "conv_bias": conv(p["conv_bias"])[..., xbc],
            "dt_bias": heads(p["dt_bias"], 1), "A_log": heads(p["A_log"], 1),
            "D": heads(p["D"], 1), "norm": {"weight": heads(
                p["norm"]["weight"], P)},
            "out_proj": heads(p["out_proj"].T, P).T}


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(model, ref,
                                                           kind):
    """The guide's test of the cut, for each kind of layer: the mixer's
    shares of whole groups of heads, the attention's shares of query heads
    with the KV head they read, and the expert shares (each with the shared
    expert, counted once) add up to the uncut layer, in the program and in
    the reference alike."""
    from benchmark.parts.nemotron3_super import weights
    n = 2
    whole = dict(model, ssm_heads=2 * model["ssm_heads"],
                 ssm_groups=2 * model["ssm_groups"],
                 num_heads=2 * model["num_heads"],
                 num_kv_heads=2 * model["num_kv_heads"],
                 experts_held=model["n_experts"], held_first=0)
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 40, model["hidden_size"]))
    if kind == "M":
        p = weights._mixer(whole, key, 0.02)
        layer = lambda m: nn.Mamba2Mixer(  # noqa: E731
            m["hidden_size"], m["ssm_heads"], m["ssm_head_dim"],
            m["ssm_groups"], m["ssm_state"], chunk_size=m["chunk_size"])
        shares = [(_mixer_share(p, whole, s, n), model) for s in range(n)]
        run_ref, once = ref.mixer, None
    elif kind == "*":
        p = weights._attention(whole, key, 0.02)
        layer = lambda m: nn.Attention(  # noqa: E731
            m["hidden_size"], m["num_heads"], causal=True,
            num_kv_heads=m["num_kv_heads"], head_dim=m["head_dim"])
        q, kv = model["num_heads"] * model["head_dim"], \
            model["num_kv_heads"] * model["head_dim"]
        shares = [({"wq": p["wq"][:, s * q:(s + 1) * q],
                    "wk": p["wk"][:, s * kv:(s + 1) * kv],
                    "wv": p["wv"][:, s * kv:(s + 1) * kv],
                    "wo": p["wo"][s * q:(s + 1) * q]}, model)
                   for s in range(n)]
        run_ref, once = ref.attention, None
    else:
        p = weights._experts(whole, key, 0.02)
        p["bias"] = 0.05 * jax.random.normal(key, p["bias"].shape)
        E, held = model["n_experts"], model["n_experts"] // n
        layer = lambda m: nn.RoutedExperts(  # noqa: E731
            m["hidden_size"], E, m["top_k"], m["expert_width"],
            held=(m["held_first"], m["experts_held"]),
            n_shared=m["shared_width"] // m["expert_width"],
            routed_scale=m["routed_scale"], activation="relu2",
            latent=m["latent"])
        shares = [(dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[s * held:(s + 1) * held], p["experts"])),
            dict(model, held_first=s * held, experts_held=held))
            for s in range(n)]
        run_ref = lambda q, x, m: ref.experts(q, x, m)[0]  # noqa: E731
        once = lambda q, x: ref.relu2(q["shared"], x)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        full, _ = layer(whole).apply(p, {}, x)
        np.testing.assert_allclose(full, run_ref(p, x, whole), rtol=TOL,
                                   atol=TOL * float(jnp.max(jnp.abs(full))))
        total = jnp.zeros_like(full)
        for q, m in shares:
            y, _ = layer(m).apply(q, {}, x)
            np.testing.assert_allclose(
                y, run_ref(q, x, m), rtol=TOL,
                atol=TOL * float(jnp.max(jnp.abs(y))))
            total = total + y
        if once is not None:
            total = total - (n - 1) * once(p, x)
    assert _gap(total, full) <= TOL


def test_the_tiny_configuration_is_the_real_ones_shape(root):
    data = harness.load_json(root, "configs", "tiny-nemotron3.json")
    real = harness.load_json(harness.HERE, "configs", REAL + ".json")
    assert data["entry"]["parts"] == real["entry"]["parts"]
    assert set(data["model"]) == set(real["model"])
    assert set(data["faults"]) == set(real["faults"])
    bm_checks.check_limits(root, CELL)


def test_the_real_configuration_is_the_catalogs_cut_to_a_share():
    data = harness.load_json(harness.HERE, "configs", REAL + ".json")
    m, pub = data["model"], data["published"]
    assert data["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "mamba_num_heads", "n_groups",
                               "num_attention_heads", "num_key_value_heads",
                               "vocab_size"]
    for key, value in pub.items():
        if key not in data["reduced"] and key != "hybrid_override_pattern":
            assert data[key] == value, key
    # the pattern is held in `published` only: the cut's form has no string
    assert "hybrid_override_pattern" not in data
    assert "hybrid_override_pattern" not in data["published_keys"].values()
    assert m["layer_pattern"] == pub["hybrid_override_pattern"][:11]
    assert m["layer_pattern"] == "MEMEMEM*EME"
    assert (m["layer_pattern"].count("M"), m["layer_pattern"].count("E"),
            m["layer_pattern"].count("*")) == (5, 5, 1)
    assert (pub["hybrid_override_pattern"].count("M"),
            pub["hybrid_override_pattern"].count("E"),
            pub["hybrid_override_pattern"].count("*")) == (40, 40, 8)
    assert m["num_layers"] == len(m["layer_pattern"]) == data["deployment"][
        "layer_period"]
    assert m["n_experts"] == pub["n_routed_experts"] == 512
    assert data["deployment"]["chips_per_layer"] * m["experts_held"] == 512
    assert (pub["mamba_num_heads"] // m["ssm_heads"], pub["n_groups"]
            // m["ssm_groups"], pub["num_attention_heads"] // m["num_heads"],
            pub["num_key_value_heads"] // m["num_kv_heads"]) == (4, 4, 4, 2)
    assert m["vocab_size"] * 8 == pub["vocab_size"]
    # the gated norm's groups are whole in the share: 8192 / 8 channels
    assert m["ssm_heads"] * m["ssm_head_dim"] // m["ssm_groups"] == (
        pub["mamba_num_heads"] * pub["mamba_head_dim"] // pub["n_groups"])
    bm_checks.check_cut(data)


def test_the_parts_count_the_shares_own_operations_and_parameters(cell):
    real = harness.load_json(harness.HERE, "configs", REAL + ".json")["model"]
    ops = cell["parts"].ops
    assert ops.routed_rows_per_token(real) == 22 * 8 / 512
    assert ops.kernel_layers(real, "flash_fwd") == 1
    # 8 query heads of 128 over the causal pairs, k and v repeated to them
    flops, nbytes = ops.flash_train_ops_bytes(real, 1, 4096)
    assert flops == 7 * 2 * 1024 * (4096 * 4097 // 2)
    assert nbytes == 12 * 4096 * 1024 * 4
    assert ops.mixer_matmul_per_token(real) == 4096 * 4640 + 2048 * 4096
    assert ops.attention_matmul_per_token(real) == 9_437_184
    per_token = ops.matmul_flops_per_token(real)
    assert per_token == pytest.approx(0.99e9, rel=0.01)
    # C B^T, the masked product, the chunk state, C . state, the recurrence
    assert ops.ssd_flops(real, 4096) == 2 * 32 * (
        2 * 128 * 128 * 128 + 32 * 128 * 128 * 64 + 2 * 32 * 128 * 64 * 128
        + 32 * 64 * 128) * 5
    flops, nbytes = ops.ssd_train_ops_bytes(real, 1, 4096)
    assert flops == 3 * ops.ssd_flops(real, 4096)
    assert nbytes == (3 * 4096 * (2048 + 512 + 32) + 2 * 4096 * 2048) * 4 * 5
    few, _ = ops.experts_train_ops_bytes(real, 4096, 0)
    more, _ = ops.experts_train_ops_bytes(real, 4096, 1408)
    assert more - few == 5 * 3 * 2 * 1408 * 2 * 1024 * 2688
    shapes = jax.eval_shape(lambda: cell["parts"].weights._tree(
        real, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == pytest.approx(773.6e6, rel=1e-3)
    assert n * 16 / 1e9 == pytest.approx(12.38, abs=0.01)


def test_the_ssd_reader_reads_nothing_where_nothing_was_traced(root, cell):
    for name in ("ssm_mixer_ms", "ssd_scan_ms", "ssd_scan_roofline"):
        metric = harness.load_json(root, "metrics", name + ".json")
        reader = harness.load_reader(metric, root)
        ctx = {"trace": None, "window": {}, "config": cell["config_data"],
               "parts": cell["parts"], "chips": 1}
        assert reader(ctx, **metric["args"]) is None
