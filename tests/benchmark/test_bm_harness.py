"""The harness end to end on tiny configurations on the CPU: it is driven
by data, it refuses device metrics off the chip, and a broken timed path
comes out as not correct."""
import json
import os
import subprocess
import sys

import pytest

import bm_util
from benchmark import harness, run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bm_util.tiny_root(tmp_path_factory.mktemp("bm"))


@pytest.mark.parametrize("cell", ["tiny_train", "tiny_dp4"])
def test_a_new_cell_runs_from_added_files_alone(root, cell):
    """One configuration file, one traffic file, one cell file (and, for
    the traced run below, one metric file) were ADDED to a copy of
    ``benchmark/``; no file that was there was edited (``tiny_root``
    compares every one)."""
    out = bm_util.run_tiny(root, cell)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "compared"
    assert all(r["limit"] is not None for r in out["compared"].values())


def test_no_cpu_number_is_written_under_a_device_metrics_name(root):
    for trace in (0, 1):
        out = bm_util.run_tiny(root, "tiny_train", trace=trace)
        assert out["metrics"] == {} and "rehearsal" in out
        assert "busy_s" not in out["device"] and "breakdown" not in out
        assert out["device"]["platform"] == "cpu"


def test_an_added_metric_file_is_found_by_name(root):
    names = [m["name"] for m in harness.metrics_for("tiny_train", root)]
    assert names == ["tiny_steps"]
    read = harness.load_reader(harness.metrics_for("tiny_train", root)[0],
                               root)
    assert read({"window": {"steps": 7}}) == 7
    shipped = {m["name"] for m in harness.metrics_for("gpt2m_train_1k")}
    assert {"train_step_mfu", "flash_train_roofline",
            "device_idle.train"} <= shipped


def test_the_look_for_a_chip_fails_on_the_cpu(capsys):
    cell = harness.load_cell("gpt2m_train_1k")
    with pytest.raises(SystemExit) as e:
        run.find_devices(cell)
    assert e.value.code != 0
    assert "not 'tpu'" in capsys.readouterr().err


def test_a_directory_with_only_the_benchmark_prints_no_result(tmp_path):
    import shutil
    shutil.copytree(os.path.join(bm_util.REPO, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "gpt2m_train_1k", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "cannot import bigdl_tpu" in p.stderr


# ---- the timed path broken underneath: `correct` has to come out false

def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    from bigdl_tpu.optim import Adam
    real = Adam.update

    def frozen(self, grads, params, opt_state, lr):
        _, new_state = real(self, grads, params, opt_state, lr)
        return params, new_state

    monkeypatch.setattr(Adam, "update", frozen)
    out = bm_util.run_tiny(root, "tiny_train")
    assert out["correct"] is False
    assert out["compared"]["delta_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(root, monkeypatch):
    from bigdl_tpu import nn
    real = nn.LMCriterion._forward

    def half(self, input, target):
        n = input.shape[0] // 2
        return real(self, input[:n], target[:n])

    monkeypatch.setattr(nn.LMCriterion, "_forward", half)
    out = bm_util.run_tiny(root, "tiny_train")
    assert out["correct"] is False
    assert out["compared"]["grad_norm_gap"]["value"] > 10 * 1e-3


def test_the_exchange_between_chips_left_out_is_not_correct(
        root, monkeypatch):
    """zero1 over four virtual devices, every device keeping its own
    gradient for its slice instead of the sum over the devices."""
    import jax
    from bigdl_tpu.parallel import allreduce
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")

    def no_exchange(g, axis, scatter_dimension=0, tiled=True):
        n = jax.lax.psum(1, axis)
        size = g.shape[0] // n
        mine = jax.lax.dynamic_slice_in_dim(
            g, jax.lax.axis_index(axis) * size, size)
        return mine * n

    monkeypatch.setattr(allreduce.lax, "psum_scatter", no_exchange)
    out = bm_util.run_tiny(root, "tiny_dp4")
    assert out["correct"] is False
    assert out["compared"]["grad_norm_gap"]["value"] > 10 * 1e-3


def test_a_step_skipped_inside_the_window_is_not_correct(root, monkeypatch):
    """A NaN planted in the loss of the rows that the first three steps
    were not fed, under the program's own ``skip`` policy: the comparison
    of steps 1-3 sees nothing, the run counts the skipped steps as failed,
    takes their tokens out, and is not correct."""
    import jax.numpy as jnp
    from bigdl_tpu import nn
    from benchmark import traffic, train
    real_build, real_forward = train.build, nn.LMCriterion._forward
    seen = {}

    def build_and_keep(*a, **kw):
        seen["fed"] = real_build(*a, **kw)
        return seen["fed"]

    monkeypatch.setattr(train, "build", build_and_keep)
    sound = bm_util.run_tiny(root, "tiny_train")
    assert sound["correct"] is True and sound["failed"] == 0
    cell = harness.load_cell("tiny_train", root)
    job, m = cell["traffic_data"], cell["config_data"]["model"]
    rows = traffic.train_rows(job, bm_util.SEED, 1, m["vocab_size"])
    fed_first = {tuple(int(t) for t in x.feature()) for x in seen["fed"][2]}
    late = [r for r in rows if tuple(int(t) for t in r[:-1]) not in fed_first]
    assert late, "some row is fed only after the checked steps"
    mark = int(late[0][1])
    assert sum(int(r[1]) == mark for r in rows) == 1

    def poisoned(self, input, target):
        loss = real_forward(self, input, target)
        return jnp.where(jnp.any(target[:, 0] == mark), jnp.nan, loss)

    def build(*a, **kw):
        model, opt, fed, B = real_build(*a, **kw)
        opt.set_nan_policy("skip")
        return model, opt, fed, B

    monkeypatch.setattr(nn.LMCriterion, "_forward", poisoned)
    monkeypatch.setattr(train, "build", build)
    out = bm_util.run_tiny(root, "tiny_train")
    assert out["failed"] >= 1 and out["correct"] is False
    assert all(r["value"] <= r["limit"] for r in out["compared"].values())
    w = out["window"]
    assert w["tokens"] < w["steps"] * w["tokens_per_step"]


# ---- the comparison itself

def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    ref = {"a": 10.0, "b": 1.0, "c": 1e-6}
    gap, leaf = harness.worst_leaf_gap({"a": 10.5, "b": 1.0, "c": 2e-6}, ref)
    assert leaf == "a" and gap == pytest.approx(0.05)
    # the all-but-zero leaf is measured against the median leaf, not itself
    gap, leaf = harness.worst_leaf_gap({"a": 10.0, "b": 1.0, "c": 0.5}, ref)
    assert leaf == "c" and gap == pytest.approx(0.5, rel=1e-3)
    assert harness.excluded_leaves(ref) == {"c"}
    with pytest.raises(ValueError):
        harness.worst_leaf_gap({"a": 1.0}, ref)


@pytest.mark.parametrize("numbers,limits,want", [
    ({"x": 0.1}, {"x": 0.2}, True),
    ({"x": 0.3}, {"x": 0.2}, False),
    ({"x": float("nan")}, {"x": 0.2}, False),
    ({}, {"x": 0.2}, False),
    ({"x": 0.0}, {"x": 0.0}, True),
    ({"x": 0.1, "y": 0.0}, {"x": 0.2}, False),     # y has no limit
])
def test_decide_holds_every_number_to_its_limit(numbers, limits, want):
    ok, rows = harness.decide(numbers, limits)
    assert ok is want and rows["x"]["limit"] == limits["x"]
    assert set(rows) == set(numbers) | set(limits)


def test_decide_fails_a_run_with_a_failed_step():
    assert harness.decide({"x": 0.0}, {"x": 0.1}, failed=1)[0] is False
    assert harness.decide({"x": 0.0}, {"x": 0.1}, failed=0)[0] is True


def test_worst_leaf_diff_is_a_norm_of_a_difference_against_leaf_or_median():
    ref = {"a": 10.0, "b": 1.0, "c": 1e-6}
    worst, leaf = harness.worst_leaf_diff({"a": 0.5, "c": 0.2}, ref)
    assert leaf == "c" and worst == pytest.approx(0.2)
    worst, leaf = harness.worst_leaf_diff({"a": 0.5}, ref)
    assert leaf == "a" and worst == pytest.approx(0.05)
    kinds = harness.by_kind({"block0/w": 0.5, "block1/w": 0.1, "embed": 1.0},
                            {"block0/w": 1.0, "block1/w": 1.0, "embed": 2.0})
    assert kinds["w"] == (pytest.approx(0.3), pytest.approx(0.5))
    assert kinds["embed"] == (0.5, 0.5)


@pytest.mark.parametrize("state", ["bfloat16", "float32"])
def test_the_lower_precision_control_is_not_correct_at_test_size(root, state):
    """The control: the reference put in the program's place, in bfloat16
    throughout or (``float32`` state) in its forward and backward passes
    alone. At the tests' size both fail the tiny cell's limits, as they
    fail the real cells' at theirs (PERF.md has those readings)."""
    import jax.numpy as jnp
    from benchmark import reference, traffic, train, weights
    cell = harness.load_cell("tiny_train", root)
    m, job = cell["config_data"]["model"], cell["traffic_data"]
    o = cell["config_data"]["entry"]["optimizer"]
    rows = traffic.train_rows(job, 11, 1, m["vocab_size"])
    b = [(rows[i * 2:i * 2 + 2, :-1], rows[i * 2:i * 2 + 2, 1:])
         for i in range(3)]
    ref = reference.train_steps(weights.make_params(m, 11), b, m, o,
                                row_block=1, keep_first_grad=True)
    low = reference.train_steps(weights.make_params(m, 11), b, m, o,
                                row_block=1, dtype=jnp.bfloat16,
                                state_dtype=jnp.dtype(state),
                                keep_first_grad=True)
    diff = reference.diff_norms(low["first_grad"], ref["first_grad"])
    ok, rows_ = harness.decide(
        train.compare(low, dict(ref, grad_diff_norms=diff)), cell["limits"])
    assert ok is False, rows_
    assert rows_["grad_diff"]["value"] > rows_["grad_diff"]["limit"]


@pytest.mark.parametrize("cell", ["tiny_train", "tiny_dp4"])
def test_the_probe_holds_controls_and_faults_to_the_cells_limits(
        root, cell, capsys):
    """``benchmark.probe`` reads the controls and the faults by the run's
    own comparison and ``decide``: every one comes out not correct."""
    from benchmark import probe
    probe.probe(harness.load_cell(cell, root), [7])
    lines = [json.loads(line[len("probe: "):]) for line in
             capsys.readouterr().out.splitlines()
             if line.startswith("probe: ")]
    want = {"control_bf16", "control_bf16_pass", "fault_half_batch"}
    if cell == "tiny_dp4":
        want.add("fault_no_exchange")
    assert {x["what"] for x in lines} == want
    assert all(x["correct"] is False for x in lines), lines
