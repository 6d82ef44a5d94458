"""The performance introspection plane (PR 7): compiled-program
artifacts at every compile-site kind, live MFU gauges vs the offline
bench math, cluster metric aggregation with straggler attribution, and
the perf-regression gate's exit codes."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu import observability as obs
from bigdl_tpu.observability import cluster, perf

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_perf(monkeypatch, tmp_path):
    """Isolated observability + artifact registry + flight dir; peak
    FLOPs pinned so MFU is well-defined on CPU."""
    monkeypatch.setenv("BIGDL_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setenv("BIGDL_TPU_PEAK_FLOPS", "1e9")
    monkeypatch.delenv("BIGDL_TPU_METRIC_SNAP_S", raising=False)
    obs.disable()
    obs.reset()
    obs.registry().reset()
    perf.reset()
    yield
    obs.disable()
    obs.reset()
    obs.registry().reset()
    perf.reset()


def _mlp(d_in=8):
    return nn.Sequential(nn.Linear(d_in, 16), nn.ReLU(), nn.Linear(16, 1))


def _train(steps=6, superstep=1, batch=16, model=None):
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import max_iteration
    rng = np.random.RandomState(0)
    x = rng.randn(steps * batch, 8).astype(np.float32)
    y = rng.randn(steps * batch, 1).astype(np.float32)
    opt = LocalOptimizer(model or _mlp(), (x, y), nn.MSECriterion(),
                         optim_method=SGD(learningrate=0.01),
                         end_trigger=max_iteration(steps),
                         batch_size=batch)
    if superstep > 1:
        opt.set_superstep(superstep)
    opt.optimize()
    return opt


# ------------------------------------------------- artifact capture

def test_optimizer_step_records_artifact():
    obs.enable()
    _train(steps=4)
    arts = [a for a in perf.registry().artifacts()
            if a.name == "optim/step"]
    assert len(arts) == 1, arts
    a = arts[0]
    assert a.kind == "train_step" and a.steps_per_program == 1
    assert a.compile_seconds > 0
    assert a.input_shapes, a.to_dict()
    # CPU XLA exposes cost analysis: FLOPs and memory present
    assert a.flops and a.flops > 0
    assert a.resident_bytes() and a.resident_bytes() > 0
    assert a.degraded is None
    # mirrored into the metrics registry for the exporters
    assert obs.registry().counter("compile/programs").value == 1


def test_superstep_program_records_k():
    obs.enable()
    _train(steps=4, superstep=2)
    a = perf.registry().latest("optim/step")
    assert a is not None and a.steps_per_program == 2
    # the [K, batch, ...] stack is visible in the recorded shapes
    assert any(s.startswith("(2, ") for s in a.input_shapes), \
        a.input_shapes


def test_evaluator_forward_records_artifacts():
    from bigdl_tpu.optim.evaluator import Evaluator
    from bigdl_tpu.optim.validation import Loss, Top1Accuracy
    obs.enable()
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    m.ensure_initialized()
    rng = np.random.RandomState(1)
    x = rng.randn(32, 8).astype(np.float32)
    y = rng.randint(1, 5, (32,)).astype(np.int64)
    from bigdl_tpu.dataset.dataset import DataSet
    ds = DataSet.from_arrays(x, y)
    Evaluator(m).evaluate(ds, [Top1Accuracy()], batch_size=16)
    names = {a.name for a in perf.registry().artifacts()}
    assert "eval/forward_stats" in names, names


def test_predictor_and_serving_warmup_record_bucket_artifacts():
    from bigdl_tpu.serving import ServingEngine
    from bigdl_tpu.optim.predictor import shape_buckets
    obs.enable()
    m = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    m.ensure_initialized()
    eng = ServingEngine(m, input_shape=(4,), max_batch=8, warmup=True)
    with eng:
        eng.predict(np.zeros(4, np.float32), timeout=30)
    fwd_arts = [a for a in perf.registry().artifacts()
                if a.name.startswith("predict/forward")]
    # one artifact per warmup bucket; the live request reuses bucket 1
    assert len(fwd_arts) == len(shape_buckets(8)), fwd_arts
    assert all(a.kind == "forward" for a in fwd_arts)


def test_disabled_observability_records_nothing():
    _train(steps=3)
    assert perf.registry().artifacts() == []
    assert obs.registry().get("perf/mfu") is None


def test_analyze_compiled_degrades_without_apis():
    class NoApis:
        pass

    class RaisingApis:
        def cost_analysis(self):
            raise NotImplementedError("backend says no")

        def memory_analysis(self):
            raise NotImplementedError

    assert perf.analyze_compiled(NoApis()) == {}
    assert perf.analyze_compiled(RaisingApis()) == {}
    art = perf.record_compiled("x", "forward", NoApis())
    assert art.degraded and art.flops is None


def test_instrumented_jit_compile_failure_propagates_once():
    """A lower/compile failure is the program's own (a Mosaic refusal on
    the chip): it propagates, and the wrapper does NOT mark itself broken
    and pay for a second compile through the plain jit path."""
    obs.enable()
    calls = {"lower": 0, "jit": 0}

    class BrokenLower:
        def __call__(self, *args):
            calls["jit"] += 1
            return args[0]

        def lower(self, *args):
            calls["lower"] += 1
            raise RuntimeError("Mosaic refused the kernel")

    wrapped = perf.instrument_jit(BrokenLower(), name="t/broken",
                                  kind="forward")
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        wrapped(jnp.ones((3,)))
    assert calls == {"lower": 1, "jit": 0}
    assert perf.registry().latest("t/broken") is None
    assert obs.registry().counter("compile/degraded").value == 0
    # still armed: the next call reports the same failure, not a silent
    # jit-path success
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        wrapped(jnp.ones((3,)))
    assert calls["jit"] == 0


def test_instrumented_jit_one_compile_per_shape():
    obs.enable()
    calls = {"n": 0}

    def f(x):
        calls["n"] += 1  # traced once per distinct shape
        return x + 1

    wrapped = perf.instrument_jit(jax.jit(f), name="t/shapes",
                                  kind="forward")
    for _ in range(3):
        wrapped(jnp.ones((4,)))
    wrapped(jnp.ones((8,)))
    assert wrapped.compiled_shape_count() == 2
    assert len(perf.registry().artifacts()) == 2
    assert wrapped.last_artifact is perf.registry().artifacts()[-1]


# ------------------------------------------------------- live MFU

def test_live_mfu_agrees_with_offline_bench_math(monkeypatch):
    """The acceptance bar: perf/mfu_mean within 10% of the MFU computed
    offline the way bench.py computes it — XLA cost-analysis FLOPs of
    the SAME compiled program over the measured step wall time, against
    the same peak table."""
    # the CPU is not in the peak table: MFU needs the explicit ceiling
    monkeypatch.setenv("BIGDL_TPU_PEAK_FLOPS", "1e12")
    obs.enable()
    opt = _train(steps=8)
    reg = obs.registry()
    live = reg.gauge("perf/mfu_mean").value
    assert live > 0

    art = perf.registry().latest("optim/step")
    # offline: bench.py's formula — flops * dispatches / wall / peak —
    # over the measured (non-compile) FULL iteration walls (fetch +
    # step: the gauge divides by the whole iteration so async sync
    # policies can't flatter it)
    walls = [d + s for d, s in zip(opt.metrics.values["data_time"][1:],
                                   opt.metrics.values["step_time"][1:])]
    offline = (art.flops * len(walls)) / sum(walls) / perf.peak_flops("")
    assert live == pytest.approx(offline, rel=0.10), (live, offline)
    # instantaneous gauge and flops throughput exist alongside
    assert reg.gauge("perf/mfu").value > 0
    assert reg.gauge("perf/model_flops_per_s").value > 0


def test_live_mfu_flops_match_independent_aot_compile():
    """The artifact's FLOPs equal an independent AOT cost analysis of
    an equivalent program — the live gauge inherits XLA's number, not a
    hand-rolled estimate."""
    obs.enable()
    _train(steps=3)
    art = perf.registry().latest("optim/step")
    assert art.flops > 0
    # independent: any second compile of the same-shape step must agree
    # to within float noise; sanity-bound against the analytic FLOPs of
    # the MLP instead of recompiling the whole step (fwd+bwd+SGD of an
    # 8->16->1 MLP at batch 16 is O(10k) flops, not O(1M))
    assert 1e3 < art.flops < 1e6


def test_phase_decomposition_fractions():
    obs.enable()
    _train(steps=6)
    reg = obs.registry()
    host = reg.gauge("perf/phase_host_frac").value
    disp = reg.gauge("perf/phase_dispatch_frac").value
    dev = reg.gauge("perf/phase_device_frac").value
    for v in (host, disp, dev):
        assert 0.0 <= v <= 1.0, (host, disp, dev)
    assert host + disp + dev == pytest.approx(1.0, abs=0.05), \
        (host, disp, dev)


def test_peak_flops_table_and_env_override(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_PEAK_FLOPS", raising=False)
    assert perf.peak_flops("TPU v5 lite") == 197.0e12
    assert perf.peak_flops("TPU v5p chip") == 459.0e12
    # a device the table does not know is an error, not an assumed v5e
    for kind in ("cpu", "", "unknown accelerator"):
        with pytest.raises(perf.UnknownDeviceError, match="device_kind"):
            perf.peak_flops(kind)
    monkeypatch.setenv("BIGDL_TPU_PEAK_FLOPS", "2.5e12")
    assert perf.peak_flops("TPU v5 lite") == 2.5e12
    assert perf.peak_flops("cpu") == 2.5e12


def test_no_mfu_gauge_without_a_known_peak(monkeypatch):
    """On a device with no known ceiling the step loop still publishes
    FLOP/s and the phase split, and leaves the MFU gauges unpublished."""
    monkeypatch.delenv("BIGDL_TPU_PEAK_FLOPS", raising=False)
    obs.enable()
    _train(steps=4)
    reg = obs.registry()
    assert reg.gauge("perf/model_flops_per_s").value > 0
    assert reg.get("perf/mfu") is None and reg.get("perf/mfu_mean") is None


def test_step_perf_peak_unsticks_when_env_unset(monkeypatch):
    """A smoke-phase BIGDL_TPU_PEAK_FLOPS override must not survive
    unsetting the env in the same process (a cached 1e9 would read MFU
    ~200,000x high on the real chip)."""
    sp = perf._StepPerf()
    monkeypatch.setenv("BIGDL_TPU_PEAK_FLOPS", "1e9")
    assert sp.peak() == 1e9
    monkeypatch.delenv("BIGDL_TPU_PEAK_FLOPS")
    assert sp.peak() is None  # re-resolved: the CPU has no table entry
    monkeypatch.setenv("BIGDL_TPU_PEAK_FLOPS", "3e9")
    assert sp.peak() == 3e9  # and a CHANGED override re-resolves too


def test_clamped_superstep_artifact_records_its_own_k():
    """A checkpoint trigger firing mid-group clamps the dispatch to a
    j<K prefix, which compiles a SEPARATE program — its artifact must
    record j steps, not the configured K (flops_per_step would read
    K/j-fold low otherwise)."""
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.optim.trigger import max_iteration
    import tempfile
    obs.enable()
    rng = np.random.RandomState(0)
    x = rng.randn(96, 8).astype(np.float32)
    y = rng.randn(96, 1).astype(np.float32)
    opt = LocalOptimizer(_mlp(), (x, y), nn.MSECriterion(),
                         optim_method=SGD(learningrate=0.01),
                         end_trigger=max_iteration(6), batch_size=16)
    opt.set_superstep(4)
    # checkpoint at every 2nd iteration: groups clamp to 2-step prefixes
    from bigdl_tpu.optim.trigger import several_iteration
    opt.set_checkpoint(several_iteration(2), tempfile.mkdtemp())
    opt.optimize()
    ks = sorted({a.steps_per_program for a in perf.registry().artifacts()
                 if a.name == "optim/step"})
    assert ks == [2], ks  # every dispatched program really ran 2 steps
    for a in perf.registry().artifacts():
        if a.name == "optim/step":
            assert any(s.startswith("(2, ") for s in a.input_shapes)


def test_bench_peak_table_is_the_shared_one():
    """bench.py's offline MFU and the live gauge read the same table."""
    sys.path.insert(0, _REPO)
    try:
        import bench
        assert bench._peak_flops("TPU v5 lite") == \
            perf.peak_flops("TPU v5 lite")
    finally:
        sys.path.remove(_REPO)


# ------------------------------------------- artifact dump + report

def test_dump_artifacts_and_xla_report_round_trip(tmp_path):
    obs.enable()
    _train(steps=3)
    obs.registry().gauge("mem/device_peak_bytes", unit="bytes").set(1e9)
    path = perf.dump_artifacts()
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["schema"] == perf.ARTIFACT_SCHEMA
    assert any(p["name"] == "optim/step" for p in doc["programs"])
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "xla_report.py"),
         path], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "optim/step" in proc.stdout
    assert "HBM headroom" in proc.stdout
    # unreadable dump: exit 2
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "xla_report.py"),
         str(bad)], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2


def test_crash_bundle_carries_programs():
    from bigdl_tpu.observability import flight
    obs.enable()
    _train(steps=2)
    bundle = flight.crash_bundle(error=RuntimeError("x"))
    assert any(p["name"] == "optim/step" for p in bundle["programs"])


# ------------------------------------------------- cluster metrics

def _write_snapshot(d, idx, step_time_mean, hb_age=0.5, step=100,
                    final=False):
    """A per-process snapshot file in the writer's exact schema."""
    doc = {
        "final": final,
        "schema": cluster.SNAPSHOT_SCHEMA,
        "written_at": time.time(),
        "pid": 1000 + idx,
        "process_index": idx,
        "step": step,
        "metrics": {
            "optim/step_time": {"type": "histogram", "unit": "",
                                "count": 10, "sum": step_time_mean * 10,
                                "mean": step_time_mean,
                                "min": step_time_mean,
                                "max": step_time_mean, "quantiles": {}},
            "optim/throughput": {"type": "gauge", "unit": "samples/s",
                                 "value": 16.0 / step_time_mean},
            "failure/last_beat_age_s": {"type": "gauge", "unit": "s",
                                        "value": hb_age},
        },
    }
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"metrics_p{idx:05d}.json"), "w") as f:
        json.dump(doc, f)


def test_snapshot_writer_cadence_and_atomicity(tmp_path):
    w = cluster.MetricSnapshotWriter(every_s=3600, directory=str(tmp_path),
                                     process_index=7)
    obs.registry().counter("optim/steps").inc(5)
    assert w.maybe_write(step=5)  # first call writes immediately
    assert w.maybe_write(step=6) is None  # cadence not elapsed
    assert w.writes == 1
    snaps = cluster.read_snapshots(str(tmp_path))
    assert len(snaps) == 1 and snaps[0]["process_index"] == 7
    assert snaps[0]["step"] == 5
    assert snaps[0]["metrics"]["optim/steps"]["value"] == 5
    # zero interval: disabled entirely
    w0 = cluster.MetricSnapshotWriter(every_s=0, directory=str(tmp_path))
    assert w0.maybe_write() is None and not w0.enabled


def test_rank0_aggregation_attributes_injected_straggler(tmp_path):
    d = str(tmp_path)
    _write_snapshot(d, 0, 0.010)
    _write_snapshot(d, 1, 0.011)
    _write_snapshot(d, 2, 0.033, hb_age=120.0)  # slow AND stale: dying
    # a torn write from a dying peer is skipped, not fatal
    with open(os.path.join(d, "metrics_p00003.json"), "w") as f:
        f.write('{"schema": "bigdl_tpu.metric_snapshot.v1", "wri')
    view = cluster.aggregate(d)
    assert view["n_processes"] == 3
    assert view["step_time_skew"] == pytest.approx(3.0, rel=0.01)
    assert len(view["stragglers"]) == 1
    s = view["stragglers"][0]
    assert s["process_index"] == 2 and s["suspect_dead"] is True
    assert s["heartbeat_age_s"] == 120.0

    out = cluster.write_aggregate(d, context={"elastic_attempt": 1})
    assert out and os.path.exists(out)
    saved = json.load(open(out))
    assert saved["context"]["elastic_attempt"] == 1
    assert cluster.latest_aggregate(d) == out
    # headline numbers mirrored for the local exporters
    assert obs.registry().gauge("cluster/stragglers").value == 1


def test_finished_process_not_attributed_as_suspect_dead(tmp_path):
    """ISSUE 15 satellite: a replica process that exited CLEANLY writes
    a terminal ``final: true`` snapshot — its step-time mean freezes
    and its heartbeat age grows forever, which used to read exactly
    like a wedged process. The aggregate must attribute the WEDGED
    writer (no final marker, slow, stale heartbeat) and skip the
    finished one."""
    d = str(tmp_path)
    _write_snapshot(d, 0, 0.010)
    _write_snapshot(d, 3, 0.011)
    # finished: slow-looking frozen mean + very stale heartbeat, but
    # terminal final:true — retired, not dying
    _write_snapshot(d, 1, 0.060, hb_age=500.0, final=True)
    # wedged: same signature WITHOUT the final marker — a real suspect
    _write_snapshot(d, 2, 0.060, hb_age=500.0)
    view = cluster.aggregate(d)
    assert view["n_processes"] == 4
    by_idx = {r["process_index"]: r for r in view["processes"]}
    assert by_idx[1]["final"] is True and by_idx[2]["final"] is False
    assert [s["process_index"] for s in view["stragglers"]] == [2]
    assert view["stragglers"][0]["suspect_dead"] is True

    # the writer's own terminal write carries the marker
    w = cluster.MetricSnapshotWriter(every_s=3600, directory=d,
                                     process_index=7)
    w.write(step=9, final=True)
    snaps = {s["process_index"]: s for s in cluster.read_snapshots(d)}
    assert snaps[7]["final"] is True
    assert snaps[0].get("final", False) is False


def test_snapshot_writer_extra_sections(tmp_path):
    """MetricSnapshotWriter.add_section: a registered provider's dict
    lands in every snapshot under its name (the fleet agent's
    ``serving`` section rides this); a raising provider is skipped, a
    core-field collision is refused."""
    w = cluster.MetricSnapshotWriter(every_s=3600, directory=str(tmp_path),
                                     process_index=3)
    w.add_section("serving", lambda: {"queue_depth": 4,
                                      "active_version": "v1"})
    w.add_section("broken", lambda: 1 / 0)
    with pytest.raises(ValueError, match="collides"):
        w.add_section("metrics", dict)
    w.write(step=1)
    snap = cluster.read_snapshots(str(tmp_path))[0]
    assert snap["serving"] == {"queue_depth": 4, "active_version": "v1"}
    assert "broken" not in snap


def test_cluster_report_tool_round_trip(tmp_path):
    d = str(tmp_path)
    _write_snapshot(d, 0, 0.010)
    _write_snapshot(d, 1, 0.040, hb_age=99.0)
    prom = os.path.join(d, "cluster.prom")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "cluster_report.py"),
         d, "--prom", prom], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "stragglers: 1" in proc.stdout
    assert "DYING" in proc.stdout
    text = open(prom).read()
    assert 'bigdl_cluster_step_time_mean_s{process="1"} 0.04' in text
    assert "bigdl_cluster_step_time_skew" in text
    # empty dir: exit 2 (nothing to merge)
    empty = tmp_path / "empty"
    empty.mkdir()
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "cluster_report.py"),
         str(empty)], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2


def test_optimizer_ticks_snapshots_under_env(monkeypatch, tmp_path):
    d = str(tmp_path / "snaps")
    monkeypatch.setenv("BIGDL_TPU_FLIGHT_DIR", d)
    monkeypatch.setenv("BIGDL_TPU_METRIC_SNAP_S", "0.01")
    obs.enable()
    _train(steps=4)
    snaps = cluster.read_snapshots(d)
    assert len(snaps) == 1  # one process, latest-state file
    assert snaps[0]["step"] == 4  # terminal snapshot carries end state


def test_elastic_restart_writes_cluster_aggregate(monkeypatch, tmp_path):
    """ElasticRunner merges the per-process snapshots at every restart
    (one coherent timeline across the reshape)."""
    from bigdl_tpu.parallel.elastic import ElasticRunner
    from bigdl_tpu.parallel.failure import TrainingHalted
    d = str(tmp_path / "flight")
    monkeypatch.setenv("BIGDL_TPU_FLIGHT_DIR", d)
    _write_snapshot(d, 0, 0.02)

    class FakeOpt:
        def __init__(self):
            self.calls = 0

        def load_checkpoint(self, p):
            pass

        def optimize(self):
            raise TrainingHalted(cause="stall", failure_class="permanent",
                                 checkpoint_path=None, bundle_path=None,
                                 epoch=1, neval=3, lost_processes=())

    class Dev:
        process_index = 0

    runner = ElasticRunner(lambda devices, attempt: FakeOpt(),
                           checkpoint_dir=str(tmp_path / "ckpt"),
                           max_restarts=1, devices=[Dev()],
                           backoff_s=0.0)
    with pytest.raises(TrainingHalted):
        runner.run()
    assert runner.restarts == 1
    agg = cluster.latest_aggregate(d)
    assert agg is not None
    saved = json.load(open(agg))
    # both halts post-mortem: the restart (attempt 0) and the terminal
    # budget exhaustion (attempt 1) each merged a view; latest wins
    assert saved["context"]["elastic_attempt"] == 1
    assert saved["context"]["cause"] == "stall"


# ------------------------------------------------- perf gate

def _gate(args, **kw):
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "perf_gate.py")]
        + args, capture_output=True, text=True, timeout=120, **kw)


def _metrics_file(tmp_path, name, rows):
    p = tmp_path / name
    p.write_text(json.dumps(rows))
    return str(p)


_ROWS = [
    {"metric": "bench/x_images_per_sec", "value": 100.0,
     "unit": "images/sec/chip", "kind": "gauge"},
    {"metric": "bench/y_p99_ms", "value": 20.0, "unit": "ms",
     "kind": "gauge"},
    {"metric": "bench/x_images_per_sec/mfu", "value": 0.30, "unit": "",
     "kind": "gauge"},
    {"metric": "bench/x_images_per_sec/vs_baseline", "value": 1.7,
     "unit": "", "kind": "gauge"},  # provenance: not gated
]


def test_perf_gate_pass_fail_exit_codes(tmp_path):
    cur = _metrics_file(tmp_path, "cur.json", _ROWS)
    base = str(tmp_path / "base.json")
    assert _gate(["--current", cur, "--baseline", base,
                  "--update"]).returncode == 0
    # identical metrics: pass
    assert _gate(["--current", cur, "--baseline", base]).returncode == 0

    # >= 20% throughput regression: fail (band is 15%)
    worse = [dict(r) for r in _ROWS]
    worse[0]["value"] = 79.0
    cur2 = _metrics_file(tmp_path, "cur2.json", worse)
    p = _gate(["--current", cur2, "--baseline", base])
    assert p.returncode == 1
    assert "bench/x_images_per_sec" in p.stderr

    # within the band: pass
    ok = [dict(r) for r in _ROWS]
    ok[0]["value"] = 90.0
    cur3 = _metrics_file(tmp_path, "cur3.json", ok)
    assert _gate(["--current", cur3, "--baseline", base]).returncode == 0


def test_perf_gate_latency_direction(tmp_path):
    cur = _metrics_file(tmp_path, "cur.json", _ROWS)
    base = str(tmp_path / "base.json")
    _gate(["--current", cur, "--baseline", base, "--update"])
    # p99 RISING 50% is a regression even though the number went up
    worse = [dict(r) for r in _ROWS]
    worse[1]["value"] = 30.0
    cur2 = _metrics_file(tmp_path, "cur2.json", worse)
    p = _gate(["--current", cur2, "--baseline", base])
    assert p.returncode == 1 and "bench/y_p99_ms" in p.stderr
    # p99 dropping is an improvement, not a failure
    better = [dict(r) for r in _ROWS]
    better[1]["value"] = 10.0
    cur3 = _metrics_file(tmp_path, "cur3.json", better)
    p = _gate(["--current", cur3, "--baseline", base])
    assert p.returncode == 0 and "IMPROVED" in p.stdout


def test_perf_gate_missing_files_pass_unless_strict(tmp_path):
    missing = str(tmp_path / "nope.json")
    assert _gate(["--current", missing]).returncode == 0
    assert _gate(["--current", missing, "--strict"]).returncode == 1
    cur = _metrics_file(tmp_path, "cur.json", _ROWS)
    nobase = str(tmp_path / "nobase.json")
    assert _gate(["--current", cur, "--baseline", nobase]).returncode == 0
    assert _gate(["--current", cur, "--baseline", nobase,
                  "--strict"]).returncode == 1


def test_perf_gate_provenance_gauges_not_gated(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_perf_gate", os.path.join(_REPO, "tools", "perf_gate.py"))
    perf_gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(perf_gate)
    picked = perf_gate.gated_metrics(_ROWS)
    assert "bench/x_images_per_sec" in picked
    assert "bench/y_p99_ms" in picked
    assert picked["bench/y_p99_ms"]["direction"] == "lower"
    assert "bench/x_images_per_sec/mfu" in picked  # MFU IS perf
    assert "bench/x_images_per_sec/vs_baseline" not in picked


def test_repo_baseline_gates_current_metrics():
    """The committed pin passes against the committed BENCH_METRICS —
    the tier-1 `make perf-gate` contract."""
    p = _gate([], cwd=_REPO)
    assert p.returncode == 0, p.stderr + p.stdout
