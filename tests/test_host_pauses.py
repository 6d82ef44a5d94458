"""The host's pauses inside the program's tracing: Python's collector as
``host/gc`` spans on the thread that runs it and as the ``step`` span's
``host/gc_*`` counters, the epoch boundary as one ``epoch/turnover`` span;
and ``tools/step_pauses.py``, which reads both back from a profile."""
import gc
import glob
import os
import sys
import threading
import tracemalloc

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bigdl_tpu import nn
from bigdl_tpu import observability as obs
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.dataset.transformer import Transformer
from bigdl_tpu.observability import trace as obs_trace
from bigdl_tpu.optim import SGD, LocalOptimizer, Trigger
from bigdl_tpu.optim.trigger import max_iteration

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import step_pauses  # noqa: E402

COUNTERS = ("host/gc_ms", "host/gc_collections", "host/gc_full")


def hooked():
    return obs_trace._on_gc in gc.callbacks


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()
    assert not hooked()


def host_events(log_dir):
    """[(name, line, start, end, stats)] of the host plane's events."""
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return [(e.name, li, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for li, line in enumerate(plane.lines) for e in line.events]


def profiled(tmp_path, body):
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return host_events(str(tmp_path))


class CollectAt(Transformer):
    """Passes samples through and runs a full collection as the
    ``at``-th sample is fetched."""

    def __init__(self, at):
        self.at = at

    def apply(self, it):
        for i, s in enumerate(it):
            if i == self.at:
                gc.collect()
            yield s


def optimizer(n=16, batch=4, transform=None, end=None):
    rng = np.random.RandomState(0)
    samples = [Sample(rng.randn(8).astype(np.float32),
                      np.float32(rng.randint(1, 3))) for _ in range(n)]
    ds = DataSet.array(samples)
    if transform is not None:
        ds = ds.transform(transform)
    model = nn.Sequential().add(nn.Linear(8, 2)).add(nn.LogSoftMax())
    opt = LocalOptimizer(model=model, training_set=ds,
                         criterion=nn.ClassNLLCriterion(),
                         optim_method=SGD(learningrate=0.1), batch_size=batch)
    opt.set_end_when(end or max_iteration(3))
    return opt


# ------------------------------------------------------------- the hook

def test_the_hook_is_counted_and_in_the_callbacks_once():
    assert not hooked()
    obs.gc_hook_install()
    obs.gc_hook_install()
    assert gc.callbacks.count(obs_trace._on_gc) == 1
    obs.gc_hook_remove()
    assert hooked()
    obs.gc_hook_remove()
    assert not hooked()
    obs.gc_hook_remove()            # one remove too many undoes nothing
    assert not hooked()


def test_every_collection_is_counted_once_without_growing_memory():
    """The callback neither recurses nor allocates without bound: with
    the automatic collector off, N forced collections are N counted, N
    full, and the heap after 2,000 of them is what it was."""
    was = gc.isenabled()
    gc.disable()
    obs.gc_hook_install()
    try:
        pauses = obs.GcPauses()
        for _ in range(50):
            gc.collect(2)
        ms, n, full = pauses.take()
        assert (n, full) == (50, 50) and ms > 0
        for _ in range(20):
            gc.collect(0)
        ms, n, full = pauses.take()
        assert (n, full) == (20, 0) and ms > 0
        assert pauses.take() == (0.0, 0, 0)
        tracemalloc.start()
        for _ in range(100):
            gc.collect(0)
        before = tracemalloc.take_snapshot()
        for _ in range(2000):
            gc.collect(0)
        after = tracemalloc.take_snapshot()
        tracemalloc.stop()
        grown = sum(s.size_diff for s in after.compare_to(before, "filename")
                    if s.traceback[0].filename == obs_trace.__file__)
        assert grown < 4096, grown
        assert pauses.take()[1] == 2100
    finally:
        obs.gc_hook_remove()
        if was:
            gc.enable()


def test_a_collection_on_another_thread_is_counted_and_spanned_there(
        tmp_path):
    obs.gc_hook_install()
    try:
        pauses = obs.GcPauses()

        def body():
            def worker():
                gc.collect()
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
            with obs.span("t/main"):
                pass
        ev = profiled(tmp_path, body)
        ms, n, full = pauses.take()
        assert n >= 1 and full >= 1 and ms > 0
    finally:
        obs.gc_hook_remove()
    main = {e[1] for e in ev if e[0] == "t/main"}
    full_spans = [e for e in ev if e[0] == obs.GC_SPAN
                  and e[4].get("generation") == 2]
    assert full_spans and all(e[1] not in main for e in full_spans)
    stats = full_spans[0][4]
    assert {"generation", "collected", "uncollectable"} <= set(stats)


def test_the_hook_is_gone_after_optimize_returns_and_after_it_raises():
    seen = []

    def end(state):
        seen.append(hooked())
        return state["neval"] >= 2

    optimizer(end=Trigger(end)).optimize()
    assert seen and all(seen) and not hooked()

    def explode(state):
        if state["neval"] >= 2:
            raise RuntimeError("end trigger failed")
        return False

    with pytest.raises(RuntimeError, match="end trigger failed"):
        optimizer(end=Trigger(explode)).optimize()
    assert not hooked()


# --------------------------------------------------------- in the loop

def test_a_step_that_collects_carries_its_span_and_counters(tmp_path):
    """A full collection forced while step 2's batch is fetched (inline
    staging, on the loop's thread): its ``host/gc`` span nests in that
    ``step``, whose counters read it; every step carries the three
    counters, zero allowed."""
    opt = optimizer(n=32, transform=CollectAt(8), end=max_iteration(5))
    opt.set_prefetch(0)
    ev = profiled(tmp_path, opt.optimize)
    steps = sorted((e for e in ev if e[0] == "step"), key=lambda e: e[2])
    assert [e[4]["step_num"] for e in steps] == [0, 1, 2, 3, 4]
    for e in steps:
        assert set(COUNTERS) <= set(e[4]), e[4]
        assert e[4]["host/gc_ms"] >= 0 and e[4]["host/gc_collections"] >= 0
    full = [e for e in ev if e[0] == obs.GC_SPAN
            and e[4]["generation"] == 2]
    assert len(full) == 1
    _, line, g0, g1, stats = full[0]
    assert stats["collected"] >= 0 and stats["uncollectable"] >= 0
    holder, = [e for e in steps if e[1] == line and e[2] <= g0 <= g1 <= e[3]]
    assert holder[4]["step_num"] == 2
    assert holder[4]["host/gc_ms"] > 0
    assert holder[4]["host/gc_collections"] >= 1
    assert holder[4]["host/gc_full"] >= 1
    assert all(e[4]["host/gc_full"] == 0 for e in steps if e is not holder)
    fetch, = [e for e in ev if e[0] == "step/data_fetch"
              and e[2] <= g0 <= g1 <= e[3]]
    assert fetch[1] == line


def test_gc_time_has_one_entry_per_counted_step():
    opt = optimizer(n=16, transform=CollectAt(5), end=max_iteration(6))
    opt.optimize()
    gc_time = opt.metrics.values["gc_time"]
    assert len(gc_time) == 6 == len(opt.metrics.values["data_time"])
    assert all(v >= 0 for v in gc_time) and sum(gc_time) > 0


def test_epoch_turnover_is_one_span_a_boundary(tmp_path):
    """Four steps an epoch, ten steps: two boundaries, each one
    ``epoch/turnover`` between the last step of an epoch and the first of
    the next, with its seconds in ``epoch_turnover_time``."""
    obs.enable()
    opt = optimizer(n=16, end=max_iteration(10))
    opt.optimize()
    spans = obs.get_tracer().events()
    turn = sorted((s for s in spans if s.name == "epoch/turnover"),
                  key=lambda s: s.start_ns)
    assert [s.args["epoch"] for s in turn] == [1, 2]
    assert all(s.depth == 0 for s in turn)
    steps = sorted((s for s in spans if s.name == "step"),
                   key=lambda s: s.start_ns)
    counted = [s for s in steps if "host/gc_ms" in s.args]
    assert len(counted) == 10
    for s in turn:
        before = [p for p in steps if p.end_ns <= s.start_ns]
        after = [p for p in steps if p.start_ns >= s.end_ns]
        assert before and after
        # nothing of a step overlaps the turnover
        assert len(before) + len(after) == len(steps)
    assert len(opt.metrics.values["epoch_turnover_time"]) == 2
    assert all(v > 0 for v in opt.metrics.values["epoch_turnover_time"])


# ------------------------------------------------- reading it back

def _trace(spans, ops, dev="0"):
    return {"devices": {dev: [[0, s, d] for s, d in ops]},
            "spans": spans}


def test_idle_under_a_gc_span_of_a_thread_that_runs_no_step():
    # device busy 0-100 and 300-400 of a 0-400 window: 200 ns idle, of
    # which 50 lie under a collection on the stager's thread
    spans = [["bm/traced", 0, 0, 400, None],
             ["step", 1, 0, 200, {"step_num": 1, "host/gc_ms": 0.5,
                                  "host/gc_collections": 1,
                                  "host/gc_full": 0}],
             ["step", 1, 200, 200, {"step_num": 2, "host/gc_ms": 0.0,
                                    "host/gc_collections": 0,
                                    "host/gc_full": 0}],
             ["host/gc", 2, 150, 50, {"generation": 0}],
             ["host/gc", 2, 350, 40, {"generation": 0}]]
    trace = _trace(spans, [(0, 100), (300, 100)])
    assert step_pauses.gc_ms(trace) == pytest.approx(0.25)
    assert step_pauses.idle_under(trace, [obs.GC_SPAN], steps=2) == \
        pytest.approx(50 / 1e6 / 2)


def test_no_counter_reads_as_nothing_and_no_span_as_zero():
    bare = [["bm/traced", 0, 0, 400, None],
            ["step", 1, 0, 400, {"step_num": 1}]]
    trace = _trace(bare, [(0, 100)])
    assert step_pauses.gc_ms(trace) is None
    assert step_pauses.idle_under(trace, [obs.GC_SPAN], steps=1) is None
    zero = [["bm/traced", 0, 0, 400, None],
            ["step", 1, 0, 400, {"step_num": 1, "host/gc_ms": 0.0,
                                 "host/gc_collections": 0,
                                 "host/gc_full": 0}]]
    trace = _trace(zero, [(0, 100)])
    assert step_pauses.gc_ms(trace) == 0.0
    assert step_pauses.idle_under(trace, [obs.GC_SPAN], steps=1) == 0.0


def test_a_stalled_step_names_what_held_the_device_idle():
    """Three steps of 100 ns of device work; the second waits 300 ns, of
    which 200 lie under a full collection inside its ``step/triggers``
    and 100 under the epoch's turnover that follows."""
    spans = [["step", 1, 0, 110, {"step_num": 0, "host/gc_ms": 0.0,
                                  "host/gc_collections": 0,
                                  "host/gc_full": 0}],
             ["step/dispatch", 1, 5, 5, None],
             ["step", 1, 110, 310, {"step_num": 1, "host/gc_ms": 0.0,
                                    "host/gc_collections": 0,
                                    "host/gc_full": 0}],
             ["step/dispatch", 1, 115, 5, None],
             ["step/triggers", 1, 215, 205, None],
             ["host/gc", 1, 220, 200, {"generation": 2, "collected": 7,
                                       "uncollectable": 0}],
             ["epoch/turnover", 1, 420, 100, {"epoch": 1}],
             ["step", 1, 520, 110, {"step_num": 2, "host/gc_ms": 2e-4,
                                    "host/gc_collections": 1,
                                    "host/gc_full": 1}],
             ["step/dispatch", 1, 525, 5, None]]
    trace = _trace(spans, [(10, 100), (120, 100), (530, 100)])
    trace["events"] = [["/host:CPU 7", "TransferFromDevice", 200, 320],
                       ["/host:CPU 1", "float", 600, 10]]
    rows = step_pauses.by_step(trace)
    assert [r["step"] for r in rows] == [0, 1, 2]
    slow = max(rows, key=lambda r: r["wall_ms"])
    assert slow["step"] == 1
    assert slow["wall_ms"] == pytest.approx(410 / 1e6)
    assert slow["idle_ms"]["host/gc"] == pytest.approx(200 / 1e6)
    assert slow["idle_ms"]["epoch/turnover"] == pytest.approx(100 / 1e6)
    assert slow["gc"] == [{"generation": 2, "collected": 7,
                           "uncollectable": 0, "ms": 200 / 1e6}]
    assert slow["gap"] == [220, 300 / 1e6]
    text = step_pauses.report(rows, top=1, trace=trace)
    assert "host/gc" in text and "TransferFromDevice" in text
    assert slow["during"] == [("/host:CPU 7", "TransferFromDevice",
                               pytest.approx(300 / 1e6))]
