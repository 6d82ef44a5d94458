"""The program's spans on the profiler's clock: inside a ``jax.profiler``
session every ``obs.span`` is a ``TraceAnnotation`` of the same name in
the profile's host plane, enabled or not; outside one, and disabled, it
records nothing anywhere."""
import glob
import os
import threading

import jax
import pytest
from jax.profiler import ProfileData

from bigdl_tpu import observability as obs


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def host_events(log_dir, prefixes=("step", "stager", "t/")):
    """{name: [(line index, start, end, stats)]} of the host plane's events
    whose names start with one of ``prefixes``."""
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.setdefault(e.name, []).append(
                        (li, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return out


def profiled(tmp_path, body):
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return host_events(str(tmp_path))


def one_step(step_num):
    with obs.span("step", step_num=step_num):
        with obs.span("step/data_fetch"):
            pass
        with obs.span("step/dispatch", k=3) as sp:
            sp.annotate(rows=16)


def test_a_disabled_span_lands_in_the_profile_under_its_own_name(tmp_path):
    assert not obs.enabled()
    ev = profiled(tmp_path, lambda: one_step(7))
    assert set(ev) == {"step", "step/data_fetch", "step/dispatch"}
    (line, s0, s1, stats), = ev["step"]
    # the step is the profiler's step boundary, with its number
    assert stats["step_num"] == 7 and stats["_r"] == 1
    (dline, d0, d1, dstats), = ev["step/dispatch"]
    assert dstats == {"k": 3, "rows": 16}
    # children nest under the step, on the step's own thread line
    assert dline == line and s0 <= d0 <= d1 <= s1
    (fline, f0, f1, _), = ev["step/data_fetch"]
    assert fline == line and s0 <= f0 <= f1 <= d0
    # and the in-memory tracer kept nothing
    assert obs.get_tracer().events() == []


def test_outside_a_session_a_disabled_span_records_nothing(tmp_path):
    assert not obs.enabled()
    one_step(1)                      # no session: the profiler's no-op
    ev = profiled(tmp_path, lambda: None)
    assert ev == {} and obs.get_tracer().events() == []
    sp = obs.span("step/dispatch")
    with sp:
        pass
    assert sp.duration_s == 0.0 and sp.annotate(x=1) is sp


def test_an_enabled_span_goes_to_both_sinks_under_one_name(tmp_path):
    obs.enable()
    ev = profiled(tmp_path, lambda: one_step(9))
    assert ev["step"][0][3]["step_num"] == 9
    assert ev["step/dispatch"][0][3] == {"k": 3, "rows": 16}
    got = {e.name: e for e in obs.get_tracer().events()}
    assert set(got) == set(ev)
    assert got["step"].args == {"step_num": 9}
    assert got["step/dispatch"].args == {"k": 3, "rows": 16}
    assert got["step/dispatch"].depth == 1


def test_another_threads_span_comes_through_on_its_own_line(tmp_path):
    def body():
        def worker():
            with obs.span("stager/source_wait"):
                pass
        with obs.span("step", step_num=0):
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
    ev = profiled(tmp_path, body)
    assert ev["stager/source_wait"][0][0] != ev["step"][0][0]


def test_the_training_loop_emits_its_spans_into_a_profile_when_disabled(
        tmp_path):
    """LeNet through ``Optimizer`` with tracing disabled, profiled: every
    iteration is a ``step`` with its four children, the stager's wait is
    on another line, and the dispatch path is the plain jit's."""
    import numpy as np
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.optim import SGD, LocalOptimizer
    from bigdl_tpu.optim.trigger import max_iteration
    rng = np.random.RandomState(0)
    samples = [Sample(rng.randn(8).astype(np.float32),
                      np.float32(rng.randint(1, 3))) for _ in range(16)]
    model = nn.Sequential().add(nn.Linear(8, 2)).add(nn.LogSoftMax())
    opt = LocalOptimizer(model=model, training_set=DataSet.array(samples),
                         criterion=nn.ClassNLLCriterion(),
                         optim_method=SGD(learningrate=0.1), batch_size=4)
    opt.set_end_when(max_iteration(3))
    ev = profiled(tmp_path, opt.optimize)
    assert [e[3]["step_num"] for e in ev["step"]] == [0, 1, 2]
    for child in ("step/data_fetch", "step/dispatch", "step/loss_sync",
                  "step/triggers"):
        assert len(ev[child]) == 3, child
        for (_, c0, c1, _), (_, s0, s1, _) in zip(ev[child], ev["step"]):
            assert s0 <= c0 <= c1 <= s1, child
    assert ev["stager/source_wait"][0][0] != ev["step"][0][0]
    assert not obs.enabled() and obs.get_tracer().events() == []
    assert opt._step_fn.compiled_shape_count() == 0    # the AOT path unused
