"""The reduction from a trace to numbers: on hand-made intervals, and on a
small trace recorded on the chip (``data/train_trace_trim.json``: the first
steps of this PR's first traced run of ``gpt2m_train_1k``, TPU v5 lite)."""
import json
import os

import pytest

import bm_util  # noqa: F401
from benchmark import trace_reduce as tr

MS = 1_000_000
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "train_trace_trim.json")


def hand_made():
    """Two devices over a 100 ms window. Device 0: busy 0-40 and 50-90,
    an all-reduce alone from 30-40 and hidden behind compute from 60-70."""
    d0 = [["fusion.1", 0, 30 * MS], ["all-reduce.1", 30 * MS, 10 * MS],
          ["flash_kernel", 50 * MS, 20 * MS], ["all-reduce.2", 60 * MS, 10 * MS],
          ["fusion.1", 70 * MS, 20 * MS]]
    d1 = [["fusion.1", 0, 20 * MS], ["all-reduce.1", 20 * MS, 25 * MS]]
    host = [["bm/traced", 0, 100 * MS], ["bm/optimize", 0, 45 * MS],
            ["bm/submit", 88 * MS, 12 * MS]]
    return {"devices": {"0": d0, "1": d1}, "modules": {}, "host": host}


@pytest.mark.parametrize("text,want", [
    ('%jvp__.36 = (f32[16,16,1024,64]{3,2,1,0:T(8,128)}, f32[16,16,1024,128]'
     '{3,2,1,0:T(8,128)}) custom-call(f32[16,16,1024,64]{3,2,1,0:T(8,128)} '
     '%bitcast.147), custom_call_target="tpu_custom_call", operand_layout',
     "jvp__.36 custom-call tpu_custom_call f32[16,16,1024,64]"),
    ('%fusion.479 = (f32[50257,1024]{1,0:T(8,128)}, f32[50257,1024]{1,0}) '
     'fusion(f32[50257,1024]{1,0:T(8,128)} %opt_state), kind=kLoop, '
     'calls=%fused_computation.629', "fusion.479 fusion f32[50257,1024]"),
    ('%all-reduce.3 = f32[1024]{0} all-reduce(f32[1024]{0} %x), channel_id=1',
     "all-reduce.3 all-reduce f32[1024]"),
    ("jit_step(10250020529971544573)", "jit_step(10250020529971544573)"),
])
def test_short_name_keeps_what_tells_operations_apart(text, want):
    assert tr.short_name(text) == want


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (0, 3), (2, 4), (7, 7)]) == [[0, 4], [5, 7]]
    assert tr.total(tr.merge([(0, 10), (5, 20)])) == 20
    assert tr.subtract([[0, 10], [20, 30]], [[2, 4], [8, 22]]) == [
        [0, 2], [4, 8], [22, 30]]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_busy_idle_and_window():
    t = hand_made()
    assert tr.window_seconds(t) == pytest.approx(0.1)
    assert tr.busy_seconds(t) == {"0": pytest.approx(0.08),
                                  "1": pytest.approx(0.045)}
    assert tr.idle_share(t) == pytest.approx(20.0)   # the fullest device


def test_kernel_time_by_name():
    t = hand_made()
    assert tr.kernel_seconds(t, r"flash")["0"] == (pytest.approx(0.02), 1)
    assert tr.kernel_seconds(t, r"^fusion")["0"] == (pytest.approx(0.05), 2)
    assert tr.kernel_seconds(t, r"nothing")["0"] == (0.0, 0)


def test_collective_time_not_hidden_behind_compute():
    t = hand_made()
    # device 0: 10 ms alone (the second one overlaps the kernel);
    # device 1: 25 ms alone -> the worst device reads 25%
    assert tr.collective_exposed_share(t) == pytest.approx(25.0)
    t["devices"] = {"0": [["fusion.1", 0, 30 * MS]]}
    assert tr.collective_exposed_share(t) is None


def test_gaps_are_labelled_by_what_the_host_was_doing():
    t = hand_made()
    gaps = dict(tr.idle_gaps(t))
    # device 0 idles 40-50 (inside bm/optimize 0-45: 5 ms of 10 covered)
    # and 90-100 (inside bm/submit)
    assert gaps == {"bm/optimize": pytest.approx(0.01),
                    "bm/submit": pytest.approx(0.01)}
    t["host"] = [["bm/traced", 0, 100 * MS]]
    assert dict(tr.idle_gaps(t)) == {"unannotated": pytest.approx(0.02)}
    ops = tr.top_ops(hand_made())
    assert ops[0][0] == "fusion.1" and ops[0][1] == pytest.approx(0.035)
    assert len(tr.breakdown(hand_made())["device_ops"]) <= 10


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_trace_busy_and_idle(recorded):
    w = tr.window_seconds(recorded)
    busy = tr.busy_seconds(recorded)
    assert list(busy) == ["0"] and 0 < busy["0"] <= w
    assert 0 <= tr.idle_share(recorded) < 100
    assert tr.idle_share(recorded) == pytest.approx(100 * (1 - busy["0"] / w))


def test_recorded_trace_has_the_flash_kernels_and_no_collective(recorded):
    pattern = json.load(open(os.path.join(
        bm_util.REPO, "benchmark", "metrics",
        "flash_train_roofline.json")))["args"]["kernel_pattern"]
    seconds, calls = tr.kernel_seconds(recorded, pattern)["0"]
    assert calls >= 3 * 24 and 0 < seconds < tr.busy_seconds(recorded)["0"]
    assert tr.collective_exposed_share(recorded) is None    # one chip
    b = tr.breakdown(recorded)
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in b["device_ops"])
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        tr.window_seconds(recorded) - tr.busy_seconds(recorded)["0"])
