"""``benchmark/program_trace.py``: the one rule that sorts an operation into
a phase of the step, the protobuf wire reader that finds the scope paths,
and the reduction to per-step milliseconds on a recorded trace
(``data/train_trace_scoped.json``: steps 8 and 9 of a traced run of
``gpt2m_train_1k`` on this PR's tree, TPU v5 lite, in the helper's plain
form, scope paths as the chip wrote them; events under 1 us dropped, times
moved to start at 0, ``bm/traced`` cut to the two steps)."""
import glob
import json
import os

import pytest

import bm_util
from benchmark import harness, program_trace as pt

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000
STEPS = 2
LAYERS = 24


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "train_trace_scoped.json")) as f:
        return json.load(f)


def reader(name):
    metric = harness.load_json(harness.HERE, "metrics", name + ".json")
    return harness.load_reader(metric), metric["args"]


def ctx_of(trace, steps=STEPS, remat=True):
    return {"trace": {}, "program_trace": trace,
            "window": {"traced_steps": steps},
            "config": {"model": {"num_layers": LAYERS},
                       "entry": {"remat": remat}},
            "cell": {"name": "gpt2m_train_1k"}}


# ---- the one classification, on the op_name strings the chip wrote

@pytest.mark.parametrize("op_name,phase,top", [
    ("jit(step)/jvp(block0)/attn/flash_fwd/pallas_call", "forward", "block*"),
    ("jit(step)/jvp(block17)/ffn/dot_general", "forward", "block*"),
    ("jit(step)/jvp(head)/dot_general", "forward", "head"),
    ("jit(step)/jvp(loss)/jit(take_along_axis)", "forward", "loss"),
    ("jit(step)/jvp(embed)/jit(_take)", "forward", "embed"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "block3/attn/flash_fwd/pallas_call", "recompute", "block*"),
    ("checkpoint/rematted_computation/block1/attn/reshape", "recompute",
     "block*"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/block3/attn/"
     "flash_bwd_dkv/pallas_call", "backward", "block*"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/block3/ln1/add_any",
     "backward", "block*"),
    ("jit(step)/transpose(jvp(jvp()))/remat2", "backward", "remat2"),
    ("jit(step)/transpose(jvp(head))/dot_general", "backward", "head"),
    ("jit(step)/transpose(jvp(loss))", "backward", "loss"),
    ("jit(step)/transpose(jvp(embed))/jit(_take)/scatter-add", "backward",
     "embed"),
    ("jit(step)/optim_update/jit(_where)/select_n", "optimizer",
     "optim_update"),
    ("jit(step)/grad_clip/mul", "optimizer", "grad_clip"),
    ("jit(local_step)/shard_map/optim_update/grad_exchange/all_gather",
     "exchange", "optim_update"),
    ("jit(local_step)/shard_map/optim_update/psum", "optimizer",
     "optim_update"),
    ("jit(local_step)/shard_map/jvp(block5)/ffn/dot_general", "forward",
     "block*"),
    ("jit(local_step)/grad_exchange/all_gather", "exchange",
     "grad_exchange"),
    ("jit(_threefry_split)/slice", "unscoped", "slice"),
    ("", "unscoped", "-"),
])
def test_an_operation_is_of_one_phase_by_its_op_name_alone(op_name, phase,
                                                           top):
    assert pt.phase_of(op_name) == phase
    assert pt.top_scope(op_name) == top


@pytest.mark.parametrize("opcode,phase", [
    ("all-reduce", "exchange"), ("all-gather", "exchange"),
    ("reduce-scatter", "exchange"), ("copy", "unscoped"),
    ("fusion", "unscoped")])
def test_a_collective_that_lost_its_name_is_still_the_exchange(opcode, phase):
    """On four chips XLA turns the gradient's reduce-scatter into
    ``all-reduce.72`` with no ``op_name``. A named operation goes by its
    name, whatever its opcode."""
    assert pt.phase_of("", opcode) == phase
    assert pt.phase_of("jit(local_step)/shard_map/optim_update/psum",
                       "all-reduce") == "optimizer"


def test_scope_path_unwraps_the_transforms_and_drops_jitted_names():
    wrappers, path = pt.scope_path(
        "jit(step)/transpose(jvp(jvp()))/checkpoint/block3/attn/reshape")
    assert wrappers == {"jit", "transpose", "jvp"}
    assert path == ("checkpoint", "block3", "attn", "reshape")
    assert pt.scope_path("jit(step)/jvp(loss)/jit(clip)") == (
        {"jit", "jvp"}, ("loss",))


# ---- the wire reader

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_the_wire_reader_reads_varints_and_nested_messages():
    inner = _field(1, 300) + _field(2, b"abc")
    msg = _field(1, 7) + _field(4, inner) + _field(3, 1 << 40)
    got = list(pt.fields(msg))
    assert got[0] == (1, 7) and got[2] == (3, 1 << 40)
    assert [(n, bytes(v) if not isinstance(v, int) else v)
            for n, v in pt.fields(got[1][1])] == [(1, 300), (2, b"abc")]
    with pytest.raises(ValueError):
        list(pt.fields(_varint(1 << 3 | 3)))       # a group: not read


def test_metadata_stats_finds_a_stat_of_the_event_metadata(tmp_path):
    """An XSpace with one plane: two stat names, two events' metadata, the
    scope once as a string and once as a reference to a stat name."""
    stat_meta = lambda key, name: _field(5, _field(1, key) + _field(
        2, _field(1, key) + _field(2, name)))
    event = lambda key, name, stat: _field(4, _field(1, key) + _field(
        2, _field(1, key) + _field(2, name) + _field(5, stat)))
    plane = (_field(2, b"/device:TPU:0") + stat_meta(3, b"tf_op")
             + stat_meta(9, b"jit(step)/jvp(head)/dot_general:")
             + stat_meta(4, b"flops")
             + event(1, b"%fusion.1 = f32[] fusion()",
                     _field(1, 3) + _field(5, b"jit(step)/optim_update/mul:"))
             + event(2, b"%fusion.2 = f32[] fusion()",
                     _field(1, 4) + _field(3, 12))
             + event(5, b"%fusion.5 = f32[] fusion()",
                     _field(1, 3) + _field(7, 9)))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(4, b"host"))
    assert pt.metadata_stats(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[] fusion()": "jit(step)/optim_update/mul:",
        "%fusion.5 = f32[] fusion()": "jit(step)/jvp(head)/dot_general:"}}


def test_the_wire_reader_agrees_with_the_profilers_own_reader(tmp_path):
    """A profile made here on the CPU: every event name the profiler's
    ``ProfileData`` shows in the host plane is an event-metadata name the
    wire reader finds, and the program's spans come through as spans."""
    import jax
    from jax.profiler import ProfileData
    from bigdl_tpu import observability as obs
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("step", step_num=4):
            with obs.span("step/dispatch"):
                jax.jit(lambda x: x * 2)(1.0).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    with open(path, "rb") as f:
        planes = [dict(_plane(v)) for n, v in pt.fields(f.read()) if n == 1]
    host = next(p for p in planes if p["name"].startswith("/host:CPU"))
    shown = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name == host["name"]
             for line in plane.lines for e in line.events}
    assert {"step", "step/dispatch"} <= shown <= host["events"]
    with pytest.raises(ValueError, match="XLA Ops"):
        pt.load_xplane(path)            # a CPU profile has no device plane


def _plane(buf):
    names = set()
    for n, v in pt.fields(buf):
        if n == 2:
            yield "name", bytes(v).decode()
        elif n == 4:
            meta = dict(pt.fields(dict(pt.fields(v))[2]))
            names.add(bytes(meta[2]).decode())
    yield "events", names


# ---- the reduction, on the recorded trace

def test_phases_sum_to_busy_and_little_is_unscoped(recorded):
    dev = pt.fullest(recorded)
    busy = pt.busy_ms(recorded, STEPS)[dev]
    phases = pt.by_phase(recorded, STEPS, dev)
    assert set(phases) == set(pt.PHASES)
    assert sum(phases.values()) == pytest.approx(busy, rel=5e-3)
    assert phases["unscoped"] < 0.03 * busy
    assert phases["exchange"] == 0                  # one chip
    assert phases["backward"] > phases["forward"] > phases["recompute"] > 0
    assert sum(pt.by_top_scope(recorded, STEPS, dev).values()) == \
        pytest.approx(sum(phases.values()))
    assert pt.names_its_work(recorded) and pt.has_spans(recorded)


@pytest.mark.parametrize("metric,calls", [
    ("flash_fwd_ms", 2 * LAYERS), ("flash_bwd_dkv_ms", LAYERS),
    ("flash_bwd_dq_ms", LAYERS)])
def test_a_kernel_is_found_by_its_name_and_its_calls_are_checked(
        recorded, metric, calls, capsys):
    read, args = reader(metric)
    ms = read(ctx_of(recorded), **args)
    got = pt.device_ms(recorded, STEPS, pt.kernel_filter(args["kernel"]))["0"]
    assert got == (pytest.approx(ms), calls) and ms > 10
    assert f"{calls} calls a step" in capsys.readouterr().err
    # a window that cut a step: the calls are no whole number of layers
    with pytest.raises(LookupError, match="calls a step"):
        read(ctx_of(recorded, steps=3), **args)


def test_the_three_kernels_are_all_the_pallas_time_the_old_metric_reads(
        recorded):
    names = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
    each = [pt.device_ms(recorded, STEPS, pt.kernel_filter(k))["0"][0]
            for k in names]
    every = pt.device_ms(recorded, STEPS,
                         lambda op: op[1] == "custom-call"
                         and op[0].startswith("flash_"))["0"][0]
    assert sum(each) == pytest.approx(every)
    # a name that is the start of another's is not taken for it
    assert pt.device_ms(recorded, STEPS, pt.kernel_filter("flash"))["0"] == (
        0, 0)


@pytest.mark.parametrize("metric", [
    "step_fwd_ms", "step_recompute_ms", "step_bwd_ms", "step_optim_ms",
    "attn_layout_ms", "head_loss_ms"])
def test_the_scope_metrics_read_the_recorded_trace(recorded, metric):
    read, args = reader(metric)
    ms = read(ctx_of(recorded), **args)
    assert ms > 0
    if "phase" in args:
        assert ms == pytest.approx(
            pt.by_phase(recorded, STEPS, "0")[args["phase"]])


def test_the_copies_under_attn_are_copies_and_sit_under_attn(recorded):
    read, args = reader("attn_layout_ms")
    layout = read(ctx_of(recorded), **args)
    attn = pt.device_ms(recorded, STEPS, pt.scope_filter(scope=["attn"]))["0"]
    copies = pt.device_ms(recorded, STEPS,
                          pt.scope_filter(opcode=["copy", "transpose"]))["0"]
    assert layout < attn[0] and layout <= copies[0]
    assert layout > 0.8 * copies[0]        # most copies of the step are these


def test_what_a_metric_reads_missing_is_an_error_never_nought(recorded):
    none_left = dict(recorded, ops=[
        [op[0].replace("flash_bwd_dq", "other"), op[1],
         op[2].replace("/head", "/tail").replace("(head)", "(tail)")
         .replace("loss", "other")] for op in recorded["ops"]])
    read, args = reader("flash_bwd_dq_ms")
    with pytest.raises(LookupError, match="flash_bwd_dq"):
        read(ctx_of(none_left), **args)
    read, args = reader("head_loss_ms")
    with pytest.raises(LookupError, match="head"):
        read(ctx_of(none_left), **args)
    spans = [s for s in recorded["spans"] if s[0] != "step/dispatch"]
    read, args = reader("train_dispatch_ms")
    with pytest.raises(LookupError, match="step/dispatch"):
        read(ctx_of(dict(recorded, spans=spans)), **args)


def test_a_program_that_names_nothing_reads_as_nothing(recorded):
    """The parent of this PR under these readers: no scope, no kernel
    name, no span. Every new metric is left out; none raises."""
    bare = {"ops": [[f"custom-call.{i}" if op[1] == "custom-call" else op[0],
                     op[1], "jit(step)/jvp()/mul"]
                    for i, op in enumerate(recorded["ops"])],
            "devices": recorded["devices"],
            "spans": [s for s in recorded["spans"] if s[0].startswith("bm/")]}
    assert not pt.names_its_work(bare) and not pt.has_spans(bare)
    for name in sorted(os.listdir(os.path.join(harness.HERE, "metrics"))):
        metric = harness.load_json(harness.HERE, "metrics", name) \
            if name.endswith(".json") else None
        if metric and metric["reader"] in ("scope_ms.py", "kernel_ms.py",
                                           "host_span_ms.py"):
            assert harness.load_reader(metric)(
                ctx_of(bare), **metric["args"]) is None, name
    assert "idle under no span" in pt.table(bare, STEPS)
    # and off the chip there is no trace at all
    read, args = reader("train_dispatch_ms")
    assert read(dict(ctx_of(recorded), trace=None), **args) is None


def test_the_dispatch_span_is_averaged_over_the_steps(recorded):
    read, args = reader("train_dispatch_ms")
    each = pt.span_ms(recorded, "step/dispatch")
    assert len(each) == STEPS
    assert read(ctx_of(recorded), **args) == pytest.approx(sum(each) / STEPS)


def hand_made():
    """One device over 100 ms: busy 0-40 and 60-100, idle 40-60. The step
    spans 0-100 with ``step/loss_sync`` 0-45, ``step/triggers`` 45-50 (and
    ``step/validate`` 46-49 inside it), nothing 50-52, ``step/dispatch``
    52-70; another thread waits 40-60 for its source."""
    return {"ops": [["fusion.1", "fusion", "jit(step)/optim_update/mul"]],
            "devices": {"0": [[0, 0, 40 * MS], [0, 60 * MS, 40 * MS]]},
            "spans": [["bm/traced", 0, 0, 100 * MS, None],
                      ["step", 0, 0, 100 * MS, 3],
                      ["step/loss_sync", 0, 0, 45 * MS, None],
                      ["step/triggers", 0, 45 * MS, 5 * MS, None],
                      ["step/validate", 0, 46 * MS, 3 * MS, None],
                      ["step/dispatch", 0, 52 * MS, 18 * MS, None],
                      ["stager/source_wait", 1, 40 * MS, 20 * MS, None]]}


def test_idle_time_is_split_among_the_child_spans_by_overlap():
    under, none = pt.idle_by_span(hand_made(), 1)
    assert under == {"step/loss_sync": pytest.approx(5.0),
                     "step/triggers": pytest.approx(5.0),
                     "step/dispatch": pytest.approx(8.0)}
    assert none == pytest.approx(2.0)       # 50-52: under `step` alone
    read, args = reader("train_idle_unspanned_ms")
    assert read(ctx_of(hand_made(), steps=1), **args) == pytest.approx(2.0)
    only_step = dict(hand_made(), spans=hand_made()["spans"][:2])
    with pytest.raises(LookupError, match="step/"):
        read(ctx_of(only_step, steps=1), **args)


def test_on_the_recorded_trace_the_idle_gap_is_put_down_to_named_spans(
        recorded):
    under, none = pt.idle_by_span(recorded, STEPS)
    idle = sum(under.values()) + none
    assert set(under) == {"step/data_fetch", "step/prepare", "step/dispatch",
                          "step/loss_sync", "step/triggers"}
    assert 3 < idle < 9 and none < 0.2 * idle
    assert max(under, key=under.get) == "step/loss_sync"
    text = pt.table(recorded, STEPS)
    assert "phase unscoped" in text and "idle under step/prepare" in text


# ---- where the profile is, and that the readers are found from added files

def test_the_helper_looks_where_run_cell_puts_the_profile(tmp_path,
                                                          monkeypatch):
    """``ctx`` does not carry the trace directory: the helper derives it
    from its own place, as ``run_cell`` derives it from ``root``. A traced
    run of a copy of ``benchmark/`` shows both rules give one place."""
    import importlib.util
    root = bm_util.tiny_root(tmp_path)
    seen = {}
    real = harness.Tracer.__init__

    def spy(self, log_dir):
        seen["log_dir"] = log_dir
        real(self, log_dir)

    monkeypatch.setattr(harness.Tracer, "__init__", spy)
    out = bm_util.run_tiny(root, "tiny_train", trace=1)
    assert out["correct"] is True and out["metrics"] == {}
    spec = importlib.util.spec_from_file_location(
        "benchmark.program_trace_copy", os.path.join(root, "program_trace.py"))
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    assert copy.trace_dir("tiny_train") == seen["log_dir"]
    assert pt.trace_dir("x") == os.path.join(
        bm_util.REPO, ".bench_out", "trace", "x")
