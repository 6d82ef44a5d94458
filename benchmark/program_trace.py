"""What the program says about its own work, read from a run's profile.

``trace_reduce.load_xplane`` keeps shortened device names and the
benchmark's own ``bm/`` annotations. This helper loads the same
``.xplane.pb`` a second time into a plain form that keeps, for every device
operation, the HLO instruction's name (a Pallas kernel's is its ``name=``),
its opcode and the scope path the program gave it (``jax.named_scope``, and
JAX's own ``jvp(…)``, ``transpose(…)``, ``rematted_computation`` frames),
and for the host every span the program opened (``obs.span``: a
``TraceAnnotation`` of the same name), with its thread and ``step_num``:

    {"ops":     [[instruction, opcode, scope], ...],         # a table
     "devices": {"0": [[op, start_ns, dur_ns], ...]},        # XLA Ops line
     "spans":   [[name, thread, start_ns, dur_ns, step_num], ...]}

Everything else works on that form, so the arithmetic is tested on a
recorded trace (``tests/benchmark/data``) without a chip.

Where the scope path comes from: on the TPU the profiler names a device
event by its whole HLO instruction, without the ``metadata={op_name=…}``
tail, and the event's own stats hold times only. The ``op_name`` is the
``tf_op`` stat of the event's METADATA entry (``XEventMetadata.stats``),
which ``jax.profiler.ProfileData`` does not show; ``metadata_stats`` reads
it from the file's protobuf wire format (three message types, no
dependency).
"""
from __future__ import annotations

import functools
import os
import re
import sys

from . import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW_SPAN = tr.WINDOW_ANNOTATION
STEP_SPAN = "step"
PHASES = ("forward", "recompute", "backward", "optimizer", "exchange",
          "unscoped")
OPTIMIZER_SCOPES = ("grad_clip", "optim_update")
EXCHANGE_SCOPE = "grad_exchange"
RECOMPUTE_FRAME = "rematted_computation"
#: the stat of a device event's metadata that holds its ``op_name``
SCOPE_STAT = "tf_op"
#: frames JAX writes into a name that are neither a scope nor an operation
FRAMES = ("checkpoint", RECOMPUTE_FRAME, "shard_map")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
#: a span of the program: ``step`` or ``<subsystem>/<phase>``
_SPAN = re.compile(r"^(step|[a-z_]+(/[\w.\-]+)+)$")


def trace_dir(cell_name: str) -> str:
    """Where ``run.run_cell`` puts a traced run's profile (it removes the
    directory only after the readers ran): beside this package."""
    return os.path.join(os.path.dirname(HERE), ".bench_out", "trace",
                        cell_name)


# ---------------------------------------------------------- the wire format

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    the bytes for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, value


def _map_entry(buf):
    entry = dict(fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def metadata_stats(path: str) -> dict:
    """{plane name: {event name: its ``tf_op``}} from the event metadata
    of every plane of an ``.xplane.pb`` (XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7; XStatMetadata.name = 2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for n, value in fields(plane):
            if n == 2:
                name = bytes(value).decode()
            elif n == 4:
                events.append(_map_entry(value)[1])
            elif n == 5:
                key, meta = _map_entry(value)
                stat_names[key] = bytes(dict(fields(meta)).get(2, b"")).decode()
        wanted = {k for k, v in stat_names.items() if v == SCOPE_STAT}
        found = {}
        for meta in events:
            event_name = None
            for n, value in fields(meta):
                if n == 2:
                    event_name = bytes(value).decode()
                elif n == 5:
                    st = dict(fields(value))
                    if st.get(1) in wanted:
                        found[event_name] = (
                            bytes(st[5]).decode() if 5 in st
                            else stat_names.get(st.get(7), ""))
        if found:
            out[name] = found
    return out


# ------------------------------------------------------------ the plain form

def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    scopes = metadata_stats(path)
    data = ProfileData.from_file(path)
    ops, index = [], {}
    out = {"ops": ops, "devices": {}, "spans": []}
    for plane in data.planes:
        m = tr._DEVICE_PLANE.match(plane.name)
        if m:
            scope_of = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                rows = []
                for e in line.events:
                    op = index.get(e.name)
                    if op is None:
                        op = index[e.name] = len(ops)
                        ops.append(_instruction(e.name) + [
                            scope_of.get(e.name, "").rpartition(":")[0]])
                    rows.append([op, int(e.start_ns), int(e.duration_ns)])
                out["devices"][m.group(1)] = rows
        elif plane.name.startswith("/host:"):
            for thread, line in enumerate(plane.lines):
                for e in line.events:
                    if _SPAN.match(e.name):
                        step = dict(e.stats).get("step_num")
                        out["spans"].append(
                            [e.name, thread, int(e.start_ns),
                             int(e.duration_ns), step])
    if not out["devices"]:
        raise ValueError(f"{path}: no '/device:TPU:<n>' plane with an "
                         "'XLA Ops' line")
    return out


def _instruction(text: str):
    """[name, opcode] of the HLO instruction the profiler names a device
    event by: ``%flash_fwd.48 = (…) custom-call(…), …``."""
    lhs, _, rhs = text.partition(" = ")
    name, code = tr._LHS.match(lhs), tr._OPCODE.search(rhs)
    return [name.group(1) if name else lhs, code.group(1) if code else ""]


def of(ctx):
    """The run's program trace, loaded once for all the readers of a run
    (kept in ``ctx``); the first load prints the table. None off the
    chip."""
    if ctx.get("trace") is None:
        return None
    if "program_trace" not in ctx:
        path = tr.find_xplane(trace_dir(ctx["cell"]["name"]))
        ctx["program_trace"] = load_xplane(path)
        steps = ctx["window"].get("traced_steps") or 0
        if steps:
            print(table(ctx["program_trace"], steps), file=sys.stderr,
                  flush=True)
    return ctx["program_trace"]


# ----------------------------------------------------- the one classification

@functools.lru_cache(maxsize=1 << 16)
def scope_path(scope: str):
    """(wrappers, path) of an ``op_name``: the transforms JAX wrapped
    around any part of it (``jvp``, ``transpose``, …) and the components
    inside them, frames kept, jitted functions' names dropped:
    ``jit(step)/transpose(jvp(jvp()))/checkpoint/block3/attn/reshape`` ->
    ({"jit", "transpose", "jvp"}, ("checkpoint", "block3", "attn",
    "reshape"))."""
    wrappers, path = set(), []
    for part in scope.split("/"):
        m = _WRAPPED.match(part)
        while m:
            wrappers.add(m.group(1))
            part = "" if m.group(1) in ("jit", "pjit") else m.group(2)
            m = _WRAPPED.match(part)
        if part:
            path.append(part)
    return frozenset(wrappers), tuple(path)


def phase_of(scope: str, opcode: str = "") -> str:
    """Which part of the step an operation belongs to, by its ``op_name``.
    The program's scopes name the optimizer and the exchange; JAX names
    the rest: what is differentiated runs under ``jvp(…)``, its backward
    under ``transpose(…)``, and what ``jax.checkpoint`` runs a second time
    under ``rematted_computation``. One operation is known by its opcode:
    XLA rewrites the gradient's reduce-scatter into an all-reduce that
    carries no ``op_name`` at all, and a collective without a name is
    still the exchange."""
    wrappers, path = scope_path(scope)
    if EXCHANGE_SCOPE in path or (
            not scope and tr.COLLECTIVE.match(opcode)):
        return "exchange"
    if any(s in path for s in OPTIMIZER_SCOPES):
        return "optimizer"
    if RECOMPUTE_FRAME in path:
        return "recompute"
    if "transpose" in wrappers:
        return "backward"
    if "jvp" in wrappers:
        return "forward"
    return "unscoped"


def top_scope(scope: str) -> str:
    """The first component that is not a frame, layers folded:
    ``block*``, ``head``, ``loss``, ``optim_update``; "-" without any."""
    for part in scope_path(scope)[1]:
        if part not in FRAMES:
            return re.sub(r"block\d+$", "block*", part)
    return "-"


def names_its_work(trace: dict) -> bool:
    """Whether the compiled step carries the program's scopes at all. A
    program from before they existed reads as None, not as an error."""
    return any(OPTIMIZER_SCOPES[1] in scope_path(op[2])[1]
               for op in trace["ops"])


def has_spans(trace: dict) -> bool:
    return any(s[0] == STEP_SPAN for s in trace["spans"])


# ------------------------------------------------------------------ reduction

def window_of(trace: dict):
    for name, _, s, d, _ in trace["spans"]:
        if name == WINDOW_SPAN:
            return s, s + d
    spans = [(s, s + d) for ev in trace["devices"].values() for _, s, d in ev]
    return min(s for s, _ in spans), max(e for _, e in spans)


def _inside(trace: dict):
    """{device: [(op row, start, dur)]} of the events wholly inside the
    window."""
    lo, hi = window_of(trace)
    return {dev: [(trace["ops"][op], s, d) for op, s, d in ev
                  if s >= lo and s + d <= hi]
            for dev, ev in trace["devices"].items()}


def device_ms(trace: dict, steps: int, keep) -> dict:
    """{device: (ms a step, events a step)} of the operations ``keep(op
    row)`` accepts."""
    out = {}
    for dev, ev in _inside(trace).items():
        hit = [d for op, _, d in ev if keep(op)]
        out[dev] = (sum(hit) / 1e6 / steps, len(hit) / steps)
    return out


def scope_filter(phase=None, scope=None, opcode=None):
    """An operation's row -> whether it is of this phase, under one of
    these scopes (a path component), of one of these opcodes."""
    def keep(op):
        _, code, name = op
        if phase is not None and phase_of(name, code) != phase:
            return False
        if scope is not None and not set(scope) & set(scope_path(name)[1]):
            return False
        return opcode is None or code in opcode
    return keep


def kernel_filter(kernel: str):
    """A Pallas kernel by its ``name=``: XLA numbers the instructions,
    ``flash_fwd.48``."""
    rx = re.compile(re.escape(kernel) + r"(\.\d+)?$")
    return lambda op: op[1] == "custom-call" and bool(rx.match(op[0]))


def busy_ms(trace: dict, steps: int) -> dict:
    lo, hi = window_of(trace)
    return {dev: tr.total(tr.merge(tr.clip(
        [(s, s + d) for _, s, d in ev], lo, hi))) / 1e6 / steps
        for dev, ev in trace["devices"].items()}


def fullest(trace: dict) -> str:
    busy = busy_ms(trace, 1)
    return max(busy, key=busy.get)


def _ms_by(trace: dict, steps: int, dev: str, key) -> dict:
    acc = {}
    for op, _, d in _inside(trace)[dev]:
        k = key(op)
        acc[k] = acc.get(k, 0) + d
    return {k: v / 1e6 / steps for k, v in acc.items()}


def by_phase(trace: dict, steps: int, dev: str) -> dict:
    """{phase: ms a step} on one device: every operation inside the
    window is of exactly one phase."""
    return {**dict.fromkeys(PHASES, 0.0),
            **_ms_by(trace, steps, dev, lambda op: phase_of(op[2], op[1]))}


def by_top_scope(trace: dict, steps: int, dev: str) -> dict:
    return _ms_by(trace, steps, dev, lambda op: top_scope(op[2]))


def span_ms(trace: dict, name: str):
    """Durations in ms of the spans of this name that lie wholly inside
    the window."""
    lo, hi = window_of(trace)
    return [d / 1e6 for n, _, s, d, _ in trace["spans"]
            if n == name and s >= lo and s + d <= hi]


def idle_by_span(trace: dict, steps: int, under: str = STEP_SPAN + "/"):
    """({child span: idle ms a step under it}, idle ms a step under none):
    the idle time of the fullest device inside the window, split by exact
    overlap among the spans whose names start with ``under`` on the
    thread that runs the steps. A span inside another such span counts
    with the outer one."""
    lo, hi = window_of(trace)
    dev = fullest(trace)
    gaps = tr.subtract([[lo, hi]], tr.merge(tr.clip(
        [(s, s + d) for _, s, d in trace["devices"][dev]], lo, hi)))
    threads = {t for n, t, *_ in trace["spans"] if n == STEP_SPAN}
    children, end = {}, lo
    for n, t, s, d, _ in sorted(trace["spans"], key=lambda r: r[2]):
        if n.startswith(under) and t in threads and s >= end:
            children.setdefault(n, []).append((s, s + d))
            end = s + d
    acc, covered = {}, 0
    for n, spans in children.items():
        spans = tr.merge(tr.clip(spans, lo, hi))
        under_n = tr.total(gaps) - tr.total(tr.subtract(gaps, spans))
        acc[n] = under_n / 1e6 / steps
        covered += under_n
    return acc, (tr.total(gaps) - covered) / 1e6 / steps


def table(trace: dict, steps: int) -> str:
    """Device ms a step by phase and by top-level scope, idle ms a step
    by host span: the fullest device."""
    dev = fullest(trace)
    busy = busy_ms(trace, steps)[dev]
    rows = [f"program trace: device {dev}, {steps} steps, busy "
            f"{busy:.3f} ms a step"]
    share = lambda v: f"{v:10.3f} ms {100 * v / busy:6.2f}%"
    for name, v in by_phase(trace, steps, dev).items():
        rows.append(f"  phase {name:<26s}{share(v)}")
    scopes = sorted(by_top_scope(trace, steps, dev).items(),
                    key=lambda kv: -kv[1])
    for name, v in scopes:
        if v >= 0.05:
            rows.append(f"  scope {name:<26s}{share(v)}")
    under, none = idle_by_span(trace, steps)
    for name, v in sorted(under.items(), key=lambda kv: -kv[1]):
        rows.append(f"  idle under {name:<21s}{v:10.3f} ms")
    rows.append(f"  idle under no span of {STEP_SPAN + '/':<10s}{none:10.3f} ms")
    return "\n".join(rows)
