"""From a profiler trace to numbers: busy and idle, kernel time by name,
collectives not hidden behind compute, the device operations that took most
time, and the longest idle gaps labelled by what the host was doing.

``load_xplane`` turns JAX's ``.xplane.pb`` into a small plain form, and
everything else works on that form, so the arithmetic is tested on a
recorded trace (``tests/benchmark/data``) without a chip:

    {"devices": {"0": [[name, start_ns, dur_ns], ...]},   # XLA Ops line
     "modules": {"0": [[name, start_ns, dur_ns], ...]},   # XLA Modules line
     "host":    [[name, start_ns, dur_ns], ...]}          # bm/ annotations
"""
from __future__ import annotations

import glob
import os
import re

ANNOTATION_PREFIX = "bm/"
WINDOW_ANNOTATION = "bm/traced"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)


_LHS = re.compile(r"^%?([\w.\-]+)")
_OPCODE = re.compile(r"\b([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"\b([a-z][a-z0-9]*\[[0-9,]*\])")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str) -> str:
    """The profiler names a device event by its whole HLO instruction. Keep
    what tells operations apart: ``<result> <opcode> [<custom-call target>]
    <first result shape>``, so a Pallas kernel reads
    ``jvp__.36 custom-call tpu_custom_call f32[16,16,1024,64]``."""
    lhs, sep, rhs = text.partition(" = ")
    if not sep:
        return text
    parts = [_LHS.match(lhs).group(1) if _LHS.match(lhs) else lhs]
    op = _OPCODE.search(rhs)
    if op:
        parts.append(op.group(1))
    target = _TARGET.search(rhs)
    if target:
        parts.append(target.group(1))
    shape = _SHAPE.search(rhs)
    if shape:
        parts.append(shape.group(1))
    return " ".join(parts)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "modules": {}, "host": []}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                key = {"XLA Ops": "devices",
                       "XLA Modules": "modules"}.get(line.name)
                if key:
                    out[key][m.group(1)] = [
                        [short_name(e.name), int(e.start_ns),
                         int(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        out["host"].append(
                            [e.name, int(e.start_ns), int(e.duration_ns)])
    if not out["devices"]:
        raise ValueError(
            f"{path}: no '/device:TPU:<n>' plane with an 'XLA Ops' line; "
            f"planes: {[p.name for p in data.planes]}")
    return out


# -------------------------------------------------------------- intervals

def merge(intervals):
    """Union of ``(start, end)`` intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of the disjoint sorted ``a`` that no interval of the disjoint
    sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _spans(events, pattern=None, invert=False):
    keep = (lambda n: True) if pattern is None else (
        lambda n: bool(pattern.search(n)) != invert)
    return [(s, s + d) for n, s, d in events if keep(n)]


# ---------------------------------------------------------------- reduction

def window_of(trace: dict):
    """The traced window in the trace's clock: the ``bm/traced`` host
    annotation where it is there, else the span of the device events."""
    for name, s, d in trace["host"]:
        if name == WINDOW_ANNOTATION:
            return s, s + d
    spans = [sp for ev in trace["devices"].values() for sp in _spans(ev)]
    return min(s for s, _ in spans), max(e for _, e in spans)


def busy_seconds(trace: dict) -> dict:
    """{device: seconds in which an operation ran, inside the window}."""
    lo, hi = window_of(trace)
    return {dev: total(merge(clip(_spans(ev), lo, hi))) / 1e9
            for dev, ev in trace["devices"].items()}


def window_seconds(trace: dict) -> float:
    lo, hi = window_of(trace)
    return (hi - lo) / 1e9


def idle_share(trace: dict) -> float:
    """1 - busy / window on the fullest-loaded device, in percent."""
    w = window_seconds(trace)
    return 100.0 * (1.0 - max(busy_seconds(trace).values()) / w)


def kernel_seconds(trace: dict, pattern: str) -> dict:
    """{device: (seconds, calls)} of the events whose name matches."""
    rx = re.compile(pattern)
    lo, hi = window_of(trace)
    out = {}
    for dev, ev in trace["devices"].items():
        hit = [(s, s + d) for n, s, d in ev
               if rx.search(n) and s >= lo and s + d <= hi]
        out[dev] = (sum(e - s for s, e in hit) / 1e9, len(hit))
    return out


def collective_exposed_share(trace: dict) -> float:
    """Share of the window, on the worst device, in which a collective
    runs and no other operation does, in percent. None without any."""
    lo, hi = window_of(trace)
    worst = None
    for ev in trace["devices"].values():
        coll = merge(clip(_spans(ev, COLLECTIVE), lo, hi))
        if not coll:
            continue
        comp = merge(clip(_spans(ev, COLLECTIVE, invert=True), lo, hi))
        share = 100.0 * total(subtract(coll, comp)) / (hi - lo)
        worst = share if worst is None else max(worst, share)
    return worst


def top_ops(trace: dict, n: int = 10):
    """[[name, seconds]]: device operations by total time, averaged over
    the devices. The instance number is folded, so the same operation of
    every layer counts as one: ``fusion f32[16,1024,4096]``."""
    lo, hi = window_of(trace)
    acc = {}
    for ev in trace["devices"].values():
        for name, s, d in ev:
            if s >= lo and s + d <= hi:
                name = name.partition(" ")[2] or name
                acc[name] = acc.get(name, 0) + d
    k = max(1, len(trace["devices"]))
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9 / k] for name, ns in rows]


def idle_gaps(trace: dict, n: int = 10):
    """[[label, seconds]]: idle time of the fullest-loaded device summed by
    the host annotation that covers most of each gap ("unannotated" where
    none does), longest first."""
    lo, hi = window_of(trace)
    busy = busy_seconds(trace)
    dev = max(busy, key=busy.get)
    gaps = subtract([[lo, hi]], merge(clip(_spans(trace["devices"][dev]),
                                           lo, hi)))
    notes = [(name, s, s + d) for name, s, d in trace["host"]
             if name != WINDOW_ANNOTATION]
    acc = {}
    for gs, ge in gaps:
        best, cover = "unannotated", 0
        for name, s, e in notes:
            c = min(e, ge) - max(s, gs)
            if c > cover:
                best, cover = name, c
        acc[best] = acc.get(best, 0) + (ge - gs)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def breakdown(trace: dict) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}
