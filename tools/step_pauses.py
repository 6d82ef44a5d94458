#!/usr/bin/env python
"""Place a slow step: for every training step of a profile, its wall time
and the device's idle time split by the program's host spans.

Profile a range of steps with the optimizer's own window, then read it:

    BIGDL_TPU_PROFILE=5:60 BIGDL_TPU_PROFILE_DIR=/path/prof python train.py
    python tools/step_pauses.py /path/prof [--top 5] [--json rows.json]

A step runs from the start of its ``step`` span to the start of the next
one, so an epoch's ``epoch/turnover`` counts with the step before it. Its
idle time is that of the busiest device (``/device:TPU:<n>``, the ``XLA
Ops`` line), each idle instant put down to the first of ``CAUSES`` whose
span covers it: a collection (``host/gc``) on any thread first, because it
holds the GIL; then the loop thread's ``epoch/turnover`` and ``step/*``
children; then the stager's wait for its source. What none covers reads
under ``other``: the runtime, or host code outside the program's spans.
Each row also lists the collections inside the step (generation, objects
collected, ms), the ``step`` span's ``host/gc_*`` counters and its longest
idle gap; for the longest steps the report adds what else the profile
holds during that gap (runtime events of every host thread, the Python
tracer's calls, the device's other lines), by time overlapped.

The module's functions work on a plain form, so they are tested without
a chip:

    {"devices": {"0": [[op, start_ns, dur_ns], ...]},
     "spans":   [[name, thread, start_ns, dur_ns, stats or None], ...],
     "events":  [[line, name, start_ns, dur_ns], ...]}      # all the rest
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from benchmark.trace_reduce import clip, merge, subtract, total  # noqa: E402

STEP, GC, TURNOVER = "step", "host/gc", "epoch/turnover"
WINDOW = "bm/traced"
GC_COUNT = "host/gc_collections"
#: what an idle instant is put down to, first match wins; the first and
#: the last are read on every thread, the others on the loop's
CAUSES = (GC, TURNOVER, "step/data_fetch", "step/prepare", "step/dispatch",
          "step/loss_sync", "step/triggers", "stager/source_wait")
_ANY_THREAD = (GC, "stager/source_wait")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SPAN = re.compile(r"^(step|bm/traced|[a-z_]+(/[\w.\-]+)+)$")


def load(path: str) -> dict:
    """The plain form of an ``.xplane.pb``, or of the newest one under a
    profile directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {"devices": {}, "spans": [], "events": []}
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE.match(plane.name)
        host = plane.name.startswith("/host:")
        for thread, line in enumerate(plane.lines):
            if m and line.name == "XLA Ops":
                out["devices"][m.group(1)] = [
                    [0, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events]
                continue
            where = f"{plane.name} #{thread} {line.name}"
            for e in line.events:
                if host and _SPAN.match(e.name):
                    out["spans"].append(
                        [e.name, thread, int(e.start_ns),
                         int(e.duration_ns), dict(e.stats) or None])
                else:
                    out["events"].append(
                        [where, e.name, int(e.start_ns), int(e.duration_ns)])
    return out


def _busy(trace, dev):
    return merge((s, s + d) for _, s, d in trace["devices"].get(dev, ()))


def fullest(trace):
    """The busiest device; None in a profile without one (the CPU's)."""
    return max(trace["devices"], default=None,
               key=lambda d: total(_busy(trace, d)))


def window(trace):
    """(start, end) of the benchmark's traced window, else of the device's
    work, else of the steps."""
    for name, _, s, d, _ in trace["spans"]:
        if name == WINDOW:
            return s, s + d
    ops = [(s, s + d) for ev in trace["devices"].values() for _, s, d in ev]
    ops = ops or [(s, s + d) for n, _, s, d, _ in trace["spans"] if n == STEP]
    return min(s for s, _ in ops), max(e for _, e in ops)


def _steps(trace, lo=None, hi=None):
    return sorted((r for r in trace["spans"] if r[0] == STEP
                   and (lo is None or lo <= r[2] <= hi)), key=lambda r: r[2])


def gc_ms(trace):
    """Mean ``host/gc_ms`` over the window's ``step`` spans that carry the
    counters; None where none does (a program without the hook)."""
    got = [r[4]["host/gc_ms"] for r in _steps(trace, *window(trace))
           if r[4] and GC_COUNT in r[4]]
    return sum(got) / len(got) if got else None


def idle_under(trace, names, steps):
    """Idle ms a step of the fullest device inside the window under the
    spans of these names on any thread; None where no ``step`` span
    carries the collector's counters."""
    lo, hi = window(trace)
    if not any(r[4] and GC_COUNT in r[4] for r in _steps(trace, lo, hi)):
        return None
    gaps = subtract([(lo, hi)], clip(_busy(trace, fullest(trace)), lo, hi))
    spans = merge(clip([(s, s + d) for n, _, s, d, _ in trace["spans"]
                        if n in names], lo, hi))
    return (total(gaps) - total(subtract(gaps, spans))) / 1e6 / steps


def by_step(trace):
    """One row a ``step`` span of the loop's thread: ``step`` (its
    number), ``wall_ms`` (to the next step's start), ``span_ms``,
    ``idle_ms`` by cause, ``gc`` (the collections inside it) and the
    span's counters."""
    steps = _steps(trace)
    if not steps:
        return []
    loop = steps[0][1]
    steps = [r for r in steps if r[1] == loop]
    dev = fullest(trace)
    busy = _busy(trace, dev)
    by_cause = {c: merge((s, s + d) for n, t, s, d, _ in trace["spans"]
                         if n == c and (c in _ANY_THREAD or t == loop))
                for c in CAUSES}
    gcs = [r for r in trace["spans"] if r[0] == GC]
    rows = []
    for i, (_, _, s, d, stats) in enumerate(steps):
        nxt = steps[i + 1][2] if i + 1 < len(steps) else s + d
        left = subtract([(s, nxt)], clip(busy, s, nxt))
        gap = max(left, key=lambda g: g[1] - g[0], default=(s, s))
        idle = {}
        for cause in CAUSES:
            cover = clip(by_cause[cause], s, nxt)
            rest = subtract(left, cover)
            idle[cause] = (total(left) - total(rest)) / 1e6
            left = rest
        idle["other"] = total(left) / 1e6
        stats = stats or {}
        rows.append({
            "step": stats.get("step_num"), "start_ns": s,
            "wall_ms": (nxt - s) / 1e6, "span_ms": d / 1e6,
            "idle_ms": idle, "gap": [gap[0], (gap[1] - gap[0]) / 1e6],
            "gc": [{"generation": (g[4] or {}).get("generation"),
                    "collected": (g[4] or {}).get("collected"),
                    "uncollectable": (g[4] or {}).get("uncollectable"),
                    "ms": g[3] / 1e6}
                   for g in gcs if s <= g[2] < nxt],
            **{k: v for k, v in stats.items() if k.startswith("host/")}})
    return rows


def during(trace, start_ns, ms, top=8):
    """[(line, event name, ms overlapped)] of the profile's other events
    inside ``start_ns`` + ``ms``, the most overlapped first: on each line
    the innermost only (an event holding another of its line is the
    caller's frame, not what ran)."""
    lo, hi = start_ns, start_ns + ms * 1e6
    lines = {}
    for where, name, s, d in trace.get("events", ()):
        if min(s + d, hi) > max(s, lo):
            lines.setdefault(where, []).append((s, s + d, name))
    acc = {}
    for where, hit in lines.items():
        for s, e, name in hit:
            if any(s <= s2 and e2 <= e and (s2, e2) != (s, e)
                   for s2, e2, _ in hit):
                continue
            acc[where, name] = acc.get((where, name), 0) + \
                min(e, hi) - max(s, lo)
    return [(w, n, v / 1e6) for (w, n), v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def report(rows, top=5, trace=None) -> str:
    """The ``top`` longest steps, each with its idle by cause, beside the
    median step; given the trace, what else ran in each one's longest idle
    gap (set on the row as ``during``)."""
    if not rows:
        return "no step span in the profile"
    walls = sorted(r["wall_ms"] for r in rows)
    out = [f"{len(rows)} steps, median {walls[len(walls) // 2]:.3f} ms"]
    for r in sorted(rows, key=lambda r: -r["wall_ms"])[:top]:
        idle = ", ".join(f"{k} {v:.3f}" for k, v in r["idle_ms"].items()
                         if v > 0)
        gcs = "; ".join(f"gen {g['generation']} {g['collected']} objects "
                        f"{g['ms']:.3f} ms" for g in r["gc"])
        out.append(f"step {r['step']}: {r['wall_ms']:.3f} ms; idle ms: "
                   f"{idle or '-'}; collections: {gcs or '-'}")
        if trace is not None:
            r["during"] = during(trace, *r["gap"])
            out.append(f"  in its longest idle gap ({r['gap'][1]:.3f} ms): "
                       + "; ".join(f"{w} | {n} {v:.3f}"
                                   for w, n, v in r["during"]))
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("profile", help="a profile directory or .xplane.pb")
    ap.add_argument("--top", type=int, default=5)
    ap.add_argument("--json", help="write every step's row here")
    args = ap.parse_args(argv)
    trace = load(args.profile)
    rows = by_step(trace)
    counted = sum(1 for r in rows if GC_COUNT in r)
    print(f"host/gc_ms a step {gc_ms(trace)}; idle ms a step under {GC} "
          f"{idle_under(trace, [GC], counted) if counted else None}")
    print(report(rows, args.top, trace))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
