"""What the expert layers counted, read from the program's ``step`` spans
(``Optimizer`` writes the model's counters of a step onto its span, where it
has read the loss), mean over the traced steps that carry them:

``rows_local_share``    rows routed to the experts held here, an expert
                        layer, in percent of tokens x top_k;
``load_max_over_mean``  the fullest expert's load over the mean load, the
                        worst expert layer and the worst traced step;
``experts_roofline``    the least time the chip could take for one step's
                        held and shared expert matmuls at the rows counted
                        (``experts_train_ops_bytes`` of the configuration's
                        ``ops`` part) over the device time under the scopes
                        ``experts`` and ``shared``, recomputation included
                        in the time and not in the operations.

A program without the counters reads as nothing."""
import sys

from benchmark import peaks, program_trace as pt, trace_reduce as tr

ROWS, LOAD = "moe/rows_local", "moe/load_max_over_mean"


def step_counters(ctx, name):
    """The values of one counter on the ``step`` spans inside the traced
    window, in order."""
    if "step_stats" not in ctx:
        from jax.profiler import ProfileData
        data = ProfileData.from_file(
            tr.find_xplane(pt.trace_dir(ctx["cell"]["name"])))
        lo, hi = pt.window_of(pt.of(ctx))
        ctx["step_stats"] = [
            dict(e.stats) for plane in data.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events
            if e.name == pt.STEP_SPAN and lo <= e.start_ns <= hi]
        for key in (ROWS, LOAD):
            print(f"benchmark: counter {key} by traced step: "
                  f"{[float(s[key]) for s in ctx['step_stats'] if key in s]}",
                  file=sys.stderr, flush=True)
    return [float(s[name]) for s in ctx["step_stats"] if name in s]


def read(ctx, what):
    if pt.of(ctx) is None or not ctx["window"].get("traced_steps"):
        return None
    w, m = ctx["window"], ctx["config"]["model"]
    if what == "load_max_over_mean":
        values = step_counters(ctx, LOAD)
        return max(values) if values else None
    rows = step_counters(ctx, ROWS)
    if not rows:
        return None
    rows = sum(rows) / len(rows)
    if what == "rows_local_share":
        return 100.0 * rows / (w["tokens_per_step"] * m["top_k"])
    if what != "experts_roofline":
        raise LookupError(f"moe_counters reads no {what!r}")
    per_dev = pt.device_ms(pt.of(ctx), w["traced_steps"],
                           pt.scope_filter(scope=["experts", "shared"]))
    ms = max(v for v, _ in per_dev.values())
    if ms <= 0:
        raise LookupError("no device operation under the scopes "
                          "'experts' and 'shared'")
    flops, nbytes = ctx["parts"].ops.experts_train_ops_bytes(
        m, w["tokens_per_step"], rows)
    least, _ = peaks.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * least * 1e3 / ms
