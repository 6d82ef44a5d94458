"""Flash attention forward + backward against the roofline: the least time
the chip could take for one step's attention (operations and bytes from the
shapes, ``benchmark/ops.py``) x traced steps / the kernels' time in the
trace. A kernel the trace lacks is an error, never 0."""
from benchmark import ops, trace_reduce as tr


def read(ctx, kernel_pattern):
    w, m = ctx["window"], ctx["config"]["model"]
    if ctx["trace"] is None or not w.get("traced_steps"):
        return None
    per_dev = tr.kernel_seconds(ctx["trace"], kernel_pattern)
    seconds = max(s for s, _ in per_dev.values())
    if seconds <= 0:
        raise LookupError(f"no device event matches {kernel_pattern!r}")
    flops, nbytes = ops.flash_train_ops_bytes(
        m, w["batch"] // ctx["chips"], w["seq_len"])
    least, _ = ops.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * least * w["traced_steps"] / seconds
