"""The state-space scan's share of its roofline, read from a traced run:
the least time the chip could take for one step's chunked scans (Mamba-2's
SSD at ``chunk_size``, forward and backward, no recomputation:
``ssd_train_ops_bytes`` of the configuration's ``ops`` part) over the device
time of the operations under the scope ``ssd`` (forward, recomputed and
backward). The operations are the algorithm's whatever implements it, so a
kernel that takes the scan's place is judged on the same count. A program
that names no ``ssd`` operation reads as nothing."""
from benchmark import peaks, program_trace as pt


def read(ctx):
    trace, w = pt.of(ctx), ctx["window"]
    if trace is None or not w.get("traced_steps") \
            or not pt.names_its_work(trace):
        return None
    per_dev = pt.device_ms(trace, w["traced_steps"],
                           pt.scope_filter(scope=["ssd"]))
    ms = max(v for v, _ in per_dev.values())
    if ms <= 0:
        return None
    flops, nbytes = ctx["parts"].ops.ssd_train_ops_bytes(
        ctx["config"]["model"], w["batch"] // ctx["chips"], w["seq_len"])
    least, _ = peaks.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * least * 1e3 / ms
