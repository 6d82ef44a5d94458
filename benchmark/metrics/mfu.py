"""Whole-step share of the chip's bf16 peak: model operations of the work
done in the traced window / traced seconds / (chips x peak). Recomputation
is not counted. The f32 product path multiplies in bf16 passes on the MXU,
so the bf16 peak is the denominator."""
from benchmark import ops


def read(ctx):
    w, m = ctx["window"], ctx["config"]["model"]
    if ctx["peaks"] is None or not w.get("traced_s"):
        return None
    if not w["traced_steps"]:
        return None
    flops = (ops.train_flops_per_sequence(m, w["seq_len"]) * w["batch"]
             * w["traced_steps"])
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / w["traced_s"] / peak
