#!/usr/bin/env python3
"""Paged-attention tiling sweep: block-size x row-bucket, dense vs
kernel, with `tools/xla_report.py`-compatible artifacts.

For each KV ``block_size`` (the cache geometry — and therefore the
kernel's K/V tile) and each decode row bucket, this builds a
``DecodeScheduler``, warms every dispatchable shape, and collects the
compiled ``serve/decode_step`` artifacts (XLA ``cost_analysis`` FLOPs /
bytes-accessed via the PR-7 introspection plane) for BOTH attention
paths. The table is the evidence the ISSUE-11 kernel claim rests on:
the dense arm's bytes-accessed carries the gathered-view term (grows
with bucket x table width), the kernel arm's does not.

CAVEAT (printed loudly): on CPU the kernel runs through the Pallas
INTERPRETER, whose lowering is a jax while-loop — its cost analysis
describes the interpreter program, not the mosaic kernel, so the
bytes drop is only measurable on the TPU. Run it there through the chip
tool (`chiprun -- python tools/paged_sweep.py`) to record the real
numbers; the CPU run still validates shapes, dispatch and the dense-side
growth curve.

Usage::

    python tools/paged_sweep.py [--block-sizes 16,32] [--slots 8]
                                [--json out.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _build_and_collect(model, block_size, max_slots, paged_env):
    from bigdl_tpu.observability import perf
    from bigdl_tpu.serving import DecodeScheduler
    if paged_env is None:
        os.environ.pop("BIGDL_TPU_PAGED_ATTN", None)
    else:
        os.environ["BIGDL_TPU_PAGED_ATTN"] = paged_env
    n0 = len(perf.registry().artifacts())
    sched = DecodeScheduler(model, max_slots=max_slots,
                            block_size=block_size, max_seq_len=256,
                            prefill_chunk=16)
    try:
        sched.start(warmup=True)
    finally:
        sched.shutdown()
    out = []
    for a in perf.registry().artifacts()[n0:]:
        if a.name != "serve/decode_step":
            continue
        toks = next((s for s in a.input_shapes if s.endswith(":int32")),
                    "?")
        out.append({"tokens": toks,
                    "flops": a.flops,
                    "bytes_accessed": a.analysis.get("bytes_accessed"),
                    "compile_s": round(a.compile_seconds, 3)})
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--block-sizes", default="16,32")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--json", default=None,
                    help="also write the sweep as JSON")
    args = ap.parse_args()

    import jax
    from bigdl_tpu import observability as obs
    from bigdl_tpu.models.transformer_lm import TransformerLM
    obs.enable()
    backend = jax.default_backend()
    kernel_env = "on" if backend == "tpu" else "interpret"
    if backend != "tpu":
        print("paged_sweep: WARNING — backend is %r: the kernel arm runs "
              "the Pallas INTERPRETER, whose cost analysis describes the "
              "interpreter program, not the mosaic kernel. bytes_accessed "
              "deltas below are only meaningful on TPU." % backend,
              file=sys.stderr)

    model = TransformerLM(vocab_size=128, hidden_size=64, num_heads=4,
                          filter_size=128, num_layers=2, max_len=512,
                          num_kv_heads=2)
    model.ensure_initialized()

    sweep = []
    for bs in [int(b) for b in args.block_sizes.split(",")]:
        dense = _build_and_collect(model, bs, args.slots, "off")
        kern = _build_and_collect(model, bs, args.slots, kernel_env)
        sweep.append({"block_size": bs, "backend": backend,
                      "kernel_mode": kernel_env,
                      "dense": dense, "kernel": kern})
        print(f"\nblock_size={bs} ({backend}, kernel={kernel_env})")
        print(f"  {'tokens':>14} {'dense bytes':>12} {'kernel bytes':>13} "
              f"{'drop':>6}")
        kern_by = {k["tokens"]: k for k in kern}
        for d in dense:
            k = kern_by.get(d["tokens"])
            db, kb = d["bytes_accessed"], k and k["bytes_accessed"]
            drop = f"{db / kb:.2f}x" if (db and kb) else "-"
            print(f"  {d['tokens']:>14} {db or 0:>12.0f} "
                  f"{(kb or 0):>13.0f} {drop:>6}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema": "bigdl_tpu.paged_sweep.v1",
                       "sweep": sweep}, f, indent=1)
        print(f"\npaged_sweep: wrote {args.json}")


if __name__ == "__main__":
    main()
