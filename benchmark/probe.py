"""Readings for the limits of ``correct``: the controls and the faults at the
cell's own size, many seeds to a process, each held to the cell's limits by
the run's own ``decide``.

    python -m benchmark.probe --workload <cell> --seeds 1,2,3 [--out file]
        [--cases control_bf16,fault_half_batch]

No program runs: the reference at float32/highest is set against itself
  control_bf16       in bfloat16 throughout (weights, passes, Adam)
  control_bf16_pass  forward and backward in bfloat16, float32 weights,
                     gradients and Adam (what a later PR would be tempted by)
  fault_half_batch   half of the batch left out, the mean over the rest
  fault_no_exchange  (cells of several chips) one chip's rows alone
and every number goes through the run's own comparison. ``--out`` also
writes the worst and the median leaf of the gradient's difference by kind
of leaf.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import harness, reference, traffic, train, weights
from .run import CHECKOUT, fail


def probe(cell, seeds, out=None, only=()):
    import jax
    import jax.numpy as jnp
    cfg, job = cell["config_data"], cell["traffic_data"]
    m, o = cfg["model"], cfg["entry"]["optimizer"]
    chips = cell["chips"]
    B = job["batch_per_chip"] * chips
    keep = job.get("grad_diff_leaves", ())
    # both sides are the reference, so a four-chip cell's readings need
    # not hold four chips: the rows go over the chips that are there
    devices = jax.devices() if len(jax.devices()) > 1 else None
    spread = len(devices) if devices else 1
    cases = [("control_bf16", {"dtype": jnp.bfloat16}),
             ("control_bf16_pass", {"dtype": jnp.bfloat16,
                                    "state_dtype": jnp.float32}),
             ("fault_half_batch", {"keep_rows": B // 2})]
    if chips > 1:
        cases.append(("fault_no_exchange", {"keep_rows": B // chips}))
    if only:
        cases = [c for c in cases if c[0] in only]
    for s in seeds:
        rows = traffic.train_rows(job, s, chips, m["vocab_size"])
        batches = [(rows[i * B:(i + 1) * B, :-1], rows[i * B:(i + 1) * B, 1:])
                   for i in range(job["check_steps"])]
        run = lambda **kw: reference.train_steps(
            weights.make_params(m, s), batches, m, o, devices=devices,
            row_block=job.get("reference_row_block", 2) * spread, **kw)
        ref = run(keep_first_grad=True)
        for name, kw in cases:
            got = run(keep_first_grad=True, **kw)
            first = {k: a for k, a in got.pop("first_grad").items()
                     if keep == "all" or k.split("/")[0] in keep}
            diff = reference.diff_norms(first, ref["first_grad"])
            numbers = train.compare(got, dict(ref, grad_diff_norms=diff))
            ok, compared = harness.decide(numbers, cell["limits"])
            line = {"seed": s, "what": name, "correct": ok, **numbers}
            print("probe: " + json.dumps(line), flush=True)
            if out:
                with open(out, "a") as f:
                    f.write(json.dumps(dict(line, by_kind=harness.by_kind(
                        diff, ref["grad_norms"]))) + "\n")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    ap.add_argument("--cases", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, CHECKOUT)
    cell = harness.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        fail("the readings are taken on the chip or not at all")
    probe(cell, [int(s) for s in args.seeds.split(",")], args.out,
          [c for c in args.cases.split(",") if c])


if __name__ == "__main__":
    main()
