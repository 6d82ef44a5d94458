"""Readings for the limits of ``correct`` where a configuration names faults
of its own: ``benchmark.probe``'s comparison (the reference at
float32/highest set against itself), over the controls and over every entry
of the configuration's ``faults`` (``{name: keyword arguments of the
reference part's train_steps}``: part of the batch or of the mathematics
left out), each held to the cell's limits by the run's own ``decide``.

    python tests/benchmark/bm_faults.py --workload <cell> --seeds 1,2 [--cases a,b]

On the chip for a cell's readings; the tests run it on the CPU at toy size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import harness, traffic, train  # noqa: E402


def cases_of(cell):
    import jax.numpy as jnp
    cases = {"control_bf16": {"dtype": jnp.bfloat16},
             "control_bf16_pass": {"dtype": jnp.bfloat16,
                                   "state_dtype": jnp.float32}}
    cases.update(cell["config_data"].get("faults", {}))
    return cases


def probe(cell, seeds, only=(), log=None):
    """One line a seed and case: ``{"seed", "what", "correct", numbers}``."""
    cfg, job = cell["config_data"], cell["traffic_data"]
    reference, weights = cell["parts"].reference, cell["parts"].weights
    m, o = cfg["model"], cfg["entry"]["optimizer"]
    B = job["batch_per_chip"] * cell["chips"]
    keep = job.get("grad_diff_leaves", ())
    lines = []
    for s in seeds:
        rows = traffic.train_rows(job, s, cell["chips"], m["vocab_size"])
        batches = [(rows[i * B:(i + 1) * B, :-1], rows[i * B:(i + 1) * B, 1:])
                   for i in range(job["check_steps"])]
        run = lambda **kw: reference.train_steps(
            weights.make_params(m, s), batches, m, o, log=log,
            row_block=job.get("reference_row_block", 2), **kw)
        ref = run(keep_first_grad=True)
        for name, kw in cases_of(cell).items():
            if only and name not in only:
                continue
            got = run(keep_first_grad=True, **kw)
            first = {k: a for k, a in got.pop("first_grad").items()
                     if keep == "all" or k.split("/")[0] in keep}
            diff = reference.diff_norms(first, ref["first_grad"])
            numbers = train.compare(got, dict(ref, grad_diff_norms=diff))
            ok, _ = harness.decide(numbers, cell["limits"])
            lines.append({"seed": s, "what": name, "correct": ok, **numbers})
            print("probe: " + json.dumps(lines[-1]), flush=True)
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--cases", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the readings are taken on the chip or not at all")
    probe(cell, [int(s) for s in args.seeds.split(",")],
          [c for c in args.cases.split(",") if c], log=harness.stamp)


if __name__ == "__main__":
    main()
