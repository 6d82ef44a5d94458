"""Run one cell once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run is a new process: it makes the weights on the device from ``--seed``,
warms the cell's own shapes (set-up), measures for ``--seconds``, compares
what the timed path produced with the plain reference, and prints as its
last line of standard output one JSON object. It fails, and prints no
result, when it finds no TPU or another number of chips than the cell asks
for, when a program was compiled inside the window, or when a traced run
lacks a kernel that one of the cell's metrics reads.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from . import harness, peaks as peaks_mod  # noqa: E402

CHECKOUT = os.path.dirname(harness.HERE)


def fail(msg: str, code: int = 1):
    print(f"benchmark: FAILED - {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def find_devices(cell):
    """The chips the cell asks for, or no run."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        fail(f"platform is {d.platform!r} ({d.device_kind}), not 'tpu': "
             "a cell is measured on the chip or not at all")
    if len(devices) != cell["chips"]:
        fail(f"cell {cell['name']} asks for {cell['chips']} chip(s), JAX "
             f"finds {len(devices)}")
    return devices


def run_cell(cell, seed, seconds, trace, root=harness.HERE, t_start=None):
    """Everything of a run but the look for a chip. Returns the result
    object (``metrics`` empty off the chip: a CPU number is never written
    under a device metric's name)."""
    import jax
    from bigdl_tpu.utils import engine

    t_start = T_START if t_start is None else t_start
    engine.maybe_enable_compilation_cache()
    devices = jax.devices()
    on_chip = devices[0].platform == "tpu"
    trace_dir = os.path.join(os.path.dirname(root), ".bench_out", "trace",
                             cell["name"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    env = {"watch": harness.CompileWatch(), "trace_dir": trace_dir}
    kind = cell["config_data"]["entry"]["kind"]
    if kind != cell["traffic_data"]["kind"]:
        fail(f"cell {cell['name']}: configuration is built as {kind!r}, "
             f"traffic is for {cell['traffic_data']['kind']!r}")
    # the driver of a kind of entry is the module of that name beside
    # this one (today `train`): a new kind brings its file
    if not os.path.isfile(os.path.join(harness.HERE, f"{kind}.py")):
        fail(f"no driver for an entry of kind {kind!r}")
    driver = importlib.import_module(f"{__package__}.{kind}")
    res = driver.run(cell, seed, seconds, bool(trace), env)
    window = res["window"]
    slow = [(n, round(d, 1)) for n, d in env["watch"].programs if d >= 2.0]
    harness.stamp(f"{len(env['watch'].programs)} programs built, those over "
                  f"2 s: {slow}; cache {engine.compilation_cache_stats()}")
    if window["compiled_in_window"]:
        fail(f"{len(window['compiled_in_window'])} program(s) compiled "
             f"inside the window: {window['compiled_in_window'][:6]}")

    setup_s = res["t_open"] - t_start
    correct, rows = harness.decide(res["numbers"], cell["limits"],
                                   res["failed"])
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"] if on_chip else len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": bool(correct), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    e2e = dict(res["end_to_end"], setup_s=setup_s)
    units = {m["name"]: m["unit"] for m in
             harness.load_json(root, "end_to_end.json")}
    if not trace:
        for name in cell["end_to_end"] + ["setup_s"]:
            if on_chip:
                out["metrics"][name] = {"value": e2e[name],
                                        "unit": units[name]}
    else:
        reduced = res["tracer"].reduce() if on_chip else None
        ctx = {"cell": cell, "config": cell["config_data"],
               "mix": cell["traffic_data"], "window": window,
               "trace": reduced, "chips": cell["chips"],
               "peaks": peaks_mod.peaks_for(dev.device_kind) if on_chip
               else None}
        for metric in harness.metrics_for(cell["name"], root):
            if metric["source"] == "device_trace" and not on_chip:
                continue
            try:
                value = harness.load_reader(metric, root)(
                    ctx, **metric.get("args", {}))
            except LookupError as e:
                fail(f"metric {metric['name']}: {e}")
            if value is not None and on_chip:
                out["metrics"][metric["name"]] = {"value": value,
                                                  "unit": metric["unit"]}
        if reduced is not None:
            from . import trace_reduce
            busy = trace_reduce.busy_seconds(reduced)
            device["busy_s"] = sum(busy.values()) / len(busy)
            device["window_s"] = trace_reduce.window_seconds(reduced)
            out["breakdown"] = trace_reduce.breakdown(reduced)
            shutil.rmtree(trace_dir, ignore_errors=True)
    if not on_chip:
        out["rehearsal"] = f"platform {dev.platform}: no metric is reported"
    out["window"] = {k: v for k, v in window.items()
                     if isinstance(v, (int, float, str)) or v is None}
    out["compared"] = rows
    return out


def report(out):
    """The numbers compared, each beside its limit: the last lines of
    standard error; the result: the last line of standard output."""
    print(f"benchmark: correct = {out['correct']} attempted = "
          f"{out['attempted']} failed = {out['failed']}; compared:",
          file=sys.stderr)
    for name, row in out["compared"].items():
        print(f"benchmark: compared {name} = {row['value']!r} "
              f"(limit {row['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, CHECKOUT)
    try:
        import bigdl_tpu  # noqa: F401
    except ImportError as e:
        fail(f"cannot import bigdl_tpu from {CHECKOUT} ({e}): run from the "
             "root of a checkout")
    try:
        cell = harness.load_cell(args.workload)
    except FileNotFoundError as e:
        fail(f"no such cell, configuration or traffic file: {e}")
    find_devices(cell)
    report(run_cell(cell, args.seed, args.seconds, args.trace))


if __name__ == "__main__":
    main()
