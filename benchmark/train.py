"""A training cell: ``Optimizer.optimize()`` is the entry, the benchmark's
own end trigger stamps every iteration, reads the state of the first steps
for the comparison, opens the window after the warm-up steps and ends the
run at ``--seconds``. One object is built, checked and timed."""
from __future__ import annotations

import gc
import time

import numpy as np

from . import harness, reference, traffic, weights


def _live(opt, what):
    """The parameters or Adam's first moment as a tree of the model's
    layout, from the optimizer's live state after a step. Under ``zero1``
    the program keeps both as one flat vector (the moment sharded over the
    chips): it is brought to the host and cut by the program's own view."""
    params, opt_state, _ = opt._live_state
    got = params if what == "params" else opt_state["m"]
    if getattr(opt, "parameter_mode", None) == "zero1":
        return opt._flat.unflatten(np.asarray(got))
    return got


def build(cell, seed, chips):
    import jax
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import Transformer
    from bigdl_tpu.optim import Adam, Optimizer
    from bigdl_tpu.utils import engine

    cfg, job = cell["config_data"], cell["traffic_data"]
    m, entry = cfg["model"], cfg["entry"]
    B = job["batch_per_chip"] * chips
    rows = traffic.train_rows(job, seed, chips, m["vocab_size"])
    samples = [Sample(r[:-1].astype(np.float32), r[1:].astype(np.float32))
               for r in rows]
    fed = []
    keep = job["check_steps"] * B

    class Recorder(Transformer):
        """Passes samples through and keeps the first steps' rows as fed."""

        def apply(self, it):
            for s in it:
                if len(fed) < keep:
                    fed.append(s)
                yield s

    engine.set_seed(int(seed) & 0x7FFFFFFF)
    model = harness.build_model(m, remat=entry.get("remat", False))
    model.params = weights.make_params(m, seed)
    model.state = {}
    kw = {}
    if job.get("parameter_mode"):
        kw["parameter_mode"] = job["parameter_mode"]
    o = entry["optimizer"]
    # `Optimizer` picks Local or Distri from the devices it sees; on the
    # chip the run has exactly the cell's chips (run.find_devices). Only a
    # CPU test with more virtual devices than the cell asks for names the
    # class the factory would pick on a one-chip machine.
    make = Optimizer
    if chips == 1 and len(jax.devices()) > 1:
        from bigdl_tpu.optim import LocalOptimizer as make
    elif 1 < chips < len(jax.devices()):
        from bigdl_tpu.parallel.mesh import data_parallel_mesh
        kw["mesh"] = data_parallel_mesh(chips)
    opt = make(model=model,
                    training_set=DataSet.array(samples).transform(Recorder()),
                    criterion=nn.LMCriterion(padding_value=0),
                    optim_method=Adam(learningrate=o["learning_rate"],
                                      beta1=o["beta1"], beta2=o["beta2"],
                                      epsilon=o["epsilon"]),
                    batch_size=B, **kw)
    want = "DistriOptimizer" if chips > 1 else "LocalOptimizer"
    if type(opt).__name__ != want:
        raise SystemExit(f"benchmark: Optimizer resolved to "
                         f"{type(opt).__name__} on {len(jax.devices())} "
                         f"device(s); the cell asks for {want}")
    return model, opt, fed, B


def run(cell, seed, seconds, trace, env):
    import jax
    from bigdl_tpu.optim import Trigger

    chips = cell["chips"]
    cfg, job = cell["config_data"], cell["traffic_data"]
    m = cfg["model"]
    T = job["seq_len"]
    harness.stamp("imports done")
    model, opt, fed, B = build(cell, seed, chips)
    harness.stamp("model, weights and optimizer built")
    b1 = cfg["entry"]["optimizer"]["beta1"]
    check_steps, warm = job["check_steps"], job["warmup_steps"]
    tracer = harness.Tracer(env["trace_dir"]) if trace else None
    trace_at = warm + 2

    norms = jax.jit(reference.leaf_norms)
    delta = jax.jit(lambda p, q: reference.leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, p, q)))
    w = {"seen": 0, "losses": [], "t_open": None, "steps": 0, "mark": None,
         "span": None, "traced_steps": 0, "t_steps": [], "gc_s": 0.0,
         "first_grad": {}}
    keep_leaves = job.get("grad_diff_leaves", ())

    def on_gc(phase, info):
        # what the collector takes inside the window, to place a stall
        if w["t_open"] is not None:
            if phase == "start":
                w["gc_t0"] = time.perf_counter()
            elif "gc_t0" in w:
                w["gc_s"] += time.perf_counter() - w.pop("gc_t0")

    def end(state):
        n = state["neval"]
        if n == w["seen"]:          # the epoch boundary asks once more
            return False
        w["seen"] = n
        if n <= warm:
            harness.stamp(f"step {n} done")
        if n <= check_steps:
            w["losses"].append(float(state["loss"]))
            if n == 1:
                mom = _live(opt, "m")
                w["grad_norms"] = {k: v / (1 - b1) for k, v in
                                   reference.flat(norms(mom)).items()}
                # the first gradient itself on the cell's chosen leaves,
                # kept on the host until the reference has its own
                w["first_grad"] = {
                    k: np.asarray(a) / np.float32(1 - b1) for k, a in
                    reference.leaf_arrays(mom).items()
                    if keep_leaves == "all" or k.split("/")[0] in keep_leaves}
            if n == check_steps:
                params = _live(opt, "params")
                # the weights the run started from, made again from the
                # seed by the call that made them (they were donated)
                w["delta_norms"] = reference.flat(
                    delta(params, weights.make_params(m, seed)))
            harness.stamp(f"step {n} state read")
        if n < warm:
            return False
        if w["t_open"] is None:     # n == warm, or past it over a skip
            w["mark"] = env["watch"].mark()
            w["data_from"] = len(opt.metrics.values.get("data_time", ()))
            w["t_open"] = time.perf_counter()
            w["span"] = harness.annotate("optimize")
            w["span"].__enter__()
            return False
        w["steps"] += 1
        now = time.perf_counter()
        w["t_steps"].append(now)
        if tracer is not None:
            if n == trace_at:
                w["span"].__exit__(None, None, None)
                tracer.start()
                w["span"] = harness.annotate("optimize")
                w["span"].__enter__()
            elif tracer.running and (
                    n == trace_at + job["trace_steps"]
                    or now - w["t_open"] >= seconds):
                w["traced_steps"] = n - trace_at
                w["span"].__exit__(None, None, None)
                tracer.stop()
                w["span"] = harness.annotate("optimize")
                w["span"].__enter__()
        return now - w["t_open"] >= seconds

    opt.set_end_when(Trigger(end))
    gc.callbacks.append(on_gc)
    try:
        opt.optimize()
        jax.block_until_ready(model.params)
        t_close = time.perf_counter()
    finally:
        gc.callbacks.remove(on_gc)
    gaps = np.diff([w["t_open"]] + w["t_steps"]) if w["t_steps"] else [0.0]
    harness.stamp(f"window closed after {w['steps']} steps; longest step "
                  f"{max(gaps):.3f} s (step {int(np.argmax(gaps)) + 1} of the "
                  f"window), median {float(np.median(gaps)):.3f} s, "
                  f"collector {w['gc_s']:.3f} s")
    if w["span"] is not None:
        w["span"].__exit__(None, None, None)
    if w["t_open"] is None:
        raise SystemExit("benchmark: the run ended before its window opened")
    late = env["watch"].since(w["mark"])
    window_s = t_close - w["t_open"]
    # a step the program skipped over a NaN trained nothing, and one it
    # replayed after a fault is not what the cell times: both are failures
    # of the run, and a skipped step's tokens are not counted
    skipped = len(opt.metrics.values.get("nan_skips", ()))
    failed = skipped + len(opt.metrics.values.get("fault_retries", ()))
    tokens = max(0, w["steps"] - skipped) * B * T
    data_wait = opt.metrics.values.get("data_time", [])[w["data_from"]:]
    mem = harness.memory_peak_bytes(jax.devices()[:chips])

    # the comparison: the program's state is freed, then the reference
    # follows the first steps on the rows the program was fed
    batches = []
    for s in range(check_steps):
        part = fed[s * B:(s + 1) * B]
        batches.append((np.stack([x.feature() for x in part]).astype(np.int32),
                        np.stack([x.label() for x in part]).astype(np.int32)))
    model.params = model.grad_params = None
    opt._live_state = None
    del model, opt
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.train_steps(
        weights.make_params(m, seed), batches, m, cfg["entry"]["optimizer"],
        row_block=job.get("reference_row_block", 2) * chips,
        first_grad=w["first_grad"] or None,
        devices=jax.devices()[:chips] if chips > 1 else None,
        log=harness.stamp)
    numbers = compare(w, ref)
    reference_s = time.perf_counter() - t_ref
    if ref.get("grad_diff_norms"):
        kinds = harness.by_kind(ref["grad_diff_norms"], ref["grad_norms"])
        harness.stamp("first gradient's difference by kind of leaf, median "
                      "and worst: " + ", ".join(
                          f"{k} {a:.2e} {b:.2e}" for k, (a, b) in kinds.items()))
    window = {
        "kind": "train", "window_s": window_s, "steps": w["steps"],
        "tokens": tokens, "tokens_per_step": B * T, "batch": B, "seq_len": T,
        "chips": chips, "data_wait_s": data_wait,
        "traced_steps": w["traced_steps"],
        "traced_s": (tracer.t1 - tracer.t0) if tracer and tracer.t1 else None,
        "compiled_in_window": late, "losses": w["losses"],
        "reference_losses": ref["losses"], "reference_s": reference_s,
        "step_longest_s": float(max(gaps)),
        "step_longest_at": int(np.argmax(gaps)) + 1,
        "step_median_s": float(np.median(gaps)), "collector_s": w["gc_s"]}
    end_to_end = {"train_tok_per_s": tokens / window_s}
    return {"t_open": w["t_open"], "window": window,
            "end_to_end": end_to_end, "numbers": numbers,
            "attempted": w["steps"], "failed": failed,
            "memory_peak_bytes": mem, "tracer": tracer}


def compare(w, ref):
    """The numbers compared, each against a limit of its own in the cell's
    file: every checked step's loss, the first gradient's norm and the
    parameters' change, the norms by the worst leaf; and, on the leaves the
    job names, the norm of the first gradient's DIFFERENCE from the
    reference's (a gap of norms averages rounding noise away, this does
    not: it is the number that sees the precision of the passes)."""
    numbers = {}
    for i, (a, b) in enumerate(zip(w["losses"], ref["losses"]), 1):
        numbers[f"loss{i}_gap"] = abs(a - b) / abs(b)
    skip = harness.excluded_leaves(ref["grad_norms"])
    numbers["grad_norm_gap"], _ = harness.worst_leaf_gap(
        w["grad_norms"], ref["grad_norms"])
    numbers["delta_norm_gap"], _ = harness.worst_leaf_gap(
        w["delta_norms"], ref["delta_norms"], exclude=skip)
    if ref.get("grad_diff_norms"):
        numbers["grad_diff"], _ = harness.worst_leaf_diff(
            ref["grad_diff_norms"], ref["grad_norms"])
    return numbers
