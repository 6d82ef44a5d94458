"""What both kinds of run share: files by name, the compile watch, the
profiler window, memory readings and the comparison that decides
``correct``."""
from __future__ import annotations

import importlib.util
import json
import os
import statistics
import time

HERE = os.path.dirname(os.path.abspath(__file__))


_T0 = time.perf_counter()


def stamp(label: str):
    """A line of the run's timeline on standard error."""
    import sys
    print(f"benchmark: +{time.perf_counter() - _T0:8.2f}s {label}",
          file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = HERE) -> dict:
    """A cell with its configuration and traffic mix, each found by name."""
    cell = load_json(root, "workloads", f"{name}.json")
    cell["name"] = name
    cell["config_data"] = load_json(root, "configs", f"{cell['config']}.json")
    cell["traffic_data"] = load_json(root, "traffic",
                                     f"{cell['traffic']}.json")
    return cell


def metrics_for(cell_name: str, root: str = HERE):
    """Every per-layer metric file that lists this cell."""
    out = []
    mdir = os.path.join(root, "metrics")
    for fn in sorted(os.listdir(mdir)):
        if fn.endswith(".json"):
            m = load_json(mdir, fn)
            if cell_name in m["workloads"]:
                out.append(m)
    return out


def load_reader(metric: dict, root: str = HERE):
    path = os.path.join(root, "metrics", metric["reader"])
    spec = importlib.util.spec_from_file_location(
        "bm_reader_" + metric["reader"].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def build_model(m: dict, **kw):
    """The program's model at the configuration's sizes."""
    from bigdl_tpu.models import TransformerLM
    return TransformerLM(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_heads=m["num_heads"], filter_size=m["filter_size"],
        num_layers=m["num_layers"], max_len=m["max_len"],
        ffn_activation=m["ffn_activation"], **kw)


class CompileWatch:
    """Counts the programs JAX builds: one ``backend_compile_duration``
    event each, compiled or loaded from the persistent cache."""

    def __init__(self):
        import jax.monitoring
        self.programs = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs.append((kw.get("fun_name", "?"), duration))

    def mark(self) -> int:
        return len(self.programs)

    def since(self, mark: int):
        return [n for n, _ in self.programs[mark:]]


class Tracer:
    """A profiler window inside the measured window, with the benchmark's
    own host annotations on the profiler's clock."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.t0 = self.t1 = None
        self._span = None

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bm/traced")
        self._span.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @property
    def running(self):
        return self.t0 is not None and self.t1 is None

    def reduce(self):
        from . import trace_reduce
        return trace_reduce.load_xplane(trace_reduce.find_xplane(self.log_dir))


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation("bm/" + name)


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats()
        if st is None:
            return 0        # the CPU backend reports none (rehearsal only)
        peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks)


# ------------------------------------------------------------ the comparison

def excluded_leaves(ref_grad_norms: dict, share: float = 1e-3):
    """Leaves whose reference gradient is nought to rounding: under a
    thousandth of the median leaf's. Adam moves them by round-off alone,
    so their change is not compared."""
    med = statistics.median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v < share * med}


def worst_leaf_gap(prog: dict, ref: dict, exclude=()):
    """The gap between the program's norm and the reference's (not the
    norm of a difference), by the worst leaf, against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))[:4]}")
    med = statistics.median(ref.values())
    worst, where = 0.0, None
    for k, r in ref.items():
        if k in exclude:
            continue
        g = abs(prog[k] - r) / max(r, med)
        if g >= worst:
            worst, where = g, k
    return worst, where


def worst_leaf_diff(diff_norms: dict, ref_norms: dict):
    """The norm of a difference (not a gap of norms), by the worst of the
    leaves in ``diff_norms``, against the reference's norm of that leaf or
    of the median leaf, whichever is larger."""
    med = statistics.median(ref_norms.values())
    worst, where = 0.0, None
    for k, d in diff_norms.items():
        g = d / max(ref_norms[k], med)
        if g >= worst:
            worst, where = g, k
    return worst, where


def by_kind(diff: dict, ref_norms: dict):
    """{kind of leaf: (median, worst)} of diff / max(leaf, median leaf)."""
    med = statistics.median(ref_norms.values())
    kinds = {}
    for k, d in diff.items():
        kind = k.split("/", 1)[1] if k.startswith("block") else k
        kinds.setdefault(kind, []).append(d / max(ref_norms[k], med))
    return {k: (statistics.median(v), max(v)) for k, v in kinds.items()}


def decide(numbers: dict, limits: dict, failed: int = 0):
    """``correct`` and the rows compared: each number beside its limit. A
    number that is missing, not finite, or has no limit fails, and so does
    a run with a failed step."""
    rows, ok = {}, failed == 0
    for name in list(limits) + [n for n in numbers if n not in limits]:
        v, limit = numbers.get(name), limits.get(name)
        good = (v is not None and v == v and limit is not None
                and v <= limit)
        rows[name] = {"value": v, "limit": limit}
        ok = ok and good
    return ok, rows
