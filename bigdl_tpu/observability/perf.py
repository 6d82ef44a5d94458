"""Performance introspection: what the compiled programs COST.

The span/health layers say *that* a step is slow; this module says *how
far from the hardware ceiling* it is. Three pieces:

* **Compiled-program analytics** — every compile site (the optimizer's
  train step, the evaluator/predictor forwards, the serving warmup
  buckets) routes its ``jax.jit`` through :class:`InstrumentedJit`,
  which AOT-lowers and compiles each distinct input-shape signature
  explicitly and records a :class:`CompiledArtifact` into the process
  :class:`ArtifactRegistry`: XLA's own ``cost_analysis()`` FLOPs /
  bytes-accessed, ``memory_analysis()`` argument/output/temp bytes,
  compile wall time, input shapes, and compile-cache provenance
  (hit/miss deltas from the persistent-cache counters). Backends whose
  executables lack the analysis APIs degrade to a shape-and-timing-only
  artifact — never an error. ``tools/xla_report.py`` renders the
  registry (per-program table + HBM headroom).
* **Live MFU** — :func:`note_step` divides the artifact's model FLOPs
  by the step wall time the loop already measures and by the device's
  peak FLOP/s (:func:`peak_flops`, env-overridable with
  ``BIGDL_TPU_PEAK_FLOPS``), publishing ``perf/mfu`` (last dispatch),
  ``perf/mfu_mean`` (run-cumulative), ``perf/model_flops_per_s`` and a
  host-vs-dispatch-vs-device step-phase decomposition
  (``perf/phase_*_frac``) from the phase times the spans already
  stamp. Pure host-side arithmetic on numbers the loop already has —
  zero new device readbacks, ``check_no_sync`` clean.
* **Artifact export** — :func:`dump_artifacts` writes the registry (+
  the ``mem/*`` gauges for headroom context) as strict JSON next to
  the flight bundles, which is what ``tools/xla_report.py`` and the
  crash bundle consume.

Import discipline: like the rest of ``bigdl_tpu.observability`` this
module is stdlib-only at import time (report tools load the package
without a backend); jax is imported lazily inside the functions that
need it.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Dict, List, Optional

from . import metrics as _metrics
from . import trace as _trace

_LOG = logging.getLogger("bigdl_tpu.observability.perf")

ARTIFACT_SCHEMA = "bigdl_tpu.xla_programs.v1"

# bf16 peak FLOP/s per chip by device_kind substring (public specs).
# Ordered: first substring match wins (v5p before v5). The ONE table
# bench.py's offline MFU and the live perf/mfu gauge share — they must
# never disagree about the ceiling.
PEAK_FLOPS_TABLE = (
    ("v6", 918.0e12), ("v5p", 459.0e12), ("v5", 197.0e12),
    ("v4", 275.0e12), ("v3", 123.0e12), ("v2", 46.0e12),
)


class UnknownDeviceError(LookupError):
    """``device_kind`` is not in :data:`PEAK_FLOPS_TABLE` and no
    ``BIGDL_TPU_PEAK_FLOPS`` override names a ceiling: utilization
    against an assumed peak would be a made-up number."""


def peak_flops(device_kind: str = "") -> float:
    """Peak FLOP/s for ``device_kind``. ``BIGDL_TPU_PEAK_FLOPS`` (a
    float, e.g. ``1e12``) overrides the table — the knob the CPU tests
    and non-TPU backends use to make MFU well-defined. A device the
    table does not know raises :class:`UnknownDeviceError`."""
    env = os.environ.get("BIGDL_TPU_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            _LOG.warning("ignoring unparsable BIGDL_TPU_PEAK_FLOPS=%r", env)
    dk = (device_kind or "").lower()
    for sub, f in PEAK_FLOPS_TABLE:
        if sub in dk:
            return f
    raise UnknownDeviceError(
        f"no peak FLOP/s known for device_kind {device_kind!r}; add it to "
        "PEAK_FLOPS_TABLE or set BIGDL_TPU_PEAK_FLOPS")


def analyze_compiled(compiled) -> Dict[str, float]:
    """Best-effort extraction of XLA's cost/memory analysis from an AOT
    ``jax.stages.Compiled``. Every field is optional: a backend (or jax
    version) without the API contributes nothing, never an exception —
    the artifact then records shapes and compile time only."""
    out: Dict[str, float] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            for src, dst in (("flops", "flops"),
                             ("bytes accessed", "bytes_accessed"),
                             ("transcendentals", "transcendentals")):
                v = ca.get(src)
                if isinstance(v, (int, float)) and v >= 0:
                    out[dst] = float(v)
    except Exception:  # noqa: BLE001 — analytics must never break a build
        pass
    try:
        ma = compiled.memory_analysis()
        for src, dst in (
                ("argument_size_in_bytes", "argument_bytes"),
                ("output_size_in_bytes", "output_bytes"),
                ("temp_size_in_bytes", "temp_bytes"),
                ("alias_size_in_bytes", "alias_bytes"),
                ("generated_code_size_in_bytes", "generated_code_bytes")):
            v = getattr(ma, src, None)
            if isinstance(v, (int, float)) and v >= 0:
                out[dst] = float(v)
    except Exception:  # noqa: BLE001
        pass
    return out


class CompiledArtifact:
    """One compiled XLA program, as the introspection plane sees it."""

    __slots__ = ("name", "kind", "input_shapes", "steps_per_program",
                 "compile_seconds", "analysis", "cache_hits",
                 "cache_misses", "backend", "device_kind", "created_at",
                 "degraded")

    def __init__(self, name: str, kind: str, input_shapes: List[str],
                 steps_per_program: int = 1, compile_seconds: float = 0.0,
                 analysis: Optional[Dict[str, float]] = None,
                 cache_hits: int = 0, cache_misses: int = 0,
                 backend: str = "", device_kind: str = "",
                 degraded: Optional[str] = None):
        self.name = name
        self.kind = kind
        self.input_shapes = list(input_shapes)
        self.steps_per_program = int(steps_per_program)
        self.compile_seconds = float(compile_seconds)
        self.analysis = dict(analysis or {})
        self.cache_hits = int(cache_hits)
        self.cache_misses = int(cache_misses)
        self.backend = backend
        self.device_kind = device_kind
        self.created_at = time.time()
        self.degraded = degraded

    @property
    def flops(self) -> Optional[float]:
        return self.analysis.get("flops")

    @property
    def flops_per_step(self) -> Optional[float]:
        f = self.analysis.get("flops")
        if f is None:
            return None
        return f / max(1, self.steps_per_program)

    def resident_bytes(self) -> Optional[float]:
        """Device-memory footprint of one execution (arguments + outputs
        + temporaries) — what ``tools/xla_report.py`` holds against the
        ``mem/device_peak_bytes`` gauge for HBM headroom."""
        keys = ("argument_bytes", "output_bytes", "temp_bytes")
        if not any(k in self.analysis for k in keys):
            return None
        return sum(self.analysis.get(k, 0.0) for k in keys)

    def to_dict(self) -> Dict:
        return {
            "name": self.name, "kind": self.kind,
            "input_shapes": list(self.input_shapes),
            "steps_per_program": self.steps_per_program,
            "compile_seconds": round(self.compile_seconds, 6),
            "analysis": dict(self.analysis),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "backend": self.backend, "device_kind": self.device_kind,
            "created_at": self.created_at,
            "degraded": self.degraded,
        }

    def __repr__(self):
        f = self.flops
        return (f"CompiledArtifact({self.name!r}, kind={self.kind!r}, "
                f"flops={f if f is not None else 'n/a'}, "
                f"compile={self.compile_seconds:.3f}s)")


class ArtifactRegistry:
    """Process-wide list of compiled-program artifacts (thread-safe).

    Recording also mirrors aggregates into the metrics registry —
    ``compile/programs``, ``compile/wall_s`` (histogram),
    ``compile/flops_last`` / ``compile/resident_bytes_last`` gauges —
    so the Prometheus/bench exporters see compile activity without a
    second collection path."""

    def __init__(self):
        self._artifacts: List[CompiledArtifact] = []
        self._lock = threading.Lock()

    def record(self, artifact: CompiledArtifact) -> CompiledArtifact:
        with self._lock:
            self._artifacts.append(artifact)
        reg = _metrics.registry()
        reg.counter("compile/programs").inc()
        reg.histogram("compile/wall_s", unit="s").observe(
            artifact.compile_seconds)
        if artifact.degraded:
            reg.counter("compile/degraded").inc()
        if artifact.flops is not None:
            reg.gauge("compile/flops_last", unit="flops").set(artifact.flops)
        rb = artifact.resident_bytes()
        if rb is not None:
            reg.gauge("compile/resident_bytes_last", unit="bytes").set(rb)
        return artifact

    def artifacts(self) -> List[CompiledArtifact]:
        with self._lock:
            return list(self._artifacts)

    def latest(self, name: str) -> Optional[CompiledArtifact]:
        with self._lock:
            for a in reversed(self._artifacts):
                if a.name == name:
                    return a
        return None

    def by_name(self) -> Dict[str, List[CompiledArtifact]]:
        out: Dict[str, List[CompiledArtifact]] = {}
        for a in self.artifacts():
            out.setdefault(a.name, []).append(a)
        return out

    def clear(self):
        with self._lock:
            self._artifacts.clear()


_registry = ArtifactRegistry()


def registry() -> ArtifactRegistry:
    return _registry


def reset():
    """Clear artifacts AND the live-MFU accumulators (tests)."""
    _registry.clear()
    _steps.reset()


def _backend_info():
    """(backend, device_kind) — lazy jax, never raises (pure-host tests
    and report tools record artifacts without a backend)."""
    try:
        import jax
        dev = jax.devices()[0]
        return jax.default_backend(), getattr(dev, "device_kind", "")
    except Exception:  # noqa: BLE001
        return "", ""


def _shape_strs(args) -> List[str]:
    """Flat ``shape:dtype`` strings for an argument tuple (lazy jax)."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(args)
    except Exception:  # noqa: BLE001
        return []
    out = []
    for l in leaves[:64]:  # bound: a big param tree is provenance noise
        shape = getattr(l, "shape", ())
        dtype = getattr(l, "dtype", type(l).__name__)
        out.append(f"{tuple(shape)}:{dtype}")
    if len(leaves) > 64:
        out.append(f"... +{len(leaves) - 64} more leaves")
    return out


def _cache_counters():
    reg = _metrics.registry()
    return (reg.counter("engine/compile_cache_hits").value,
            reg.counter("engine/compile_cache_misses").value)


def record_compiled(name: str, kind: str, compiled=None, *,
                    compile_seconds: float = 0.0, input_shapes=None,
                    steps_per_program: int = 1, cache_hits: int = 0,
                    cache_misses: int = 0,
                    degraded: Optional[str] = None) -> CompiledArtifact:
    """Record one compiled program into the process registry (and the
    ``compile/*`` metrics). ``compiled`` may be None (degraded sites)."""
    analysis = analyze_compiled(compiled) if compiled is not None else {}
    if compiled is not None and not analysis and degraded is None:
        degraded = "cost/memory analysis unavailable on this backend"
    backend, device_kind = _backend_info()
    return _registry.record(CompiledArtifact(
        name, kind, input_shapes or [], steps_per_program=steps_per_program,
        compile_seconds=compile_seconds, analysis=analysis,
        cache_hits=cache_hits, cache_misses=cache_misses,
        backend=backend, device_kind=device_kind, degraded=degraded))


class InstrumentedJit:
    """AOT-compiling wrapper around a ``jax.jit``-ed function: the same
    call surface, but every distinct input-shape signature is lowered +
    compiled EXPLICITLY (``fn.lower(*args).compile()``) so its XLA cost
    and memory analysis land in the artifact registry — the jit call
    path gives no public handle on its executables.

    * One compile per signature, exactly like jit's own cache (and it
      shares the persistent compilation cache, so a warm process pays
      tracing only).
    * ``key_argnums`` bounds the per-call keying cost: compile sites
      whose parameter trees are shape-stable for the life of the
      function (the optimizer step: params/opt-state never change
      shape, only the batch does) key on the data arguments alone.
    * A failure to lower or compile PROPAGATES: it is the program's own
      (a Mosaic refusal, an OOM at compile), the jit path would only pay
      for it a second time, and the caller must see it. Only an AOT
      executable that rejects its arguments (stricter than jit about
      placement) falls back to the plain jit path for this wrapper.
    * When observability is disabled the wrapper IS the plain jit call
      — one flag read of overhead, no artifacts (PR-1 contract: the
      disabled path stays bulletproof and free).
    """

    def __init__(self, jit_fn, *, name: str, kind: str,
                 key_argnums: Optional[tuple] = None,
                 steps_per_program=1):
        self._jit = jit_fn
        self.name = name
        self.kind = kind
        self.key_argnums = tuple(key_argnums) if key_argnums else None
        #: int, or ``callable(args) -> int`` resolved at compile time —
        #: a clamped superstep compiles a separate program with FEWER
        #: steps than the configured K, and its artifact must say so
        self.steps_per_program = steps_per_program \
            if callable(steps_per_program) else int(steps_per_program)
        self._compiled: Dict[tuple, object] = {}
        self._artifacts: Dict[tuple, CompiledArtifact] = {}
        #: artifact of the program the LAST __call__ executed — what an
        #: MFU caller must read (a clamped superstep runs a different
        #: program than the full-K dispatch; "latest by name" would lie)
        self.last_artifact: Optional[CompiledArtifact] = None
        #: True when the last __call__ paid a compile — its wall time
        #: measures XLA, not the model; MFU accounting must skip it
        self.last_call_compiled = False
        self._broken = False
        self._lock = threading.Lock()

    def _key(self, args) -> Optional[tuple]:
        try:
            import jax
            src = args if self.key_argnums is None else \
                tuple(args[i] for i in self.key_argnums)
            return tuple(
                (tuple(getattr(l, "shape", ())),
                 str(getattr(l, "dtype", type(l).__name__)))
                for l in jax.tree_util.tree_leaves(src))
        except Exception:  # noqa: BLE001
            return None

    def _steps(self, args) -> int:
        if not callable(self.steps_per_program):
            return self.steps_per_program
        try:
            return int(self.steps_per_program(args))
        except Exception:  # noqa: BLE001 — provenance, never a failure
            return 1

    def _compile(self, key, args):
        h0, m0 = _cache_counters()
        t0 = time.perf_counter()
        compiled = self._jit.lower(*args).compile()
        dt = time.perf_counter() - t0
        h1, m1 = _cache_counters()
        art = record_compiled(
            self.name, self.kind, compiled,
            compile_seconds=dt, input_shapes=_shape_strs(args),
            steps_per_program=self._steps(args),
            cache_hits=int(h1 - h0), cache_misses=int(m1 - m0))
        with self._lock:
            self._compiled[key] = compiled
            self._artifacts[key] = art
        return compiled

    def __call__(self, *args):
        if self._broken or not _trace.enabled():
            return self._jit(*args)
        key = self._key(args)
        if key is None:
            return self._jit(*args)
        compiled = self._compiled.get(key)
        self.last_artifact = self._artifacts.get(key)
        self.last_call_compiled = False
        if compiled is None:
            self.last_call_compiled = True
            compiled = self._compile(key, args)
            self.last_artifact = self._artifacts.get(key)
        try:
            return compiled(*args)
        except (TypeError, ValueError) as e:
            # argument/layout validation raises BEFORE execution — the
            # donated buffers are still alive, so re-running through the
            # jit path is safe; the AOT strictness is this wrapper's own
            # doing, so it degrades permanently
            self._broken = True
            _LOG.warning(
                "%s: AOT executable rejected its arguments (%s: %s); "
                "falling back to the jit path", self.name,
                type(e).__name__, e)
            return self._jit(*args)
        # anything else (XlaRuntimeError: device OOM, dead collective,
        # lost device) propagates UNTOUCHED: the buffers may already be
        # donated — a silent jit re-run would trip 'Array has been
        # deleted' and bury the real error the Tier-2 FaultPolicy's
        # classify_failure needs to see — and the failure is the
        # device's, not the AOT path's, so the wrapper stays armed

    def compiled_shape_count(self) -> int:
        return len(self._compiled)


def instrument_jit(jit_fn, *, name: str, kind: str,
                   key_argnums: Optional[tuple] = None,
                   steps_per_program: int = 1) -> InstrumentedJit:
    """Wrap an already-``jax.jit``-ed function for artifact capture."""
    return InstrumentedJit(jit_fn, name=name, kind=kind,
                           key_argnums=key_argnums,
                           steps_per_program=steps_per_program)


# ------------------------------------------------------------------ MFU

_UNRESOLVED = object()   # _StepPerf: no peak lookup has happened yet


class _StepPerf:
    """Run-cumulative live-MFU bookkeeping (host floats only)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._total_flops = 0.0
        self._total_wall = 0.0
        self._peak = None       # None = no ceiling known: MFU unpublished
        self._peak_env = _UNRESOLVED  # override value the cache was built for

    def reset(self):
        with self._lock:
            self._total_flops = 0.0
            self._total_wall = 0.0
            self._peak = None
            self._peak_env = _UNRESOLVED

    def peak(self) -> Optional[float]:
        """The device's peak FLOP/s, or None when the device is not in
        the table and no override is set (a CPU run: the MFU gauges then
        stay unpublished rather than divide by an assumed ceiling)."""
        # one lazy device_kind lookup per process; re-resolved whenever
        # the BIGDL_TPU_PEAK_FLOPS override CHANGES — including being
        # unset (a smoke-phase override must not leak into the real
        # measurement later in the same process)
        env = os.environ.get("BIGDL_TPU_PEAK_FLOPS")
        if env != self._peak_env:
            _, dk = _backend_info()
            try:
                self._peak = peak_flops(dk)
            except UnknownDeviceError:
                self._peak = None
            self._peak_env = env
        return self._peak

    def note(self, flops: Optional[float], wall_s: float,
             host_s: Optional[float] = None,
             dispatch_s: Optional[float] = None):
        """``wall_s`` is the FULL iteration wall (fetch + dispatch +
        resolve) — the throughput definition of MFU (delivered FLOPs
        per second of wall clock, the same denominator bench.py's
        timed loop uses). Under ``async``/``window:K`` the dispatch
        call alone returns in microseconds while the device still
        computes; dividing by that sliver would read MFU orders of
        magnitude HIGH exactly when the run is host-bound, inverting
        the signal. The iteration wall is ≥ the device time under
        every sync policy, so the gauge can only under-claim, never
        flatter."""
        if flops is None or wall_s <= 0:
            return
        peak = self.peak()
        with self._lock:
            self._total_flops += flops
            self._total_wall += wall_s
            tf, tw = self._total_flops, self._total_wall
        reg = _metrics.registry()
        reg.gauge("perf/model_flops_per_s", unit="flops/s").set(
            flops / wall_s)
        if peak is not None:
            reg.gauge("perf/mfu").set(flops / wall_s / peak)
            reg.gauge("perf/mfu_mean").set(tf / tw / peak)
        reg.counter("perf/model_flops", unit="flops").inc(flops)
        if host_s is not None and dispatch_s is not None:
            # host = producing/fetching the batch, dispatch = enqueueing
            # the program, device = the remainder of the iteration
            # (dominated by the loss-resolution wait on device compute)
            total = max(wall_s, 1e-12)
            device_s = max(wall_s - host_s - dispatch_s, 0.0)
            reg.gauge("perf/phase_host_frac").set(host_s / total)
            reg.gauge("perf/phase_dispatch_frac").set(dispatch_s / total)
            reg.gauge("perf/phase_device_frac").set(device_s / total)


_steps = _StepPerf()


def note_step(artifact, wall_s: float,
              host_s: Optional[float] = None,
              dispatch_s: Optional[float] = None):
    """Publish the live MFU gauges for one completed dispatch:
    ``artifact`` is the :class:`CompiledArtifact` of the program that
    just ran (an :class:`InstrumentedJit`'s ``last_artifact``), or a
    registry name to look up the newest by. ``wall_s`` is the FULL
    iteration wall the loop already measured (see :meth:`_StepPerf.
    note` for why the dispatch sliver alone would lie under async
    policies). Pure host arithmetic — no device access of any kind.
    Quietly does nothing when the artifact is missing or carries no
    FLOPs (degraded backend): a gauge that silently lies is worse than
    one that is absent."""
    art = _registry.latest(artifact) if isinstance(artifact, str) \
        else artifact
    if art is None:
        return
    _steps.note(art.flops, wall_s, host_s=host_s, dispatch_s=dispatch_s)


# ---------------------------------------------------------------- export

def artifacts_snapshot() -> List[Dict]:
    return [a.to_dict() for a in _registry.artifacts()]


def dump_artifacts(path: Optional[str] = None) -> Optional[str]:
    """Write the artifact registry (+ the ``mem/*`` gauges for HBM
    headroom context) as strict JSON; returns the path. Defaults into
    the flight-bundle directory (``xla_programs_<pid>.json``). Never
    raises — export is advisory."""
    try:
        from . import flight as _flight
        if path is None:
            d = _flight.bundle_dir()
            os.makedirs(d, exist_ok=True)
            path = os.path.join(d, f"xla_programs_{os.getpid()}.json")
        mem = {name: inst for name, inst in
               _metrics.registry().snapshot().items()
               if name.startswith("mem/") or name.startswith("compile/")}
        doc = {
            "schema": ARTIFACT_SCHEMA,
            "written_at": time.time(),
            "pid": os.getpid(),
            "programs": artifacts_snapshot(),
            "metrics": mem,
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(_flight._json_safe(doc), f, indent=1, default=str,
                      allow_nan=False)
        os.replace(tmp, path)
        return path
    except Exception:  # noqa: BLE001
        _LOG.exception("failed to dump compiled-program artifacts")
        return None
