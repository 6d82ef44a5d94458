"""Unified tracing + metrics (the production-operator view of training).

The reference exposed training progress only through TensorBoard
``TrainSummary``/``ValidationSummary``; everything else — step phase
timing, collective bytes, probe latency — lived in ad-hoc dicts and
prints. This package is the one subsystem the rest of the codebase
reports into:

* ``trace`` — span-based tracer: ``with observability.span("step/dispatch"):``
  nests via a thread-local stack, stamps monotonic clocks, and survives
  exceptions (the span closes and is tagged with the error type).
* ``metrics`` — a process-global registry of counters, gauges and
  histograms (reservoir quantiles), keyed by slash-namespaced names
  (``optim/step_time``, ``collective/psum_bytes``).
* ``exporters`` — Chrome trace-event JSON (load in Perfetto /
  chrome://tracing), Prometheus text format, a bridge into the existing
  ``visualization.Summary`` event files (TensorBoard keeps working), and
  the BENCH_*.json-compatible metric-line dump shared with ``bench.py``.
* ``health`` — whether the system is ALIVE: a stall watchdog over
  per-component progress beacons (``health/stall`` events), rolling
  loss/grad-norm anomaly detectors (spikes, plateaus, NaN streaks),
  device-memory telemetry (``mem/*`` live gauges), and env-gated
  ``jax.profiler`` windows (``BIGDL_TPU_PROFILE=start:stop``).
* ``flight`` — a bounded ring of recent structured events dumped as a
  JSON crash bundle on unhandled failure; render post-mortems with
  ``tools/flight_report.py``.
* ``perf`` — what the compiled programs COST: per-program XLA
  cost/memory artifacts from every compile site (``compile/*``,
  rendered by ``tools/xla_report.py``) and live MFU / step-phase
  gauges (``perf/mfu``, ``perf/phase_*_frac``) derived from them.
* ``cluster`` — per-process metric-snapshot files merged by rank 0
  into one cluster view (step-time skew, straggler attribution joined
  with heartbeat ages); render with ``tools/cluster_report.py``.

Zero-overhead when disabled: ``span()`` returns a shared no-op context
manager and call-sites guard metric writes with ``enabled()`` — the
disabled cost in the optimizer hot loop is one module-global flag read
per phase. Enable with ``observability.enable()`` or
``BIGDL_TPU_TRACE=1`` in the environment.

Span naming convention: ``<subsystem>/<phase>`` with the subsystem as a
stable prefix (``step/``, ``eval/``, ``predict/``, ``bench/``,
``host/``, ``epoch/``); nested
phases extend the parent's name (``step`` > ``step/data_fetch``).
"""
from __future__ import annotations

import os as _os

from .trace import (Tracer, enable, disable, enabled, span, instant,
                    complete, get_tracer, reset, GC_SPAN, GcPauses,
                    gc_hook_install, gc_hook_remove)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      registry, counter, gauge, histogram)
from .exporters import (chrome_trace, write_chrome_trace, prometheus_text,
                        SummaryBridge, metrics_dump, write_metrics_dump,
                        record_bench_line)
from . import flight
from . import health
from . import perf
from . import cluster

if _os.environ.get("BIGDL_TPU_TRACE") == "1":
    enable()
