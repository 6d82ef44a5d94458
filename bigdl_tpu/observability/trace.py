"""Span-based tracer: nested, thread-safe, monotonic, exception-safe.

Design constraints, in order:

1. **Disabled cost is the profiler's own no-op.** The hot-path
   spelling is ``with span("step/dispatch"):`` — when tracing is off
   that call builds one ``jax.profiler.TraceAnnotation``, which outside
   a profiler session reads one flag and records nothing (well under a
   microsecond; no clock read, no lock). The training loop keeps the
   instrumentation inline at all times (no conditional code paths to
   bit-rot).
2. **Monotonic clocks.** Spans stamp ``time.perf_counter_ns()``; wall
   clocks (NTP steps, suspend) must never produce negative durations in
   a trace.
3. **Thread-correct nesting.** Each thread owns its span stack
   (``threading.local``) so the async checkpoint writer or a prefetch
   thread nests its own spans without corrupting the main loop's stack.
   Finished spans land in one shared list (CPython list.append is
   atomic; the exporters snapshot under the tracer lock).
4. **One call site, one name, both sinks.** Every span is also a
   ``jax.profiler.TraceAnnotation`` of the same name with its args, so
   whenever a profiler session runs (the benchmark's traced window, a
   ``BIGDL_TPU_PROFILE`` window) the program's spans sit in the
   profile's host plane, on the device trace's clock, enabled or not.
   A span given ``step_num=`` is the profiler's step boundary
   (``StepTraceAnnotation``).
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation


class Span:
    """One finished (or open) span. Times are perf_counter nanoseconds."""

    __slots__ = ("name", "start_ns", "end_ns", "tid", "depth", "args")

    def __init__(self, name: str, start_ns: int, tid: int, depth: int,
                 args: Optional[Dict] = None):
        self.name = name
        self.start_ns = start_ns
        self.end_ns: Optional[int] = None
        self.tid = tid
        self.depth = depth
        self.args = args

    @property
    def duration_ns(self) -> int:
        return (self.end_ns or self.start_ns) - self.start_ns

    def __repr__(self):
        return (f"Span({self.name!r}, dur={self.duration_ns / 1e6:.3f}ms, "
                f"depth={self.depth})")


class _SpanHandle:
    """Context manager that closes its span exactly once, exception or
    not; an exception tags the span (``error: ExcType``) instead of
    leaking an open span on the stack."""

    __slots__ = ("_tracer", "_span", "_annotation")

    def __init__(self, tracer: "Tracer", sp: Span):
        self._tracer = tracer
        self._span = sp
        self._annotation = _annotation(sp.name, sp.args or {})

    def annotate(self, **kw):
        """Attach key/values to the live span (shows up in the Chrome
        trace ``args`` pane and in the profiler's event)."""
        if self._span.args is None:
            self._span.args = {}
        self._span.args.update(kw)
        self._annotation.set_metadata(**kw)
        return self

    @property
    def duration_s(self) -> float:
        """Elapsed seconds (final after ``__exit__``) — lets call-sites
        feed a histogram from the SAME clock reads the span made instead
        of timing the interval twice."""
        return self._span.duration_ns / 1e9

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.annotate(error=exc_type.__name__)
        self._annotation.__exit__(exc_type, exc, tb)
        self._tracer._finish(self._span)
        return False


class _ProfilerSpan(TraceAnnotation):
    """The handle of the disabled path: the profiler's annotation alone
    (its own no-op outside a session; it opens where it is built, as a
    ``Span`` does), with the handle's ``annotate`` and ``duration_s``."""

    duration_s = 0.0

    def annotate(self, **kw):
        self.set_metadata(**kw)
        return self


def _annotation(name: str, args: Dict) -> _ProfilerSpan:
    if "step_num" in args:
        # what jax.profiler.StepTraceAnnotation passes: the profiler's
        # step view takes its boundaries from these
        return _ProfilerSpan(name, _r=1, **args)
    return _ProfilerSpan(name, **args)


class Tracer:
    def __init__(self, max_events: int = 1_000_000):
        # max_events bounds memory on multi-hour runs: once full the
        # tracer drops new spans (and counts the drops) rather than OOM
        self.max_events = max_events
        self.dropped = 0
        self._events: List[Span] = []
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._epoch_ns = time.perf_counter_ns()

    # -- recording -------------------------------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, **args) -> _SpanHandle:
        st = self._stack()
        sp = Span(name, time.perf_counter_ns(), threading.get_ident(),
                  len(st), args or None)
        st.append(sp)
        return _SpanHandle(self, sp)

    def _append(self, sp: Span):
        # lock: reset() clears the list + re-stamps the epoch; an append
        # racing it would land a pre-epoch span (negative export ts).
        # The ONE capacity gate for every recording path (finish/
        # instant/complete) — the drop policy must not fork per path.
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(sp)

    def _finish(self, sp: Span):
        sp.end_ns = time.perf_counter_ns()
        st = self._stack()
        # exception-safe even if an inner handle leaked: pop through to
        # this span rather than corrupting the depth bookkeeping
        while st and st[-1] is not sp:
            st.pop()
        if st:
            st.pop()
        self._append(sp)

    def complete(self, name: str, start_ns: int, end_ns: int,
                 tid: Optional[int] = None, **args):
        """Record an already-finished span from caller-supplied
        ``perf_counter_ns`` stamps (depth 0) — for intervals whose
        start predates the recording call, e.g. a serving request's
        queue wait measured from its enqueue stamp when its batch is
        finally cut. Bypasses the nesting stack. ``tid`` defaults to
        the current thread; pass a synthetic (e.g. negative) id when
        several retro spans OVERLAP — complete events on one tid are
        nested-by-containment in the trace format and in
        ``tools/trace_report.py``, so overlapping siblings must each
        ride their own virtual lane to keep self-times honest."""
        sp = Span(name, int(start_ns),
                  threading.get_ident() if tid is None else tid, 0,
                  args or None)
        sp.end_ns = int(end_ns)
        self._append(sp)

    def instant(self, name: str, **args):
        """Zero-duration marker event (nan skips, trigger fires)."""
        sp = Span(name, time.perf_counter_ns(), threading.get_ident(),
                  len(self._stack()), args or None)
        sp.end_ns = sp.start_ns
        self._append(sp)

    # -- reading ---------------------------------------------------------
    def events(self) -> List[Span]:
        with self._lock:
            return list(self._events)

    def reset(self):
        with self._lock:
            self._events.clear()
            self.dropped = 0
            self._epoch_ns = time.perf_counter_ns()

    @property
    def epoch_ns(self) -> int:
        """perf_counter origin for relative timestamps in exports."""
        return self._epoch_ns


# -- process-global state ------------------------------------------------
_enabled = False
_tracer = Tracer()


def get_tracer() -> Tracer:
    return _tracer


def enable():
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def reset():
    """Clear collected spans (and the shared registry's owner does its
    own reset; this touches only the tracer)."""
    _tracer.reset()


def span(name: str, **args):
    """Module-level hot-path entry: always the profiler's annotation
    (nothing outside a profiler session), and a recorded span besides
    when enabled. ``step_num=`` marks a training step."""
    if not _enabled:
        return _annotation(name, args)
    return _tracer.span(name, **args)


#: the span of one collection of Python's cyclic garbage collector
GC_SPAN = "host/gc"

# -- the collector's pauses (``gc.callbacks``) ------------------------------
# A collection holds the GIL, so one on any thread stops the loop too.
# CPython runs one collection at a time process-wide, so one slot holds
# the collection in progress. The callback takes no lock (a collection can
# start inside any allocation, a lock's holder included) and allocates a
# fixed handful of objects a collection; the in-memory tracer does not
# see the span, only a profiler session does.
_gc_users = 0
_gc_users_lock = threading.Lock()
_gc_open = None               # (annotation, generation, start ns)
_gc_totals = (0, 0, 0)        # ns, collections, generation-2 collections


def _on_gc(phase, info):
    global _gc_open, _gc_totals
    if phase == "start":
        ann = _ProfilerSpan(GC_SPAN, generation=info["generation"])
        ann.__enter__()
        _gc_open = (ann, info["generation"], time.perf_counter_ns())
    elif _gc_open is not None:
        (ann, gen, t0), _gc_open = _gc_open, None
        ns, n, full = _gc_totals
        _gc_totals = (ns + time.perf_counter_ns() - t0, n + 1,
                      full + (gen == 2))
        ann.set_metadata(collected=info["collected"],
                         uncollectable=info["uncollectable"])
        ann.__exit__(None, None, None)


def gc_hook_install():
    """Open a ``host/gc`` span (``generation=``; ``collected=`` and
    ``uncollectable=`` at its end) around every collection on whatever
    thread runs it, and sum the pauses for :class:`GcPauses`. Reference
    counted: the hook is in ``gc.callbacks`` once, however many callers
    hold it; each :func:`gc_hook_remove` undoes one install."""
    global _gc_users
    with _gc_users_lock:
        _gc_users += 1
        if _gc_users == 1:
            gc.callbacks.append(_on_gc)


def gc_hook_remove():
    global _gc_users, _gc_open
    with _gc_users_lock:
        if _gc_users <= 0:
            return
        _gc_users -= 1
        if _gc_users == 0:
            gc.callbacks.remove(_on_gc)
            _gc_open = None


class GcPauses:
    """The collector's pauses since the previous :meth:`take` (or since
    this reader was made), summed over the process while the hook was
    installed: each reader keeps its own mark on the process-wide totals,
    so two loops never take each other's."""

    __slots__ = ("_mark",)

    def __init__(self):
        self._mark = _gc_totals

    def take(self):
        """(ms, collections, generation-2 collections) since the last
        call."""
        now, (ns, n, full) = _gc_totals, self._mark
        self._mark = now
        return (now[0] - ns) / 1e6, now[1] - n, now[2] - full


def instant(name: str, **args):
    if _enabled:
        _tracer.instant(name, **args)


def complete(name: str, start_ns: int, end_ns: int,
             tid: Optional[int] = None, **args):
    """Record a retrospective span from explicit ``perf_counter_ns``
    stamps (no-op when disabled). See :meth:`Tracer.complete` for the
    ``tid`` contract on overlapping spans."""
    if _enabled:
        _tracer.complete(name, start_ns, end_ns, tid=tid, **args)
