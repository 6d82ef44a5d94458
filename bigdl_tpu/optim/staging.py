"""Asynchronous batch staging: overlap host batch prep with device compute.

The serial loop pays ``produce batch -> device_put -> dispatch`` every
iteration, so the device idles while the host decodes/places the next
batch — the executor-side stall BigDL's Spark pipeline hid behind RDD
prefetch. ``BatchStager`` moves produce+place onto one bounded lookahead
thread: while step N runs on the device, the stager pulls batches
N+1..N+depth from the dataset iterator and stages them (sharded
``device_put`` via the caller's place function), so the hot loop's
``step/data_fetch`` collapses to a queue pop of an already-on-device
batch. The native ``bf16_nhwc`` prefetcher composes directly: its decode
workers emit accelerator-ready buffers and the stager's place call is a
cast-free, transpose-free ``device_put``.

Correctness invariants:

* **Order-preserving.** One worker thread, one FIFO queue — the consumer
  sees batches in exactly the serial order, so training trajectories are
  bitwise identical to the serial loop (tests/test_pipeline_loop.py).
* **Error-transparent.** An exception in the dataset iterator or the
  place function is re-raised in the consumer at the matching ``next()``.
* **No thread leaks.** ``close()`` (idempotent, also called on iterator
  exhaustion) unblocks and joins the worker; threads are named
  ``bigdl_tpu-stager`` so tests can assert none survive.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Optional

from .. import observability as obs
from ..observability import health as _health

THREAD_NAME = "bigdl_tpu-stager"

_SENTINEL = object()


class BatchStager:
    """Bounded lookahead stager: a daemon thread pulls items from
    ``source``, maps them through ``stage_fn`` (host decode + device
    placement) and parks up to ``depth`` staged results in a FIFO queue.

    Iterate it like the source iterable; call :meth:`close` (or use as a
    context manager) to shut the worker down early — e.g. when an end
    trigger fires mid-epoch.

    Stacking stage (superstep fusion): with ``group=K`` and a
    ``group_fn``, the worker collects up to K staged items and emits ONE
    ``group_fn([item, ...])`` result per group — the optimizer's group
    fn assembles the ``[K, batch, ...]`` stacked device arrays a
    superstep dispatch consumes, so the whole stack+place cost rides the
    stager thread and the hot loop still dequeues one element. The final
    group of an epoch may be smaller than K (epoch-end clamping)."""

    def __init__(self, source: Iterable, stage_fn: Callable, depth: int = 2,
                 name: str = "stager", group: int = 1,
                 group_fn: Optional[Callable] = None,
                 group_key: Optional[Callable] = None,
                 stall_deadline_s: Optional[float] = None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if group < 1:
            raise ValueError(f"group must be >= 1, got {group}")
        if group > 1 and group_fn is None:
            raise ValueError("group > 1 requires a group_fn")
        self._source = source
        self._stage_fn = stage_fn
        self._group = group
        self._group_fn = group_fn
        # items whose key differs cannot share a stack (a prefetcher's
        # ragged final batch must not np.stack against full ones): a key
        # change flushes the pending group and starts a new one
        self._group_key = group_key or (lambda item: None)
        self._name = name
        # per-instance metric names: a mid-training eval/predict stager
        # must not clobber the training stager's queue-depth signal
        self._depth_gauge = f"optim/{name}_queue_depth"
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._err = None
        self._done = False
        # stall watchdog: the worker pulses per source item AND while
        # healthily blocked on a full queue (the consumer owns that
        # wait) — silence therefore means the worker is wedged inside
        # next(source) or stage_fn (a hung decode or device_put), the
        # exact "training stopped, no error" case the watchdog pages on.
        # No-op beacon when observability is disabled.
        self._beacon = _health.beacon(f"stager/{name}",
                                      deadline_s=stall_deadline_s)
        self._thread = threading.Thread(
            target=self._run, name=THREAD_NAME, daemon=True)
        self._thread.start()

    # -- worker ----------------------------------------------------------
    def _run(self):
        it = iter(self._source)
        pending = []  # staged items awaiting a full group (group > 1)
        try:
            exhausted = False
            while not self._stop.is_set() and not exhausted:
                with obs.span(f"{self._name}/source_wait"):
                    t0 = time.perf_counter()
                    try:
                        item = next(it)
                    except StopIteration:
                        exhausted = True
                self._beacon.pulse()  # per source ITEM — a group-mode
                # iteration may `continue` below while still pending
                if obs.enabled():
                    # time the worker spent blocked on the upstream
                    # iterator (dataset produce): large values mean the
                    # stager itself is input-bound and a deeper queue
                    # won't help
                    obs.histogram(f"optim/{self._name}_source_wait_s",
                                  unit="s").observe(time.perf_counter() - t0)
                if exhausted:
                    emit = []
                    if pending:  # epoch tail: a smaller final group
                        emit, pending = [self._group_fn(pending)], []
                elif self._group > 1:
                    staged = self._stage_fn(item)
                    emit = []
                    if pending and self._group_key(staged) != \
                            self._group_key(pending[0]):
                        emit, pending = [self._group_fn(pending)], []
                    pending.append(staged)
                    if len(pending) == self._group:
                        emit.append(self._group_fn(pending))
                        pending = []
                    if not emit:
                        continue
                else:
                    emit = [self._stage_fn(item)]
                for staged in emit:
                    while not self._stop.is_set():
                        try:
                            self._q.put(staged, timeout=0.1)
                            break
                        except queue.Full:
                            # a full queue is the CONSUMER's wait, not a
                            # stager stall — keep the beacon fresh
                            self._beacon.pulse()
                            continue
                if obs.enabled():
                    obs.gauge(self._depth_gauge).set(self._q.qsize())
        except BaseException as e:  # noqa: BLE001 — re-raised in consumer
            self._err = e
        finally:
            self._beacon.close()
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass
            while not self._stop.is_set():
                try:
                    self._q.put(_SENTINEL, timeout=0.1)
                    break
                except queue.Full:
                    continue

    # -- consumer --------------------------------------------------------
    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        while True:
            try:
                item = self._q.get(timeout=0.5)
                break
            except queue.Empty:
                if not self._thread.is_alive():
                    # worker died between put attempts; whatever is
                    # queued was consumed already — surface its error
                    self._done = True
                    self._reraise()
                    raise StopIteration
        if item is _SENTINEL:
            self._done = True
            self._thread.join(timeout=30)
            self._reraise()
            raise StopIteration
        if obs.enabled():
            obs.gauge(self._depth_gauge).set(self._q.qsize())
        return item

    def _reraise(self):
        if self._err is not None:
            err, self._err = self._err, None
            raise err

    def close(self):
        """Stop the worker and join it (idempotent, never raises). Any
        staged-but-unconsumed batches are dropped."""
        self._stop.set()
        try:  # drain so a worker blocked on a full queue wakes promptly
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            # the worker is wedged inside stage_fn (e.g. a device_put to
            # a hung device) — surface the leak instead of pretending the
            # join succeeded
            import logging
            logging.getLogger(__name__).warning(
                "stager %r worker did not join within 30s (blocked in "
                "stage_fn?) — daemon thread leaked", self._name)
        # a wedged worker never reaches its own finally — the closed
        # run must not keep paging the watchdog
        self._beacon.close()
        self._done = True

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False


class _SerialStager:
    """Depth-0/1 fallback with the same iterator + ``close()`` surface:
    stages each item inline at ``next()`` — the serial loop, unchanged,
    so ``set_prefetch(0)`` is an exact A/B switch. ``group``/``group_fn``
    stack inline with the same semantics as the threaded stager."""

    def __init__(self, source: Iterable, stage_fn: Callable,
                 group: int = 1, group_fn: Optional[Callable] = None,
                 group_key: Optional[Callable] = None):
        if group > 1 and group_fn is None:
            raise ValueError("group > 1 requires a group_fn")
        self._it = iter(source)
        self._stage_fn = stage_fn
        self._group = group
        self._group_fn = group_fn
        self._group_key = group_key or (lambda item: None)
        self._carry = []  # lookahead item that broke the previous group

    def __iter__(self):
        return self

    def __next__(self):
        if self._group <= 1:
            return self._stage_fn(next(self._it))
        pending, self._carry = self._carry, []
        while len(pending) < self._group:
            try:
                staged = self._stage_fn(next(self._it))
            except StopIteration:
                if pending:
                    break  # epoch tail: a smaller final group
                raise
            if pending and self._group_key(staged) != \
                    self._group_key(pending[0]):
                self._carry = [staged]  # shape break: next group starts here
                break
            pending.append(staged)
        return self._group_fn(pending)

    def close(self):
        close = getattr(self._it, "close", None)
        if close is not None:
            try:
                close()
            except Exception:
                pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False


def staged(source: Iterable, stage_fn: Callable, depth: int = 2,
           name: str = "stager", group: int = 1,
           group_fn: Optional[Callable] = None,
           group_key: Optional[Callable] = None,
           stall_deadline_s: Optional[float] = None):
    """Pick the pipelined or serial staging wrapper by ``depth``
    (>= 2 spawns the lookahead thread; 0/1 stays inline). ``group``/
    ``group_fn``/``group_key`` enable the superstep stacking stage on
    either. ``stall_deadline_s`` arms the threaded stager's watchdog
    beacon (None = the ``BIGDL_TPU_STALL_S`` default); the serial
    stager runs inline under the caller's own beacon."""
    if depth >= 2:
        return BatchStager(source, stage_fn, depth=depth, name=name,
                           group=group, group_fn=group_fn,
                           group_key=group_key,
                           stall_deadline_s=stall_deadline_s)
    return _SerialStager(source, stage_fn, group=group, group_fn=group_fn,
                         group_key=group_key)


def stager_threads_alive() -> int:
    """Live stager worker threads (tests assert 0 after shutdown)."""
    return sum(1 for t in threading.enumerate()
               if t.name == THREAD_NAME and t.is_alive())


def place_host_value(x):
    """Table-aware host→device placement — the ONE spelling shared by the
    optimizer/evaluator/predictor stage functions, so a future placement
    change (pinned buffers, explicit shardings) lands everywhere at once."""
    import jax
    import jax.numpy as jnp
    from ..utils.table import Table
    if x is None:
        return None
    return (jax.tree_util.tree_map(jnp.asarray, x)
            if isinstance(x, Table) else jnp.asarray(x))
