"""Predictor (parity: reference ``optim/Predictor.scala`` /
``optim/LocalPredictor.scala`` / ``optim/PredictionService.scala``).

Also home of the ONE compiled inference forward per model
(:func:`shared_forward`) and the pad-to-bucket shape discipline both
``Predictor.predict()`` and the online serving engine
(``bigdl_tpu/serving/``) ride: every forward dispatch uses a shape from
a bounded bucket set, so the compiled-executable population stays small
and the persistent compile cache (``engine/compile_cache_hits|misses``)
stays hot across processes.
"""
from __future__ import annotations

import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as obs
from ..dataset.dataset import AbstractDataSet, ShardedDataSet, DataSet
from .staging import staged
from ..utils import engine
from ..utils.table import Table


# --------------------------------------------------------------------------
# shape buckets: the bounded set of compiled batch shapes
# --------------------------------------------------------------------------

def bucket_for(n: int, max_batch: int) -> int:
    """Smallest power-of-two >= ``n``, capped at ``max_batch`` — the
    padded batch size a ragged batch of ``n`` rows dispatches as. The
    reachable shape set is {1, 2, 4, ..., 2^k, max_batch}: bounded, so
    warmup can precompile it and a ragged epoch tail (or a serving
    micro-batch of any occupancy) never pays a fresh XLA compile beyond
    that set."""
    if n <= 0:
        raise ValueError(f"batch rows must be positive, got {n}")
    if n >= max_batch:
        return max_batch
    b = 1
    while b < n:
        b <<= 1
    return min(b, max_batch)


def shape_buckets(max_batch: int):
    """The full bucket set for ``max_batch``: ascending powers of two
    plus ``max_batch`` itself (deduplicated) — what serving warmup
    compiles at startup."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b <<= 1
    out.append(max_batch)
    return tuple(out)


def leading_dim(x) -> int:
    """Rows in a (possibly Table-structured) batch."""
    if isinstance(x, Table):
        leaves = jax.tree_util.tree_leaves(x)
        return int(leaves[0].shape[0]) if leaves else 0
    return int(np.shape(x)[0])


def pad_leading(x, bucket: int):
    """Zero-pad a batch (array or Table of arrays) along axis 0 up to
    ``bucket`` rows. Host-side (numpy) when given host values — do this
    BEFORE device placement so the transfer and the compiled shape are
    both the bucket shape. Rows past the true count are zeros; callers
    slice them away after the forward (padded rows are compute waste,
    never a correctness input)."""
    def _pad(a):
        n = a.shape[0]
        if n == bucket:
            return a
        if n > bucket:
            raise ValueError(f"batch of {n} rows exceeds bucket {bucket}")
        pad = [(0, bucket - n)] + [(0, 0)] * (a.ndim - 1)
        return (np.pad(a, pad) if isinstance(a, np.ndarray)
                else jnp.pad(a, pad))
    if isinstance(x, Table):
        return jax.tree_util.tree_map(_pad, x)
    return _pad(np.asarray(x) if not isinstance(x, jnp.ndarray) else x)


# --------------------------------------------------------------------------
# the shared compiled forward
# --------------------------------------------------------------------------

class CompiledForward:
    """ONE jit'd ``(params, state, x) -> output`` inference forward for a
    model instance. ``Predictor.predict()`` and the serving engine both
    call through here, so a bucket shape compiles ONCE per process no
    matter which consumer touches it first (and lands in the persistent
    compile cache for the next process). Taking ``params`` explicitly is
    what makes serving hot-swap free: a new model version is new params
    through the SAME compiled executable, zero recompiles."""

    def __init__(self, model):
        # weakly held: this object is the VALUE in a WeakKeyDictionary
        # keyed by the model — a strong ref here would keep the key (and
        # its executables) alive forever, defeating the weak keying
        self._model_ref = weakref.ref(model)
        self._jit = None
        self._lock = threading.Lock()

    @property
    def model(self):
        return self._model_ref()

    def fn(self):
        if self._jit is None:
            with self._lock:
                if self._jit is None:
                    model_ref = self._model_ref
                    engine.maybe_enable_compilation_cache()

                    def fwd(params, state, x):
                        # runs at TRACE time only (once per bucket shape);
                        # anyone compiling a new shape necessarily still
                        # holds params, but the model may be gone if only
                        # this wrapper was retained
                        model = model_ref()
                        if model is None:
                            raise RuntimeError(
                                "model was garbage-collected; cannot "
                                "trace a new input shape")
                        out, _ = model.apply(params, state, x,
                                             training=False)
                        return out
                    # every bucket shape (Predictor batches, serving
                    # warmup/live buckets) records its own
                    # CompiledArtifact — params/state are shape-stable,
                    # so the signature key is the input alone
                    model = model_ref()
                    name = f"predict/forward/{type(model).__name__}" \
                        if model is not None else "predict/forward"
                    self._jit = obs.perf.instrument_jit(
                        jax.jit(fwd), name=name, kind="forward",
                        key_argnums=(2,))
        return self._jit

    def __call__(self, params, state, x):
        return self.fn()(params, state, x)

    def compiled_shape_count(self) -> int:
        """Distinct input shapes compiled so far (tests assert the
        bucket discipline keeps this bounded). Counts both the
        instrumented AOT entries (observability on) and the inner jit
        cache (observability off)."""
        if self._jit is None:
            return 0
        return (self._jit.compiled_shape_count()
                + int(self._jit._jit._cache_size()))


_shared_forwards = weakref.WeakKeyDictionary()
_shared_lock = threading.Lock()


def shared_forward(model) -> CompiledForward:
    """The process-wide :class:`CompiledForward` for ``model`` (weakly
    keyed — dropping the model drops its executable cache)."""
    fwd = _shared_forwards.get(model)
    if fwd is None:
        with _shared_lock:
            fwd = _shared_forwards.get(model)
            if fwd is None:
                fwd = CompiledForward(model)
                _shared_forwards[model] = fwd
    return fwd


class Predictor:
    def __init__(self, model, batch_per_partition: int = 4,
                 prefetch_depth: int = 2):
        """``batch_per_partition`` (reference parity: Predictor.scala's
        batchPerPartition) sets the default per-device batch —
        ``predict(ds)`` without an explicit ``batch_size`` runs
        ``batch_per_partition * device_count`` samples per forward, the
        XLA analog of the reference's per-Spark-partition batching."""
        self.model = model
        self.batch_per_partition = batch_per_partition
        self.prefetch_depth = prefetch_depth
        self._superstep = 1
        self._scan_jit = None

    def set_superstep(self, k: int):
        """Fuse K prediction batches into ONE compiled ``lax.scan``
        dispatch (the Evaluator's superstep, for the output path): the
        stager stacks K same-shape staged batches to [K, B, ...], one
        program runs all K forwards, and the per-batch outputs come back
        as device-resident slices of the [K, B, ...] stack — the lagged
        readback window in :meth:`predict` is unchanged.
        ``predict/dispatches`` counts compiled calls (K-fold drop
        asserted in tests/test_superstep.py)."""
        if k < 1:
            raise ValueError(f"superstep must be >= 1, got {k}")
        self._superstep = int(k)
        return self

    def _default_batch(self):
        return self.batch_per_partition * max(1, len(jax.devices()))

    def _forward_fn(self):
        return shared_forward(self.model)

    def _scan_forward_fn(self):
        if self._scan_jit is None:
            model = self.model
            engine.maybe_enable_compilation_cache()

            def fwd_scan(params, state, xs):
                def body(_, x):
                    out, _s = model.apply(params, state, x, training=False)
                    return None, out
                return jax.lax.scan(body, None, xs)[1]
            self._scan_jit = obs.perf.instrument_jit(
                jax.jit(fwd_scan),
                name=f"predict/forward_scan/{type(model).__name__}",
                kind="forward", key_argnums=(2,))
        return self._scan_jit

    def _iter_outputs(self, dataset, batch_size):
        """Yields DEVICE-resident per-batch ``(output, rows)`` pairs: the
        dispatch loop never blocks on a device→host copy, so batch N+1's
        forward (and the stager's transfers) overlap batch N's compute.
        A ragged final batch is zero-padded on the HOST to its power-of-
        two bucket (``bucket_for``), so every dispatch reuses a compiled
        shape from the bounded bucket set; ``rows`` is the true count the
        consumer slices back to. Consumers that want host arrays fetch at
        the end (``predict`` does ONE ``device_get`` over the whole run)
        or per batch themselves."""
        if isinstance(dataset, np.ndarray):
            dataset = DataSet.from_arrays(dataset)
        self.model.ensure_initialized()
        fwd = self._forward_fn()
        max_batch = batch_size

        def _stage(mb):
            from .staging import place_host_value
            x = mb.get_input()
            n = leading_dim(x)
            if 0 < n < max_batch:
                x = pad_leading(x, bucket_for(n, max_batch))
            return place_host_value(x), n

        k = self._superstep

        def _group(items):
            # [(x, n), ...] -> ([K, B, ...] device stack, (n, ...)) on
            # the stager thread (equal padded shapes via the group key)
            from .evaluator import _stack_tree
            return (_stack_tree([x for x, _ in items]),
                    tuple(n for _, n in items))

        def _gkey(item):
            from .evaluator import _tree_shape_key
            return _tree_shape_key(item[0])

        batched = ShardedDataSet(dataset, batch_size, drop_last=False)
        batches = staged(batched.data(train=False), _stage,
                         depth=self.prefetch_depth, name="predict_stager",
                         group=k, group_fn=_group if k > 1 else None,
                         group_key=_gkey if k > 1 else None)
        scan_fwd = self._scan_forward_fn() if k > 1 else None
        try:
            for item in batches:
                sp = obs.span("predict/batch")
                if k > 1:
                    xs, ns = item
                    with sp:
                        outs = scan_fwd(self.model.params,
                                        self.model.state, xs)
                    if obs.enabled():
                        obs.counter("predict/dispatches").inc()
                        obs.histogram("predict/batch_s", unit="s").observe(
                            sp.duration_s)
                    # device-resident slices of the [K, B, ...] stack —
                    # the consumer's lagged-fetch window is unchanged
                    for i, n in enumerate(ns):
                        yield jax.tree_util.tree_map(lambda o, i=i: o[i],
                                                     outs), n
                    continue
                x, n = item
                with sp:
                    out = fwd(self.model.params, self.model.state, x)
                if obs.enabled():
                    obs.counter("predict/dispatches").inc()
                    obs.histogram("predict/batch_s", unit="s").observe(
                        sp.duration_s)
                yield out, n
        finally:
            # an abandoned generator (predict_class slicing, early break)
            # must still join the stager thread
            batches.close()

    def predict(self, dataset, batch_size=None):
        from collections import deque
        depth = max(1, self.prefetch_depth)
        outs = []
        window = deque()  # device outputs in flight (bounds HBM residency)
        for out, n in self._iter_outputs(dataset,
                                         batch_size or self._default_batch()):
            window.append((out, n))
            if len(window) > depth:
                # sync-ok: LAGGED fetch — this output is `depth` batches
                # old, so the device pipeline never drains (the old code
                # blocked on the CURRENT batch every iteration), while
                # only depth+1 outputs ever live in device memory
                o, k = window.popleft()
                outs.append(np.asarray(o)[:k])
                if obs.enabled():
                    obs.counter("predict/readbacks").inc()
        if window:
            # sync-ok: end-of-run drain of the in-flight window
            fetched = jax.device_get([o for o, _ in window])
            outs.extend(np.asarray(o)[:k]
                        for o, (_, k) in zip(fetched, window))
            if obs.enabled():
                obs.counter("predict/readbacks").inc()
        if not outs:
            return np.empty((0,), np.float32)
        return np.concatenate(outs, axis=0)

    def predict_class(self, dataset, batch_size=None):
        """1-based argmax class, parity with predictClass."""
        return np.argmax(self.predict(dataset, batch_size), axis=-1) + 1


class PredictionService(Predictor):
    """Thread-safe serving facade (parity: optim/PredictionService.scala).
    XLA compiled functions are thread-safe; this is a thin alias — the
    full online engine (micro-batching, buckets, backpressure, hot swap)
    lives in ``bigdl_tpu/serving/``."""
