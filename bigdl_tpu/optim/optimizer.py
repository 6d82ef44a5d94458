"""Training drivers.

Parity: reference ``optim/Optimizer.scala``, ``optim/LocalOptimizer.scala``,
``optim/DistriOptimizer.scala``, ``optim/AbstractOptimizer.scala``,
``optim/Metrics.scala``, plus DistriOptimizer's checkpoint/summary/validation
plumbing (DistriOptimizer.scala:90-640).

Execution model (TPU-first):

* The whole training step — forward, loss (+ per-layer regularizers),
  backward, gradient clipping, optimizer update — is ONE jitted function.
  The reference re-enters the JVM interpreter per layer per step; here XLA
  compiles the step once and fuses across layer boundaries.
* ``LocalOptimizer``: single device.
* ``DistriOptimizer``: the global batch is laid out over the mesh ``data``
  axis. Two parameter modes:
  - ``replicated`` (default): params replicated, XLA GSPMD inserts the
    gradient all-reduce over ICI automatically — the hardware analog of the
    reference's block-manager all-reduce;
  - ``zero1``: params flattened to one contiguous vector and updated
    slice-per-device via psum_scatter/all_gather (see
    ``parallel/allreduce.py``) — the literal TPU translation of
    AllReduceParameter's owner-slice design, with sharded optimizer state.
* LR schedules, triggers, checkpointing, validation, summaries run host-side
  between steps (control, not compute).
"""
from __future__ import annotations

import contextlib
import logging
import os
import pickle
import queue
import threading
import time
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import observability as obs
from ..observability import cluster as _cluster
from ..observability import flight as _flight
from ..observability import health as _health
from ..parallel import chaos as _chaos
from ..parallel.failure import (FaultPolicy, HeartbeatLost, TrainingHalted,
                                PERMANENT, TRANSIENT, classify_failure,
                                probe_mesh, _run_with_timeout)
from .optim_method import OptimMethod, Plateau, SGD
from .regularizer import regularizer_tree, regularization_loss
from .trigger import Trigger, max_epoch as _max_epoch
from ..dataset.dataset import AbstractDataSet, ShardedDataSet, DataSet
from ..dataset.minibatch import MiniBatch
from ..nn.module import Module, Criterion
from .staging import staged
from ..utils import engine
from ..utils.table import Table

_tmap = jax.tree_util.tree_map
_LOG = logging.getLogger(__name__)

def _read_umask():
    """The process umask, read WITHOUT the os.umask(0)/restore dance
    when possible — that flip is process-wide, and another thread
    creating a file inside the window (serving batcher, a lazy import
    off a worker thread) would get world-writable modes. Linux exposes
    it race-free in /proc; elsewhere fall back to the racy read once
    here at import."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("Umask:"):
                    return int(line.split()[1], 8)
    except (OSError, ValueError, IndexError):
        pass
    um = os.umask(0)
    os.umask(um)
    return um


# _atomic_pickle restores umask-based modes on its mkstemp tmps, which
# are born 0600
_UMASK = _read_umask()


def _atomic_pickle(path, payload):
    """Crash-consistent write: unique tmp + fsync + atomic rename +
    directory fsync. A kill at ANY point — mid-dump, post-dump
    pre-rename, post-rename pre-dir-sync under power loss — leaves
    either the previous intact file or the complete new one, never a
    truncated 'latest' (the file every recovery path — nan resume,
    remediation halt, elastic restart — trusts blindly). The tmp name
    is unique per write (mkstemp), so a writer killed mid-dump can
    never have its half-written tmp renamed over the target by a later
    writer reusing the same tmp path, and concurrent writers (two
    optimizers sharing a checkpoint dir) never interleave into one
    file. Failed writes remove their tmp — no litter accumulates."""
    _chaos.maybe_fire("checkpoint/write")
    import tempfile
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(
        prefix=os.path.basename(path) + ".", suffix=".tmp", dir=d)
    try:
        with os.fdopen(fd, "wb") as f:
            # mkstemp creates 0600 and os.replace keeps the tmp's mode;
            # a checkpoint must stay as readable as a plain open() would
            # have made it (eval jobs / backup agents under another uid)
            os.fchmod(f.fileno(), 0o666 & ~_UMASK)
            pickle.dump(payload, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    # fsync the DIRECTORY: the rename itself must survive power loss,
    # or recovery could see the pre-checkpoint directory state
    try:
        dfd = os.open(d, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass  # platforms without dir fsync keep file-level durability


class _AsyncCheckpointWriter:
    """One daemon writer thread; submissions are written IN ORDER (so the
    'latest checkpoint' on disk is always the latest submitted), each via
    the atomic tmp+rename. ``flush`` drains the queue and re-raises the
    first writer error (a silently failing checkpointer is worse than a
    crashed one). The reference writes checkpoints synchronously on the
    Spark driver (Optimizer.setCheckpoint → File.save); on TPU the step
    loop should not stall on host file IO."""

    def __init__(self, max_pending: int = 2):
        # bounded: a slow disk backpressures the training loop instead of
        # accumulating one full host model copy per checkpoint interval
        self._q: queue.Queue = queue.Queue(maxsize=max_pending)
        self._err = None
        self._thread = None

    def _run(self):
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                path, payload = item
                try:
                    _atomic_pickle(path, payload)
                except Exception as e:  # noqa: BLE001 — surfaced in flush
                    if self._err is None:
                        self._err = e
            finally:
                self._q.task_done()

    def submit(self, path, payload):
        if self._thread is None:
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
        self._q.put((path, payload))
        if obs.enabled():
            obs.gauge("checkpoint/queue_depth").set(self._q.qsize())

    def flush(self, timeout=None):
        if self._thread is not None:
            if timeout is None:
                self._q.join()
            else:
                deadline = time.monotonic() + timeout
                while self._q.unfinished_tasks and \
                        time.monotonic() < deadline:
                    time.sleep(0.05)
                if self._q.unfinished_tasks:
                    raise TimeoutError(
                        f"{self._q.unfinished_tasks} async checkpoint "
                        f"write(s) still pending after {timeout}s")
        if self._err is not None:
            err, self._err = self._err, None
            raise RuntimeError(
                f"async checkpoint write failed: {err}") from err

    def close(self, timeout=None):
        """Flush, then stop the writer thread (optimize() calls this so
        no daemon thread outlives the run). ``timeout`` bounds the whole
        attempt for halt paths: a writer wedged on hung storage (dead
        NFS mid-remediation) is ABANDONED to its daemon fate instead of
        wedging the exit — the remediation checkpoint already landed
        synchronously, and an elastic resume prefers the halt's own
        checkpoint path over mtime, so a late-landing stale write
        cannot be silently resumed."""
        try:
            self.flush(timeout)
        finally:
            if self._thread is not None:
                try:
                    self._q.put_nowait(None)
                except queue.Full:
                    pass  # wedged writer never drains: abandon it
                self._thread.join(timeout=30 if timeout is None
                                  else timeout)
                self._thread = None


class Metrics:
    """Per-phase timing metrics (parity: optim/Metrics.scala).

    Retained as the optimizer-local view (``.values`` is part of the
    public surface); when observability is enabled every ``add`` also
    mirrors into the process-global registry as an
    ``optim/<name>`` histogram, so the Prometheus/Chrome exporters and
    the TensorBoard bridge see the same numbers without a second
    collection path."""

    def __init__(self, namespace: str = "optim"):
        self.values = {}
        self._namespace = namespace

    def add(self, name, value):
        self.values.setdefault(name, []).append(value)
        if obs.enabled():
            obs.histogram(f"{self._namespace}/{name}").observe(value)

    def mean(self, name):
        if name not in self.values:
            raise KeyError(
                f"no metric named {name!r} has been recorded "
                f"(seen: {sorted(self.values)})")
        v = self.values[name]
        return sum(v) / len(v)

    def summary(self):
        return {k: self.mean(k) for k in self.values}


def _frozen_mask(model):
    """Mask pytree matching ``model.params``: 0.0 under frozen modules
    (Module.freeze), 1.0 elsewhere; None when nothing is frozen.

    Per-module flags, no ancestor propagation: ``freeze()`` marks whole
    subtrees, so ``unfreeze("head")`` under a frozen root works."""
    from ..nn.module import Container
    from ..nn.recurrent import Recurrent
    model.ensure_initialized()
    if not any(getattr(m, "_frozen", False) for m in model.modules_iter()):
        return None

    def rec(m, p):
        if isinstance(m, Recurrent) and isinstance(p, dict) and "cell" in p:
            return {"cell": rec(m.cell, p["cell"])}
        if isinstance(m, Container) and isinstance(p, dict):
            out = {}
            for k, v in p.items():
                if k.isdigit() and int(k) < len(m.modules):
                    out[k] = rec(m.modules[int(k)], v)
                else:
                    out[k] = _leaf_mask(m, v)
            return out
        return _leaf_mask(m, p)

    def _leaf_mask(m, p):
        val = 0.0 if getattr(m, "_frozen", False) else 1.0
        return _tmap(lambda a: val, p)

    return rec(model, model.params)


def _scan_superstep(step):
    """Lift a single-step function ``step(params, opt_state, mstate, x, y,
    lr, rng) -> (loss, params', opt_state', mstate')`` into a superstep:
    ``lax.scan`` over K stacked microbatches threading the training state
    through K updates inside ONE XLA program. Losses come back as a
    single ``[K]`` device array — one dispatch and one batched readback
    amortize the per-step host costs K-fold. The per-microstep math (incl.
    the in-step NaN guard: a non-finite microstep keeps the previous
    state, later microsteps proceed from it — exactly the K=1 'skip'
    dataflow) is the same program the per-step loop compiles; trajectories
    match K=1 bitwise for fusion-insensitive bodies (elementwise/matmul
    MLPs — asserted in tests/test_superstep.py). XLA may re-fuse across
    microstep boundaries, which can reorder a handful of GEMM/conv
    accumulations — measured <= 4e-9 absolute drift on LeNet/CPU over 8
    steps, i.e. last-mantissa-bit float noise, never a semantic change."""

    def superstep(params, opt_state, mstate, xs, ys, lrs, rngs):
        def body(carry, inp):
            p, o, m = carry
            x, y, lr, rng = inp
            loss, p, o, m = step(p, o, m, x, y, lr, rng)
            return (p, o, m), loss

        (params, opt_state, mstate), losses = jax.lax.scan(
            body, (params, opt_state, mstate), (xs, ys, lrs, rngs))
        return losses, params, opt_state, mstate

    return superstep


def _steps_in_stack(args):
    """The step count of ONE superstep program, read off the
    ``[k, batch, ...]`` stack's leading dim at compile time — a clamped
    j<K group compiles its OWN program and its artifact must say j, not
    the configured K."""
    leaves = jax.tree_util.tree_leaves(args[3])
    return leaves[0].shape[0] if leaves else 1


@jax.named_scope("grad_clip")
def _clip_grads(grads, clip_const=None, clip_norm=None):
    if clip_const is not None:
        lo, hi = clip_const
        grads = _tmap(lambda g: jnp.clip(g, lo, hi), grads)
    if clip_norm is not None:
        leaves = jax.tree_util.tree_leaves(grads)
        total = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
        scale = jnp.minimum(1.0, clip_norm / (total + 1e-12))
        grads = _tmap(lambda g: g * scale, grads)
    return grads


def _loss_fn(model, criterion):
    """``(params, mstate, x, y, rng) -> (loss, new model state)``, the
    function every step builder differentiates. The model's own scopes
    name the forward; criterion and regulariser go under ``loss``. Losses
    the model returns in its state (``new_state["losses"]``: scalars by
    name, e.g. a sparse attention's indexer objective or an expert layer's
    balance term) are added to the criterion's; a model that returns none
    gives the same program as before there were any."""
    reg_tree = regularizer_tree(model)

    def loss_fn(params, mstate, x, y, rng):
        out, new_state = model.apply(params, mstate, x, training=True,
                                     rng=rng)
        with jax.named_scope("loss"):
            loss = criterion._forward(out, y)
            if reg_tree:
                loss = loss + regularization_loss(reg_tree, params)
            if isinstance(new_state, dict) and new_state.get("losses"):
                loss = loss + sum(new_state["losses"].values())
        return loss, new_state

    return loss_fn


# what :meth:`BaseOptimizer._account_loss` says became of a step
_COUNTED, _SKIPPED, _RESTORED = "counted", "skipped", "restored"


def _host_xy(mb):
    """A MiniBatch's host ``(x, y)``: what the stager extracts per
    microbatch when ``_stage_group`` places the stack."""
    return mb.get_input(), mb.get_target()


def _guarded(loss, new, old):
    """NaN/Inf guard inside the compiled step (buffers are donated, so
    the host can't roll back): a non-finite loss keeps the previous
    state and only the loss reports the failure."""
    ok = jnp.isfinite(loss)
    return _tmap(lambda a, b: jnp.where(ok, a, b), new, old)


class RemediationPolicy:
    """Tier-1 observe→act configuration: what the optimizer DOES when
    the health layer (PR 5) sees trouble, instead of only recording it.

    * **Stall remediation** — when the step loop's watchdog beacon
      stalls, the policy probes the mesh (``probe_mesh``, bounded by
      ``probe_timeout_s``) to classify transient vs. dead. A dead mesh
      — or any stall when ``halt_on_stall`` is set — checkpoints the
      last resolved training state from the watchdog thread (the loop
      itself is wedged), dumps a flight bundle, and requests a
      :class:`~bigdl_tpu.parallel.failure.TrainingHalted` exit: the run
      leaves artifacts instead of hanging forever. ``exit_process``
      additionally ``os._exit(86)`` s after the artifacts land, for
      loops wedged beyond rescue in a dead collective. The checkpoint's
      device→host fetch is itself bounded by
      ``halt_artifact_timeout_s`` (it has no deadline of its own, and a
      dead mesh would otherwise wedge the watchdog thread doing the
      remediating); on expiry the halt lands bundle-only.
    * **Heartbeat membership** — with a ``heartbeat``
      (:class:`~bigdl_tpu.parallel.failure.Heartbeat`), the loop beats
      every ``heartbeat_every`` steps with ``heartbeat_timeout_s``; a
      lost or stale exchange checkpoints-and-halts with the stale peer
      ids recorded as ``lost_processes`` — the membership signal the
      elastic restarter reshapes the mesh from.
    * **Anomaly-driven control** — ``health/plateau`` events (from the
      losses the sync policy already resolves — zero new readbacks)
      optionally drive the LR: a :class:`Plateau` schedule gets
      :meth:`~Plateau.force_reduction`, any other schedule a
      ``plateau_factor`` multiplier (``health/lr_reduced`` event);
      ``early_stop_plateaus`` ends the run cleanly after N plateaus,
      and ``max_spikes`` checkpoint-and-halts a diverging run after N
      ``health/loss_spike`` events.
    * **Stragglers** — with a ``straggler_monitor``, per-step times are
      recorded and a report runs every ``straggler_every`` steps;
      persistent stragglers fire ``health/straggler`` (see
      :class:`~bigdl_tpu.parallel.failure.StragglerMonitor`).

    Stall/probe remediation needs observability enabled (the watchdog
    is the trigger); heartbeat, anomaly control and stragglers work
    either way.
    """

    def __init__(self, halt_on_stall: bool = False,
                 probe_timeout_s: float = 30.0,
                 exit_process: bool = False,
                 halt_artifact_timeout_s: float = 120.0,
                 heartbeat=None, heartbeat_every: int = 0,
                 heartbeat_timeout_s: float = 60.0,
                 plateau_lr: bool = False, plateau_factor: float = 0.1,
                 min_lr_scale: float = 1e-4,
                 early_stop_plateaus: Optional[int] = None,
                 max_spikes: Optional[int] = None,
                 straggler_monitor=None, straggler_every: int = 0):
        if heartbeat is not None and heartbeat_every < 1:
            raise ValueError("heartbeat needs heartbeat_every >= 1 "
                             f"(got {heartbeat_every})")
        if straggler_monitor is not None and straggler_every < 1:
            raise ValueError("straggler_monitor needs straggler_every >= 1 "
                             f"(got {straggler_every})")
        self.halt_on_stall = halt_on_stall
        self.probe_timeout_s = float(probe_timeout_s)
        self.exit_process = exit_process
        self.halt_artifact_timeout_s = float(halt_artifact_timeout_s)
        self.heartbeat = heartbeat
        self.heartbeat_every = int(heartbeat_every)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.plateau_lr = plateau_lr
        self.plateau_factor = float(plateau_factor)
        self.min_lr_scale = float(min_lr_scale)
        self.early_stop_plateaus = early_stop_plateaus
        self.max_spikes = max_spikes
        self.straggler_monitor = straggler_monitor
        self.straggler_every = int(straggler_every)
        # per-run bookkeeping (reset by Optimizer.optimize())
        self.plateaus = 0
        self.spikes = 0
        self._last_beat_neval = 0
        self._last_straggler_neval = 0

    def reset_run_state(self):
        self.plateaus = 0
        self.spikes = 0
        self._last_beat_neval = 0
        self._last_straggler_neval = 0


class BaseOptimizer:
    def __init__(self, model: Module, training_set, criterion: Criterion,
                 optim_method: Optional[OptimMethod] = None,
                 end_trigger: Optional[Trigger] = None, batch_size: int = 32):
        self.model = model
        self.criterion = criterion
        self.optim_method = optim_method or SGD(learningrate=0.01)
        self.end_trigger = end_trigger or _max_epoch(1)
        self.batch_size = batch_size
        self.training_set = self._as_dataset(training_set)

        self.validation_trigger = None
        self.validation_set = None
        self.validation_methods = None
        self.checkpoint_trigger = None
        self.checkpoint_path = None
        self.checkpoint_overwrite = True
        self.checkpoint_async = False
        self._ckpt_writer = _AsyncCheckpointWriter()
        self.train_summary = None
        self.val_summary = None
        self.clip_const = None
        self.clip_norm = None
        self.nan_policy = "error"  # or "skip" / "resume"
        self.max_nan_retries = 10  # consecutive non-finite steps before abort
        self.sync_policy = "sync"  # or "async" / "window:K"
        self.prefetch_depth = 2    # >= 2 enables the lookahead stager
        self.superstep = 1         # K fused steps per dispatch (lax.scan)
        self._pending_loss = None
        self._loss_window = deque()
        self._resolved_step = None  # provenance of the last resolved loss
        self.metrics = Metrics()
        self._step_fn = None
        # health layer (active only while observability is enabled):
        # stall watchdog deadline/callback, anomaly-detector config
        # (None disables; a dict overrides SeriesMonitor defaults)
        self.stall_deadline_s = None   # None -> BIGDL_TPU_STALL_S default
        self.stall_startup_grace_s = None  # None -> max(deadline, default)
        self._stall_grace_pending = False
        self.on_stall = None
        self.anomaly_config: Optional[dict] = {}
        self._step_beacon = _health.NULL_BEACON
        self._loss_monitor = None
        self._profiler = None
        # cluster metric snapshots (BIGDL_TPU_METRIC_SNAP_S cadence;
        # a zero interval makes every maybe_write a single comparison)
        self._snap_writer = _cluster.MetricSnapshotWriter(every_s=0)
        # self-healing (PR 6): Tier-1 observe→act policy, Tier-2
        # dispatch retry budget, and the cross-thread halt/live-state
        # channel the watchdog-thread remediation writes into
        self.remediation: Optional[RemediationPolicy] = None
        self.fault_policy: Optional[FaultPolicy] = None
        self._halt_requested: Optional[TrainingHalted] = None
        self._live_state = None        # (params, opt_state, mstate)
        self._remediation_lr_scale = 1.0
        self._remediating = False      # one stall remediation in flight

    # -- reference API surface ------------------------------------------
    def set_model(self, model):
        """Swap the model for optimizer reuse (pyspark Optimizer.set_model).
        Training PROGRESS resets with it: the epoch/iteration counters and
        any checkpoint-resume optimizer state belong to the old model —
        without the reset a second ``optimize()`` would stop at the old
        end-trigger after one step (or feed the old model's opt-state tree
        into the new step)."""
        self.model = model
        self.optim_method.state = {"neval": 0, "epoch": 1}
        self._resume_opt_state = None
        return self

    def set_criterion(self, criterion):
        """Swap the criterion for optimizer reuse (pyspark
        Optimizer.set_criterion). The step is rebuilt on the next
        ``optimize()``."""
        self.criterion = criterion
        return self

    def set_traindata(self, training_set, batch_size=None):
        """Swap the training data for optimizer reuse (pyspark
        Optimizer.set_traindata)."""
        self.training_set = self._as_dataset(training_set)
        if batch_size:
            self.batch_size = batch_size
        return self

    def set_summary_trigger(self, name, trigger):
        """Modify when a summary named tag is recorded (pyspark
        Optimizer.set_summary_trigger). Train tags: "Loss",
        "LearningRate", "Throughput". Validation: "Validation" gates all
        validation scalars; a per-method tag (its repr) gates one."""
        val_tags = {repr(m) for m in (self.validation_methods or ())}
        is_val_tag = name.startswith("Validation") or name in val_tags
        if is_val_tag:
            if self.val_summary is None:
                raise ValueError(
                    "set_summary_trigger(%r): validation tag but no "
                    "validation summary is set — call set_val_summary "
                    "first (the train loop only consults Loss/"
                    "LearningRate/Throughput)" % (name,))
            target = self.val_summary
        elif self.train_summary is not None:
            target = self.train_summary
        else:
            raise ValueError("set a train/val summary before "
                             "set_summary_trigger")
        target.set_summary_trigger(name, trigger)
        return self

    def prepare_input(self):
        """Materialise the dataset ahead of ``optimize`` (pyspark
        Optimizer.prepare_input — there, forces the cached RDD; here the
        dataset protocol is already local, so this just touches one
        batch to surface IO errors early). Open-epoch datasets (the
        native prefetchers spawn decode workers per data() call) are
        skipped — pulling one batch would leave a whole epoch's worker
        run open."""
        if getattr(self.training_set, "_epoch_open", None) is not None:
            return self
        it = iter(self.training_set.data(train=False))
        try:
            next(it, None)
        finally:
            # generator-backed datasets may hold resources (open files,
            # worker pools) in the abandoned iterator — release eagerly
            close = getattr(it, "close", None)
            if close is not None:
                close()
        return self

    def set_validation(self, trigger, dataset, methods, batch_size=None):
        self.validation_trigger = trigger
        self.validation_set = self._as_dataset(dataset)
        self.validation_methods = list(methods)
        self.validation_batch = batch_size or self.batch_size
        return self

    def set_checkpoint(self, trigger, path, overwrite=True,
                       async_write=False):
        """``async_write=True`` moves serialization + file IO onto a
        background writer thread (ordered, atomic) so the training loop
        only pays the device→host fetch; ``wait_for_checkpoints()`` (also
        called at the end of ``optimize``) flushes and surfaces errors."""
        self.checkpoint_trigger = trigger
        self.checkpoint_path = path
        self.checkpoint_overwrite = overwrite
        self.checkpoint_async = async_write
        os.makedirs(path, exist_ok=True)
        return self

    def set_train_summary(self, summary):
        self.train_summary = summary
        return self

    def set_val_summary(self, summary):
        self.val_summary = summary
        return self

    def set_end_when(self, trigger):
        self.end_trigger = trigger
        return self

    def set_gradclip_const(self, clip_min: float, clip_max: float):
        self.clip_const = (clip_min, clip_max)
        return self

    def set_gradclip_l2norm(self, clip_norm: float):
        self.clip_norm = clip_norm
        return self

    def disable_gradclip(self):
        self.clip_const = self.clip_norm = None
        return self

    def set_sync_policy(self, policy: str):
        """'sync' (default) reads each step's loss immediately — the host
        blocks on the device every iteration. 'async' reads the PREVIOUS
        step's loss instead, so the next batch is prepared and enqueued
        while the device still computes (loss logging, NaN detection and
        min-loss triggers lag one step; the in-step NaN guard keeps params
        safe on-device either way). Use 'async' for device-bound training.

        'window:K' generalizes async: up to K losses stay in flight as
        device arrays and the host resolves the OLDEST only once the
        window is full, so loss observation (logging, NaN detection,
        min-loss triggers) lags K-1 steps and the device pipeline is
        never drained by a blocking read. 'window:1' == 'sync'. The NaN
        policy semantics are preserved — a non-finite resolved loss
        raises/skips/replays-from-checkpoint exactly like sync, just K-1
        steps later (params stay safe meanwhile via the in-step guard).
        """
        if isinstance(policy, str) and policy.startswith("window:"):
            k = int(policy.split(":", 1)[1])
            if k < 1:
                raise ValueError(f"window size must be >= 1, got {k}")
        else:
            assert policy in ("sync", "async")
        self.sync_policy = policy
        return self

    def set_prefetch(self, depth: int):
        """Lookahead depth of the batch stager: with ``depth >= 2`` a
        host thread produces and device_puts batches N+1..N+depth while
        step N runs, collapsing ``step/data_fetch`` to a queue pop.
        ``0``/``1`` keep the serial fetch (exact A/B switch — the staged
        loop is order-preserving, so trajectories are identical)."""
        depth = int(depth)
        if depth < 0:
            raise ValueError(f"prefetch depth must be >= 0, got {depth}")
        self.prefetch_depth = depth
        return self

    def set_superstep(self, k: int):
        """Fuse K training steps into ONE compiled XLA program: the step
        becomes a ``lax.scan`` over K stacked microbatches that threads
        (params, opt_state, model state) through K updates on device, so
        the host pays one dispatch, one batched ``[K]`` loss readback and
        one round of bookkeeping per K steps instead of per step — the
        win when host dispatch dominates (small/medium models).
        Semantics stay identical to K=1: LR schedules
        are precomputed as a ``[K]`` vector, the per-step RNG stream is
        unchanged, and dispatches auto-clamp so a superstep never
        straddles an epoch end or a checkpoint/validation/end-trigger
        boundary. When K > 1 the batched readback REPLACES the per-loss
        resolution of ``sync``/``async``/``window:K`` (loss observation,
        NaN detection and loss-driven triggers resolve once per
        superstep — the same K-step observation lag ``window:K`` has).
        ``1`` restores the per-step loop exactly.

        Equivalence: the scan body IS the per-step program, so the
        trajectory matches K=1 bitwise for fusion-insensitive models
        (MLPs); where XLA re-fuses across microstep boundaries (conv/
        GEMM epilogues) a handful of accumulations reorder — measured
        <= 4e-9 absolute drift on LeNet/CPU, float ulp noise."""
        k = int(k)
        if k < 1:
            raise ValueError(f"superstep must be >= 1, got {k}")
        self.superstep = k
        return self

    def _window_k(self) -> Optional[int]:
        if isinstance(self.sync_policy, str) and \
                self.sync_policy.startswith("window:"):
            return int(self.sync_policy.split(":", 1)[1])
        return None

    def set_stall_deadline(self, seconds: float, on_stall=None,
                           startup_grace_s=None):
        """Arm the stall watchdog for this optimizer's loops: the step
        loop and its batch stager pulse progress beacons, and a beacon
        quiet for ``seconds`` fires a structured ``health/stall`` event
        (plus ``on_stall(beacon, age_s)`` when given) instead of the run
        silently hanging — the remote-TPU 'no output' failure mode.
        Active only while observability is enabled; the default deadline
        without this call is ``BIGDL_TPU_STALL_S`` (600s).

        ``startup_grace_s``: the deadline in force until the FIRST
        dispatch completes. The first step blocks for the whole XLA
        compile — minutes on a real pod — which is silence a
        steady-state deadline would misread as a stall (and, with
        ``RemediationPolicy(halt_on_stall=True)``, kill a healthy run
        before it trained a step). Defaults to
        ``max(seconds, BIGDL_TPU_STALL_S)``; the step loop tightens the
        beacon to ``seconds`` the moment the first step lands."""
        seconds = float(seconds)
        if seconds <= 0:
            raise ValueError(f"stall deadline must be > 0, got {seconds}")
        if startup_grace_s is not None and float(startup_grace_s) < seconds:
            raise ValueError(
                f"startup_grace_s ({startup_grace_s}) must be >= the "
                f"steady-state deadline ({seconds})")
        self.stall_deadline_s = seconds
        self.stall_startup_grace_s = None if startup_grace_s is None \
            else float(startup_grace_s)
        self.on_stall = on_stall
        return self

    def set_remediation(self, policy: Optional[RemediationPolicy]):
        """Arm the Tier-1 observe→act loop (see
        :class:`RemediationPolicy`): stalls and heartbeat loss
        checkpoint-and-exit with a flight bundle instead of hanging,
        plateau/spike anomalies optionally drive the LR schedule and
        early-stop, straggler reports run on a cadence. ``None``
        disarms."""
        if policy is not None and not isinstance(policy, RemediationPolicy):
            raise TypeError(f"expected RemediationPolicy, got {policy!r}")
        self.remediation = policy
        return self

    def set_fault_policy(self, policy: Optional[FaultPolicy]):
        """Arm Tier-2 dispatch retry (see
        :class:`~bigdl_tpu.parallel.failure.FaultPolicy`): every
        dispatch snapshots the resolved host-side training state first,
        and a TRANSIENT device/collective failure replays the in-flight
        step — under superstep fusion, the whole K-step group — from
        that snapshot after an exponential backoff, so a dropped
        connection costs one step's latency instead of the run. The replay
        reuses the step's exact batches, lr vector and rng keys, so a
        retried run is bitwise-identical to a fault-free one. Permanent
        failures raise immediately (Tier 3 owns those). The per-
        dispatch snapshot is a device→host fetch of params/opt-state —
        meaningful overhead, so arm this for flaky transports, not by
        default. ``None`` disarms.

        SINGLE-CONTROLLER ONLY: the replay re-enters restore placement
        and the compiled step's collectives on THIS process alone. In a
        multi-controller run a failure one process sees and its peers
        don't would have only that process replaying — collectives the
        others never join, wedging the whole mesh until the watchdog
        kills it. Multi-controller transients belong to Tier 1 + Tier 3
        (heartbeat halt, checkpoint, elastic restart)."""
        if policy is not None and not isinstance(policy, FaultPolicy):
            raise TypeError(f"expected FaultPolicy, got {policy!r}")
        if policy is not None and jax.process_count() > 1:
            _LOG.warning(
                "FaultPolicy replay is single-controller: in this "
                "%d-process run a one-sided transient replay would "
                "desynchronize the mesh's collectives — rely on "
                "Tier 1 heartbeat remediation + elastic restart for "
                "cross-process faults", jax.process_count())
        self.fault_policy = policy
        return self

    def set_anomaly_detection(self, enabled: bool = True, **config):
        """Configure the rolling loss anomaly detector (spikes,
        plateaus, NaN streaks — ``observability.health.SeriesMonitor``;
        kwargs override its defaults, e.g. ``spike_sigma=6``,
        ``plateau_window=500``). It consumes the loss floats the sync
        policy already resolves — zero extra device readbacks.
        ``enabled=False`` turns it off entirely."""
        self.anomaly_config = dict(config) if enabled else None
        return self

    def set_nan_policy(self, policy: str):
        """'error' raises, 'skip' drops the step, 'resume' rolls back to the
        latest checkpoint (requires set_checkpoint) — the step-level analog of
        Spark's failed-task retry (SURVEY §5 failure detection)."""
        assert policy in ("error", "skip", "resume")
        self.nan_policy = policy
        return self

    def _latest_checkpoint(self):
        # one trust anchor for "the latest checkpoint" across every
        # recovery path: nan-resume here, elastic restart in the runner
        from ..parallel.elastic import find_latest_checkpoint
        return find_latest_checkpoint(self.checkpoint_path)

    # -- internals -------------------------------------------------------
    def _as_dataset(self, ds):
        if ds is None or isinstance(ds, AbstractDataSet):
            return ds
        if isinstance(ds, tuple) and len(ds) == 2:
            return DataSet.from_arrays(ds[0], ds[1])
        if isinstance(ds, (list,)):
            return DataSet.array(ds)
        if hasattr(ds, "data") and hasattr(ds, "size"):
            return ds  # batch-level dataset (e.g. native.NativePrefetcher)
        raise TypeError(f"unsupported dataset {type(ds)}")

    def _num_shards(self):
        return 1

    def _batched(self):
        if hasattr(self.training_set, "batches_per_epoch"):
            return self.training_set  # already yields MiniBatches
        return ShardedDataSet(self.training_set, self.batch_size,
                              num_shards=self._num_shards())

    def _step_mode(self):
        """What this mode's step is made of, for :meth:`_build_step`:
        ``(loss_fn, frozen_mask, exchange, update, specs)`` — the loss
        over the mode's parameter form, the frozen mask in that form
        (None when nothing is frozen), the gradient exchange
        ``(grads, x) -> grads`` (None when the mode has none of its
        own), ``update(grads, params, opt_state, lr)``, and the
        shard_map specs of ``(params, opt_state, mstate)`` when the step
        is an EXPLICIT per-shard program (None: a plain jit whose batch
        dim XLA partitions by itself). Here: the tree, no exchange, the
        optim method's own update."""
        return (_loss_fn(self.model, self.criterion),
                _frozen_mask(self.model), None, self.optim_method.update,
                None)

    def _build_step(self):
        """The ONE step body and its ONE ending; a mode contributes only
        what :meth:`_step_mode` names."""
        clip_const, clip_norm = self.clip_const, self.clip_norm
        loss_fn, frozen_mask, exchange, update, specs = self._step_mode()
        explicit = specs is not None
        trace_context = contextlib.nullcontext if explicit \
            else self._step_trace_context

        def step(params, opt_state, mstate, x, y, lr, rng):
            with trace_context():
                (loss, new_mstate), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mstate, x, y, rng)
            if exchange is not None:
                grads = exchange(grads, x)
            grads = _clip_grads(grads, clip_const, clip_norm)
            with jax.named_scope("optim_update"):
                if frozen_mask is not None:
                    grads = _tmap(lambda g, m: g * m, grads, frozen_mask)
                new_params, new_opt = update(grads, params, opt_state, lr)
                if frozen_mask is not None:
                    # weight decay must not move frozen params either
                    new_params = _tmap(
                        lambda n, o, m: jnp.where(m > 0, n, o),
                        new_params, params, frozen_mask)
                if explicit:
                    loss = jax.lax.pmean(loss, "data")
                    new_mstate = _tmap(lambda t: jax.lax.pmean(t, "data"),
                                       new_mstate)
                # guarded after the pmean, so every shard takes the same
                # branch — no divergence across the mesh
                return (loss,) + _guarded(
                    loss, (new_params, new_opt, new_mstate),
                    (params, opt_state, mstate))

        def local_step(params, opt_state, mstate, x, y, lr, rng):
            # one shard's step of an explicit mode: its own rng stream
            rng = jax.random.fold_in(rng, jax.lax.axis_index("data"))
            return step(params, opt_state, mstate, x, y, lr, rng)

        fn, batch, steps = local_step if explicit else step, P("data"), 1
        if self.superstep > 1:
            # the scan lives INSIDE the shard_map body: the ZeRO-1
            # psum_scatter/update/all_gather cycle stays in the compiled
            # loop (the cross-replica sharded update must ride the scan
            # for superstep fusion to pay off — one program, K collective
            # rounds, zero host round-trips in between). Batch stacks
            # carry the scan dim first, per-step batch dim sharded.
            fn, batch, steps = \
                _scan_superstep(fn), P(None, "data"), _steps_in_stack
        if explicit:
            from ..utils.compat import shard_map
            fn = shard_map(fn, mesh=self.mesh,
                           in_specs=specs + (batch, batch, P(), P()),
                           out_specs=(P(),) + specs, check_vma=False)
        return self._instrument_step(
            jax.jit(fn, donate_argnums=(0, 1, 2)), steps)

    def _step_trace_context(self):
        """Context the forward+backward of the default step traces
        under (overridden by DistriOptimizer, whose batch dim jit
        partitions automatically)."""
        return contextlib.nullcontext()

    def _instrument_step(self, jit_fn, steps_per_program):
        """Route the compiled step through the perf-introspection
        wrapper: each distinct batch signature records a
        CompiledArtifact (XLA FLOPs/bytes, memory footprint, compile
        wall time, cache provenance) that the live ``perf/mfu`` gauge
        and ``tools/xla_report.py`` read. Params/opt-state/model-state
        shapes are fixed for the life of the step fn, so the signature
        keys on the batch arguments alone (argnums 3, 4).
        ``steps_per_program`` is 1, or under superstep fusion the
        builder's reader of the per-program step count."""
        return obs.perf.instrument_jit(
            jit_fn, name="optim/step", kind="train_step",
            key_argnums=(3, 4), steps_per_program=steps_per_program)

    def _place_batch(self, x, y):
        from .staging import place_host_value
        return place_host_value(x), place_host_value(y)

    def _stage_minibatch(self, mb):
        """Produce-side staging at K = 1: host MiniBatch -> the loop's
        element ``(1, x, y)`` with device-resident x, y. Runs on the
        stager thread when prefetch is enabled (the native bf16_nhwc
        prefetcher's batches pass through as a cast-free device_put),
        inline otherwise."""
        return (1,) + self._place_batch(*_host_xy(mb))

    def _stage_group(self, items):
        """Superstep stacking stage (runs on the stager thread): K host
        microbatches ``(x, y)`` (``_host_xy``: placement happens once
        per GROUP here, so the whole ``[K, batch, ...]`` stack ships in
        one (sharded) device_put) -> one ``(k, xs, ys)`` element with
        device-resident ``[k, batch, ...]`` stacks, so the hot loop
        dequeues one element per dispatch. ``np.asarray`` first: the
        native prefetchers may hand device-resident batches
        (direct-to-device staging); the stack itself must run on host
        memory."""
        def stack(vals):
            return _tmap(lambda *ls: np.stack([np.asarray(l) for l in ls]),
                         *vals)
        xs = stack([x for x, _ in items])
        ys = stack([y for _, y in items])
        xs, ys = self._place_group(xs, ys)
        return len(items), xs, ys

    def _place_group(self, xs, ys):
        """Host ``[k, batch, ...]`` stacks -> device (overridden by
        DistriOptimizer to shard the per-step batch dim over the mesh)."""
        from .staging import place_host_value
        return place_host_value(xs), place_host_value(ys)

    @staticmethod
    def _stage_group_key(staged):
        """Stacking compatibility key: the per-step batch size. A ragged
        final batch (batch-level datasets without drop-remainder) must
        start its own smaller group, not np.stack against full ones."""
        x, _ = staged
        leaves = jax.tree_util.tree_leaves(x)
        return leaves[0].shape[0] if leaves else 0

    def _observe_loss(self, loss, step=None):
        """Apply the sync policy to this step's device loss. Returns the
        resolved host float to examine this iteration, or None when the
        windowed policy has not filled its in-flight budget yet. Every
        resolution is one host<->device sync, counted in
        ``optim/loss_syncs`` (supersteps cut this K-fold).

        ``step`` is the iteration this DISPATCH belongs to; under
        ``async``/``window:K`` the returned float describes an OLDER
        dispatch, and ``self._resolved_step`` names it — the health
        layer (flight ring, anomaly detector) must attribute a lagged
        loss to the step that produced it, not the step that read it."""
        k = self._window_k()
        self._resolved_step = step
        if k is not None:
            self._loss_window.append((step, loss))
            if obs.enabled():
                obs.gauge("optim/loss_window_inflight").set(
                    len(self._loss_window))
            if len(self._loss_window) < k:
                return None
            if obs.enabled():
                obs.counter("optim/loss_syncs").inc()
            self._resolved_step, oldest = self._loss_window.popleft()
            # sync-ok: windowed resolve of the OLDEST in-flight loss
            return float(oldest)
        if obs.enabled():
            obs.counter("optim/loss_syncs").inc()
        if self.sync_policy == "async":
            # examine the PREVIOUS step's loss: the device keeps
            # computing while the host preps the next batch
            prev, self._pending_loss = self._pending_loss, (step, loss)
            if prev is not None:
                self._resolved_step, loss = prev
            # sync-ok: lagged read (first step resolves its own loss)
            return float(loss)
        # sync-ok: sync policy blocks on every step by definition
        return float(loss)

    def _publish_counters(self, mstate, fused, step_span):
        """What the model counted in the step whose loss was just read
        (``mstate["counters"]``: scalars by name, an expert layer's routed
        rows): into ``metrics`` and onto the ``step`` span, one small
        readback where the loop has waited for the device already. Under a
        lagged loss policy the newest state is still in flight and nothing
        is read."""
        counters = mstate.get("counters") if isinstance(mstate, dict) else None
        if not counters or not (fused or self.sync_policy == "sync"):
            return
        for name, v in jax.device_get(counters).items():
            self.metrics.add(name, float(v))
            step_span.annotate(**{name: float(v)})

    def _drain_pending_losses(self, state):
        """Resolve losses still in flight when the loop ends (async's one
        pending read, window:K's up-to-K-1 tail) — a NaN pending on the
        final steps must not be swallowed."""
        pending = list(self._loss_window)
        self._loss_window.clear()
        if self._pending_loss is not None:
            pending.append(self._pending_loss)
            self._pending_loss = None
        for _step, dev in pending:
            final = float(dev)  # sync-ok: end-of-run drain
            if np.isfinite(final):
                state["loss"] = final
            elif self.nan_policy == "error":
                raise FloatingPointError(
                    f"non-finite loss {final} on a final step "
                    f"({self.sync_policy} lagged read)")
            else:
                self.metrics.add("nan_skips", 1.0)

    def _checkpoint_payload(self, params, opt_state, mstate, state):
        """Host snapshot of the full training state. The optimizer state
        rides in CANONICAL (mesh-shape-agnostic) form — for ZeRO-1 the
        flat sharded vectors are unflattened back to params-shaped trees
        (``AllReduceParameter.state_to_canonical``) — so the same
        checkpoint restores under any device count or parameter mode:
        the contract elastic restart (Tier 3) depends on."""
        return {
            **self._host_step_state(params, opt_state, mstate),
            # from the CALLER's state, not self.optim_method.state: the
            # watchdog-thread halt path passes a snapshot taken next to
            # its _live_state read, and re-reading the live dict here
            # could pair step-N params with step-N+1 counters if the
            # loop unwedges mid-halt (in the loop paths ``state`` IS
            # optim_method.state, so this is the same dict)
            "optim_host_state": dict(state),
            "epoch": state["epoch"], "neval": state["neval"],
        }

    def _host_step_state(self, params, opt_state, mstate):
        """Host copies of the in-step trees in the checkpoint's
        CANONICAL (mesh-shape-agnostic) form — the single definition the
        checkpoint payload and the Tier-2 replay snapshot share, and the
        exact shape :meth:`_restore_step_state` parses."""
        return {
            "params": _tmap(np.asarray, self._params_for_checkpoint(params)),
            "opt_state": self._opt_state_for_checkpoint(opt_state),
            "model_state": self._to_host(mstate),
        }

    def _checkpoint(self, params, opt_state, mstate, state, tag=None,
                    force_sync=False):
        """Write one checkpoint; returns its path. ``tag`` overrides the
        name suffix (remediation checkpoints are tagged so a post-mortem
        can tell a scheduled snapshot from a halt artifact — both match
        the ``checkpoint*.bigdl`` pattern every restore path globs).
        ``force_sync`` bypasses the async writer: a halt must not race
        its own exit."""
        if tag is None:
            tag = "" if self.checkpoint_overwrite else \
                f"_e{state['epoch']}_i{state['neval']}"
        path = os.path.join(self.checkpoint_path, f"checkpoint{tag}.bigdl")
        # the device→host fetch is the only synchronous part; serialization
        # and file IO can ride the writer thread (async_write)
        payload = self._checkpoint_payload(params, opt_state, mstate, state)
        async_write = self.checkpoint_async and not force_sync
        with obs.span("step/checkpoint_submit", async_write=async_write):
            if async_write:
                self._ckpt_writer.submit(path, payload)
            else:
                _atomic_pickle(path, payload)
        if obs.enabled():
            _flight.record("checkpoint", path=path, neval=state["neval"],
                           epoch=state["epoch"],
                           async_write=async_write)
        return path

    def wait_for_checkpoints(self):
        """Block until every async checkpoint write has landed (re-raising
        a writer failure). No-op for synchronous checkpoints."""
        self._ckpt_writer.flush()

    def _close_checkpoints(self, timeout=None):
        self._ckpt_writer.close(timeout=timeout)

    def load_checkpoint(self, path):
        """Resume training state from a snapshot (parity:
        Optimizer.setCheckpoint + File.load resume flow)."""
        with open(path, "rb") as f:
            payload = pickle.load(f)
        self.model.ensure_initialized()
        self.model.params = _tmap(jnp.asarray, payload["params"])
        self.model.state = _tmap(jnp.asarray, payload["model_state"])
        self.optim_method.state.update(payload["optim_host_state"])
        self._resume_opt_state = _tmap(jnp.asarray, payload["opt_state"])
        return self

    def _validate(self, state):
        if self.validation_set is None:
            return None
        was_training = self.model.train_mode
        self.model.evaluate()
        from .evaluator import Evaluator
        with obs.span("step/validate", neval=state["neval"]):
            results = Evaluator(self.model).evaluate(
                self.validation_set, self.validation_methods,
                self.validation_batch)
        if was_training:
            self.model.training()
        scores = {}
        for method, res in zip(self.validation_methods, results):
            val, _ = res.result()
            scores[repr(method)] = val
            if self.val_summary is not None:
                # triggers gate recording: "Validation" gates every
                # validation scalar, the per-method tag gates one
                rec = self.val_summary.should_record
                if rec("Validation", state) and rec(repr(method), state):
                    self.val_summary.add_scalar(repr(method), val,
                                                state["neval"])
        if scores:
            state["score"] = list(scores.values())[0]
        return scores

    # -- main loop -------------------------------------------------------
    def optimize(self) -> Module:
        """Run training to the end trigger. With observability enabled
        the run is health-instrumented: the step loop and stager pulse
        stall beacons, the resolved losses feed the anomaly detector
        and the flight recorder, device-memory gauges register when the
        backend supports them, and an unhandled failure (including the
        NaN-policy aborts) dumps a flight-recorder crash bundle before
        re-raising — ``tools/flight_report.py`` renders it."""
        self._halt_requested = None
        self._live_state = None
        self._remediation_lr_scale = 1.0
        self._remediating = False
        if self.remediation is not None:
            self.remediation.reset_run_state()
        # with a remediation policy the stall callback is the Tier-1
        # handler (which chains any user on_stall); without one the
        # user callback rides the beacon directly as before
        on_stall = self._stall_handler if self.remediation is not None \
            else self.on_stall
        # the beacon opens at the startup grace (first dispatch = whole
        # XLA compile, legitimately silent for minutes) and is tightened
        # to the steady-state deadline when the first step lands
        deadline = self.stall_deadline_s \
            if self.stall_deadline_s is not None \
            else _health.default_stall_deadline()
        grace = self.stall_startup_grace_s \
            if self.stall_startup_grace_s is not None \
            else max(deadline, _health.default_stall_deadline())
        self._step_beacon = _health.beacon(
            "optim/step", deadline_s=max(grace, deadline),
            on_stall=on_stall)
        self._stall_grace_pending = (
            grace > deadline
            and self._step_beacon is not _health.NULL_BEACON)
        self._profiler = _health.profiler_window_from_env()
        self._loss_monitor = None
        if self.anomaly_config is not None and \
                (obs.enabled() or self.remediation is not None):
            # remediation's anomaly-driven control consumes the monitor's
            # returned events, so it runs even with observability off
            self._loss_monitor = _health.SeriesMonitor(
                "loss", **self.anomaly_config)
        if obs.enabled():
            _health.ensure_memory_telemetry()
            # re-read the snapshot cadence per run (tests and launchers
            # set BIGDL_TPU_METRIC_SNAP_S around individual runs)
            self._snap_writer = _cluster.default_writer()
            st = self.optim_method.state
            _flight.record("train/start", epoch=st.get("epoch"),
                           neval=st.get("neval"), seed=engine.get_seed(),
                           batch_size=self.batch_size,
                           superstep=self.superstep,
                           sync_policy=self.sync_policy)
        # the collector's pauses as ``host/gc`` spans, and per step as
        # the ``step`` span's ``host/gc_*`` counters, for this run only
        obs.gc_hook_install()
        self._gc_pauses = obs.GcPauses()
        try:
            return self._optimize_impl()
        except TrainingHalted:
            raise  # Tier-1 already landed its checkpoint + bundle
        except BaseException as e:
            if obs.enabled():
                st = self.optim_method.state
                _flight.dump_crash_bundle(error=e, context={
                    "component": "optimizer",
                    "epoch": st.get("epoch"), "neval": st.get("neval"),
                    "seed": engine.get_seed(),
                    "batch_size": self.batch_size,
                    "superstep": self.superstep,
                    "sync_policy": self.sync_policy,
                    "nan_policy": self.nan_policy})
            raise
        finally:
            obs.gc_hook_remove()
            if self._snap_writer.enabled and obs.enabled():
                # terminal snapshot: the cluster merge must see this
                # process's END state, not its last cadence tick —
                # final=True so a finished process never reads as a
                # suspect-dead straggler once its snapshot goes stale
                self._snap_writer.write(
                    step=self.optim_method.state.get("neval"), final=True)
            self._step_beacon.close()
            self._step_beacon = _health.NULL_BEACON
            self._live_state = None
            if self._profiler is not None:
                self._profiler.close()
                self._profiler = None
            try:
                # idempotent (the success path already closed it,
                # UNBOUNDED — durability on a clean exit): a
                # TrainingHalted/crash exit must not leak the async
                # writer thread or let its queued stale writes keep
                # landing under the ElasticRunner's NEXT attempt, and
                # must not block forever on storage wedged badly enough
                # to be part of why we're halting
                self._close_checkpoints(timeout=30.0)
            except Exception:
                _LOG.exception("async checkpoint writer close failed")

    def _optimize_impl(self) -> Module:
        self.model.ensure_initialized()
        self.model.training()
        params, mstate = self.model.params, self.model.state
        opt_state = getattr(self, "_resume_opt_state", None)
        if opt_state is None:
            opt_state = self.optim_method.init_state(params)
        params, opt_state, mstate = self._prepare(params, opt_state, mstate)
        engine.maybe_enable_compilation_cache()
        with obs.span("optimizer/build_step"):
            self._step_fn = self._build_step()
        if obs.enabled():
            obs.gauge("engine/compile_cache_entries").set(
                engine.compilation_cache_entries())
        # never consume a dead run's in-flight losses
        self._pending_loss = None
        self._loss_window.clear()

        optim = self.optim_method
        state = optim.state  # {'neval', 'epoch', ...}
        batched = self._batched()
        # K is read ONCE a run, beside the build of the step that scans
        # it: the stager's grouping and the loop's element follow from
        # the same reading, so the three cannot disagree
        fused = self.superstep > 1
        done = False
        nan_streak = 0
        batches, epoch_start = self._open_epoch(batched, fused)
        while not done:
            box = {"params": params, "opt_state": opt_state,
                   "mstate": mstate, "nan_streak": nan_streak, "done": done}
            try:
                self._run_epoch(batches, state, box, fused)
            except BaseException:
                batches.close()  # join the stager thread — no leaks, ever
                raise
            params, opt_state, mstate = \
                box["params"], box["opt_state"], box["mstate"]
            nan_streak, done = box["nan_streak"], box["done"]
            if done:
                batches.close()
                break
            # the epoch boundary, from the exhausted stager's join to the
            # next one's start: one span, so that the loop's host time
            # lies under ``step`` or here
            with obs.span("epoch/turnover", epoch=state["epoch"]):
                t_turn = time.perf_counter()
                batches.close()
                state["epoch"] += 1
                state["epoch_finished"] = True
                epoch_s = time.time() - epoch_start
                self.metrics.add("epoch_time", epoch_s)
                self._fire_epoch(state, params, opt_state, mstate)
                done = bool(self.end_trigger(state))
                if not done:
                    batches, epoch_start = self._open_epoch(batched, fused)
                turnover_s = time.perf_counter() - t_turn
            self.metrics.add("epoch_turnover_time", turnover_s)
            if obs.enabled():
                _flight.record("epoch", epoch=state["epoch"] - 1,
                               neval=state["neval"], epoch_time_s=epoch_s,
                               turnover_s=turnover_s)

        # drain the async/window in-flight losses (a NaN pending on the
        # final steps must not be swallowed)
        self._drain_pending_losses(state)
        self.model.params, self.model.state = \
            self._collect(params, mstate, opt_state)
        self.model.grad_params = _tmap(jnp.zeros_like, self.model.params)
        self._close_checkpoints()  # land async writes, stop the writer
        return self.model

    def _open_epoch(self, batched, fused):
        """Reshuffle and start the epoch's stager: (batches, wall start).
        The stager owns produce + device placement; with prefetch_depth
        >= 2 both run on a lookahead thread while the device computes,
        otherwise inline (the serial loop). With superstep K > 1 it also
        owns the stacking stage: groups of K microbatches assemble into
        [K, batch, ...] device stacks and the hot loop dequeues one per
        dispatch."""
        batched.shuffle()
        epoch_start = time.time()
        return staged(batched.data(train=True),
                      _host_xy if fused else self._stage_minibatch,
                      depth=self.prefetch_depth, name="stager",
                      group=self.superstep,
                      group_fn=self._stage_group if fused else None,
                      group_key=self._stage_group_key,
                      stall_deadline_s=self.stall_deadline_s), epoch_start

    # -- self-healing tiers ---------------------------------------------
    def _dispatch_guarded(self, params, opt_state, mstate, *args):
        """The dispatch path, wrapped by the Tier-2 FaultPolicy when
        armed: snapshot the resolved host-side state BEFORE the call
        (the compiled step donates its state buffers — after a failed
        dispatch the device arrays may already be invalidated, so the
        replay must re-place from host), then on a retryable failure
        back off, restore, and replay the same step (or whole superstep
        group: same batches, same lr vector, same rng keys — bitwise
        the trajectory a fault-free run takes). Non-retryable failures
        propagate untouched."""
        fp = self.fault_policy
        if fp is None:
            return self._step_fn(params, opt_state, mstate, *args)
        snap = self._host_step_state(params, opt_state, mstate)
        if obs.enabled():
            obs.counter("optim/fault_snapshots").inc()
        while True:
            try:
                out = self._step_fn(params, opt_state, mstate, *args)
                # async dispatch defers device/collective failures to
                # the first readback, which happens at the loss sync far
                # OUTSIDE this guard — resolve here so a transient
                # surfaces where the retry can catch it (the armed path
                # is already serialized by the per-dispatch snapshot)
                jax.block_until_ready(out)  # sync-ok: Tier-2 fault guard
                fp.record_success()
                return out
            except FloatingPointError:
                raise  # NaN policy owns numeric failures, not the retry tier
            except Exception as e:
                cls = classify_failure(e)
                if not fp.should_retry(cls):
                    if obs.enabled():
                        _health.emit("fault_exhausted", failure_class=cls,
                                     error=f"{type(e).__name__}: {e}",
                                     consecutive=fp.consecutive)
                    raise
                fp.record_failure()
                delay = fp.backoff_s()
                # mirrors into the registry as optim/fault_retries; the
                # health/fault_retry counter rides the emit below
                self.metrics.add("fault_retries", 1.0)
                if obs.enabled():
                    _health.emit("fault_retry", failure_class=cls,
                                 error=f"{type(e).__name__}: {e}",
                                 attempt=fp.consecutive,
                                 backoff_s=round(delay, 3))
                if delay > 0:
                    fp.sleep(delay)
                params, opt_state, mstate = self._restore_step_state(snap)

    def _tighten_stall_deadline(self):
        """Drop the beacon's startup compile grace down to the
        steady-state stall deadline — called once the first dispatch
        completes (one bool check per step after that)."""
        if not self._stall_grace_pending:
            return
        self._stall_grace_pending = False
        # pulse BEFORE lowering the deadline: the beacon's age still
        # spans the whole compile, which would trip the tight deadline
        # instantly; the completed first dispatch IS the progress signal
        self._step_beacon.pulse()
        self._step_beacon.deadline_s = self.stall_deadline_s
        _health.watchdog().poke()  # recompute the check interval now

    def _check_halt(self):
        """Surface a halt the watchdog-thread remediation requested
        while this loop was blocked (checked at every iteration top and
        after every dispatch)."""
        if self._halt_requested is not None:
            ex, self._halt_requested = self._halt_requested, None
            raise ex

    def _try_halt_checkpoint(self, state, live):
        """Drain queued async writes, then land the synchronous
        remediation checkpoint from ``live`` ``(params, opt_state,
        mstate)``. Best-effort: any failure logs and returns None — it
        must not mask the halt."""
        try:
            self.wait_for_checkpoints()
        except Exception:
            _LOG.exception("async checkpoint drain failed during remediation")
        if live is None:
            return None
        try:
            p, o, m = live
            return self._checkpoint(
                p, o, m, state, force_sync=True,
                tag=f"_remediation_e{state.get('epoch', 0)}"
                    f"_i{state.get('neval', 0)}")
        except Exception:
            _LOG.exception(
                "remediation checkpoint failed (halting anyway; "
                "a wedged dispatch may have donated the live "
                "buffers)")
            return None

    def _land_halt_checkpoint(self, state, live, timeout_s=None):
        """Checkpoint step of the halt landing. ``timeout_s`` bounds the
        attempt on a disposable daemon worker: the device→host fetch
        inside has no deadline of its own, and on a DEAD mesh it blocks
        forever — which must never wedge the single watchdog monitor
        thread stall remediation runs on (``exit_process`` would never
        fire and every other beacon would go unmonitored). On expiry
        the worker is abandoned and the halt proceeds without a
        checkpoint (the flight bundle and ``TrainingHalted`` are pure
        host-side work and still land)."""
        if not self.checkpoint_path:
            return None
        if timeout_s is None:
            return self._try_halt_checkpoint(state, live)
        res = _run_with_timeout(
            lambda: self._try_halt_checkpoint(state, live), timeout_s)
        if res.get("timeout"):
            _LOG.error(
                "remediation checkpoint did not land within %.1fs "
                "(device fetch wedged on a dead mesh?); halting "
                "without one", timeout_s)
            return None
        return res.get("value")

    def _land_halt_artifacts(self, cause, state, live, error=None,
                             failure_class=PERMANENT, lost_processes=(),
                             ckpt_timeout_s=None, **extra):
        """Shared Tier-1 artifact landing — the loop-side :meth:`_halt`
        and the watchdog-thread :meth:`_stall_handler` must stay in
        lockstep, so there is exactly one copy: drain in-flight async
        checkpoint writes FIRST (a queued pre-halt write landing after
        the remediation snapshot would out-mtime it and
        ``find_latest_checkpoint`` would silently resume stale state),
        land the synchronous remediation checkpoint when the ``live``
        ``(params, opt_state, mstate)`` handles are available (bounded
        by ``ckpt_timeout_s`` when the caller cannot afford to block —
        see :meth:`_land_halt_checkpoint`), dump the flight bundle,
        emit ``health/remediation``, and return the
        :class:`TrainingHalted` for the caller to raise (step loop) or
        queue (watchdog thread). Every artifact is best-effort — a
        failure must not mask the halt."""
        ckpt = self._land_halt_checkpoint(state, live,
                                          timeout_s=ckpt_timeout_s)
        bundle = _flight.dump_crash_bundle(error=error, context={
            "component": "optimizer/remediation", "cause": cause,
            "failure_class": failure_class,
            "epoch": state.get("epoch"), "neval": state.get("neval"),
            "checkpoint": ckpt,
            "lost_processes": list(lost_processes), **extra})
        _health.emit("remediation", cause=cause,
                     failure_class=failure_class, checkpoint=ckpt,
                     bundle=bundle, neval=state.get("neval"),
                     lost_processes=list(lost_processes), **extra)
        return TrainingHalted(
            cause=cause, failure_class=failure_class, checkpoint_path=ckpt,
            bundle_path=bundle, epoch=state.get("epoch"),
            neval=state.get("neval"), lost_processes=lost_processes)

    def _halt(self, cause, state, params, opt_state, mstate, error=None,
              failure_class=PERMANENT, lost_processes=()):
        """Tier-1 checkpoint-and-exit from the step loop itself: land
        the halt artifacts and raise the :class:`TrainingHalted` they
        describe. The checkpoint fetch is bounded just like the
        watchdog path's: a heartbeat-loss halt is often remediating a
        mesh with a DEAD peer, and an unbounded device→host fetch of
        state sharded across it would wedge the run inside its own
        remediation."""
        pol = self.remediation
        raise self._land_halt_artifacts(
            cause, state, (params, opt_state, mstate), error=error,
            failure_class=failure_class, lost_processes=lost_processes,
            ckpt_timeout_s=pol.halt_artifact_timeout_s
            if pol is not None else None) from error

    def _stall_handler(self, beacon, age_s):
        """Watchdog-fired stall remediation entry: run the user's
        ``on_stall`` inline (cheap, PR-5 contract), then hand the
        classify-and-land work to a disposable side thread — the probe
        (``probe_timeout_s``) plus the bounded halt checkpoint
        (``halt_artifact_timeout_s``) can block for minutes, and the
        SINGLE watchdog monitor thread must keep checking every other
        beacon (serving batcher, stager, heartbeat prober) meanwhile.
        The beacon stays latched until the side thread's verdict
        (re-arm or halt), so one episode spawns one remediation."""
        if self.on_stall is not None:
            try:
                self.on_stall(beacon, age_s)
            except Exception:
                _LOG.exception("on_stall failed")
        pol = self.remediation
        if pol is None or self._halt_requested is not None \
                or self._remediating:
            return
        self._remediating = True
        threading.Thread(target=self._remediate_stall,
                         args=(beacon, age_s),
                         name="bigdl-stall-remediation",
                         daemon=True).start()

    def _remediate_stall(self, beacon, age_s):
        """Side-thread body of stall remediation: probe the mesh to
        classify the stall, and for a dead mesh (or ``halt_on_stall``)
        land the halt artifacts — the step loop is the thing that
        stopped, so it cannot save itself. The checkpoint comes from
        ``_live_state`` (the handles of the last COMPLETED dispatch —
        consistent by construction; best-effort if the wedged dispatch
        already donated them), then the halt is queued for the loop to
        raise if it ever unwedges; ``exit_process`` force-exits for
        loops that never will."""
        try:
            pol = self.remediation
            cls, err = TRANSIENT, None
            mesh = getattr(self, "mesh", None)
            if mesh is not None and pol.probe_timeout_s > 0:
                res = probe_mesh(mesh, timeout_s=pol.probe_timeout_s)
                if not res.ok:
                    cls = PERMANENT
                    err = RuntimeError(
                        f"mesh probe failed after {age_s:.1f}s stall of "
                        f"{beacon.name}: {res.error}")
            if cls != PERMANENT and not pol.halt_on_stall:
                # transient verdict: the watchdog already paged — but a
                # wedged loop will never pulse the stall latch clear
                # itself, and the monitor skips latched beacons, so
                # re-arm the deadline clock: a mesh that dies LATER in
                # the same stall episode gets probed (and halted) again
                # instead of hanging the run with remediation armed
                beacon.rearm()
                return
            # snapshot: if the loop unwedges mid-handler, a live state
            # dict would shear (tag, payload and exception each reading
            # a different neval)
            state = dict(self.optim_method.state)
            self._halt_requested = self._land_halt_artifacts(
                "stall", state, self._live_state, error=err,
                failure_class=cls, stalled_component=beacon.name,
                age_s=round(age_s, 3),
                ckpt_timeout_s=pol.halt_artifact_timeout_s)
            if pol.exit_process:
                os._exit(86)  # artifacts are on disk; the loop never is
        except Exception:
            _LOG.exception("stall remediation failed")
        finally:
            self._remediating = False

    def _apply_anomaly_events(self, pol, state, events):
        """Anomaly-driven control: act on the health events the loss
        monitor fired for THIS iteration's resolved losses. Returns
        True when the run should end cleanly (plateau early-stop)."""
        for ev in events:
            kind = ev.get("kind", "")
            if kind == "health/plateau":
                pol.plateaus += 1
                if pol.plateau_lr:
                    self._reduce_lr_for_plateau(pol, state)
                if pol.early_stop_plateaus is not None and \
                        pol.plateaus >= pol.early_stop_plateaus:
                    _health.emit("early_stop", reason="plateau",
                                 neval=state["neval"],
                                 plateaus=pol.plateaus)
                    return True
            elif kind.endswith("_spike"):
                pol.spikes += 1
                if pol.max_spikes is not None and \
                        pol.spikes >= pol.max_spikes:
                    raise FloatingPointError(
                        f"{pol.spikes} loss spikes "
                        f"(RemediationPolicy.max_spikes="
                        f"{pol.max_spikes}) — the run is diverging")
        return False

    def _reduce_lr_for_plateau(self, pol, state):
        sched = getattr(self.optim_method, "learningrate_schedule", None)
        if isinstance(sched, Plateau):
            mult = sched.force_reduction()
        else:
            self._remediation_lr_scale = max(
                self._remediation_lr_scale * pol.plateau_factor,
                pol.min_lr_scale)
            mult = self._remediation_lr_scale
        _health.emit("lr_reduced", reason="plateau", neval=state["neval"],
                     multiplier=mult,
                     schedule=type(sched).__name__ if sched else None)
        if obs.enabled():
            # under superstep fusion the reduction is applied to the
            # NEXT group's lr vector (the detection itself came off
            # this group's batched loss readback) — the instant marks
            # where the policy acted so the one-group lag is visible
            obs.counter("optim/lr_reductions").inc()
            obs.instant("optim/lr_reduced", neval=state["neval"],
                        multiplier=mult)

    def _remediation_tick(self, state, params, opt_state, mstate,
                          events, step_time_s=None):
        """One per-iteration (per-superstep-group under fusion) pass of
        the Tier-1 policy. Returns True when training should end
        cleanly; raises via :meth:`_halt` on heartbeat loss or spike
        overload. Runs host-side between dispatches — no readbacks
        beyond what the sync policy already resolved."""
        pol = self.remediation
        if pol is None:
            return False
        try:
            if events and self._apply_anomaly_events(pol, state, events):
                return True
        except FloatingPointError as e:
            self._halt("loss_spikes", state, params, opt_state, mstate,
                       error=e, failure_class=PERMANENT)
        hb = pol.heartbeat
        if hb is not None and \
                state["neval"] - pol._last_beat_neval >= pol.heartbeat_every:
            pol._last_beat_neval = state["neval"]
            try:
                stale = hb.beat(timeout_s=pol.heartbeat_timeout_s)
            except HeartbeatLost as e:
                self._halt("heartbeat_lost", state, params, opt_state,
                           mstate, error=e, failure_class=PERMANENT)
            if stale:
                self._halt("heartbeat_stale", state, params, opt_state,
                           mstate, failure_class=PERMANENT,
                           error=HeartbeatLost(
                               f"peers {stale} stopped advancing their "
                               f"heartbeat counters"),
                           lost_processes=stale)
        sm = pol.straggler_monitor
        if sm is not None:
            if step_time_s is not None:
                sm.record(step_time_s)
            # distance-based cadence, not ``% == 0``: under superstep
            # fusion neval advances by K and might never land on a
            # multiple (the heartbeat check above has the same shape)
            if state["neval"] - pol._last_straggler_neval >= \
                    pol.straggler_every:
                pol._last_straggler_neval = state["neval"]
                sm.report()  # emits health/straggler on persistence
        return False

    def _clamp_superstep(self, state, k):
        """Largest j <= k such that no end/validation/checkpoint trigger
        would fire at an iteration INTERIOR to a j-step dispatch: the
        triggers are probed (side-effect-free) at the simulated counters
        neval+1 .. neval+k-1, and the dispatch is cut so any firing point
        lands exactly on a superstep boundary — host bookkeeping then
        runs at the same iteration it would under K=1. Loss/score-driven
        triggers are probed with the values as observed so far (the
        superstep-granularity lag documented in set_superstep)."""
        if k <= 1:
            return k
        triggers = [t for t in (self.end_trigger, self.validation_trigger,
                                self.checkpoint_trigger) if t is not None]
        if not triggers:
            return k
        sim = dict(state)
        sim["epoch_finished"] = False
        for i in range(1, k):
            sim["neval"] = state["neval"] + i
            for t in triggers:
                fired = t.probe(sim) if hasattr(t, "probe") \
                    else bool(t(dict(sim)))
                if fired:
                    return i
        return k

    def _account_loss(self, state, box, step_no, loss_val, lr, rate,
                      events):
        """Account ONE dispatched step by its resolved loss — the one
        place that holds the NaN policy, the flight record, the loss
        monitor and the summaries, for any K. ``step_no`` is the 1-based
        iteration that PRODUCED ``loss_val`` (under async/window:K up to
        K-1 before the step being counted); ``loss_val`` is None when a
        windowed policy has resolved nothing yet. The monitor's events go
        onto ``events``. Says what became of the step: ``_COUNTED``,
        ``_SKIPPED`` (non-finite, 'skip'), or ``_RESTORED`` (non-finite,
        'resume': the checkpoint's state is in ``box`` and
        ``optim_method.state``, and whatever the dispatch resolved after
        this loss describes updates the restore just discarded)."""
        if loss_val is None or np.isfinite(loss_val):
            if loss_val is not None:
                # windowed policies have no resolved loss until K are in
                # flight — the streak/loss state only moves on an
                # actually-observed value
                box["nan_streak"] = 0
                state["loss"] = loss_val
            state["neval"] += 1
            state["epoch_finished"] = False
            if loss_val is not None:
                # provenance rides the already-resolved host float — no
                # extra readback; under async/window:K the loss belongs
                # to step_no, up to K-1 before the current iteration
                if obs.enabled():
                    _flight.record("step", neval=step_no,
                                   epoch=state["epoch"], loss=loss_val)
                if self._loss_monitor is not None:
                    events.extend(
                        self._loss_monitor.observe(loss_val, step_no))
            if self.train_summary is not None:
                rec = self.train_summary.should_record
                if loss_val is not None and rec("Loss", state):
                    self.train_summary.add_scalar(
                        "Loss", loss_val, state["neval"])
                if rec("LearningRate", state):
                    self.train_summary.add_scalar(
                        "LearningRate", lr, state["neval"])
                if rec("Throughput", state):
                    self.train_summary.add_scalar(
                        "Throughput", rate, state["neval"])
            return _COUNTED
        box["nan_streak"] += 1
        if obs.enabled():
            _flight.record("nan", neval=step_no, epoch=state["epoch"],
                           loss=loss_val, policy=self.nan_policy)
        if self._loss_monitor is not None:
            self._loss_monitor.observe(loss_val, step_no)
        if self.nan_policy == "error":
            raise FloatingPointError(
                f"non-finite loss {loss_val} at iteration "
                f"{state['neval']} — enable "
                f"set_nan_policy('skip') to drop such steps")
        if box["nan_streak"] > self.max_nan_retries:
            raise FloatingPointError(
                f"{box['nan_streak']} consecutive non-finite steps "
                f"(nan_policy='{self.nan_policy}') — data or "
                "hyperparameters are unrecoverably bad")
        if self.nan_policy == "resume":
            self.wait_for_checkpoints()  # in-flight writes
            snap = self._latest_checkpoint()
            if snap is None:
                raise FloatingPointError(
                    "non-finite loss with nan_policy='resume' "
                    "but no checkpoint saved yet — call "
                    "set_checkpoint(...) first")
            with open(snap, "rb") as f:
                payload = pickle.load(f)
            self.optim_method.state.update(payload["optim_host_state"])
            box["params"], box["opt_state"], box["mstate"] = \
                self._restore_step_state(payload)
            # in-flight losses refer to pre-restore steps
            self._pending_loss = None
            self._loss_window.clear()
            self.metrics.add("nan_resumes", 1.0)
            obs.instant("step/nan_resume", neval=state["neval"])
            return _RESTORED
        # 'skip': the in-step guard (in-scan, under fusion) already kept
        # the previous params; count the iteration so end triggers advance
        self.metrics.add("nan_skips", 1.0)
        obs.instant("step/nan_skip", neval=state["neval"])
        state["neval"] += 1
        return _SKIPPED

    def _run_epoch(self, batches, state, box, fused):
        """One epoch of the pipelined step loop, for any K. ``batches``
        yields the stager's elements ``(k, xs, ys)``, device-resident:
        one batch (``k`` = 1, ``fused`` false) or a ``[k, batch, ...]``
        stack whose k fused steps run inside one XLA program
        (``fused``). An iteration fetches one element, prepares its lr
        and rng (the ``[k]`` vectors), dispatches, resolves the losses —
        at K = 1 what the sync policy hands out (none, or one that may
        be older than this step), under fusion the whole ``[k]`` vector
        with ONE batched readback — accounts each through
        :meth:`_account_loss`, preserving K=1 semantics at 1/K the sync
        count, and then does the boundary work once. Mutable step state
        travels in ``box`` (params/opt_state/mstate/nan_streak/done) so
        every exit path — exhaustion, end trigger, an exception mid-step
        — leaves the caller with the latest device handles."""
        optim = self.optim_method
        params, opt_state, mstate = \
            box["params"], box["opt_state"], box["mstate"]
        pending = None  # clamped remainder of a group (device slices)
        try:
            while True:
                self._step_beacon.pulse()
                self._check_halt()
                with obs.span("step", step_num=state["neval"]) as stp:
                    t0 = time.perf_counter()
                    with obs.span("step/data_fetch"):
                        if pending is not None:
                            (k, xs, ys), pending = pending, None
                        else:
                            try:
                                k, xs, ys = next(batches)
                            except StopIteration:
                                return
                    t1 = time.perf_counter()
                    with obs.span("step/prepare"):
                        j = self._clamp_superstep(state, k)
                        if j < k:
                            # a trigger fires mid-group: dispatch the
                            # prefix now, park the rest (device-side
                            # slices — no host copy)
                            pending = (k - j, _tmap(lambda a: a[j:], xs),
                                       _tmap(lambda a: a[j:], ys))
                            xs = _tmap(lambda a: a[:j], xs)
                            ys = _tmap(lambda a: a[:j], ys)
                            k = j
                        # *1.0 is bitwise-exact: the remediation scale only
                        # changes lr after a plateau actually reduced it
                        scale = self._remediation_lr_scale
                        if fused:
                            stp.annotate(k=k)
                            lr = lrs = [l * scale
                                        for l in optim.current_lr_vector(k)]
                            # one dispatch, same stream
                            rng = engine.next_rng_keys(k)
                        else:
                            lr = optim.current_lr() * scale
                            lrs = (lr,)
                            rng = engine.next_rng_key()
                    dsp = obs.span("step/dispatch")
                    with dsp:
                        loss, params, opt_state, mstate = \
                            self._dispatch_guarded(
                                params, opt_state, mstate, xs, ys,
                                jnp.asarray(lr, jnp.float32), rng)
                    # the last COMPLETED dispatch's handles: what the
                    # watchdog-thread stall remediation checkpoints
                    self._live_state = (params, opt_state, mstate)
                    self._tighten_stall_deadline()
                    if obs.enabled():
                        obs.counter("engine/dispatches").inc()
                    with obs.span("step/loss_sync"):
                        # step provenance: the dispatch just issued is
                        # iterations neval+1 .. neval+k
                        first = state["neval"] + 1
                        if fused:
                            # sync-ok: the ONE batched [k] readback per superstep
                            losses = np.asarray(loss).tolist()
                            steps = range(first, first + k)
                            if obs.enabled():
                                obs.counter("optim/loss_syncs").inc()
                        else:
                            # async/window:K resolve an OLDER step —
                            # _resolved_step names it
                            losses = (self._observe_loss(loss, first),)
                            steps = (self._resolved_step,)
                        self._publish_counters(mstate, fused, stp)
                    t2 = time.perf_counter()
                    # the collector's pauses since the previous step's
                    # read, on any thread: host numbers, no readback
                    gc_ms, gc_n, gc_full = self._gc_pauses.take()
                    stp.annotate(**{"host/gc_ms": gc_ms,
                                    "host/gc_collections": gc_n,
                                    "host/gc_full": gc_full})
                    # what follows the resolved losses: their host replay
                    # step by step, then bookkeeping, remediation and the
                    # triggers (validation, checkpoint, the caller's end
                    # trigger) once, at the boundary
                    with obs.span("step/triggers"):
                        rate = k * self.batch_size / max(t2 - t0, 1e-9)
                        health_events = []
                        for step_no, loss_val, step_lr in zip(steps, losses,
                                                              lrs):
                            fate = self._account_loss(
                                state, box, step_no, loss_val, step_lr,
                                rate, health_events)
                            if fate is _RESTORED:
                                params, opt_state, mstate = box["params"], \
                                    box["opt_state"], box["mstate"]
                                break
                        if fate is not _COUNTED:
                            # as at K=1, a step that did not count goes on
                            # to the next dispatch past the boundary work.
                            # But a group's pre-NaN spike/plateau events
                            # describe losses that really happened — the
                            # policy must see them, or a diverging run that
                            # interleaves spikes with NaN restores starves
                            # max_spikes forever and loops
                            # checkpoint-restore indefinitely
                            if health_events and self._remediation_tick(
                                    state, params, opt_state, mstate,
                                    health_events, step_time_s=t2 - t1):
                                box["done"] = True
                                return
                            continue
                        if self._profiler is not None:
                            self._profiler.maybe_tick(state["neval"])
                        self.metrics.add("data_time", t1 - t0)
                        self.metrics.add("gc_time", gc_ms / 1e3)
                        self.metrics.add("step_time", t2 - t1)
                        if obs.enabled():
                            obs.counter("optim/steps").inc(k)
                            obs.gauge("optim/throughput",
                                      unit="samples/s").set(rate)
                            # live MFU + step-phase gauges: host floats the
                            # loop already measured, zero new readbacks. A
                            # dispatch that paid a compile measures XLA, not
                            # the model — excluded, like bench warmup. The
                            # wall is the FULL iteration (t0→t2): under
                            # async/window:K the dispatch+resolve sliver
                            # alone excludes the device time entirely. One
                            # artifact covers the whole K-step program (a
                            # clamped j<K dispatch reads ITS program's
                            # artifact, not the full-K one), so flops over
                            # that wall IS the fused-dispatch MFU.
                            if not getattr(self._step_fn,
                                           "last_call_compiled", True):
                                obs.perf.note_step(
                                    getattr(self._step_fn, "last_artifact",
                                            None),
                                    wall_s=t2 - t0, host_s=t1 - t0,
                                    dispatch_s=dsp.duration_s)
                            self._snap_writer.maybe_write(
                                step=state["neval"])
                        if self._remediation_tick(state, params, opt_state,
                                                  mstate, health_events,
                                                  step_time_s=t2 - t1):
                            box["done"] = True
                            return
                        # checkpoint/validation/end triggers evaluate ONCE
                        # at the superstep boundary, where params and the
                        # iteration counter are consistent: clamping already
                        # aligned every counter-driven firing point to a
                        # boundary, and a loss-driven trigger (which the
                        # probe cannot foresee) defers at most K-1 steps —
                        # it must never pair interior counters with
                        # post-superstep params in a checkpoint
                        self._fire_mid_epoch(state, params, opt_state, mstate)
                        if self.end_trigger(state):
                            box["done"] = True
                            return
        finally:
            box.update(params=params, opt_state=opt_state, mstate=mstate)

    def _fire_mid_epoch(self, state, params, opt_state, mstate):
        fired = False
        if self.validation_trigger is not None and \
                self.validation_trigger(state):
            self.model.params, self.model.state = \
                self._collect(params, mstate, opt_state)
            self._validate(state)
            fired = True
        if self.checkpoint_trigger is not None and \
                self.checkpoint_trigger(state):
            self._checkpoint(params, opt_state, mstate, state)
            fired = True
        return fired

    def _fire_epoch(self, state, params, opt_state, mstate):
        self._fire_mid_epoch(state, params, opt_state, mstate)

    # hooks overridden by DistriOptimizer
    def _to_host(self, tree):
        """Fetch a tree to host numpy for checkpointing."""
        return _tmap(np.asarray, tree)

    def _opt_state_for_checkpoint(self, opt_state):
        """Host optimizer state in CANONICAL (mesh-shape-agnostic) form;
        the local/replicated state already is — the ZeRO-1 override
        unflattens its sharded vectors."""
        return self._to_host(opt_state)

    def _prepare(self, params, opt_state, mstate):
        return params, opt_state, mstate

    def _collect(self, params, mstate, opt_state=None):
        return params, mstate

    def _params_for_checkpoint(self, params):
        return params

    def _restore_step_state(self, payload):
        """Rebuild in-step (params, opt_state, mstate) from a checkpoint
        payload WITHOUT recreating sharding machinery (the compiled step fn
        closes over it)."""
        return self._prepare(_tmap(jnp.asarray, payload["params"]),
                             _tmap(jnp.asarray, payload["opt_state"]),
                             _tmap(jnp.asarray, payload["model_state"]))


class LocalOptimizer(BaseOptimizer):
    """Single-device training (parity: optim/LocalOptimizer.scala — there,
    multi-threaded CPU minibatch stacking; here one XLA device owns the whole
    batch)."""


class DistriOptimizer(BaseOptimizer):
    """Mesh data-parallel training (parity: optim/DistriOptimizer.scala)."""

    def __init__(self, model, training_set, criterion, optim_method=None,
                 end_trigger=None, batch_size: int = 32, mesh=None,
                 parameter_mode: str = "replicated",
                 compress: str = "none", wire_dtype: str = "none",
                 sparse_embedding="auto"):
        """``compress`` / ``wire_dtype``: ZeRO-1 gradient-wire knobs
        (``parallel.allreduce`` module docstring) — ``compress`` is the
        legacy wire-dtype psum, ``wire_dtype`` the fp32-master-
        accumulation all_to_all wire. Both off by default; mutually
        exclusive.

        ``sparse_embedding``: per-layer gradient-wire path selection
        (the Parallax exchange — ``nn.sparse.
        sparse_embedding_grad_allreduce``, docs/DISTRIBUTED.md). The
        step is built as an explicit shard_map whose per-layer exchange
        picks, AT TRACE TIME from the static shapes, the cheaper wire
        for each gradient leaf: the model's leading embedding layer
        ships its touched ``(indices, value rows)`` when ``B_local *
        (H+1) < vocab * H`` elements, every other leaf (and an
        embedding whose batch would not win) rides the dense ``pmean``.
        Replicated parameter mode only — ZeRO-1's flat-vector wire has
        no per-layer seam.

        The default ``"auto"`` selects the wire by itself whenever it
        applies SAFELY — replicated mode, the model input is a leading
        ``LookupTable``'s ids, no ``w_regularizer`` on it — and rides
        the ordinary dense path otherwise. Pass ``True`` to make the
        selection a CONTRACT (a model the wire cannot serve is a typed
        refusal instead of a silent fallback), ``False`` to force the
        dense wire off entirely."""
        super().__init__(model, training_set, criterion, optim_method,
                         end_trigger, batch_size)
        from ..parallel.mesh import get_default_mesh
        self.mesh = mesh or get_default_mesh()
        if "data" not in self.mesh.axis_names:
            raise ValueError("DistriOptimizer mesh needs a 'data' axis")
        if sparse_embedding is True and parameter_mode != "replicated":
            raise ValueError(
                "sparse_embedding selects per-LAYER gradient wires — "
                "ZeRO-1 ships one flat vector and has no per-layer "
                "seam; use parameter_mode='replicated'")
        self.parameter_mode = parameter_mode
        self.compress = compress
        self.wire_dtype = wire_dtype
        self.sparse_embedding = sparse_embedding
        self._arp = None
        self._flat = None

    def _num_shards(self):
        return self.mesh.shape["data"]

    def _to_host(self, tree):
        # ZeRO-1 opt state is sharded P('data') across processes in
        # multi-controller runs; np.asarray on non-addressable shards
        # raises. gather_to_host reshards to replicated first (collective
        # — checkpoint triggers fire symmetrically on every process).
        from ..parallel.sharding import gather_to_host
        return gather_to_host(tree, self.mesh)

    def _check_split_agreement(self):
        """Multi-controller: every process feeds its own data split; if
        the per-process batch counts differ, the extra steps on the larger
        split would block forever in the cross-process psum. Fail loudly
        at setup instead of deadlocking mid-epoch."""
        from ..parallel.sharding import is_multi_process
        if not is_multi_process(self.mesh):
            return
        import jax.numpy as jnp
        from jax.experimental import multihost_utils
        src = self._batched()
        n = getattr(src, "batches_per_epoch", 0)
        n = int(n() if callable(n) else (n or 0))
        counts = np.asarray(multihost_utils.process_allgather(
            jnp.asarray([n], jnp.int32))).reshape(-1)
        if len(set(counts.tolist())) > 1:
            raise ValueError(
                "per-process dataset splits disagree on batches/epoch "
                f"{counts.tolist()}; pad or trim the local splits so every "
                "process takes the same number of steps (uneven splits "
                "deadlock in the cross-process gradient psum)")

    def _place_batch(self, x, y):
        from ..parallel.sharding import shard_batch
        return (shard_batch(x, self.mesh), shard_batch(y, self.mesh))

    def _place_group(self, xs, ys):
        from ..parallel.sharding import shard_stacked_batch
        return (shard_stacked_batch(xs, self.mesh),
                shard_stacked_batch(ys, self.mesh))

    def _prepare(self, params, opt_state, mstate):
        from ..parallel.sharding import shard_params, put_global
        self._check_split_agreement()
        if self.parameter_mode == "zero1":
            from ..parallel.allreduce import AllReduceParameter
            self._arp = AllReduceParameter(
                self.optim_method, self.mesh, compress=self.compress,
                wire_dtype=getattr(self, "wire_dtype", "none"))
            # a loaded checkpoint's optimizer state is CANONICAL
            # (params-shaped, mesh-agnostic): prepare() re-flattens and
            # re-pads it against THIS mesh's shard boundaries, so the
            # same snapshot restores under any device count — the
            # elastic-restart contract. Without a loaded checkpoint the
            # state passed in is a fresh init for the wrong (tree)
            # layout; the sharded init replaces it.
            resume = opt_state \
                if getattr(self, "_resume_opt_state", None) is not None \
                else None
            flat_w, opt_state = self._arp.prepare(params,
                                                  resume_state=resume)
            self._flat = self._arp.flat
            mstate = shard_params(mstate, self.mesh)
            return put_global(flat_w, self.mesh, P()), opt_state, mstate
        params = shard_params(params, self.mesh)
        opt_state = shard_params(opt_state, self.mesh)
        mstate = shard_params(mstate, self.mesh)
        return params, opt_state, mstate

    def _collect(self, params, mstate, opt_state=None):
        if self.parameter_mode == "zero1":
            return self._flat.unflatten(jax.device_get(params)), mstate
        return params, mstate

    def _params_for_checkpoint(self, params):
        if self.parameter_mode == "zero1":
            return self._flat.unflatten(jax.device_get(params))
        return params

    def _opt_state_for_checkpoint(self, opt_state):
        if self.parameter_mode == "zero1" and self._arp is not None:
            # gather the sharded flat vectors, then unflatten to the
            # canonical params-shaped form — the checkpoint carries no
            # shard-boundary provenance (restores under any mesh shape)
            return self._arp.state_to_canonical(self._to_host(opt_state))
        return self._to_host(opt_state)

    def _restore_step_state(self, payload):
        from ..parallel.sharding import shard_params, put_global
        params = _tmap(jnp.asarray, payload["params"])
        mstate = shard_params(_tmap(jnp.asarray, payload["model_state"]),
                              self.mesh)
        if self.parameter_mode == "zero1" and self._arp is not None:
            # reuse the existing FlatParameter/AllReduceParameter — the
            # compiled step closes over them; only re-place the data.
            # The payload's optimizer state is canonical (params-shaped;
            # legacy flat vectors are re-padded too) — widen it back to
            # THIS mesh's flat shard layout before placing.
            flat_w = put_global(self._flat.flatten(params), self.mesh, P())
            opt_state = self._arp.place_canonical_state(
                payload["opt_state"])
            return flat_w, opt_state, mstate
        opt_state = _tmap(jnp.asarray, payload["opt_state"])
        return (shard_params(params, self.mesh),
                shard_params(opt_state, self.mesh), mstate)

    def _sparse_embedding_path(self):
        """Locate the embedding layer whose ids are the model input:
        the model itself, or the first child of a leading Sequential.
        Returns ``(param_path, vocab_size)`` — the gradient leaf at
        ``param_path`` is the one whose wire the per-layer selection
        may route sparse (its row ids are ``clip(input - 1, ...)``,
        the LookupTable's 1-based convention)."""
        from ..nn.linear import LookupTable
        m = self.model
        emb, path = None, None
        if isinstance(m, LookupTable):
            emb, path = m, ("weight",)
        else:
            mods = getattr(m, "modules", None)
            if mods and isinstance(mods[0], LookupTable):
                emb, path = mods[0], ("0", "weight")
        if emb is None:
            raise ValueError(
                "sparse_embedding=True needs the model input to BE the "
                "embedding ids: a LookupTable model, or a Sequential "
                "whose first child is a LookupTable — got "
                f"{type(m).__name__}")
        if emb.w_regularizer is not None:
            # weight decay's gradient is DENSE (lambda*w on every vocab
            # row); the (indices, values) exchange ships only the rows
            # this batch touched, so a regularized embedding would
            # silently train different weights than the dense wire
            raise ValueError(
                "sparse_embedding=True cannot ride a w_regularizer'd "
                "embedding: the regularizer gradient is dense over the "
                "whole vocab, which the sparse (indices, values) "
                "exchange cannot carry — drop the regularizer or the "
                "sparse wire")
        return path, emb.n_index

    def _sparse_embedding_enabled(self) -> bool:
        """Resolve the ``sparse_embedding`` knob into a build decision.
        ``True``/``False`` are explicit; ``"auto"`` picks the per-layer
        wire exactly when ``_sparse_embedding_path`` would accept the
        model under replicated mode, and falls back to the dense path
        otherwise — the typed refusals stay reserved for the explicit
        opt-in, where a silent fallback would hide a misconfiguration
        the caller paid to rule out."""
        se = self.sparse_embedding
        if se == "auto":
            if self.parameter_mode != "replicated":
                return False
            try:
                self._sparse_embedding_path()
            except ValueError:
                return False
            return True
        return bool(se)

    def _sparse_exchange(self):
        """The per-layer gradient-wire path (sparse_embedding=True):
        the exchange of an EXPLICIT shard_map data-parallel step —
        unlike the default replicated path (where XLA's sharding
        propagation inserts one implicit psum over all grads), each
        gradient leaf here picks its own wire at trace time. The
        embedding leaf ships ``(indices, value rows)`` via the Parallax
        exchange when that is fewer elements than its dense gradient;
        everything else rides ``pmean``. Trace-time byte counters
        (``collective/sparse_grad_wire_traced_bytes`` vs
        ``collective/grad_dense_traced_bytes``) make the win
        auditable per dispatch."""
        from ..nn.sparse import embedding_grad_rows
        from ..parallel.allreduce import sparse_embedding_grad_allreduce
        path, vocab = self._sparse_embedding_path()
        superstep_k = self.superstep

        @jax.named_scope("grad_exchange")
        def exchange(grads, x):
            ids = jnp.clip(x.reshape(-1).astype(jnp.int32) - 1, 0,
                           vocab - 1)
            picked = {"sparse": 0}

            def walk(tree, p=()):
                if isinstance(tree, dict):
                    return {k: walk(v, p + (k,)) for k, v in tree.items()}
                g = tree
                if p == path:
                    sparse_elems = ids.shape[0] * (g.shape[-1] + 1)
                    dense_elems = int(np.prod(g.shape))
                    if sparse_elems < dense_elems:
                        picked["sparse"] += 1
                        rows = embedding_grad_rows(g, ids)
                        return sparse_embedding_grad_allreduce(
                            ids, rows, vocab_size=vocab, axis="data",
                            traced_steps=superstep_k)
                if obs.enabled():
                    # trace-time: bytes this leaf ships on the dense wire
                    # sync-ok: static shape arithmetic, no device value
                    nbytes = float(g.size * g.dtype.itemsize) * superstep_k
                    obs.counter("collective/grad_dense_traced_bytes",
                                unit="B").inc(nbytes)
                return jax.lax.pmean(g, "data")

            out = walk(grads)
            if obs.enabled():
                obs.gauge("collective/sparse_layers_selected").set(
                    picked["sparse"])
            return out

        return exchange

    def _step_trace_context(self):
        # replicated mode's default step is a plain jit whose batch dim
        # XLA partitions over 'data'; Pallas kernels inside it must be
        # shard_mapped by their dispatcher (parallel/flash.py)
        from ..parallel.flash import data_parallel_context
        return data_parallel_context(self.mesh, "data")

    def _step_mode(self):
        """The two explicit modes differ from the replicated default in
        how the gradient is exchanged and who applies the update:
        ``zero1`` differentiates over the flat vector and exchanges
        INSIDE ``arp.update`` (sharded optimizer state); the sparse wire
        exchanges per leaf (:meth:`_sparse_exchange`) before the optim
        method's own update on replicated state."""
        loss_fn, frozen_mask, exchange, update, specs = super()._step_mode()
        if self.parameter_mode == "zero1":
            arp, flat, superstep_k = self._arp, self._flat, self.superstep
            if frozen_mask is not None:
                frozen_mask = flat.flatten(_tmap(
                    lambda p, m: jnp.full(jnp.shape(p), m, jnp.float32),
                    self.model.params, frozen_mask))
            tree_loss_fn = loss_fn

            def loss_fn(flat_w, mstate, x, y, rng):
                return tree_loss_fn(flat.unflatten(flat_w), mstate, x, y,
                                    rng)

            def update(gflat, flat_w, opt_slice, lr):
                return arp.update(gflat, flat_w, opt_slice, lr,
                                  traced_steps=superstep_k)

            specs = (P(), arp.state_specs(),
                     _tmap(lambda _: P(), self.model.state))
        elif self._sparse_embedding_enabled():
            exchange, specs = self._sparse_exchange(), (P(), P(), P())
        return loss_fn, frozen_mask, exchange, update, specs


class ParallelOptimizer(DistriOptimizer):
    """Name parity: optim/ParallelOptimizer.scala — the reference's
    layer-wise-parallel gradient aggregation variant. Under XLA the jitted
    step already aggregates all gradients in one fused program, so this is
    the same engine as DistriOptimizer."""


class Optimizer(BaseOptimizer):
    """Factory with the reference's signature (optim/Optimizer.scala apply):
    picks Local vs Distri from the engine mesh size."""

    def __new__(cls, model=None, training_set=None, training_rdd=None,
                criterion=None, optim_method=None, end_trigger=None,
                batch_size: int = 32, mesh=None, **kw):
        training = training_set if training_set is not None else training_rdd
        from ..parallel.mesh import get_default_mesh
        m = mesh or (get_default_mesh() if len(jax.devices()) > 1 else None)
        if m is not None and m.size > 1:
            return DistriOptimizer(model, training, criterion, optim_method,
                                   end_trigger, batch_size, mesh=m, **kw)
        obj = object.__new__(LocalOptimizer)
        obj.__init__(model, training, criterion, optim_method, end_trigger,
                     batch_size)
        return obj

    @staticmethod
    def create(model, training_set, criterion, end_trigger=None,
               batch_size=32, optim_method=None, cores=None,
               bigdl_type="float"):
        """pyspark ``Optimizer.create`` spelling (the ``cores``/
        ``bigdl_type`` args are JVM-era and ignored; local-vs-distributed
        is picked from the engine mesh like the constructor)."""
        return Optimizer(model=model, training_set=training_set,
                         criterion=criterion, optim_method=optim_method,
                         end_trigger=end_trigger, batch_size=batch_size)
