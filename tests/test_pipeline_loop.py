"""Pipelined training loop (PR 2): stager equivalence, windowed loss
sync, NaN semantics under lag, thread hygiene, and the data_fetch
collapse acceptance criterion."""
import threading
import time

import numpy as np
import pytest

from bigdl_tpu import nn, observability as obs
from bigdl_tpu.dataset import DataSet, mnist
from bigdl_tpu.dataset.minibatch import MiniBatch
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.models import LeNet5
from bigdl_tpu.optim import (LocalOptimizer, SGD, max_iteration, max_epoch,
                             several_iteration, Top1Accuracy)
from bigdl_tpu.optim.staging import (BatchStager, staged,
                                     stager_threads_alive)
from bigdl_tpu.utils import engine


def _flat(tree):
    import jax
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


def _trees_equal(a, b):
    return all(np.array_equal(x, y) for x, y in zip(_flat(a), _flat(b)))


# ---------------------------------------------------------------------------
# equivalence: the staged loop must be bitwise-identical to the serial one
# ---------------------------------------------------------------------------

def _train_lenet(policy, depth, tmp_path, tag):
    """LeNet/MNIST run returning (params, final checkpoint payload)."""
    import pickle, os
    engine.set_seed(11)
    imgs, labels = mnist.load(n_synthetic=128)
    ds = DataSet.array(mnist.to_samples(imgs, labels))
    model = LeNet5(10)
    steps = 8
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(),
                         SGD(learningrate=0.05, momentum=0.9),
                         max_iteration(steps), batch_size=32)
    ckpt_dir = str(tmp_path / tag)
    opt.set_checkpoint(several_iteration(steps), ckpt_dir)
    opt.set_sync_policy(policy)
    opt.set_prefetch(depth)
    opt.optimize()
    with open(os.path.join(ckpt_dir, "checkpoint.bigdl"), "rb") as f:
        payload = pickle.load(f)
    return model.params, payload


def test_pipelined_loop_bitwise_equivalent(tmp_path):
    """Identical final params AND opt_state vs the serial loop across
    sync policies — the stager/window change WHEN the host observes,
    never what the device computes."""
    ref_params, ref_ckpt = _train_lenet("sync", 0, tmp_path, "serial")
    for i, (policy, depth) in enumerate([("sync", 3), ("async", 3),
                                         ("window:3", 3), ("window:1", 2)]):
        params, ckpt = _train_lenet(policy, depth, tmp_path, f"cfg{i}")
        assert _trees_equal(ref_params, params), (policy, depth)
        assert _trees_equal(ref_ckpt["params"], ckpt["params"]), (policy,
                                                                  depth)
        assert _trees_equal(ref_ckpt["opt_state"], ckpt["opt_state"]), \
            (policy, depth)
    assert stager_threads_alive() == 0


def test_window_policy_validation():
    opt = LocalOptimizer(nn.Linear(2, 1), DataSet.from_arrays(
        np.zeros((4, 2), np.float32), np.zeros((4, 1), np.float32)),
        nn.MSECriterion(), SGD(), max_iteration(1), 2)
    opt.set_sync_policy("window:4")
    assert opt._window_k() == 4
    with pytest.raises(ValueError):
        opt.set_sync_policy("window:0")
    with pytest.raises(ValueError):
        opt.set_sync_policy("window:x")
    with pytest.raises(ValueError):
        opt.set_prefetch(-1)


# ---------------------------------------------------------------------------
# NaN policy semantics under a windowed (lagged) sync
# ---------------------------------------------------------------------------

def _poisoned_dataset(n=64, dim=4, bad=1):
    """Linear-regression samples with `bad` NaN features — exactly one
    poisoned batch per epoch, every other step finite."""
    rng = np.random.RandomState(0)
    xs = rng.randn(n, dim).astype(np.float32)
    ys = (xs @ rng.randn(dim, 1)).astype(np.float32)
    xs[:bad] = np.nan
    return DataSet.array([Sample(x, y) for x, y in zip(xs, ys)])


def test_window_nan_skip_recovers():
    """nan_policy='skip' under window:4: the poisoned batch is observed
    K-1 steps late, counted as a skip, and training still converges to
    finite params (the in-step guard held them safe meanwhile)."""
    ds = _poisoned_dataset()
    m = nn.Linear(4, 1)
    opt = LocalOptimizer(m, ds, nn.MSECriterion(), SGD(learningrate=0.05),
                         max_epoch(3), batch_size=16)
    opt.set_sync_policy("window:4").set_prefetch(3)
    opt.set_nan_policy("skip")
    opt.optimize()
    assert opt.metrics.mean("nan_skips") == 1.0
    assert len(opt.metrics.values["nan_skips"]) >= 1
    assert all(np.isfinite(l).all() for l in _flat(m.params))
    assert np.isfinite(opt.optim_method.state["loss"])
    assert stager_threads_alive() == 0


def test_window_nan_resume_replays_checkpoint(tmp_path):
    """nan_policy='resume' under window:3 replays from the checkpoint
    exactly like the sync loop: in-flight window cleared, counters
    rolled back to the snapshot, run completes finite."""
    ds = _poisoned_dataset()
    m = nn.Linear(4, 1)
    opt = LocalOptimizer(m, ds, nn.MSECriterion(), SGD(learningrate=0.05),
                         max_epoch(2), batch_size=16)
    opt.set_checkpoint(several_iteration(1), str(tmp_path))
    opt.set_sync_policy("window:3").set_prefetch(2)
    opt.set_nan_policy("resume")
    opt.optimize()
    assert len(opt.metrics.values["nan_resumes"]) >= 1
    assert len(opt._loss_window) == 0  # cleared on restore and drained
    assert all(np.isfinite(l).all() for l in _flat(m.params))
    assert stager_threads_alive() == 0


def test_window_nan_on_final_steps_not_swallowed():
    """A NaN still in flight when the loop ends (window larger than the
    remaining steps) must surface in the end-of-run drain."""
    rng = np.random.RandomState(0)
    xs = (rng.randn(32, 4) * 100).astype(np.float32)
    ys = (rng.randn(32, 1) * 100).astype(np.float32)
    ds = DataSet.array([Sample(x, y) for x, y in zip(xs, ys)])
    m = nn.Sequential(nn.Linear(4, 8), nn.Linear(8, 1))
    opt = LocalOptimizer(m, ds, nn.MSECriterion(), SGD(learningrate=1e12),
                         max_epoch(1), batch_size=16)  # 2 steps, window 4
    opt.set_sync_policy("window:4").set_prefetch(2)
    with pytest.raises(FloatingPointError):
        opt.optimize()
    assert stager_threads_alive() == 0


# ---------------------------------------------------------------------------
# stager hygiene: shutdown, error transparency, order
# ---------------------------------------------------------------------------

def test_stager_no_thread_leak_on_error_paths():
    """Every optimize() exit — including a FloatingPointError mid-epoch —
    joins the stager thread (asserted over threading.enumerate())."""
    before = {t.ident for t in threading.enumerate()}
    rng = np.random.RandomState(0)
    xs = rng.randn(64, 4).astype(np.float32)
    ds = DataSet.array([Sample(x, x[:1]) for x in xs])
    opt = LocalOptimizer(nn.Linear(4, 1), ds, nn.MSECriterion(),
                         SGD(learningrate=1e20), max_iteration(5), 32)
    opt.set_prefetch(4)
    with pytest.raises(FloatingPointError):
        opt.optimize()
    assert stager_threads_alive() == 0
    leaked = [t for t in threading.enumerate()
              if t.ident not in before and t.name.startswith("bigdl_tpu")]
    assert leaked == []


def test_stager_propagates_source_errors():
    class Exploding:
        def __iter__(self):
            yield from range(3)
            raise ValueError("decode failed")

    st = BatchStager(Exploding(), lambda v: v * 2, depth=2)
    got = []
    with pytest.raises(ValueError, match="decode failed"):
        for v in st:
            got.append(v)
    assert got == [0, 2, 4]  # order preserved up to the failure
    st.close()
    assert stager_threads_alive() == 0


def test_stager_close_mid_stream_and_serial_fallback():
    st = staged(iter(range(100)), lambda v: v + 1, depth=3)
    assert next(st) == 1
    st.close()  # early shutdown: no hang, no leak
    assert stager_threads_alive() == 0
    # depth 0/1 never spawns a thread but keeps the same surface
    ser = staged(iter(range(3)), lambda v: v + 1, depth=1)
    assert list(ser) == [1, 2, 3]
    ser.close()
    assert stager_threads_alive() == 0


def test_evaluator_predictor_staged_paths():
    from bigdl_tpu.optim.evaluator import Evaluator
    from bigdl_tpu.optim.predictor import Predictor
    imgs, labels = mnist.load(n_synthetic=64)
    ds = DataSet.array(mnist.to_samples(imgs, labels))
    model = LeNet5(10)
    model.ensure_initialized()
    res = Evaluator(model, prefetch_depth=3).evaluate(
        ds, [Top1Accuracy()], batch_size=16)
    acc, n = res[0].result()
    assert n == 64
    preds = Predictor(model, prefetch_depth=3).predict(ds, batch_size=16)
    assert preds.shape[0] == 64
    assert stager_threads_alive() == 0


# ---------------------------------------------------------------------------
# acceptance: data_fetch collapses to a queue pop with the stager on
# ---------------------------------------------------------------------------

class _SlowBatches:
    """Batch-level dataset with a fixed per-batch produce delay — a
    stand-in for host-side decode (the realdata JPEG path)."""

    def __init__(self, n_batches, batch, dim, delay):
        rng = np.random.RandomState(0)
        self.xs = [rng.randn(batch, dim).astype(np.float32)
                   for _ in range(n_batches)]
        self.ys = [rng.randn(batch, dim).astype(np.float32)
                   for _ in range(n_batches)]
        self.n_batches, self.batch, self.delay = n_batches, batch, delay

    def size(self):
        return self.n_batches * self.batch

    def batches_per_epoch(self):
        return self.n_batches

    def shuffle(self):
        return self

    def data(self, train=True):
        for x, y in zip(self.xs, self.ys):
            time.sleep(self.delay)
            yield MiniBatch(x, y)


def _mean_fetch_seconds(depth):
    obs.enable()
    obs.reset()
    obs.registry().reset()
    try:
        ds = _SlowBatches(12, 256, 2048, 0.02)
        m = nn.Linear(2048, 2048)  # step compute >> produce delay
        opt = LocalOptimizer(m, ds, nn.MSECriterion(), SGD(learningrate=0.01),
                             max_epoch(1), batch_size=256)
        opt.set_prefetch(depth)
        opt.optimize()
        spans = [s for s in obs.get_tracer().events()
                 if s.name == "step/data_fetch"]
        # 12 real fetches + the exhaustion probe (StopIteration) — drop it
        assert len(spans) == 13
        spans = spans[:-1]
        return sum(s.duration_ns for s in spans) / len(spans) / 1e9
    finally:
        obs.disable()
        obs.reset()
        obs.registry().reset()


def test_stager_collapses_data_fetch_5x():
    """ISSUE 2 acceptance: with the stager (depth >= 2), mean
    step/data_fetch drops >= 5x vs the serial loop when produce time
    overlaps device compute."""
    serial = _mean_fetch_seconds(0)
    staged_t = _mean_fetch_seconds(4)
    assert serial >= 0.02  # sanity: serial pays the produce delay
    assert serial / staged_t >= 5.0, (serial, staged_t)
    assert stager_threads_alive() == 0


# ---------------------------------------------------------------------------
# persistent compile cache wiring
# ---------------------------------------------------------------------------

def test_compile_cache_env_gate_and_entries(tmp_path, monkeypatch,
                                            restore_jax_cache_config):
    monkeypatch.setenv("BIGDL_TPU_COMPILE_CACHE", "0")
    engine._state["compile_cache_dir"] = None
    assert engine.maybe_enable_compilation_cache() is None
    assert engine.compilation_cache_entries() == 0
    monkeypatch.setenv("BIGDL_TPU_COMPILE_CACHE", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    d = engine.maybe_enable_compilation_cache()
    assert d == str(tmp_path)
    assert engine.compilation_cache_dir() == str(tmp_path)
    # idempotent: the second call returns the same dir without re-init
    assert engine.maybe_enable_compilation_cache() == str(tmp_path)
    assert engine.compilation_cache_entries() == 0
    (tmp_path / "a_compiled_executable").write_bytes(b"x")
    assert engine.compilation_cache_entries() == 1
