"""Multihost stager coverage (ROADMAP open item #2): the BatchStager's
per-process lookahead + the ``_check_split_agreement`` guard, exercised
under (a) a mocked multi-process mesh for the uneven-split failure path
and (b) a REAL 2-process ``jax.distributed`` rendezvous training with
prefetch and superstep groups on per-process data splits.

Separate file from test_multihost*.py so pytest-xdist loadfile sharding
overlaps the subprocess rendezvous with other workers."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet, mnist
from bigdl_tpu.models import LeNet5
from bigdl_tpu.optim import DistriOptimizer, SGD, MaxIteration
from bigdl_tpu.utils import engine

from multihost_util import _free_port, skip_if_backend_unsupported


def test_uneven_split_agreement_raises(monkeypatch):
    """Per-process batch counts that disagree must fail loudly at setup
    (the extra steps on the larger split would deadlock in the
    cross-process psum) — simulated 2-process mesh: this process reports
    4 batches/epoch, the allgather claims the peer reports 3."""
    from jax.sharding import Mesh
    from bigdl_tpu.parallel import sharding
    from jax.experimental import multihost_utils

    engine.set_seed(1)
    imgs, labels = mnist.load(n_synthetic=64)
    ds = DataSet.array(mnist.to_samples(imgs, labels))
    mesh = Mesh(np.array(jax.devices()), ("data",))
    opt = DistriOptimizer(LeNet5(10), ds, nn.ClassNLLCriterion(),
                          SGD(learningrate=0.01), MaxIteration(1),
                          batch_size=16, mesh=mesh)
    monkeypatch.setattr(sharding, "is_multi_process", lambda m: True)
    monkeypatch.setattr(multihost_utils, "process_allgather",
                        lambda x: np.asarray([[4], [3]], np.int32))
    with pytest.raises(ValueError, match="disagree on batches/epoch"):
        opt._check_split_agreement()


def test_even_split_agreement_passes(monkeypatch):
    """Matching per-process counts pass the guard (the mocked allgather
    echoes this process's count for both peers)."""
    from jax.sharding import Mesh
    from bigdl_tpu.parallel import sharding
    from jax.experimental import multihost_utils

    engine.set_seed(1)
    imgs, labels = mnist.load(n_synthetic=64)
    ds = DataSet.array(mnist.to_samples(imgs, labels))
    mesh = Mesh(np.array(jax.devices()), ("data",))
    opt = DistriOptimizer(LeNet5(10), ds, nn.ClassNLLCriterion(),
                          SGD(learningrate=0.01), MaxIteration(1),
                          batch_size=16, mesh=mesh)
    n = opt._batched().batches_per_epoch()
    monkeypatch.setattr(sharding, "is_multi_process", lambda m: True)
    monkeypatch.setattr(multihost_utils, "process_allgather",
                        lambda x: np.asarray([[n], [n]], np.int32))
    opt._check_split_agreement()  # no raise


_STAGER_DRIVER = r"""
import os, sys
pid, n, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
dp = 8 // n
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={dp}"
import jax
jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=n,
                           process_id=pid)
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.experimental import multihost_utils

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet, mnist
from bigdl_tpu.models import LeNet5
from bigdl_tpu.optim import DistriOptimizer, SGD, MaxIteration
from bigdl_tpu.optim.staging import stager_threads_alive

mesh = Mesh(np.array(jax.devices()), ("data",))
imgs, labels = mnist.load(n_synthetic=64)
per = 64 // n   # each controller feeds a DIFFERENT slice of the data
imgs = imgs[pid * per:(pid + 1) * per]
labels = labels[pid * per:(pid + 1) * per]

# (a) per-process lookahead stager feeding cross-process training
ds = DataSet.array(mnist.to_samples(imgs, labels))
opt = DistriOptimizer(LeNet5(10), ds, nn.ClassNLLCriterion(),
                      SGD(learningrate=0.01), MaxIteration(3),
                      batch_size=8, mesh=mesh)
opt.set_prefetch(3)
opt.optimize()
loss = float(opt.optim_method.state["loss"])
assert np.isfinite(loss), loss
agreed = multihost_utils.process_allgather(jnp.asarray([loss]))
assert np.allclose(np.asarray(agreed).reshape(-1), loss), agreed
assert stager_threads_alive() == 0

# (b) superstep groups over the same per-process splits: the stacking
# stage runs on each process's stager thread; the scanned program psums
# across the process boundary every microstep
ds2 = DataSet.array(mnist.to_samples(imgs, labels))
opt2 = DistriOptimizer(LeNet5(10), ds2, nn.ClassNLLCriterion(),
                       SGD(learningrate=0.01), MaxIteration(4),
                       batch_size=8, mesh=mesh)
opt2.set_prefetch(3).set_superstep(2)
opt2.optimize()
loss2 = float(opt2.optim_method.state["loss"])
assert np.isfinite(loss2), loss2
assert opt2.optim_method.state["neval"] == 4
agreed2 = multihost_utils.process_allgather(jnp.asarray([loss2]))
assert np.allclose(np.asarray(agreed2).reshape(-1), loss2), agreed2
assert stager_threads_alive() == 0

print(f"MULTIHOST_STAGER_OK_{pid}")
"""


@pytest.mark.parametrize("n", [2])
def test_multi_process_stager_and_superstep(n):
    try:
        port = _free_port()
    except OSError:
        pytest.skip("no localhost sockets in this sandbox")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # driver sets its own device count
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _STAGER_DRIVER, str(pid), str(n), str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for pid in range(n)]
    outs = []
    for pid, proc in enumerate(procs):
        try:
            out, err = proc.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for p2 in procs:
                p2.kill()
            raise
        outs.append((pid, proc.returncode, out, err))
    skip_if_backend_unsupported(outs)
    for pid, rc, out, err in outs:
        assert rc == 0, f"process {pid} failed:\n{err[-3000:]}"
        assert f"MULTIHOST_STAGER_OK_{pid}" in out
