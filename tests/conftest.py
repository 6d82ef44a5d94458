"""Test config: force CPU backend with 8 virtual devices so distributed
(mesh/shard_map) paths are exercised without TPU hardware.

The persistent compile cache is off for the suite (tests of the cache
wiring turn it on into a temp dir): its default home is
``<checkout>/.jax_cache``, and the chip tool copies the tree as it
stands on disk — a CPU suite must not fill it.
"""
import os

os.environ.setdefault("BIGDL_TPU_COMPILE_CACHE", "0")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = \
        _flags + " --xla_force_host_platform_device_count=8"

import jax

jax.config.update("jax_platforms", "cpu")
assert jax.default_backend() == "cpu", "tests must run on CPU"

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy full-size checks (big-model forwards, real-TF "
        "cross-validation). Skipped by default to keep `make test` inside "
        "the verification budget; run with BIGDL_TPU_SLOW=1 or -m slow. "
        "Every component keeps an unmarked smoke-size test.")


def pytest_collection_modifyitems(config, items):
    if os.environ.get("BIGDL_TPU_SLOW") == "1":
        return
    if "slow" in (config.getoption("-m") or ""):
        return
    skip = pytest.mark.skip(
        reason="slow: opt in with BIGDL_TPU_SLOW=1 or -m slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    from bigdl_tpu.utils import engine
    engine.set_seed(42)
    yield


@pytest.fixture()
def restore_jax_cache_config():
    """The compile-cache dir and threshold are process-global jax config:
    put them (and the engine's record of them) back, so later tests don't
    write their programs into a temp dir."""
    from bigdl_tpu.utils import engine
    prior = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs,
             engine._state["compile_cache_dir"])
    yield
    jax.config.update("jax_compilation_cache_dir", prior[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prior[1])
    engine._state["compile_cache_dir"] = prior[2]
