"""Runtime engine: device discovery, mesh construction, seeds.

Parity: reference ``utils/Engine.scala`` — there it configures Spark executor
cores/nodes and the MKL thread pools. On TPU the analog is device/mesh
management: how many chips, what logical mesh axes (data/model/seq), and the
host-side PRNG. XLA owns intra-chip parallelism, so there is no thread-pool
knob to tune; ``Engine.init`` instead fixes the mesh every distributed
component uses.
"""
from __future__ import annotations

import logging
import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np

logger = logging.getLogger("bigdl_tpu")

_state = {
    "initialized": False,
    "mesh": None,
    "seed": None,
    "rng_key": None,
    "node_number": 1,
    "core_number": 1,
    "engine_type": "xla",
    "compile_cache_dir": None,
    "cache_listener": False,
}


def init(node_number: int = 1,
         core_number: Optional[int] = None,
         mesh_shape: Optional[Sequence[int]] = None,
         mesh_axes: Sequence[str] = ("data",),
         seed: int = 42,
         devices=None):
    """Initialise the engine (parity: Engine.init, utils/Engine.scala:106).

    ``mesh_shape``/``mesh_axes`` define the logical device mesh. Default is a
    1-D ``data`` mesh over every visible device. Multi-host initialisation
    (jax.distributed) must happen before calling this.
    """
    devices = list(devices if devices is not None else jax.devices())
    if core_number is None:
        core_number = len(devices)
    if mesh_shape is None:
        mesh_shape = (len(devices),)
    dev_arr = np.array(devices[: int(np.prod(mesh_shape))]).reshape(mesh_shape)
    mesh = jax.sharding.Mesh(dev_arr, tuple(mesh_axes))
    _state.update(initialized=True, mesh=mesh, seed=seed,
                  rng_key=jax.random.PRNGKey(seed),
                  node_number=node_number, core_number=core_number)
    maybe_enable_compilation_cache()
    return mesh


def is_initialized() -> bool:
    return _state["initialized"]


def get_mesh() -> jax.sharding.Mesh:
    if _state["mesh"] is None:
        init()
    return _state["mesh"]


def set_seed(seed: int):
    _state["seed"] = seed
    _state["rng_key"] = jax.random.PRNGKey(seed)


def get_seed():
    return _state["seed"]


def next_rng_key():
    """Split and return a fresh PRNG key from the global stream."""
    if _state["rng_key"] is None:
        set_seed(42 if _state["seed"] is None else _state["seed"])
    _state["rng_key"], sub = jax.random.split(_state["rng_key"])
    return sub


def _split_many(key, k):
    """k chained splits in one compiled program; returns (chain, [k] subs)."""
    return jax.lax.scan(lambda c, _: tuple(jax.random.split(c)), key,
                        None, length=k)


_split_many_jit = None


def next_rng_keys(k: int):
    """``k`` fresh keys from the global stream, stacked ``[k, ...]``, in
    ONE dispatch — bitwise the keys ``k`` successive :func:`next_rng_key`
    calls would return (each split depends only on its input key, so the
    scanned chain reproduces the sequential chain exactly). The superstep
    loop uses this so per-dispatch host work stays O(1) in K."""
    if _state["rng_key"] is None:
        set_seed(42 if _state["seed"] is None else _state["seed"])
    global _split_many_jit
    if _split_many_jit is None:
        _split_many_jit = jax.jit(_split_many, static_argnums=1)
    _state["rng_key"], subs = _split_many_jit(_state["rng_key"], int(k))
    return subs


def node_number() -> int:
    return _state["node_number"]


def core_number() -> int:
    return _state["core_number"]


def engine_type() -> str:
    return _state["engine_type"]


def device_count() -> int:
    return len(jax.devices())


def default_dtype():
    return np.float32


#: where the persistent compile cache lives when the environment does not
#: say: ``<checkout>/.jax_cache`` (git-ignored), resolved from this file's
#: own location. The path is part of JAX's cache key, so it must be the
#: same on every run of the same checkout — never ``~``, a temp name, a
#: pid or a time.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache(min_compile_time_secs: float = 0.0):
    """Turn on JAX's persistent compilation cache — the ONE placement rule.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
    cache and nothing in code names another (there is deliberately no
    directory argument). Where it is not set, the cache is
    ``<checkout>/.jax_cache``. Safe to call more than once.

    ``min_compile_time_secs`` defaults to 0: a serving warm-up compiles
    many small decode-bucket programs, and any of them left out of the
    cache is a compile a restarted server pays again.
    """
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or _CHECKOUT_CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    # jax reads the variable itself at import; the update only matters
    # when it is unset (our default) or was set after jax was imported
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    _state["compile_cache_dir"] = cache_dir
    _register_cache_events()
    return cache_dir


def maybe_enable_compilation_cache():
    """Idempotent, env-gated cache enable — the lazy entry point every
    compile site (``Optimizer._build_step``, ``Evaluator``/``Predictor``
    forward builds, ``DecodeScheduler``/``ServingEngine`` construction)
    calls before jitting, so a restarted process skips straight to
    execution. ``BIGDL_TPU_COMPILE_CACHE=0`` opts out; the location is
    :func:`enable_compilation_cache`'s rule."""
    if _state["compile_cache_dir"]:
        return _state["compile_cache_dir"]
    if os.environ.get("BIGDL_TPU_COMPILE_CACHE", "1").lower() in (
            "0", "false", "off"):
        return None
    try:
        return enable_compilation_cache()
    except (OSError, ValueError) as e:  # unwritable dir must not stop training
        logger.warning("persistent compilation cache unavailable: %s", e)
        return None


def compilation_cache_dir():
    """The active persistent-cache directory, or None when disabled."""
    return _state["compile_cache_dir"]


def compilation_cache_stats() -> dict:
    """One-call provenance snapshot of the persistent compile cache —
    what the perf-introspection reports embed next to each program's
    hit/miss deltas."""
    from .. import observability as obs
    reg = obs.registry()
    return {
        "dir": _state["compile_cache_dir"],
        "entries": compilation_cache_entries(),
        "hits": int(reg.counter("engine/compile_cache_hits").value),
        "misses": int(reg.counter("engine/compile_cache_misses").value),
    }


def compilation_cache_entries() -> int:
    """Number of compiled executables in the persistent cache (0 when
    disabled) — exported as the ``engine/compile_cache_entries`` gauge."""
    d = _state["compile_cache_dir"]
    if not d or not os.path.isdir(d):
        return 0
    try:
        return sum(1 for f in os.listdir(d) if not f.startswith("."))
    except OSError:
        return 0


def _register_cache_events():
    """Bridge jax's compilation-cache monitoring events into the
    observability registry: ``engine/compile_cache_hits`` /
    ``engine/compile_cache_misses`` counters (a hit means a ``jit``
    skipped XLA compilation entirely — the cross-process win the
    persistent cache exists for)."""
    if _state["cache_listener"]:
        return
    from jax import monitoring
    from .. import observability as obs
    names = {
        "/jax/compilation_cache/cache_hits": "engine/compile_cache_hits",
        "/jax/compilation_cache/cache_misses": "engine/compile_cache_misses",
    }

    def _on_event(event, **kw):
        name = names.get(event)
        if name is not None and obs.enabled():
            obs.counter(name).inc()

    monitoring.register_event_listener(_on_event)
    _state["cache_listener"] = True


class RandomGenerator:
    """Parity: utils/RandomGenerator.scala — thin facade over the engine PRNG."""

    @staticmethod
    def set_seed(seed):
        set_seed(seed)
        np.random.seed(seed & 0x7FFFFFFF)

    @staticmethod
    def uniform(lo, hi, shape=()):
        return jax.random.uniform(next_rng_key(), shape, minval=lo, maxval=hi)

    @staticmethod
    def normal(mean, std, shape=()):
        return mean + std * jax.random.normal(next_rng_key(), shape)
