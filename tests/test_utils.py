"""Utils tests: Table, Shape, DirectedGraph, File, Engine, misc."""
import os

import numpy as np
import jax
import pytest

from bigdl_tpu.utils import (Table, T, Shape, SingleShape, MultiShape,
                             DirectedGraph, GraphNode, Edge, File, ThreadPool,
                             crc32, string_hash, engine)


def test_table_pytree():
    t = T(np.ones(3), np.zeros(2))
    assert t[1].shape == (3,)
    assert len(t) == 2
    leaves = jax.tree_util.tree_leaves(t)
    assert len(leaves) == 2
    mapped = jax.tree_util.tree_map(lambda x: x + 1, t)
    assert isinstance(mapped, Table)
    assert np.allclose(mapped[1], 2.0)
    # nested
    nested = T(T(np.ones(1)), np.zeros(1))
    assert len(jax.tree_util.tree_leaves(nested)) == 2


def test_table_insert_set():
    t = Table()
    t.insert(5)
    t[3] = 7
    assert t[1] == 5 and t[3] == 7 and t[2] is None
    assert t.length() == 3


def test_shape():
    s = Shape.of(3, 4)
    assert isinstance(s, SingleShape)
    assert s.to_single() == [3, 4]
    m = Shape.of(Shape.of(1), Shape.of(2, 3))
    assert isinstance(m, MultiShape)
    assert len(m.to_multi()) == 2


def test_directed_graph():
    a, b, c, d = (GraphNode(x) for x in "abcd")
    a.add(b)
    a.add(c)
    b.add(d)
    c.add(d)
    g = DirectedGraph(a)
    topo = [n.element for n in g.topology_sort()]
    assert topo.index("a") < topo.index("b") < topo.index("d")
    assert topo.index("a") < topo.index("c") < topo.index("d")
    assert g.size() == 4
    bfs = [n.element for n in g.bfs()]
    assert bfs[0] == "a" and set(bfs) == set("abcd")
    dfs = [n.element for n in g.dfs()]
    assert dfs[0] == "a"
    # cycle detection
    d.add(a)
    with pytest.raises(ValueError):
        DirectedGraph(a).topology_sort()


def test_file_roundtrip(tmp_path):
    p = str(tmp_path / "obj.bin")
    File.save({"a": np.ones(3)}, p)
    obj = File.load(p)
    assert np.allclose(obj["a"], 1.0)
    with pytest.raises(IOError):
        File.save({}, p, overwrite=False)


def test_thread_pool():
    tp = ThreadPool(4)
    out = tp.invoke_and_wait([lambda i=i: i * i for i in range(8)])
    assert out == [i * i for i in range(8)]
    tp.shutdown()


def test_hash_utils():
    assert crc32(b"hello") == crc32(b"hello")
    assert crc32(b"hello") != crc32(b"world")
    assert string_hash("x") != string_hash("y")


def test_engine_mesh():
    mesh = engine.init(mesh_shape=(4, 2), mesh_axes=("data", "model"))
    assert mesh.shape["data"] == 4
    assert mesh.shape["model"] == 2
    assert engine.get_mesh() is mesh
    k1 = engine.next_rng_key()
    k2 = engine.next_rng_key()
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    engine.init()  # restore default 1-axis mesh for other tests


def test_device_memory_stats():
    from bigdl_tpu.utils import device_memory_stats
    stats = device_memory_stats()
    assert len(stats) == 8


def test_compilation_cache_follows_the_environment(
        tmp_path, monkeypatch, restore_jax_cache_config):
    """JAX_COMPILATION_CACHE_DIR set -> that directory and no other."""
    env_dir = str(tmp_path / "env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    assert engine.enable_compilation_cache() == env_dir
    assert os.path.isdir(env_dir)
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert engine.compilation_cache_dir() == env_dir
    # every program is worth keeping: a serving warm-up's small bucket
    # programs must not fall under a minimum-compile-time threshold
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compilation_cache_defaults_to_the_checkout(
        monkeypatch, restore_jax_cache_config):
    """Unset -> <checkout>/.jax_cache, resolved from the package's own
    location (never ~, a temp name, a pid or a time)."""
    import bigdl_tpu
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(bigdl_tpu.__file__)))
    want = os.path.join(checkout, ".jax_cache")
    existed = os.path.isdir(want)
    try:
        assert engine.enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        if not existed and os.path.isdir(want) and not os.listdir(want):
            os.rmdir(want)


def test_no_argument_can_override_the_cache_variable(
        tmp_path, monkeypatch, restore_jax_cache_config):
    """There is deliberately no directory argument: the variable (or the
    checkout default) is the only way to place the cache."""
    import inspect
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert list(inspect.signature(
        engine.enable_compilation_cache).parameters) == [
            "min_compile_time_secs"]
    with pytest.raises(TypeError):
        engine.enable_compilation_cache(cache_dir=str(tmp_path / "arg"))
    assert not (tmp_path / "arg").exists()
