"""Per-layer bigdl.proto round-trip sweep — every public Module class in
``bigdl_tpu.nn`` must save→load through the protobuf serializer with its
type, config, and param/state trees intact.

Parity: the reference exercises exactly this with a reflection-default
serializer plus a per-layer SerializerSpec sweep
(``utils/serializer/ModuleSerializer.scala:199``); this is the bigdl_tpu
equivalent. Classes with required ctor args get an instance factory below;
zero-arg classes are auto-instantiated. The coverage assertion at the bottom
guarantees no newly-added class silently escapes the sweep.
"""
import inspect
import os

import jax
import numpy as np
import pytest

import bigdl_tpu.nn as N
from bigdl_tpu.nn.module import Module
from bigdl_tpu.loaders.bigdl_proto import save_bigdl, load_bigdl

# abstract bases / machinery that users never instantiate directly
EXEMPT = {
    "Module", "Container", "Cell", "Layer", "TableOperation",
}


def _graph(cls):
    inp = N.Input()
    h = N.Linear(6, 5)(inp)
    out = N.ReLU()(h)
    return cls(inp, out)


# instance factories for classes whose ctor has required args
SPECS = {
    "Add": lambda: N.Add(6),
    "AddConstant": lambda: N.AddConstant(1.5),
    "Attention": lambda: N.Attention(8, 2),
    "BatchNormalization": lambda: N.BatchNormalization(6),
    "BifurcateSplitTable": lambda: N.BifurcateSplitTable(1),
    "Bilinear": lambda: N.Bilinear(4, 5, 3),
    "BinaryTreeLSTM": lambda: N.BinaryTreeLSTM(6, 5),
    "Bottle": lambda: N.Bottle(N.Linear(4, 3)),
    "CAdd": lambda: N.CAdd((6,)),
    "CMul": lambda: N.CMul((6,)),
    "Clamp": lambda: N.Clamp(-1.0, 1.0),
    "Concat": lambda: N.Concat(1, N.Linear(4, 3), N.Linear(4, 2)),
    "ConvLSTMPeephole": lambda: N.ConvLSTMPeephole(3, 4),
    "ConvLSTMPeephole3D": lambda: N.ConvLSTMPeephole3D(3, 4),
    "Cosine": lambda: N.Cosine(4, 3),
    "DynamicGraph": lambda: _graph(N.DynamicGraph),
    "Euclidean": lambda: N.Euclidean(4, 3),
    "ExpandSize": lambda: N.ExpandSize([2, 6]),
    "FeedForwardNetwork": lambda: N.FeedForwardNetwork(8, 16),
    "GRU": lambda: N.GRU(6, 5),
    "GaussianDropout": lambda: N.GaussianDropout(0.3),
    "GaussianNoise": lambda: N.GaussianNoise(0.2),
    "Graph": lambda: _graph(N.Graph),
    "Highway": lambda: N.Highway(6),
    "Index": lambda: N.Index(1),
    "InferReshape": lambda: N.InferReshape([-1, 3]),
    "JoinTable": lambda: N.JoinTable(1),
    "L1Penalty": lambda: N.L1Penalty(0.01),
    "LSTM": lambda: N.LSTM(6, 5),
    "LSTMPeephole": lambda: N.LSTMPeephole(6, 5),
    "LatentAttention": lambda: N.LatentAttention(16, 2, 8, 6, 2, 8,
                                                 gated=True),
    "LayerNormalization": lambda: N.LayerNormalization(8),
    "Linear": lambda: N.Linear(6, 4),
    "LocallyConnected1D": lambda: N.LocallyConnected1D(8, 4, 3, 2),
    "LocallyConnected2D": lambda: N.LocallyConnected2D(2, 8, 8, 3, 3, 3),
    "LookupTable": lambda: N.LookupTable(10, 6),
    "LookupTableSparse": lambda: N.LookupTableSparse(10, 6),
    "MapTable": lambda: N.MapTable(N.Linear(4, 3)),
    "Maxout": lambda: N.Maxout(6, 4, 2),
    "Mamba2Mixer": lambda: N.Mamba2Mixer(8, 4, 4, 2, 8, chunk_size=4),
    "MixtureOfExperts": lambda: N.MixtureOfExperts(8, 2),
    "Model": lambda: _graph(N.Model),
    "MulConstant": lambda: N.MulConstant(2.0),
    "NormalizeScale": lambda: N.NormalizeScale(size=(1, 6, 1, 1)),
    "RMSNorm": lambda: N.RMSNorm(8),
    "Recurrent": lambda: N.Recurrent(N.LSTM(6, 5)),
    "BiRecurrent": lambda: N.BiRecurrent().add(N.RnnCell(6, 5)),
    "MultiRNNCell": lambda: N.MultiRNNCell([N.RnnCell(6, 6),
                                            N.RnnCell(6, 6)]),
    "Narrow": lambda: N.Narrow(1, 0, 2),
    "NarrowTable": lambda: N.NarrowTable(1, 1),
    "Pack": lambda: N.Pack(1),
    "Padding": lambda: N.Padding(1, 2, 2),
    "Power": lambda: N.Power(2.0),
    "PriorBox": lambda: N.PriorBox([16.0], aspect_ratios=[2.0],
                                   img_size=64, step=8.0),
    "Proposal": lambda: N.Proposal(100, 10, [0.5, 1.0, 2.0], [8.0]),
    "RNN": lambda: N.RNN(6, 5),
    "RecurrentDecoder": lambda: N.RecurrentDecoder(4).add(N.RnnCell(5, 5)),
    "View": lambda: N.View(2, 3),
    "Replicate": lambda: N.Replicate(3),
    "Reshape": lambda: N.Reshape([2, 3]),
    "ResizeBilinear": lambda: N.ResizeBilinear(8, 8),
    "RnnCell": lambda: N.RnnCell(6, 5),
    "RoiAlign": lambda: N.RoiAlign(3, 3),
    "RoiPooling": lambda: N.RoiPooling(3, 3),
    "SReLU": lambda: N.SReLU((6,)),
    "Scale": lambda: N.Scale((1, 6)),
    "Select": lambda: N.Select(1, 0),
    "SelectTable": lambda: N.SelectTable(1),
    "SparseLinear": lambda: N.SparseLinear(6, 4),
    "SpatialAveragePooling": lambda: N.SpatialAveragePooling(2, 2),
    "SpatialBatchNormalization": lambda: N.SpatialBatchNormalization(3),
    "SpatialConvolution": lambda: N.SpatialConvolution(3, 4, 3, 3),
    "SpatialConvolutionMap": lambda: N.SpatialConvolutionMap(
        np.array([[0, 0], [1, 1], [2, 2]], np.int32), 3, 3),
    "SpatialDilatedConvolution": lambda: N.SpatialDilatedConvolution(
        3, 4, 3, 3, dilation_w=2, dilation_h=2),
    "SpatialFullConvolution": lambda: N.SpatialFullConvolution(3, 4, 3, 3),
    "SpatialMaxPooling": lambda: N.SpatialMaxPooling(2, 2),
    "SpatialSeparableConvolution": lambda: N.SpatialSeparableConvolution(
        3, 6, 2, 3, 3),
    "SpatialShareConvolution": lambda: N.SpatialShareConvolution(3, 4, 3, 3),
    "SpatialZeroPadding": lambda: N.SpatialZeroPadding(1, 1, 1, 1),
    "SplitTable": lambda: N.SplitTable(1),
    "SparseAttention": lambda: N.SparseAttention(16, 4, 2, 8, 2, 8, 16),
    "RoutedExperts": lambda: N.RoutedExperts(8, 4, 2, 6, held=(0, 2),
                                             n_shared=1),
    "StaticGraph": lambda: _graph(N.StaticGraph),
    "SublayerBlock": lambda: N.SublayerBlock(
        N.RoutedExperts(8, 4, 2, 6, held=(0, 2), activation="relu2",
                        latent=4, n_shared=2, bias_update=1e-3), "ffn", 8,
        norm="rms"),
    "TemporalConvolution": lambda: N.TemporalConvolution(4, 6, 3),
    "TemporalMaxPooling": lambda: N.TemporalMaxPooling(2),
    "TimeDistributed": lambda: N.TimeDistributed(N.Linear(4, 3)),
    "Transformer": lambda: N.Transformer(32, hidden_size=16, num_heads=2,
                                         filter_size=32,
                                         num_hidden_layers=1),
    "TransformerBlock": lambda: N.TransformerBlock(8, 2, 16),
    "Transpose": lambda: N.Transpose([(1, 2)]),
    "TreeLSTM": lambda: N.TreeLSTM(6, 5),
    "Unsqueeze": lambda: N.Unsqueeze(1),
    "UpSampling1D": lambda: N.UpSampling1D(2),
    "VolumetricAveragePooling": lambda: N.VolumetricAveragePooling(2, 2, 2),
    "VolumetricBatchNormalization": lambda:
        N.VolumetricBatchNormalization(3),
    "VolumetricConvolution": lambda: N.VolumetricConvolution(3, 4, 2, 3, 3),
    "VolumetricFullConvolution": lambda:
        N.VolumetricFullConvolution(3, 4, 2, 3, 3),
    "VolumetricMaxPooling": lambda: N.VolumetricMaxPooling(2, 2, 2),
}


def _public_module_classes():
    out = []
    for n in dir(N):
        c = getattr(N, n)
        if inspect.isclass(c) and issubclass(c, Module) and n not in EXEMPT:
            out.append(n)
    return out


ALL_CLASSES = _public_module_classes()


def _instance(name):
    if name in SPECS:
        return SPECS[name]()
    return getattr(N, name)()


def _tree_equal(t1, t2, name):
    l1, s1 = jax.tree_util.tree_flatten(t1)
    l2, s2 = jax.tree_util.tree_flatten(t2)
    assert s1 == s2, f"{name}: tree structure changed\n{s1}\n{s2}"
    for a, b in zip(l1, l2):
        if hasattr(a, "dtype") or hasattr(b, "dtype"):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype, f"{name}: dtype {a.dtype}->{b.dtype}"
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-6, err_msg=name)
        else:
            assert a == b, f"{name}: leaf {a!r} != {b!r}"


@pytest.mark.parametrize("name", ALL_CLASSES)
def test_roundtrip(name, tmp_path):
    m = _instance(name)
    m.ensure_initialized()
    path = str(tmp_path / "m.bigdl")
    save_bigdl(m, path)
    m2 = load_bigdl(path)
    assert type(m2) is type(m)
    _tree_equal(m.params, m2.params, name)
    _tree_equal(m.state, m2.state, name)


class _DtypeBag(Module):
    def _init_params(self, rng):
        import ml_dtypes
        import jax.numpy as jnp
        return {
            "i32": jnp.asarray(np.array([-5, 3, -(2**31)], np.int32)),
            "i8": jnp.asarray(np.array([-128, 0, 127], np.int8)),
            "u8": jnp.asarray(np.array([0, 255], np.uint8)),
            "b": jnp.asarray(np.array([True, False])),
            "f16": jnp.asarray(np.array([1.5, -2.25], np.float16)),
            "bf16": jnp.asarray(np.array([0.5, -3.0], ml_dtypes.bfloat16)),
            # plain-numpy f64 leaf: the generic tier must restore it as
            # exact float64 (_NDT_F64), not the reference DOUBLE→f32 path
            "f64": np.array([1e-300, 2.5, -7.125], np.float64),
            "scalar": jnp.float32(2.5),
        }

    def _apply(self, params, state, x, training, rng):
        return x


class _TupleTree(Module):
    def _init_params(self, rng):
        import jax.numpy as jnp
        return {"pair": (jnp.zeros((2,)), jnp.ones((3,)))}

    def _apply(self, params, state, x, training, rng):
        return x


def test_generic_tier_dtypes_roundtrip(tmp_path):
    """Negative int32, bool, f16, bf16, int8 tensor leaves all survive the
    generic tier with exact dtype and value (user-defined Module subclass,
    exercising the out-of-package pickled-config path too)."""
    m = _DtypeBag()
    m.ensure_initialized()
    path = str(tmp_path / "d.bigdl")
    save_bigdl(m, path)
    m2 = load_bigdl(path)
    _tree_equal(m.params, m2.params, "_DtypeBag")
    assert np.asarray(m2.params["scalar"]).shape == ()


def test_tuple_in_param_tree_roundtrips_via_pickle(tmp_path):
    """A tuple inside the param tree keeps its treedef (pickle fallback)."""
    m = _TupleTree()
    m.ensure_initialized()
    path = str(tmp_path / "t.bigdl")
    save_bigdl(m, path)
    m2 = load_bigdl(path)
    assert isinstance(m2.params["pair"], tuple)
    _tree_equal(m.params, m2.params, "_TupleTree")


def test_sweep_covers_every_public_class():
    """A class added to bigdl_tpu.nn without a spec (when it needs one)
    fails test_roundtrip via auto-instantiation — this guards the inverse:
    specs for classes that no longer exist."""
    missing = [n for n in SPECS if n not in ALL_CLASSES]
    assert not missing, f"specs for non-existent classes: {missing}"


def test_proto_random_composition_fuzz(tmp_path):
    """Fuzz the UNIVERSAL serializer: random Sequential/ConcatTable
    compositions mixing reference-tier and generic-tier layers must
    round-trip through bigdl.proto with identical eval outputs (seeded,
    deterministic)."""
    import jax
    rng = np.random.RandomState(77)

    def rand_model(seed):
        r = np.random.RandomState(seed)
        dim = int(r.randint(3, 9))
        layers = [N.Linear(6, dim)]
        cur = dim
        for _ in range(int(r.randint(2, 6))):
            c = r.randint(0, 10)
            if c == 0:
                nxt = int(r.randint(3, 9))
                layers.append(N.Linear(cur, nxt))
                cur = nxt
            elif c == 1:
                layers.append(N.ReLU())
            elif c == 2:
                layers.append(N.PReLU(cur))          # generic tier
            elif c == 3:
                layers.append(N.BatchNormalization(cur))
            elif c == 4:
                layers.append(N.LayerNormalization(cur))  # generic tier
            elif c == 5:
                layers.append(N.Highway(cur))        # generic tier
            elif c == 6:
                layers.append(N.ELU(0.5))            # generic tier
            elif c == 7:
                layers.append(N.Sequential(
                    N.ConcatTable().add(N.Identity()).add(
                        N.Linear(cur, cur)),
                    N.CAddTable()))                  # mixed container
            elif c == 8:
                layers.append(N.Dropout(0.2))
            else:
                layers.append(N.SoftPlus())          # generic tier
        return N.Sequential(*layers)

    # 4 compositions by default (~10s of tier-1 budget), the full 8
    # under the slow tier — the per-class sweep above already covers
    # every layer individually; the fuzz adds composition coverage
    n = 8 if os.environ.get("BIGDL_TPU_SLOW") == "1" else 4
    for i in range(n):
        m = rand_model(int(rng.randint(0, 10_000)))
        m.ensure_initialized()
        m.evaluate()
        x = np.random.RandomState(i).randn(4, 6).astype(np.float32)
        ref = np.asarray(m.forward(x))
        path = str(tmp_path / f"pf{i}.bigdl")
        save_bigdl(m, path)
        m2 = load_bigdl(path)
        m2.evaluate()
        np.testing.assert_allclose(np.asarray(m2.forward(x)), ref,
                                   atol=1e-5, err_msg=f"model {i}: {m}")


# ---------------------------------------------------------------------------
# pickle trust model (r5 — ADVICE r4 medium finding)
# ---------------------------------------------------------------------------


class _EvilReduce:
    """Pickles to a REDUCE that would invoke os.system on load."""

    def __init__(self, path):
        self.path = path

    def __reduce__(self):
        import os
        return (os.system, (f"touch {self.path}",))


def _crafted_generic_module(attrs):
    """Minimal generic-tier BigDLModule wire bytes with the given custom
    (bytes-payload) attrs — what an attacker-controlled .bigdl file is."""
    from bigdl_tpu.loaders import bigdl_proto as BP
    from bigdl_tpu.loaders.wire import field_bytes, field_string
    out = field_string(
        7, BP._NATIVE_PREFIX + "bigdl_tpu.nn.elementwise.Identity")
    for k, blob in attrs.items():
        entry = field_string(1, k) + field_bytes(2, BP._attr_custom(blob))
        out += field_bytes(8, entry)
    return out


@pytest.mark.parametrize("attr", ["cfg_pickle", "param_pickle",
                                  "state_pickle", "cfgp:frob"])
def test_load_refuses_os_system_gadget(attr, tmp_path):
    """A crafted .bigdl file whose pickled attr REDUCEs to os.system must
    raise, not execute (default restricted unpickler)."""
    import pickle as _p
    marker = tmp_path / "pwned"
    data = _crafted_generic_module({attr: _p.dumps(_EvilReduce(marker))})
    with pytest.raises(Exception, match="refusing to unpickle"):
        load_bigdl(data)
    assert not marker.exists(), "gadget executed!"


def test_allow_pickle_false_refuses_pickled_attrs(tmp_path):
    """allow_pickle=False refuses any pickled attr with a clear error, and
    'unsafe' still loads the (benign) file."""
    m = _TupleTree()
    m.ensure_initialized()
    path = str(tmp_path / "t.bigdl")
    save_bigdl(m, path)  # tuple treedef rides the pickle fallback
    with pytest.raises(ValueError, match="allow_pickle=False"):
        load_bigdl(path, allow_pickle=False)
    m2 = load_bigdl(path, allow_pickle="unsafe")
    _tree_equal(m.params, m2.params, "_TupleTree-unsafe")


def test_allow_pickle_false_loads_reference_tier(tmp_path):
    """Reference-compatible files never carry pickle — allow_pickle=False
    must load them unchanged (the reference ModuleLoader trust model)."""
    m = N.Sequential(N.Linear(6, 5), N.ReLU())
    m.ensure_initialized()
    path = str(tmp_path / "ref.bigdl")
    save_bigdl(m, path)
    m2 = load_bigdl(path, allow_pickle=False)
    x = np.random.RandomState(0).randn(3, 6).astype(np.float32)
    m.evaluate(), m2.evaluate()
    np.testing.assert_allclose(np.asarray(m2.forward(x)),
                               np.asarray(m.forward(x)), atol=1e-6)


def test_restricted_unpickler_allows_user_module_subclass(tmp_path):
    """Out-of-package Module subclasses (this test module) still load under
    the default restricted policy — the generic tier's documented scope."""
    m = _TupleTree()
    m.ensure_initialized()
    path = str(tmp_path / "user.bigdl")
    save_bigdl(m, path)
    m2 = load_bigdl(path)  # default: restricted
    assert isinstance(m2, _TupleTree)
    _tree_equal(m.params, m2.params, "_TupleTree-restricted")


def _su(s):
    """Pickle SHORT_BINUNICODE opcode for a short string."""
    b = s.encode() if isinstance(s, str) else s
    return b"\x8c" + bytes([len(b)]) + b


def _sb(b):
    """Pickle SHORT_BINBYTES / BINBYTES opcode."""
    return (b"C" + bytes([len(b)]) if len(b) < 256
            else b"B" + len(b).to_bytes(4, "little")) + b


def _stack_global_pickle(module, name, arg_pickle):
    """Hand-built protocol-4 stream: STACK_GLOBAL(module, name) REDUCEd on
    one bytes arg — the dotted-name re-export bypass shape."""
    return (b"\x80\x04" + _su(module) + _su(name) + b"\x93"
            + _sb(arg_pickle) + b"\x85R.")


def test_load_refuses_stack_global_reexport_bypass(tmp_path):
    """Protocol-4 STACK_GLOBAL with a dotted name must not reach module
    attributes of whitelisted packages (e.g. the `pickle` module imported
    inside bigdl_tpu.loaders.bigdl_proto → pickle.loads → raw unpickle)."""
    import pickle as _p
    marker = tmp_path / "pwned2"
    inner = _p.dumps(_EvilReduce(marker))
    evil = _stack_global_pickle(
        "bigdl_tpu.loaders.bigdl_proto", "pickle.loads", inner)
    data = _crafted_generic_module({"cfg_pickle": evil})
    with pytest.raises(Exception, match="refusing to unpickle"):
        load_bigdl(data)
    assert not marker.exists(), "dotted-name bypass executed!"


def test_load_refuses_numpy_exec_helper(tmp_path):
    """numpy is not an open package: its exec-style helpers
    (numpy.testing._private.utils.runstring) must be refused."""
    code = _su("import os; os.system('false')")
    evil = (b"\x80\x04" + _su("numpy.testing._private.utils")
            + _su("runstring") + b"\x93" + code + b"}\x86R.")
    data = _crafted_generic_module({"cfg_pickle": evil})
    with pytest.raises(Exception, match="refusing to unpickle"):
        load_bigdl(data)


def test_load_refuses_numpy_memmap_file_write(tmp_path):
    """numpy.memmap is a file-write primitive — the numpy-types branch must
    admit only scalar/dtype types."""
    victim = tmp_path / "victim.bin"
    victim.write_bytes(b"AAAAAAAA")
    evil = (b"\x80\x04" + _su("numpy") + _su("memmap") + b"\x93"
            + _su(str(victim)) + b"\x85R.")
    data = _crafted_generic_module({"cfg_pickle": evil})
    with pytest.raises(Exception, match="refusing to unpickle"):
        load_bigdl(data)
    assert victim.read_bytes() == b"AAAAAAAA"


def test_load_refuses_module_object_resolution():
    """Resolving a MODULE object through an open package would let BUILD
    rewrite package globals — must be refused (classes/callables only)."""
    evil = b"\x80\x04" + _su("bigdl_tpu") + _su("loaders") + b"\x93."
    data = _crafted_generic_module({"cfg_pickle": evil})
    with pytest.raises(Exception, match="refusing to unpickle"):
        load_bigdl(data)
    import bigdl_tpu.loaders
    assert bigdl_tpu.loaders.bigdl_proto is not None


def test_load_refuses_loader_reentry_laundering(tmp_path):
    """load_bigdl itself must not be REDUCE-invocable: a crafted file could
    otherwise re-enter load_bigdl(<inner bytes>, 'unsafe') and run raw
    pickle. Functions are refused wholesale from open packages."""
    import pickle as _p
    marker = tmp_path / "pwned3"
    inner = _crafted_generic_module({"cfg_pickle":
                                     _p.dumps(_EvilReduce(marker))})

    evil = (b"\x80\x04" + _su("bigdl_tpu.loaders.bigdl_proto")
            + _su("load_bigdl") + b"\x93" + _sb(inner) + _su("unsafe")
            + b"\x86R.")
    data = _crafted_generic_module({"cfg_pickle": evil})
    with pytest.raises(Exception, match="refusing to unpickle"):
        load_bigdl(data)
    assert not marker.exists(), "loader re-entry executed!"


def test_allow_pickle_rejects_ambiguous_values():
    """Falsy-but-not-False values (0, None) must not silently mean
    'restricted' — only True/False/'unsafe' are accepted."""
    for bad in (0, None, 1, "restricted"):
        with pytest.raises(ValueError, match="allow_pickle must be"):
            load_bigdl(b"", allow_pickle=bad)


def test_ufunc_config_roundtrips_under_restricted(tmp_path):
    """A config holding a numpy ufunc (TableOperation(np.add) style) must
    load under the default restricted policy — ufuncs are data-only."""
    m = N.TableOperation(np.add) if hasattr(N, "TableOperation") else None
    if m is None:
        pytest.skip("no TableOperation")
    path = str(tmp_path / "uf.bigdl")
    save_bigdl(m, path)
    m2 = load_bigdl(path)
    a = np.ones((2, 3), np.float32)
    from bigdl_tpu.utils import Table
    np.testing.assert_allclose(np.asarray(m2.forward(Table(a, a))),
                               2 * a, atol=0)


class _I64Bag(Module):
    def _init_params(self, rng):
        return {"steps": np.array([2**40 + 3, -7], np.int64),
                "w64": np.array([1e-300, 2.5], np.float64)}

    def _apply(self, params, state, x, training, rng):
        return x


def test_i64_f64_leaves_roundtrip_with_zero_grads(tmp_path):
    """int64 leaves must not truncate to int32 (2**40+3 -> 3), and the
    kept-as-numpy leaves must get ZERO grad_params, not alias the param
    values."""
    m = _I64Bag()
    m.ensure_initialized()
    path = str(tmp_path / "i.bigdl")
    save_bigdl(m, path)
    m2 = load_bigdl(path)
    s = np.asarray(m2.params["steps"])
    assert s.dtype == np.int64 and s[0] == 2**40 + 3, s
    g = m2.grad_params["steps"]
    assert g is not m2.params["steps"]
    assert np.asarray(g).sum() == 0
    assert np.asarray(m2.grad_params["w64"]).sum() == 0
