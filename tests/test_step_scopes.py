"""The compiled step names its parts: every step builder's lowered text
carries the scope paths an operator knows from the parameter tree
(``block<i>/{ln1,attn,ln2,ffn}``, ``embed``, ``ln_f``, ``head``), ``loss``,
``grad_clip``, ``optim_update`` and, across devices, ``grad_exchange``; the
flash kernels carry their own names. The names are parameter keys, never
``Module.name``, so they do not change with the order of construction."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.models import TransformerLM
from bigdl_tpu.optim import Adam, DistriOptimizer, LocalOptimizer
from bigdl_tpu.optim.trigger import max_iteration
from bigdl_tpu.parallel.mesh import data_parallel_mesh
from utils import jaxpr_equations


def lm_samples(n=8, t=16, vocab=64):
    rows = np.random.RandomState(0).randint(1, vocab, size=(n, t + 1))
    return [Sample(r[:-1].astype(np.float32), r[1:].astype(np.float32))
            for r in rows]


def toy_lm(remat=True):
    return TransformerLM(vocab_size=64, hidden_size=32, num_heads=2,
                         filter_size=64, num_layers=2, max_len=16,
                         remat=remat)


def op_names(make, model, samples, criterion, **kw):
    """The ``loc("…")`` names of the lowered text of the step that one
    iteration of ``optimize()`` dispatched."""
    opt = make(model=model, training_set=DataSet.array(samples),
               criterion=criterion, optim_method=Adam(learningrate=1e-3),
               batch_size=4, **kw)
    opt.set_gradclip_l2norm(1.0)
    opt.set_end_when(max_iteration(1))
    seen = {}
    real = opt._dispatch_guarded

    def shape_of(a):
        # a sharding only where the array spans the mesh: the scalars are
        # uncommitted arrays on one device, which a jit takes anywhere
        spans = len(a.sharding.device_set) > 1
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=a.sharding if spans else None)

    def spy(*args):
        seen["args"] = jax.tree_util.tree_map(shape_of, args)
        return real(*args)

    opt._dispatch_guarded = spy
    opt.optimize()
    text = opt._step_fn._jit.lower(*seen["args"]).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


def has(names, *parts):
    """Some op name holds every part, in this order."""
    rx = re.compile(".*".join(re.escape(p) for p in parts))
    return any(rx.search(n) for n in names)


BUILDERS = {
    "local": (LocalOptimizer, {}),
    "zero1": (DistriOptimizer, {"parameter_mode": "zero1"}),
}


@pytest.fixture(scope="module")
def lowered():
    out = {}
    for key, (make, kw) in BUILDERS.items():
        if make is DistriOptimizer:
            kw = dict(kw, mesh=data_parallel_mesh(4))
        out[key] = op_names(make, toy_lm(), lm_samples(),
                            nn.LMCriterion(padding_value=0), **kw)
    return out


@pytest.mark.parametrize("builder", list(BUILDERS))
@pytest.mark.parametrize("scope", [
    ("embed",), ("block0", "attn"), ("block0", "ln1"), ("block1", "ln2"),
    ("block1", "ffn"), ("ln_f",), ("head",), ("loss",), ("grad_clip",),
    ("optim_update",)])
def test_the_step_carries_the_scope(lowered, builder, scope):
    assert has(lowered[builder], *scope), sorted(lowered[builder])[:40]


def test_forward_backward_and_recomputation_are_told_apart_by_jax(lowered):
    names = lowered["local"]
    assert has(names, "jvp(", "block0", "attn")
    assert has(names, "transpose(", "block0", "attn")
    assert has(names, "rematted_computation", "block1", "ffn")
    # the optimizer is in neither
    assert not any("jvp(" in n or "transpose(" in n
                   for n in names if "optim_update" in n)


def test_the_exchange_is_named_under_zero1_only(lowered):
    assert has(lowered["zero1"], "optim_update", "grad_exchange")
    assert not has(lowered["local"], "grad_exchange")


def test_the_recomputation_frame_appears_only_with_remat():
    names = op_names(LocalOptimizer, toy_lm(remat=False), lm_samples(),
                     nn.LMCriterion(padding_value=0))
    assert has(names, "transpose(", "block1", "ffn")
    assert not has(names, "rematted_computation")
    assert not has(names, "checkpoint")


def test_remat_recomputes_the_block_but_not_the_flash_forward(monkeypatch):
    """With ``remat`` and the flash kernel the recomputation frame still
    holds the block's projections and FFN and holds no ``flash_fwd``: the
    kernel's ``o`` and ``lse`` were kept (``nn.attention.remat_block``).
    The forward and the backward kernels keep their names."""
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    names = op_names(LocalOptimizer, toy_lm(), lm_samples(),
                     nn.LMCriterion(padding_value=0))
    assert has(names, "rematted_computation", "block0", "attn")
    assert has(names, "rematted_computation", "block1", "attn")
    assert has(names, "rematted_computation", "block1", "ffn")
    assert not has(names, "rematted_computation", "flash_fwd")
    assert has(names, "jvp(", "block0", "attn", "flash_fwd")
    assert has(names, "transpose(", "block0", "attn", "flash_bwd_dkv")
    assert has(names, "transpose(", "block1", "attn", "flash_bwd_dq")


def test_the_sparse_step_carries_the_same_four_scope_names():
    """The sparse wire (``_sparse_exchange``) wants the ids to be the
    model's input, so its toy is a ``Sequential`` whose children carry
    their container keys (``Container.child_apply``)."""
    model = nn.Sequential().add(nn.LookupTable(64, 8)) \
        .add(nn.Select(2, 1)).add(nn.Linear(8, 3)).add(nn.LogSoftMax())
    rng = np.random.RandomState(0)
    samples = [Sample(rng.randint(1, 65, size=(4,)).astype(np.float32),
                      np.float32(rng.randint(1, 4))) for _ in range(16)]
    names = op_names(DistriOptimizer, model, samples,
                     nn.ClassNLLCriterion(), mesh=data_parallel_mesh(4),
                     sparse_embedding=True)
    for scope in ("loss", "grad_exchange", "grad_clip", "optim_update"):
        assert has(names, scope), scope
    assert has(names, "jvp(", "0") and has(names, "jvp(", "2")


def test_scope_paths_do_not_change_with_the_order_of_construction():
    def paths(model):
        fn = jax.jit(lambda p, x: model.apply(p, {}, x, training=False)[0])
        text = fn.lower(model.init()[0], jnp.ones((2, 16), jnp.int32)) \
            .as_text(debug_info=True)
        return set(re.findall(r'loc\("(jit[^"]+)"', text))

    first = toy_lm(remat=False)
    nn.Linear(3, 3), nn.Linear(3, 3)         # moves the instance counter
    second = toy_lm(remat=False)
    assert first.name != second.name
    assert paths(first) == paths(second)
    assert has(paths(first), "block1", "attn")


def test_the_flash_kernels_carry_their_names():
    from bigdl_tpu.kernels.flash_attention import flash_attention_fused
    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    fwd = lambda q, k, v: flash_attention_fused(
        q, k, v, causal=True, interpret=True).sum()
    assert "flash_fwd" in str(jax.make_jaxpr(fwd)(q, q, q))
    bwd = str(jax.make_jaxpr(jax.grad(fwd, argnums=(0, 1, 2)))(q, q, q))
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert name in bwd, name


def test_flash_attention_moves_no_head(monkeypatch):
    """Two 64-wide heads fill a 128-lane block, so the training step's
    attention runs on the projections' own ``[B, T, H*D]``: under the scope
    ``attn`` the gradient of a ``remat`` LM transposes no 4-D array (no head
    split, no merge; forward, recomputation and backward), and each kernel
    is called once a layer under its own name and no other kernel is."""
    monkeypatch.setenv("BIGDL_TPU_FLASH", "interpret")
    layers = 2
    model = TransformerLM(vocab_size=64, hidden_size=256, num_heads=4,
                          filter_size=64, num_layers=layers, max_len=128,
                          remat=True)
    params, _ = model.init(jax.random.PRNGKey(0))
    ids = jnp.ones((2, 128), jnp.int32)

    def loss(p):
        return jnp.sum(jnp.tanh(model.apply(p, {}, ids,
                                            training=False)[0] * 0.01))

    eqns = list(jaxpr_equations(jax.make_jaxpr(jax.grad(loss))(params).jaxpr))
    moved = [(e.invars[0].aval.shape, str(e.source_info.name_stack))
             for e in eqns if e.primitive.name == "transpose"
             and e.invars[0].aval.ndim == 4
             and "attn" in str(e.source_info.name_stack)]
    assert not moved, moved
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    assert sorted(kernels) == sorted(
        ["flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"] * layers)
