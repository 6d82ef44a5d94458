"""Core module abstraction for bigdl_tpu.

Parity: reference ``nn/abstractnn/AbstractModule.scala`` + ``nn/Container.scala``.

Design (TPU-first, NOT a translation):

The reference implements ``forward``/``backward`` as mutable in-place tensor
updates per layer (updateOutput / updateGradInput / accGradParameters), because
on CPU each Spark task re-runs the interpreted layer graph. On TPU everything
must be a pure traced function so XLA can fuse and compile it once. So each
module here is two things at once:

* a **pure functional core**: ``init(rng) -> (params, state)`` and
  ``apply(params, state, input, training, rng) -> (output, new_state)``, where
  ``params``/``state`` are pytrees. This is what ``jit``/``grad``/``vmap``/
  ``shard_map`` consume, and what the optimizers differentiate.
* a **stateful facade** with the reference's Torch-style API: ``forward``,
  ``backward`` (gradInput + parameter-gradient accumulation, derived from
  ``jax.vjp`` instead of hand-written updateGradInput), ``parameters()``,
  ``zero_grad_parameters``, ``training()/evaluate()``, ``save``/``load``.

Gradients therefore never need per-layer backward code: autodiff supplies the
exact ``updateGradInput``/``accGradParameters`` pair for every layer.
"""
from __future__ import annotations

import contextlib
import os
import pickle
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import engine
from ..utils.table import Table

Params = Dict[str, Any]
State = Dict[str, Any]


def _to_numpy_tree(tree):
    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)


class Node:
    """A node in a computation DAG (parity: utils/Node.scala + nn/Graph).

    Created by calling a module on other nodes: ``y = Linear(3, 4)(x_node)``.
    """

    __slots__ = ("module", "prevs", "name", "mod_idx")

    def __init__(self, module, prevs, name=None):
        self.module = module
        self.prevs = list(prevs)
        self.name = name or (module.name if module is not None else "input")
        self.mod_idx = None  # set by Graph at construction

    def __repr__(self):
        return f"Node({self.name})"


class Module:
    """Base class of all layers and containers."""

    _instance_counter = [0]

    def __init__(self, name: Optional[str] = None):
        Module._instance_counter[0] += 1
        self.name = name or f"{type(self).__name__}{Module._instance_counter[0]}"
        self.params: Optional[Params] = None
        self.state: Optional[State] = None
        self.grad_params: Optional[Params] = None
        self.output = None
        self.grad_input = None
        self.train_mode = True
        self._scale_w = 1.0
        self._scale_b = 1.0

    # ------------------------------------------------------------------
    # functional core — subclasses override these
    # ------------------------------------------------------------------
    def _init_params(self, rng) -> Params:
        return {}

    def _init_state(self) -> State:
        return {}

    def _apply(self, params: Params, state: State, x, training: bool, rng):
        raise NotImplementedError(type(self).__name__)

    # ------------------------------------------------------------------
    # functional API
    # ------------------------------------------------------------------
    def init(self, rng=None) -> Tuple[Params, State]:
        rng = rng if rng is not None else engine.next_rng_key()
        return self._init_params(rng), self._init_state()

    def apply(self, params: Params, state: State, x, training: bool = False,
              rng=None, scope: Optional[str] = None):
        """Pure forward. Returns ``(output, new_state)``. ``scope`` is the
        key of ``params`` in the caller's parameter tree: inside a
        compiled program the layer's operations then carry it in their
        names (``jax.named_scope``), so a profile reads in the paths an
        operator knows from the parameter tree. (``self.name`` would not
        do: its default counts instances, so it changes with the order of
        construction.)"""
        try:
            with jax.named_scope(scope) if scope else contextlib.nullcontext():
                out = self._apply(params, state, x, training, rng)
        except Exception as e:
            # LayerException parity (utils/LayerException.scala): errors
            # deep inside a model carry the failing layer's identity.
            # add_note keeps the original exception type/traceback intact.
            note = f"Layer info: {self.name} ({type(self).__name__})"
            if hasattr(e, "add_note"):
                e.add_note(note)
            else:
                # Python < 3.11: PEP-678 notes as a plain attribute —
                # tracebacks won't render them, but programmatic readers
                # (tests, error reporters) see the same __notes__ list
                try:
                    notes = getattr(e, "__notes__", None)
                    if notes is None:
                        notes = e.__notes__ = []
                    notes.append(note)
                except Exception:
                    pass  # exotic exception without a writable __dict__
            raise
        if isinstance(out, tuple) and len(out) == 2 and isinstance(out[1], dict):
            return out
        return out, state

    # ------------------------------------------------------------------
    # stateful torch-style facade (parity: AbstractModule.scala:103-420)
    # ------------------------------------------------------------------
    def ensure_initialized(self):
        if self.params is None:
            self.params, self.state = self.init()
            self.grad_params = jax.tree_util.tree_map(jnp.zeros_like, self.params)
        return self

    def forward(self, x):
        self.ensure_initialized()
        rng = engine.next_rng_key() if self.train_mode else None
        self.output, self.state = self.apply(self.params, self.state, x,
                                             training=self.train_mode, rng=rng)
        return self.output

    def __call__(self, *args):
        # Calling on Node(s) builds a graph; calling on data runs forward.
        if len(args) == 1 and isinstance(args[0], Node):
            return Node(self, [args[0]])
        if len(args) >= 1 and all(isinstance(a, Node) for a in args):
            return Node(self, list(args))
        if len(args) == 1 and isinstance(args[0], (list, tuple)) and \
                all(isinstance(a, Node) for a in args[0]) and len(args[0]) > 0:
            return Node(self, list(args[0]))
        if len(args) == 1:
            return self.forward(args[0])
        return self.forward(Table(*args))

    def backward(self, x, grad_output):
        """gradInput + parameter-grad accumulation via one vjp.

        Parity: AbstractModule.backward = updateGradInput + accGradParameters.
        """
        self.ensure_initialized()
        rng = engine.next_rng_key() if self.train_mode else None

        def f(p, inp):
            return self.apply(p, self.state, inp, training=self.train_mode,
                              rng=rng)[0]

        _, vjp_fn = jax.vjp(f, self.params, x)
        gp, gi = vjp_fn(grad_output)
        self.grad_params = jax.tree_util.tree_map(
            lambda a, b: a + self._scale_w * b, self.grad_params, gp)
        self.grad_input = gi
        return gi

    def update_grad_input(self, x, grad_output):
        def f(inp):
            return self.apply(self.params, self.state, inp,
                              training=self.train_mode)[0]
        _, vjp_fn = jax.vjp(f, x)
        self.grad_input = vjp_fn(grad_output)[0]
        return self.grad_input

    def acc_grad_parameters(self, x, grad_output):
        self.backward(x, grad_output)

    def zero_grad_parameters(self):
        if self.grad_params is not None:
            self.grad_params = jax.tree_util.tree_map(jnp.zeros_like,
                                                      self.grad_params)

    def parameters(self):
        """Return (weights, gradWeights) as flat lists (parity:
        AbstractModule.parameters)."""
        self.ensure_initialized()
        ws = jax.tree_util.tree_leaves(self.params)
        gs = jax.tree_util.tree_leaves(self.grad_params)
        return ws, gs

    def get_parameters(self):
        """Single flattened (weight, grad) vector pair.

        Parity: Module.getParameters compacting storage — the reference's
        contiguous flat parameter is the basis of its block all-reduce; here
        ``ravel_pytree`` provides the same contiguous view.
        """
        from jax.flatten_util import ravel_pytree
        self.ensure_initialized()
        flat_w, unravel = ravel_pytree(self.params)
        flat_g, _ = ravel_pytree(self.grad_params)
        return flat_w, flat_g, unravel

    def get_weights(self):
        self.ensure_initialized()
        return _to_numpy_tree(self.params)

    def set_weights(self, weights):
        self.ensure_initialized()
        self.params = jax.tree_util.tree_map(
            lambda cur, new: jnp.asarray(new, dtype=jnp.asarray(cur).dtype)
            if hasattr(cur, "dtype") else new,
            self.params, weights)
        return self

    # -- modes ----------------------------------------------------------
    def training(self):
        self.train_mode = True
        return self

    def evaluate(self):
        self.train_mode = False
        return self

    def is_training(self):
        return self.train_mode

    # -- misc parity helpers --------------------------------------------
    def set_name(self, name):
        self.name = name
        return self

    def get_name(self):
        return self.name

    def set_scale_w(self, s):
        self._scale_w = s
        return self

    def set_scale_b(self, s):
        self._scale_b = s
        return self

    def reset(self):
        self.params, self.state = self.init()
        self.grad_params = jax.tree_util.tree_map(jnp.zeros_like, self.params)
        return self

    def clone(self):
        import copy
        return copy.deepcopy(self)

    def modules_iter(self):
        yield self

    def find_module(self, name):
        for m in self.modules_iter():
            if m.name == name:
                return m
        return None

    # -- fine-tuning (parity: AbstractModule.freeze/unfreeze) -----------
    def freeze(self, *names):
        """Mark this module (or named descendants) as not-to-be-updated.

        Parity: AbstractModule.freeze — the Optimizer's jitted step zeroes
        gradients and restores frozen params after each update, so neither
        gradients nor weight decay move them. The flag is set on every
        module in the target subtree, so ``freeze()`` then
        ``unfreeze("head")`` releases just the head. Only modules reachable
        via ``modules_iter`` participate; for a composite layer holding
        private children, freeze the composite itself.
        """
        targets = self._freeze_targets(names, "freeze")
        for t in targets:
            for m in t.modules_iter():
                m._frozen = True
        return self

    def unfreeze(self, *names):
        """Parity: AbstractModule.unfreeze."""
        targets = self._freeze_targets(names, "unfreeze")
        for t in targets:
            for m in t.modules_iter():
                m._frozen = False
        return self

    def _freeze_targets(self, names, what):
        if not names:
            return [self]
        targets = []
        for n in names:
            m = self.find_module(n)
            if m is None:
                raise ValueError(f"{what}: no module named {n}")
            targets.append(m)
        return targets

    def is_frozen(self):
        return getattr(self, "_frozen", False)

    # -- extra (non-gradient) parameters: running stats etc. ------------
    def get_extra_parameter(self):
        """State leaves (running stats etc.) as a flat list.

        Parity: AbstractModule.getExtraParameter."""
        self.ensure_initialized()
        return jax.tree_util.tree_leaves(self.state)

    def set_extra_parameter(self, extra):
        """Parity: AbstractModule.setExtraParameter."""
        self.ensure_initialized()
        leaves, treedef = jax.tree_util.tree_flatten(self.state)
        if len(extra) != len(leaves):
            raise ValueError(f"expected {len(leaves)} extra parameters, "
                             f"got {len(extra)}")
        new = []
        for i, (e, c) in enumerate(zip(extra, leaves)):
            cur = jnp.asarray(c)
            arr = jnp.asarray(e, dtype=cur.dtype)
            if arr.shape != cur.shape:
                raise ValueError(f"extra parameter {i}: shape {arr.shape} "
                                 f"does not match {cur.shape}")
            new.append(arr)
        self.state = jax.tree_util.tree_unflatten(treedef, new)
        return self

    # -- conversions (parity: AbstractModule.quantize / save*) ----------
    def quantize(self, calibration=None):
        """Int8-inference copy (parity: AbstractModule.quantize)."""
        from ..quantization.quantize import quantize as _q
        return _q(self, calibration=calibration)

    def save_torch(self, path):
        """Parity: AbstractModule.saveTorch."""
        from ..loaders.torchfile import save_torch as _s
        _s(self, path)
        return self

    def save_caffe(self, prototxt_path, caffemodel_path,
                   input_shape=(3, 224, 224)):
        """Parity: AbstractModule.saveCaffe."""
        from ..loaders.caffe_persister import save_caffe as _s
        _s(self, prototxt_path, caffemodel_path, input_shape=input_shape)
        return self

    def save_tf(self, input_shape, path=None):
        """Parity: AbstractModule.saveTF — returns the GraphDef bytes."""
        from ..loaders.tf_saver import save_tf_graph as _s
        return _s(self, input_shape, path)

    # -- prediction helpers (parity: AbstractModule.predict/predictClass)
    def predict(self, dataset, batch_size=32):
        from ..optim.predictor import Predictor
        return Predictor(self).predict(dataset, batch_size)

    def predict_class(self, dataset, batch_size=32):
        from ..optim.predictor import Predictor
        return Predictor(self).predict_class(dataset, batch_size)

    def evaluate_dataset(self, dataset, methods, batch_size=32):
        from ..optim.evaluator import Evaluator
        return Evaluator(self).evaluate(dataset, methods, batch_size)

    # -- serialization (parity: Module.save / Module.loadModule) --------
    def save(self, path, overwrite=True):
        if not overwrite and os.path.exists(path):
            raise IOError(f"{path} exists and overwrite=False")
        self.ensure_initialized()
        payload = {
            "module": self._strip_runtime(),
            "params": _to_numpy_tree(self.params),
            "state": _to_numpy_tree(self.state),
        }
        with open(path, "wb") as f:
            pickle.dump(payload, f)
        return self

    def _strip_runtime(self):
        import copy
        c = copy.copy(self)
        c.params = None
        c.state = None
        c.grad_params = None
        c.output = None
        c.grad_input = None
        return c

    @staticmethod
    def load(path):
        with open(path, "rb") as f:
            payload = pickle.load(f)
        m = payload["module"]
        m.params = jax.tree_util.tree_map(jnp.asarray, payload["params"])
        m.state = jax.tree_util.tree_map(jnp.asarray, payload["state"])
        m.grad_params = jax.tree_util.tree_map(jnp.zeros_like, m.params)
        return m

    def save_orbax(self, path, overwrite=True):
        """Write params+state as an Orbax checkpoint directory — the JAX
        ecosystem's interchange format (sharding-aware, async-capable,
        readable by any orbax consumer). Complements the self-contained
        pickle ``save`` (which also captures the module topology; orbax
        stores arrays only, so ``load_orbax`` needs a constructed module).
        ``overwrite`` matches :meth:`save`'s default (periodic checkpoint
        loops re-save to the same path)."""
        import orbax.checkpoint as ocp
        self.ensure_initialized()
        ckptr = ocp.PyTreeCheckpointer()
        ckptr.save(os.path.abspath(str(path)),
                   {"params": _to_numpy_tree(self.params),
                    "state": _to_numpy_tree(self.state)},
                   force=overwrite)
        return self

    def load_orbax(self, path):
        """Restore params+state saved by :meth:`save_orbax` into THIS
        module (shapes/structure must match its architecture)."""
        import orbax.checkpoint as ocp
        ckptr = ocp.PyTreeCheckpointer()
        payload = ckptr.restore(os.path.abspath(str(path)))
        self.params = jax.tree_util.tree_map(jnp.asarray, payload["params"])
        self.state = jax.tree_util.tree_map(jnp.asarray, payload["state"])
        self.grad_params = jax.tree_util.tree_map(jnp.zeros_like,
                                                  self.params)
        return self

    def save_weights(self, path):
        self.ensure_initialized()
        flat = {}

        def rec(prefix, tree):
            if isinstance(tree, dict):
                for k, v in tree.items():
                    rec(f"{prefix}/{k}" if prefix else str(k), v)
            else:
                flat[prefix] = np.asarray(tree)
        rec("", self.params)
        np.savez(path, **flat)
        return self

    def load_weights(self, path):
        self.ensure_initialized()
        data = np.load(path if str(path).endswith(".npz") else str(path) + ".npz")

        def rec(prefix, tree):
            if isinstance(tree, dict):
                return {k: rec(f"{prefix}/{k}" if prefix else str(k), v)
                        for k, v in tree.items()}
            return jnp.asarray(data[prefix])
        self.params = rec("", self.params)
        return self

    def __repr__(self):
        return f"{type(self).__name__}({self.name})"


class Container(Module):
    """Base container holding an ordered list of children.

    Parity: nn/Container.scala. Child params/state live under string index keys
    so the container's params form a plain nested dict pytree.
    """

    def __init__(self, *modules, name=None):
        super().__init__(name=name)
        self.modules: list = list(modules)

    def add(self, module):
        self.modules.append(module)
        return self

    def _init_params(self, rng):
        return {str(i): m._init_params(jax.random.fold_in(rng, i))
                for i, m in enumerate(self.modules)}

    def _init_state(self):
        return {str(i): m._init_state() for i, m in enumerate(self.modules)}

    def child_apply(self, i, params, state, x, training, rng):
        sub_rng = None if rng is None else jax.random.fold_in(rng, i)
        out, new_sub = self.modules[i].apply(params[str(i)], state[str(i)], x,
                                             training, sub_rng, scope=str(i))
        return out, new_sub

    def training(self):
        super().training()
        for m in self.modules:
            m.training()
        return self

    def evaluate(self):
        super().evaluate()
        for m in self.modules:
            m.evaluate()
        return self

    def modules_iter(self):
        yield self
        for m in self.modules:
            yield from m.modules_iter()

    def __getitem__(self, i):
        return self.modules[i]

    def __repr__(self):
        inner = ", ".join(repr(m) for m in self.modules)
        return f"{type(self).__name__}({inner})"


class Criterion:
    """Loss base class (parity: nn/abstractnn/AbstractCriterion.scala).

    ``forward(input, target) -> scalar``; ``backward`` derives gradInput via
    autodiff instead of a hand-written updateGradInput.
    """

    def __init__(self, size_average: bool = True):
        self.size_average = size_average
        self.output = None
        self.grad_input = None

    def _forward(self, input, target):
        raise NotImplementedError

    def forward(self, input, target):
        self.output = self._forward(input, target)
        return self.output

    def __call__(self, input, target):
        return self.forward(input, target)

    def backward(self, input, target):
        self.grad_input = jax.grad(lambda i: self._forward(i, target))(input)
        return self.grad_input
