"""Mixture-of-Experts layers (single-program form).

TPU-first addition beyond the reference (BigDL 0.x has no MoE; its
closest relative is the gating ``nn/MixtureTable.scala``, which these
generalize with learned routing).

:class:`MixtureOfExperts` is the Switch-style layer: top-1 routing with a
capacity that DROPS, through one-hot dispatch/combine tensors; its SPMD
expert-parallel counterpart is :func:`bigdl_tpu.parallel.moe.moe_ffn` (same
dispatch/combine math over a device mesh). :class:`RoutedExperts` is the
layer of today's expert decoders: top-k of sigmoid scores with a
selection-only balancing bias, shared experts, a sorted dispatch that drops
nothing, and ``held``: the experts one chip of an expert-parallel
deployment holds, routed over all of them (on one chip without the
exchange). Both drop into a block like an ordinary FFN.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .module import Module
from ..parallel.moe import expert_capacity, top1_routing

ROWS_LOCAL = "moe/rows_local"
LOAD_MAX_OVER_MEAN = "moe/load_max_over_mean"
BALANCE = "moe/balance"


class MixtureOfExperts(Module):
    """Switch-style MoE FFN as an ordinary layer (single-program form).

    Top-1 routing with capacity + load-balance loss over (B, T, D) or
    (N, D) inputs; experts are (D→hidden→D) FFNs evaluated via the same
    dense dispatch/combine einsums as :func:`parallel.moe.moe_ffn` (which is
    the expert-parallel shard_map form of this layer). The auxiliary loss is
    stored in ``state['aux_loss']`` after each forward so optimizers can
    regularize routing.
    """

    def __init__(self, hidden_size: int, n_experts: int,
                 ffn_hidden: Optional[int] = None,
                 capacity_factor: float = 1.25, name=None):
        super().__init__(name=name)
        self.hidden_size = hidden_size
        self.n_experts = n_experts
        self.ffn_hidden = ffn_hidden or 4 * hidden_size
        self.capacity_factor = capacity_factor

    def _init_params(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        d, h, E = self.hidden_size, self.ffn_hidden, self.n_experts
        s1, s2 = 1.0 / np.sqrt(d), 1.0 / np.sqrt(h)
        return {"router": jax.random.normal(k1, (d, E)) * s1,
                "w1": jax.random.normal(k2, (E, d, h)) * s1,
                "w2": jax.random.normal(k3, (E, h, d)) * s2}

    def _init_state(self):
        return {"aux_loss": jnp.zeros(())}

    def _apply(self, params, state, x, training, rng):
        shape = x.shape
        t = int(np.prod(shape[:-1]))
        h = x.reshape(t, shape[-1])
        capacity = expert_capacity(t, self.n_experts,
                                   self.capacity_factor)
        logits = h @ params["router"]
        dispatch, combine, aux = top1_routing(logits, capacity)
        expert_in = jnp.einsum("td,tec->ecd", h, dispatch)
        mid = jax.nn.relu(jnp.einsum("ecd,edh->ech", expert_in,
                                     params["w1"]))
        out = jnp.einsum("ech,ehd->ecd", mid, params["w2"])
        y = jnp.einsum("tec,ecd->td", combine, out)
        new_state = dict(state)
        new_state["aux_loss"] = aux
        return y.reshape(shape), new_state


def union_states(*states):
    """One block's state from its layers' (an attention's, an FFN's):
    their ``counters`` and their ``losses`` side by side."""
    out = {}
    for s in states:
        for kind in ("counters", "losses"):
            if s and kind in s:
                out.setdefault(kind, {}).update(s[kind])
    return out


def carried(state):
    """What a layer's state carries from one step to the next (an expert
    layer's moved bias): all of it but the ``counters`` and ``losses`` that
    each forward makes anew."""
    return {k: v for k, v in (state or {}).items()
            if k not in ("counters", "losses")}


def merge_counters(states):
    """One model state from the layers' states: ``{"counters": {each
    counter: the mean over the layers that count it, the worst layer's for
    moe/load_max_over_mean}, "losses": {each loss: the sum over the
    layers}}``, the kinds no layer has left out (``{}``: none)."""
    out = {}
    counted = [s["counters"] for s in states if s and "counters" in s]
    if counted:
        out["counters"] = {}
        names = dict.fromkeys(k for c in counted for k in c)
        # the means first, then the worst: the order ops are traced in
        for name in sorted(names, key=lambda n: n == LOAD_MAX_OVER_MEAN):
            having = [c for c in counted if name in c]
            out["counters"][name] = (
                jnp.max(jnp.stack([c[name] for c in having]))
                if name == LOAD_MAX_OVER_MEAN
                else sum(c[name] for c in having) / len(having))
    losses = [s["losses"] for s in states if s and "losses" in s]
    if losses:
        out["losses"] = {name: sum(l[name] for l in losses if name in l)
                         for name in dict.fromkeys(
                             k for l in losses for k in l)}
    return out


class RoutedExperts(Module):
    """Top-k routed experts with shared experts, as DeepSeek-V3's
    ``deepseek_v3`` configs describe the layer, told which experts it
    holds:

        s   = sigmoid(x Wr)                           over ALL n_experts
        sel = top_k of s + bias        (``bias``: selection only, no gradient;
                                        the ``noaux_tc`` balancing bias)
        w   = s[sel] / (sum s[sel] + 1e-20) * routed_scale
        y   = sum_{i in sel, i held} w_i E_i(x) + Shared(x)
        E(x) = W2 (silu(W1 x) * W3 x)                 (SwiGLU, no biases)

    ``held = (first, count)``: the layer routes over all ``n_experts`` and
    computes experts ``first .. first + count - 1``, the share of one chip
    of an expert-parallel deployment; what the absent experts would add is
    left out (``None``: all are held). The router runs in float32 at the
    highest matmul precision whatever the rest does, as the published
    implementations compute the gate.

    Dispatch is by sorting, without dropping: the (token, choice) pairs
    are sorted by expert, each held expert's rows gathered into
    ``capacity`` slots of ONE ``[count, capacity, H]`` buffer, the experts
    run as a batched product over it, and the weighted rows are added back
    into their tokens (a gather and a scatter-add of ``count * capacity``
    rows, and the same two transposed in the backward pass: on a v5e a
    scatter-add of 16,384 rows of 2,048 costs 2.0 ms, the six gathers a
    token that avoid it 5.3); no ``(tokens, experts, capacity)`` tensor is
    built. ``capacity_factor``
    sets the slots an expert has as a multiple of the even share ``tokens *
    top_k / n_experts`` (None: ``tokens``, which no routing can exceed).
    An expert sent more rows than its slots is an ERROR, not a drop: the
    layer's output is NaN, which the trainer's guard counts as a failed
    step.

    ``scoring="softmax"`` (Qwen-MoE's router, as ``norm_topk_prob``
    configs use it): ``s = softmax(x Wr)`` over all experts, ``sel = top_k
    of s`` with no bias (the layer has none), ``w = s[sel] / sum s[sel] *
    routed_scale``. ``balance_coef`` > 0 adds the load-balancing loss
    ``balance_coef * E * sum_e f_e P_e`` to the state's ``losses`` (as
    ``moe/balance``), ``f_e`` the share of the layer's tokens that chose
    expert e among their top k (summing to k), ``P_e`` its mean score.

    ``activation="relu2"``: ungated squared-ReLU experts, ``E(u) = W2
    relu(W1 u)^2`` (``nemotron_h``). ``latent`` (LatentMoE): the routed
    experts work in a narrower space, ``y_r = (sum w_i E_i(x W_down))
    W_up`` with ``W_down [H, latent]`` and ``W_up [latent, H]`` shared by
    all experts (scope ``experts``; the router still reads ``x``). The
    shared experts take the layer's activation.

    ``bias_update`` (``u`` of ``noaux_tc``; sigmoid scoring): the bias
    follows the load as training goes, ``b_i <- b_i + u * sign(mean load -
    load_i)`` after every training forward, from the load that forward
    routed (DeepSeek-V3's update between steps). The bias is then
    ``params["bias"]``, set before training and left alone by the
    optimizer (it has no gradient), plus ``state["bias"]``, what the
    updates have added since, which the layer's state carries from one
    step to the next.

    State: ``{"counters": {"moe/rows_local": rows routed to the held
    experts, "moe/load_max_over_mean": the fullest of ALL experts' load
    over the mean load}}``, ``"losses"`` where ``balance_coef`` > 0 and
    ``"bias"`` where ``bias_update`` > 0."""

    # class defaults: pickles from before these options lack the attrs
    scoring = "sigmoid"
    balance_coef = 0.0
    activation = "swiglu"
    latent = None
    bias_update = 0.0

    def __init__(self, hidden_size: int, n_experts: int, top_k: int,
                 expert_hidden: int, held=None, n_shared: int = 0,
                 routed_scale: float = 1.0, capacity_factor=None,
                 scoring: str = "sigmoid", balance_coef: float = 0.0,
                 activation: str = "swiglu", latent: Optional[int] = None,
                 bias_update: float = 0.0, name=None):
        super().__init__(name=name)
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring must be 'sigmoid' or 'softmax', got "
                             f"{scoring!r}")
        if activation not in ("swiglu", "relu2"):
            raise ValueError(f"activation must be 'swiglu' or 'relu2', got "
                             f"{activation!r}")
        if bias_update and scoring != "sigmoid":
            raise ValueError("bias_update moves the sigmoid router's bias; "
                             "softmax scoring has none")
        self.scoring, self.balance_coef = scoring, balance_coef
        self.activation, self.latent = activation, latent
        self.bias_update = bias_update
        from .attention import FeedForwardNetwork
        first, count = held or (0, n_experts)
        if not (0 <= first and first + count <= n_experts and count > 0):
            raise ValueError(f"held {held} is not a range of the "
                             f"{n_experts} experts")
        self.hidden_size, self.n_experts, self.top_k = (
            hidden_size, n_experts, top_k)
        self.expert_hidden, self.held = expert_hidden, (first, count)
        self.routed_scale = routed_scale
        self.capacity_factor = capacity_factor
        self.shared = FeedForwardNetwork(
            hidden_size, n_shared * expert_hidden, activation=activation,
            bias=False) if n_shared else None

    def _init_params(self, rng):
        k = jax.random.split(rng, 5)
        H, f, E = self.hidden_size, self.expert_hidden, self.n_experts
        d, n = self.latent or H, self.held[1]
        s1, s2 = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
        p = {"router": jax.random.normal(k[0], (H, E)) * (1.0 / np.sqrt(H)),
             "experts": {"w1": jax.random.normal(k[1], (n, d, f)) * s1,
                         "w2": jax.random.normal(k[3], (n, f, d)) * s2}}
        if self.activation == "swiglu":
            p["experts"]["w3"] = jax.random.normal(k[2], (n, d, f)) * s1
        if self.latent:
            k5, k6 = jax.random.split(jax.random.fold_in(rng, 5))
            p["latent"] = {
                "down": jax.random.normal(k5, (H, d)) * (1.0 / np.sqrt(H)),
                "up": jax.random.normal(k6, (d, H)) * s1}
        if self.scoring == "sigmoid":
            p["bias"] = jnp.zeros((E,))
        if self.shared:
            p["shared"] = self.shared._init_params(k[4])
        return p

    def _init_state(self):
        state = {"counters": {ROWS_LOCAL: jnp.zeros(()),
                              LOAD_MAX_OVER_MEAN: jnp.zeros(())}}
        if self.balance_coef:
            state["losses"] = {BALANCE: jnp.zeros(())}
        if self.bias_update:
            state["bias"] = jnp.zeros((self.n_experts,))
        return state

    def capacity(self, tokens: int) -> int:
        if self.capacity_factor is None:
            return tokens
        return min(tokens, expert_capacity(
            tokens * self.top_k, self.n_experts, self.capacity_factor))

    def route(self, params, x):
        """``(sel [T, K] int32, w [T, K])`` for rows ``x [T, H]``."""
        return self._route(params, x)[:2]

    def _route(self, params, x, moved=None):
        """:meth:`route` and the scores ``[T, E]`` of every expert;
        ``moved``: what :attr:`bias_update` has added to the bias."""
        logits = jnp.dot(
            x.astype(jnp.float32), params["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        if self.scoring == "softmax":
            s = jax.nn.softmax(logits, axis=-1)
            _, sel = jax.lax.top_k(s, self.top_k)
        else:
            s = jax.nn.sigmoid(logits)
            b = jax.lax.stop_gradient(params["bias"].astype(jnp.float32))
            if moved is not None:
                b = b + moved
            _, sel = jax.lax.top_k(s + b, self.top_k)
        w = jnp.take_along_axis(s, sel, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return (sel.astype(jnp.int32),
                (w * self.routed_scale).astype(x.dtype), s)

    def _plan(self, sel, capacity):
        """Which (token, choice) pair fills each slot. Pairs are sorted by
        held expert (the others last); slot ``(e, c)`` is the c-th pair of
        held expert ``e``. Returns the slots' pairs and validity, the load
        of every expert and whether a held expert overflowed."""
        first, count = self.held
        local = sel.reshape(-1) - first
        key = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        load = jnp.sum(sel.reshape(-1, 1) == jnp.arange(self.n_experts),
                       axis=0, dtype=jnp.int32)
        size = jax.lax.dynamic_slice_in_dim(load, first, count)
        start = jnp.cumsum(size) - size
        c = jnp.arange(capacity, dtype=jnp.int32)
        slot_valid = (c[None, :] < size[:, None]).reshape(-1)
        slot_pair = order[jnp.minimum(start[:, None] + c[None, :],
                                      order.shape[0] - 1)].reshape(-1)
        return slot_pair, slot_valid, load, jnp.any(size > capacity)

    def _apply(self, params, state, x, training, rng):
        shape = x.shape
        h = x.reshape(-1, shape[-1])
        T = h.shape[0]
        count = self.held[1]
        capacity = self.capacity(T)
        moved = None
        if self.bias_update:
            moved = state.get("bias", jnp.zeros((self.n_experts,)))
        with jax.named_scope("route"):
            sel, w, scores = self._route(params, h, moved)
            slot_pair, slot_valid, load, overflow = self._plan(sel, capacity)
            slot_token = slot_pair // self.top_k
        u = h
        if self.latent:
            with jax.named_scope("experts"):
                u = h @ params["latent"]["down"]
        with jax.named_scope("route"):
            xs = jnp.where(slot_valid[:, None], u[slot_token], 0)
        with jax.named_scope("experts"):
            e = params["experts"]
            xs = xs.reshape(count, capacity, -1)
            pre = jnp.einsum("ecd,edf->ecf", xs, e["w1"])
            if self.activation == "relu2":
                mid = jnp.square(jax.nn.relu(pre))
            else:
                mid = jax.nn.silu(pre) * jnp.einsum("ecd,edf->ecf", xs,
                                                    e["w3"])
            ys = jnp.einsum("ecf,efd->ecd", mid, e["w2"])
        with jax.named_scope("route"):
            w_slot = jnp.where(slot_valid, w.reshape(-1)[slot_pair], 0)
            y = jnp.zeros_like(u).at[slot_token].add(
                w_slot[:, None] * ys.reshape(count * capacity, -1))
            y = jnp.where(overflow, jnp.nan, y)
            loadf = load.astype(jnp.float32)
            counters = {
                ROWS_LOCAL: jnp.sum(jax.lax.dynamic_slice_in_dim(
                    loadf, self.held[0], count)),
                LOAD_MAX_OVER_MEAN: jnp.max(loadf) / jnp.mean(loadf)}
        if self.latent:
            with jax.named_scope("experts"):
                y = y @ params["latent"]["up"]
        state = {"counters": counters}
        if self.bias_update:
            with jax.named_scope("route"):
                state["bias"] = moved + self.bias_update * jnp.sign(
                    jnp.mean(loadf) - loadf) if training else moved
        if self.balance_coef:
            with jax.named_scope("route"):
                # E * sum_e f_e P_e: f_e the tokens' choices of e a token
                # (summing to top_k), P_e the mean score; f has no gradient
                state["losses"] = {BALANCE: self.balance_coef * self.n_experts
                                   * jnp.sum(loadf / T
                                             * jnp.mean(scores, axis=0))}
        if self.shared:
            s, _ = self.shared.apply(params["shared"], {}, h, training, None,
                                     scope="shared")
            y = y + s
        return y.reshape(shape), state
