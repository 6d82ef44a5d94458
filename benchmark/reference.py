"""The plain reference: a pre-LN decoder-only Transformer in straightforward
``jax.numpy``, written from the model's equations. It imports nothing of the
program, uses no kernel, cache or batching trick, and runs in float32 at the
highest matmul precision. ``dtype=jnp.bfloat16`` is the lower-precision
control: weights, activations, gradients, Adam's moments and the update all
in bfloat16, default precision; with ``state_dtype=float32`` beside it only
the forward and backward passes are in bfloat16 and the weights, the
gradients as Adam gets them, the moments and the update stay float32.

    h0      = E[ids] * sqrt(H) + PE                 (sinusoidal PE)
    a       = h + Attn(LN1(h)),  h' = a + FFN(LN2(a))   per layer
    logits  = LN_f(h_L) @ E^T                       (tied head)
    Attn(x) = merge(softmax(q k^T / sqrt(d) + causal) v) @ Wo
    FFN(x)  = act(x W1 + b1) W2 + b2                (ReLU or tanh-GELU)
    loss    = mean over targets != pad of (logsumexp(logits) - logits[t])
    Adam    : m, v moments with bias correction, no weight decay

The layers are one ``lax.scan`` over stacked weights (``stack``). The training
reference accumulates gradients over blocks of rows so that it fits on one
chip beside nothing else; each layer is rematerialised.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def sinusoid(length: int, hidden: int):
    pos = np.arange(length, dtype=np.float64)[:, None]
    dim = np.arange(hidden // 2, dtype=np.float64)[None, :]
    angle = pos / np.power(10000.0, 2.0 * dim / hidden)
    return np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)


def layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["weight"] + p["bias"]


def _act(name):
    if name == "relu":
        return lambda x: jnp.maximum(x, 0)
    if name == "gelu":      # tanh approximation (GPT-2's gelu_new)
        return lambda x: 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(f"reference has no activation {name!r}")


def block(p, h, m):
    B, T, H = h.shape
    nh = m["num_heads"]
    d = H // nh
    eps = m["layer_norm_eps"]

    def heads(x):
        return x.reshape(B, T, nh, d).transpose(0, 2, 1, 3)

    n = layer_norm(h, p["ln1"], eps)
    q, k, v = (heads(n @ p["attn"][w]) for w in ("wq", "wk", "wv"))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s.astype(F32), -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(h.dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
    h = h + o.transpose(0, 2, 1, 3).reshape(B, T, H) @ p["attn"]["wo"]
    n = layer_norm(h, p["ln2"], eps)
    f = _act(m["ffn_activation"])(n @ p["ffn"]["w1"] + p["ffn"]["b1"])
    return h + f @ p["ffn"]["w2"] + p["ffn"]["b2"]


def stack(params, consume=False):
    """The same weights with the layers' leaves stacked along a new leading
    axis: ``{"embed", "ln_f", "blocks": {"attn": {"wq": [L, H, H], ...}}}``.
    One ``lax.scan`` over that axis then compiles one layer, not
    ``num_layers`` copies of it (a sixth of a minute instead of more than
    one, and an executable of megabytes instead of 170 MB). Leaf by leaf;
    ``consume`` takes the layers out of ``params`` and drops each leaf once
    it is stacked, so the peak is the weights plus one stacked leaf."""
    n = sum(1 for k in params if k.startswith("block"))
    take = params.pop if consume else params.get
    flat_blocks = [jax.tree_util.tree_flatten(take(f"block{i}"))
                   for i in range(n)]
    treedef = flat_blocks[0][1]
    leaves = [layer for layer, _ in flat_blocks]
    stacked = []
    for j in range(treedef.num_leaves):
        stacked.append(jnp.stack([layer[j] for layer in leaves]))
        if consume:
            for layer in leaves:
                layer[j] = None
    return {"embed": params["embed"], "ln_f": params["ln_f"],
            "blocks": jax.tree_util.tree_unflatten(treedef, stacked)}


def hidden(sp, ids, m, remat=False):
    """LN_f(h_L) for ids ``[B, T]`` from stacked weights (``stack``)."""
    H = m["hidden_size"]
    dt = sp["embed"].dtype
    h = jnp.take(sp["embed"], ids, axis=0) * jnp.asarray(math.sqrt(H), dt)
    h = h + jnp.asarray(sinusoid(ids.shape[1], H), dt)
    run = (lambda p, h: block(p, h, m))
    if remat:
        run = jax.checkpoint(run)
    h, _ = jax.lax.scan(lambda h, p: (run(p, h), None), h, sp["blocks"])
    return layer_norm(h, sp["ln_f"], m["layer_norm_eps"])


def logits(sp, ids, m):
    return (hidden(sp, ids, m) @ sp["embed"].T).astype(F32)


def cast(params, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), params)


def _precision(dtype):
    return "highest" if dtype == F32 else "default"


# ----------------------------------------------------------------- training

def loss_sum(params, ids, targets, m, pad, dtype):
    """(sum of token losses, count of targets that are not ``pad``)."""
    low = cast(params, dtype)
    z = (hidden(low, ids, m, remat=True) @ low["embed"].T).astype(F32)
    lse = jax.scipy.special.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    valid = (targets != pad).astype(F32)
    return jnp.sum((lse - gold) * valid), jnp.sum(valid)


def adam_update(params, grads, mom, vel, t, lr, b1, b2, eps):
    mom = jax.tree_util.tree_map(
        lambda a, g: (b1 * a + (1 - b1) * g).astype(a.dtype), mom, grads)
    vel = jax.tree_util.tree_map(
        lambda a, g: (b2 * a + (1 - b2) * g * g).astype(a.dtype), vel, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda w, a, b: (w - lr * (a / c1) / (jnp.sqrt(b / c2) + eps)
                         ).astype(w.dtype), params, mom, vel)
    return params, mom, vel


def leaf_norms(tree):
    """{path: l2 norm} as float32 scalars (a tree of the same shape)."""
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))), tree)


def stacked_leaf_norms(sp):
    """Norms of a stacked tree, one per layer for the stacked leaves."""
    per_layer = lambda a: jnp.sqrt(jnp.sum(
        jnp.square(a.astype(F32)).reshape(a.shape[0], -1), axis=1))
    return {"embed": leaf_norms(sp["embed"]), "ln_f": leaf_norms(sp["ln_f"]),
            "blocks": jax.tree_util.tree_map(per_layer, sp["blocks"])}


def flat_stacked(norms):
    """``{"block3/attn/wq": float}``: the names of the unstacked tree."""
    out = {k: v for k, v in flat({"embed": norms["embed"],
                                  "ln_f": norms["ln_f"]}).items()}
    leaves = jax.tree_util.tree_flatten_with_path(norms["blocks"])[0]
    for path, vec in leaves:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        for i, v in enumerate(np.asarray(vec)):
            out[f"block{i}/{name}"] = float(v)
    return out


def stacked_leaf_arrays(sp):
    """``{"block3/attn/wq": array}``: the leaves of a stacked tree under
    the names of the unstacked one."""
    out = leaf_arrays({"embed": sp["embed"], "ln_f": sp["ln_f"]})
    for path, a in jax.tree_util.tree_flatten_with_path(sp["blocks"])[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        for i in range(a.shape[0]):
            out[f"block{i}/{name}"] = a[i]
    return out


def leaf_arrays(tree):
    """``{"block3/attn/wq": array}`` from a tree of arrays."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): a
            for path, a in leaves}


def diff_norms(got: dict, ref: dict):
    """``{leaf: l2 norm of got - ref}`` over the leaves of ``got``."""
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(a, F32) - ref[k].astype(F32))))) for k, a in got.items()}


def train_steps(params, batches, m, opt, pad=0, row_block=2, dtype=F32,
                state_dtype=None, keep_rows=None, first_grad=None,
                keep_first_grad=False, devices=None, log=None):
    """Follow ``len(batches)`` Adam steps from ``params``.

    ``batches``: list of (ids[B, T], targets[B, T]) int arrays. Returns
    ``{"losses": [...], "grad_norms": {leaf: norm of the first gradient},
    "delta_norms": {leaf: norm of params_end - params_start}}`` with the
    norms as flat ``{path: float}`` dicts. ``first_grad``: ``{leaf: array}``
    of another run's first gradient (the program's, or this function's own
    under ``keep_first_grad``); the result then has ``grad_diff_norms``, the
    norm of its difference from this run's by leaf. ``devices``: the rows of
    a block are spread over them, the weights held on each (nothing else
    changes: the same ``jax.numpy`` under one ``jit``). ``keep_rows`` (a
    fault: rows per batch that are used, the mean taken over them).
    """
    state_dtype = dtype if state_dtype is None else state_dtype
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])

    @jax.jit
    def grad_block(p, ids, tg):
        with jax.default_matmul_precision(_precision(dtype)):
            (s, n), g = jax.value_and_grad(
                lambda p: loss_sum(p, ids, tg, m, pad, dtype),
                has_aux=True)(p)
        return s, n, g

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    scale = jax.jit(lambda g, n: jax.tree_util.tree_map(
        lambda x: x / n, g), donate_argnums=0)
    update = jax.jit(lambda p, g, mo, ve, t: adam_update(
        p, g, mo, ve, t, lr, b1, b2, eps), donate_argnums=(2, 3))
    norms = jax.jit(stacked_leaf_norms)
    delta = jax.jit(lambda a, b: stacked_leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))

    # the control keeps its weights, moments and update in ``state_dtype``
    params = cast(stack(params, consume=True), state_dtype)
    put_rows = lambda a: a
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(devices), ("rows",))
        params = jax.device_put(params, NamedSharding(mesh, P()))
        # a block that does not divide over the devices stays whole
        put_rows = lambda a: a if a.shape[0] % len(devices) else (
            jax.device_put(a, NamedSharding(mesh, P("rows"))))
    start = params
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    vel = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms, extra = [], None, {}
    for step, (ids, tg) in enumerate(batches, 1):
        ids, tg = np.asarray(ids, np.int32), np.asarray(tg, np.int32)
        if keep_rows is not None:
            ids, tg = ids[:keep_rows], tg[:keep_rows]
        tot_s = tot_n = grads = None
        for r in range(0, ids.shape[0], row_block):
            s, n, g = grad_block(params, put_rows(ids[r:r + row_block]),
                                 put_rows(tg[r:r + row_block]))
            if grads is None:
                tot_s, tot_n, grads = s, n, g
            else:
                tot_s, tot_n, grads = tot_s + s, tot_n + n, add(grads, g)
        grads = scale(grads, tot_n)
        losses.append(float(tot_s / tot_n))
        if grad_norms is None:
            grad_norms = flat_stacked(norms(grads))
            if first_grad is not None:
                extra["grad_diff_norms"] = diff_norms(
                    first_grad, stacked_leaf_arrays(grads))
            if keep_first_grad:
                extra["first_grad"] = stacked_leaf_arrays(grads)
        new, mom, vel = update(params, grads, mom, vel,
                               jnp.asarray(float(step), F32))
        del grads
        params = new
        if log:
            log(f"reference step {step} loss {losses[-1]:.6f}")
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": flat_stacked(delta(params, start)), **extra}


def flat(tree):
    """{"block0/attn/wq": float} from a tree of scalars."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v)
            for path, v in leaves}
