"""The reference part of the hybrid Mamba-2 / latent-expert decoder
(``nemotron_h`` configs): one chip's share of the model in plain
``jax.numpy`` from its equations, float32 at the highest matmul precision.
It imports nothing of the program, uses no kernel, chunked algorithm, sort
or dispatch, and is given the same share: the first ``ssm_groups`` groups
of every mixer's heads, ``num_heads`` query heads and the ``num_kv_heads``
they read, experts ``held_first .. held_first + experts_held - 1`` of every
expert layer, routed over all ``n_experts``.

    h0     = E[ids]                              (unscaled, no positions)
    h      = h + Layer_i(RMS(h))   for the kind of character i of the
                                   layer pattern
    logits = RMS_f(h_L) @ W_head                 (untied head)
    M(x)   : [z, xBC, dt] = x W_in;  xBC = silu(conv_K(xBC) + b);
             dt = softplus(dt + dt_bias);  A = -exp(A_log);
             per head h of group G(h) = h // (heads / groups), from S = 0:
             S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T,  y_t = S_t C_t + D x_t
             (position by position: ``lax.scan``);
             y = RMS over each group's channels(y * silu(z)) * w;  y W_out
    *(x)   : q_h = (x Wq)_h, k, v = (x Wk), (x Wv) for the group's KV head,
             o_h = softmax(q_h k^T / sqrt(d) + causal) v;  o Wo  (no RoPE)
    E(x)   : s = sigmoid(x Wr) over all experts; sel = top_k of s + b (b
             without gradient); w = s[sel] / sum s[sel] * routed_scale;
             u = x W_down;  y = (sum_{i in sel, held} w_i W2_i relu(W1_i u)^2)
             W_up + W2_s relu(W1_s x)^2
    loss   = CE, the mean over the targets != pad; Adam, no weight decay;
             after each step b_i += bias_update * sign(mean load - load_i),
             load_i the rows that step's forward sent to expert i

The recurrence goes chunk by chunk (``chunk_size`` positions, one
checkpoint a chunk, the state carried between them), so that its gradient
holds one state a chunk and fits at 4,096 positions; attention one block of
``Q_BLOCK`` queries at a time; the held experts as a loop with masks. The
router's matmul, sigmoid and top-k are float32 at ``highest`` in every
dtype (the configuration's ``precision``). ``dtype=bfloat16`` is the
lower-precision control; ``fault`` leaves part of the mathematics out:
``half_batch`` (the second half of every row's targets), ``no_routed``
(the held experts' output), ``chunk_reset`` (the state is not passed from
one chunk to the next), ``no_conv`` (the causal convolution),
``ungrouped_norm`` (one RMS over all the held channels in place of one a
group)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as dense

F32 = dense.F32
leaf_norms, flat, leaf_arrays, diff_norms = (
    dense.leaf_norms, dense.flat, dense.leaf_arrays, dense.diff_norms)
FAULTS = ("half_batch", "no_routed", "chunk_reset", "no_conv",
          "ungrouped_norm")
Q_BLOCK = 512


def rms_norm(x, p, eps, groups=1):
    """RMS over each of ``groups`` equal parts of the last dim, then the
    weight."""
    xf = x.astype(F32)
    xg = xf.reshape(*xf.shape[:-1], groups, -1)
    y = xg / jnp.sqrt(jnp.mean(jnp.square(xg), axis=-1, keepdims=True) + eps)
    return (y.reshape(xf.shape) * p["weight"].astype(F32)).astype(x.dtype)


def recurrence(x, dt, A, B, C, chunk, reset=False):
    """y ``[b, T, heads, P]`` of the per-head state recurrence, position by
    position, from a zero state; ``reset``: from zero at every chunk."""
    b, T, nh, P = x.shape
    hg = nh // B.shape[2]
    Bh, Ch = jnp.repeat(B, hg, axis=2), jnp.repeat(C, hg, axis=2)
    pad = -T % chunk
    c = (T + pad) // chunk

    def chunks(a):      # [b, T, ...] -> [c, chunk, b, ...]
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(b, c, chunk, *a.shape[2:]), (1, 2),
                            (0, 1))

    def step(S, inp):
        x_t, dt_t, b_t, c_t = inp
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t)

    @jax.checkpoint
    def one_chunk(S, inp):
        if reset:
            S = jnp.zeros_like(S)
        return jax.lax.scan(step, S, inp)

    S0 = jnp.zeros((b, nh, P, B.shape[3]), x.dtype)
    _, y = jax.lax.scan(one_chunk, S0, tuple(map(chunks, (x, dt, Bh, Ch))))
    return jnp.moveaxis(y, (0, 1), (1, 2)).reshape(b, c * chunk, nh, P)[:, :T]


def mixer(p, x, m, fault=None):
    b, T, _ = x.shape
    nh, P, g, N, K = (m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"],
                      m["ssm_state"], m["conv_kernel"])
    inner = nh * P
    zxbcdt = x @ p["in_proj"]
    z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * g * N]
    dt = zxbcdt[..., 2 * inner + 2 * g * N:]
    if fault != "no_conv":
        xp = jnp.pad(xbc, [(0, 0), (K - 1, 0), (0, 0)])
        xbc = sum(xp[:, k:k + T] * p["conv_weight"][k] for k in range(K)) \
            + p["conv_bias"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :inner].reshape(b, T, nh, P)
    B = xbc[..., inner:inner + g * N].reshape(b, T, g, N)
    C = xbc[..., inner + g * N:].reshape(b, T, g, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = recurrence(xs, dt, A, B, C, m["chunk_size"],
                   reset=fault == "chunk_reset") + p["D"][:, None] * xs
    y = y.reshape(b, T, inner) * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], m["rms_norm_eps"],
                 1 if fault == "ungrouped_norm" else g)
    return y @ p["out_proj"]


def attention(p, x, m):
    B, T, _ = x.shape
    nh, kvh, d = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = (x @ p["wq"]).reshape(B, T, kvh, nh // kvh, d)
    k = (x @ p["wk"]).reshape(B, T, kvh, d)
    v = (x @ p["wv"]).reshape(B, T, kvh, d)
    qb = min(Q_BLOCK, T)
    cols = jnp.arange(T)

    def block(r0):
        rows = r0 + jnp.arange(qb)
        qr = jax.lax.dynamic_slice_in_dim(q, r0, qb, axis=1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qr, k).astype(F32) * d ** -0.5
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        prob = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", prob, v).reshape(B, qb, nh * d)

    o = jax.lax.map(jax.checkpoint(block), jnp.arange(0, T, qb))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, nh * d) @ p["wo"]


def relu2(p, x):
    return jnp.square(jax.nn.relu(x @ p["w1"])) @ p["w2"]


def router_scores(p, x):
    """``sigmoid(x Wr)``, float32 at the highest precision whatever the
    dtype of the pass."""
    return jax.nn.sigmoid(jnp.dot(x.astype(F32), p["router"].astype(F32),
                                  precision=jax.lax.Precision.HIGHEST))


def experts(p, x, m, fault=None, moved=None):
    """The held experts' part of the layer in the latent, every held expert
    over every row with its own rows picked out by a mask, plus the shared
    expert; and the rows sent to each of all the experts. ``moved``: what
    the bias's updates have added to ``p["bias"]``."""
    s = router_scores(p, x)
    b = p["bias"].astype(F32) + (0.0 if moved is None else moved)
    _, sel = jax.lax.top_k(s + jax.lax.stop_gradient(b), m["top_k"])
    load = jnp.bincount(sel.reshape(-1), length=m["n_experts"]).astype(F32)
    w = jnp.take_along_axis(s, sel, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * m["routed_scale"]
    u = x @ p["latent"]["down"]
    y = jnp.zeros_like(u)
    if fault != "no_routed":
        def one(y, expert):
            i, ws = expert
            wi = jnp.sum(jnp.where(sel == m["held_first"] + i, w, 0),
                         axis=-1).astype(x.dtype)
            return y + wi[..., None] * relu2(ws, u), None
        y, _ = jax.lax.scan(one, y, (jnp.arange(m["experts_held"]),
                                     p["experts"]))
    return y @ p["latent"]["up"] + relu2(p["shared"], x), load


def block(p, h, m, kind, fault=None, moved=None):
    """``h + Layer(RMS(h))`` and, for an expert layer, its load."""
    n = rms_norm(h, p["ln"], m["rms_norm_eps"])
    if kind == "M":
        return h + mixer(p["ssm"], n, m, fault), None
    if kind == "*":
        return h + attention(p["attn"], n, m), None
    y, load = experts(p["ffn"], n, m, fault, moved)
    return h + y, load


def hidden(params, ids, m, fault=None, moved=None):
    """The residual stream after every layer, and ``{block name: load}``
    of the expert layers; ``moved``: ``{block name: what the updates have
    added to its bias}``."""
    h, loads = jnp.take(params["embed"], ids, axis=0), {}
    for i, kind in enumerate(m["layer_pattern"]):
        name = f"block{i}"
        h, load = jax.checkpoint(lambda p, h, b, kind=kind: block(
            p, h, m, kind, fault, b))(params[name], h, (moved or {}).get(name))
        if load is not None:
            loads[name] = load
    return h, loads


def _ce_sum(z, targets, pad):
    z = z.astype(F32)
    lse = jax.scipy.special.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - gold) * (targets != pad).astype(F32))


def loss_sum(params, ids, targets, m, pad, dtype, fault=None, moved=None,
             loads=False):
    """The summed loss; with ``loads``, ``(loss, the expert layers'
    loads)``."""
    low = dense.cast(params, dtype)
    h, load = hidden(low, ids, m, fault, moved)
    z = rms_norm(h, low["ln_f"], m["rms_norm_eps"]) @ low["head"]
    return (_ce_sum(z, targets, pad), load) if loads else _ce_sum(
        z, targets, pad)


def train_steps(params, batches, m, opt, pad=0, row_block=1, dtype=F32,
                state_dtype=None, keep_rows=None, first_grad=None,
                keep_first_grad=False, devices=None, log=None, fault=None):
    """The contract of ``harness.load_parts``, one device. The batch goes
    as ONE block (``row_block`` must hold it); the loss is the mean over the
    batch's targets. Between a step's update and the next the moments wait
    on the host, so that the device holds weights, one gradient and one
    block's activations."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}: {FAULTS}")
    state_dtype = dtype if state_dtype is None else state_dtype
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])
    precision = "highest" if dtype == F32 else "default"

    def grad(p, moved, ids, tg, n):
        def loss(p):
            value, load = loss_sum(p, ids, tg, m, pad, dtype, fault, moved,
                                   loads=True)
            return value / n, load
        with jax.default_matmul_precision(precision):
            return jax.value_and_grad(loss, has_aux=True)(p)

    grad = jax.jit(grad)
    update = jax.jit(lambda p, g, mo, ve, t: dense.adam_update(
        p, g, mo, ve, t, lr, b1, b2, eps), donate_argnums=(0, 2, 3))
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))

    params = dense.cast(params, state_dtype)
    start = jax.device_get(params)
    mom = vel = None
    losses, out = [], {}
    # what noaux_tc's update has added to each expert layer's bias (the
    # program keeps it in the model's state, beside the parameters)
    moved = {f"block{i}": jnp.zeros((m["n_experts"],), F32)
             for i, kind in enumerate(m["layer_pattern"]) if kind == "E"}
    nudge = jax.jit(lambda b, load: b + m["bias_update"] * jnp.sign(
        jnp.mean(load) - load))
    for step, (ids, tg) in enumerate(batches, 1):
        ids, tg = (np.array(a, np.int32)[:keep_rows] for a in (ids, tg))
        if ids.shape[0] > row_block:
            raise ValueError(f"{ids.shape[0]} rows in blocks of {row_block}: "
                             "the reference takes the batch in one")
        if fault == "half_batch":
            tg[:, tg.shape[1] // 2:] = pad
        n = max(float(np.sum(tg != pad)), 1.0)
        (value, loads), grads = grad(params, moved, ids, tg, n)
        losses.append(float(value))
        moved = {k: nudge(b, loads[k]) for k, b in moved.items()}
        if step == 1:
            out["grad_norms"] = flat(leaf_norms(grads))
            if first_grad is not None:
                out["grad_diff_norms"] = diff_norms(first_grad,
                                                    leaf_arrays(grads))
            if keep_first_grad:
                out["first_grad"] = jax.device_get(leaf_arrays(grads))
        mom, vel = (zeros(params), zeros(params)) if mom is None else (
            jax.device_put(mom), jax.device_put(vel))
        params, mom, vel = update(params, grads, mom, vel,
                                  jnp.asarray(float(step), F32))
        del grads
        mom, vel = jax.device_get(mom), jax.device_get(vel)
        if log:
            log(f"reference step {step} loss {losses[-1]:.6f}")
    out["delta_norms"] = flat(leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(F32) - jnp.asarray(b, F32), params, start)))
    return dict(out, losses=losses)
