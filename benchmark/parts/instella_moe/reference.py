"""The reference part of the gated-latent-attention expert decoder
(``deepseek_v3`` configs with ``gated_attention``): one chip's share of the
model in plain ``jax.numpy`` from its equations, float32 at the highest
matmul precision. It imports nothing of the program, uses no kernel, sort
or dispatch, and is given the same share: the experts ``held_first ..
held_first + experts_held - 1`` of every expert layer, routed over all
``n_experts``.

    h0      = E[ids]                                  (unscaled, no positions)
    a       = h + Attn(RMS(h)),  h' = a + FFN(RMS(a))   per layer, eps as given
    logits  = RMS_f(h_L) @ W_head                     (untied head)
    Attn(x) : q_h = x Wq (nope + rope a head); [c ; k_pe] = x Wkva;
              [k_nope_h ; v_h] = RMS(c) Wkvb; RoPE on q_h[nope:] and on k_pe
              (one for all heads; YaRN frequencies); k_h = [k_nope_h ; k_pe];
              o_h = softmax(scale * q_h k_h^T + causal) v_h,
              scale = (nope + rope)^-1/2 * mscale^2;
              y = (o * sigmoid(x Wg)) Wo
    FFN(x)  = W2 (silu(W1 x) * W3 x)                   (the first_k_dense layers)
    MoE(x)  : s = sigmoid(x Wr); sel = top_k of s + b (b: no gradient);
              w = s[sel] / (sum s[sel] + 1e-20) * routed_scale;
              y = sum_{i in sel, i held} w_i E_i(x) + Shared(x)
    MTP     : x_i = W_eh [RMS(E[t_{i+1}]) ; RMS(h_L,i)], one block of the
              last layer's kind, RMS of its own, the main embedding and
              head; row i predicts t_{i+2}
    loss    = CE(main) + mtp_loss_weight * CE(mtp), each a mean over its
              targets != pad; Adam, no weight decay

The router's matmul, sigmoid and top-k are float32 at ``highest`` in every
dtype (the configuration's ``precision``). ``dtype=bfloat16`` is the
lower-precision control as in ``benchmark/reference.py``; ``fault`` leaves
part of the mathematics out (``no_routed``, ``no_shared``, ``no_mtp``,
``no_bias``), ``keep_rows`` part of the batch."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as dense

F32 = dense.F32
leaf_norms, flat, leaf_arrays, diff_norms = (
    dense.leaf_norms, dense.flat, dense.leaf_arrays, dense.diff_norms)
FAULTS = ("no_routed", "no_shared", "no_mtp", "no_bias")


def rms_norm(x, p, eps):
    xf = x.astype(F32)
    y = xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * p["weight"].astype(F32)).astype(x.dtype)


def yarn_inv_freq(m):
    """Inverse frequencies of the rotary dims: plain RoPE's, or YaRN's
    blend of interpolated and extrapolated ones by a linear ramp between
    the dims that turn beta_fast and beta_slow times over the original
    length."""
    dim, base, rs = m["qk_rope_head_dim"], m["rope_theta"], m["rope_scaling"]
    freq = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not rs:
        return 1.0 / freq
    turns = lambda n: (dim * math.log(
        rs["original_max_position_embeddings"] / (n * 2 * math.pi))
        / (2 * math.log(base)))
    low = max(math.floor(turns(rs["beta_fast"])), 0)
    high = min(math.ceil(turns(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (1.0 / (rs["factor"] * freq)) * (1 - keep) + (1.0 / freq) * keep


def softmax_scale(m):
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    rs = m["rope_scaling"]
    if rs and rs["factor"] > 1:
        mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= mscale * mscale
    return scale


def rope(x, m):
    """x ``[B, T, heads, rope]``: dim i turns with dim i + rope/2 (the
    pairing of the program's ``rotary_embedding``)."""
    T, half = x.shape[1], x.shape[-1] // 2
    angle = np.arange(T, dtype=np.float64)[:, None] * yarn_inv_freq(m)[None]
    cos = jnp.asarray(np.cos(angle), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), F32)[None, :, None, :]
    a, b = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           -1).astype(x.dtype)


def attention(p, x, m):
    B, T, _ = x.shape
    nh, r = m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    q = (x @ p["wq"]).reshape(B, T, nh, dn + dr)
    kva = x @ p["wkva"]
    c = rms_norm(kva[..., :r], p["kv_norm"], m["rms_norm_eps"])
    kv = (c @ p["wkvb"]).reshape(B, T, nh, dn + dv)
    k_pe = rope(kva[..., r:][:, :, None, :], m)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], m)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (B, T, nh, dr))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(F32) * softmax_scale(m)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, kv[..., dn:]).reshape(B, T, nh * dv)
    if "wg" in p:
        o = o * jax.nn.sigmoid(x @ p["wg"])
    return o @ p["wo"]


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def router_scores(p, x):
    """``sigmoid(x Wr)``, float32 at the highest precision whatever the
    dtype of the pass."""
    return jax.nn.sigmoid(jnp.dot(x.astype(F32), p["router"].astype(F32),
                                  precision=jax.lax.Precision.HIGHEST))


def choose(scores, bias, m):
    """(sel ``[.., K]``, weights ``[.., K]``) from the scores and the
    selection-only bias."""
    _, sel = jax.lax.top_k(scores + jax.lax.stop_gradient(bias.astype(F32)),
                           m["top_k"])
    w = jnp.take_along_axis(scores, sel, axis=-1)
    return sel, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * m["routed_scale"]


def experts(p, x, m, fault=None):
    """The expert FFN of ``x [B, T, H]``: every held expert over every
    row, its own rows picked out by a mask."""
    bias = jnp.zeros_like(p["bias"]) if fault == "no_bias" else p["bias"]
    sel, w = choose(router_scores(p, x), bias, m)
    y = jnp.zeros_like(x)
    if fault != "no_routed":
        def one(y, expert):
            """One held expert over every row; the rows that chose it are
            picked out by its mask."""
            i, ws = expert
            mine = sel == m["held_first"] + i
            wi = jnp.sum(jnp.where(mine, w, 0), axis=-1).astype(x.dtype)
            return y + wi[..., None] * swiglu(ws, x), None
        # a loop over the held experts (a scan: one expert's program, not
        # experts_held copies of it)
        y, _ = jax.lax.scan(one, y, (jnp.arange(m["experts_held"]),
                                     p["experts"]))
    if "shared" in p and fault != "no_shared":
        y = y + swiglu(p["shared"], x)
    return y


def block(p, h, m, fault=None):
    eps = m["rms_norm_eps"]
    a = h + attention(p["attn"], rms_norm(h, p["ln1"], eps), m)
    n = rms_norm(a, p["ln2"], eps)
    if "router" in p["ffn"]:
        return a + experts(p["ffn"], n, m, fault)
    return a + swiglu(p["ffn"], n)


def trunk(params, ids, m, fault=None, remat=False, upto=None):
    """The last block's output (before the final norm); ``upto``: after
    that many blocks."""
    run = lambda p, h: block(p, h, m, fault)
    if remat:
        run = jax.checkpoint(run)
    h = jnp.take(params["embed"], ids, axis=0)
    for i in range(m["num_layers"] if upto is None else upto):
        h = run(params[f"block{i}"], h)
    return h


def mtp_input(params, ids, h, m):
    """``W_eh [RMS(E[t_{i+1}]) ; RMS(h_i)]``; the last row's next token is
    not in ``ids`` (the first is put there: the row predicts nothing)."""
    p, eps = params["mtp"], m["rms_norm_eps"]
    nxt = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
    e = rms_norm(jnp.take(params["embed"], nxt, axis=0), p["enorm"], eps)
    return jnp.concatenate([e, rms_norm(h, p["hnorm"], eps)], -1) \
        @ p["eh_proj"]


def _ce_sum(z, targets, pad):
    z = z.astype(F32)
    lse = jax.scipy.special.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - gold) * (targets != pad).astype(F32))


def loss_sums(params, ids, targets, m, pad, dtype, fault=None):
    """(sum of the main head's token losses, sum of the MTP head's)."""
    low = dense.cast(params, dtype)
    eps = m["rms_norm_eps"]
    h = trunk(low, ids, m, fault, remat=True)
    main = _ce_sum(rms_norm(h, low["ln_f"], eps) @ low["head"], targets, pad)
    if "mtp" not in low or fault == "no_mtp":
        return main, jnp.zeros((), F32)
    x = jax.checkpoint(lambda p, x: block(p, x, m, fault))(
        low["mtp"]["block"], mtp_input(low, ids, h, m))
    z = rms_norm(x, low["mtp"]["ln_f"], eps) @ low["head"]
    return main, _ce_sum(z[:, :-1], targets[:, 1:], pad)


def logits(params, ids, m):
    """The main head's logits, float32 at the highest precision."""
    with jax.default_matmul_precision("highest"):
        h = trunk(params, ids, m)
        return (rms_norm(h, params["ln_f"], m["rms_norm_eps"])
                @ params["head"]).astype(F32)


def train_steps(params, batches, m, opt, pad=0, row_block=1, dtype=F32,
                state_dtype=None, keep_rows=None, first_grad=None,
                keep_first_grad=False, devices=None, log=None, fault=None):
    """The contract of ``harness.load_parts``, one device. The gradient is
    accumulated over blocks of ``row_block`` rows, each loss divided by
    its count of targets over the WHOLE batch; between a step's update
    and the next the moments wait on the host, so that the device holds
    weights, one gradient and one block's activations."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}: {FAULTS}")
    state_dtype = dtype if state_dtype is None else state_dtype
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])
    lam = m.get("mtp_loss_weight", 0.0) if m.get("mtp") else 0.0
    precision = "highest" if dtype == F32 else "default"

    def block_grad(p, acc, ids, tg, n_main, n_mtp):
        def loss(p):
            a, b = loss_sums(p, ids, tg, m, pad, dtype, fault)
            return a / n_main + lam * b / n_mtp
        with jax.default_matmul_precision(precision):
            value, g = jax.value_and_grad(loss)(p)
        return value, jax.tree_util.tree_map(jnp.add, acc, g)

    block_grad = jax.jit(block_grad, donate_argnums=1)
    update = jax.jit(lambda p, g, mo, ve, t: dense.adam_update(
        p, g, mo, ve, t, lr, b1, b2, eps), donate_argnums=(0, 2, 3))
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))

    params = dense.cast(params, state_dtype)
    start = jax.device_get(params)
    mom = vel = None
    losses, out = [], {}
    for step, (ids, tg) in enumerate(batches, 1):
        ids, tg = (np.asarray(a, np.int32)[:keep_rows] for a in (ids, tg))
        n_main = max(float(np.sum(tg != pad)), 1.0)
        n_mtp = max(float(np.sum(tg[:, 1:] != pad)), 1.0)
        grads, total = zeros(params), 0.0
        for r in range(0, ids.shape[0], row_block):
            value, grads = block_grad(params, grads, ids[r:r + row_block],
                                      tg[r:r + row_block], n_main, n_mtp)
            total += float(value)
        losses.append(total)
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
