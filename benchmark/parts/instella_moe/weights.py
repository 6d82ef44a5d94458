"""The weights part of the gated-latent-attention expert decoder: the whole
tree in the program's layout from the seed, float32, and the routers'
selection bias ``b`` (``e_score_correction_bias``) set as a trained model's
is.

The layout is the program's (``nn.Transformer`` with ``LatentAttention`` and
``RoutedExperts``): ``[in, out]`` matrices, experts stacked ``[held, in,
out]``, the MTP module under ``mtp``. The distributions are the benchmark's
own: a normal embedding of standard deviation 0.02 * sqrt(H) (what the dense
cell's scaled embeddings come to: this model leaves its embeddings unscaled,
and at 0.02 the token's own row drowns in the first layers' outputs, so that
routing follows the context, neighbours route alike and a step's loads
scatter four times wider than independent tokens would), Glorot-uniform
matrices (an expert's by its own fans), norm weights 1 + normal 0.02.

``b`` is NOT random and not zero. A deployed job of this model runs with
balanced experts because ``noaux_tc`` moves ``b`` against every expert's
load each step; a random router with ``b = 0`` sends the tokens to a few hot
experts, and which of them are among the ones held here changes with the
seed (the refused cells of PR 28 and 29). So set-up runs the published rule

    b_i <- b_i + u * sign(mean load - load_i)

with a shrinking ``u``, layer by layer, over seeded uniform token ids (the
cells' own distribution) through the reference's forward pass, until the
layer's fullest expert is within ``threshold`` of the mean on that sample.
``b`` has no gradient, so Adam leaves it where set-up put it. The same tree
on every call: the biases of a (model, seed) are kept in the process."""
from __future__ import annotations

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.weights import seed_key

_BIASES = {}


def _reference():
    """The reference part beside this file, whatever root it was laid in."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "reference.py")
    spec = importlib.util.spec_from_file_location("bm_part_instella_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _glorot(key, shape):
    s = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -s, s)


def _norm(key, n):
    return {"weight": 1.0 + 0.02 * jax.random.normal(key, (n,), jnp.float32)}


def _attention(m, key):
    H, nh, r = m["hidden_size"], m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    k = jax.random.split(key, 6)
    return {"wq": _glorot(k[0], (H, nh * (dn + dr))),
            "wkva": _glorot(k[1], (H, r + dr)),
            "kv_norm": _norm(k[2], r),
            "wkvb": _glorot(k[3], (r, nh * (dn + dv))),
            "wg": _glorot(k[4], (H, nh * dv)),
            "wo": _glorot(k[5], (nh * dv, H))}


def _swiglu(key, H, F, lead=()):
    k = jax.random.split(key, 3)
    return {"w1": _glorot(k[0], lead + (H, F)),
            "w3": _glorot(k[1], lead + (H, F)),
            "w2": _glorot(k[2], lead + (F, H))}


def _block(m, key, dense):
    H = m["hidden_size"]
    k = jax.random.split(key, 6)
    if dense:
        ffn = _swiglu(k[1], H, m["dense_width"])
    else:
        F = m["expert_width"]
        ffn = {"router": _glorot(k[1], (H, m["n_experts"])),
               "bias": jnp.zeros((m["n_experts"],), jnp.float32),
               "experts": _swiglu(k[2], H, F, (m["experts_held"],)),
               "shared": _swiglu(k[3], H, m["n_shared"] * F)}
    return {"attn": _attention(m, k[0]), "ffn": ffn,
            "ln1": _norm(k[4], H), "ln2": _norm(k[5], H)}


def _tree(m, key):
    H, V, L = m["hidden_size"], m["vocab_size"], m["num_layers"]
    k = jax.random.split(key, L + 4)
    p = {"embed": 0.02 * math.sqrt(H) * jax.random.normal(k[0], (V, H),
                                                          jnp.float32),
         "head": _glorot(k[1], (H, V)), "ln_f": _norm(k[2], H)}
    for i in range(L):
        p[f"block{i}"] = _block(m, k[4 + i], i < m["first_k_dense"])
    if m.get("mtp"):
        km = jax.random.split(k[3], 5)
        p["mtp"] = {"enorm": _norm(km[0], H), "hnorm": _norm(km[1], H),
                    "eh_proj": _glorot(km[2], (2 * H, H)),
                    "block": _block(m, km[3], L <= m["first_k_dense"]),
                    "ln_f": _norm(km[4], H)}
    return p


# ------------------------------------------------------------ the balance

def balance_bias(scores, top_k: int, threshold: float, u0: float = 0.02,
                 decay: float = 0.995, u_min: float = 1e-5,
                 max_steps: int = 20000):
    """The selection bias that evens the load of ``scores [N, E]`` under
    top-k of ``scores + b``: the published update with a step ``u`` that
    shrinks from ``u0`` by ``decay`` an iteration down to ``u_min``, run
    until the fullest expert's load is within ``threshold`` of the mean (or
    ``max_steps``). Returns ``(b, max over mean at the end, iterations, max
    over mean at b = 0)``."""
    E = scores.shape[-1]

    def spread(b):
        _, sel = jax.lax.top_k(scores + b, top_k)
        load = jnp.sum(sel.reshape(-1, 1) == jnp.arange(E), axis=0,
                       dtype=jnp.float32)
        return load, jnp.max(load) / jnp.mean(load)

    def go(state):
        b, it, _ = state
        load, _ = spread(b)
        u = jnp.maximum(u0 * decay ** it.astype(jnp.float32), u_min)
        b = b + u * jnp.sign(jnp.mean(load) - load)
        return b, it + 1, spread(b)[1]

    b0 = jnp.zeros((E,), jnp.float32)
    start = spread(b0)[1]
    b, it, worst = jax.lax.while_loop(
        lambda s: (s[2] > threshold) & (s[1] < max_steps), go,
        (b0, jnp.zeros((), jnp.int32), start))
    return b, worst, it, start


def calibrate(params, m, seed, log=None):
    """``{path of an expert layer's ffn: b}`` for this tree: the sample's
    rows go through the reference's layers one after the other, each expert
    layer's bias balanced on its own normed input before the layer's output
    is taken."""
    ref, cal = _reference(), m["calibration"]
    eps = m["rms_norm_eps"]
    rng = np.random.default_rng([int(seed), 0xCA11B])
    ids = rng.integers(1, m["vocab_size"], size=(cal["rows"], cal["seq_len"]),
                       dtype=np.int64).astype(np.int32)
    balance = jax.jit(lambda s: balance_bias(
        s, m["top_k"], cal["threshold"], cal["u0"], cal["decay"]))

    @jax.jit
    def before_ffn(p, h):
        with jax.default_matmul_precision("highest"):
            a = h + ref.attention(p["attn"],
                                  ref.rms_norm(h, p["ln1"], eps), m)
            n = ref.rms_norm(a, p["ln2"], eps)
            s = ref.router_scores(p["ffn"], n) if "router" in p["ffn"] \
                else None
            return a, n, s

    @jax.jit
    def after_ffn(ffn, a, n):
        with jax.default_matmul_precision("highest"):
            return a + (ref.experts(ffn, n, m) if "router" in ffn
                        else ref.swiglu(ffn, n))

    def through(p, hs, name):
        """One block over the rows, one row at a time; its bias first."""
        parts = [before_ffn(p, h) for h in hs]
        ffn = p["ffn"]
        if "router" in ffn:
            scores = jnp.concatenate(
                [s.reshape(-1, s.shape[-1]) for _, _, s in parts])
            b, worst, it, start = balance(scores)
            ffn = dict(ffn, bias=b)
            found[name] = np.asarray(b)
            if log:
                log(f"calibration: {name} max/mean load {float(start):.3f} "
                    f"-> {float(worst):.4f} after {int(it)} updates")
        return [after_ffn(ffn, a, n) for a, n, _ in parts]

    found = {}
    embed = jax.jit(lambda e, i: jnp.take(e, i, axis=0))
    hs = [embed(params["embed"], ids[r:r + 1]) for r in range(ids.shape[0])]
    for i in range(m["num_layers"]):
        hs = through(params[f"block{i}"], hs, f"block{i}")
    if m.get("mtp"):
        mtp_in = jax.jit(lambda p, i, h: ref.mtp_input(p, i, h, m))
        shared = {k: params[k] for k in ("embed", "mtp")}
        xs = [mtp_in(shared, ids[r:r + 1], h) for r, h in enumerate(hs)]
        through(params["mtp"]["block"], xs, "mtp/block")
    return found


def _with_biases(params, found):
    for name, b in found.items():
        node = params
        for part in name.split("/"):
            node = node[part]
        node["ffn"]["bias"] = jnp.asarray(b)
    return params


def make_params(model_cfg: dict, seed: int, sharding=None,
                log=harness.stamp):
    """The whole tree in one compiled call, then the calibrated biases laid
    into it (computed once a process for a model and seed)."""
    fn = jax.jit(lambda key: _tree(model_cfg, key), out_shardings=sharding)
    params = fn(seed_key(seed))
    key = (json.dumps(model_cfg, sort_keys=True), int(seed))
    if key not in _BIASES:
        _BIASES[key] = calibrate(params, model_cfg, seed, log)
    return _with_biases(params, _BIASES[key])
