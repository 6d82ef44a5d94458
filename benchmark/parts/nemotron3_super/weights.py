"""The weights part of the hybrid Mamba-2 / latent-expert decoder: the
whole tree in the program's layout from the seed, float32, and the expert
layers' selection bias ``b`` set as a trained model's is.

The layout is the program's (``nn.Transformer`` over a layer pattern):
``block{i}`` holds ``ln`` and one of ``ssm`` (``Mamba2Mixer``), ``attn``
(``Attention``) or ``ffn`` (``RoutedExperts``), ``[in, out]`` matrices, the
held experts stacked ``[held, in, out]``. The distributions are
``nemotron_h``'s initialisation: every matrix normal 0.02
(``initializer_range``), the ones that write into the residual stream (the
mixer's ``out_proj``, attention's ``wo``, the experts' and the shared
expert's ``w2``) normal 0.02 / sqrt(88) (``rescale_prenorm_residual`` at the
published depth), ``dt_bias`` the inverse softplus of a log-uniform ``dt``
in [``time_step_min``, ``time_step_max``] floored at ``time_step_floor``,
``A_log = log U(1, 16)``, ``D = 1``, the convolution as a depthwise
``Conv1d``'s default (uniform within 1 / sqrt(K)); norm weights 1 + normal
0.02, and the embedding normal 0.02 * sqrt(H), the benchmark's for its
unscaled-embedding expert decoders (at 0.02 a token's own row is drowned by
the first layers' outputs and routing follows the context).

``b`` is set at set-up by ``noaux_tc``'s own rule, ``b_i <- b_i + u *
sign(mean load - load_i)``, layer by layer over seeded token ids through the
reference's forward pass, as ``parts/instella_moe/weights.py`` does it
(``balance_bias``) and for the reason given there: a random router with
``b = 0`` sends the tokens to a few hot experts. ``b`` has no gradient. The
same tree on every call: the biases of a (model, seed) are kept in the
process."""
from __future__ import annotations

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.parts.instella_moe.weights import balance_bias
from benchmark.weights import seed_key

_BIASES = {}
STD = 0.02                                          # initializer_range


def _reference():
    """The reference part beside this file, whatever root it was laid in."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "reference.py")
    spec = importlib.util.spec_from_file_location("bm_part_nemotron3_ref",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _normal(key, shape, std=STD):
    return std * jax.random.normal(key, shape, jnp.float32)


def _norm(key, n):
    return {"weight": 1.0 + 0.02 * jax.random.normal(key, (n,), jnp.float32)}


def _mixer(m, key, out_std):
    H, nh, P = m["hidden_size"], m["ssm_heads"], m["ssm_head_dim"]
    g, N, K = m["ssm_groups"], m["ssm_state"], m["conv_kernel"]
    inner, conv = nh * P, nh * P + 2 * g * N
    k = jax.random.split(key, 7)
    s = 1.0 / math.sqrt(K)
    lo, hi = math.log(m["time_step_min"]), math.log(m["time_step_max"])
    dt = jnp.maximum(jnp.exp(jax.random.uniform(k[3], (nh,), jnp.float32,
                                                lo, hi)),
                     m["time_step_floor"])
    return {"in_proj": _normal(k[0], (H, inner + conv + nh)),
            "conv_weight": jax.random.uniform(k[1], (K, conv), jnp.float32,
                                              -s, s),
            "conv_bias": jax.random.uniform(k[2], (conv,), jnp.float32, -s, s),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(k[4], (nh,), jnp.float32,
                                                1.0, 16.0)),
            "D": jnp.ones((nh,), jnp.float32),
            "norm": _norm(k[5], inner),
            "out_proj": _normal(k[6], (inner, H), out_std)}


def _attention(m, key, out_std):
    H, d = m["hidden_size"], m["head_dim"]
    k = jax.random.split(key, 4)
    return {"wq": _normal(k[0], (H, m["num_heads"] * d)),
            "wk": _normal(k[1], (H, m["num_kv_heads"] * d)),
            "wv": _normal(k[2], (H, m["num_kv_heads"] * d)),
            "wo": _normal(k[3], (m["num_heads"] * d, H), out_std)}


def _experts(m, key, out_std):
    H, L, F, S = (m["hidden_size"], m["latent"], m["expert_width"],
                  m["shared_width"])
    n = m["experts_held"]
    k = jax.random.split(key, 7)
    return {"router": _normal(k[0], (H, m["n_experts"])),
            "bias": jnp.zeros((m["n_experts"],), jnp.float32),
            "latent": {"down": _normal(k[1], (H, L)),
                       "up": _normal(k[2], (L, H))},
            "experts": {"w1": _normal(k[3], (n, L, F)),
                        "w2": _normal(k[4], (n, F, L), out_std)},
            "shared": {"w1": _normal(k[5], (H, S)),
                       "w2": _normal(k[6], (S, H), out_std)}}


MAKE = {"M": ("ssm", _mixer), "*": ("attn", _attention),
        "E": ("ffn", _experts)}


def _tree(m, key):
    H, V = m["hidden_size"], m["vocab_size"]
    pattern = m["layer_pattern"]
    out_std = STD / math.sqrt(m["published_layers"])
    k = jax.random.split(key, len(pattern) + 3)
    p = {"embed": STD * math.sqrt(H) * jax.random.normal(k[0], (V, H),
                                                         jnp.float32),
         "head": _normal(k[1], (H, V)), "ln_f": _norm(k[2], H)}
    for i, kind in enumerate(pattern):
        name, make = MAKE[kind]
        kl, km = jax.random.split(k[3 + i])
        p[f"block{i}"] = {"ln": _norm(kl, H), name: make(m, km, out_std)}
    return p


def calibrate(params, m, seed, log=None):
    """``{block name: b}`` for this tree: the sample's rows go through the
    reference's layers one after the other, each expert layer's bias
    balanced on its own normed input before the layer's output is taken."""
    ref, cal = _reference(), m["calibration"]
    eps = m["rms_norm_eps"]
    rng = np.random.default_rng([int(seed), 0xCA11B])
    ids = rng.integers(1, m["vocab_size"], size=(cal["rows"], cal["seq_len"]),
                       dtype=np.int64).astype(np.int32)
    balance = jax.jit(lambda s: balance_bias(
        s, m["top_k"], cal["threshold"], cal["u0"], cal["decay"]))
    scores = jax.jit(lambda p, h: ref.router_scores(
        p["ffn"], ref.rms_norm(h, p["ln"], eps)).reshape(-1, m["n_experts"]))
    layer = {kind: jax.jit(lambda p, h, kind=kind: ref.block(p, h, m,
                                                             kind)[0])
             for kind in set(m["layer_pattern"])}
    embed = jax.jit(lambda e, i: jnp.take(e, i, axis=0))
    found = {}
    with jax.default_matmul_precision("highest"):
        hs = [embed(params["embed"], ids[r:r + 1])
              for r in range(ids.shape[0])]
        for i, kind in enumerate(m["layer_pattern"]):
            name, p = f"block{i}", params[f"block{i}"]
            if kind == "E":
                b, worst, it, start = balance(jnp.concatenate(
                    [scores(p, h) for h in hs]))
                p = dict(p, ffn=dict(p["ffn"], bias=b))
                found[name] = np.asarray(b)
                if log:
                    log(f"calibration: {name} max/mean load {float(start):.3f}"
                        f" -> {float(worst):.4f} after {int(it)} updates")
            hs = [layer[kind](p, h) for h in hs]
    return found


def make_params(model_cfg: dict, seed: int, sharding=None,
                log=harness.stamp):
    """The whole tree in one compiled call, then the calibrated biases laid
    into it (computed once a process for a model and seed)."""
    fn = jax.jit(lambda key: _tree(model_cfg, key), out_shardings=sharding)
    params = fn(seed_key(seed))
    key = (json.dumps(model_cfg, sort_keys=True), int(seed))
    if key not in _BIASES:
        _BIASES[key] = calibrate(params, model_cfg, seed, log)
    for name, b in _BIASES[key].items():
        params[name]["ffn"]["bias"] = jnp.asarray(b)
    return params
