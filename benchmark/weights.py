"""Weights from the seed, made on the device in one jitted call, float32.

The benchmark makes them and hands the same tree to the program and to the
reference. The layout (key names, ``[in, out]`` matrices) is the interface
of the model under test; the distributions are the benchmark's own: normal
0.02 for the tied embedding, Glorot-uniform matrices, small normal biases
and LayerNorm offsets so that no leaf has a zero gradient by symmetry.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _glorot(key, shape):
    s = math.sqrt(6.0 / (shape[0] + shape[1]))
    return jax.random.uniform(key, shape, jnp.float32, -s, s)


def _tree(m: dict, key):
    H, F, L, V = (m["hidden_size"], m["filter_size"], m["num_layers"],
                  m["vocab_size"])
    k_embed, k_lnf, k_blocks = jax.random.split(key, 3)

    def ln(k):
        k1, k2 = jax.random.split(k)
        return {"weight": 1.0 + 0.02 * jax.random.normal(k1, (H,)),
                "bias": 0.02 * jax.random.normal(k2, (H,))}

    def one_block(key):
        k = jax.random.split(key, 10)
        return {
            "attn": {"wq": _glorot(k[0], (H, H)), "wk": _glorot(k[1], (H, H)),
                     "wv": _glorot(k[2], (H, H)), "wo": _glorot(k[3], (H, H))},
            "ffn": {"w1": _glorot(k[4], (H, F)),
                    "b1": 0.02 * jax.random.normal(k[5], (F,)),
                    "w2": _glorot(k[6], (F, H)),
                    "b2": 0.02 * jax.random.normal(k[7], (H,))},
            "ln1": ln(k[8]), "ln2": ln(k[9])}

    # every layer in one vmapped draw: the program holds one layer's
    # generators, not num_layers copies (it compiles in seconds)
    blocks = jax.vmap(one_block)(jax.random.split(k_blocks, L))
    p = {"embed": 0.02 * jax.random.normal(k_embed, (V, H), jnp.float32),
         "ln_f": ln(k_lnf)}
    for i in range(L):
        p[f"block{i}"] = jax.tree_util.tree_map(lambda a, i=i: a[i], blocks)
    return p


def make_params(model_cfg: dict, seed: int, sharding=None):
    """The whole tree in one compiled call (``sharding``: where every leaf
    is placed; None is the default device)."""
    fn = jax.jit(lambda key: _tree(model_cfg, key), out_shardings=sharding)
    return fn(seed_key(seed))
