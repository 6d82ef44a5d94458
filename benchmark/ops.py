"""Operations and bytes the algorithm needs, as functions of shapes.

These count what the mathematics requires, whatever implements it: a
multiply-add is 2 operations, recomputation is never counted, and causal
attention is counted at the half of the score matrix it needs (no credit
for the masked half a kernel may compute).
"""
from __future__ import annotations


def param_count(m: dict) -> int:
    """Parameters of the pre-LN decoder as this repo builds it (tied head,
    no attention biases, sinusoidal positions hold no parameter)."""
    H, F, L, V = (m["hidden_size"], m["filter_size"], m["num_layers"],
                  m["vocab_size"])
    per_layer = 4 * H * H + 2 * H * F + F + H + 4 * H   # attn, ffn, 2 LN
    return V * H + L * per_layer + 2 * H


def matmul_flops_per_token(m: dict) -> int:
    """Forward matmul operations for one token, attention scores apart:
    q, k, v, o projections, the two FFN matmuls and the tied head."""
    H, F, L, V = (m["hidden_size"], m["filter_size"], m["num_layers"],
                  m["vocab_size"])
    return 2 * (L * (4 * H * H + 2 * H * F) + V * H)


def attn_flops(m: dict, q_tokens: int, kv_pairs: int) -> int:
    """Forward attention operations (QK^T and PV) over ``kv_pairs``
    (query, key) pairs in every layer: 4 * head_dim * heads per pair."""
    return 4 * m["hidden_size"] * m["num_layers"] * kv_pairs


def causal_pairs(t: int) -> int:
    """(query, key) pairs a causal pass over ``t`` positions needs."""
    return t * (t + 1) // 2


def train_flops_per_sequence(m: dict, t: int) -> int:
    """Forward + backward of one sequence of ``t`` tokens: the backward
    pass costs twice the forward (two matmuls for each forward one)."""
    fwd = t * matmul_flops_per_token(m) + attn_flops(m, t, causal_pairs(t))
    return 3 * fwd


def flash_train_ops_bytes(m: dict, batch: int, t: int, itemsize: int = 4):
    """One training step's attention, all layers: forward (QK^T, PV) and
    backward (dV, dP, dQ, dK, and the scores once more to rebuild P, which
    the algorithm needs because P is never stored): 2 + 5 matmuls of
    ``2 * head_dim`` operations a pair. Bytes: q, k, v, o read or written
    once forward; q, k, v, o, do read and dq, dk, dv written backward."""
    H, L = m["hidden_size"], m["num_layers"]
    pairs = batch * causal_pairs(t)
    flops = (2 + 5) * 2 * H * pairs * L
    tensor = batch * t * H * itemsize
    return flops, (4 + 8) * tensor * L


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """Least time the chip could take, and which bound binds."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
