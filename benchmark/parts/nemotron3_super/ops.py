"""The ops part of the hybrid Mamba-2 / latent-expert decoder: operations
and bytes the algorithm needs for THIS CHIP'S SHARE, as functions of shapes.
A multiply-add is 2 operations and recomputation is never counted. Causal
attention is counted at the half of the score matrix it needs; a token's
routed work is what the held experts do of it under even routing, ``top_k *
experts_held / n_experts`` rows a token (0.34375 at 22 x 8 / 512); the
state-space scan is Mamba-2's chunked algorithm (SSD) at ``chunk_size``,
whatever implements it."""
from __future__ import annotations


def layers(m: dict, kind: str) -> int:
    """Layers of this character of the pattern."""
    return m["layer_pattern"].count(kind)


def mixer_matmul_per_token(m: dict) -> int:
    """Multiply-adds of one mixer's in- and out-projections a token."""
    H, inner = m["hidden_size"], m["ssm_heads"] * m["ssm_head_dim"]
    proj = 2 * inner + 2 * m["ssm_groups"] * m["ssm_state"] + m["ssm_heads"]
    return H * proj + inner * H


def attention_matmul_per_token(m: dict) -> int:
    """Multiply-adds of one attention's q, k, v and o projections a
    token."""
    return m["hidden_size"] * m["head_dim"] * (2 * m["num_heads"]
                                                + 2 * m["num_kv_heads"])


def routed_rows_per_token(m: dict) -> float:
    return m["top_k"] * m["experts_held"] / m["n_experts"]


def expert_matmul_per_token(m: dict) -> float:
    """Multiply-adds of one expert layer a token: the router, the latent's
    two projections, the held experts' share and the shared expert."""
    H, L = m["hidden_size"], m["latent"]
    return (H * m["n_experts"] + 2 * H * L
            + routed_rows_per_token(m) * 2 * L * m["expert_width"]
            + 2 * H * m["shared_width"])


def matmul_flops_per_token(m: dict) -> float:
    """Forward matmul operations a token, attention scores and the scan
    apart."""
    return 2 * (layers(m, "M") * mixer_matmul_per_token(m)
                + layers(m, "*") * attention_matmul_per_token(m)
                + layers(m, "E") * expert_matmul_per_token(m)
                + m["hidden_size"] * m["vocab_size"])


def causal_pairs(t: int) -> int:
    return t * (t + 1) // 2


def attn_flops(m: dict, t: int) -> int:
    """Forward QK^T and PV of every head over the causal pairs, every
    attention layer."""
    return (2 * m["num_heads"] * 2 * m["head_dim"] * causal_pairs(t)
            * layers(m, "*"))


def ssd_flops(m: dict, t: int) -> int:
    """Forward operations of the chunked scan of one sequence, every mixer:
    a chunk's ``C B^T`` a group, its masked product with X a head, the
    chunk's state ``B^T X`` and ``C . state`` a head, and the recurrence
    over the chunks (a multiply-add per state element a chunk)."""
    L, nh, P = m["chunk_size"], m["ssm_heads"], m["ssm_head_dim"]
    g, N = m["ssm_groups"], m["ssm_state"]
    chunks = -(-t // L)
    per_chunk = g * L * L * N + nh * L * L * P + 2 * nh * L * P * N \
        + nh * P * N
    return 2 * chunks * per_chunk * layers(m, "M")


def train_flops_per_sequence(m: dict, t: int) -> float:
    return 3 * (t * matmul_flops_per_token(m) + attn_flops(m, t)
                + ssd_flops(m, t))


def ssd_train_ops_bytes(m: dict, batch: int, t: int, itemsize: int = 4):
    """One training step's chunked scans, every mixer, forward and backward
    (3 x the forward's operations). Bytes: x, B, C and dt read and y
    written forward; the same four and dy read and their four gradients
    written backward."""
    nh, P = m["ssm_heads"], m["ssm_head_dim"]
    ins = t * (nh * P + 2 * m["ssm_groups"] * m["ssm_state"] + nh)
    out = t * nh * P
    nbytes = (3 * ins + 2 * out) * itemsize * batch * layers(m, "M")
    return 3 * ssd_flops(m, t) * batch, nbytes


def experts_train_ops_bytes(m: dict, tokens: int, rows_local: float,
                            itemsize: int = 4):
    """One training step's expert matmuls, every expert layer: the latent's
    two projections and the shared expert over every token, the held
    experts over the ``rows_local`` rows a layer routed here, forward and
    backward (3 x the forward's operations). Bytes: each weight read
    forward and backward and its gradient written; a routed row's latent
    input read and output written, a token's input and output at full
    width, forward, and with their gradients once more backward."""
    H, L, F, S = (m["hidden_size"], m["latent"], m["expert_width"],
                  m["shared_width"])
    macs = rows_local * 2 * L * F + tokens * (2 * H * L + 2 * H * S)
    weights = (2 * L * F * m["experts_held"] + 2 * H * L + 2 * H * S) \
        * itemsize
    acts = (rows_local * 2 * L + tokens * 2 * H) * itemsize
    n = layers(m, "E")
    return 3 * 2 * macs * n, (3 * weights + 3 * acts) * n


def flash_train_ops_bytes(m: dict, batch: int, t: int, itemsize: int = 4):
    """One training step's flash kernels, every attention layer, as
    ``benchmark/ops.py`` counts the dense decoder's: 2 + 5 matmuls of ``2 *
    head_dim`` operations a causal pair and query head; q, k, v, o moved
    once forward, q, k, v, o, do read and dq, dk, dv written backward. The
    KV head reaches the kernels repeated to every query head of its group,
    so k and v are as wide as q there."""
    width = m["num_heads"] * m["head_dim"]
    flops = (2 + 5) * 2 * width * batch * causal_pairs(t) * layers(m, "*")
    tensor = batch * t * width * itemsize
    return flops, (4 + 8) * tensor * layers(m, "*")


def kernel_layers(m: dict, kernel: str) -> int:
    """The flash kernels run once an attention layer."""
    return layers(m, "*")
