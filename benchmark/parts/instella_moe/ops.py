"""The ops part of the gated-latent-attention expert decoder: operations
and bytes the algorithm needs for THIS CHIP'S SHARE, as functions of
shapes. A multiply-add is 2 operations, recomputation is never counted,
causal attention is counted at the half of the score matrix it needs, and a
token's routed work is what the held experts do of it under even routing:
``top_k * experts_held / n_experts`` rows a token (0.75 at 6 x 8 / 64)."""
from __future__ import annotations


def attention_layers(m: dict) -> int:
    return m["num_layers"] + (1 if m.get("mtp") else 0)


def expert_layers(m: dict) -> int:
    """Expert layers, the MTP module's block among them."""
    n = m["num_layers"] - m["first_k_dense"]
    return n + (1 if m.get("mtp") and n > 0 else 0)


def attention_matmul_per_token(m: dict) -> int:
    """Multiply-adds of one layer's projections for one token: q, the
    latent's down- and up-projection, the gate and the output."""
    H, nh, r = m["hidden_size"], m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    return (H * nh * (dn + dr) + H * (r + dr) + r * nh * (dn + dv)
            + 2 * H * nh * dv)


def routed_rows_per_token(m: dict) -> float:
    return m["top_k"] * m["experts_held"] / m["n_experts"]


def matmul_flops_per_token(m: dict) -> float:
    """Forward matmul operations for one token, attention scores apart."""
    H, F, V = m["hidden_size"], m["expert_width"], m["vocab_size"]
    dense = m["first_k_dense"] * 3 * H * m["dense_width"]
    expert = expert_layers(m) * (
        H * m["n_experts"] + 3 * H * F * m["n_shared"]
        + routed_rows_per_token(m) * 3 * H * F)
    heads = H * V * (2 if m.get("mtp") else 1)
    mtp = 2 * H * H if m.get("mtp") else 0
    return 2 * (attention_layers(m) * attention_matmul_per_token(m) + dense
                + expert + heads + mtp)


def causal_pairs(t: int) -> int:
    return t * (t + 1) // 2


def attn_flops(m: dict, kv_pairs: int) -> int:
    """Forward QK^T and PV over ``kv_pairs`` pairs in every layer."""
    per_pair = 2 * m["num_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    return per_pair * attention_layers(m) * kv_pairs


def train_flops_per_sequence(m: dict, t: int) -> float:
    return 3 * (t * matmul_flops_per_token(m) + attn_flops(m, causal_pairs(t)))


def flash_train_ops_bytes(m: dict, batch: int, t: int, itemsize: int = 4):
    """One training step's attention kernels, all layers, as
    ``benchmark/ops.py`` counts the dense decoder's: 2 + 5 matmuls of ``2 *
    head_dim`` operations a pair and head; q, k, v, o moved once forward, q,
    k, v, o, do read and dq, dk, dv written backward. q/k and v heads are
    equally wide here (the kernels' condition)."""
    d = m["v_head_dim"]
    width = m["num_heads"] * d
    flops = (2 + 5) * 2 * width * batch * causal_pairs(t) * attention_layers(m)
    tensor = batch * t * width * itemsize
    return flops, (4 + 8) * tensor * attention_layers(m)


def experts_train_ops_bytes(m: dict, tokens: int, rows_local: float,
                            itemsize: int = 4):
    """One training step's expert matmuls, all expert layers: the held
    experts over the ``rows_local`` rows a layer that were routed here and
    the shared experts over every token, forward and backward (3 x the
    forward's operations). Bytes: each weight read forward and backward and
    its gradient written; each row's input read and output written forward,
    both with their gradients once more backward."""
    H, F = m["hidden_size"], m["expert_width"]
    rows = rows_local + tokens * m["n_shared"]
    flops = 3 * 2 * 3 * H * F * rows
    weights = 3 * H * F * (m["experts_held"] + m["n_shared"]) * itemsize
    acts = (rows_local + tokens) * 2 * H * itemsize
    return (flops * expert_layers(m),
            (3 * weights + 3 * acts) * expert_layers(m))


def kernel_layers(m: dict, kernel: str) -> int:
    """Every layer, the MTP module's among them, has one attention."""
    return attention_layers(m)
