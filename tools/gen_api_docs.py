"""Generate docs/API.md — an index of the public API with first-line
docstrings. Re-run after adding exports:

    JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python tools/gen_api_docs.py
"""
from __future__ import annotations

import inspect
import os

SECTIONS = [
    ("bigdl_tpu.nn", "Layers, criteria, containers, graphs"),
    ("bigdl_tpu.optim", "Optimizers, schedules, triggers, validation"),
    ("bigdl_tpu.keras", "Keras-1.2 style front-end"),
    ("bigdl_tpu.ops", "TF-style ops"),
    ("bigdl_tpu.parallel", "Mesh/collectives/ZeRO/ring/pipeline/MoE"),
    ("bigdl_tpu.dataset", "DataSet/MiniBatch/loaders/text"),
    ("bigdl_tpu.transform.vision", "Image augmentation pipeline"),
    ("bigdl_tpu.models", "Model zoo"),
    ("bigdl_tpu.loaders", "Interop: caffe/t7/TF/bigdl.proto/keras-json"),
    ("bigdl_tpu.quantization", "Int8 PTQ + calibration"),
    ("bigdl_tpu.dlframes", "DataFrame pipeline stages"),
    ("bigdl_tpu.serving", "Online inference: micro-batching/buckets/"
     "backpressure/hot swap"),
    ("bigdl_tpu.visualization", "TensorBoard event writing"),
    ("bigdl_tpu.observability", "Tracing + metrics + exporters"),
    ("bigdl_tpu.utils", "Engine/Table/Shape/RNG/File/graph utils"),
    ("bigdl_tpu.kernels", "Pallas TPU kernels"),
]


# options and sizes that a first docstring line cannot carry, by module
NOTES = """## Notes: options and sizes

`nn.SparseAttention(hidden_size, num_heads, num_kv_heads, head_dim,
index_heads, index_head_dim, topk, rope_theta=1e4, norm_eps=1e-6,
block_q=512, block_k=512)`: grouped-query causal
self-attention (query head h reads KV head h // (num_heads / num_kv_heads))
with QK-norm (one RMSNorm weight of head_dim a side) and RoPE over all
head_dim dims, over the `min(t + 1, topk)` keys a lightning indexer
(index_heads query heads of index_head_dim, one key head, RoPE on the first
half of its dims, a LayerNorm on the key) scores highest; ties go to the
earlier key. Parameters: `wq [H, nh*d]`, `wk`, `wv [H, kvh*d]`, `wo [nh*d,
H]`, `q_norm`, `k_norm`, `index/{wq [H, hI*dI], wk [H, dI], norm, ww [H,
hI]}`. Returns `(y, {"losses": {"dsa/kl": the mean KL}, "counters":
{"dsa/tiles_visited": share}})`. On the chip: head_dim a multiple of 128,
T a multiple of block_q (a multiple of 256) and block_k; state `T * T` bits
a layer for the selection (33.5 MB at 16k). Keye-VL-2.0's widths: 2048,
32 / 4 heads of 128, 16 index heads of 64, topk 2048.

`nn.RoutedExperts(..., scoring="sigmoid" | "softmax", balance_coef=0.0)`:
softmax scoring has no selection bias (no `bias` parameter);
`balance_coef > 0` returns `coef * E * sum_e f_e P_e` as the state's loss
`moe/balance`. `Optimizer` adds every `losses` entry a model's state
returns to the criterion's loss (`optim/optimizer.py::_loss_fn`).
`activation="relu2"`: ungated experts `W2 relu(W1 u)^2` (`experts/{w1,
w2}`); `latent=L`: the routed experts work on `u = x latent/down [H, L]`
and their weighted sum goes back through `latent/up [L, H]` (scope
`experts`; the router reads `x`); the `n_shared` shared experts take
the layer's activation (`shared/{w1 [H, n_shared*F], w2}`).
`bias_update=u` (sigmoid scoring): after every training forward the bias
moves by `u * sign(mean load - load_i)`; the moved part is the state's
`bias [E]` (in a `Transformer`, the model state's `block{i}/bias`), added
to `params["bias"]` when selecting.
`nn.FeedForwardNetwork(..., activation="relu2")`: `relu(x w1)^2 w2`.

`nn.Mamba2Mixer(hidden_size, num_heads, head_dim, n_groups, state_size,
conv_kernel=4, chunk_size=128, norm_eps=1e-5)`: Mamba-2's mixer; head h
reads group h // (num_heads / n_groups). Parameters: `in_proj [H, 2*nh*P +
2*g*N + nh]` (z, x, B, C, dt), `conv_weight [K, nh*P + 2*g*N]`,
`conv_bias`, `dt_bias`, `A_log`, `D [nh]`, `norm/weight [nh*P]` (RMS a
group of nh*P/g channels), `out_proj [nh*P, H]`. The scan is
`nn.ssd_scan(x [b, T, nh, P], dt, A, B [b, T, g, N], C, chunk)` (scope
`ssd`), a length not a multiple of chunk padded with dt = 0. A share of
whole groups of heads is the same layer with fewer heads and groups.
Nemotron-3-Super's widths: 4096, 128 heads of 64 in 8 groups, state 128.

`nn.Transformer(..., layer_pattern="MEMEMEM*EME", make_layer=fn)`: one
`nn.SublayerBlock` a character, `h + module(RMSNorm(h))` with the module
`make_layer(kind, i)` returns under the key and scope `ssm` (M), `attn`
(*) or `ffn` (E). `nn.Attention(..., head_dim=d)`: q and o
`num_heads * d` wide where that is not hidden_size.

`kernels.dsa_select(qi [N, hI, T, dI], kit [N, dI, T], w [N, hI, T],
topk)` -> bitmask `[N, T/32, T]` int32, logsumexp `[N, 1, T]`;
`kernels.dsa_attention(q, k, v, bits, heads, kv_heads, scale)` -> o, lse;
`kernels.dsa_index_loss(qi, kit, w, ilse, bits, q, k, lse, heads,
kv_heads, scale)` -> the summed KL, differentiable in qi, kit and w.
"""


def first_line(obj) -> str:
    # __doc__ directly, NOT inspect.getdoc: getdoc inherits the base-class
    # docstring, labeling every undocumented layer "Base class of all..."
    doc = getattr(obj, "__doc__", None) or ""
    line = doc.strip().splitlines()[0].strip() if doc.strip() else ""
    return line


def gen():
    out = ["# `bigdl_tpu` API index",
           "",
           "Generated by `tools/gen_api_docs.py` — public names per "
           "package with their first docstring line. See docs/MIGRATION.md "
           "for workflow-level mapping from the reference framework.",
           ""]
    import importlib
    for modname, blurb in SECTIONS:
        mod = importlib.import_module(modname)
        names = getattr(mod, "__all__", None) or \
            [n for n in dir(mod) if not n.startswith("_")]
        rows = []
        has_all = bool(getattr(mod, "__all__", None))
        for n in sorted(set(names)):
            obj = getattr(mod, n, None)
            if obj is None or inspect.ismodule(obj):
                continue
            if not has_all:
                # dir() fallback: keep only names defined inside the
                # package — no typing/__future__ imports in the index
                src = getattr(obj, "__module__", "")
                if not (src or "").startswith("bigdl_tpu"):
                    continue
            line = first_line(obj)
            if not line:
                # undocumented one-liner (e.g. _unary-generated): point at
                # the defining file, whose module docstring carries the
                # reference-parity citation
                src = getattr(obj, "__module__", "") or ""
                line = f"see `{src.replace('.', '/')}.py`" if src else ""
            line = line.replace("|", "\\|")
            if len(line) > 110:
                line = line[:107] + "..."
            rows.append(f"| `{n}` | {line} |")
        if not rows:
            continue
        out += [f"## {modname} — {blurb}", "",
                f"{len(rows)} public names.", "",
                "| name | summary |", "|---|---|"] + rows + [""]
    return "\n".join(out) + "\n" + NOTES


if __name__ == "__main__":
    text = gen()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "API.md")
    with open(path, "w") as f:
        f.write(text)
    print(f"wrote {path} ({len(text.splitlines())} lines)")
