"""The builder part of the hybrid Mamba-2 / latent-expert decoder
(``nemotron_h`` configs): the program's ``nn.Transformer`` over a layer
pattern, each layer ONE sublayer behind its own RMSNorm and residual
(``SublayerBlock``): ``M`` a ``Mamba2Mixer`` holding this chip's groups of
heads, ``*`` grouped causal attention without positions (``Attention`` with
``head_dim``, this chip's query heads and the KV head they read), ``E``
``RoutedExperts`` with squared-ReLU experts in a latent, a sigmoid router
over all experts whose selection bias follows the load step by step
(``noaux_tc``) and one shared expert, told which experts this chip holds.
An untied head, no positional add; the criterion is ``LMCriterion``."""
from __future__ import annotations

# named here so that a program without these layers fails when the part is
# loaded (``harness.load_cell``), before any device is touched
from bigdl_tpu.nn import Mamba2Mixer, SublayerBlock  # noqa: F401


def build(m: dict, remat: bool):
    from bigdl_tpu import nn
    H, eps = m["hidden_size"], m["rms_norm_eps"]

    def layer(kind, i):
        if kind == "M":
            return nn.Mamba2Mixer(
                H, m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"],
                m["ssm_state"], conv_kernel=m["conv_kernel"],
                chunk_size=m["chunk_size"], norm_eps=eps)
        if kind == "*":
            return nn.Attention(H, m["num_heads"], causal=True,
                                num_kv_heads=m["num_kv_heads"],
                                head_dim=m["head_dim"])
        # the one shared expert of shared_width is n_shared experts' width:
        # the same squared-ReLU FFN at full width
        n_shared, rest = divmod(m["shared_width"], m["expert_width"])
        assert n_shared and not rest, "the shared expert is not whole experts"
        return nn.RoutedExperts(
            H, m["n_experts"], m["top_k"], m["expert_width"],
            held=(m["held_first"], m["experts_held"]), n_shared=n_shared,
            routed_scale=m["routed_scale"],
            capacity_factor=m["capacity_factor"], activation="relu2",
            latent=m["latent"], bias_update=m["bias_update"])

    model = nn.Transformer(
        vocab_size=m["vocab_size"], hidden_size=H, mode="lm", remat=remat,
        pos_encoding="none", embed_scale=False, norm="rms", norm_eps=eps,
        tied_head=False, layer_pattern=m["layer_pattern"], make_layer=layer)
    model.state = model._init_state()
    return model, nn.LMCriterion(padding_value=0)
