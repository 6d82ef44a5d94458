"""The builder part of the gated-latent-attention expert decoder
(``deepseek_v3`` configs): the program's ``nn.Transformer`` behind its
options (RMSNorm, ``LatentAttention`` with the sigmoid gate, a SwiGLU FFN in
the first ``first_k_dense`` layers and ``RoutedExperts`` after them, told
which experts this chip holds, an untied head, no positional add, one MTP
module) and the criterion the trainer is given: BigDL's ``ParallelCriterion``
over the Table of the two heads' logits, the one label array serving both;
the MTP head's first position predicts nothing, and a ``TransformerCriterion``
puts the padding value at that target."""
from __future__ import annotations

# named here so that a program without these layers fails when the part is
# loaded (``harness.load_cell``), before any device is touched
from bigdl_tpu.nn import LatentAttention, RoutedExperts  # noqa: F401


def build(m: dict, remat: bool):
    from bigdl_tpu import nn
    H, eps = m["hidden_size"], m["rms_norm_eps"]

    def attention():
        return nn.LatentAttention(
            H, m["num_heads"], m["kv_lora_rank"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"],
            rope_theta=m["rope_theta"], rope_scaling=m["rope_scaling"],
            norm_eps=eps, gated=True)

    def ffn(i):
        if i < m["first_k_dense"]:
            return nn.FeedForwardNetwork(H, m["dense_width"],
                                         activation="swiglu", bias=False)
        return nn.RoutedExperts(
            H, m["n_experts"], m["top_k"], m["expert_width"],
            held=(m["held_first"], m["experts_held"]),
            n_shared=m["n_shared"], routed_scale=m["routed_scale"],
            capacity_factor=m.get("capacity_factor"))

    model = nn.Transformer(
        vocab_size=m["vocab_size"], hidden_size=H, num_heads=m["num_heads"],
        filter_size=m["dense_width"], num_hidden_layers=m["num_layers"],
        mode="lm", remat=remat, pos_encoding="none", embed_scale=False,
        norm="rms", norm_eps=eps, tied_head=False, make_attention=attention,
        make_ffn=ffn, mtp=bool(m.get("mtp")))
    model.state = model._init_state()
    main = nn.LMCriterion(padding_value=0)
    if not m.get("mtp"):
        return model, main
    # the target of the MTP head's first position -> the padding value
    first_masked = nn.Sequential().add(nn.Narrow(2, 2, -1)).add(
        nn.Padding(2, -1, 2, 0.0))
    first_masked.ensure_initialized()      # here, not inside the step's trace
    criterion = nn.ParallelCriterion(repeat_target=True)
    criterion.add(main, 1.0)
    criterion.add(nn.TransformerCriterion(nn.LMCriterion(padding_value=0),
                                          None, first_masked),
                  m["mtp_loss_weight"])
    return model, criterion
