"""Builder of the ``glm_4_7_flash`` configuration: the program's Symbol from
the configuration's file (``models.transformer_lm``, the one transformer
definition, told its block variants by a ``TransformerConfig``), and how its
arguments and output map onto the plain reference beside it
(``perf/refs/glm_4_7_flash.py``)."""
from perf.refs import glm_4_7_flash as ref  # noqa: F401  (the loop takes it from here)

PREFIX = "tfm_"
DATA, LABEL = "data", "softmax_label"


def symbol(cfg, wl):
    from mxnet_tpu.models import transformer_lm
    from mxnet_tpu.models.configs import TransformerConfig
    if not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["attention_bias"] or cfg["hidden_act"] != "silu" \
            or cfg["topk_method"] != "noaux_tc" or cfg["n_group"] != 1 \
            or cfg["rope_scaling"] is not None \
            or cfg["partial_rotary_factor"] != 1 \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        # SparseMoE selects under a bias over one group and normalises over
        # the selected; the latent heads are all their own key/value heads
        raise ValueError("the graph has one expert group selected under a "
                         "bias, normalised top-k weights, an untied head, no "
                         "attention bias, silu experts, a plain rope over "
                         "the whole rotary part and a key/value head a head")
    tc = TransformerConfig(
        cfg["name"], cfg["vocab_size"], cfg["num_hidden_layers"],
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["intermediate_size"], wl["seq_len"],
        norm="rms", norm_eps=cfg["rms_norm_eps"], position="rope",
        rope_theta=float(cfg["rope_theta"]), ffn="swiglu",
        attention="latent", q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        num_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["n_routed_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts_held"],
        expert_offset=cfg["expert_offset"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling=cfg["routed_scaling_factor"], moe_weight_eps=1e-20,
        mtp_layers=cfg["num_nextn_predict_layers"],
        mtp_loss_weight=cfg["mtp_loss_weight"])
    return transformer_lm(tc, prefix=PREFIX)


def shapes(cfg, wl):
    bt = (wl["batch"], wl["seq_len"])
    return {DATA: bt}, {LABEL: bt}


def leaf_name(arg_name):
    """The reference's name of one of the program's arguments."""
    return arg_name[len(PREFIX):]


def step_loss(output, label):
    """The step's loss from what the loop fetched: the graph's head is the
    two mean cross-entropies' weighted sum itself."""
    del label
    return float(output.reshape(-1)[0])


def row_losses(output, label):
    """The graph's head is the batch's mean: no row's own loss to compare."""
    del output, label
    return None


def items_per_step(cfg, wl):
    return wl["batch"] * wl["seq_len"]


step_flops = ref.step_flops
# the latent layers' score and value products of one step, as the roofline
# reads them
mla_attention_flops = ref.mla_attention_flops
mla_attention_bytes = ref.mla_attention_bytes
