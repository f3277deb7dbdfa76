"""Builder of the ``kimi_linear_48b_a3b`` configuration: the program's Symbol
from the configuration's file (``models.transformer_lm``, the one
transformer definition, told its block variants by a ``TransformerConfig``),
and how its arguments and output map onto the plain reference beside it
(``perf/refs/kimi_linear_48b_a3b.py``).

``kda_core_flops`` / ``kda_core_bytes`` are the KDA core's REQUIRED work,
whatever implements it, for ``kda_core_roofline_pct.train`` (the reference
module's docstrings count them: the recurrence's three products a head and
position, 6 dk dv operations forward and twice that backward; q, k, v, o
and their gradients once at the compute dtype, g, beta and theirs once at
float32: 0.155 TFLOP and 1.61 GB a step at the cell's size, bound by
bytes).  ``mla_attention_flops`` / ``mla_attention_bytes`` are the latent
layer's score and value products under GLM-4.7-Flash's names (scores over
192 dims, values of 128), for the latent layer's metrics to read."""
from perf.refs import kimi_linear_48b_a3b as ref  # noqa: F401  (the loop takes it from here)

PREFIX = "tfm_"
DATA, LABEL = "data", "softmax_label"


def symbol(cfg, wl):
    from mxnet_tpu.models import transformer_lm
    from mxnet_tpu.models.configs import TransformerConfig
    lin = cfg["linear_attn_config"]
    if not cfg["moe_renormalize"] or cfg["tie_word_embeddings"] \
            or cfg["hidden_act"] != "silu" or not cfg["mla_use_nope"] \
            or cfg["moe_router_activation_func"] != "sigmoid" \
            or cfg["num_expert_group"] != 1 or cfg["q_lora_rank"] \
            or cfg["num_nextn_predict_layers"] \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or lin["num_heads"] != cfg["num_attention_heads"]:
        # SparseMoE selects under a bias over one group and normalises over
        # the selected; the latent heads take full-rank queries, no
        # positions, and are all their own key/value heads; KDA has as
        # many heads as the latent layers
        raise ValueError("the graph has one expert group selected under a "
                         "bias by sigmoid scores, normalised top-k weights, "
                         "an untied head, silu experts, latent attention "
                         "with full-rank queries and no positions, a "
                         "key/value head a head, as many KDA heads as "
                         "latent ones and no prediction module")
    kinds = tuple(ref.mixer(cfg, l) for l in range(cfg["num_hidden_layers"]))
    tc = TransformerConfig(
        cfg["name"], cfg["vocab_size"], cfg["num_hidden_layers"],
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["intermediate_size"], wl["seq_len"],
        norm="rms", norm_eps=cfg["rms_norm_eps"], position="none",
        ffn="swiglu",
        layer_types=tuple("kda" if k == "kda" else "full_attention"
                          for k in kinds),
        kda_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"],
        attention="latent", q_lora_rank=0,
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        num_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_token"],
        experts_held=cfg["num_experts_held"],
        expert_offset=cfg["expert_offset"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["num_shared_experts"],
        routed_scaling=cfg["routed_scaling_factor"], moe_weight_eps=1e-20)
    return transformer_lm(tc, prefix=PREFIX)


def shapes(cfg, wl):
    bt = (wl["batch"], wl["seq_len"])
    return {DATA: bt}, {LABEL: bt}


def leaf_name(arg_name):
    """The reference's name of one of the program's arguments."""
    return arg_name[len(PREFIX):]


def step_loss(output, label):
    """The step's loss from what the loop fetched: the graph's head is the
    mean cross-entropy itself."""
    del label
    return float(output.reshape(-1)[0])


def row_losses(output, label):
    """The graph's head is the batch's mean: no row's own loss to compare."""
    del output, label
    return None


def items_per_step(cfg, wl):
    return wl["batch"] * wl["seq_len"]


step_flops = ref.step_flops
kda_core_flops = ref.kda_core_flops
kda_core_bytes = ref.kda_core_bytes
mla_attention_flops = ref.mla_attention_flops
mla_attention_bytes = ref.mla_attention_bytes
