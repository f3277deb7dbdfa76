"""Builder of the ``mellum2_12b_a2_5b`` configuration: the program's Symbol
from the configuration's file (``models.transformer_lm``, the one transformer
definition, told its block variants by a ``TransformerConfig``), and how its
arguments and output map onto the plain reference beside it
(``perf/refs/mellum2_12b_a2_5b.py``)."""
from perf.refs import mellum2_12b_a2_5b as ref  # noqa: F401  (the loop takes it from here)

PREFIX = "tfm_"
DATA, LABEL = "data", "softmax_label"


def _rope(section):
    """One section of ``rope_parameters`` as ``TransformerConfig.rope`` takes
    it: (theta, yarn's five numbers or none)."""
    if section["rope_type"] == "default":
        return float(section["rope_theta"]), ()
    if section["rope_type"] != "yarn":
        raise ValueError("MultiHeadAttention turns by default or yarn "
                         "frequencies, not %r" % (section["rope_type"],))
    return float(section["rope_theta"]), tuple(float(section[k]) for k in (
        "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "attention_factor"))


def symbol(cfg, wl):
    from mxnet_tpu.models import transformer_lm
    from mxnet_tpu.models.configs import TransformerConfig
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names %d layers of %d"
                         % (len(cfg["layer_types"]), cfg["num_hidden_layers"]))
    if set(cfg["mlp_layer_types"]) != {"sparse"} or \
            len(cfg["mlp_layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("every layer's feed-forward is sparse in this "
                         "configuration, not %r" % (cfg["mlp_layer_types"],))
    if not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["attention_bias"] or cfg["hidden_act"] != "silu":
        # SparseMoE normalises over the selected; the head is its own matrix
        raise ValueError("the graph has normalised top-k weights, an untied "
                         "head, no attention bias and silu experts")
    tc = TransformerConfig(
        cfg["name"], cfg["vocab_size"], cfg["num_hidden_layers"],
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["intermediate_size"], wl["seq_len"],
        norm="rms", norm_eps=cfg["rms_norm_eps"], position="rope",
        rope=tuple((kind, *_rope(section))
                   for kind, section in sorted(cfg["rope_parameters"].items())),
        ffn="swiglu", n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], window=cfg["sliding_window"],
        layer_types=tuple(cfg["layer_types"]), num_dense_layers=0,
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts_held"],
        expert_offset=cfg["expert_offset"],
        moe_d_ff=cfg["moe_intermediate_size"], moe_score="softmax")
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
# the sliding layers' score and value products of one step, as the roofline
# reads them
window_attention_flops = ref.window_attention_flops
window_attention_bytes = ref.window_attention_bytes
