"""Builder of the ``lfm2_24b_a2b`` configuration: the program's Symbol from
the configuration's file (``models.transformer_lm``, the one transformer
definition, told its block variants by a ``TransformerConfig``), and how its
arguments and output map onto the plain reference beside it
(``perf/refs/lfm2_24b_a2b.py``)."""
from perf.refs import lfm2_24b_a2b as ref  # noqa: F401  (the loop takes it from here)

PREFIX = "tfm_"
DATA, LABEL = "data", "softmax_label"


def symbol(cfg, wl):
    from mxnet_tpu.models import transformer_lm
    from mxnet_tpu.models.configs import TransformerConfig
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names %d layers of %d"
                         % (len(cfg["layer_types"]), cfg["num_hidden_layers"]))
    if not cfg["norm_topk_prob"] or cfg["routed_scaling_factor"] != 1:
        # SparseMoE has the one weighting the source states: normalised
        # over the selected, scaled by 1
        raise ValueError("SparseMoE weights experts by norm_topk_prob with "
                         "routed_scaling_factor 1, not %r / %r"
                         % (cfg["norm_topk_prob"],
                            cfg["routed_scaling_factor"]))
    tc = TransformerConfig(
        cfg["name"], cfg["vocab_size"], cfg["num_hidden_layers"],
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["intermediate_size"], wl["seq_len"],
        norm="rms", norm_eps=cfg["norm_eps"], position="rope",
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        ffn="swiglu", n_kv_heads=cfg["num_key_value_heads"], qk_norm=True,
        layer_types=tuple(cfg["layer_types"]),
        conv_kernel=cfg["conv_L_cache"],
        num_dense_layers=cfg["num_dense_layers"],
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts_held"],
        expert_offset=cfg["expert_offset"],
        moe_d_ff=cfg["moe_intermediate_size"], tie_head=True)
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
# the experts' grouped products of one step, as the roofline reads them
moe_expert_flops = ref.moe_expert_flops
moe_expert_bytes = ref.moe_expert_bytes
