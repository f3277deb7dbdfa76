"""Builder of the ``sdar_30b_a3b`` configuration: the program's Symbol from
the configuration's file (``models.transformer_lm``, the one transformer
definition, told its block variants and its block-diffusion objective by a
``TransformerConfig``), and how its arguments and output map onto the plain
reference beside it (``perf/refs/sdar_30b_a3b.py``).

The data is the noised document x_t and the label the clean one x_0, both
(batch, L): the reference's ``make_batches`` draws them through
``mxnet_tpu.io.block_diffusion_noise``, the function
``mxnet_tpu.io.BlockDiffusionIter`` noises by.  A step's items are the L
tokens of each document, not the 2L positions its layers see.

``bd_attention_flops`` / ``bd_attention_bytes`` are the REQUIRED work of
the attention cores under the block-diffusion mask, whatever implements
the mask (the reference module's docstrings count it: the score and value
products over the visible pairs, two forward and four backward; q, k, v, o
and their gradients once): 4.95 TFLOP and 1.81 GB a step at the cell's
size, bound by operations."""
from perf.refs import sdar_30b_a3b as ref  # noqa: F401  (the loop takes it from here)

PREFIX = "tfm_"
DATA, LABEL = "data", "softmax_label"


def symbol(cfg, wl):
    from mxnet_tpu.models import transformer_lm
    from mxnet_tpu.models.configs import TransformerConfig
    if not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["attention_bias"] or cfg["hidden_act"] != "silu" \
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1 \
            or cfg["use_sliding_window"] or cfg["rope_scaling"]:
        # SparseMoE normalises over the selected; every layer has experts;
        # the head is its own matrix; attention is full, unscaled rope
        raise ValueError("the graph has normalised top-k weights, experts "
                         "in every layer, an untied head, no attention bias, "
                         "silu experts and full attention under plain rope")
    tc = TransformerConfig(
        cfg["name"], cfg["vocab_size"], cfg["num_hidden_layers"],
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["intermediate_size"], wl["seq_len"],
        norm="rms", norm_eps=cfg["rms_norm_eps"], position="rope",
        rope_theta=float(cfg["rope_theta"]), ffn="swiglu",
        n_kv_heads=cfg["num_key_value_heads"], qk_norm=True,
        head_dim=cfg["head_dim"], num_dense_layers=0,
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts_held"],
        expert_offset=cfg["expert_offset"],
        moe_d_ff=cfg["moe_intermediate_size"], moe_score="softmax",
        diffusion_block=cfg["block_length"],
        diffusion_mask_id=cfg["mask_token_id"])
    return transformer_lm(tc, prefix=PREFIX)


def shapes(cfg, wl):
    bt = (wl["batch"], wl["seq_len"])
    return {DATA: bt}, {LABEL: bt}


def leaf_name(arg_name):
    """The reference's name of one of the program's arguments."""
    return arg_name[len(PREFIX):]


def step_loss(output, label):
    """The step's loss from what the loop fetched: the graph's head is the
    weighted mean itself."""
    del label
    return float(output.reshape(-1)[0])


def row_losses(output, label):
    """The graph's head is the batch's mean: no row's own loss to compare."""
    del output, label
    return None


def items_per_step(cfg, wl):
    return wl["batch"] * wl["seq_len"]


step_flops = ref.step_flops
bd_attention_flops = ref.bd_attention_flops
bd_attention_bytes = ref.bd_attention_bytes
