"""Builder of the ``gpt2_medium`` configuration: the program's Symbol from
the configuration's file, and how its arguments and output map onto the
plain reference beside it (``perf/refs/gpt2_medium.py``)."""
from perf.refs import gpt2_medium as ref  # noqa: F401  (the loop takes it from here)

PREFIX = "tfm_"
DATA, LABEL = "data", "softmax_label"


def symbol(cfg, wl):
    from mxnet_tpu.models import transformer_lm
    from mxnet_tpu.models.configs import TransformerConfig
    tc = TransformerConfig(cfg["name"], cfg["vocab_size"], cfg["n_layer"],
                           cfg["n_embd"], cfg["n_head"], cfg["n_inner"],
                           wl["seq_len"])
    if wl["seq_len"] != cfg["n_positions"]:
        raise ValueError("the cell's sequences (%d) are not the context the "
                         "configuration states (%d)"
                         % (wl["seq_len"], cfg["n_positions"]))
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
