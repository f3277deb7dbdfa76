"""Partial shape inference hints + input names for parameter-bearing ops.

Reference analog: per-op ``FInferShape`` functions (e.g. ``ConvolutionShape``
in src/operator/nn/convolution.cc) which *fill in* weight/bias shapes from the
data shape so ``simple_bind`` can allocate parameters automatically, and
``FListInputNames`` which names them (data/weight/bias...) for
``list_arguments``.  TPU-native: full-output inference is jax.eval_shape; only
the backward "fill the unknown param shapes" step needs these hints.
"""
from __future__ import annotations

import numpy as np

from .registry import OPS


def _conv_hint(attrs, shapes):
    data = shapes[0]
    if data is None:
        return shapes
    k = attrs["kernel"]
    nf, g = attrs["num_filter"], attrs["num_group"]
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (nf, data[1] // g) + tuple(k)
    if len(out) > 2 and out[2] is None and not attrs["no_bias"]:
        out[2] = (nf,)
    return out


def _deconv_hint(attrs, shapes):
    data = shapes[0]
    if data is None:
        return shapes
    k = attrs["kernel"]
    nf, g = attrs["num_filter"], attrs["num_group"]
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (data[1], nf // g) + tuple(k)
    if len(out) > 2 and out[2] is None and not attrs["no_bias"]:
        out[2] = (nf,)
    return out


def _fc_hint(attrs, shapes):
    data = shapes[0]
    if data is None:
        return shapes
    nh = attrs["num_hidden"]
    in_dim = int(np.prod(data[1:])) if attrs.get("flatten", True) else data[-1]
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (nh, in_dim)
    if len(out) > 2 and out[2] is None and not attrs["no_bias"]:
        out[2] = (nh,)
    return out


def _channel_hint(axis_attr=None, default_axis=1, n_params=None):
    def hint(attrs, shapes):
        data = shapes[0]
        if data is None:
            return shapes
        ax = attrs.get(axis_attr, default_axis) if axis_attr else default_axis
        c = data[ax % len(data)]
        out = list(shapes)
        for i in range(1, len(out)):
            if out[i] is None:
                out[i] = (c,)
        return out
    return hint


def _embedding_hint(attrs, shapes):
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (attrs["input_dim"], attrs["output_dim"])
    return out


def _fill(shapes, want):
    """``shapes`` with its unknown parameter shapes (inputs 1..) taken from
    ``want``, the op's parameter shapes in input order."""
    out = list(shapes)
    for i in range(1, len(out)):
        if out[i] is None:
            out[i] = want[i - 1]
    return out


def _mha_hint(attrs, shapes):
    """MultiHeadAttention: the projection weights in the FullyConnected
    (out, in) orientation — query (num_heads * head, model_dim) and output
    its transpose, key and value (num_kv_heads * head, model_dim), all
    square unless the heads are grouped or ``head_dim`` names a head that is
    not model_dim / num_heads — and, under ``qk_norm``, the two per-head
    gains of (head,)."""
    data = shapes[0]
    if data is None:
        return shapes
    D = data[-1]
    H = attrs["num_heads"]
    hd = attrs.get("head_dim") or D // H
    if attrs.get("kv_lora_rank"):
        # latent attention's seven (``ops.nn._latent_heads``)
        rq, rkv, dr = (attrs["q_lora_rank"], attrs["kv_lora_rank"],
                       attrs["qk_rope_head_dim"])
        dv = attrs.get("v_head_dim") or hd
        return _fill(shapes, [(rq, D), (rq,), (H * hd, rq), (rkv + dr, D),
                              (rkv,), (H * (hd - dr + dv), rkv),
                              (D, H * dv)])
    kv = hd * (attrs.get("num_kv_heads") or H)
    return _fill(shapes, [(H * hd, D), (kv, D), (kv, D), (D, H * hd), (hd,),
                          (hd,)])


def _short_conv_hint(attrs, shapes):
    """ShortConv: in_proj (3 dim, dim), the taps (dim, kernel), out_proj
    (dim, dim)."""
    data = shapes[0]
    if data is None:
        return shapes
    D = data[-1]
    return _fill(shapes, [(3 * D, D), (D, attrs["kernel"]), (D, D)])


def _sparse_moe_hint(attrs, shapes):
    """SparseMoE: the router over all experts, the bias (sigmoid scoring
    only), the weights of the ``num_held`` experts held (0: all) as
    (expert, out, in), the load."""
    data = shapes[0]
    if data is None:
        return shapes
    D, E, F = data[-1], attrs["num_experts"], attrs["num_hidden"]
    held = attrs.get("num_held") or E
    bias = [(E,)] if attrs.get("score", "sigmoid") == "sigmoid" else []
    return _fill(shapes, [(E, D)] + bias + [(held, F, D), (held, F, D),
                                            (held, D, F), (E,)])


def _rnn_hint(attrs, shapes):
    """RNN: packed parameter size + state shapes from the TNC data shape
    (reference rnn-inl.h RNNShape/GetParamSize)."""
    data = shapes[0]
    if data is None:
        return shapes
    from .rnn import rnn_param_size
    h, L = attrs["state_size"], attrs["num_layers"]
    bi = attrs["bidirectional"]
    dirs = 2 if bi else 1
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (rnn_param_size(L, h, data[2], bi, attrs["mode"]),)
    for i in (2, 3):
        if len(out) > i and out[i] is None:
            out[i] = (L * dirs, data[1], h)
    return out


def _softmax_label_hint(attrs, shapes):
    """SoftmaxOutput: label = data shape minus the class dim."""
    data = shapes[0]
    if data is None:
        return shapes
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        if attrs.get("multi_output"):
            out[1] = (data[0],) + tuple(data[2:])
        else:
            out[1] = (data[0],)
    return out


def _label_like_hint(attrs, shapes):
    """Regression outputs: label shape defaults to data shape."""
    data = shapes[0]
    if data is None:
        return shapes
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = data
    return out


def install():
    cfg = {
        "Convolution": (("data", "weight", "bias"), (), _conv_hint),
        "Deconvolution": (("data", "weight", "bias"), (), _deconv_hint),
        "FullyConnected": (("data", "weight", "bias"), (), _fc_hint),
        "BatchNorm": (("data", "gamma", "beta", "moving_mean", "moving_var"),
                      (3, 4), _channel_hint("axis", 1)),
        "LayerNorm": (("data", "gamma", "beta"), (),
                      _channel_hint("axis", -1)),
        "InstanceNorm": (("data", "gamma", "beta"), (), _channel_hint()),
        "Embedding": (("data", "weight"), (), _embedding_hint),
        "MultiHeadAttention": (("data", "query_weight", "key_weight",
                                "value_weight", "out_proj_weight",
                                "q_norm_gamma", "k_norm_gamma",
                                # latent attention's own (kv_lora_rank)
                                "q_a_weight", "q_a_norm_gamma",
                                "q_b_weight", "kv_a_weight",
                                "kv_a_norm_gamma", "kv_b_weight"), (),
                               _mha_hint),
        "RMSNorm": (("data", "gamma"), (), _channel_hint(None, -1)),
        "ShortConv": (("data", "in_proj_weight", "conv_weight",
                       "out_proj_weight"), (), _short_conv_hint),
        "SparseMoE": (("data", "router_weight", "expert_bias",
                       "expert_gate_weight", "expert_up_weight",
                       "expert_down_weight", "expert_load"), (-1,),
                      _sparse_moe_hint),
        "LeakyReLU": (("data", "gamma"), (), _channel_hint()),
        "RNN": (("data", "parameters", "state", "state_cell"), (),
                _rnn_hint),
        "SoftmaxOutput": (("data", "label"), (), _softmax_label_hint),
        "LinearRegressionOutput": (("data", "label"), (), _label_like_hint),
        "LogisticRegressionOutput": (("data", "label"), (), _label_like_hint),
        "MAERegressionOutput": (("data", "label"), (), _label_like_hint),
        "softmax_cross_entropy": (("data", "label"), (), _label_like_hint),
        "SequenceMask": (("data", "sequence_length"), (), None),
        "SequenceLast": (("data", "sequence_length"), (), None),
        "SequenceReverse": (("data", "sequence_length"), (), None),
        "dot": (("lhs", "rhs"), (), None),
        "batch_dot": (("lhs", "rhs"), (), None),
        "broadcast_add": (("lhs", "rhs"), (), None),
        "broadcast_sub": (("lhs", "rhs"), (), None),
        "broadcast_mul": (("lhs", "rhs"), (), None),
        "broadcast_div": (("lhs", "rhs"), (), None),
    }
    for name, (arg_names, aux, hint) in cfg.items():
        op = OPS.get(name)
        if op is None:
            continue
        op.arg_names = list(arg_names)
        op.aux_inputs = tuple(aux)
        if hint is not None:
            op.shape_hint = hint


install()
