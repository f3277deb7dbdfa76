"""The ops an LFM2-class hybrid needs (RMSNorm, silu, grouped-query rotary
attention with per-head norms, ShortConv, SparseMoE) and the model built
from them by the one transformer definition, each against the plain
reference the benchmark keeps (``perf/refs/lfm2_24b_a2b.py``: float32
``jax.numpy``, nothing of the program) at toy widths on seeded weights.
"""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import transformer_lm
from mxnet_tpu.models.configs import TransformerConfig
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.registry import OPS

from perf.models import lfm2_24b_a2b as builder
from perf.refs import common as ref_common
from perf.refs import lfm2_24b_a2b as ref
from perf.refs import train as ref_train

#: the benchmark's configuration at toy widths: the same five layers (every
#: kind present), 4 experts held of 16, 2 a token
TOY = {
    "name": "lfm2_toy", "conv_L_cache": 3, "hidden_size": 64,
    "intermediate_size": 192, "moe_intermediate_size": 48,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_dense_layers": 1, "num_experts": 16,
    "num_experts_per_tok": 2, "num_experts_held": 4, "expert_offset": 0,
    "num_hidden_layers": 5, "routed_scaling_factor": 1,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "vocab_size": 256, "dtype": "float32",
}
WL = {"batch": 2, "seq_len": 32, "rotation": 2, "ids": "all", "chips": 1,
      "optimizer": "adam",
      "optimizer_params": {"learning_rate": 1e-3, "beta1": 0.9,
                           "beta2": 0.999, "epsilon": 1e-8, "wd": 0.0,
                           "rescale_grad": 1.0}}
D, HD = TOY["hidden_size"], 16


def _op(name, **attrs):
    op = OPS[name]
    parsed = op.parse_attrs(dict(attrs))
    return lambda *xs: op.fn(parsed, *xs)


def _rand(key, shape, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(key), shape,
                                     jnp.float32)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(1e-6, np.abs(want).max()))


def _agree(fn, ref_fn, args, tol=2e-5):
    """Forward and every argument's gradient (of a fixed random projection
    of the result) against the reference."""
    y, y_ref = fn(*args), ref_fn(*args)
    _close(y, y_ref, tol)
    probe = _rand(99, y_ref.shape)
    nums = tuple(range(len(args)))
    g = jax.grad(lambda *a: jnp.sum(fn(*a) * probe), nums)(*args)
    g_ref = jax.grad(lambda *a: jnp.sum(ref_fn(*a) * probe), nums)(*args)
    for a, b in zip(g, g_ref):
        _close(a, b, tol)


# ------------------------------------------------------------------- ops
def test_rms_norm_forward_and_gradient():
    x, g = _rand(0, (2, 8, D)), 1.0 + _rand(1, (D,), 0.1)
    _agree(_op("RMSNorm", eps=1e-5), lambda x, g: ref._rms(x, g, 1e-5),
           (x, g))


def test_activation_silu_forward_and_gradient():
    x = _rand(2, (4, 33), 3.0)
    _agree(_op("Activation", act_type="silu"),
           lambda x: x / (1.0 + jnp.exp(-x)), (x,))


def test_short_conv_forward_and_gradient():
    x = _rand(3, (2, 16, D))
    w_in, taps, w_out = (_rand(4, (3 * D, D), 0.1), _rand(5, (D, 3), 0.5),
                         _rand(6, (D, D), 0.1))

    def plain(x, w_in, taps, w_out):
        return ref._conv(TOY, "float32", x, {
            "conv_in_proj_weight": w_in, "conv_conv_weight": taps,
            "conv_out_proj_weight": w_out})

    _agree(_op("ShortConv", kernel=3), plain, (x, w_in, taps, w_out))


def test_short_conv_is_causal():
    """Position t sees positions t-2..t and nothing later."""
    args = (_rand(4, (3 * D, D), 0.1), _rand(5, (D, 3), 0.5),
            _rand(6, (D, D), 0.1))
    x = _rand(3, (1, 16, D))
    later = x.at[:, 9:].set(_rand(7, (1, 7, D)))
    conv = _op("ShortConv", kernel=3)
    a, b = conv(x, *args), conv(later, *args)
    _close(a[:, :9], b[:, :9])
    assert not np.allclose(a[:, 9:], b[:, 9:])


@pytest.fixture
def interpret_kernel(monkeypatch):
    monkeypatch.setattr(pa, "INTERPRET", True)


def _attention_args(T):
    kv = TOY["num_key_value_heads"] * HD
    return (_rand(10, (2, T, D)), _rand(11, (D, D), 0.1),
            _rand(12, (kv, D), 0.1), _rand(13, (kv, D), 0.1),
            _rand(14, (D, D), 0.1), 1.0 + _rand(15, (HD,), 0.1),
            1.0 + _rand(16, (HD,), 0.1))


def _plain_attention(x, wq, wk, wv, wo, gq, gk):
    return ref._attention(TOY, "float32", x, {
        "attn_query_weight": wq, "attn_key_weight": wk,
        "attn_value_weight": wv, "attn_out_proj_weight": wo,
        "attn_q_norm_gamma": gq, "attn_k_norm_gamma": gk})


_GQA = dict(num_heads=4, num_kv_heads=2, qk_norm=True, rope_theta=1e6,
            causal=True)


def test_grouped_normed_rotary_attention_xla_arm():
    """2 key/value heads under 4 query heads, per-head RMSNorm, rotary
    positions: forward and gradients (a key/value head's is the sum over
    its query heads) on the XLA arm."""
    telemetry.enable()
    before = telemetry.value("attention_dispatch_total", path="reference")
    _agree(_op("MultiHeadAttention", **_GQA), _plain_attention,
           _attention_args(32))
    assert telemetry.value("attention_dispatch_total",
                           path="reference") > before


def test_grouped_normed_rotary_attention_kernel_arm(interpret_kernel):
    """The same through the three flash kernels (interpreted)."""
    telemetry.enable()
    before = telemetry.value("attention_dispatch_total",
                             path="flash_interpret")
    _agree(_op("MultiHeadAttention", **_GQA), _plain_attention,
           _attention_args(128), tol=2e-4)
    assert telemetry.value("attention_dispatch_total",
                           path="flash_interpret") > before


def test_attention_refuses_gains_without_qk_norm():
    args = _attention_args(32)
    with pytest.raises(mx.base.MXNetError):
        _op("MultiHeadAttention", num_heads=4, num_kv_heads=2)(*args)


def test_rotary_turns_pairs_by_position():
    """Position 0 is left as it is; a later position keeps each (i, i + d/2)
    pair's length."""
    from mxnet_tpu.ops.nn import _rotary
    x = _rand(20, (1, 1, 8, HD))
    y = _rotary(x, 1e6)
    _close(y[..., 0, :], x[..., 0, :])
    half = HD // 2
    _close(y[..., :half] ** 2 + y[..., half:] ** 2,
           x[..., :half] ** 2 + x[..., half:] ** 2, 1e-5)
    _close(y, ref._rotary(x, 1e6))


# ------------------------------------------------------------- SparseMoE
def _moe_weights(held, e=16, f=48):
    return {"moe_router_weight": _rand(30, (e, D), 0.3),
            "moe_expert_bias": _rand(31, (e,), 0.1),
            "moe_expert_gate_weight": _rand(32, (held, f, D), 0.1),
            "moe_expert_up_weight": _rand(33, (held, f, D), 0.1),
            "moe_expert_down_weight": _rand(34, (held, D, f), 0.1)}


_MOE_ORDER = ("moe_router_weight", "moe_expert_bias",
              "moe_expert_gate_weight", "moe_expert_up_weight",
              "moe_expert_down_weight")


def _moe(held, offset=0, k=2):
    op = _op("SparseMoE", num_experts=16, num_experts_per_tok=k,
             num_hidden=48, num_held=held, expert_offset=offset)
    return lambda x, *w: op(x, *w, jnp.zeros((16,), jnp.float32))


def _plain_moe(held, offset=0, k=2):
    cfg = {**TOY, "num_experts_held": held, "expert_offset": offset,
           "num_experts_per_tok": k}
    return lambda x, *w: ref._experts(cfg, "float32", x,
                                      dict(zip(_MOE_ORDER, w)))


def test_sparse_moe_forward_and_gradient():
    telemetry.enable()
    before = telemetry.value("moe_dispatch_total", path="dense")
    x = _rand(35, (2, 24, D))
    w = tuple(_moe_weights(4)[n] for n in _MOE_ORDER)
    moe = _moe(4)
    _agree(lambda *a: moe(*a)[0], _plain_moe(4), (x, *w))
    assert telemetry.value("moe_dispatch_total", path="dense") > before
    # the bias steers the selection and takes no gradient
    g_bias = jax.grad(lambda b: jnp.sum(moe(x, w[0], b, *w[2:])[0]))(w[1])
    assert not np.any(np.asarray(g_bias))


@pytest.mark.parametrize("dtype,crowd", [("float32", 0), ("float32", 2),
                                         ("bfloat16", 0)],
                         ids=["spread", "all_held", "bf16"])
def test_sparse_moe_at_lane_widths_forward_and_gradient(dtype, crowd):
    """128-wide experts (whole lanes) against the reference, forward and
    every gradient: the routing spread over 16 experts of which 4 are held,
    every selection on held experts 1 and 2 (no drop), and bfloat16
    activations and expert weights under a float32 router (the bf16
    policy's types), held by the rounding's size."""
    d, f = 128, 128
    cfg = {**TOY, "hidden_size": d, "moe_intermediate_size": f}
    lo = jnp.dtype(dtype)
    x = _rand(50, (1, 128, d)).astype(lo)
    w = (_rand(51, (16, d), 0.3), _rand(52, (16,), 0.1),
         *(_rand(53 + i, shape, 0.1).astype(lo)
           for i, shape in enumerate([(4, d, f), (4, d, f), (4, f, d)])))
    w = (w[0], w[1].at[1:1 + crowd].add(10.0), *w[2:])
    op = _op("SparseMoE", num_experts=16, num_experts_per_tok=2,
             num_hidden=f, num_held=4)

    def got(*a):
        return op(*a, jnp.zeros((16,), jnp.float32))[0].astype(jnp.float32)

    def want(x, *w):
        return ref._experts(cfg, "float32", x.astype(jnp.float32), dict(
            zip(_MOE_ORDER, (a.astype(jnp.float32) for a in w))))

    assert op(x, *w, jnp.zeros((16,), jnp.float32))[0].dtype == lo
    _agree(got, want, (x, *w), tol=2e-4 if dtype == "float32" else 3e-2)


def test_sparse_moe_costs_the_same_whatever_the_routing():
    """The compiled layer has no operation whose extent follows the data:
    the same program text for a router that spreads the tokens and one that
    sends every token to one held expert, and no loop, branch, grouped
    product or kernel call in it (the router's top-k aside)."""
    x = _rand(35, (2, 24, D))
    w = _moe_weights(4)
    crowded = dict(w, moe_expert_bias=w["moe_expert_bias"].at[1].add(10.0))
    moe = _moe(4)
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(moe(*a)[0] ** 2),
                            (0, 1, 3, 4, 5)))
    texts = [grad.lower(x, *(ws[n] for n in _MOE_ORDER)).as_text()
             for ws in (w, crowded)]
    assert texts[0] == texts[1]
    for word in ("while", "conditional", "ragged", "custom_call"):
        assert word not in texts[0].replace("stablehlo.custom_call @mhlo."
                                            "topk", ""), word


def test_sparse_moe_counts_every_experts_load():
    x = _rand(35, (2, 24, D))
    w = _moe_weights(4)
    _, load = _moe(4, offset=8)(x, *(w[n] for n in _MOE_ORDER))
    sel, _ = ref.route(TOY, x, w["moe_router_weight"], w["moe_expert_bias"])
    want = np.bincount(np.asarray(sel).ravel(), minlength=16)
    np.testing.assert_array_equal(np.asarray(load), want)
    assert want.sum() == 2 * 24 * 2


def test_sparse_moe_shares_add_up():
    """Four shares of 4 experts, summed, are the uncut 16-expert layer."""
    x = _rand(36, (2, 24, D))
    whole = _moe_weights(16)
    want = _plain_moe(16)(x, *(whole[n] for n in _MOE_ORDER))
    total = 0.0
    for off in range(0, 16, 4):
        share = [whole[n] if n in _MOE_ORDER[:2] else whole[n][off:off + 4]
                 for n in _MOE_ORDER]
        total = total + _moe(4, offset=off)(x, *share)[0]
    _close(total, want)
    # and one share alone is not the layer
    assert not np.allclose(_moe(4)(x, *share)[0], want, atol=1e-3)


@pytest.mark.parametrize("crowd", [1, 2], ids=["one_expert", "all_held"])
def test_sparse_moe_drops_no_token(crowd):
    """Every token routed to the same held expert(s) — the worst imbalance,
    with ``crowd`` = k every selection of every token falls on a held
    expert — still gives the reference's result, forward and gradient."""
    x = _rand(37, (2, 24, D))
    w = _moe_weights(4)
    w["moe_expert_bias"] = w["moe_expert_bias"].at[1:1 + crowd].add(10.0)
    args = (x, *(w[n] for n in _MOE_ORDER))
    moe = _moe(4)
    _, load = moe(*args)
    assert np.all(np.asarray(load)[1:1 + crowd] == 2 * 24)
    _agree(lambda *a: moe(*a)[0], _plain_moe(4), args)


def test_sparse_moe_holding_nothing_routed_gives_nought():
    """No selection falls on the held experts: the share is zero, and so
    are the held experts' gradients."""
    x = _rand(38, (1, 8, D))
    w = _moe_weights(4)
    w["moe_expert_bias"] = w["moe_expert_bias"].at[:4].add(-10.0)
    args = (x, *(w[n] for n in _MOE_ORDER))
    moe = _moe(4)
    assert not np.any(np.asarray(moe(*args)[0]))
    g = jax.grad(lambda *a: jnp.sum(moe(*a)[0]), (3, 4, 5))(*args)
    assert all(np.all(np.isfinite(np.asarray(a))) and not np.any(
        np.asarray(a)) for a in g)


# ------------------------------------------------------------ the model
def _bind(cfg, seed=5):
    net = builder.symbol(cfg, WL)
    shapes = dict(data=(WL["batch"], WL["seq_len"]),
                  softmax_label=(WL["batch"], WL["seq_len"]))
    exe = net.simple_bind(mx.cpu(0), grad_req="write", **shapes)
    params = {k: np.asarray(v, np.float32)
              for k, v in ref.init_params(cfg, seed).items()}
    batch = ref.make_batches(cfg, WL, seed)[0]
    for name, arr in exe.arg_dict.items():
        if name == "data":
            arr[:] = np.asarray(batch[0])
        elif name == "softmax_label":
            arr[:] = np.asarray(batch[1])
        else:
            arr[:] = params[builder.leaf_name(name)]
    return exe, params, batch


def test_toy_model_loss_and_every_leafs_gradient():
    exe, params, batch = _bind(TOY)
    loss = float(exe.forward(is_train=True)[0].asnumpy().ravel()[0])
    exe.backward()
    want, grads, _ = ref.loss_and_grad(
        TOY, {k: jnp.asarray(v) for k, v in params.items()}, batch)
    assert abs(loss - float(want)) < 1e-5 * abs(float(want))
    assert set(grads) == {builder.leaf_name(n) for n in exe.grad_dict
                          if n not in ("data", "softmax_label")}
    for name, g in grads.items():
        got = exe.grad_dict[builder.PREFIX + name].asnumpy()
        if name.endswith("expert_bias"):
            assert not np.any(got), name
        else:
            _close(got, g, 2e-4)
    # the load of every expert layer went to its auxiliary state
    loads = [exe.aux_dict[n].asnumpy() for n in sorted(exe.aux_dict)]
    assert len(loads) == 4
    tokens = WL["batch"] * WL["seq_len"]
    assert all(l.sum() == tokens * TOY["num_experts_per_tok"]
               for l in loads)


def test_toy_model_three_adam_steps_through_the_fused_step(monkeypatch):
    """Module's fused step against the reference's trainer: each step's
    loss, and every leaf's change after the three."""
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    telemetry.enable()
    seed = 11
    net = builder.symbol(TOY, WL)
    shape = (WL["batch"], WL["seq_len"])
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",), context=[mx.cpu(0)])
    mod.bind(data_shapes=[("data", shape)],
             label_shapes=[("softmax_label", shape)])
    start = ref.init_params(TOY, seed)
    mod.init_params(mx.init.Uniform(0.01), arg_params={
        n: mx.nd.array(np.asarray(start[builder.leaf_name(n)], np.float32))
        for n in mod._param_names})
    mod.init_optimizer(kvstore="local", optimizer="adam",
                       optimizer_params=dict(WL["optimizer_params"]))
    fused0 = telemetry.value("step_dispatch_total", path="fused")
    batches = ref.make_batches(TOY, WL, seed)
    losses = []
    for t in range(ref_train.STEPS):
        x, y = batches[t % len(batches)]
        mod.forward_backward(mx.io.DataBatch(
            data=[mx.nd.array(np.asarray(x))],
            label=[mx.nd.array(np.asarray(y))]))
        mod.update()
        losses.append(float(mod.get_outputs()[0].asnumpy().ravel()[0]))
    assert telemetry.value("step_dispatch_total", path="fused") - fused0 == 3
    want = ref_train.run(ref, TOY, WL, seed)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    args, aux = mod.get_params()
    change = {builder.leaf_name(n): float(np.linalg.norm(
        a.asnumpy() - np.asarray(start[builder.leaf_name(n)], np.float32)))
        for n, a in args.items()}
    for name, norm in want["change_norm"].items():
        assert abs(change[name] - norm) <= 2e-3 * max(norm, 1e-6), name
    assert change["l1_moe_expert_bias"] == 0.0
    # get_params is where the load gauge is filled
    layer = builder.PREFIX + "l1_moe"
    got = [telemetry.value("moe_expert_load", layer=layer, expert=str(e))
           for e in range(TOY["num_experts"])]
    np.testing.assert_array_equal(got, aux[layer + "_expert_load"].asnumpy())
    assert sum(got) == WL["batch"] * WL["seq_len"] * 2


def test_reference_faults_and_control_read_apart():
    """The two faults of the mechanism and the lower-precision control,
    planted in the reference, move what `correct` compares."""
    sound = ref_train.run(ref, TOY, WL, 3)
    for fault in ref.FAULTS:
        got = ref_train.run(ref, {**TOY, "fault": fault}, WL, 3)
        gaps = ref_train.compare(got, sound)
        assert gaps["grad_norm_gap"][0] > 0.02, fault
    control = ref_train.compare(
        ref_train.run(ref, TOY, WL, 3, precision=ref_common.CONTROL), sound)
    witness = ref_train.compare(
        ref_train.run(ref, TOY, WL, 3, precision="bfloat16"), sound)
    # by the median leaf: one routing flip at a bfloat16 tie can move a
    # single expert's leaf of a model this small as far as the control does
    assert control["grad_norm_median_gap"][0] > \
        3 * witness["grad_norm_median_gap"][0]


def _tied(tie):
    return TransformerConfig("tie", 64, 1, 32, 2, 64, 8, norm="rms",
                             position="rope", ffn="swiglu", tie_head=tie)


def test_tied_heads_gradient_is_the_sum_of_its_two_uses():
    rng = np.random.RandomState(3)
    shapes = dict(data=(2, 8), softmax_label=(2, 8))
    tied = transformer_lm(_tied(True)).simple_bind(
        mx.cpu(0), grad_req="write", **shapes)
    untied = transformer_lm(_tied(False)).simple_bind(
        mx.cpu(0), grad_req="write", **shapes)
    assert "tfm_lm_head_weight" not in tied.arg_dict
    for name, arr in untied.arg_dict.items():
        if name in shapes:
            value = rng.randint(0, 64, arr.shape).astype(np.float32)
        elif name == "tfm_lm_head_weight":
            continue
        else:
            value = (0.3 * rng.standard_normal(arr.shape)).astype(np.float32)
        arr[:] = value
        tied.arg_dict[name][:] = value
    untied.arg_dict["tfm_lm_head_weight"][:] = \
        untied.arg_dict["tfm_tok_embedding_weight"].asnumpy()
    for exe in (tied, untied):
        exe.forward(is_train=True)
        exe.backward()
    _close(tied.outputs[0].asnumpy(), untied.outputs[0].asnumpy())
    _close(tied.grad_dict["tfm_tok_embedding_weight"].asnumpy(),
           untied.grad_dict["tfm_tok_embedding_weight"].asnumpy()
           + untied.grad_dict["tfm_lm_head_weight"].asnumpy())


def test_gpt2s_seven_fields_build_the_graph_they_built():
    """``transformer_lm`` from GPT-2's seven positional fields: the same
    argument names, in order, and the same loss on seeded weights as before
    the block variants existed (both pinned from the parent commit)."""
    tc = TransformerConfig("pin", 256, 2, 64, 4, 256, 16)
    net = transformer_lm(tc, prefix="tfm_")
    names = net.list_arguments()
    assert len(names) == 31
    assert hashlib.sha1(",".join(names).encode()).hexdigest() == \
        "369628d5e6daf1f08af4c3018ecb05fbfc55102f"
    assert net.list_auxiliary_states() == []
    exe = net.simple_bind(mx.cpu(0), grad_req="null", data=(2, 16),
                          softmax_label=(2, 16))
    rng = np.random.RandomState(7)
    for n in names:
        a = exe.arg_dict[n]
        if n in ("data", "softmax_label"):
            a[:] = rng.randint(0, 256, a.shape).astype(np.float32)
        elif n.endswith("_gamma"):
            a[:] = 1.0 + 0.1 * rng.standard_normal(a.shape).astype(np.float32)
        else:
            a[:] = 0.05 * rng.standard_normal(a.shape).astype(np.float32)
    loss = float(exe.forward(is_train=False)[0].asnumpy().ravel()[0])
    assert abs(loss - 5.631292343139648) < 1e-6


def test_gpt2s_attention_traces_to_the_program_it_was():
    """Without the variants the op's jaxpr has no norm, no rotation and no
    repeated heads.  (At GPT-2-medium's own shape the jaxpr of the op and of
    its gradient through the kernels is letter for letter the parent's:
    PERF.md, PR 27.)"""
    x, w = _rand(40, (1, 16, D)), _rand(41, (D, D), 0.1)
    text = str(jax.make_jaxpr(_op("MultiHeadAttention", num_heads=4))(
        x, w, w, w, w))
    assert not any(p in text for p in ("rsqrt", "cos", "sin", "concatenate"))
    grouped = str(jax.make_jaxpr(_op("MultiHeadAttention", **_GQA))(
        *_attention_args(16)))
    assert all(p in grouped for p in ("rsqrt", "cos", "sin", "concatenate"))


# ------------------------------------------------------- policy and rules
def test_router_and_bias_stay_float32_under_bf16():
    from mxnet_tpu import amp
    net = builder.symbol(TOY, WL)
    types = amp.type_dict_for(net, ("data",), ("softmax_label",))
    for name, t in types.items():
        f32 = name.endswith(("_gamma", "_router_weight", "_expert_bias",
                             "softmax_label"))
        assert (np.dtype(t) == np.float32) == f32, name


def test_megatron_rules_know_the_new_names():
    from mxnet_tpu.parallel.mesh import make_mesh, megatron_rules, P
    devs = jax.devices()[:8]
    rules = megatron_rules(make_mesh({"ep": 4, "tp": 2}, devs))
    for name, shape, spec in [
            ("l0_conv_in_proj_weight", (192, 64), P("tp", None)),
            ("l0_conv_out_proj_weight", (64, 64), P(None, "tp")),
            ("l0_conv_conv_weight", (64, 3), P()),
            ("l0_ffn_gate_weight", (192, 64), P("tp", None)),
            ("l0_ffn_up_weight", (192, 64), P("tp", None)),
            ("l0_ffn_down_weight", (64, 192), P(None, "tp")),
            ("l1_moe_expert_gate_weight", (4, 48, 64), P("ep")),
            ("l1_moe_expert_down_weight", (4, 64, 48), P("ep")),
            ("l1_moe_router_weight", (16, 64), P()),
            ("l1_moe_expert_bias", (16,), P()),
            ("l1_attn_key_weight", (32, 64), P("tp", None))]:
        assert rules.spec_for(name, shape) == spec, name
    no_ep = megatron_rules(make_mesh({"dp": 4, "tp": 2}, devs))
    assert no_ep.spec_for("l1_moe_expert_down_weight", (4, 64, 48)) == P()


def test_config_refuses_what_it_cannot_build():
    with pytest.raises(ValueError):
        TransformerConfig("x", 64, 2, 32, 2, 64, 8, norm="batch")
    with pytest.raises(ValueError):
        TransformerConfig("x", 64, 2, 32, 2, 64, 8, layer_types=("conv",))
    with pytest.raises(ValueError):
        TransformerConfig("x", 64, 1, 32, 2, 64, 8, layer_types=("scan",))


# ------------------------------------- the graphs and kernels of PR 30's cells
@pytest.mark.parametrize("cell,json_sha1,names_sha1", [
    ("gpt2m_train_1k", "a386f0c2266cbe6ba5a4bffb26f9a2cee432fe9f",
     "01b4f1e0a01dc1a8204ed18ee464f1ac37208fb2"),
    ("lfm2moe_train_2k", "06a79f66a7a78f26af3bf35bd388b5c2c29fbcba",
     "5bffd99842a238faef09ac67046265aff19d1da5")])
def test_the_accepted_cells_graphs_are_what_they_were(cell, json_sha1,
                                                      names_sha1):
    """``gpt2_medium`` and ``lfm2_24b_a2b`` at their rehearsal sizes: the
    Symbol's JSON (every node, input and attr: an attr that is off is
    absent) and its arguments and auxiliary states, in order, are letter for
    letter what they were before ``MultiHeadAttention`` had a head size, a
    window and YaRN and ``SparseMoE`` a scoring (sha1s taken on commit
    c48259c)."""
    import os
    from perf import harness
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    got = harness.load_cell(root, cell, rehearse=True)
    with mx.name.NameManager():     # unnamed nodes count from nought
        net = got.builder.symbol(got.config, got.workload)
    assert hashlib.sha1(net.tojson().encode()).hexdigest() == json_sha1
    names = net.list_arguments() + ["|"] + net.list_auxiliary_states()
    assert hashlib.sha1(",".join(names).encode()).hexdigest() == names_sha1


@pytest.mark.parametrize("shape,jaxpr_sha1", [
    ((1, 4, 1024, 64), "e8869a860c1dfe67af21949af06f9b50e76c5723"),
    ((1, 2, 4096, 128), "491860d3020ed32b4da60ad26e1c6a2fb6cfacae")],
    ids=["one_block", "512_blocks"])
def test_flash_attention_without_a_window_is_the_kernels_it_was(shape,
                                                                jaxpr_sha1):
    """``flash_attention(window=None)``, forward and the three gradients,
    traces to one pinned jaxpr (kernel bodies included): in one block as the
    GPT-2 and LFM2 cells run it, and in 512-blocks.  Pinned first on commit
    c48259c, before the kernels knew a window; taken again in PR 34, whose
    backward is one kernel where it was two (the forward's part of the text
    did not change); the lowered text itself carries the checkout's path
    and cannot be pinned."""
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v, True)
                       .astype(jnp.float32) ** 2)

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, q))
    assert hashlib.sha1(text.encode()).hexdigest() == jaxpr_sha1
