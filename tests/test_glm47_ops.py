"""What a GLM-4.7-Flash-class model needs of the program: latent attention
in ``MultiHeadAttention`` (low-rank queries and keys/values, heads split into
a non-rotary and a rotary part, one rotated key part for all heads),
``SparseMoE``'s scaling factor and epsilon, a shared expert beside the routed
ones, the multi-token-prediction module, each against the plain reference
the benchmark keeps (``perf/refs/glm_4_7_flash.py``: float32 ``jax.numpy``,
nothing of the program) at toy widths on seeded weights; and that the graphs
of the configurations the benchmark already had are what they were.
"""
import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models.configs import TransformerConfig
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.registry import OPS

from perf.models import glm_4_7_flash as builder
from perf.refs import glm_4_7_flash as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the benchmark's configuration at toy widths: a dense layer and two with
#: experts, 4 experts held of 16, 2 a token, a head of 24 + 8 (the rotary
#: part a quarter, as the published 192 + 64), the module on top
TOY = {
    "name": "glm_toy", "attention_bias": False, "hidden_act": "silu",
    "hidden_size": 48, "intermediate_size": 96, "moe_intermediate_size": 40,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 2, "num_key_value_heads": 2, "n_group": 1,
    "topk_group": 1, "n_routed_experts": 16, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 32,
    "vocab_size": 200, "num_experts_held": 4, "expert_offset": 0,
    "mtp_loss_weight": 0.1, "dtype": "float32",
}
WL = {"batch": 2, "seq_len": 16}
D, H, HD = TOY["hidden_size"], TOY["num_attention_heads"], 32
LATENT = dict(num_heads=H, head_dim=HD, qk_rope_head_dim=8, q_lora_rank=24,
              kv_lora_rank=16, v_head_dim=32, rope_theta=1e6, eps=1e-5)
MLA = ("q_a_weight", "q_a_norm_gamma", "q_b_weight", "kv_a_weight",
       "kv_a_norm_gamma", "kv_b_weight", "out_proj_weight")


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


def _latent_args(t, key=10):
    """(data, the op's seven weights) at the toy widths."""
    shapes = [(24, D), (24,), (H * HD, 24), (16 + 8, D), (16,),
              (H * (24 + 32), 16), (D, H * 32)]
    ws = [1.0 + _rand(key + i, s, 0.1) if len(s) == 1
          else _rand(key + i, s, 0.2) for i, s in enumerate(shapes, 1)]
    return (_rand(key, (2, t, D)), *ws)


def _plain_attention(x, *ws):
    return ref._attention(TOY, "float32", x,
                          {"mla_" + n: w for n, w in zip(MLA, ws)})


# ------------------------------------------------------- latent attention
def test_latent_attention_on_the_xla_arm():
    _agree(_op("MultiHeadAttention", **LATENT), _plain_attention,
           _latent_args(16))


def test_latent_attention_on_the_kernels(monkeypatch):
    """The same on the flash kernels (interpreted), a head of 32 whose
    rotary part is a quarter, in one block and in 128-blocks."""
    monkeypatch.setattr(pa, "INTERPRET", True)
    telemetry.enable()
    try:
        n0 = telemetry.value("attention_dispatch_total",
                             path="flash_interpret")
        l0 = telemetry.value("attention_latent_total")
        _agree(_op("MultiHeadAttention", **LATENT), _plain_attention,
               _latent_args(128), tol=2e-4)
        assert telemetry.value("attention_dispatch_total",
                               path="flash_interpret") > n0
        assert telemetry.value("attention_latent_total") > l0
    finally:
        telemetry.disable()
    monkeypatch.setattr(pa, "default_blocks", lambda *a: (128, 128))
    args = _latent_args(256, key=30)
    _close(_op("MultiHeadAttention", **LATENT)(*args),
           _plain_attention(*args), tol=2e-4)


def test_the_shared_key_parts_gradient_is_the_sum_over_heads():
    """The output projection adds the heads' outputs, so the op over ``H``
    heads is the sum of ``H`` one-head ops on each head's rows of
    ``q_b_weight`` / ``kv_b_weight`` and columns of ``out_proj_weight``;
    the ONE rotated key part (the last rows of ``kv_a_weight``) gets the
    sum of the heads' gradients."""
    x, qa, gq, qb, kva, gkv, kvb, wo = _latent_args(16)
    probe = _rand(98, (2, 16, D))

    def whole(kva):
        return jnp.sum(_op("MultiHeadAttention", **LATENT)(
            x, qa, gq, qb, kva, gkv, kvb, wo) * probe)

    def head(i, kva):
        one = _op("MultiHeadAttention", **{**LATENT, "num_heads": 1})
        return jnp.sum(one(
            x, qa, gq, qb[i * HD:(i + 1) * HD], kva, gkv,
            kvb[i * 56:(i + 1) * 56], wo[:, i * 32:(i + 1) * 32]) * probe)

    g = jax.grad(whole)(kva)
    parts = [jax.grad(lambda w, i=i: head(i, w))(kva) for i in range(H)]
    _close(g[16:], sum(p[16:] for p in parts))
    assert all(float(jnp.abs(p[16:]).max()) > 0 for p in parts)


def test_latent_attention_refuses_what_it_cannot_build():
    args = _latent_args(16)
    # (q_lora_rank 0 is the full-rank query's form, which takes one query
    # weight where these are three; rope_theta 0 is the form that turns
    # nothing)
    for bad in (dict(qk_rope_head_dim=0), dict(q_lora_rank=0),
                dict(rope_theta=-1.0), dict(window=4), dict(num_kv_heads=1),
                dict(qk_rope_head_dim=HD)):
        with pytest.raises(mx.base.MXNetError):
            _op("MultiHeadAttention", **{**LATENT, **bad})(*args)
    net = mx.sym.MultiHeadAttention(mx.sym.Variable("data"), name="l0_mla",
                                    **LATENT)
    assert net.list_arguments() == ["data"] + ["l0_mla_" + n for n in MLA]
    shapes = net.infer_shape(data=(2, 16, D))[0]
    assert shapes[1:] == [tuple(a.shape) for a in args[1:]]
    # the plain op keeps its five inputs
    plain = mx.sym.MultiHeadAttention(mx.sym.Variable("data"), num_heads=4,
                                      name="a")
    assert plain.list_arguments() == [
        "data", "a_query_weight", "a_key_weight", "a_value_weight",
        "a_out_proj_weight"]


def test_a_head_of_256_at_4096_positions_takes_the_kernels():
    from mxnet_tpu.ops.nn import MHA_KV_VMEM, mha_uses_kernel
    assert MHA_KV_VMEM == 8 << 20
    assert mha_uses_kernel(1, 20, 4096, 256, jnp.bfloat16)
    assert mha_uses_kernel(1, 32, 4096, 128, jnp.bfloat16)
    assert not mha_uses_kernel(1, 20, 8192, 256, jnp.bfloat16)
    # ring attention's own bound is where it was
    assert pa.kv_fits_vmem(4096, 128) and not pa.kv_fits_vmem(4096, 256)
    assert not pa.flash_attention_available(1, 8, 8192, 8192, 128)


# -------------------------------------------------------------- SparseMoE
def _moe_args(held, key=50):
    e, fe = TOY["n_routed_experts"], TOY["moe_intermediate_size"]
    return (_rand(key, (2, 16, D)), _rand(key + 1, (e, D), 0.5),
            _rand(key + 2, (e,), 0.1), _rand(key + 3, (held, fe, D), 0.2),
            _rand(key + 4, (held, fe, D), 0.2),
            _rand(key + 5, (held, D, fe), 0.2), jnp.zeros((e,)))


MOE_NAMES = ("moe_router_weight", "moe_expert_bias", "moe_expert_gate_weight",
             "moe_expert_up_weight", "moe_expert_down_weight")
GLM_MOE = dict(num_experts=16, num_experts_per_tok=2, num_hidden=40,
               routed_scaling=1.8, weight_eps=1e-20)


def test_sparse_moe_scaling_and_epsilon_against_the_reference():
    args = _moe_args(4)

    def op(x, wr, b, g, u, d):
        return _op("SparseMoE", num_held=4, **GLM_MOE)(
            x, wr, b, g, u, d, args[-1])[0]

    def plain(x, *ws):
        return ref.routed_experts(TOY, "float32", x,
                                  dict(zip(MOE_NAMES, ws)))

    _agree(op, plain, args[:-1])
    # the factor is on the weights: 1.8 times what a factor of 1 gives
    one = _op("SparseMoE", num_held=4, **{**GLM_MOE, "routed_scaling": 1.0})
    _close(op(*args[:-1]), 1.8 * one(*args)[0])


def test_sparse_moe_defaults_are_lfm2s():
    """Without the two attrs the op traces to what it traced to: LFM2's
    1e-6 and no multiply by a factor."""
    args = _moe_args(4)
    base = dict(num_experts=16, num_experts_per_tok=2, num_hidden=40,
                num_held=4)
    plain = str(jax.make_jaxpr(_op("SparseMoE", **base))(*args))
    said = str(jax.make_jaxpr(_op(
        "SparseMoE", routed_scaling=1.0, weight_eps=1e-6, **base))(*args))
    assert plain == said
    scaled = str(jax.make_jaxpr(_op(
        "SparseMoE", routed_scaling=1.8, **base))(*args))
    assert scaled.count(" mul ") == plain.count(" mul ") + 1
    from perf.refs import lfm2_24b_a2b as lfm2
    cfg = {"num_experts": 16, "num_experts_per_tok": 2,
           "num_experts_held": 4, "expert_offset": 0,
           "norm_topk_prob": True, "routed_scaling_factor": 1}
    want = lfm2._experts(cfg, "float32", args[0], dict(zip(MOE_NAMES,
                                                           args[1:6])))
    _close(_op("SparseMoE", **base)(*args)[0], want)


def test_the_holders_shares_add_up_to_the_whole_layer():
    """The share test: the four holders' parts of an expert layer (4 of 16
    experts each, ``expert_offset`` 0, 4, 8, 12), with the shared expert,
    which every holder computes alike, counted once, add up to what the
    uncut reference gives for the whole layer."""
    x, wr, b, g, u, d, load = _moe_args(16, key=70)
    fe = TOY["moe_intermediate_size"]
    shared = {"shared_gate_weight": _rand(80, (fe, D), 0.2),
              "shared_up_weight": _rand(81, (fe, D), 0.2),
              "shared_down_weight": _rand(82, (D, fe), 0.2)}
    whole = ref.expert_layer(
        {**TOY, "num_experts_held": 16}, "float32", x,
        {**dict(zip(MOE_NAMES, (wr, b, g, u, d))), **shared})
    parts = [
        _op("SparseMoE", num_held=4, expert_offset=off, **GLM_MOE)(
            x, wr, b, g[off:off + 4], u[off:off + 4], d[off:off + 4],
            load)[0]
        for off in (0, 4, 8, 12)]
    fc = _op("FullyConnected", num_hidden=fe, flatten=False, no_bias=True)
    down = _op("FullyConnected", num_hidden=D, flatten=False, no_bias=True)
    once = down(jax.nn.silu(fc(x, shared["shared_gate_weight"]))
                * fc(x, shared["shared_up_weight"]),
                shared["shared_down_weight"])
    _close(sum(parts) + once, whole)
    # every part is needed, and so is the shared expert
    assert float(jnp.abs(parts[3]).max()) > 0
    assert float(jnp.abs(whole - sum(parts)).max()) > 1e-3


# ------------------------------------------------- the model and its module
def _bound(cfg=TOY, seed=5):
    """The program's graph bound on the CPU with the reference's weights
    (float32), one batch, forward and backward run."""
    net = builder.symbol(cfg, WL)
    b, t = WL["batch"], WL["seq_len"]
    exe = net.simple_bind(mx.cpu(0), grad_req="write", data=(b, t),
                          softmax_label=(b, t))
    params = {k: v.astype(jnp.float32)
              for k, v in ref.init_params(cfg, seed).items()}
    rng = np.random.RandomState(seed)
    # gains off one and weights large enough that every leaf's gradient
    # stands clear of rounding
    params = {k: (1.0 + 0.1 * rng.standard_normal(v.shape)
                  if k.endswith("_gamma") else 5.0 * np.asarray(v))
              .astype(np.float32) for k, v in params.items()}
    ids = rng.randint(0, cfg["vocab_size"], (b, t + 1)).astype(np.float32)
    for name, arr in exe.arg_dict.items():
        if name == "data":
            arr[:] = ids[:, :-1]
        elif name == "softmax_label":
            arr[:] = ids[:, 1:]
        else:
            arr[:] = params[builder.leaf_name(name)]
    exe.forward(is_train=True)
    exe.backward()
    return exe, {k: jnp.asarray(v) for k, v in params.items()}, \
        (jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:]))


def test_the_model_agrees_with_the_reference_leaf_by_leaf():
    """Loss and every leaf's gradient: the latent layers, the dense layer,
    the routed and the shared experts, the module; the embedding and the
    head, each one graph variable used twice, get the sum of their two
    gradients."""
    exe, params, batch = _bound()
    loss, grads, _ = ref.loss_and_grad(TOY, params, batch)
    assert abs(float(exe.outputs[0].asnumpy().ravel()[0]) - float(loss)) \
        < 2e-5 * float(loss)
    assert set(exe.grad_dict) - {"data", "softmax_label"} == \
        {"tfm_" + k for k in grads}
    for name, want in grads.items():
        got = exe.grad_dict["tfm_" + name].asnumpy()
        if name.endswith("expert_bias"):
            assert not np.any(got) and not np.any(np.asarray(want))
            continue
        _close(got, want, tol=3e-4)
    # the two uses: without the module's the two leaves read apart
    main_only = ref.loss_and_grad({**TOY, "fault": "no_mtp"}, params,
                                  batch)[1]
    for name in ("tok_embedding_weight", "lm_head_weight"):
        got = exe.grad_dict["tfm_" + name].asnumpy()
        gap = np.abs(got - np.asarray(main_only[name])).max()
        assert gap > 1e-2 * np.abs(got).max(), name


def test_the_last_position_is_out_of_the_second_loss():
    """loss = CE_main + 0.1 * the mean of the module's cross-entropy over
    the positions that have a target two ahead: all but a row's last.  The
    reference's two parts say so; ``no_mtp`` reads apart."""
    exe, params, batch = _bound()
    got = float(exe.outputs[0].asnumpy().ravel()[0])
    main = float(ref.loss_and_grad({**TOY, "fault": "no_mtp"}, params,
                                   batch)[0])
    second = (got - main) / TOY["mtp_loss_weight"]
    # a cross-entropy over 200 rows at these weights: near ln(200)
    assert 0.5 * np.log(200) < second < 2.0 * np.log(200)
    # the module's hidden state at the last position moves nothing: a
    # label that only the cut-off slot reads (the first, rolled to the end)
    # is the main loss's alone
    ids, labels = batch
    exe2, _, _ = _bound()
    moved = np.asarray(labels).copy()
    moved[:, 0] = (moved[:, 0] + 1) % TOY["vocab_size"]
    exe2.arg_dict["softmax_label"][:] = moved
    exe2.forward(is_train=True)
    both = ref.loss_and_grad(TOY, params, (ids, jnp.asarray(moved)))[0]
    assert abs(float(exe2.outputs[0].asnumpy().ravel()[0]) - float(both)) \
        < 2e-5 * float(both)


def test_the_references_blocks_change_nothing(monkeypatch):
    """The reference works its heads in groups, its queries and the heads'
    logits in blocks, so that it fits beside ``perf/refs/train.py``'s state
    on the chip: the sizes of the blocks move no number."""
    _, params, batch = _bound()
    loss, grads, _ = ref.loss_and_grad(TOY, params, batch)
    for name, size in (("HEAD_GROUP", 1), ("Q_BLOCK", 4), ("HEAD_ROWS", 8)):
        monkeypatch.setattr(ref, name, size)
    ref._stages.cache_clear()
    try:
        loss2, grads2, _ = ref.loss_and_grad(TOY, params, batch)
    finally:
        ref._stages.cache_clear()
    assert abs(float(loss2 - loss)) < 1e-6 * float(loss)
    for name in grads:
        _close(grads2[name], grads[name], tol=1e-5)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_reads_apart(fault):
    """Every fault planted in the reference moves the loss or a leaf's
    gradient by far more than the program differs from the sound one."""
    _, params, batch = _bound()
    loss, grads, _ = ref.loss_and_grad(TOY, params, batch)
    bad_loss, bad, _ = ref.loss_and_grad({**TOY, "fault": fault}, params,
                                         batch)
    gap = max(float(jnp.linalg.norm(bad[k] - grads[k])
                    / (jnp.linalg.norm(grads[k]) + 1e-12)) for k in grads
              if not k.endswith("expert_bias"))
    assert gap > 0.02 or abs(float(bad_loss - loss)) > 1e-3 * float(loss)


def test_loss_false_returns_the_main_logits_only():
    from mxnet_tpu.models import transformer_lm
    tc = TransformerConfig(
        "g", 200, 2, 48, 2, 96, 16, norm="rms", position="rope", ffn="swiglu",
        attention="latent", q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
        num_dense_layers=1, num_experts=16, experts_per_tok=2,
        experts_held=4, moe_d_ff=40, n_shared_experts=1, mtp_layers=1,
        mtp_loss_weight=0.1)
    net = transformer_lm(tc, loss=False)
    assert not any("mtp0" in n for n in net.list_arguments())
    assert net.infer_shape(data=(2, 16))[1] == [(2, 16, 200)]
    telemetry.enable()
    try:
        s0 = telemetry.value("moe_shared_experts_total")
        m0 = telemetry.value("mtp_modules_total")
        names = transformer_lm(tc).list_arguments()
        assert telemetry.value("moe_shared_experts_total") == s0 + 2
        assert telemetry.value("mtp_modules_total") == m0 + 1
    finally:
        telemetry.disable()
    # one variable each for the embedding and the head, used twice
    for leaf in ("tfm_tok_embedding_weight", "tfm_lm_head_weight"):
        assert names.count(leaf) == 1
    assert "tfm_mtp0_embedding_weight" not in names
    assert "tfm_mtp0_head_weight" not in names


def test_config_refuses_what_it_cannot_build():
    base = ("x", 64, 2, 32, 2, 64, 8)
    with pytest.raises(ValueError):
        TransformerConfig(*base, attention="latent")       # no sizes
    with pytest.raises(ValueError):
        TransformerConfig(*base, attention="linear")
    with pytest.raises(ValueError):
        TransformerConfig(*base, mtp_layers=2)
    with pytest.raises(ValueError):
        builder.symbol({**TOY, "n_group": 2}, WL)


def test_megatron_rules_know_the_latent_names():
    from mxnet_tpu.parallel.mesh import make_mesh, megatron_rules, P
    rules = megatron_rules(make_mesh({"dp": 4, "tp": 2}, jax.devices()[:8]))
    for name, shape, spec in [
            ("tfm_l1_mla_q_a_weight", (24, 48), P()),
            ("tfm_l1_mla_kv_a_weight", (24, 48), P()),
            ("tfm_l1_mla_q_b_weight", (64, 24), P("tp", None)),
            ("tfm_l1_mla_kv_b_weight", (112, 16), P("tp", None)),
            ("tfm_l1_mla_out_proj_weight", (48, 64), P(None, "tp")),
            ("tfm_l1_mla_q_a_norm_gamma", (24,), P()),
            ("tfm_l1_shared_gate_weight", (40, 48), P("tp", None)),
            ("tfm_l1_shared_down_weight", (48, 40), P(None, "tp")),
            ("tfm_mtp0_proj_weight", (48, 96), P("tp", None))]:
        assert rules.spec_for(name, shape) == spec, name


# ------------------------------ the graphs the benchmark had are what they were
@pytest.mark.parametrize("cell,sha1", [
    ("gpt2m_train_1k", "8a653d6ce2120e7b0ef3ce2dfc90db02a1ce0eb2"),
    ("lfm2moe_train_2k", "cba1de751a71effd3e87c81e3934174c5f37d236"),
    ("mellum2moe_train_4k", "e714e52fd840b5f1b87e39a3870aecbf9e3f1eef")])
def test_the_accepted_cells_programs_are_the_parents(cell, sha1,
                                                     monkeypatch):
    """``gpt2_medium``, ``lfm2_24b_a2b`` and ``mellum2_12b_a2_5b`` at their
    rehearsal sizes under the bf16 policy: the lowered text of forward +
    backward is letter for letter what the parent commit (2c03997) lowers
    them to, before ``MultiHeadAttention`` had a latent form and ``SparseMoE``
    a scaling factor (no kernel at these sizes, so the text carries no
    path)."""
    from mxnet_tpu import amp
    from perf import harness
    monkeypatch.setenv("MXNET_TPU_BF16", "1")
    got = harness.load_cell(ROOT, cell, rehearse=True)
    with mx.name.NameManager():     # unnamed nodes count from nought
        net = got.builder.symbol(got.config, got.workload)
    data, label = got.builder.shapes(got.config, got.workload)
    exe = net.simple_bind(
        mx.cpu(0), grad_req="write",
        type_dict=amp.type_dict_for(net, tuple(data), tuple(label)),
        **data, **label)
    plan = exe._plan(True)
    text = exe._fwd_bwd_fn().lower(
        [exe.arg_dict[n]._data for n in plan.arg_names],
        [exe.aux_dict[n]._data for n in plan.aux_names],
        exe._keys(plan), exe._default_ograds()).as_text()
    assert hashlib.sha1(text.encode()).hexdigest() == sha1
