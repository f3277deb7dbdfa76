"""What a Kimi-Linear-class model needs of the program: Kimi Delta Attention
(``DeltaAttention``: the gated delta rule in chunks, forward and backward,
against the recurrence position by position), latent attention without a
query rank and without positions, the flash kernels with values narrower
than the keys, and the model built by ``transformer_lm`` from the
configuration, each against the plain reference the benchmark keeps
(``perf/refs/kimi_linear_48b_a3b.py``: float32 ``jax.numpy``, nothing of the
program) at toy widths on seeded weights.

Tolerances: the program and the reference both run float32 on the CPU, so
what differs is the order of the sums (the chunked form against the
recurrence, the kernels' blocks against one softmax): 1e-4 of the largest
value where a chunked sum meets a sequential one over up to 150 positions,
2e-4 on the interpreted kernels (as ``tests/test_glm47_ops.py`` has them),
2e-5 where the two compute the same sums in the same order.
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models.configs import TransformerConfig
from mxnet_tpu.ops import delta_rule as dr
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import pallas_delta_rule as pdr
from mxnet_tpu.ops.nn import _mha_reference, mha_uses_kernel
from mxnet_tpu.ops.registry import OPS

from perf.models import kimi_linear_48b_a3b as builder
from perf.refs import kimi_linear_48b_a3b as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the benchmark's configuration at toy widths: the same five layers (a
#: dense KDA layer, then KDA, KDA, MLA, KDA with experts), 4 experts held of
#: 16, 4 a token, 4 KDA heads of 8, 4 latent heads of 12 + 4 with values of 8
TOY = {
    "name": "kimi_toy", "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 32, "intermediate_size": 64, "kv_lora_rank": 16,
    "linear_attn_config": {"full_attn_layers": [4], "head_dim": 8,
                           "kda_layers": [1, 2, 3, 5], "num_heads": 4,
                           "short_conv_kernel_size": 4},
    "mla_use_nope": True, "moe_intermediate_size": 24,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_attention_heads": 4, "num_expert_group": 1, "num_experts": 16,
    "num_experts_per_token": 4, "num_hidden_layers": 5,
    "num_key_value_heads": 4, "num_nextn_predict_layers": 0,
    "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 4, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "v_head_dim": 8, "vocab_size": 200, "num_experts_held": 4,
    "expert_offset": 0, "dtype": "float32",
}
WL = {"batch": 2, "seq_len": 80}            # two chunks, the second short
D, H, DK = 32, 4, 8
KDA_NAMES = ("query_weight", "key_weight", "value_weight", "q_conv_weight",
             "k_conv_weight", "v_conv_weight", "f_a_weight", "f_b_weight",
             "A_log", "dt_bias", "beta_weight", "g_a_weight", "g_b_weight",
             "o_norm_gamma", "out_proj_weight")


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
    assert np.all(np.isfinite(got)) and np.all(np.isfinite(want))
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


# ------------------------------------------------------ the chunked rule
def _rule_args(decay, T=150, dv=8, dk=16):
    """q, k (unit per head), v, g, beta over B 2, H 2: ``decay`` is the
    log-decay's scale a step (``A_log = log 16`` and gate inputs up to
    about 3 give 16 x softplus(3) = 48)."""
    B, Hh = 2, 2
    q, k = (_rand(s, (B, T, Hh, dk)) for s in (1, 2))
    q, k = (a / jnp.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    v = _rand(3, (B, T, Hh, dv))
    g = -decay * jax.nn.softplus(_rand(4, (B, T, Hh, dk), 2.0))
    # half the channels barely decay: the state keeps something to carry
    g = g * (jax.random.uniform(jax.random.PRNGKey(5), g.shape) < 0.5)
    beta = jax.nn.sigmoid(_rand(6, (B, T, Hh)))
    return q, k, v, g, beta


@pytest.mark.parametrize("decay,form", [
    (0.05, "chunked"), (16.0, "chunked"), (1000.0, "chunked"),
    (0.05, "kernel"), (16.0, "kernel"), (1000.0, "kernel")],
    ids=["mild", "A_log_log16", "extreme", "kernel_mild",
         "kernel_A_log_log16", "kernel_extreme"])
def test_chunked_rule_agrees_with_the_recurrence(decay, form):
    """Over 150 positions (three chunks, the last short), B 2, values
    narrower than keys; at decays as strong as ``A_log = log 16`` with large
    gate inputs and far past them (a chunk's log-decay reaches -64,000:
    any ``exp(-G)`` would overflow), the outputs and the gradients of q, k,
    v, g and beta are the recurrence's, finite.  ``kernel``: the Pallas
    kernels (interpreted) at heads of 128, against the recurrence and
    against the chunked form."""
    with jax.default_matmul_precision("highest"):
        if form == "chunked":
            _agree(dr.gated_delta_rule, dr.delta_rule_reference,
                   _rule_args(decay), tol=1e-4)
            return
        args = _rule_args(decay, dv=128, dk=128)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(pdr, "INTERPRET", True)
            _agree(dr.gated_delta_rule, dr.delta_rule_reference, args,
                   tol=1e-4)
            _agree(dr.gated_delta_rule,
                   lambda *a: dr._chunked_rule(*a, 128 ** -0.5), args,
                   tol=1e-4)


def _kernels_in(jaxpr, prefix=""):
    """(name stack, kernel) of every ``pallas_call`` in a jaxpr, the name
    stacks of the equations around it joined in front."""
    for eqn in jaxpr.eqns:
        stack = "/".join(s for s in (prefix, str(eqn.source_info.name_stack))
                         if s)
        if eqn.primitive.name == "pallas_call":
            yield stack
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernels_in(sub, stack)


def test_kernel_rule_counts_its_path_and_carries_its_scope():
    """At heads of 128 one ``linear_attention_dispatch_total{path="kernel"}``
    a core traced and none ``chunked``; the forward and the backward kernel
    are the two ``pallas_call``s of the gradient, each under the op's
    ``DeltaAttention:<node>`` scope and the part ``delta_rule``, so that
    ``kda_core_roofline_pct.train``'s pattern finds both.  Heads that do not
    fill the lanes, or an odd number of them, keep the chunked form."""
    q = jax.ShapeDtypeStruct((1, 150, 2, 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 150, 2, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, 150, 2), jnp.float32)

    def loss(*a):
        with jax.named_scope("DeltaAttention:tfm_l0_kda"):
            return jnp.sum(dr.gated_delta_rule(*a).astype(jnp.float32))

    telemetry.enable()
    try:
        n0 = {p: telemetry.value("linear_attention_dispatch_total", path=p)
              for p in ("kernel", "chunked")}
        jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2, 3, 4)))(
            q, q, q, g, beta).jaxpr
        assert telemetry.value("linear_attention_dispatch_total",
                               path="kernel") == n0["kernel"] + 1
        assert telemetry.value("linear_attention_dispatch_total",
                               path="chunked") == n0["chunked"]
    finally:
        telemetry.disable()
    stacks = list(_kernels_in(jaxpr))
    assert [s.rsplit("/", 1)[-1] for s in stacks] == ["delta_rule_fwd",
                                                     "delta_rule_bwd"]
    with open(os.path.join(ROOT, "perf", "metrics",
                           "kda_core_roofline_pct.train.json")) as f:
        reads = re.compile(json.load(f)["params"]["kernel"])
    assert all(reads.search(s) for s in stacks)
    assert stacks[1].startswith("transpose(")
    assert pdr.available(2, 128, 128) and pdr.available(64, 256, 128)
    assert not pdr.available(3, 128, 128)
    assert not pdr.available(2, 16, 8) and not pdr.available(2, 128, 64)


def test_kernel_rule_runs_each_device_on_its_own_rows():
    """Under a mesh in context the kernels run under ``shard_map``, each
    device on its own batch rows (``dp``): the output and the gradients are
    those of the call without a mesh, and the kernel path is counted."""
    from jax.sharding import Mesh
    args = _rule_args(16.0, T=64, dv=128, dk=128)
    probe = _rand(99, args[2].shape)

    def run(*a):
        return jax.grad(lambda *x: jnp.sum(dr.gated_delta_rule(*x) * probe),
                        (0, 1, 2, 3, 4))(*a)

    with pytest.MonkeyPatch.context() as mp, \
            jax.default_matmul_precision("highest"):
        mp.setattr(pdr, "INTERPRET", True)
        want = jax.jit(run)(*args)
        telemetry.enable()
        try:
            n0 = telemetry.value("linear_attention_dispatch_total",
                                 path="kernel")
            with jax.set_mesh(Mesh(np.array(jax.devices()[:2]), ("dp",))):
                got = jax.jit(run)(*args)
            assert telemetry.value("linear_attention_dispatch_total",
                                   path="kernel") == n0 + 1
        finally:
            telemetry.disable()
    for a, b in zip(got, want):
        _close(a, b, 1e-5)


def test_chunked_rule_counts_its_path_and_carries_its_scope():
    """One ``linear_attention_dispatch_total{path="chunked"}`` a core traced;
    forward and backward operations carry the scope ``delta_rule``."""
    args = _rule_args(1.0, T=64)
    telemetry.enable()
    try:
        n0 = telemetry.value("linear_attention_dispatch_total",
                             path="chunked")
        text = jax.jit(jax.grad(lambda *a: jnp.sum(
            dr.gated_delta_rule(*a)), (0, 1, 2, 3, 4))).lower(
                *args).compile().as_text()
        assert telemetry.value("linear_attention_dispatch_total",
                               path="chunked") == n0 + 1
    finally:
        telemetry.disable()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("delta_rule" in n and "transpose(" not in n for n in names)
    assert any("delta_rule" in n and "transpose(" in n for n in names)


# ------------------------------------------------------------ the KDA op
def _kda_args(key=10, T=80, strong=False):
    """(data, the op's fifteen weights) at the toy widths.  ``strong``:
    every head's rate at 16 (``A_log = log 16``) and the gate's bias at 3."""
    hd = H * DK
    shapes = [(hd, D)] * 3 + [(hd, 4)] * 3 + [(DK, D), (hd, DK), (H,),
                                              (hd,), (H, D), (DK, D),
                                              (hd, DK), (DK,), (D, hd)]
    ws = [_rand(key + i, s, 0.5 if i in (4, 5, 6) else 0.3)
          for i, s in enumerate(shapes, 1)]
    ws[8] = jnp.full((H,), np.log(16.0) if strong else 0.5)
    ws[9] = jnp.full((hd,), 3.0 if strong else -2.0) + _rand(key, (hd,), 0.5)
    ws[13] = 1.0 + _rand(key + 20, (DK,), 0.1)
    return (_rand(key, (2, T, D)), *ws)


def _plain_kda(x, *ws):
    return ref._kda(TOY, "float32", x,
                    {"kda_" + n: w for n, w in zip(KDA_NAMES, ws)})


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
def test_the_op_agrees_with_the_plain_layer(strong):
    """``DeltaAttention`` against the reference's layer (projections, the
    three convolutions, L2 norms, gates, the recurrence, the gated norm, the
    output product): the output and the gradient of the data and of every
    weight, over 80 positions (a chunk and a part)."""
    with jax.default_matmul_precision("highest"):
        _agree(_op("DeltaAttention", num_heads=H, head_dim=DK), _plain_kda,
               _kda_args(strong=strong), tol=1e-4)


def test_the_op_names_its_inputs_and_their_shapes():
    net = mx.sym.DeltaAttention(mx.sym.Variable("data"), num_heads=H,
                                head_dim=DK, name="l0_kda")
    assert net.list_arguments() == ["data"] + ["l0_kda_" + n
                                               for n in KDA_NAMES]
    shapes = net.infer_shape(data=(2, 80, D))[0]
    assert shapes[1:] == [tuple(a.shape) for a in _kda_args()[1:]]
    with pytest.raises(mx.base.MXNetError):
        _op("DeltaAttention", num_heads=H, head_dim=DK + 1)(*_kda_args())


def test_the_decay_rates_stay_float32_under_the_bf16_policy():
    from mxnet_tpu import amp
    net = mx.sym.DeltaAttention(mx.sym.Variable("data"), num_heads=H,
                                head_dim=DK, name="l0_kda")
    td = amp.type_dict_for(net, ("data",), ())
    for name in ("A_log", "dt_bias", "o_norm_gamma"):
        assert td["l0_kda_" + name] == np.float32
    assert td["l0_kda_f_b_weight"] == amp.compute_dtype()


# ---------------------------------------------- latent attention, Kimi's form
LATENT = dict(num_heads=4, head_dim=16, qk_rope_head_dim=4, q_lora_rank=0,
              kv_lora_rank=16, v_head_dim=8, rope_theta=0.0, eps=1e-5)
MLA_NAMES = ("query_weight", "kv_a_weight", "kv_a_norm_gamma",
             "kv_b_weight", "out_proj_weight")


def _latent_args(t, key=40):
    shapes = [(4 * 16, D), (16 + 4, D), (16,), (4 * (12 + 8), 16), (D, 32)]
    ws = [1.0 + _rand(key + i, s, 0.1) if len(s) == 1
          else _rand(key + i, s, 0.3) for i, s in enumerate(shapes, 1)]
    return (_rand(key, (2, t, D)), *ws)


def _plain_mla(x, *ws):
    return ref._mla(TOY, "float32", x,
                    {"mla_" + n: w for n, w in zip(MLA_NAMES, ws)})


def test_latent_attention_without_query_rank_or_positions():
    """One full-rank query product, nothing turned, the 4-wide key part
    shared by the four heads, values of 8 under keys of 16: on the XLA arm, and
    on the flash kernels (interpreted) in 128-blocks."""
    op = _op("MultiHeadAttention", **LATENT)
    _agree(op, _plain_mla, _latent_args(80))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "INTERPRET", True)
        telemetry.enable()
        try:
            n0 = telemetry.value("attention_dispatch_total",
                                 path="flash_interpret")
            _agree(op, _plain_mla, _latent_args(256, key=50), tol=2e-4)
            assert telemetry.value("attention_dispatch_total",
                                   path="flash_interpret") > n0
        finally:
            telemetry.disable()
    net = mx.sym.MultiHeadAttention(mx.sym.Variable("data"), name="l3_mla",
                                    **LATENT)
    assert net.list_arguments() == ["data"] + ["l3_mla_" + n
                                               for n in MLA_NAMES]
    shapes = net.infer_shape(data=(2, 16, D))[0]
    assert shapes[1:] == [tuple(a.shape) for a in _latent_args(16)[1:]]
    # a rotation reads apart: the form is not GLM's with a dead rope
    turned = _op("MultiHeadAttention", **{**LATENT, "rope_theta": 1e4})
    args = _latent_args(16)
    assert float(jnp.abs(turned(*args) - op(*args)).max()) > 1e-3


# ------------------------------------ the flash kernels, values of their own
@pytest.mark.parametrize("blocks", [None, 128], ids=["one_block", "128"])
def test_flash_kernels_take_values_narrower_than_keys(blocks):
    """``flash_fwd`` / ``flash_dqkv`` with keys of 192 and values of 128
    (the cell's latent layer), interpreted, against ``_mha_reference``:
    the output and the gradients of q, k and v."""
    r = np.random.RandomState(7)
    q, k = (jnp.asarray(r.randn(1, 2, 256, 192), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(r.randn(1, 2, 256, 128), jnp.float32)
    scale = 192 ** -0.5
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "INTERPRET", True)
        _agree(lambda *a: pa.flash_attention(*a, True, scale, blocks,
                                             blocks),
               lambda *a: _mha_reference(*a, True, scale), (q, k, v),
               tol=2e-4)


@pytest.mark.parametrize("shape,window,sha1", [
    ((1, 4, 1024, 64), None, "5c8ca3a2baf48d32f1f06408dc4e8fd848b824cb"),
    ((1, 2, 4096, 256), None, "6b021e5f7d6589caa5c69fbd11391a0c9ea940eb"),
    ((1, 4, 4096, 128), 1024, "ad3c144af9ac02eafee2647b4926b5196709fa17")],
    ids=["gpt2m_one_block", "glm47_512_blocks", "mellum2_window"])
def test_the_kernels_at_equal_widths_are_the_parents(shape, window, sha1):
    """Where values are as wide as keys (every cell before this one), the
    forward and backward kernels' programs (their jaxprs: grid, blocks and
    body) are letter for letter what the parent commit (69e7c8d) traces."""
    import hashlib
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    st = jax.ShapeDtypeStruct(shape[:3], jnp.float32)
    sc = shape[-1] ** -0.5
    text = str(jax.make_jaxpr(lambda a, b, c: pa._fwd_program(
        a, b, c, True, sc, None, None, "lse", False, window))(q, q, q))
    text += str(jax.make_jaxpr(lambda a, b, c, d, l, e: pa._dqkv_program(
        a, b, c, d, l, e, True, sc, None, None, jnp.dtype(jnp.bfloat16),
        False, window))(q, q, q, q, st, st))
    assert hashlib.sha1(text.encode()).hexdigest() == sha1


def test_the_cells_latent_shape_takes_the_kernels():
    """1 x 32 heads x 4096 positions, keys of 192 and values of 128: the
    kernel arm, the keys' and values' VMEM counted apart; at values as wide
    as the keys the budgets are what they were."""
    assert mha_uses_kernel(1, 32, 4096, 192, jnp.bfloat16, dv=128)
    # 4096 x (320 + 320) x 2 bytes, double-buffered, is past 8 MB
    assert not mha_uses_kernel(1, 32, 4096, 320, jnp.bfloat16, dv=320)
    assert mha_uses_kernel(1, 32, 4096, 320, jnp.bfloat16, dv=128)
    assert pa.kv_fits_vmem(4096, 256, jnp.bfloat16, 8 << 20) \
        == pa.kv_fits_vmem(4096, 256, jnp.bfloat16, 8 << 20, dv=256)
    for shape in ((1, 4096, 512, 512, 256, 2, 2), (1, 8192, 512, 512, 128, 2,
                                                   2)):
        G, Tq, TQ, BK, Dh, i, o = shape
        lanes = -(-Dh // 128) * 128
        whole, block = G * Tq * lanes, G * BK * lanes
        stats = 2 * 2 * G * (Tq // TQ) * 8 * TQ * 4
        before = (2 * 2 * whole * i + 2 * 2 * block * i + 2 * 2 * block * o
                  + stats + whole * 4 + 2 * whole * o)
        assert pa._bwd_vmem_bytes(*shape) == before


# ----------------------------------------------------------------- experts
MOE_NAMES = ("moe_router_weight", "moe_expert_bias", "moe_expert_gate_weight",
             "moe_expert_up_weight", "moe_expert_down_weight")
KIMI_MOE = dict(num_experts=16, num_experts_per_tok=4, num_hidden=24,
                routed_scaling=2.446, weight_eps=1e-20)


def test_the_holders_shares_add_up_to_the_whole_layer():
    """The share test: the four holders' parts of an expert layer (4 of 16
    experts each, ``expert_offset`` 0, 4, 8, 12, 4 experts a token), with
    the shared expert, which every holder computes alike, counted once, add
    up to what the uncut reference gives for the whole layer."""
    e, fe = 16, 24
    x = _rand(70, (2, 16, D))
    wr, b = _rand(71, (e, D), 0.5), _rand(72, (e,), 0.1)
    g, u = _rand(73, (e, fe, D), 0.2), _rand(74, (e, fe, D), 0.2)
    d = _rand(75, (e, D, fe), 0.2)
    shared = {"shared_gate_weight": _rand(80, (fe, D), 0.2),
              "shared_up_weight": _rand(81, (fe, D), 0.2),
              "shared_down_weight": _rand(82, (D, fe), 0.2)}
    whole = ref.expert_layer(
        {**TOY, "num_experts_held": 16}, "float32", x,
        {**dict(zip(MOE_NAMES, (wr, b, g, u, d))), **shared})
    parts = [
        _op("SparseMoE", num_held=4, expert_offset=off, **KIMI_MOE)(
            x, wr, b, g[off:off + 4], u[off:off + 4], d[off:off + 4],
            jnp.zeros((e,)))[0]
        for off in (0, 4, 8, 12)]
    fc = _op("FullyConnected", num_hidden=fe, flatten=False, no_bias=True)
    down = _op("FullyConnected", num_hidden=D, flatten=False, no_bias=True)
    once = down(jax.nn.silu(fc(x, shared["shared_gate_weight"]))
                * fc(x, shared["shared_up_weight"]),
                shared["shared_down_weight"])
    _close(sum(parts) + once, whole, tol=5e-5)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
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
    # gains off one and matrices large enough that every leaf's gradient
    # stands clear of rounding; the decay rates and taps as drawn
    params = {k: (1.0 + 0.1 * rng.standard_normal(v.shape)
                  if k.endswith("_gamma") else
                  np.asarray(v) if k.endswith(("_A_log", "_dt_bias",
                                               "_conv_weight"))
                  else 5.0 * np.asarray(v))
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
    """Loss and every leaf's gradient: four KDA layers (the first dense),
    the latent layer without positions, the routed and the shared experts,
    the head; four chunked cores are traced, one a KDA layer."""
    telemetry.enable()
    try:
        n0 = telemetry.value("linear_attention_dispatch_total",
                             path="chunked")
        exe, params, batch = _bound()
        assert telemetry.value("linear_attention_dispatch_total",
                               path="chunked") - n0 in (4, 8)
    finally:
        telemetry.disable()
    with jax.default_matmul_precision("highest"):
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
        _close(got, want, tol=5e-4)


def test_the_references_blocks_change_nothing(monkeypatch):
    """The reference checkpoints its recurrence, its queries and its
    head's rows in blocks, so that it fits beside ``perf/refs/train.py``'s
    state on the chip: the sizes of the blocks move no number."""
    _, params, batch = _bound()
    loss, grads, _ = ref.loss_and_grad(TOY, params, batch)
    for name, size in (("KDA_BLOCK", 16), ("Q_BLOCK", 16),
                       ("HEAD_ROWS", 8)):
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


def test_config_takes_kda_layers_latent_layers_and_no_positions():
    base = ("x", 64, 2, 32, 2, 64, 8)
    latent = dict(attention="latent", kv_lora_rank=16, qk_nope_head_dim=12,
                  qk_rope_head_dim=4, v_head_dim=8)
    tc = TransformerConfig(*base, position="none",
                           layer_types=("kda", "full_attention"),
                           kda_head_dim=16, **latent)
    assert tc.q_lora_rank == 0
    with pytest.raises(ValueError):          # a kda layer needs its heads
        TransformerConfig(*base, position="none",
                          layer_types=("kda", "full_attention"), **latent)
    with pytest.raises(ValueError):          # latent layers take no window
        TransformerConfig(*base, position="none", window=4,
                          kda_head_dim=16,
                          layer_types=("sliding_attention", "kda"), **latent)
    with pytest.raises(ValueError):
        TransformerConfig(*base, position="alibi")
    with pytest.raises(ValueError):
        builder.symbol({**TOY, "mla_use_nope": False}, WL)
    net = builder.symbol(TOY, WL)
    names = net.list_arguments()
    assert sum(n.endswith("_kda_A_log") for n in names) == 4
    assert "tfm_l3_mla_query_weight" in names
    assert not any("pos_embedding" in n or "q_a_weight" in n for n in names)


def test_the_configuration_file_holds_the_catalog_row():
    with open(os.path.join(ROOT, "perf", "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        cfg = json.load(f)
    published = {"first_k_dense_replace": 1, "head_dim": 72,
                 "hidden_size": 2304, "intermediate_size": 9216,
                 "kv_lora_rank": 512, "moe_intermediate_size": 1024,
                 "num_attention_heads": 32, "num_experts": 256,
                 "num_experts_per_token": 8, "num_shared_experts": 1,
                 "q_lora_rank": None, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "rms_norm_eps": 1e-5, "routed_scaling_factor": 2.446,
                 "mla_use_nope": True, "tie_word_embeddings": False}
    for key, value in published.items():
        assert cfg[key] == value, key
    lin = cfg["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 163840
    assert cfg["num_experts_held"] * 32 == cfg["num_experts"]
    assert [ref.mixer(cfg, l) for l in range(5)] == \
        ["kda", "kda", "kda", "mla", "kda"]
    assert set(cfg["reduced_note"]) == set(cfg["reduced"])
    n = 0
    for _, shape, _, _ in ref.param_spec(cfg):
        n += int(np.prod(shape))
    assert n == 602434432


def test_operations_and_bytes_by_hand():
    with open(os.path.join(ROOT, "perf", "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        cfg = json.load(f)
    wl = {"batch": 1, "seq_len": 4096}
    t = 4096
    assert ref.kda_core_flops(cfg, wl) == 4 * 18 * 128 * 128 * 32 * t
    assert 0.15e12 < ref.kda_core_flops(cfg, wl) < 0.16e12
    assert ref.kda_core_bytes(cfg, wl) == 4 * t * 32 * (
        2 * 2 * 4 * 128 + 2 * 4 * 129)
    assert 1.6e9 < ref.kda_core_bytes(cfg, wl) < 1.62e9
    # bound by bytes
    assert ref.kda_core_bytes(cfg, wl) / 819e9 > \
        ref.kda_core_flops(cfg, wl) / 197e12
    assert ref.mla_attention_flops(cfg, wl) == \
        3 * 2 * (t * (t + 1) // 2) * 32 * (192 + 128)
    assert ref.mla_attention_bytes(cfg, wl) == t * 32 * 2 * 4 * (192 + 128)
    assert ref.expert_rows(cfg, wl) == 8 * 128         # 128 an expert
    assert builder.kda_core_flops is ref.kda_core_flops


def test_the_rehearsal_of_the_cell_is_correct():
    """The configuration's ``--rehearse`` on the CPU: the tiny model's three
    fused steps through ``Module`` against the reference, under the
    rehearsal's limits, every step on the fused path."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         "kimilinear_train_4k", "--seed", "2147483999", "--seconds", "2",
         "--trace", "1", "--rehearse"], capture_output=True, text=True,
        timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert set(line["check"]) >= {"grad_norm_gap", "change_norm_gap"}
    read = line["info"]["rehearsal"]
    for name in ("step_mfu_pct.train", "fused_path_pct.train",
                 "donation_copies.train"):
        assert name in read, name
