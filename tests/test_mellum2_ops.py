"""What a Mellum2-class mixture needs of the ops (a window the flash kernels
skip blocks for, heads of a size of their own, YaRN's frequencies on some
layers and the default ones on others, softmax-scored experts) and the model
built from them by the one transformer definition, each against the plain
reference the benchmark keeps (``perf/refs/mellum2_12b_a2_5b.py``: float32
``jax.numpy``, nothing of the program) at toy widths on seeded weights.
"""
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.models import transformer_lm
from mxnet_tpu.models.configs import TransformerConfig
from mxnet_tpu.ops import nn as ops_nn
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.registry import OPS

from perf.models import mellum2_12b_a2_5b as builder
from perf.refs import common as ref_common
from perf.refs import mellum2_12b_a2_5b as ref
from perf.refs import train as ref_train

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "perf", "configs",
                       "mellum2_12b_a2_5b.json")) as _f:
    PUBLISHED = json.load(_f)
#: the benchmark's configuration at toy widths: the same four layers (three
#: sliding, one full, each with its own rope), heads of 16 under a stream of
#: 32 (32 / 4 heads = 8), 4 experts held of 16, 2 a token, a window of 8
TOY = {**PUBLISHED, "hidden_size": 32, "head_dim": 16,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "moe_intermediate_size": 48, "num_experts": 16,
       "num_experts_per_tok": 2, "num_experts_held": 4, "vocab_size": 256,
       "sliding_window": 8, "dtype": "float32"}
WL = {"batch": 2, "seq_len": 32, "rotation": 2, "ids": "all", "chips": 1,
      "optimizer": "adam",
      "optimizer_params": {"learning_rate": 1e-3, "beta1": 0.9,
                           "beta2": 0.999, "epsilon": 1e-8, "wd": 0.0,
                           "rescale_grad": 1.0}}
D, HD = TOY["hidden_size"], TOY["head_dim"]
YARN = (16.0, 8192.0, 32.0, 1.0, 1.2772588722239782)


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


@pytest.fixture
def interpret_kernel(monkeypatch):
    monkeypatch.setattr(pa, "INTERPRET", True)


# ------------------------------------------------------ the window's kernels
#: (T, block_q, block_k, window): a window that is no multiple of the block,
#: one of a single block, one narrower than a block (the diagonal block then
#: carries both masks), one position, blocks that are not square either way,
#: and the blocks the shape picks for itself
WINDOWS = [(256, 64, 64, 96), (256, 64, 64, 64), (256, 64, 64, 40),
           (256, 64, 64, 1), (256, 64, 64, 129), (256, 64, 128, 96),
           (256, 128, 64, 96), (384, 128, 128, 200), (256, None, None, 100)]


@pytest.mark.parametrize("T,block_q,block_k,window", WINDOWS)
def test_windowed_kernels_against_the_masked_xla_arm(
        interpret_kernel, T, block_q, block_k, window):
    """Forward and the one backward kernel (interpreted) skip and mask to the
    same result as ``_mha_reference`` under the same window, output and all
    three gradients.  Tolerance 1e-5 of each tensor's largest entry: both sides
    are float32, the kernels sum the keys block by block."""
    q, k, v, probe = (_rand(70 + i, (1, 2, T, 32)) for i in range(4))
    scale = 1.0 / math.sqrt(32)

    def kernel(q, k, v):
        return pa.flash_attention(q, k, v, True, scale, block_q, block_k,
                                  window)

    def masked(q, k, v):
        return ops_nn._mha_reference(q, k, v, True, scale, window)

    _close(kernel(q, k, v), masked(q, k, v), 1e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * probe), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(masked(*a) * probe), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        # one position a query: every gradient of q and k is nought, and
        # the kernel's is rounding around it
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5,
            atol=1e-5 * max(1.0, float(np.abs(np.asarray(b)).max())))


@pytest.mark.parametrize("window", [256, 300])
def test_a_window_of_the_whole_sequence_is_plain_causal(
        interpret_kernel, window):
    """A window that reaches position 0 from every query runs the kernels a
    plain causal call runs: equal bit for bit, output and gradients, and
    the same traced program."""
    q, k, v, probe = (_rand(80 + i, (1, 2, 256, 32)) for i in range(4))

    def loss(window):
        return lambda *a: jnp.sum(pa.flash_attention(
            *a, True, None, 64, 64, window) * probe)

    a = jax.value_and_grad(loss(window), (0, 1, 2))(q, k, v)
    b = jax.value_and_grad(loss(None), (0, 1, 2))(q, k, v)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert str(jax.make_jaxpr(jax.grad(loss(window), (0, 1, 2)))(q, k, v)) \
        == str(jax.make_jaxpr(jax.grad(loss(None), (0, 1, 2)))(q, k, v))


def test_a_window_needs_causal_attention():
    q = _rand(1, (1, 1, 128, 32))
    with pytest.raises(ValueError):
        pa.flash_attention(q, q, q, False, None, None, None, 64)
    with pytest.raises(ValueError):
        pa.kv_block_plan(256, 256, causal=True, window=0)


@pytest.mark.parametrize("T,window,blocks,want", [
    (4096, 1024, (None, None), (21, 15)),   # the cell's: 15 of 36 skipped
    (4096, None, (None, None), (36, 0)),
    (4096, 4096, (None, None), (36, 0)),    # plain causal
    (4096, 1000, (None, None), (21, 15)),   # an edge inside a block
    (4096, 1537, (None, None), (26, 10)),   # a block and a key further back
    (2048, None, (None, None), (1, 0)),     # one 2,048-block
    (2048, 512, (None, None), (7, 3)),      # 512-blocks under a window
    (256, 96, (64, 128), (6, 0)),
])
def test_the_plan_counts_what_the_loop_bounds_take_in(T, window, blocks,
                                                      want):
    """``kv_block_plan`` by the kernel's own bounds on python ints: no
    kernel runs.  At T 4096 in 512-blocks under a window of 1024 a query
    block reaches the block the window's edge crosses, the one before the
    diagonal and the diagonal: 1 + 2 + 6 x 3 = 21 of the 36 causal blocks."""
    assert pa.kv_block_plan(T, T, True, window, *blocks) == want


def test_the_window_skips_what_it_says_it_skips(interpret_kernel):
    """Keys in the blocks a query block skips may hold anything, NaN
    included: nothing of them reaches that block's result or gradients."""
    T, W = 256, 64
    q, k, v, probe = (_rand(90 + i, (1, 1, T, 32)) for i in range(4))

    def loss(q, k, v):
        out = pa.flash_attention(q, k, v, True, None, 64, 64, W)
        return jnp.sum((out * probe)[:, :, -64:])    # the last query block

    # the last query block sees blocks 2 (its edge) and 3 only
    poisoned = k.at[:, :, :128].set(jnp.nan)
    a = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
    b = jax.value_and_grad(loss, (0, 1, 2))(q, poisoned, v)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1][0][:, :, -64:]),
                                  np.asarray(b[1][0][:, :, -64:]))
    for i in (1, 2):        # dk and dv of the keys only that block sees
        np.testing.assert_array_equal(np.asarray(a[1][i][:, :, 192:]),
                                      np.asarray(b[1][i][:, :, 192:]))


# ------------------------------------------------------------------- rope
def test_yarn_frequencies_by_hand():
    """The 64 frequencies of the published full-attention section, against
    numbers worked by hand: theta 5e5, head 128, factor 16 over 8192
    original positions, beta_fast 32, beta_slow 1.  c(32) = 128 ln(8192 /
    (64 pi)) / (2 ln 5e5) = 18.08 and c(1) = 34.98, so dimensions up to 18
    keep theta^(-i/64), dimensions from 35 take a sixteenth of it, and
    dimension i between keeps (1 - (i - 18) / 17 x 15 / 16) of it."""
    got = ops_nn.yarn_frequencies(128, 5e5, YARN)
    assert got.shape == (64,)
    by_hand = {0: 1.0, 1: 0.8146172338565447, 18: 0.024955408670558694,
               19: 0.019208015577607825, 26: 0.0027043825167258223,
               34: 0.00011040869063028003, 35: 4.7781061769823416e-05,
               63: 1.5344629944572555e-07}
    for i, want in by_hand.items():
        assert abs(got[i] - want) <= 1e-12 * want, i
    i = np.arange(64)
    plain = 5e5 ** (-i / 64.0)
    keep = 1.0 - np.clip((i - 18) / 17.0, 0.0, 1.0) * 15.0 / 16.0
    np.testing.assert_allclose(got, plain * keep, rtol=1e-12)
    # and the reference's own, written from the same equations apart
    mine, factor = ref.frequencies(
        128, PUBLISHED["rope_parameters"]["full_attention"])
    np.testing.assert_allclose(got, mine, rtol=1e-12)
    assert factor == 1.2772588722239782 == 0.1 * math.log(16.0) + 1.0


def test_rotary_under_yarn_scales_cos_and_sin():
    """Position 0 is multiplied by the attention factor alone; a later
    position's pairs keep their length times it; and without yarn the
    rotation is the one it was."""
    x = _rand(20, (1, 1, 8, HD))
    y = ops_nn._rotary(x, 5e5, YARN)
    _close(y[..., 0, :], YARN[4] * x[..., 0, :])
    half = HD // 2
    _close(y[..., :half] ** 2 + y[..., half:] ** 2,
           YARN[4] ** 2 * (x[..., :half] ** 2 + x[..., half:] ** 2), 1e-5)
    freqs, factor = ref.frequencies(HD, TOY["rope_parameters"][
        "full_attention"])
    _close(y, ref._rotary(x, freqs, factor))
    plain, one = ref.frequencies(HD, TOY["rope_parameters"][
        "sliding_attention"])
    _close(ops_nn._rotary(x, 5e5), ref._rotary(x, plain, one))
    assert not np.allclose(y, ops_nn._rotary(x, 5e5), atol=1e-3)


# --------------------------------------------------- MultiHeadAttention
def _attention_args(T):
    h, kv = TOY["num_attention_heads"], TOY["num_key_value_heads"]
    return (_rand(10, (2, T, D)), _rand(11, (h * HD, D), 0.1),
            _rand(12, (kv * HD, D), 0.1), _rand(13, (kv * HD, D), 0.1),
            _rand(14, (D, h * HD), 0.1))


def _plain_attention(kind, cfg=TOY):
    def plain(x, wq, wk, wv, wo):
        return ref._attention(cfg, "float32", kind, x, {
            "attn_query_weight": wq, "attn_key_weight": wk,
            "attn_value_weight": wv, "attn_out_proj_weight": wo})
    return plain


_SLIDING = dict(num_heads=4, num_kv_heads=2, head_dim=16, window=8,
                rope_theta=5e5, causal=True)
_FULL = dict(num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=5e5,
             rope_yarn=YARN, causal=True)


@pytest.mark.parametrize("kind,attrs", [("sliding_attention", _SLIDING),
                                        ("full_attention", _FULL)])
def test_attention_with_its_own_head_size_xla_arm(kind, attrs):
    """Heads of 16 under a stream of 32 (32 / 4 = 8), 2 key/value heads
    under 4, a window of 8 with the default rope or no window under YaRN:
    forward and gradients on the XLA arm."""
    telemetry.enable()
    before = telemetry.value("attention_dispatch_total", path="reference")
    _agree(_op("MultiHeadAttention", **attrs), _plain_attention(kind),
           _attention_args(32))
    assert telemetry.value("attention_dispatch_total",
                           path="reference") > before


@pytest.mark.parametrize("kind,attrs,path", [
    ("sliding_attention", dict(_SLIDING, window=40), "flash_window_interpret"),
    ("full_attention", _FULL, "flash_interpret")])
def test_attention_with_its_own_head_size_kernel_arm(interpret_kernel, kind,
                                                     attrs, path):
    """The same through the three flash kernels (interpreted) at T 128: the
    sliding layer's dispatch is counted as ``flash_window``, the full
    layer's as ``flash``, and the key blocks by the layer's kind."""
    telemetry.enable()
    before = telemetry.value("attention_dispatch_total", path=path)
    seen = {fate: telemetry.value("attention_kv_blocks_total", kind=kind,
                                  fate=fate) for fate in ("visited",
                                                          "skipped")}
    cfg = dict(TOY, sliding_window=40)
    _agree(_op("MultiHeadAttention", **attrs), _plain_attention(kind, cfg),
           _attention_args(128), tol=2e-4)
    assert telemetry.value("attention_dispatch_total", path=path) > before
    # T 128 is one block: one visited, none skipped, a compiled variant
    assert telemetry.value("attention_kv_blocks_total", kind=kind,
                           fate="visited") > seen["visited"]
    assert telemetry.value("attention_kv_blocks_total", kind=kind,
                           fate="skipped") == seen["skipped"]


def test_attention_window_of_the_sequence_or_more_is_causal():
    args = _attention_args(32)
    a = _op("MultiHeadAttention", **dict(_SLIDING, window=32))(*args)
    b = _op("MultiHeadAttention", **dict(_SLIDING, window=0))(*args)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    narrow = _op("MultiHeadAttention", **_SLIDING)(*args)
    assert not np.allclose(narrow, b, atol=1e-4)


def test_attention_refuses_what_it_cannot_build():
    args = _attention_args(32)
    with pytest.raises(mx.base.MXNetError):     # a window and no causal mask
        _op("MultiHeadAttention", **dict(_SLIDING, causal=False))(*args)
    with pytest.raises(mx.base.MXNetError):     # yarn needs a theta
        _op("MultiHeadAttention", **dict(_FULL, rope_theta=0.0))(*args)
    with pytest.raises(mx.base.MXNetError):     # and its five numbers
        _op("MultiHeadAttention", **dict(_FULL, rope_yarn=(16.0, 8192.0)))(
            *args)
    with pytest.raises(mx.base.MXNetError):     # 32 / 3 heads, no head_dim
        _op("MultiHeadAttention", num_heads=3)(*args)


def test_shapes_follow_the_head_size():
    from mxnet_tpu import symbol as sym
    net = sym.MultiHeadAttention(sym.Variable("data"), num_heads=32,
                                 num_kv_heads=4, head_dim=128, name="a")
    shapes, _, _ = net.infer_shape(data=(1, 16, 2304))
    assert dict(zip(net.list_arguments(), shapes)) == {
        "data": (1, 16, 2304), "a_query_weight": (4096, 2304),
        "a_key_weight": (512, 2304), "a_value_weight": (512, 2304),
        "a_out_proj_weight": (2304, 4096)}


# ------------------------------------------------------------- SparseMoE
_MOE_ORDER = ("moe_router_weight", "moe_expert_gate_weight",
              "moe_expert_up_weight", "moe_expert_down_weight")


def _moe_weights(held, e=16, f=48):
    return {"moe_router_weight": _rand(30, (e, D), 0.5),
            "moe_expert_gate_weight": _rand(32, (held, f, D), 0.1),
            "moe_expert_up_weight": _rand(33, (held, f, D), 0.1),
            "moe_expert_down_weight": _rand(34, (held, D, f), 0.1)}


def _moe(held, offset=0, k=2, e=16):
    op = _op("SparseMoE", num_experts=e, num_experts_per_tok=k,
             num_hidden=48, num_held=held, expert_offset=offset,
             score="softmax")
    return lambda x, *w: op(x, *w, jnp.zeros((e,), jnp.float32))


def _plain_moe(held, offset=0, k=2, e=16):
    cfg = {**TOY, "num_experts": e, "num_experts_held": held,
           "expert_offset": offset, "num_experts_per_tok": k}
    return lambda x, *w: ref._experts(cfg, "float32", x,
                                      dict(zip(_MOE_ORDER, w)))


def test_softmax_moe_forward_and_gradient():
    telemetry.enable()
    before = telemetry.value("moe_score_total", score="softmax")
    x = _rand(35, (2, 24, D))
    w = tuple(_moe_weights(4)[n] for n in _MOE_ORDER)
    moe = _moe(4)
    _agree(lambda *a: moe(*a)[0], _plain_moe(4), (x, *w))
    assert telemetry.value("moe_score_total", score="softmax") > before


def test_softmax_moe_weights_are_normalised_over_the_selected():
    """Holding every expert, with experts that return their input's mean
    times one: the layer's weights for a token sum to one."""
    x = _rand(36, (1, 8, D))
    sel, w = ref.route(TOY, x, _moe_weights(4)["moe_router_weight"])
    _close(jnp.sum(w, -1), jnp.ones((1, 8)))
    assert sel.shape == (1, 8, 2)
    # softmax, then top-k: the selected are the two largest logits
    logits = jnp.matmul(x, _moe_weights(4)["moe_router_weight"].T)
    np.testing.assert_array_equal(np.sort(np.asarray(sel), -1), np.sort(
        np.asarray(jax.lax.top_k(logits, 2)[1]), -1))


def test_softmax_moe_takes_no_bias():
    x = _rand(35, (2, 24, D))
    w = _moe_weights(4)
    op = _op("SparseMoE", num_experts=16, num_experts_per_tok=2,
             num_hidden=48, num_held=4, score="softmax")
    with pytest.raises(mx.base.MXNetError):     # seven inputs: a bias
        op(x, w["moe_router_weight"], jnp.zeros((16,)),
           *(w[n] for n in _MOE_ORDER[1:]), jnp.zeros((16,)))
    sig = _op("SparseMoE", num_experts=16, num_experts_per_tok=2,
              num_hidden=48, num_held=4)
    with pytest.raises(mx.base.MXNetError):     # six inputs: none
        sig(x, *(w[n] for n in _MOE_ORDER), jnp.zeros((16,)))
    with pytest.raises(mx.base.MXNetError):
        OPS["SparseMoE"].parse_attrs(dict(
            num_experts=16, num_experts_per_tok=2, num_hidden=48,
            score="tanh"))


def test_softmax_moe_symbol_has_no_bias_argument():
    from mxnet_tpu import symbol as sym
    net = sym.SparseMoE(sym.Variable("data"), num_experts=16,
                        num_experts_per_tok=2, num_hidden=48, num_held=4,
                        score="softmax", name="m")
    assert net.list_arguments() == [
        "data", "m_router_weight", "m_expert_gate_weight",
        "m_expert_up_weight", "m_expert_down_weight"]
    assert net.list_auxiliary_states() == ["m_expert_load"]
    shapes, _, aux = net.infer_shape(data=(2, 8, D))
    assert shapes[1:] == [(16, D), (4, 48, D), (4, 48, D), (4, D, 48)]
    assert aux == [(16,)]
    # by keyword too: the load lands on the last input
    load = sym.Variable("load")
    again = sym.SparseMoE(data=sym.Variable("data"), expert_load=load,
                          num_experts=16, num_experts_per_tok=2,
                          num_hidden=48, score="softmax", name="m")
    assert again.list_auxiliary_states() == ["load"]
    assert "m_expert_bias" not in again.list_arguments()
    # and sigmoid scoring keeps its seven
    sig = sym.SparseMoE(sym.Variable("data"), num_experts=16,
                        num_experts_per_tok=2, num_hidden=48, name="m")
    assert sig.list_arguments()[2] == "m_expert_bias"
    assert sig.list_auxiliary_states() == ["m_expert_load"]


def test_softmax_moe_counts_every_experts_load():
    x = _rand(35, (2, 24, D))
    w = _moe_weights(4)
    _, load = _moe(4, offset=8)(x, *(w[n] for n in _MOE_ORDER))
    sel, _ = ref.route(TOY, x, w["moe_router_weight"])
    want = np.bincount(np.asarray(sel).ravel(), minlength=16)
    np.testing.assert_array_equal(np.asarray(load), want)
    assert want.sum() == 2 * 24 * 2


def test_the_eight_holders_parts_add_up_to_the_uncut_layer():
    """The published router (64 experts, 8 a token): the parts of the eight
    holders, offsets 0, 8 .. 56 with 8 experts each, summed, are the layer
    with all 64 experts in one place; one holder's part alone is not."""
    x = _rand(36, (2, 16, D))
    whole = _moe_weights(64, e=64)
    want = _plain_moe(64, k=8, e=64)(x, *(whole[n] for n in _MOE_ORDER))
    total, parts = 0.0, []
    for off in range(0, 64, 8):
        share = [whole[n] if n == "moe_router_weight"
                 else whole[n][off:off + 8] for n in _MOE_ORDER]
        parts.append(_moe(8, offset=off, k=8, e=64)(x, *share)[0])
        total = total + parts[-1]
    _close(total, want)
    assert not np.allclose(parts[0], want, atol=1e-3)
    # the reference's own share is the program's, holder by holder
    for off, part in zip(range(0, 64, 8), parts):
        share = [whole[n] if n == "moe_router_weight"
                 else whole[n][off:off + 8] for n in _MOE_ORDER]
        _close(part, _plain_moe(8, offset=off, k=8, e=64)(x, *share))


# ------------------------------------------------------------ the model
def test_config_takes_the_new_fields_and_refuses_what_it_cannot_build():
    tc = TransformerConfig("x", 64, 2, 32, 4, 64, 8, head_dim=16, window=4,
                           layer_types=("sliding_attention",
                                        "full_attention"),
                           position="rope",
                           rope=(("full_attention", 5e5, YARN),))
    assert tc.rope_of("full_attention") == (5e5, YARN)
    assert tc.rope_of("sliding_attention") == (tc.rope_theta, ())
    # 30 / 4 heads is no head size, unless one is named
    with pytest.raises(ValueError):
        TransformerConfig("x", 64, 1, 30, 4, 64, 8)
    assert TransformerConfig("x", 64, 1, 30, 4, 64, 8, head_dim=16)
    with pytest.raises(ValueError):     # sliding layers and no window
        TransformerConfig("x", 64, 1, 32, 4, 64, 8,
                          layer_types=("sliding_attention",))
    with pytest.raises(ValueError):
        TransformerConfig("x", 64, 1, 32, 4, 64, 8, moe_score="tanh")
    with pytest.raises(ValueError):     # a yarn of two numbers
        TransformerConfig("x", 64, 1, 32, 4, 64, 8,
                          rope=(("full_attention", 5e5, (16.0, 8192.0)),))


def test_the_graph_names_its_layers_by_kind():
    """Sliding layers' nodes are ``l<i>_swa``, the full layer's ``l<i>_attn``
    (a device trace's scopes tell them apart); every attention weight keeps
    the ``l<i>_attn_`` names; the experts take no bias; the head has its own
    matrix."""
    net = builder.symbol(TOY, WL)
    nodes = {n["name"]: n for n in json.loads(net.tojson())["nodes"]}
    for i, kind in enumerate(TOY["layer_types"]):
        node = nodes["tfm_l%d_%s" % (i, "swa" if kind == "sliding_attention"
                                     else "attn")]
        assert node["op"] == "MultiHeadAttention"
        attrs = node["attrs"]
        assert attrs["head_dim"] == "16" and attrs["num_kv_heads"] == "2"
        assert ("window" in attrs) == (kind == "sliding_attention")
        assert ("rope_yarn" in attrs) == (kind == "full_attention")
        assert nodes["tfm_l%d_moe" % i]["attrs"]["score"] == "softmax"
    names = net.list_arguments()
    assert not [n for n in names if "swa" in n or "expert_bias" in n]
    assert {builder.leaf_name(n) for n in names
            if n not in ("data", "softmax_label")} \
        == {name for name, _, _, _ in ref.param_spec(TOY)}
    assert "tfm_lm_head_weight" in names
    assert sorted(net.list_auxiliary_states()) == [
        "tfm_l%d_moe_expert_load" % i for i in range(4)]


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
    """Tolerance 2e-4 of a leaf's largest entry: both sides are float32 and
    differ in the order of their sums."""
    exe, params, batch = _bind(TOY)
    loss = float(exe.forward(is_train=True)[0].asnumpy().ravel()[0])
    exe.backward()
    want, grads, _ = ref.loss_and_grad(
        TOY, {k: jnp.asarray(v) for k, v in params.items()}, batch)
    assert abs(loss - float(want)) < 1e-5 * abs(float(want))
    assert set(grads) == {builder.leaf_name(n) for n in exe.grad_dict
                          if n not in ("data", "softmax_label")}
    for name, g in grads.items():
        _close(exe.grad_dict[builder.PREFIX + name].asnumpy(), g, 2e-4)
    loads = [exe.aux_dict[n].asnumpy() for n in sorted(exe.aux_dict)]
    assert len(loads) == 4
    tokens = WL["batch"] * WL["seq_len"]
    assert all(l.sum() == tokens * TOY["num_experts_per_tok"]
               for l in loads)


def test_toy_model_three_adam_steps_through_the_fused_step(monkeypatch):
    """Module's fused step against the reference's trainer: each step's
    loss (2e-5: float32 both sides), the first gradient's norm as Adam got
    it and every leaf's change after the three (2e-3: Adam divides by the
    root of a second moment that starts at nought, so the first steps
    amplify a gradient's last digits)."""
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
    losses, first_moment = [], None
    for t in range(ref_train.STEPS):
        x, y = batches[t % len(batches)]
        mod.forward_backward(mx.io.DataBatch(
            data=[mx.nd.array(np.asarray(x))],
            label=[mx.nd.array(np.asarray(y))]))
        mod.update()
        losses.append(float(mod.get_outputs()[0].asnumpy().ravel()[0]))
        if t == 0:      # Adam's mean after one step is (1 - beta1) g
            first_moment = {
                builder.leaf_name(n): float(np.linalg.norm(
                    mod._updater.states[i][0].asnumpy())) / 0.1
                for i, n in enumerate(mod._param_names)}
    assert telemetry.value("step_dispatch_total", path="fused") - fused0 == 3
    want = ref_train.run(ref, TOY, WL, seed)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    for name, norm in want["grad_norm"].items():
        assert abs(first_moment[name] - norm) <= 2e-3 * norm, name
    args, aux = mod.get_params()
    change = {builder.leaf_name(n): float(np.linalg.norm(
        a.asnumpy() - np.asarray(start[builder.leaf_name(n)], np.float32)))
        for n, a in args.items()}
    for name, norm in want["change_norm"].items():
        assert abs(change[name] - norm) <= 2e-3 * max(norm, 1e-6), name
    layer = builder.PREFIX + "l0_moe"
    got = [telemetry.value("moe_expert_load", layer=layer, expert=str(e))
           for e in range(TOY["num_experts"])]
    np.testing.assert_array_equal(got, aux[layer + "_expert_load"].asnumpy())


#: one sliding layer and the full one: what the faults need, in half the
#: time to trace
PAIR = {**TOY, "num_hidden_layers": 2,
        "layer_types": ["sliding_attention", "full_attention"],
        "mlp_layer_types": ["sparse", "sparse"]}


@pytest.fixture(scope="module")
def sound():
    return ref_train.run(ref, PAIR, WL, 3)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_reference_faults_read_apart(sound, fault):
    """Each fault of a mechanism, planted in the reference, moves what
    `correct` compares: the worst leaf's first gradient by a fiftieth or
    more."""
    got = ref_train.run(ref, {**PAIR, "fault": fault}, WL, 3)
    gaps = ref_train.compare(got, sound)
    assert gaps["grad_norm_gap"][0] > 0.02, (fault, gaps)


def test_reference_control_reads_apart(sound):
    control = ref_train.compare(
        ref_train.run(ref, PAIR, WL, 3, precision=ref_common.CONTROL), sound)
    witness = ref_train.compare(
        ref_train.run(ref, PAIR, WL, 3, precision="bfloat16"), sound)
    # by the median leaf: one routing flip at a bfloat16 tie can move a
    # single expert's leaf of a model this small as far as the control does
    assert control["grad_norm_median_gap"][0] > \
        3 * witness["grad_norm_median_gap"][0]


def test_shapes_to_operations_by_hand():
    """The published sizes at the cell's traffic: 3,670,528 pairs a head
    inside the window of 8,390,656 causal ones; 4.36 TFLOP a step."""
    wl = {"batch": 1, "seq_len": 4096}
    assert ref.seen_pairs(4096) == 4096 * 4097 // 2 == 8390656
    assert ref.seen_pairs(4096, 1024) == 1024 * 1025 // 2 + 3072 * 1024 \
        == 3670528
    assert ref.seen_pairs(512, 1024) == ref.seen_pairs(512)
    d, v, t = 2304, 12288, 4096
    per_token = d * v + 4 * (2 * d * 4096 + 2 * d * 512 + 64 * d)
    pairs = 3 * 3670528 + 8390656
    experts = 4 * 2 * 4096 * 3 * d * 896       # 4,096 expected rows a layer
    fwd = 2 * per_token * t + 4 * pairs * 4096 + experts
    assert ref.expert_rows(PUBLISHED, wl) == 4096
    assert ref.fwd_flops(PUBLISHED, wl) == fwd
    assert 4.3e12 < ref.step_flops(PUBLISHED, wl) == 3 * fwd < 4.4e12
    assert ref.window_attention_flops(PUBLISHED, wl) \
        == 3 * 12 * 3670528 * 4096
    assert ref.window_attention_bytes(PUBLISHED, wl) \
        == 3 * (4 * 32 + 4 * 4) * 4096 * 128 * 2


def test_parameters_by_hand():
    """340.3 M: 21.23 M of attention, 0.15 of router and 49.55 of experts a
    layer, 28.31 each of embedding and head."""
    d = 2304
    attn = 2 * d * 4096 + 2 * d * 512
    layer = attn + 64 * d + 8 * 3 * d * 896 + 2 * d
    want = 4 * layer + 2 * 12288 * d + d
    got = sum(int(np.prod(shape)) for _, shape, _, _ in
              ref.param_spec(PUBLISHED))
    assert got == want and 340.2e6 < got < 340.5e6


def test_router_stays_float32_under_bf16():
    from mxnet_tpu import amp
    net = builder.symbol(TOY, WL)
    types = amp.type_dict_for(net, ("data",), ("softmax_label",))
    for name, t in types.items():
        f32 = name.endswith(("_gamma", "_router_weight", "softmax_label"))
        assert (np.dtype(t) == np.float32) == f32, name


def test_megatron_rules_shard_a_sliding_layers_weights():
    from mxnet_tpu.parallel.mesh import make_mesh, megatron_rules, P
    rules = megatron_rules(make_mesh({"ep": 4, "tp": 2}, jax.devices()[:8]))
    for name, shape, spec in [
            ("l0_attn_query_weight", (64, 32), P("tp", None)),
            ("l0_attn_key_weight", (32, 32), P("tp", None)),
            ("l0_attn_out_proj_weight", (32, 64), P(None, "tp")),
            ("l0_moe_expert_up_weight", (4, 48, 32), P("ep")),
            ("l0_moe_router_weight", (16, 32), P())]:
        assert rules.spec_for(name, shape) == spec, name
    # and a sliding layer's arguments carry exactly those names
    names = builder.symbol(TOY, WL).list_arguments()
    assert "tfm_l0_attn_query_weight" in names
    assert "tfm_l0_attn_out_proj_weight" in names


def test_tiny_ladder_builds_the_graph_it_built():
    """A config without the new fields hands the ops none of the new
    attrs."""
    tc = TransformerConfig("pin", 256, 2, 64, 4, 256, 16)
    assert tc.head_dim == 0 and tc.window == 0 and tc.rope == ()
    text = transformer_lm(tc).tojson()
    for attr in ("head_dim", "window", "rope_yarn", "score"):
        assert attr not in text
