"""What an SDAR-class model needs of the program: block-diffusion attention
(the flash kernels under the block-diffusion mask, forward and backward,
visiting only the tiles that hold a visible pair), the masked-diffusion
loss over blocks, the noising iterator users train from, the causal and
window kernels left as they were, and the model built by ``transformer_lm``
from the configuration, each against the plain reference the benchmark
keeps (``perf/refs/sdar_30b_a3b.py``: float32 ``jax.numpy``) at toy widths
on seeded weights.

Tolerances: the program and the reference both run float32 on the CPU, so
what differs is the order of the sums: 2e-4 on the interpreted kernels
against one softmax (as ``tests/test_glm47_ops.py`` has them), 2e-5 where
the two compute the same sums in the same order, 5e-4 on a whole model's
gradients (softmax routing, two layers, sums in other orders: as
``tests/test_kimi_linear_ops.py`` has it), 5e-5 on the experts' shares
(the holders' sums against one sum).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry, tracing
from mxnet_tpu.base import MXNetError
from mxnet_tpu.io import BlockDiffusionIter, SyntheticLMIter, \
    block_diffusion_noise
from mxnet_tpu.models.configs import TransformerConfig
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.nn import _mha_reference, mha_uses_kernel
from mxnet_tpu.ops.registry import OPS

from perf.models import sdar_30b_a3b as builder
from perf.refs import sdar_30b_a3b as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the benchmark's configuration at toy widths: two layers of the one kind
#: (GQA 4 / 2 heads of 8 with q/k norms, 4 experts held of 16, 4 a token),
#: documents of 16 in blocks of 4, the mask id 199 of a vocabulary of 200
TOY = {
    "name": "sdar_toy", "attention_bias": False, "decoder_sparse_step": 1,
    "head_dim": 8, "hidden_act": "silu", "hidden_size": 32,
    "intermediate_size": 64, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 24, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 16, "num_experts_per_tok": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_scaling": None, "rope_theta": 10000, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 200, "num_experts_held": 4,
    "expert_offset": 0, "block_length": 4, "mask_token_id": 199,
    "dtype": "float32",
}
WL = {"batch": 2, "seq_len": 16, "rotation": 2, "ids": "bf16_exact"}


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


def _rules(t, half, b):
    """The mask element by element from the three rules."""
    out = np.zeros((t, t), bool)
    for i in range(t):
        for j in range(t):
            ni, nj = i < half, j < half
            bi, bj = (i % half) // b, (j % half) // b
            out[i, j] = (ni and nj and bi == bj) \
                or (ni and not nj and bj < bi) \
                or (not ni and not nj and bj <= bi)
    return out


# ------------------------------------------------------------------ the mask
@pytest.mark.parametrize("half,b", [(8, 1), (12, 4), (16, 16), (24, 8)])
def test_the_mask_is_the_three_rules(half, b):
    """The kernels' tile mask (``pallas_attention._bd_visible``, the XLA
    arm's over the whole square) and the reference's ``visible`` against the
    rules written out one pair at a time."""
    want = _rules(2 * half, half, b)
    assert np.array_equal(np.asarray(pa._bd_visible(
        (2 * half, 2 * half), 0, 0, 0, half, b)), want)
    # a tile of it, queries along either axis
    assert np.array_equal(np.asarray(pa._bd_visible(
        (4, 8), 1, half - 2, 3, half, b)), want[half - 2:half + 6, 3:7].T)
    pos = jnp.arange(2 * half)
    assert np.array_equal(np.asarray(ref.visible(
        {"block_length": b}, pos, pos, half)), want)
    # every row sees itself; no row sees a noised key outside its block
    assert want.diagonal().all()
    assert not want[half:, :half].any()


@pytest.mark.parametrize("half,b,block", [
    (128, 4, 64), (192, 4, 128), (192, 1, 128), (192, 16, 128),
    (320, 16, 128)],
    ids=["aligned", "straddles_b4", "straddles_b1", "straddles_b16",
         "straddles_odd"])
def test_flash_kernels_under_the_block_diffusion_mask(half, b, block):
    """``flash_fwd`` / ``flash_dqkv`` under the block-diffusion mask,
    interpreted, against ``_mha_reference``: the output and the gradients
    of q, k and v, with L = ``half`` a multiple of the kernel's block or
    not (the boundary between the copies inside a tile)."""
    r = np.random.RandomState(half + b)
    q, k, v = (jnp.asarray(r.randn(1, 2, 2 * half, 64), jnp.float32)
               for _ in range(3))
    scale = 64 ** -0.5
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "INTERPRET", True)
        _agree(lambda *a: pa.flash_attention(*a, False, scale, block, block,
                                             None, (half, b)),
               lambda *a: _mha_reference(*a, False, scale, None, (half, b)),
               (q, k, v), tol=2e-4)


def test_the_op_takes_the_kernels_with_grouped_heads():
    """``MultiHeadAttention`` with ``diffusion_block``, grouped heads (4 of
    2), per-head norms and rope over 2 x 192 positions: the kernel arm
    (interpreted, in 128-blocks by the tile plan's default for the shape's
    test) agrees with the XLA arm, values and every input's gradient, and
    both rotate the two copies by the same positions."""
    D, H, Hkv, hd, T = 32, 4, 2, 64, 384
    attrs = dict(num_heads=H, num_kv_heads=Hkv, head_dim=hd, qk_norm=True,
                 rope_theta=1e4, causal=False, diffusion_block=4)
    args = (_rand(1, (1, T, D)), _rand(2, (H * hd, D), 0.3),
            _rand(3, (Hkv * hd, D), 0.3), _rand(4, (Hkv * hd, D), 0.3),
            _rand(5, (D, H * hd), 0.3), 1.0 + _rand(6, (hd,), 0.1),
            1.0 + _rand(7, (hd,), 0.1))
    op = _op("MultiHeadAttention", **attrs)
    telemetry.enable()
    try:
        n0 = telemetry.value("attention_dispatch_total",
                             path="flash_block_diffusion_interpret")
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(pa, "INTERPRET", True)
            kernel = jax.jit(op)(*args)
            probe = _rand(9, kernel.shape)
            g_kernel = jax.grad(lambda *a: jnp.sum(op(*a) * probe),
                                tuple(range(7)))(*args)
        assert telemetry.value("attention_dispatch_total",
                               path="flash_block_diffusion_interpret") > n0
    finally:
        telemetry.disable()
    _close(kernel, op(*args), 2e-4)
    g_ref = jax.grad(lambda *a: jnp.sum(op(*a) * probe),
                     tuple(range(7)))(*args)
    for a, b in zip(g_kernel, g_ref):
        _close(a, b, 2e-4)
    # the clean copy of a document is the noised one's twin: equal inputs
    # in both halves give equal queries and keys, so the first clean block
    # (which sees only itself) equals the first noised block (likewise)
    twin = jnp.concatenate([args[0][:, :T // 2]] * 2, axis=1)
    out = op(twin, *args[1:])
    _close(out[:, T // 2:T // 2 + 4], out[:, :4], 1e-5)


def test_the_op_refuses_a_mask_it_cannot_keep():
    x = _rand(1, (1, 24, 16))
    w = _rand(2, (16, 16))
    for attrs in (dict(causal=True, diffusion_block=4),     # its own mask
                  dict(causal=False, diffusion_block=3),    # a power of two
                  dict(causal=False, diffusion_block=8)):   # 12 % 8
        with pytest.raises(MXNetError):
            _op("MultiHeadAttention", num_heads=2, **attrs)(x, w, w, w, w)
    with pytest.raises(ValueError):
        pa.kv_block_plan(64, 64, True, diffusion=(32, 4))


# ---------------------------------------------------- the loops' tile plans
def _tile_plan(half, b, rows, cols):
    """{(query block, key block): masked} the kernels' segments give, the
    forward's by query block and the backward's by key block."""
    t = 2 * half
    fwd, bwd = {}, {}
    for i in range(t // rows):
        for lo, hi, masked in pa._bd_key_segments(i * rows, rows, cols, half,
                                                  b):
            for j in range(lo, hi):
                assert (i, j) not in fwd
                fwd[i, j] = masked
    for j in range(t // cols):
        for lo, hi, masked in pa._bd_query_segments(
                j * cols, cols, rows, half, b, t // rows):
            for i in range(lo, hi):
                assert (i, j) not in bwd
                bwd[i, j] = masked
    return fwd, bwd


@pytest.mark.parametrize("half,b,rows,cols", [
    (16, 4, 8, 8), (24, 4, 16, 8), (24, 8, 8, 16), (40, 2, 16, 16),
    (48, 16, 32, 32), (12, 1, 8, 4)])
def test_the_loops_visit_exactly_the_tiles_with_a_visible_pair(half, b, rows,
                                                               cols):
    """Forward and backward, on python ints as the kernels' bounds run on
    a program's block index: a tile is visited when and only when it holds
    a visible pair, once, and is left unmasked only where every pair of it
    is visible."""
    mask = _rules(2 * half, half, b)
    fwd, bwd = _tile_plan(half, b, rows, cols)
    for plan in (fwd, bwd):
        for i in range(2 * half // rows):
            for j in range(2 * half // cols):
                tile = mask[i * rows:(i + 1) * rows, j * cols:(j + 1) * cols]
                assert ((i, j) in plan) == tile.any(), (i, j)
                if (i, j) in plan and not plan[i, j]:
                    assert tile.all(), (i, j)


def test_the_tile_counter_at_the_cells_shape():
    """8,192 positions (L 4,096) in blocks of 4, the kernels' 512-blocks: a
    head's forward visits 80 of the 256 tiles and skips 176 (68.75 %), 24 of
    the 80 masked (the 8 noised diagonal tiles, the last clean block of each
    of the 8 noised query blocks, the 8 clean diagonal tiles); the backward
    visits the same 80."""
    assert pa.default_blocks(8192, 8192, diffusion=(4096, 4)) == (512, 512)
    assert pa.kv_block_plan(8192, 8192, False, diffusion=(4096, 4)) \
        == (80, 176)
    fwd, bwd = _tile_plan(4096, 4, 512, 512)
    assert set(fwd) == set(bwd) and len(fwd) == 80
    assert sum(fwd.values()) == 24
    # a sequence the default would take in one block still skips tiles
    assert pa.default_blocks(1024, 1024, diffusion=(512, 4)) == (512, 512)
    assert mha_uses_kernel(1, 32, 8192, 128, jnp.bfloat16)


#: the parent commit's loop bounds of the causal and window kernels at the
#: shapes of the gpt2, mellum2 and glm cells (T, window): blocks, the
#: forward's per query block (full, end[, lo, clear]), the window's
#: backward per key block (edge, hi), ``kv_block_plan`` and the diagonal's
#: bands
PARENT_BOUNDS = {
    "gpt2m": (1024, None, dict(
        blocks=(1024, 1024), plan=(1, 0), square=True, fwd=[(0, 1)],
        bwd=[])),
    "mellum2_window": (4096, 1024, dict(
        blocks=(512, 512), plan=(21, 15), square=True,
        fwd=[(0, 1, 0, 0), (1, 2, 0, 0), (2, 3, 0, 1), (3, 4, 1, 2),
             (4, 5, 2, 3), (5, 6, 3, 4), (6, 7, 4, 5), (7, 8, 5, 6)],
        bwd=[(2, 3), (3, 4), (4, 5), (5, 6), (6, 7), (7, 8), (8, 8),
             (9, 8)])),
    "mellum2_full": (4096, None, dict(
        blocks=(512, 512), plan=(36, 0), square=True,
        fwd=[(i, i + 1) for i in range(8)], bwd=[])),
    "glm47": (4096, None, dict(
        blocks=(512, 512), plan=(36, 0), square=True,
        fwd=[(i, i + 1) for i in range(8)], bwd=[])),
}


@pytest.mark.parametrize("cell", sorted(PARENT_BOUNDS))
def test_the_causal_and_window_loops_are_the_parents(cell):
    """The causal and window kernels' blocks, loop bounds and visited
    tiles, worked through the same functions the kernels call, are what
    the parent commit (18c63e5) worked; the kernels' own programs at these
    shapes are pinned letter for letter by
    ``tests/test_kimi_linear_ops.py::test_the_kernels_at_equal_widths_are_the_parents``."""
    T, window, want = PARENT_BOUNDS[cell]
    w = pa._window_of(window, True, T)
    TQ, BK = pa._pick_blocks(T, T, None, None, w)
    n = T // BK
    fwd = [pa._causal_bounds(i * TQ, TQ, BK, n)
           + (pa._window_bounds(i * TQ, TQ, BK, w) if w else ())
           for i in range(T // TQ)]
    bwd = [pa._window_q_bounds(j * BK, BK, TQ, w, T // TQ)
           for j in range(n)] if w else []
    got = dict(blocks=(TQ, BK), plan=pa.kv_block_plan(T, T, True, window),
               square=pa._square(TQ, BK, T, T, w), fwd=fwd, bwd=bwd)
    assert got == want
    assert pa.default_blocks(T, T, w) == want["blocks"]


# ------------------------------------------------------------------ the loss
def test_the_loss_weights_each_masked_position_by_its_block():
    """``block_diffusion_ce``: each masked position's cross-entropy times b
    over its block's masked count, nought elsewhere; no gradient into the
    ids."""
    b, mask = 4, 9
    logits = _rand(1, (2, 8, 10))
    x0 = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8], [8, 7, 6, 5, 4, 3, 2, 1]],
                     jnp.float32)
    xt = x0.at[0, 1].set(mask).at[0, 4:8].set(mask).at[1, 0:3].set(mask) \
        .at[1, 7].set(mask)
    got = _op("block_diffusion_ce", block=b, mask_id=mask)(logits, x0, xt)
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                              x0.astype(jnp.int32)[..., None], -1)[..., 0]
    weight = np.zeros((2, 8))
    weight[0, 1] = 4 / 1
    weight[0, 4:8] = 4 / 4
    weight[1, 0:3] = 4 / 3
    weight[1, 7] = 4 / 1
    _close(got, ce * weight, 1e-6)
    g = jax.grad(lambda lg: jnp.sum(_op("block_diffusion_ce", block=b,
                                        mask_id=mask)(lg, x0, xt)))(logits)
    g_ref = jax.grad(lambda lg: jnp.sum(-jnp.take_along_axis(
        jax.nn.log_softmax(lg), x0.astype(jnp.int32)[..., None], -1)[..., 0]
        * weight))(logits)
    _close(g, g_ref, 1e-5)
    with pytest.raises(MXNetError):          # 8 positions are not blocks of 3
        _op("block_diffusion_ce", block=3, mask_id=mask)(logits, x0, xt)


# ------------------------------------------------------------- the noising
def test_the_noise_is_seeded_and_masks_one_to_b_a_block():
    x0 = np.random.RandomState(0).randint(0, 100, (3, 64)).astype(np.float32)
    for b in (1, 4, 16):
        a = block_diffusion_noise(x0, b, 100, 4000000033)
        assert np.array_equal(a, block_diffusion_noise(x0, b, 100,
                                                       4000000033))
        # (a block of one is always masked whole)
        assert np.array_equal(a, block_diffusion_noise(x0, b, 100, 7)) \
            == (b == 1)
        assert a.dtype == x0.dtype
        masked = (a == 100).reshape(3, 64 // b, b).sum(-1)
        assert masked.min() >= 1 and masked.max() <= b
        assert np.array_equal(a[a != 100], x0[a != 100])
    # every count 1..b is drawn (uniform over 1..4 on 3 x 16 blocks)
    counts = (block_diffusion_noise(x0, 4, 100, [5, 0]) == 100).reshape(
        3, 16, 4).sum(-1)
    assert set(counts.ravel()) == {1, 2, 3, 4}
    with pytest.raises(MXNetError):          # the mask id is a data token
        block_diffusion_noise(x0, 4, int(x0[0, 0]), 1)
    with pytest.raises(MXNetError):
        block_diffusion_noise(x0, 5, 100, 1)


def test_the_iterator_yields_noised_and_clean_documents():
    """``BlockDiffusionIter`` over a token iterator: data x_t, label x_0 (the
    source's data), the same under the same seed, the noising one host span
    a batch."""
    def batches(seed):
        src = SyntheticLMIter(99, 32, batch_size=2, num_batches=3, seed=11)
        it = BlockDiffusionIter(src, 4, 99, seed=seed)
        assert it.provide_label[0].shape == (2, 32)
        return [(b.data[0].asnumpy(), b.label[0].asnumpy()) for b in it]

    t0 = time.perf_counter()
    first, again = batches(4000000033), batches(4000000033)
    spans, _ = tracing.flight.records(names=["IO::block_diffusion_noise"],
                                      since_s=t0)
    assert len(spans) == 6 and {r.cat for r in spans} == {"io"}
    src = SyntheticLMIter(99, 32, batch_size=2, num_batches=3, seed=11)
    for (xt, x0), (xt2, _), clean in zip(first, again, src):
        assert np.array_equal(xt, xt2)
        assert np.array_equal(x0, clean.data[0].asnumpy())
        assert (xt == 99).any() and np.array_equal(xt[xt != 99],
                                                   x0[xt != 99])
    assert not np.array_equal(first[0][0], first[1][0][:, :])


def test_the_reference_draws_its_batches_through_the_iterators_function():
    batches = ref.make_batches(TOY, WL, 4000000033)
    assert len(batches) == 2
    for i, (xt, x0) in enumerate(batches):
        x0 = np.asarray(x0)
        assert x0.shape == (2, 16) and not (x0 == 199).any()
        assert np.array_equal(np.asarray(xt), block_diffusion_noise(
            x0, 4, 199, [4000000033, i]))


# ----------------------------------------------------------------- experts
def test_the_holders_shares_add_up_to_the_whole_layer():
    """The share test: the 16 holders' parts of an expert layer (2 of 32
    experts each, ``expert_offset`` 0, 2, .., 30, 4 experts a token, the
    softmax router normalised over the selected) add up to what the uncut
    reference gives for the whole layer."""
    e, fe, D = 32, 24, 32
    cfg = {**TOY, "num_experts": e, "num_experts_held": e}
    x = _rand(70, (2, 16, D))
    wr = _rand(71, (e, D), 0.5)
    g, u = _rand(73, (e, fe, D), 0.2), _rand(74, (e, fe, D), 0.2)
    d = _rand(75, (e, D, fe), 0.2)
    whole = ref.experts(cfg, "float32", x, {
        "moe_router_weight": wr, "moe_expert_gate_weight": g,
        "moe_expert_up_weight": u, "moe_expert_down_weight": d})
    parts = [
        _op("SparseMoE", num_experts=e, num_experts_per_tok=4,
            num_hidden=fe, num_held=2, expert_offset=off, score="softmax")(
            x, wr, g[off:off + 2], u[off:off + 2], d[off:off + 2],
            jnp.zeros((e,)))[0]
        for off in range(0, e, 2)]
    _close(sum(parts), whole, tol=5e-5)
    assert sum(float(jnp.abs(p).max()) > 0 for p in parts) >= 12
    assert float(jnp.abs(whole - parts[0]).max()) > 1e-3


# ------------------------------------------------- the model and its module
def _bound(cfg=TOY, seed=5):
    """The program's graph bound on the CPU with the reference's weights
    (float32), one batch of the reference's rotation, forward and backward
    run."""
    net = builder.symbol(cfg, WL)
    b, t = WL["batch"], WL["seq_len"]
    exe = net.simple_bind(mx.cpu(0), grad_req="write", data=(b, t),
                          softmax_label=(b, t))
    rng = np.random.RandomState(seed)
    params = {k: (1.0 + 0.1 * rng.standard_normal(v.shape)
                  if k.endswith("_gamma") else 5.0 * np.asarray(v))
              .astype(np.float32)
              for k, v in ref.init_params(cfg, seed).items()}
    xt, x0 = ref.make_batches(cfg, WL, seed)[0]
    for name, arr in exe.arg_dict.items():
        if name == "data":
            arr[:] = np.asarray(xt)
        elif name == "softmax_label":
            arr[:] = np.asarray(x0)
        else:
            arr[:] = params[builder.leaf_name(name)]
    exe.forward(is_train=True)
    exe.backward()
    return exe, {k: jnp.asarray(v) for k, v in params.items()}, (xt, x0)


def test_the_model_agrees_with_the_reference_leaf_by_leaf():
    """Loss and every leaf's gradient: the noised and clean copies through
    two layers under the block-diffusion mask (on the CPU the XLA arm), the
    softmax experts, the head and the weighted loss over the noised half;
    every attention layer counted as block diffusion."""
    telemetry.enable()
    try:
        n0 = telemetry.value("attention_dispatch_total", path="reference")
        exe, params, batch = _bound()
        assert telemetry.value("attention_dispatch_total",
                               path="reference") - n0 in (2, 4)
    finally:
        telemetry.disable()
    with jax.default_matmul_precision("highest"):
        loss, grads, _ = ref.loss_and_grad(TOY, params, batch)
    assert abs(float(exe.outputs[0].asnumpy().ravel()[0]) - float(loss)) \
        < 2e-5 * float(loss)
    assert set(exe.grad_dict) - {"data", "softmax_label"} == \
        {"tfm_" + k for k in grads}
    for name, want in grads.items():
        _close(exe.grad_dict["tfm_" + name].asnumpy(), want, tol=5e-4)


@pytest.fixture(scope="module")
def sound():
    """The bound graph's weights and batch, and the sound reference's
    (loss, gradients) on them."""
    _, params, batch = _bound()
    loss, grads, _ = ref.loss_and_grad(TOY, params, batch)
    return params, batch, loss, grads


def test_the_references_blocks_change_nothing(monkeypatch, sound):
    """The reference takes its queries and its head's rows in blocks, so
    that it fits beside ``perf/refs/train.py``'s state on the chip: the
    sizes of the blocks move no number."""
    params, batch, loss, grads = sound
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    monkeypatch.setattr(ref, "HEAD_ROWS", 4)
    ref._stages.cache_clear()
    try:
        loss2, grads2, _ = ref.loss_and_grad(TOY, params, batch)
    finally:
        ref._stages.cache_clear()
    assert abs(float(loss2 - loss)) < 1e-6 * float(loss)
    for name in grads:
        _close(grads2[name], grads[name], tol=1e-5)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_reads_apart(fault, sound):
    """Every fault planted in the reference moves the loss or a leaf's
    gradient by far more than the program differs from the sound one."""
    params, batch, loss, grads = sound
    bad_loss, bad, _ = ref.loss_and_grad({**TOY, "fault": fault}, params,
                                         batch)
    gap = max(float(jnp.linalg.norm(bad[k] - grads[k])
                    / (jnp.linalg.norm(grads[k]) + 1e-12)) for k in grads)
    assert gap > 0.02 or abs(float(bad_loss - loss)) > 1e-3 * float(loss)


def test_config_takes_block_diffusion_only_where_it_can_keep_it():
    base = ("x", 64, 2, 32, 2, 64, 16)
    ok = dict(norm="rms", position="rope", diffusion_block=4,
              diffusion_mask_id=63)
    assert TransformerConfig(*base, **ok).diffusion_block == 4
    for bad in (dict(diffusion_block=3), dict(diffusion_mask_id=64),
                dict(diffusion_mask_id=-1), dict(position="learned"),
                dict(layer_types=("sliding_attention", "full_attention"),
                     window=4),
                dict(diffusion_block=32)):
        with pytest.raises(ValueError):
            TransformerConfig(*base, **{**ok, **bad})
    net = builder.symbol(TOY, WL)
    names = net.list_arguments()
    assert "tfm_l1_attn_q_norm_gamma" in names
    assert not any("bd_" in n for n in names)
    assert [n for n in net.get_internals().list_outputs()
            if n.endswith("_bd_output")] == ["tfm_l0_bd_output",
                                             "tfm_l1_bd_output"]
    with pytest.raises(ValueError):
        builder.symbol({**TOY, "mlp_only_layers": [0]}, WL)


def test_the_configuration_file_holds_the_catalog_row():
    with open(os.path.join(ROOT, "perf", "configs",
                           "sdar_30b_a3b.json")) as f:
        cfg = json.load(f)
    published = {"hidden_size": 2048, "head_dim": 128,
                 "num_attention_heads": 32, "num_key_value_heads": 4,
                 "moe_intermediate_size": 768, "num_experts": 128,
                 "num_experts_per_tok": 8, "norm_topk_prob": True,
                 "rms_norm_eps": 1e-6, "rope_theta": 1000000,
                 "tie_word_embeddings": False, "decoder_sparse_step": 1,
                 "mlp_only_layers": [], "model_type": "sdar_moe"}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 151936
    assert cfg["num_experts_held"] * 16 == cfg["num_experts"]
    assert set(cfg["reduced_note"]) == set(cfg["reduced"])
    assert cfg["mask_token_id"] < cfg["vocab_size"] and float(
        jnp.asarray(cfg["mask_token_id"], jnp.bfloat16)) == \
        cfg["mask_token_id"]
    n = sum(int(np.prod(shape)) for _, shape, _, _ in ref.param_spec(cfg))
    assert n == 419130880


def test_operations_and_bytes_by_hand():
    with open(os.path.join(ROOT, "perf", "configs",
                           "sdar_30b_a3b.json")) as f:
        cfg = json.load(f)
    wl = {"batch": 1, "seq_len": 4096}
    pairs = sum(4 * (4 + 4 * c) + 4 * 4 * (c + 1) for c in range(1024))
    assert ref.visible_pairs(4096, 4) == pairs == 16793600
    assert int(np.asarray(pa._bd_visible((64, 64), 0, 0, 0, 32, 4)).sum()) \
        == ref.visible_pairs(32, 4)
    assert ref.bd_attention_flops(cfg, wl) == 6 * 12 * pairs * 32 * 128
    assert 4.9e12 < ref.bd_attention_flops(cfg, wl) < 5.0e12
    assert ref.bd_attention_bytes(cfg, wl) == 6 * 144 * 8192 * 128 * 2
    # bound by operations
    assert ref.bd_attention_flops(cfg, wl) / 197e12 > \
        ref.bd_attention_bytes(cfg, wl) / 819e9
    assert ref.expert_rows(cfg, wl) == 8192 * 8 / 16       # 512 an expert
    assert builder.bd_attention_flops is ref.bd_attention_flops
    assert builder.items_per_step(cfg, wl) == 4096


def test_the_rehearsal_of_the_cell_is_correct():
    """The configuration's ``--rehearse`` on the CPU: the tiny model's three
    fused steps through ``Module`` against the reference, under the
    rehearsal's limits, every step on the fused path."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         "sdarmoe_train_4k", "--seed", "2147483999", "--seconds", "2",
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
