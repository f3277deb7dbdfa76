"""Transformer LM workload (models/) — composition + parity pins.

ISSUE 20: the decoder LM must be ONE model family across every
execution strategy — symbol graph (Module fused step), functional
blocks (pipeline/ring/MoE composition), flash vs reference attention —
with parity tests pinning that they all compute the same math.  Runs on
the virtual 8-device CPU mesh from conftest.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import get_config
from mxnet_tpu.models.transformer import (transformer_block, transformer_lm,
                                          init_block_params, block_apply,
                                          pipeline_transformer,
                                          long_context_attention,
                                          moe_transformer_ffn)

CFG = get_config("tiny", seq_len=16)


# ---------------------------------------------------------------------------
# symbol graph <-> functional block
# ---------------------------------------------------------------------------
def _bind_block(B):
    x = mx.sym.Variable("data")
    blk = transformer_block(x, CFG, 0, "")
    exe = blk.simple_bind(mx.cpu(0), grad_req="null",
                          data=(B, CFG.seq_len, CFG.d_model))
    return exe


_SYM2FN = {
    "l0_ln1_gamma": "ln1_gamma", "l0_ln1_beta": "ln1_beta",
    "l0_attn_query_weight": "query_weight",
    "l0_attn_key_weight": "key_weight",
    "l0_attn_value_weight": "value_weight",
    "l0_attn_out_proj_weight": "out_proj_weight",
    "l0_ln2_gamma": "ln2_gamma", "l0_ln2_beta": "ln2_beta",
    "l0_ffn_fc1_weight": "fc1_weight", "l0_ffn_fc1_bias": "fc1_bias",
    "l0_ffn_down_weight": "down_weight", "l0_ffn_down_bias": "down_bias",
}


def test_symbol_block_matches_functional_block():
    """The Symbol block (what Module trains) and block_apply (what the
    pipeline/parallel paths run) are the same math: same registry op
    implementations, so the outputs agree to fp32 roundoff."""
    B = 2
    exe = _bind_block(B)
    rng = np.random.RandomState(0)
    params = init_block_params(CFG, rng)
    assert set(_SYM2FN.keys()) | {"data"} == set(exe.arg_dict.keys())
    for sym_name, fn_name in _SYM2FN.items():
        arr = np.asarray(params[fn_name], np.float32)
        assert exe.arg_dict[sym_name].shape == arr.shape, sym_name
        exe.arg_dict[sym_name][:] = arr
    x = rng.standard_normal(
        (B, CFG.seq_len, CFG.d_model)).astype(np.float32)
    exe.arg_dict["data"][:] = x
    got = exe.forward(is_train=False)[0].asnumpy()
    want = np.asarray(block_apply(CFG, params, jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Module training: fused vs eager step parity + descent
# ---------------------------------------------------------------------------
def _train_losses(monkeypatch, fused, steps=3, B=4):
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1" if fused else "0")
    net = transformer_lm(CFG)
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",),
                        context=[mx.cpu(0)])
    mod.bind(data_shapes=[("data", (B, CFG.seq_len))],
             label_shapes=[("softmax_label", (B, CFG.seq_len))])
    mx.random.seed(11)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="local", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    it = mx.io.SyntheticLMIter(CFG.vocab_size, CFG.seq_len, batch_size=B,
                               num_batches=steps, seed=3)
    losses = []
    for batch in it:
        mod.forward_backward(batch)
        mod.update()
        losses.append(float(mod.get_outputs()[0].asnumpy().ravel()[0]))
    return losses


def test_fused_vs_eager_step_parity(monkeypatch):
    """The whole LM step — streaming CE head included — takes the fused
    single-program path and the eager multi-program path to the same
    loss trajectory."""
    eager = _train_losses(monkeypatch, fused=False)
    fused = _train_losses(monkeypatch, fused=True)
    np.testing.assert_allclose(fused, eager, rtol=1e-5, atol=1e-6)


def test_transformer_lm_loss_descends(monkeypatch):
    """Repeated batch: the full graph (embedding -> blocks -> CE) must
    actually learn, not just run."""
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    B = 4
    net = transformer_lm(CFG)
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",),
                        context=[mx.cpu(0)])
    mod.bind(data_shapes=[("data", (B, CFG.seq_len))],
             label_shapes=[("softmax_label", (B, CFG.seq_len))])
    mx.random.seed(5)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="local", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    rng = np.random.RandomState(0)
    toks = rng.randint(0, CFG.vocab_size, (B, CFG.seq_len))

    class _B:
        data = [mx.nd.array(toks.astype(np.float32))]
        label = [mx.nd.array(np.roll(toks, -1, axis=1).astype(np.float32))]

    losses = []
    for _ in range(8):
        mod.forward_backward(_B)
        mod.update()
        losses.append(float(mod.get_outputs()[0].asnumpy().ravel()[0]))
    assert losses[-1] < losses[0] - 0.5, losses


# ---------------------------------------------------------------------------
# parallel composition parity
# ---------------------------------------------------------------------------
def test_long_context_ring_matches_blockwise_8dev():
    """Sequence-parallel attention over the 8-way `sp` mesh vs the
    single-device blockwise scan — same numbers, shard count included
    in neither."""
    from mxnet_tpu.parallel.ring_attention import blockwise_attention
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    r = np.random.default_rng(4)
    B, H, T, D = 1, 2, 1024, 16
    q, k, v = (jnp.asarray(r.standard_normal((B, H, T, D)) * 0.5,
                           jnp.float32) for _ in range(3))
    from mxnet_tpu.parallel import make_mesh
    mesh = make_mesh({"sp": 8})
    got = long_context_attention(q, k, v, mesh, axis="sp", causal=True,
                                 block_size=128)
    ref = blockwise_attention(q, k, v, block_size=128, causal=True,
                              use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_moe_transformer_ffn_expert_parallel_parity():
    """The MoE FFN drop-in keeps (B, T, D) shape and the expert-parallel
    mesh path matches the local all-experts reference."""
    from mxnet_tpu.parallel.moe import init_moe_params
    from mxnet_tpu.parallel import make_mesh
    rng = np.random.RandomState(6)
    params = init_moe_params(rng, d_model=16, d_hidden=32, num_experts=8)
    x = jnp.asarray(rng.randn(2, 16, 16).astype(np.float32))
    ref = moe_transformer_ffn(x, params, mesh=None, k=2,
                              capacity_factor=8.0)
    assert ref.shape == x.shape
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices for the expert-parallel path")
    mesh = make_mesh({"ep": 4}, devices=jax.devices()[:4])
    out = moe_transformer_ffn(x, params, mesh=mesh, axis="ep", k=2,
                              capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_pipeline_transformer_matches_sequential():
    """Four transformer blocks as GPipe stages vs applying the same
    blocks in sequence."""
    from mxnet_tpu.parallel import make_mesh
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    stages = 4
    rng = np.random.RandomState(8)
    per_stage = [init_block_params(CFG, rng) for _ in range(stages)]
    stacked = {k: jnp.stack([p[k] for p in per_stage])
               for k in per_stage[0]}
    x = jnp.asarray(rng.randn(8, CFG.seq_len, CFG.d_model)
                    .astype(np.float32) * 0.5)
    mesh = make_mesh({"pp": stages}, devices=jax.devices()[:stages])
    got = pipeline_transformer(mesh, "pp", CFG, stacked, x, n_micro=4)
    ref = x
    for p in per_stage:
        ref = block_apply(CFG, p, ref)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# megatron sharding rules cover the model's parameter names
# ---------------------------------------------------------------------------
def test_megatron_rules_shard_transformer_names():
    """Row-parallel names (out_proj/down) must NOT be claimed by the
    column rule — the regex-order regression this PR fixed."""
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.mesh import megatron_rules, P
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = make_mesh({"dp": -1, "tp": 2})
    rules = megatron_rules(mesh)
    d = CFG.d_model
    assert rules.spec_for("tfm_l0_attn_query_weight", (d, d)) \
        == P("tp", None)
    assert rules.spec_for("tfm_l0_attn_out_proj_weight", (d, d)) \
        == P(None, "tp")
    assert rules.spec_for("tfm_l0_ffn_fc1_weight", (CFG.d_ff, d)) \
        == P("tp", None)
    assert rules.spec_for("tfm_l0_ffn_down_weight", (d, CFG.d_ff)) \
        == P(None, "tp")
    assert rules.spec_for("tfm_tok_embedding_weight",
                          (CFG.vocab_size, d)) == P(None, "tp")
    assert rules.spec_for("tfm_l0_ln1_gamma", (d,)) == P()
