"""Decoder-only transformer LM as a first-class Symbol workload.

Reference analog: none in-tree — the reference (2018) stops at
example/rnn word LMs; this is the beyond-parity workload ROADMAP item 1
names.  Two layers of API:

* **Symbol graph** (``transformer_lm`` / ``transformer_block``): the
  training graph that binds through Module and runs the fused/mesh step
  end to end — Embedding, pre-norm blocks around the
  ``MultiHeadAttention`` op (Pallas flash kernel where
  ``ops.nn.mha_uses_kernel`` admits the shape), gelu FFN, streaming-CE
  loss.  The
  fields of ``TransformerConfig`` after its first seven select the block
  variants of the one definition (RMSNorm, rotary positions, grouped
  key/value heads, the gated SiLU feed-forward, ``ShortConv`` layers by a
  layer pattern, ``SparseMoE`` experts after leading dense layers, a head
  tied to the embedding; sliding-window layers among full ones with a rope
  of each kind's own, heads of a size of their own, softmax-scored
  experts; Kimi Delta Attention layers among latent ones with no
  positions): an LFM2-class hybrid, a Mellum-class mixture or a
  Kimi-Linear-class hybrid is a config, not a second model.
  Parameter names are chosen so ``parallel.mesh.megatron_rules`` shards
  a DP×TP mesh with zero configuration: ``*_query/key/value_weight`` and
  ``*_fc1_weight`` column-parallel, ``*_out_proj_weight`` and
  ``*_down_weight`` row-parallel, ``*_embedding_weight`` vocab-split.

* **Functional block** (``init_block_params`` / ``block_apply`` +
  the composition helpers): the SAME block math as pure jax functions
  reusing the registered op implementations, which is what the
  parallel/ subsystems compose — ``pipeline_transformer`` runs blocks as
  GPipe stages, ``long_context_attention`` shards the sequence over a
  mesh ``sp`` axis via ring attention, ``moe_transformer_ffn`` swaps the
  dense FFN for the expert-parallel MoE layer.  Reusing the op fns (not
  a re-implementation) is what makes the parity tests in
  tests/test_transformer.py bit-exact.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from .configs import TransformerConfig
from .. import telemetry as _telemetry
from ..ops.registry import OPS

# counted where the graph is built (``transformer_lm``): a shared expert and
# the prediction module are graph structure, no op of their own to count in
_MOE_SHARED = _telemetry.counter(
    "moe_shared_experts_total",
    "Shared experts (a gated feed-forward every token takes beside the "
    "routed experts) in the transformer graphs built, a layer each")
_MTP_MODULES = _telemetry.counter(
    "mtp_modules_total",
    "Multi-token-prediction modules in the transformer graphs built")


# ---------------------------------------------------------------------------
# Symbol graph
# ---------------------------------------------------------------------------
def _norm(x, cfg: TransformerConfig, name: str):
    from .. import symbol as sym
    if cfg.norm == "rms":
        return sym.RMSNorm(x, eps=cfg.norm_eps, name=name)
    return sym.LayerNorm(x, eps=cfg.norm_eps, name=name)


def _feed_forward(h, cfg: TransformerConfig, idx: int, n: str):
    """The block's second half on the normed stream: sparse experts from
    layer ``num_dense_layers`` on where the config has experts, else the
    dense feed-forward of the config's kind."""
    from .. import symbol as sym

    def swiglu(width, stem):
        gate = sym.FullyConnected(h, num_hidden=width, flatten=False,
                                  no_bias=True, name=stem + "gate")
        up = sym.FullyConnected(h, num_hidden=width, flatten=False,
                                no_bias=True, name=stem + "up")
        f = sym.elemwise_mul(
            sym.Activation(gate, act_type="silu", name=stem + "silu"), up,
            name=stem + "gated")
        return sym.FullyConnected(f, num_hidden=cfg.d_model, flatten=False,
                                  no_bias=True, name=stem + "down")

    if cfg.num_experts and idx >= cfg.num_dense_layers:
        variants = {} if cfg.moe_score == "sigmoid" \
            else {"score": cfg.moe_score}
        if cfg.routed_scaling != 1.0:
            variants["routed_scaling"] = cfg.routed_scaling
        if cfg.moe_weight_eps != 1e-6:
            variants["weight_eps"] = cfg.moe_weight_eps
        routed = sym.SparseMoE(
            h, num_experts=cfg.num_experts,
            num_experts_per_tok=cfg.experts_per_tok,
            num_hidden=cfg.moe_d_ff, num_held=cfg.experts_held,
            expert_offset=cfg.expert_offset, name=n + "moe", **variants)
        if not cfg.n_shared_experts:
            return routed
        # the shared experts: one gated feed-forward of their joint width
        # that every token takes with weight 1, beside the routed sum
        # (every holder of the layer's experts computes it alike)
        if _telemetry.enabled:
            _MOE_SHARED.inc(cfg.n_shared_experts)
        return sym.elemwise_add(
            routed, swiglu(cfg.moe_d_ff * cfg.n_shared_experts,
                           n + "shared_"), name=n + "shared_sum")
    if cfg.ffn == "swiglu":
        return swiglu(cfg.d_ff, n + "ffn_")
    f = sym.FullyConnected(h, num_hidden=cfg.d_ff, flatten=False,
                           no_bias=False, name=n + "ffn_fc1")
    f = sym.Activation(f, act_type="gelu", name=n + "ffn_gelu")
    return sym.FullyConnected(f, num_hidden=cfg.d_model, flatten=False,
                              no_bias=False, name=n + "ffn_down")


def transformer_block(x, cfg: TransformerConfig, idx: int, prefix: str,
                      stem: Optional[str] = None):
    """One pre-norm decoder block: x + Mix(Norm(x)); x + FFN(Norm(x)).
    ``Mix`` is attention, the gated short convolution where
    ``cfg.layer_types[idx]`` says ``conv``, or Kimi Delta Attention where it
    says ``kda`` (node ``<prefix>l<idx>_kda``).  A ``sliding_attention``
    layer's node is ``<prefix>l<idx>_swa`` (a device trace's scopes then
    tell the two kinds of attention apart) and its weights keep the
    ``<prefix>l<idx>_attn_`` names of every attention layer, as do those of
    a layer under the block-diffusion mask (``cfg.diffusion_block``), node
    ``<prefix>l<idx>_bd``; a latent
    layer's node is ``<prefix>l<idx>_mla``, its weights ``..._mla_*``.
    ``stem`` names the block's nodes in place of ``<prefix>l<idx>_`` (the
    prediction module's block; ``idx`` still says which feed-forward)."""
    from .. import symbol as sym
    n = stem or "%sl%d_" % (prefix, idx)
    h = _norm(x, cfg, n + "ln1")
    kind = cfg.layer_types[idx] if cfg.layer_types else "full_attention"
    if kind == "conv":
        a = sym.ShortConv(h, kernel=cfg.conv_kernel, name=n + "conv")
        x = sym.elemwise_add(x, a, name=n + "conv_res")
    elif kind == "kda":
        a = sym.DeltaAttention(h, num_heads=cfg.n_heads,
                               head_dim=cfg.kda_head_dim,
                               conv_kernel=cfg.conv_kernel,
                               eps=cfg.norm_eps, name=n + "kda")
        x = sym.elemwise_add(x, a, name=n + "kda_res")
    elif cfg.attention == "latent":
        a = sym.MultiHeadAttention(
            h, num_heads=cfg.n_heads, causal=True, name=n + "mla",
            head_dim=cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
            v_head_dim=cfg.v_head_dim,
            rope_theta=cfg.rope_theta if cfg.position == "rope" else 0.0,
            eps=cfg.norm_eps)
        x = sym.elemwise_add(x, a, name=n + "attn_res")
    else:
        variants = {}
        if cfg.n_kv_heads and cfg.n_kv_heads != cfg.n_heads:
            variants["num_kv_heads"] = cfg.n_kv_heads
        if cfg.qk_norm:
            variants.update(qk_norm=True, eps=cfg.norm_eps)
        if cfg.position == "rope":
            variants["rope_theta"], yarn = cfg.rope_of(kind)
            if yarn:
                variants["rope_yarn"] = yarn
        if cfg.head_dim:
            variants["head_dim"] = cfg.head_dim
        name, causal = n + "attn", True
        if kind == "sliding_attention" or cfg.diffusion_block:
            # the weights under the names every attention layer gives them
            # (megatron_rules, checkpoints), the node under its own kind's
            mask = {"diffusion_block": cfg.diffusion_block} \
                if cfg.diffusion_block else {"window": cfg.window}
            variants.update(mask, **{
                w: sym.Variable("%s_%s" % (name, w))
                for w in ("query_weight", "key_weight", "value_weight",
                          "out_proj_weight")
                + (("q_norm_gamma", "k_norm_gamma") if cfg.qk_norm else ())})
            name, causal = (n + "bd", False) if cfg.diffusion_block \
                else (n + "swa", True)
        a = sym.MultiHeadAttention(h, num_heads=cfg.n_heads, causal=causal,
                                   name=name, **variants)
        x = sym.elemwise_add(x, a, name=n + "attn_res")
    h = _norm(x, cfg, n + "ln2")
    return sym.elemwise_add(x, _feed_forward(h, cfg, idx, n),
                            name=n + "ffn_res")


def transformer_lm(cfg: TransformerConfig, prefix: str = "tfm_",
                   loss: bool = True):
    """Build the decoder LM Symbol.

    ``loss=True`` (training): returns ``make_loss(mean(streaming CE))``
    — a scalar loss head whose implicit backward seeds ones, so
    ``Module.forward_backward`` / the fused step train it directly and
    ``get_outputs()[0]`` IS the batch loss.  ``loss=False``: returns the
    ``(B, T, vocab)`` logits (serving / eval).

    Data is ``(B, T)`` token ids, label ``(B, T)`` next tokens.  Positions
    (``cfg.position``): ``learned`` adds a learned table post-embedding
    (gpt2 style), ``rope`` turns queries and keys inside every attention
    layer, ``none`` has no positions at all (the order comes from the
    causal masks and the recurrent mixers alone).  ``cfg.tie_head``
    computes the logits on
    the embedding's own rows: one argument, ``<prefix>tok_embedding_weight``,
    whose gradient is the sum of its two uses.

    ``cfg.diffusion_block`` (training only): data is the noised document
    x_t (B, T) and label the clean one x_0; both are embedded by the one
    table and concatenated along time, every layer attends under the
    block-diffusion mask over the 2T positions, the final norm and the head
    see the noised half only, and the loss is the mean of
    ``block_diffusion_ce`` (node ``<prefix>diffusion_ce``): each masked
    position's cross-entropy against x_0 times b over its block's masked
    count.
    """
    from .. import symbol as sym
    data = sym.Variable("data")                       # (B, T) token ids
    mtp = bool(cfg.mtp_layers) and loss
    if cfg.diffusion_block and not loss:
        raise ValueError("block diffusion builds its training graph: the "
                         "clean copy comes in as the label")
    table = {"weight": sym.Variable(prefix + "tok_embedding_weight")} \
        if cfg.tie_head or mtp or cfg.diffusion_block else {}
    head = table if cfg.tie_head else \
        {"weight": sym.Variable(prefix + "lm_head_weight")} if mtp else {}
    x = sym.Embedding(data, input_dim=cfg.vocab_size,
                      output_dim=cfg.d_model,
                      name=prefix + "tok_embedding", **table)
    if cfg.position == "learned":
        # learned positions: arange(T) broadcast over the batch rides the
        # same Embedding op — slice_axis of a (1, T) iota variable would
        # need a T-sized input; instead embed positions of `data*0 + iota`
        # shape
        pos_ids = sym.broadcast_like(
            sym.expand_dims(sym.arange(0, cfg.seq_len, name=prefix + "iota"),
                            axis=0),
            data, name=prefix + "pos_ids")
        pos = sym.Embedding(pos_ids, input_dim=cfg.seq_len,
                            output_dim=cfg.d_model,
                            name=prefix + "pos_embedding")
        x = sym.broadcast_add(x, pos, name=prefix + "embed_sum")
    if cfg.diffusion_block:
        label = sym.Variable("softmax_label")         # (B, T) clean ids
        clean = sym.Embedding(label, input_dim=cfg.vocab_size,
                              output_dim=cfg.d_model,
                              name=prefix + "clean_embedding", **table)
        x = sym.concat(x, clean, dim=1, name=prefix + "both")
    for i in range(cfg.n_layers):
        x = transformer_block(x, cfg, i, prefix)
    last = x                                  # before the final norm
    if cfg.diffusion_block:
        x = sym.slice_axis(x, axis=1, begin=0, end=cfg.seq_len,
                           name=prefix + "noised")
    x = _norm(x, cfg, prefix + "final_ln")
    logits = sym.FullyConnected(x, num_hidden=cfg.vocab_size,
                                flatten=False, no_bias=True,
                                name=prefix + "lm_head", **head)
    if not loss:
        return logits
    if cfg.diffusion_block:
        ce = sym.block_diffusion_ce(logits, label, data,
                                    block=cfg.diffusion_block,
                                    mask_id=cfg.diffusion_mask_id,
                                    name=prefix + "diffusion_ce")
        return sym.make_loss(sym.mean(ce), name=prefix + "loss")
    label = sym.Variable("softmax_label")             # (B, T) next ids
    ce = sym.streaming_softmax_ce(logits, label, axis=-1,
                                  name=prefix + "ce")
    if not mtp:
        return sym.make_loss(sym.mean(ce), name=prefix + "loss")
    ce2 = _prediction_module(last, label, cfg, prefix + "mtp0_", table, head)
    total = sym.mean(ce) + cfg.mtp_loss_weight * sym.mean(ce2)
    return sym.make_loss(total, name=prefix + "loss")


def _prediction_module(last, label, cfg: TransformerConfig, n: str, table,
                       head):
    """DeepSeek-V3's multi-token-prediction module (one depth), every node
    under ``n`` = ``<prefix>mtp0_``: with ``x_i`` the last block's output
    before the final norm and ``t_{i+1}`` the step's label at ``i``,

        u_i = M [RMSNorm(x_i; g_h); RMSNorm(E[t_{i+1}]; g_e)]
        z = Block(u)      (a block of the expert kind, its own weights)
        logits = W_out RMSNorm(z; g_f')

    with the MAIN embedding ``E`` and head ``W_out`` (``table``, ``head``:
    one graph variable each, whose gradient is the sum of its uses), against
    the target ``t_{i+2}``: the label shifted by one inside the graph.  The
    last position has no such target: the module runs over all ``T``
    positions (the kernels keep their shapes) and the returned
    cross-entropies are of the first ``T - 1``."""
    from .. import symbol as sym
    if _telemetry.enabled:
        _MTP_MODULES.inc()
    e = sym.Embedding(label, input_dim=cfg.vocab_size,
                      output_dim=cfg.d_model, name=n + "embedding", **table)
    u = sym.concat(_norm(last, cfg, n + "hnorm"), _norm(e, cfg, n + "enorm"),
                   dim=2, name=n + "cat")
    u = sym.FullyConnected(u, num_hidden=cfg.d_model, flatten=False,
                           no_bias=True, name=n + "proj")
    z = transformer_block(u, cfg, cfg.n_layers, n, stem=n)
    logits = sym.FullyConnected(
        _norm(z, cfg, n + "final_ln"), num_hidden=cfg.vocab_size,
        flatten=False, no_bias=True, name=n + "head", **head)
    # position i's target is the label of position i + 1; the last
    # position's slot takes the first label and is cut off below
    target = sym.concat(
        sym.slice_axis(label, axis=1, begin=1, end=None),
        sym.slice_axis(label, axis=1, begin=0, end=1), dim=1,
        name=n + "target")
    ce = sym.streaming_softmax_ce(logits, target, axis=-1, name=n + "ce")
    return sym.slice_axis(ce, axis=1, begin=0, end=-1, name=n + "ce_seen")


# ---------------------------------------------------------------------------
# Functional block (shared math with the Symbol graph via the op registry)
# ---------------------------------------------------------------------------
_LN_ATTRS = {"axis": -1, "eps": 1e-5, "output_mean_var": False}


def _ln(x, gamma, beta):
    return OPS["LayerNorm"].fn(_LN_ATTRS, x, gamma, beta)[0]


def _mha(cfg, x, wq, wk, wv, wo):
    return OPS["MultiHeadAttention"].fn(
        {"num_heads": cfg.n_heads, "causal": True}, x, wq, wk, wv, wo)


def init_block_params(cfg: TransformerConfig, rng: np.random.RandomState,
                      dtype=jnp.float32):
    """One block's parameter dict (same shapes/orientation as the Symbol
    graph's auto-allocated args: weights are (out, in))."""
    d, f = cfg.d_model, cfg.d_ff

    def w(*shape, scale=0.02):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    return {
        "ln1_gamma": jnp.ones((d,), dtype), "ln1_beta": jnp.zeros((d,), dtype),
        "query_weight": w(d, d), "key_weight": w(d, d),
        "value_weight": w(d, d), "out_proj_weight": w(d, d),
        "ln2_gamma": jnp.ones((d,), dtype), "ln2_beta": jnp.zeros((d,), dtype),
        "fc1_weight": w(f, d), "fc1_bias": jnp.zeros((f,), dtype),
        "down_weight": w(d, f), "down_bias": jnp.zeros((d,), dtype),
    }


def block_apply(cfg: TransformerConfig, params, x):
    """Functional pre-norm block — identical math to ``transformer_block``
    (same op implementations out of the registry)."""
    h = _ln(x, params["ln1_gamma"], params["ln1_beta"])
    x = x + _mha(cfg, h, params["query_weight"], params["key_weight"],
                 params["value_weight"], params["out_proj_weight"])
    h = _ln(x, params["ln2_gamma"], params["ln2_beta"])
    h = jnp.matmul(h, params["fc1_weight"].T) + params["fc1_bias"]
    h = jax.nn.gelu(h, approximate=False)
    h = jnp.matmul(h, params["down_weight"].T) + params["down_bias"]
    return x + h


# ---------------------------------------------------------------------------
# Parallel composition
# ---------------------------------------------------------------------------
def long_context_attention(q, k, v, mesh, axis: str = "sp",
                           causal: bool = True,
                           block_size: int = 512,
                           scale: Optional[float] = None):
    """Sequence-parallel exact attention for contexts that don't fit one
    chip: ``parallel.ring_attention`` over the mesh ``axis`` — K/V shards
    rotate the ICI ring while each chip keeps its Q shard.  [B,H,T,D]
    with T sharded on ``axis``; bit-parity vs ``blockwise_attention`` is
    pinned by tests/test_transformer.py."""
    from ..parallel.ring_attention import ring_attention
    return ring_attention(q, k, v, mesh, axis=axis, causal=causal,
                          block_size=block_size, scale=scale)


def moe_transformer_ffn(x, moe_params, mesh=None, axis: str = "ep",
                        k: int = 2, capacity_factor: float = 1.25):
    """MoE FFN block body: drop-in replacement for the dense FFN half of
    ``block_apply`` (caller keeps the pre-norm + residual).  Experts are
    sharded over the mesh ``axis``; gelu to match the dense path."""
    from ..parallel.moe import moe_ffn
    T = x.shape[-2] if x.ndim > 2 else x.shape[0]
    del T
    flat = x.reshape(-1, x.shape[-1])
    out = moe_ffn(flat, moe_params, mesh=mesh, axis=axis, k=k,
                  capacity_factor=capacity_factor,
                  act=lambda a: jax.nn.gelu(a, approximate=False))
    return out.reshape(x.shape)


def pipeline_transformer(mesh, axis: str, cfg: TransformerConfig,
                         stage_params, x, n_micro: int):
    """Run transformer blocks as GPipe pipeline stages over ``mesh[axis]``:
    ``stage_params`` leaves carry a leading stage dim (one block per
    stage); microbatches stream through ``parallel.pipeline``.  Parity vs
    sequentially applying the same blocks is pinned by tests."""
    from ..parallel.pipeline import pipeline_apply

    def stage_fn(params, xb):
        return block_apply(cfg, params, xb)

    return pipeline_apply(mesh, axis, stage_fn, stage_params, x, n_micro)
