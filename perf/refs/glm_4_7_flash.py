"""Plain reference of the decoder the ``glm_4_7_flash`` configuration states
(zai-org GLM-4.7-Flash, ``model_type`` ``glm4_moe_lite``), cut to one chip's
share as the configuration's file says: the layers it keeps, the
``num_experts_held`` experts from ``expert_offset`` of every layer with
experts (the router still scores all ``n_routed_experts``), the first
``vocab_size`` rows of the vocabulary.

float32 ``jax.numpy``, matmuls at ``highest`` precision, no kernel, no cache,
nothing imported from the program.  The layers (``d`` = ``hidden_size`` 2048,
``eps`` = ``rms_norm_eps``, no bias anywhere, every weight (out, in):
``y = x W^T``):

    RMSNorm(x; g) = g * x / sqrt(mean(x^2) + eps)

    block l:  h = x + Attn(RMSNorm(x; g1));  y = h + FFN_l(RMSNorm(h; g2))

    Attn (latent attention, the same in every layer; H =
    ``num_attention_heads`` 20, r_q = ``q_lora_rank`` 768, r_kv =
    ``kv_lora_rank`` 512, d_n = ``qk_nope_head_dim`` 192, d_r =
    ``qk_rope_head_dim`` 64, d_v = ``v_head_dim`` 256):
      c_q = RMSNorm(x Wqa^T; g_q)                     Wqa [r_q, d]
      q = c_q Wqb^T, head i = [q_n_i (d_n); q_r_i (d_r)]   Wqb [H (d_n + d_r), r_q]
      [c_kv (r_kv); k_r (d_r)] = x Wkva^T             Wkva [r_kv + d_r, d]
      c_kv = RMSNorm(c_kv; g_kv)        (the d_r rotary dims are NOT normalised)
      [k_n_i (d_n); v_i (d_v)] = c_kv Wkvb^T, every head   Wkvb [H (d_n + d_v), r_kv]
      rotary positions, frequencies theta^(-2j / d_r), j = 0 .. d_r/2 - 1,
      rotate-half pairing within the d_r dims, on q_r_i and on the ONE k_r
      that every head shares
      q_i = [q_n_i; rope(q_r_i)],  k_i = [k_n_i; rope(k_r)]
      scores q_i k_i^T / sqrt(d_n + d_r), causal, softmax, times v_i;
      the H d_v outputs through Wo [d, H d_v]

    FFN_0 (``first_k_dense_replace`` 1):  Wd (silu(Wg x) * (Wu x)),
      Wg, Wu [``intermediate_size``, d]

    FFN_l, l >= 1 (E = ``n_routed_experts`` 64, k = ``num_experts_per_tok``
    4, ``topk_method`` ``noaux_tc`` with one group, ``norm_topk_prob``,
    ``routed_scaling_factor`` 1.8, ``n_shared_experts`` 1):
      s = sigmoid(x Wr^T) over all E in float32, Wr [E, d]
      sel = top_k(s + b)         (b: a buffer of E, no gradient, selection only)
      w_e = 1.8 * s_e / (sum_{e in sel} s_e + 1e-20)
      y = Shared(x) + sum_{e in sel, e held here} w_e D_e (silu(G_e x) * U_e x)
      G_e, U_e [``moe_intermediate_size``, d], D_e the transpose's shape;
      Shared: one more such gated feed-forward every token takes with weight 1

    model:  token embedding E, the blocks, a final RMSNorm, an output head
    W_out of its own, CE_main = mean cross-entropy over the tokens.

    prediction module (``num_nextn_predict_layers`` 1; DeepSeek-V3's): with
    x_i the last block's output BEFORE the final norm and t_{i+1} the step's
    label at i,
      u_i = M [RMSNorm(x_i; g_h); RMSNorm(E[t_{i+1}]; g_e)]      M [d, 2 d]
      z = Block(u)   (a block of the expert kind, its own weights)
      logits W_out RMSNorm(z; g_f') with the MAIN W_out and E, target t_{i+2}
    CE_mtp is the mean over the positions that have such a target (a
    sequence's last has none).  loss = CE_main + ``mtp_loss_weight`` CE_mtp.

The experts' weights are stacked (expert, out, in); every held expert is
computed on every token and weighted by a mask.  **Loss and gradients are
jax's own (``jax.vjp``), taken stage by stage** (embedding, each block, the
module's input, the two heads), each stage a jitted call that computes its
forward again: ``perf/refs/train.py`` holds the float32 parameters twice
(the start and the current) and Adam's two moments beside the gradient, 20
bytes a parameter, 14.1 GB at this configuration's 706 M, so one program
with every layer's temporaries does not fit beside them on a 16.9 GB chip
and one stage's does (well under 1 GB: attention five heads at a time in
query blocks of 512 over the keys at or before them, one expert at a time,
the heads' logits 1,024 positions at a time).  ``cfg["fault"]`` plants one
fault of a mechanism (``perf/tests``): ``no_shared`` (the shared expert left
out), ``rope_all`` (all d_n + d_r dims of every head rotated, head by head,
no shared part), ``no_kv_norm`` (c_kv not normalised), ``scale_one`` (1.8 ->
1), ``no_mtp`` (the second loss left out), ``top3`` (one expert fewer a
token).
"""
import functools
import json

import jax
import jax.numpy as jnp

from . import common
# the seeded rotation of (ids, next ids) drawn from the ids bfloat16 holds
# exactly: the same generator as the other language cells'
from .gpt2_medium import exact_ids, make_batches  # noqa: F401

FAULTS = ("no_shared", "rope_all", "no_kv_norm", "scale_one", "no_mtp",
          "top3")
Q_BLOCK = 512
HEAD_GROUP = 5
HEAD_ROWS = 1024
MTP = "mtp0_"


def _dims(cfg):
    """(d, H, r_q, r_kv, d_n, d_r, d_v)."""
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def _is_expert_layer(cfg, l):
    return l >= cfg["first_k_dense_replace"]


def _block_spec(cfg, p, experts):
    d, h, rq, rkv, dn, dr, dv = _dims(cfg)
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e, held = cfg["n_routed_experts"], cfg["num_experts_held"]
    spec = [(p + "ln1_gamma", (d,), "ones", "float32"),
            (p + "mla_q_a_weight", (rq, d), 0.02, "bfloat16"),
            (p + "mla_q_a_norm_gamma", (rq,), "ones", "float32"),
            (p + "mla_q_b_weight", (h * (dn + dr), rq), 0.02, "bfloat16"),
            (p + "mla_kv_a_weight", (rkv + dr, d), 0.02, "bfloat16"),
            (p + "mla_kv_a_norm_gamma", (rkv,), "ones", "float32"),
            (p + "mla_kv_b_weight", (h * (dn + dv), rkv), 0.02, "bfloat16"),
            (p + "mla_out_proj_weight", (d, h * dv), 0.02, "bfloat16"),
            (p + "ln2_gamma", (d,), "ones", "float32")]
    if not experts:
        return spec + [(p + "ffn_gate_weight", (f, d), 0.02, "bfloat16"),
                       (p + "ffn_up_weight", (f, d), 0.02, "bfloat16"),
                       (p + "ffn_down_weight", (d, f), 0.02, "bfloat16")]
    fs = fe * cfg["n_shared_experts"]
    return spec + [
        (p + "moe_router_weight", (e, d), 0.02, "float32"),
        (p + "moe_expert_bias", (e,), 0.1, "float32"),
        (p + "moe_expert_gate_weight", (held, fe, d), 0.02, "bfloat16"),
        (p + "moe_expert_up_weight", (held, fe, d), 0.02, "bfloat16"),
        (p + "moe_expert_down_weight", (held, d, fe), 0.02, "bfloat16"),
        (p + "shared_gate_weight", (fs, d), 0.02, "bfloat16"),
        (p + "shared_up_weight", (fs, d), 0.02, "bfloat16"),
        (p + "shared_down_weight", (d, fs), 0.02, "bfloat16")]


def param_spec(cfg):
    """[(name, shape, init, served dtype)] in the program's own order."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    spec = [("tok_embedding_weight", (v, d), 0.02, "bfloat16")]
    for l in range(cfg["num_hidden_layers"]):
        spec += _block_spec(cfg, "l%d_" % l, _is_expert_layer(cfg, l))
    spec += [("final_ln_gamma", (d,), "ones", "float32"),
             ("lm_head_weight", (v, d), 0.02, "bfloat16")]
    if cfg["num_nextn_predict_layers"]:
        spec += [(MTP + "hnorm_gamma", (d,), "ones", "float32"),
                 (MTP + "enorm_gamma", (d,), "ones", "float32"),
                 (MTP + "proj_weight", (d, 2 * d), 0.02, "bfloat16")]
        spec += _block_spec(cfg, MTP, True)
        spec.append((MTP + "final_ln_gamma", (d,), "ones", "float32"))
    return spec


def init_params(cfg, seed):
    """The weights as served, from the seed (``common.init_from_spec``)."""
    return common.init_from_spec(param_spec(cfg), seed)


# ------------------------------------------------------------------ layers
def _rms(x, g, eps):
    return g * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps)


def _linear(x, w, precision):
    """``x @ w.T``, w of (out, in)."""
    return common.result(jnp.matmul(common.operand(x, precision),
                                    common.operand(w, precision).T), precision)


def _rotary(x, theta):
    """[.., T, n] turned by position in the rotate-half pairing over its
    ``n`` dims: frequencies ``theta^(-2j / n)``."""
    t, n = x.shape[-2:]
    freqs = jnp.asarray([theta ** (-2.0 * j / n) for j in range(n // 2)],
                        jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., n // 2:], x[..., :n // 2]], -1)
    return x * cos + rot * sin


def _query_block(precision, lo, q, k, v):
    """softmax(q k^T / sqrt(head)) v of the queries [B,H,n,head] that start
    at position ``lo``, over the keys [B,H,lo + n,.] at or before them."""
    n, hd = q.shape[-2:]
    s = common.result(jnp.einsum(
        "bhqd,bhkd->bhqk", common.operand(q, precision),
        common.operand(k, precision)), precision) / hd ** 0.5
    keep = (lo + jnp.arange(n))[:, None] >= jnp.arange(k.shape[-2])[None, :]
    pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return common.result(jnp.einsum(
        "bhqk,bhkd->bhqd", common.operand(pr, precision),
        common.operand(v, precision)), precision)


def _head_group(cfg, precision, c_q, c_kv, k_r, q_b, kv_b):
    """The attention of one group of heads, from the two normalised
    bottlenecks and the shared key part: ``q_b`` / ``kv_b`` are the group's
    rows of the two up-projections; [B, T, heads x d_v]."""
    _, _, _, _, dn, dr, dv = _dims(cfg)
    theta = float(cfg["rope_theta"])
    bsz, t, _ = c_q.shape
    n_heads = q_b.shape[0] // (dn + dr)

    def heads(y, size):
        return y.reshape(bsz, t, n_heads, size).transpose(0, 2, 1, 3)

    q = heads(_linear(c_q, q_b, precision), dn + dr)
    kv = heads(_linear(c_kv, kv_b, precision), dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    k_r = jnp.broadcast_to(k_r[:, None], (bsz, n_heads, t, dr))
    if cfg.get("fault") == "rope_all":
        q = _rotary(q, theta)
        k = _rotary(jnp.concatenate([k_n, k_r], -1), theta)
    else:
        q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], theta)], -1)
        k = jnp.concatenate([k_n, _rotary(k_r, theta)], -1)
    n = min(Q_BLOCK, t)
    o = jnp.concatenate(
        [jax.checkpoint(functools.partial(_query_block, precision, lo))(
            q[:, :, lo:lo + n], k[:, :, :lo + n], v[:, :, :lo + n])
         for lo in range(0, t, n)], axis=2)
    return o.transpose(0, 2, 1, 3).reshape(bsz, t, n_heads * dv)


def _attention(cfg, precision, x, p):
    """The heads are worked in groups of ``HEAD_GROUP``, each under
    ``jax.checkpoint``: what a group keeps for its backward pass is the two
    bottlenecks it starts from."""
    _, h, _, rkv, dn, dr, dv = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    c_q = _rms(_linear(x, p["mla_q_a_weight"], precision),
               p["mla_q_a_norm_gamma"], eps)
    kv_a = _linear(x, p["mla_kv_a_weight"], precision)
    c_kv, k_r = kv_a[..., :rkv], kv_a[..., rkv:]
    if cfg.get("fault") != "no_kv_norm":
        c_kv = _rms(c_kv, p["mla_kv_a_norm_gamma"], eps)
    group = jax.checkpoint(functools.partial(_head_group, cfg, precision))
    o = jnp.concatenate(
        [group(c_q, c_kv, k_r,
               p["mla_q_b_weight"][i * (dn + dr):j * (dn + dr)],
               p["mla_kv_b_weight"][i * (dn + dv):j * (dn + dv)])
         for i, j in ((i, min(i + HEAD_GROUP, h))
                      for i in range(0, h, HEAD_GROUP))], axis=-1)
    return _linear(o, p["mla_out_proj_weight"], precision)


def _gated(precision, x, gate, up, down):
    g = _linear(x, gate, precision)
    u = _linear(x, up, precision)
    return _linear(jax.nn.silu(g) * u, down, precision)


def route(cfg, x, router_weight, bias):
    """(sel [.., k] expert ids, w [.., k] weights) of the tokens ``x``:
    float32 in every precision, as the program's router is."""
    fault = cfg.get("fault")
    k = cfg["num_experts_per_tok"] - (fault == "top3")
    s = jax.nn.sigmoid(jnp.matmul(x, router_weight.T))
    _, sel = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel, w * (1.0 if fault == "scale_one"
                     else cfg["routed_scaling_factor"])


def routed_experts(cfg, precision, x, p):
    """The held experts' part of the layer's routed sum: every held expert
    on every token, weighted by the token's weight for it (nought where the
    token did not select it)."""
    e, held, off = (cfg["n_routed_experts"], cfg["num_experts_held"],
                    cfg["expert_offset"])
    sel, w = route(cfg, x, p["moe_router_weight"], p["moe_expert_bias"])
    weight_of = jnp.sum(jax.nn.one_hot(sel, e, dtype=x.dtype)
                        * w[..., None], axis=-2)                # [B,T,E]
    mine = weight_of[..., off:off + held]                       # [B,T,held]
    y = jnp.zeros_like(x)
    for i in range(held):
        out = jax.checkpoint(functools.partial(_gated, precision))(
            x, p["moe_expert_gate_weight"][i], p["moe_expert_up_weight"][i],
            p["moe_expert_down_weight"][i])
        y = y + mine[..., i, None] * out
    return y


def shared_expert(cfg, precision, x, p):
    return _gated(precision, x, p["shared_gate_weight"],
                  p["shared_up_weight"], p["shared_down_weight"])


def expert_layer(cfg, precision, x, p):
    """FFN_l, l >= 1, on the normed stream: this holder's routed part and
    the shared expert."""
    y = routed_experts(cfg, precision, x, p)
    if cfg.get("fault") != "no_shared":
        y = y + shared_expert(cfg, precision, x, p)
    return y


def block(cfg, precision, experts, x, p):
    """One block on the residual stream; ``p`` by the block's own names."""
    eps = cfg["rms_norm_eps"]
    h = x + _attention(cfg, precision, _rms(x, p["ln1_gamma"], eps), p)
    f = _rms(h, p["ln2_gamma"], eps)
    if experts:
        return h + expert_layer(cfg, precision, f, p)
    return h + _gated(precision, f, p["ffn_gate_weight"], p["ffn_up_weight"],
                      p["ffn_down_weight"])


def _embed(p, ids):
    return p["tok_embedding_weight"][ids.astype(jnp.int32)]


def _head_rows(cfg, precision, p, x, targets, seen):
    """The ``seen`` positions' cross-entropies of ``x``'s logits against
    ``targets``, [B, n]."""
    x = _rms(x, p["gamma"], cfg["rms_norm_eps"])
    logits = _linear(x, p["lm_head_weight"], precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, targets.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    return (lse - picked) * seen


def _head(cfg, precision, scale, p, x, targets, seen):
    """(``scale`` x the sum over the ``seen`` positions of the
    cross-entropy of ``x``'s logits against ``targets``, each row's mean
    over them); ``p``: gamma and lm_head_weight.  ``HEAD_ROWS`` positions
    at a time, each under ``jax.checkpoint``: the logits of 4,096 positions
    over 19,360 rows are 317 MB, and their gradient as much again."""
    rows = jax.checkpoint(functools.partial(_head_rows, cfg, precision))
    t = x.shape[1]
    ce = jnp.concatenate(
        [rows(p, x[:, lo:lo + HEAD_ROWS], targets[:, lo:lo + HEAD_ROWS],
              seen[:, lo:lo + HEAD_ROWS])
         for lo in range(0, t, HEAD_ROWS)], axis=1)
    return scale * jnp.sum(ce), jnp.sum(ce, -1) / jnp.sum(seen, -1)


def _mtp_in(cfg, precision, p, x, labels):
    """u = M [RMSNorm(x; g_h); RMSNorm(E[labels]; g_e)]."""
    eps = cfg["rms_norm_eps"]
    both = jnp.concatenate(
        [_rms(x, p[MTP + "hnorm_gamma"], eps),
         _rms(_embed(p, labels), p[MTP + "enorm_gamma"], eps)], axis=-1)
    return _linear(both, p[MTP + "proj_weight"], precision)


# ------------------------------------------------- stages, jitted, cached
def _block_params(cfg, params, prefix, experts=True):
    """The block's own leaves under ``prefix``, by the block's names."""
    return {name: params[prefix + name]
            for name, *_ in _block_spec(cfg, "", experts)}


_MTP_IN = ("tok_embedding_weight", MTP + "hnorm_gamma", MTP + "enorm_gamma",
           MTP + "proj_weight")


def _renamed(grads, prefix):
    return {prefix + k: v for k, v in grads.items()}


@functools.lru_cache(maxsize=1)
def _stages(cfg_json, precision):
    """The model's stages as jitted (forward, backward) pairs; a backward
    is ``jax.vjp`` of the stage's own forward, computed again.  One
    configuration's at a time: a process that reads several (the faults,
    the control) lets the last one's programs go, which hold device memory
    this model has none of to spare."""
    cfg = json.loads(cfg_json)

    def pair(f):
        def bwd(p, x, g, *rest):
            with jax.default_matmul_precision("highest"):
                return jax.vjp(lambda p, x: f(p, x, *rest), p, x)[1](g)

        def fwd(p, x, *rest):
            with jax.default_matmul_precision("highest"):
                return f(p, x, *rest)
        return jax.jit(fwd), jax.jit(bwd)

    def head(p, x, targets, seen, scale):
        return _head(cfg, precision, scale, p, x, targets, seen)

    def head_grad(p, x, targets, seen, scale):
        """(loss part, rows, gradient of p, gradient of x)."""
        with jax.default_matmul_precision("highest"):
            (part, rows), grads = jax.value_and_grad(
                head, argnums=(0, 1), has_aux=True)(p, x, targets, seen,
                                                    scale)
        return part, rows, grads[0], grads[1]

    return {
        "dense": pair(lambda p, x: block(cfg, precision, False, x, p)),
        "expert": pair(lambda p, x: block(cfg, precision, True, x, p)),
        "mtp_in": pair(lambda p, x, labels: _mtp_in(cfg, precision, p, x,
                                                    labels)),
        "head": jax.jit(head_grad),
        "embed_grad": jax.jit(lambda p, ids, g: jax.vjp(
            lambda p: _embed(p, ids), p)[1](g)[0]),
    }


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


def _into(grads, new):
    """``new`` (a dict of gradients) added into ``grads`` leaf by leaf."""
    for k, v in new.items():
        grads[k] = v if k not in grads else _add(grads[k], v)


def hidden_states(cfg, precision, params, ids):
    """The residual stream in front of every block, and after the last."""
    st = _stages(json.dumps(cfg, sort_keys=True), precision)
    x = _embed(params, ids)
    seen = [x]
    for l in range(cfg["num_hidden_layers"]):
        experts = _is_expert_layer(cfg, l)
        x = st["expert" if experts else "dense"][0](
            _block_params(cfg, params, "l%d_" % l, experts), x)
        seen.append(x)
    return seen


def _rows_loss_and_grad(cfg, precision, params, ids, labels, tokens,
                        mtp_positions):
    """(loss part, gradients, each row's mean main cross-entropy) of one
    block of rows: the stages forward, then backward in reverse."""
    st = _stages(json.dumps(cfg, sort_keys=True), precision)
    mtp = bool(cfg["num_nextn_predict_layers"]) \
        and cfg.get("fault") != "no_mtp"
    xs = hidden_states(cfg, precision, params, ids)
    last = xs[-1]
    ones = jnp.ones(labels.shape, jnp.float32)
    grads = {}
    # the main head
    loss, rows, g_head, g_last = st["head"](
        {"gamma": params["final_ln_gamma"],
         "lm_head_weight": params["lm_head_weight"]},
        last, labels, ones, 1.0 / tokens)
    _into(grads, {"final_ln_gamma": g_head["gamma"],
                  "lm_head_weight": g_head["lm_head_weight"]})
    if mtp:
        # target t_{i+2}: the label of the next position; a row's last
        # position has none and is left out of the mean
        targets = jnp.concatenate([labels[:, 1:], labels[:, :1]], axis=1)
        seen = ones.at[:, -1].set(0.0)
        p_in = {k: params[k] for k in _MTP_IN}
        p_blk = _block_params(cfg, params, MTP)
        u = st["mtp_in"][0](p_in, last, labels)
        z = st["expert"][0](p_blk, u)
        part, _, g_head, g_z = st["head"](
            {"gamma": params[MTP + "final_ln_gamma"],
             "lm_head_weight": params["lm_head_weight"]},
            z, targets, seen, cfg["mtp_loss_weight"] / mtp_positions)
        loss = loss + part
        _into(grads, {MTP + "final_ln_gamma": g_head["gamma"],
                      "lm_head_weight": g_head["lm_head_weight"]})
        g_blk, g_u = st["expert"][1](p_blk, u, g_z)
        _into(grads, _renamed(g_blk, MTP))
        g_in, g_last2 = st["mtp_in"][1](p_in, last, g_u, labels)
        _into(grads, g_in)
        g_last = g_last + g_last2
    g = g_last
    for l in reversed(range(cfg["num_hidden_layers"])):
        experts = _is_expert_layer(cfg, l)
        prefix = "l%d_" % l
        g_blk, g = st["expert" if experts else "dense"][1](
            _block_params(cfg, params, prefix, experts), xs[l], g)
        _into(grads, _renamed(g_blk, prefix))
        xs[l + 1] = None
    _into(grads, st["embed_grad"](
        {"tok_embedding_weight": params["tok_embedding_weight"]}, ids, g))
    for k, v in params.items():     # a fault's unused leaves: nought
        if k not in grads:
            grads[k] = jnp.zeros_like(v)
    return loss, grads, rows


def loss_and_grad(cfg, params, batch, precision="float32", rows=1):
    """(loss, its gradient, every row's mean main cross-entropy),
    accumulated over blocks of ``rows`` rows."""
    ids, labels = batch
    n, t = ids.shape
    rows = min(rows, n)
    if n % rows:
        raise ValueError("batch %d is not whole blocks of %d rows" % (n, rows))
    loss, grads, per_row = None, None, []
    for r in range(0, n, rows):
        l, g, rl = _rows_loss_and_grad(
            cfg, precision, params, ids[r:r + rows], labels[r:r + rows],
            float(ids.size), float(n * (t - 1)))
        loss = l if loss is None else loss + l
        if grads is None:
            grads = g
        else:
            _into(grads, g)
        per_row.append(rl)
    return loss, grads, jnp.concatenate(per_row)


# ----------------------------------------------------- shapes -> operations
def expert_rows(cfg, wl):
    """Rows a step's tokens are EXPECTED to send to the experts held here,
    one layer: ``tokens x k x held / experts`` (even routing; a run's own
    count swings around it with the seed)."""
    return (wl["batch"] * wl["seq_len"] * cfg["num_experts_per_tok"]
            * cfg["num_experts_held"] / cfg["n_routed_experts"])


def causal_pairs(t):
    """(query, key) pairs one head's causal mask admits over ``t``
    positions."""
    return t * (t + 1) // 2


def _attention_layers(cfg):
    """The blocks that attend: the layers and the prediction module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def fwd_flops(cfg, wl):
    """Operations one forward pass requires on one batch: two per
    multiply-add of every matmul; the score and value products over the
    pairs the causal mask admits, not over the square; the routed experts'
    products at the EXPECTED rows (``expert_rows``), not at the ``tokens x
    held`` rows the program computes; norms, rotations, gates and the
    embedding's lookups cost none."""
    d, h, rq, rkv, dn, dr, dv = _dims(cfg)
    f, fe, v = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["vocab_size"])
    b, t = wl["batch"], wl["seq_len"]
    mtp = cfg["num_nextn_predict_layers"]
    attn_w = (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
              + rkv * h * (dn + dv) + h * dv * d)
    expert_w = d * cfg["n_routed_experts"] \
        + 3 * d * fe * cfg["n_shared_experts"]
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense + mtp
    per_token = (_attention_layers(cfg) * attn_w + dense * 3 * d * f
                 + sparse * expert_w + (1 + mtp) * d * v + mtp * 2 * d * d)
    attn = _attention_layers(cfg) * 2 * b * causal_pairs(t) * h \
        * (dn + dr + dv)
    experts = sparse * 2 * expert_rows(cfg, wl) * 3 * d * fe
    return 2 * per_token * b * t + attn + experts


def step_flops(cfg, wl):
    """Forward + backward: every product has a data and a weight gradient."""
    return 3 * fwd_flops(cfg, wl)


def mla_attention_flops(cfg, wl):
    """What the latent layers' score and value products REQUIRE of one
    step, whatever implements them: the causal pairs a head, two products
    forward and four backward, two operations a multiply-add, over heads of
    ``d_n + d_r`` (scores) and ``d_v`` (values)."""
    _, h, _, _, dn, dr, dv = _dims(cfg)
    return _attention_layers(cfg) * 3 * 2 * wl["batch"] \
        * causal_pairs(wl["seq_len"]) * h * (dn + dr + dv)


def mla_attention_bytes(cfg, wl):
    """Bytes those products must move: q, k and their gradients over heads
    of ``d_n + d_r``, v, o and theirs over ``d_v``, bfloat16, each in or out
    once."""
    _, h, _, _, dn, dr, dv = _dims(cfg)
    rows = wl["batch"] * wl["seq_len"] * h * 2
    return _attention_layers(cfg) * rows * 4 * (dn + dr + dv)
