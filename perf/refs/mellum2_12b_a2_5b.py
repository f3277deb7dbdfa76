"""Plain reference of the decoder the ``mellum2_12b_a2_5b`` configuration
states (JetBrains Mellum2-12B-A2.5B-Instruct, ``model_type`` ``mellum``),
cut to one chip's share as the configuration's file says: the layers it
keeps, the ``num_experts_held`` experts from ``expert_offset`` of every layer
(the router still scores all ``num_experts``), the first ``vocab_size`` rows
of the vocabulary.

float32 ``jax.numpy``, matmuls at ``highest`` precision, no kernel, no cache,
nothing imported from the program.  The layers (``d`` = ``hidden_size`` 2304,
``eps`` = ``rms_norm_eps``, no bias anywhere, every weight (out, in):
``y = x W^T``):

    RMSNorm(x; g) = g * x / sqrt(mean(x^2) + eps)

    block l:  h = x + Attn_l(RMSNorm(x; g1));  y = h + MoE(RMSNorm(h; g2))

    Attn_l:
      q = x W_q^T as ``num_attention_heads`` (32) heads of ``head_dim`` (128:
      W_q of [4096, 2304]), k = x W_k^T, v = x W_v^T as
      ``num_key_value_heads`` (4) heads ([512, 2304]);  rotary positions on q
      and k in the rotate-half pairing, with the frequencies of the layer's
      kind;  query head i attends key/value head i // 8;  scores
      q k^T / sqrt(head_dim);  position t sees position s when s <= t
      (``full_attention``) or t - ``sliding_window`` < s <= t
      (``sliding_attention``: 1024 keys, its own among them);  softmax;
      times v;  W_o of [2304, 4096]

    frequencies, i = 0 .. head_dim/2 - 1, ``theta`` = ``rope_theta``:
      ``rope_type`` ``default`` (the sliding layers):
        f_i = theta^(-2i / head_dim), cos and sin as they are
      ``rope_type`` ``yarn`` (the full layers; ``factor``,
      ``original_max_position_embeddings`` P, ``beta_fast``, ``beta_slow``,
      ``attention_factor``):
        c(b) = head_dim * ln(P / (2 pi b)) / (2 ln theta)
        low = max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)),
        head_dim - 1)                       (18 and 35 at the published sizes)
        ramp_i = clip((i - low) / (high - low), 0, 1)
        f_i = theta^(-2i/head_dim) / factor * ramp_i
              + theta^(-2i/head_dim) * (1 - ramp_i)
        cos and sin are both multiplied by attention_factor
      (static: the same at every length)

    MoE (``num_experts`` E 64, ``num_experts_per_tok`` k 8,
    ``norm_topk_prob``, no bias, no shared expert, no scaling factor):
      p = softmax(x W_g^T) over all E in float32, W_g of [E, d]
      sel = top_k(p);  w_e = p_e / sum_{e in sel} p_e
      y = sum_{e in sel, e held here} w_e * D_e (silu(G_e x) * U_e x)
      G_e, U_e of [``moe_intermediate_size``, d], D_e the transpose's shape

    model:  token embedding, the blocks, a final RMSNorm, an output head with
    its own matrix (``tie_word_embeddings`` false), mean cross-entropy over
    the tokens.

The experts' weights are stacked (expert, out, in).  Every held expert is
computed on every token and weighted by a mask.  At 4,096 positions a layer's
float32 scores are 2.1 GB, so attention is computed one key/value group (8
query heads) at a time, each under ``jax.checkpoint``.  ``cfg["fault"]``
plants one fault of a mechanism (``perf/tests``): ``no_window`` (sliding
layers see every earlier position), ``plain_rope`` (full layers turn by the
default frequencies and no attention factor), ``top7`` (one expert fewer a
token), ``drop_expert`` (the busiest held expert's output left out).
"""
import functools
import json
import math

import jax
import jax.numpy as jnp

from . import common
# the seeded rotation of (ids, next ids) drawn from the ids bfloat16 holds
# exactly: the same generator as the other language cells'
from .gpt2_medium import exact_ids, make_batches  # noqa: F401

FAULTS = ("no_window", "plain_rope", "top7", "drop_expert")


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def param_spec(cfg):
    """[(name, shape, init, served dtype)] in the program's own order."""
    d, h, kv, hd = _dims(cfg)
    fe, e, held = (cfg["moe_intermediate_size"], cfg["num_experts"],
                   cfg["num_experts_held"])
    spec = [("tok_embedding_weight", (cfg["vocab_size"], d), 0.02,
             "bfloat16")]
    for l in range(len(cfg["layer_types"])):
        p = "l%d_" % l
        spec += [(p + "ln1_gamma", (d,), "ones", "float32"),
                 (p + "attn_query_weight", (h * hd, d), 0.02, "bfloat16"),
                 (p + "attn_key_weight", (kv * hd, d), 0.02, "bfloat16"),
                 (p + "attn_value_weight", (kv * hd, d), 0.02, "bfloat16"),
                 (p + "attn_out_proj_weight", (d, h * hd), 0.02, "bfloat16"),
                 (p + "ln2_gamma", (d,), "ones", "float32"),
                 (p + "moe_router_weight", (e, d), 0.02, "float32"),
                 (p + "moe_expert_gate_weight", (held, fe, d), 0.02,
                  "bfloat16"),
                 (p + "moe_expert_up_weight", (held, fe, d), 0.02,
                  "bfloat16"),
                 (p + "moe_expert_down_weight", (held, d, fe), 0.02,
                  "bfloat16")]
    spec += [("final_ln_gamma", (d,), "ones", "float32"),
             ("lm_head_weight", (cfg["vocab_size"], d), 0.02, "bfloat16")]
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


def frequencies(hd, rope):
    """(the head's ``hd / 2`` frequencies, the factor on cos and sin) of one
    section of ``rope_parameters``."""
    theta = float(rope["rope_theta"])
    plain = [theta ** (-2.0 * i / hd) for i in range(hd // 2)]
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError("no equations for rope_type %r"
                         % (rope["rope_type"],))

    def c(b):
        return hd * math.log(rope["original_max_position_embeddings"]
                             / (2 * math.pi * b)) / (2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), hd - 1)
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f / rope["factor"] * ramp + f * (1.0 - ramp))
    return out, float(rope["attention_factor"])


def _rotary(x, freqs, factor):
    """[B,H,T,hd], rotate-half pairing."""
    t, hd = x.shape[-2:]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * factor
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rot * sin


def _rope_of(cfg, kind):
    if kind == "full_attention" and cfg.get("fault") == "plain_rope":
        return {"rope_type": "default",
                "rope_theta": cfg["rope_parameters"][kind]["rope_theta"]}
    return cfg["rope_parameters"][kind]


def _window_of(cfg, kind):
    if kind == "sliding_attention" and cfg.get("fault") != "no_window":
        return cfg["sliding_window"]
    return None


def _group_attention(window, precision, q, k, v):
    """softmax(q k^T / sqrt(hd)) v of the query heads [B,G,T,hd] that share
    one key/value head [B,1,T,hd], under the causal mask and the window."""
    t, hd = q.shape[-2:]
    s = common.result(jnp.einsum(
        "bhqd,bhkd->bhqk", common.operand(q, precision),
        common.operand(k, precision)), precision) / hd ** 0.5
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]   # query - key
    keep = ahead >= 0
    if window is not None:
        keep = keep & (ahead < window)
    pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return common.result(jnp.einsum(
        "bhqk,bhkd->bhqd", common.operand(pr, precision),
        common.operand(v, precision)), precision)


def _attention(cfg, precision, kind, x, p):
    d, h, kv, hd = _dims(cfg)
    bsz, t, _ = x.shape

    def heads(w, n):
        return _linear(x, w, precision).reshape(bsz, t, n, hd) \
            .transpose(0, 2, 1, 3)

    q = heads(p["attn_query_weight"], h)
    k = heads(p["attn_key_weight"], kv)
    v = heads(p["attn_value_weight"], kv)
    freqs, factor = frequencies(hd, _rope_of(cfg, kind))
    q, k = _rotary(q, freqs, factor), _rotary(k, freqs, factor)
    group = jax.checkpoint(functools.partial(
        _group_attention, _window_of(cfg, kind), precision))
    per = h // kv
    o = jnp.concatenate(
        [group(q[:, g * per:(g + 1) * per], k[:, g:g + 1], v[:, g:g + 1])
         for g in range(kv)], axis=1)
    o = o.transpose(0, 2, 1, 3).reshape(bsz, t, h * hd)
    return _linear(o, p["attn_out_proj_weight"], precision)


def route(cfg, x, router_weight):
    """(sel [.., k] expert ids, w [.., k] weights) of the tokens ``x``:
    float32 in every precision, as the program's router is."""
    k = cfg["num_experts_per_tok"] - (cfg.get("fault") == "top7")
    p = jax.nn.softmax(jnp.matmul(x, router_weight.T), axis=-1)
    w, sel = jax.lax.top_k(p, k)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, w


def _experts(cfg, precision, x, p):
    """The held experts' part of the layer's result: every held expert on
    every token, weighted by the token's weight for it (nought where the
    token did not select it)."""
    e, held, off = (cfg["num_experts"], cfg["num_experts_held"],
                    cfg["expert_offset"])
    sel, w = route(cfg, x, p["moe_router_weight"])
    weight_of = jnp.sum(jax.nn.one_hot(sel, e, dtype=x.dtype)
                        * w[..., None], axis=-2)                # [B,T,E]
    mine = weight_of[..., off:off + held]                       # [B,T,held]
    if cfg.get("fault") == "drop_expert":     # the busiest held expert's
        busiest = jnp.argmax(jnp.sum(mine > 0, axis=(0, 1)))
        mine = mine * (jnp.arange(held) != busiest)
    y = jnp.zeros_like(x)
    for i in range(held):
        g = _linear(x, p["moe_expert_gate_weight"][i], precision)
        u = _linear(x, p["moe_expert_up_weight"][i], precision)
        out = _linear(jax.nn.silu(g) * u, p["moe_expert_down_weight"][i],
                      precision)
        y = y + mine[..., i, None] * out
    return y


def _mixed(cfg, precision, l, x, p):
    """(h, RMSNorm(h)): the stream after the block's attention, and what its
    router and experts see."""
    eps = cfg["rms_norm_eps"]
    h = x + _attention(cfg, precision, cfg["layer_types"][l],
                       _rms(x, p["ln1_gamma"], eps), p)
    return h, _rms(h, p["ln2_gamma"], eps)


def _block(cfg, precision, l, x, p):
    h, f = _mixed(cfg, precision, l, x, p)
    return h + _experts(cfg, precision, f, p)


def _layer_params(params, l):
    pre = "l%d_" % l
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def hidden_states(cfg, precision, params, ids):
    """The residual stream in front of every block, and after the last."""
    x = params["tok_embedding_weight"][ids.astype(jnp.int32)]
    seen = [x]
    for l in range(len(cfg["layer_types"])):
        x = jax.checkpoint(functools.partial(_block, cfg, precision, l))(
            x, _layer_params(params, l))
        seen.append(x)
    return seen


def _summed_loss(cfg, precision, params, ids, labels):
    """(sum over the block's tokens of the cross-entropy, each row's mean)."""
    x = hidden_states(cfg, precision, params, ids)[-1]
    x = _rms(x, params["final_ln_gamma"], cfg["rms_norm_eps"])
    logits = _linear(x, params["lm_head_weight"], precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, labels.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked), jnp.mean(lse - picked, axis=-1)


@functools.lru_cache(maxsize=None)
def _part(cfg_json, precision, tokens):
    """The jitted (loss, gradient) of one block of rows, traced once."""
    cfg = json.loads(cfg_json)

    @jax.jit
    def part(params, ids, labels):
        with jax.default_matmul_precision("highest"):
            def scaled(p):
                total, rows = _summed_loss(cfg, precision, p, ids, labels)
                return total / tokens, rows
            (loss, rows), grads = jax.value_and_grad(
                scaled, has_aux=True)(params)
            return loss, grads, rows

    return part


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


def loss_and_grad(cfg, params, batch, precision="float32", rows=1):
    """(mean loss over the batch's tokens, its gradient, every row's mean
    loss), accumulated over blocks of ``rows`` rows."""
    ids, labels = batch
    n = ids.shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError("batch %d is not whole blocks of %d rows" % (n, rows))
    part = _part(json.dumps(cfg, sort_keys=True), precision, float(ids.size))
    loss, grads, per_row = None, None, []
    for r in range(0, n, rows):
        l, g, rl = part(params, ids[r:r + rows], labels[r:r + rows])
        loss = l if loss is None else loss + l
        grads = g if grads is None else _add(grads, g)
        per_row.append(rl)
    return loss, grads, jnp.concatenate(per_row)


# ----------------------------------------------------- shapes -> operations
def expert_rows(cfg, wl):
    """Rows a step's tokens are EXPECTED to send to the experts held here,
    one layer: ``tokens x k x held / experts`` (even routing; a run's own
    count swings around it with the seed)."""
    return (wl["batch"] * wl["seq_len"] * cfg["num_experts_per_tok"]
            * cfg["num_experts_held"] / cfg["num_experts"])


def seen_pairs(t, window=None):
    """(query, key) pairs one head's mask admits over ``t`` positions:
    ``t (t + 1) / 2`` under the causal mask, ``min(s + 1, window)`` keys for
    query ``s`` under a window."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def fwd_flops(cfg, wl):
    """Operations one forward pass requires on one batch: two per
    multiply-add of every matmul; the score and value products over the
    pairs the layer's mask admits (``seen_pairs``), not over the square; the
    experts' products at the EXPECTED rows (``expert_rows``), not at the
    ``tokens x held`` rows the program computes; norms, rotations and gates
    cost none."""
    d, h, kv, hd = _dims(cfg)
    fe, v = cfg["moe_intermediate_size"], cfg["vocab_size"]
    b, t = wl["batch"], wl["seq_len"]
    per_token, pairs = d * v, 0
    for kind in cfg["layer_types"]:
        per_token += 2 * d * h * hd + 2 * d * kv * hd + d * cfg["num_experts"]
        pairs += seen_pairs(t, cfg["sliding_window"]
                            if kind == "sliding_attention" else None)
    attn = 2 * 2 * b * pairs * h * hd           # 2 products x 2 ops
    experts = len(cfg["layer_types"]) * 2 * expert_rows(cfg, wl) * 3 * d * fe
    return 2 * per_token * b * t + attn + experts


def step_flops(cfg, wl):
    """Forward + backward: every product has a data and a weight gradient."""
    return 3 * fwd_flops(cfg, wl)


def _sliding_layers(cfg):
    return sum(k == "sliding_attention" for k in cfg["layer_types"])


def window_attention_flops(cfg, wl):
    """What the sliding layers' score and value products REQUIRE of one
    step, whatever implements them: the in-window pairs a head, two products
    forward and four backward, two operations a multiply-add."""
    _, h, _, hd = _dims(cfg)
    pairs = seen_pairs(wl["seq_len"], cfg["sliding_window"])
    return _sliding_layers(cfg) * 6 * 2 * wl["batch"] * pairs * h * hd


def window_attention_bytes(cfg, wl):
    """Bytes those products must move: q, o and their gradients over the
    query heads, k, v and theirs over the key/value heads, bfloat16, each in
    or out once."""
    _, h, kv, hd = _dims(cfg)
    rows = wl["batch"] * wl["seq_len"] * hd * 2
    return _sliding_layers(cfg) * (4 * h + 4 * kv) * rows
