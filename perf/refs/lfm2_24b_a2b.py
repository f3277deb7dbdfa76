"""Plain reference of the hybrid decoder the ``lfm2_24b_a2b`` configuration
states (LiquidAI LFM2-24B-A2B, ``model_type`` ``lfm2_moe``), cut to one
chip's share as the configuration's file says: the layers it keeps, the
``num_experts_held`` experts from ``expert_offset`` of every expert layer
(the router still scores all ``num_experts``), the first ``vocab_size`` rows
of the vocabulary.

float32 ``jax.numpy``, matmuls at ``highest`` precision, no kernel, no cache,
nothing imported from the program.  The layers (``d`` = ``hidden_size``,
``eps`` = ``norm_eps``, no bias anywhere):

    RMSNorm(x; g) = g * x / sqrt(mean(x^2) + eps)

    block l:  h = x + Op_l(RMSNorm(x; g_op));  y = h + FFN_l(RMSNorm(h; g_ffn))
      Op_l  is ``conv`` or ``full_attention`` by ``layer_types[l]``
      FFN_l is dense for l < ``num_dense_layers``, else the expert layer

    conv (gated short convolution):
      [B, C, X] = split3(x W_in^T), W_in of [3d, d];  u = B * X
      c_t = sum_{j=0..L-1} k_j * u_{t-(L-1)+j}, u zero before position 0
            (depthwise, causal, k of [d, L], L = ``conv_L_cache``)
      out = (C * c) W_out^T, W_out of [d, d]

    full_attention:
      q = x W_q^T (``num_attention_heads`` heads of d / heads), k = x W_k^T,
      v = x W_v^T (``num_key_value_heads`` heads);  per head
      q <- RMSNorm(q; g_q), k <- RMSNorm(k; g_k) over the head;  rotary
      positions on q and k (``rope_theta``, the rotate-half pairing);  query
      head i attends to key/value head i // (heads / kv heads);  causal
      softmax(q k^T / sqrt(head)) v;  W_o

    dense feed-forward:  W_2 (silu(W_1 x) * W_3 x), ``intermediate_size`` wide
    expert:              the same, ``moe_intermediate_size`` wide

    expert layer (``num_experts`` E, ``num_experts_per_tok`` k,
    ``use_expert_bias``, ``norm_topk_prob``, ``routed_scaling_factor`` 1, no
    shared expert):
      s = sigmoid(x W_g^T) in float32, W_g of [E, d]
      sel = top_k(s + b), b the expert bias (a buffer: no gradient)
      w_e = s_e / (sum_{e in sel} s_e + 1e-6) for e in sel
      y = sum_{e in sel, e held here} w_e Expert_e(x)

    model:  token embedding, the blocks, a final RMSNorm, logits on the
    embedding's own rows (tied), mean cross-entropy over the tokens.

Every weight is (out, in) (``y = x @ W^T``), the experts' stacked (expert,
out, in).  Every held expert is computed on every token and weighted by a
mask.  ``cfg["fault"]`` plants one of the two
faults of the mechanism (``perf/tests``): ``drop_expert`` leaves the busiest held
expert's output out, ``top3`` selects one expert fewer a token.
"""
import functools
import json

import jax
import jax.numpy as jnp

from . import common
# the seeded rotation of (ids, next ids) drawn from the ids bfloat16 holds
# exactly: the same generator as the other language cell's
from .gpt2_medium import exact_ids, make_batches  # noqa: F401

FAULTS = ("drop_expert", "top3")


def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, h, cfg["num_key_value_heads"], d // h


def _is_expert_layer(cfg, l):
    return l >= cfg["num_dense_layers"]


def param_spec(cfg):
    """[(name, shape, init, served dtype)] in the program's own order."""
    d, h, kv, hd = _dims(cfg)
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e, held, taps = (cfg["num_experts"], cfg["num_experts_held"],
                     cfg["conv_L_cache"])
    spec = [("tok_embedding_weight", (cfg["vocab_size"], d), 0.02,
             "bfloat16")]
    for l, kind in enumerate(cfg["layer_types"]):
        p = "l%d_" % l
        spec.append((p + "ln1_gamma", (d,), "ones", "float32"))
        if kind == "conv":
            spec += [(p + "conv_in_proj_weight", (3 * d, d), 0.02, "bfloat16"),
                     (p + "conv_conv_weight", (d, taps), 0.3, "bfloat16"),
                     (p + "conv_out_proj_weight", (d, d), 0.02, "bfloat16")]
        else:
            spec += [(p + "attn_query_weight", (d, d), 0.02, "bfloat16"),
                     (p + "attn_key_weight", (kv * hd, d), 0.02, "bfloat16"),
                     (p + "attn_value_weight", (kv * hd, d), 0.02, "bfloat16"),
                     (p + "attn_out_proj_weight", (d, d), 0.02, "bfloat16"),
                     (p + "attn_q_norm_gamma", (hd,), "ones", "float32"),
                     (p + "attn_k_norm_gamma", (hd,), "ones", "float32")]
        spec.append((p + "ln2_gamma", (d,), "ones", "float32"))
        if _is_expert_layer(cfg, l):
            spec += [(p + "moe_router_weight", (e, d), 0.02, "float32"),
                     (p + "moe_expert_bias", (e,), 0.1, "float32"),
                     (p + "moe_expert_gate_weight", (held, fe, d), 0.02,
                      "bfloat16"),
                     (p + "moe_expert_up_weight", (held, fe, d), 0.02,
                      "bfloat16"),
                     (p + "moe_expert_down_weight", (held, d, fe), 0.02,
                      "bfloat16")]
        else:
            spec += [(p + "ffn_gate_weight", (f, d), 0.02, "bfloat16"),
                     (p + "ffn_up_weight", (f, d), 0.02, "bfloat16"),
                     (p + "ffn_down_weight", (d, f), 0.02, "bfloat16")]
    spec.append(("final_ln_gamma", (d,), "ones", "float32"))
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
    """[B,H,T,hd], rotate-half pairing."""
    t, hd = x.shape[-2:]
    inv = theta ** (-jnp.arange(hd // 2, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rot * sin


def _conv(cfg, precision, x, p):
    taps = cfg["conv_L_cache"]
    t = x.shape[1]
    b, c, xx = jnp.split(_linear(x, p["conv_in_proj_weight"], precision), 3,
                         axis=-1)
    u = jnp.pad(b * xx, ((0, 0), (taps - 1, 0), (0, 0)))
    k = p["conv_conv_weight"]
    conv = sum(u[:, j:j + t, :] * k[:, j] for j in range(taps))
    return _linear(c * conv, p["conv_out_proj_weight"], precision)


def _attention(cfg, precision, x, p):
    d, h, kv, hd = _dims(cfg)
    bsz, t, _ = x.shape
    eps = cfg["norm_eps"]

    def heads(w, n):
        return _linear(x, w, precision).reshape(bsz, t, n, hd) \
            .transpose(0, 2, 1, 3)

    q = heads(p["attn_query_weight"], h)
    k = heads(p["attn_key_weight"], kv)
    v = heads(p["attn_value_weight"], kv)
    theta = float(cfg["rope_parameters"]["rope_theta"])
    q = _rotary(_rms(q, p["attn_q_norm_gamma"], eps), theta)
    k = _rotary(_rms(k, p["attn_k_norm_gamma"], eps), theta)
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    s = common.result(jnp.einsum(
        "bhqd,bhkd->bhqk", common.operand(q, precision),
        common.operand(k, precision)), precision) / hd ** 0.5
    keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    o = common.result(jnp.einsum(
        "bhqk,bhkd->bhqd", common.operand(pr, precision),
        common.operand(v, precision)), precision)
    o = o.transpose(0, 2, 1, 3).reshape(bsz, t, d)
    return _linear(o, p["attn_out_proj_weight"], precision)


def _dense_ffn(precision, x, p):
    g = _linear(x, p["ffn_gate_weight"], precision)
    u = _linear(x, p["ffn_up_weight"], precision)
    return _linear(jax.nn.silu(g) * u, p["ffn_down_weight"], precision)


def route(cfg, x, router_weight, expert_bias):
    """(sel [.., k] expert ids, w [.., k] weights) of the tokens ``x``:
    float32 in every precision, as the program's router is."""
    k = cfg["num_experts_per_tok"] - (cfg.get("fault") == "top3")
    s = jax.nn.sigmoid(jnp.matmul(x, router_weight.T))
    _, sel = jax.lax.top_k(s + jax.lax.stop_gradient(expert_bias), k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return sel, w * cfg["routed_scaling_factor"]


def _experts(cfg, precision, x, p):
    """The held experts' part of the layer's result: every held expert on
    every token, weighted by the token's weight for it (nought where the
    token did not select it)."""
    e, held, off = (cfg["num_experts"], cfg["num_experts_held"],
                    cfg["expert_offset"])
    sel, w = route(cfg, x, p["moe_router_weight"], p["moe_expert_bias"])
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
    """(h, RMSNorm(h)): the stream after the block's first half, and what
    its feed-forward (or its router) sees."""
    eps = cfg["norm_eps"]
    a = _rms(x, p["ln1_gamma"], eps)
    mix = _conv if cfg["layer_types"][l] == "conv" else _attention
    h = x + mix(cfg, precision, a, p)
    return h, _rms(h, p["ln2_gamma"], eps)


def _block(cfg, precision, l, x, p):
    h, f = _mixed(cfg, precision, l, x, p)
    if _is_expert_layer(cfg, l):
        return h + _experts(cfg, precision, f, p)
    return h + _dense_ffn(precision, f, p)


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
    x = _rms(x, params["final_ln_gamma"], cfg["norm_eps"])
    logits = _linear(x, params["tok_embedding_weight"], precision)
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
    one expert layer: ``tokens x k x held / experts`` (even routing; a
    run's own count swings around it with the seed)."""
    return (wl["batch"] * wl["seq_len"] * cfg["num_experts_per_tok"]
            * cfg["num_experts_held"] / cfg["num_experts"])


def expert_layers(cfg):
    return sum(_is_expert_layer(cfg, l)
               for l in range(len(cfg["layer_types"])))


def fwd_flops(cfg, wl):
    """Operations one forward pass requires on one batch: two per
    multiply-add of every matmul; the causal score and value products counted
    once (the half of the square that is not masked); the experts' products
    at the EXPECTED rows (``expert_rows``), not at the ``tokens x held``
    rows the program computes; norms and gates cost none."""
    d, h, kv, hd = _dims(cfg)
    f, fe, v = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["vocab_size"])
    b, t = wl["batch"], wl["seq_len"]
    per_token, attn, experts = d * v, 0, 0
    for l, kind in enumerate(cfg["layer_types"]):
        if kind == "conv":
            per_token += 4 * d * d + cfg["conv_L_cache"] * d
        else:
            per_token += 2 * d * d + 2 * d * kv * hd
            attn += 2 * (b * t * t * d)         # 2 products x 2 ops x half
        if _is_expert_layer(cfg, l):
            per_token += d * cfg["num_experts"]
            experts += 2 * expert_rows(cfg, wl) * 3 * d * fe
        else:
            per_token += 3 * d * f
    return 2 * per_token * b * t + attn + experts


def step_flops(cfg, wl):
    """Forward + backward: every product has a data and a weight gradient."""
    return 3 * fwd_flops(cfg, wl)


def moe_expert_flops(cfg, wl):
    """The experts' three products, forward + backward, of every expert
    layer of one step, at the expected rows."""
    return 3 * expert_layers(cfg) * 2 * expert_rows(cfg, wl) * 3 \
        * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_expert_bytes(cfg, wl):
    """Bytes those products must move whatever implements them: the held
    experts' weights (bfloat16) read once in the forward pass and once in
    the backward, their gradient written once, and the expected rows'
    inputs, hidden and outputs in and out once a pass."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_experts_held"] * 3 * d * fe * 2
    rows = expert_rows(cfg, wl) * (2 * d + 3 * fe) * 2
    return expert_layers(cfg) * (3 * weights + 3 * rows)
