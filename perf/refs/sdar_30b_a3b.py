"""Plain reference of the model the ``sdar_30b_a3b`` configuration states
(JetLM SDAR-30B-A3B-Chat, ``model_type`` ``sdar_moe``: the Qwen3-MoE layer
trained by block diffusion), cut to one chip's share as the configuration's
file says: the layers it keeps, the ``num_experts_held`` experts from
``expert_offset`` of every layer (the router still scores all
``num_experts``), the first ``vocab_size`` rows of the vocabulary.

float32 ``jax.numpy``, matmuls at ``highest`` precision, no kernel, no cache,
nothing of the program but the noising function the batches are drawn
through (``mxnet_tpu.io.block_diffusion_noise``, which the program's users
train from).  With ``d`` = ``hidden_size`` 2048, ``eps`` = ``rms_norm_eps``,
no bias anywhere, every weight (out, in): ``y = x W^T``, L the document's
length, b = ``block_length`` and N = 2L:

    RMSNorm(x; g) = g * x / sqrt(mean(x^2) + eps)

    input: x_0 (L ids), x_t = x_0 with ``mask_token_id`` at m positions of
      every block of b, m ~ U{1..b} (``make_batches``)
    h_0 = [E[x_t]; E[x_0]]                    (N, d); positions p_i = i mod L
    block l:  a = h + Attn_l(RMSNorm(h; g1));  h' = a + MoE_l(RMSNorm(a; g2))
    logits = RMSNorm(h_last[0:L]; g_f) W_head^T     the noised half, V rows
    loss = (1/L) sum_{i < L, x_t[i] = MASK} (b / m_{block(i)}) CE(logits_i,
      x_0[i])

    Attn_l: q = x W_q^T as ``num_attention_heads`` (32) heads of ``head_dim``
      (128), k = x W_k^T, v = x W_v^T as ``num_key_value_heads`` (4); q and
      k each through RMSNorm over the head (gains g_q, g_k of 128), then
      rotary positions p (rotate-half, theta^(-2i/128), theta
      ``rope_theta``); query head h reads key/value head h // 8; scores
      q k^T / sqrt(128) + M, softmax, times v; W_o of [d, 4096].  With
      n(i) = i < L and blk(i) = (i mod L) // b, M[i, j] = 0 where
        n(i) and n(j) and blk(j) = blk(i), or
        n(i) and not n(j) and blk(j) < blk(i), or
        not n(i) and not n(j) and blk(j) <= blk(i)
      and -inf elsewhere.

    MoE (E = ``num_experts`` 128, k = ``num_experts_per_tok`` 8,
    ``norm_topk_prob``, no shared expert):
      s = softmax(x W_r^T) over all E in float32
      sel = top_k(s);  w_e = s_e / sum_{e in sel} s_e
      y = sum_{e in sel, e held here} w_e D_e (silu(G_e x) * U_e x),
      experts ``moe_intermediate_size`` (768) wide

Attention is taken one key/value group (8 query heads) and ``Q_BLOCK``
queries at a time over all N keys under the mask of those rows, each under
``jax.checkpoint``: a layer's float32 scores at N 8,192 are 8.6 GB.  Each
held expert is computed over the tokens routed to it alone (gathered,
then added back into their rows).  **Loss and gradients are jax's own,
taken stage by stage** (the embedding, each block, the head), each stage a
jitted call that computes its forward again, as
``perf/refs/glm_4_7_flash.py`` does: ``perf/refs/train.py`` holds 20 bytes a
parameter beside it.  ``cfg["fault"]`` plants one fault of a mechanism
(``perf/tests``): ``causal_mask`` (plain causal attention over the N
positions), ``no_clean_context`` (noised rows see no clean key),
``noisy_all`` (noised rows see every earlier noised block too),
``clean_positions_shifted`` (the clean copy at positions L .. 2L - 1),
``no_time_weight`` (weight 1 in place of b / m), ``loss_on_all`` (the
unmasked positions of the noised half counted too, at their block's
b / m), ``no_qk_norm``, ``top7`` (one expert fewer a token).
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .gpt2_medium import exact_ids

FAULTS = ("causal_mask", "no_clean_context", "noisy_all",
          "clean_positions_shifted", "no_time_weight", "loss_on_all",
          "no_qk_norm", "top7")
Q_BLOCK = 512
HEAD_ROWS = 1024


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def _block_spec(cfg, p):
    d, h, kv, hd = _dims(cfg)
    fe, e, held = (cfg["moe_intermediate_size"], cfg["num_experts"],
                   cfg["num_experts_held"])
    return [(p + "ln1_gamma", (d,), "ones", "float32"),
            (p + "attn_query_weight", (h * hd, d), 0.02, "bfloat16"),
            (p + "attn_key_weight", (kv * hd, d), 0.02, "bfloat16"),
            (p + "attn_value_weight", (kv * hd, d), 0.02, "bfloat16"),
            (p + "attn_out_proj_weight", (d, h * hd), 0.02, "bfloat16"),
            (p + "attn_q_norm_gamma", (hd,), "ones", "float32"),
            (p + "attn_k_norm_gamma", (hd,), "ones", "float32"),
            (p + "ln2_gamma", (d,), "ones", "float32"),
            (p + "moe_router_weight", (e, d), 0.02, "float32"),
            (p + "moe_expert_gate_weight", (held, fe, d), 0.02, "bfloat16"),
            (p + "moe_expert_up_weight", (held, fe, d), 0.02, "bfloat16"),
            (p + "moe_expert_down_weight", (held, d, fe), 0.02, "bfloat16")]


def param_spec(cfg):
    """[(name, shape, init, served dtype)] in the program's own order."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    spec = [("tok_embedding_weight", (v, d), 0.02, "bfloat16")]
    for l in range(cfg["num_hidden_layers"]):
        spec += _block_spec(cfg, "l%d_" % l)
    return spec + [("final_ln_gamma", (d,), "ones", "float32"),
                   ("lm_head_weight", (v, d), 0.02, "bfloat16")]


def init_params(cfg, seed):
    """The weights as served, from the seed (``common.init_from_spec``)."""
    return common.init_from_spec(param_spec(cfg), seed)


def make_batches(cfg, wl, seed):
    """The seeded rotation: ``wl["rotation"]`` batches of (x_t, x_0), float32
    holding whole numbers as the program's iterator hands them.  x_0 is
    drawn from the ids below ``vocab_size`` that bfloat16 holds exactly,
    the mask id left out; x_t is x_0 noised by ``block_diffusion_noise``
    under the seed ``[seed, batch]``, as ``mxnet_tpu.io.BlockDiffusionIter``
    draws it."""
    from mxnet_tpu.io import block_diffusion_noise
    n, b, t = wl["rotation"], wl["batch"], wl["seq_len"]
    mask = cfg["mask_token_id"]
    pool = exact_ids(cfg["vocab_size"])
    pool = pool[pool != mask]

    @jax.jit
    def make(key):
        return pool[jax.random.randint(key, (n, b, t), 0, pool.shape[0])]

    x0 = np.asarray(make(jax.random.fold_in(common.key_from_seed(seed),
                                            7919)), np.float32)
    return [(jnp.asarray(block_diffusion_noise(x0[i], cfg["block_length"],
                                               mask, [int(seed), i])),
             jnp.asarray(x0[i])) for i in range(n)]


# ------------------------------------------------------------------ layers
def _rms(x, g, eps):
    return g * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps)


def _linear(x, w, precision):
    """``x @ w.T``, w of (out, in)."""
    return common.result(jnp.matmul(common.operand(x, precision),
                                    common.operand(w, precision).T), precision)


def _rotary(x, theta, pos):
    """[.., N, hd] turned by the positions ``pos`` [N], rotate-half."""
    hd = x.shape[-1]
    freqs = jnp.asarray([theta ** (-2.0 * i / hd) for i in range(hd // 2)],
                        jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rot * sin


def visible(cfg, rows, cols, half):
    """M's zeros: [len(rows), len(cols)] bool, query positions ``rows``
    against key positions ``cols`` of the 2 ``half`` (the fault's mask where
    one is planted)."""
    fault, b = cfg.get("fault"), cfg["block_length"]
    i, j = rows[:, None], cols[None, :]
    if fault == "causal_mask":
        return j <= i
    ni, nj = i < half, j < half
    bi, bj = (i % half) // b, (j % half) // b
    own = bj <= bi if fault == "noisy_all" else bj == bi
    before = jnp.zeros_like(ni) if fault == "no_clean_context" else bj < bi
    return (ni & nj & own) | (ni & ~nj & before) | (~ni & ~nj & (bj <= bi))


def _query_block(cfg, precision, lo, q, k, v):
    """softmax(q k^T / sqrt(hd) + M) v of the query heads [B,G,n,hd] from
    position ``lo`` that share one key/value head [B,1,N,hd]."""
    n, hd = q.shape[-2:]
    t = k.shape[-2]
    s = common.result(jnp.einsum(
        "bhqd,bhkd->bhqk", common.operand(q, precision),
        common.operand(k, precision)), precision) / hd ** 0.5
    keep = visible(cfg, lo + jnp.arange(n), jnp.arange(t), t // 2)
    pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return common.result(jnp.einsum(
        "bhqk,bhkd->bhqd", common.operand(pr, precision),
        common.operand(v, precision)), precision)


def _attention(cfg, precision, x, p):
    d, h, kv, hd = _dims(cfg)
    bsz, t, _ = x.shape
    eps = cfg["rms_norm_eps"]

    def heads(w, n):
        return _linear(x, w, precision).reshape(bsz, t, n, hd) \
            .transpose(0, 2, 1, 3)

    q = heads(p["attn_query_weight"], h)
    k = heads(p["attn_key_weight"], kv)
    v = heads(p["attn_value_weight"], kv)
    if cfg.get("fault") != "no_qk_norm":
        q = _rms(q, p["attn_q_norm_gamma"], eps)
        k = _rms(k, p["attn_k_norm_gamma"], eps)
    pos = jnp.arange(t) if cfg.get("fault") == "clean_positions_shifted" \
        else jnp.arange(t) % (t // 2)
    theta = float(cfg["rope_theta"])
    q, k = _rotary(q, theta, pos), _rotary(k, theta, pos)
    per, n = h // kv, min(Q_BLOCK, t)
    o = jnp.concatenate([
        jnp.concatenate([
            jax.checkpoint(functools.partial(_query_block, cfg, precision,
                                             lo))(
                q[:, g * per:(g + 1) * per, lo:lo + n], k[:, g:g + 1],
                v[:, g:g + 1])
            for lo in range(0, t, n)], axis=2)
        for g in range(kv)], axis=1)
    o = o.transpose(0, 2, 1, 3).reshape(bsz, t, h * hd)
    return _linear(o, p["attn_out_proj_weight"], precision)


def route(cfg, x, router_weight):
    """(sel [.., k] expert ids, w [.., k] weights) of the tokens ``x``:
    float32 in every precision, as the program's router is."""
    k = cfg["num_experts_per_tok"] - (cfg.get("fault") == "top7")
    s = jax.nn.softmax(jnp.matmul(x, router_weight.T), axis=-1)
    w, sel = jax.lax.top_k(s, k)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, w


def _expert(precision, x, gate, up, down):
    g = _linear(x, gate, precision)
    u = _linear(x, up, precision)
    return _linear(jax.nn.silu(g) * u, down, precision)


def experts(cfg, precision, x, p):
    """The held experts' part of the layer's result, each expert over the
    tokens routed to it: their rows gathered (the rest of the gather's
    static size reads a row of zeros), computed, weighted and added back."""
    e, held, off = (cfg["num_experts"], cfg["num_experts_held"],
                    cfg["expert_offset"])
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    n = x.shape[0]
    sel, w = route(cfg, x, p["moe_router_weight"])
    weight_of = jnp.sum(jax.nn.one_hot(sel, e, dtype=x.dtype)
                        * w[..., None], axis=-2)                # [n, E]
    padded = jnp.concatenate([x, jnp.zeros((1, shape[-1]), x.dtype)])
    y = jnp.zeros_like(x)
    for i in range(held):
        w_i = weight_of[:, off + i]
        rows = jnp.nonzero(w_i > 0, size=n, fill_value=n)[0]
        out = jax.checkpoint(functools.partial(_expert, precision))(
            padded[rows], p["moe_expert_gate_weight"][i],
            p["moe_expert_up_weight"][i], p["moe_expert_down_weight"][i])
        y = y.at[rows].add(out * w_i.at[rows].get(mode="fill",
                                                  fill_value=0.0)[:, None],
                           mode="drop")
    return y.reshape(shape)


def block(cfg, precision, x, p):
    """One block on the residual stream; ``p`` by the block's own names."""
    eps = cfg["rms_norm_eps"]
    a = x + _attention(cfg, precision, _rms(x, p["ln1_gamma"], eps), p)
    return a + experts(cfg, precision, _rms(a, p["ln2_gamma"], eps), p)


def _embed(p, ids):
    return p["tok_embedding_weight"][ids.astype(jnp.int32)]


def token_weights(cfg, xt):
    """Each noised position's weight in the loss: b / m on the masked
    positions of a block with m masked, nought elsewhere (the faults'
    weights where one is planted)."""
    b, fault = cfg["block_length"], cfg.get("fault")
    bsz, t = xt.shape
    masked = (xt == cfg["mask_token_id"]).astype(jnp.float32)
    m = jnp.sum(masked.reshape(bsz, t // b, b), -1, keepdims=True)
    per = jnp.broadcast_to(b / jnp.maximum(m, 1.0), (bsz, t // b, b))
    per = per.reshape(bsz, t)
    if fault == "no_time_weight":
        return masked
    if fault == "loss_on_all":
        return per
    return per * masked


def _head_rows(cfg, precision, p, x, targets, weights):
    x = _rms(x, p["final_ln_gamma"], cfg["rms_norm_eps"])
    logits = _linear(x, p["lm_head_weight"], precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, targets.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    return (lse - picked) * weights


def _head(cfg, precision, scale, p, x, targets, weights):
    """(``scale`` x the weighted sum of the noised half's cross-entropies,
    each row's weighted mean), ``HEAD_ROWS`` positions at a time under
    ``jax.checkpoint``."""
    rows = jax.checkpoint(functools.partial(_head_rows, cfg, precision))
    t = targets.shape[1]
    ce = jnp.concatenate(
        [rows(p, x[:, lo:lo + HEAD_ROWS], targets[:, lo:lo + HEAD_ROWS],
              weights[:, lo:lo + HEAD_ROWS])
         for lo in range(0, t, HEAD_ROWS)], axis=1)
    return scale * jnp.sum(ce), jnp.mean(ce, -1)


# ------------------------------------------------- stages, jitted, cached
def _block_params(cfg, params, prefix):
    return {name: params[prefix + name] for name, *_ in _block_spec(cfg, "")}


@functools.lru_cache(maxsize=1)
def _stages(cfg_json, precision):
    """The model's stages as jitted functions; a backward is ``jax.vjp`` of
    the stage's own forward, computed again.  One configuration's at a
    time: a process that reads several (the faults, the control) lets the
    last one's programs go."""
    cfg = json.loads(cfg_json)

    def fwd(p, x):
        with jax.default_matmul_precision("highest"):
            return block(cfg, precision, x, p)

    def bwd(p, x, g):
        with jax.default_matmul_precision("highest"):
            return jax.vjp(lambda p, x: block(cfg, precision, x, p),
                           p, x)[1](g)

    def head_grad(p, x, targets, weights, scale):
        """(loss, rows, gradient of p, gradient of x)."""
        with jax.default_matmul_precision("highest"):
            (loss, rows), grads = jax.value_and_grad(
                lambda p, x: _head(cfg, precision, scale, p, x, targets,
                                   weights),
                argnums=(0, 1), has_aux=True)(p, x)
        return loss, rows, grads[0], grads[1]

    def embed_grad(p, ids, g):
        return jax.vjp(lambda p: _embed(p, ids), p)[1](g)[0]

    return {"block": (jax.jit(fwd), jax.jit(bwd)),
            "head": jax.jit(head_grad), "embed_grad": jax.jit(embed_grad)}


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


def _into(grads, new):
    for k, v in new.items():
        grads[k] = v if k not in grads else _add(grads[k], v)


def _rows_loss_and_grad(cfg, precision, params, xt, x0, tokens):
    """(loss part, gradients, each row's weighted mean) of one block of
    rows: the stages forward, then backward in reverse."""
    st = _stages(json.dumps(cfg, sort_keys=True), precision)
    ids = jnp.concatenate([xt, x0], axis=1)
    xs = [_embed(params, ids)]
    for l in range(cfg["num_hidden_layers"]):
        xs.append(st["block"][0](_block_params(cfg, params, "l%d_" % l),
                                 xs[-1]))
    half = xt.shape[1]
    grads = {}
    loss, rows, g_head, g_noised = st["head"](
        {"final_ln_gamma": params["final_ln_gamma"],
         "lm_head_weight": params["lm_head_weight"]},
        xs[-1][:, :half], x0, token_weights(cfg, xt), 1.0 / tokens)
    _into(grads, g_head)
    g = jnp.concatenate([g_noised, jnp.zeros_like(g_noised)], axis=1)
    for l in reversed(range(cfg["num_hidden_layers"])):
        prefix = "l%d_" % l
        g_blk, g = st["block"][1](_block_params(cfg, params, prefix), xs[l],
                                  g)
        _into(grads, {prefix + k: v for k, v in g_blk.items()})
        xs[l + 1] = None
    _into(grads, st["embed_grad"](
        {"tok_embedding_weight": params["tok_embedding_weight"]}, ids, g))
    return loss, grads, rows


def loss_and_grad(cfg, params, batch, precision="float32", rows=1):
    """(loss, its gradient, every row's weighted mean), accumulated over
    blocks of ``rows`` rows; ``batch`` is (x_t, x_0)."""
    xt, x0 = batch
    n = xt.shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError("batch %d is not whole blocks of %d rows" % (n, rows))
    loss, grads, per_row = None, None, []
    for r in range(0, n, rows):
        l, g, rl = _rows_loss_and_grad(cfg, precision, params,
                                       xt[r:r + rows], x0[r:r + rows],
                                       float(xt.size))
        loss = l if loss is None else loss + l
        if grads is None:
            grads = g
        else:
            _into(grads, g)
        per_row.append(rl)
    return loss, grads, jnp.concatenate(per_row)


# ----------------------------------------------------- shapes -> operations
def expert_rows(cfg, wl):
    """Rows a step's 2L positions are EXPECTED to send to the experts held
    here, one layer: ``positions x k x held / experts`` (even routing)."""
    return (wl["batch"] * 2 * wl["seq_len"] * cfg["num_experts_per_tok"]
            * cfg["num_experts_held"] / cfg["num_experts"])


def visible_pairs(t, b):
    """(query, key) pairs one head's block-diffusion mask admits over the
    2t positions in blocks of b, with n = t / b blocks: a noised row of
    block c sees its own b and c b clean keys, a clean row of block c sees
    (c + 1) b; b^2 n (n + 1) in all (at t 4096, b 4: 16,793,600, about half
    of the causal mask's over 2t)."""
    n = t // b
    return b * b * n * (n + 1)


def fwd_flops(cfg, wl):
    """Operations one forward pass requires on one batch: two per
    multiply-add of every matmul; every layer over the 2L positions, the
    score and value products over the pairs the mask admits
    (``visible_pairs``), the experts' products at the EXPECTED routed rows
    (``expert_rows``), not at the ``positions x held`` rows the program
    computes, the head over the noised half (L positions); norms,
    rotations, gates and the embedding's lookups cost none."""
    d, h, kv, hd = _dims(cfg)
    fe, v = cfg["moe_intermediate_size"], cfg["vocab_size"]
    b, t, n = wl["batch"], wl["seq_len"], cfg["num_hidden_layers"]
    per_position = n * (2 * d * h * hd + 2 * d * kv * hd
                        + d * cfg["num_experts"])
    attn = n * 2 * 2 * b * visible_pairs(t, cfg["block_length"]) * h * hd
    routed = n * 2 * expert_rows(cfg, wl) * 3 * d * fe
    return 2 * per_position * b * 2 * t + 2 * d * v * b * t + attn + routed


def step_flops(cfg, wl):
    """Forward + backward: every product has a data and a weight gradient."""
    return 3 * fwd_flops(cfg, wl)


def bd_attention_flops(cfg, wl):
    """What the attention cores under the block-diffusion mask REQUIRE of
    one step, whatever implements them: the visible pairs a head, the score
    and value products, two forward and four backward, two operations a
    multiply-add."""
    _, h, _, hd = _dims(cfg)
    return cfg["num_hidden_layers"] * 6 * 2 * wl["batch"] \
        * visible_pairs(wl["seq_len"], cfg["block_length"]) * h * hd


def bd_attention_bytes(cfg, wl):
    """Bytes those products must move: q, o and their gradients over the
    query heads, k, v and theirs over the key/value heads, bfloat16, each
    in or out once, over the 2L positions."""
    _, h, kv, hd = _dims(cfg)
    rows = wl["batch"] * 2 * wl["seq_len"] * hd * 2
    return cfg["num_hidden_layers"] * (4 * h + 4 * kv) * rows
