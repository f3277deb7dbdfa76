"""Plain reference of the decoder the ``kimi_linear_48b_a3b`` configuration
states (moonshotai Kimi-Linear-48B-A3B-Instruct, ``model_type``
``kimi_linear``), cut to one chip's share as the configuration's file says:
the layers it keeps, the ``num_experts_held`` experts from ``expert_offset``
of every layer with experts (the router still scores all ``num_experts``),
the first ``vocab_size`` rows of the vocabulary.

float32 ``jax.numpy``, matmuls at ``highest`` precision, no kernel, no
chunking, nothing imported from the program.  The layers (``d`` =
``hidden_size`` 2304, ``eps`` = ``rms_norm_eps``, no bias anywhere, every
weight (out, in): ``y = x W^T``; layers numbered from 1 as
``linear_attn_config`` numbers them):

    RMSNorm(x; g) = g * x / sqrt(mean(x^2) + eps)

    block l:  h = x + Mix_l(RMSNorm(x; g1));  y = h + FFN_l(RMSNorm(h; g2))
    Mix_l is KDA in ``kda_layers``, MLA in ``full_attn_layers``.

    KDA (H = 32 heads, dk = dv = 128 = ``linear_attn_config.head_dim``, HD =
    4096, L = ``short_conv_kernel_size`` 4):
      q~, k~, v~ = x Wq^T, x Wk^T, x Wv^T                    W* [HD, d]
      q, k, v = silu(conv(q~; Cq)), silu(conv(k~; Ck)), silu(conv(v~; Cv))
        conv(u; C)_t = sum_{j<L} C[:, j] u_{t-L+1+j}   (depthwise, causal,
                                                        u = 0 before 0)
      q_t, k_t <- each head over sqrt(sum^2 + 1e-6)
      g = -exp(A_log[h]) softplus((x Wfa^T) Wfb^T + dt_bias)   Wfa [dk, d],
                                                   Wfb [HD, dk], one a channel
      beta = sigmoid(x Wb^T)                        Wb [H, d], one a head
      per head, S_0 = 0 (dk x dv, float32), POSITION BY POSITION:
        S'_t = Diag(exp(g_t)) S_{t-1}
        S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
        o_t = S_t^T q_t / sqrt(dk)
      out = [RMSNorm_head(o_t; g_o) sigmoid((x Wga^T) Wgb^T)] Wo^T
        (g_o of dk, one gain every head shares; Wga [dk, d], Wgb [HD, dk])

    MLA (``mla_use_nope``: no positions; H = 32, d_n = ``qk_nope_head_dim``
    128, d_r = ``qk_rope_head_dim`` 64, d_v = ``v_head_dim`` 128, r_kv =
    ``kv_lora_rank`` 512, ``q_lora_rank`` null):
      q = x Wq^T, head i = [q_n_i (d_n); q_r_i (d_r)]      Wq [H (d_n + d_r), d]
      [c (r_kv); k_r (d_r)] = x Wkva^T;  c = RMSNorm(c; g_kv)
      [k_n_i; v_i] = c Wkvb^T                         Wkvb [H (d_n + d_v), r_kv]
      k_i = [k_n_i; k_r]          (ONE k_r every head shares, not turned)
      o_i = causal softmax(q_i k_i^T / sqrt(d_n + d_r)) v_i;  out = [o] Wo^T

    FFN_1 (``first_k_dense_replace`` 1):  Wd (silu(Wg x) * (Wu x)), 9216 wide

    FFN_l, l >= 2 (E = ``num_experts`` 256, k = ``num_experts_per_token`` 8,
    one group, ``moe_renormalize``, ``routed_scaling_factor`` 2.446,
    ``num_shared_experts`` 1):
      s = sigmoid(x Wr^T) over all E in float32;  sel = top_k(s + b)
      (b a buffer of E, no gradient, selection only)
      w_e = 2.446 s_e / (sum_{e in sel} s_e + 1e-20)
      y = Shared(x) + sum_{e in sel, e held here} w_e D_e (silu(G_e x) * U_e x)

    model: token embedding, the blocks, a final RMSNorm, an output head of
    its own, the mean cross-entropy over the tokens; no positional encoding.

The KDA core is the recurrence itself, a ``lax.scan`` over positions (not
the chunked form the program runs), under ``jax.checkpoint`` over blocks of
``KDA_BLOCK`` positions so that its gradient keeps one state a block.
**Loss and gradients are jax's own (``jax.vjp``), taken stage by stage**
(embedding, each block, the head), each stage a jitted call that computes
its forward again: ``perf/refs/train.py`` holds 20 bytes a parameter (12.0
GB at this configuration's 602 M) beside it.  ``cfg["fault"]`` plants one
fault of a mechanism (``perf/tests``): ``scalar_decay`` (each head's gate
averaged over its channels), ``no_delta`` (the correction ``- S'^T k``
dropped), ``no_conv`` (the three convolutions left out), ``no_qk_l2norm``,
``rope_mla`` (the latent layer's 64-wide parts turned at ``rope_theta``),
``scale_one`` (2.446 -> 1), ``top7`` (one expert fewer a token),
``no_shared`` (the shared expert left out).
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

FAULTS = ("scalar_decay", "no_delta", "no_conv", "no_qk_l2norm", "rope_mla",
          "scale_one", "top7", "no_shared")
KDA_BLOCK = 64
Q_BLOCK = 512
HEAD_ROWS = 1024


def _kda_dims(cfg):
    """(H, dk, L)."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def _mla_dims(cfg):
    """(H, r_kv, d_n, d_r, d_v)."""
    return (cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def mixer(cfg, l):
    """``kda`` or ``mla`` of the kept layer ``l`` (from 0)."""
    lin = cfg["linear_attn_config"]
    if l + 1 in lin["kda_layers"]:
        return "kda"
    if l + 1 in lin["full_attn_layers"]:
        return "mla"
    raise ValueError("layer %d is in neither list" % (l + 1))


def _is_expert_layer(cfg, l):
    return l >= cfg["first_k_dense_replace"]


def _mixer_spec(cfg, p, kind):
    d = cfg["hidden_size"]
    if kind == "kda":
        h, dk, taps = _kda_dims(cfg)
        hd = h * dk
        return [(p + "kda_query_weight", (hd, d), 0.02, "bfloat16"),
                (p + "kda_key_weight", (hd, d), 0.02, "bfloat16"),
                (p + "kda_value_weight", (hd, d), 0.02, "bfloat16"),
                (p + "kda_q_conv_weight", (hd, taps), "taps", "bfloat16"),
                (p + "kda_k_conv_weight", (hd, taps), "taps", "bfloat16"),
                (p + "kda_v_conv_weight", (hd, taps), "taps", "bfloat16"),
                (p + "kda_f_a_weight", (dk, d), 0.02, "bfloat16"),
                (p + "kda_f_b_weight", (hd, dk), 0.02, "bfloat16"),
                (p + "kda_A_log", (h,), "a_log", "float32"),
                (p + "kda_dt_bias", (hd,), "dt_bias", "float32"),
                (p + "kda_beta_weight", (h, d), 0.02, "bfloat16"),
                (p + "kda_g_a_weight", (dk, d), 0.02, "bfloat16"),
                (p + "kda_g_b_weight", (hd, dk), 0.02, "bfloat16"),
                (p + "kda_o_norm_gamma", (dk,), "ones", "float32"),
                (p + "kda_out_proj_weight", (d, hd), 0.02, "bfloat16")]
    h, rkv, dn, dr, dv = _mla_dims(cfg)
    return [(p + "mla_query_weight", (h * (dn + dr), d), 0.02, "bfloat16"),
            (p + "mla_kv_a_weight", (rkv + dr, d), 0.02, "bfloat16"),
            (p + "mla_kv_a_norm_gamma", (rkv,), "ones", "float32"),
            (p + "mla_kv_b_weight", (h * (dn + dv), rkv), 0.02, "bfloat16"),
            (p + "mla_out_proj_weight", (d, h * dv), 0.02, "bfloat16")]


def _block_spec(cfg, p, kind, experts):
    d = cfg["hidden_size"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e, held = cfg["num_experts"], cfg["num_experts_held"]
    spec = [(p + "ln1_gamma", (d,), "ones", "float32")] \
        + _mixer_spec(cfg, p, kind) \
        + [(p + "ln2_gamma", (d,), "ones", "float32")]
    if not experts:
        return spec + [(p + "ffn_gate_weight", (f, d), 0.02, "bfloat16"),
                       (p + "ffn_up_weight", (f, d), 0.02, "bfloat16"),
                       (p + "ffn_down_weight", (d, f), 0.02, "bfloat16")]
    fs = fe * cfg["num_shared_experts"]
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
        spec += _block_spec(cfg, "l%d_" % l, mixer(cfg, l),
                            _is_expert_layer(cfg, l))
    return spec + [("final_ln_gamma", (d,), "ones", "float32"),
                   ("lm_head_weight", (v, d), 0.02, "bfloat16")]


def _draw(key, shape, init):
    """One leaf of an initialisation ``common.init_from_spec`` does not
    know: the KDA layer's (``assumed.init``)."""
    u = jax.random.uniform(key, shape, jnp.float32)
    if init == "taps":                                  # U(-1/2, 1/2)
        return u - 0.5
    if init == "a_log":                                 # log U(1, 16)
        return jnp.log(1.0 + 15.0 * u)
    if init == "dt_bias":           # softplus^-1(dt), dt ~ logU(1e-3, 1e-1)
        dt = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError("no initialisation %r" % (init,))


def init_params(cfg, seed):
    """The weights as served, from the seed: ``common.init_from_spec`` for
    the normal, unit and constant leaves, the KDA layer's own draws for the
    rest, each leaf from its own fold of the seed's key."""
    spec = param_spec(cfg)
    own = {"taps", "a_log", "dt_bias"}
    plain = common.init_from_spec(
        [s if s[2] not in own else (s[0], s[1], "zeros", s[3])
         for s in spec], seed)
    drawn = [(i, s) for i, s in enumerate(spec) if s[2] in own]

    @jax.jit
    def make(key):
        return {name: _draw(jax.random.fold_in(key, 1_000_003 + i), shape,
                            init).astype(dtype)
                for i, (name, shape, init, dtype) in drawn}

    return {**plain, **make(common.key_from_seed(seed))}


# ------------------------------------------------------------------ layers
def _rms(x, g, eps):
    return g * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps)


def _linear(x, w, precision):
    """``x @ w.T``, w of (out, in)."""
    return common.result(jnp.matmul(common.operand(x, precision),
                                    common.operand(w, precision).T), precision)


def _conv(u, taps):
    """The causal depthwise convolution of [B, T, C] by ``taps`` [C, L]."""
    t, n = u.shape[1], taps.shape[-1]
    u = jnp.pad(u, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(u[:, j:j + t] * taps[:, j] for j in range(n))


def _l2(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _recurrence(fault, q, k, v, g, beta):
    """o [B, T, H, dv] of the gated delta rule, position by position,
    ``KDA_BLOCK`` positions (or the largest block that divides ``T`` into
    whole ones) under one ``jax.checkpoint``."""
    bsz, t, h, dk = q.shape
    scale = dk ** -0.5

    def step(s, xs):
        qt, kt, vt, gt, bt = xs                       # [B, H, .]
        s = jnp.exp(gt)[..., None] * s
        pred = 0.0 if fault == "no_delta" else \
            jnp.einsum("bhcv,bhc->bhv", s, kt)
        s = s + kt[..., None] * (bt[..., None] * (vt - pred))[..., None, :]
        return s, scale * jnp.einsum("bhcv,bhc->bhv", s, qt)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(step, s, xs)

    blk = math.gcd(t, KDA_BLOCK)
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((t // blk, blk) + a.shape[:1]
                                             + a.shape[2:])
               for a in (q, k, v, g, beta))
    s0 = jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32)
    o = jax.lax.scan(block, s0, xs)[1]                # [n, blk, B, H, dv]
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def _kda(cfg, precision, x, p):
    fault = cfg.get("fault")
    h, dk, _ = _kda_dims(cfg)
    bsz, t, _ = x.shape

    def heads(y):
        return y.reshape(bsz, t, h, -1)

    def branch(weight, taps):
        y = _linear(x, p["kda_%s_weight" % weight], precision)
        if fault != "no_conv":
            y = _conv(y, p["kda_%s_conv_weight" % taps])
        return heads(jax.nn.silu(y))

    q, k, v = branch("query", "q"), branch("key", "k"), branch("value", "v")
    if fault != "no_qk_l2norm":
        q, k = _l2(q), _l2(k)
    f = _linear(_linear(x, p["kda_f_a_weight"], precision),
                p["kda_f_b_weight"], precision)
    g = -jnp.exp(p["kda_A_log"])[:, None] * heads(
        jax.nn.softplus(f + p["kda_dt_bias"]))
    if fault == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(_linear(x, p["kda_beta_weight"], precision))
    q, k, v = (common.operand(a, precision) for a in (q, k, v))
    o = common.result(_recurrence(fault, q, k, v, g, beta), precision)
    z = _linear(_linear(x, p["kda_g_a_weight"], precision),
                p["kda_g_b_weight"], precision)
    o = _rms(o, p["kda_o_norm_gamma"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(heads(z))
    return _linear(o.reshape(bsz, t, h * dk), p["kda_out_proj_weight"],
                   precision)


def _rotary(x, theta):
    """[.., T, n] turned by position in the rotate-half pairing over its
    ``n`` dims: frequencies ``theta^(-2j / n)`` (the ``rope_mla`` fault's
    only)."""
    t, n = x.shape[-2:]
    freqs = jnp.asarray([theta ** (-2.0 * j / n) for j in range(n // 2)],
                        jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    rot = jnp.concatenate([-x[..., n // 2:], x[..., :n // 2]], -1)
    return x * cos + rot * sin


def _mla(cfg, precision, x, p):
    """Every head at once, the queries in blocks of ``Q_BLOCK`` (each under
    ``jax.checkpoint``, ``lax.map``) against every key under the causal
    mask."""
    h, rkv, dn, dr, dv = _mla_dims(cfg)
    bsz, t, _ = x.shape
    q = _linear(x, p["mla_query_weight"], precision).reshape(
        bsz, t, h, dn + dr).transpose(0, 2, 1, 3)
    kv_a = _linear(x, p["mla_kv_a_weight"], precision)
    c = _rms(kv_a[..., :rkv], p["mla_kv_a_norm_gamma"], cfg["rms_norm_eps"])
    kv = _linear(c, p["mla_kv_b_weight"], precision).reshape(
        bsz, t, h, dn + dv).transpose(0, 2, 1, 3)
    k_r = kv_a[:, None, :, rkv:]
    if cfg.get("fault") == "rope_mla":
        theta = float(cfg["rope_theta"])
        k_r = _rotary(k_r, theta)
        q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], theta)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (bsz, h, t, dr))],
                        -1)
    v = kv[..., dn:]
    n = math.gcd(t, Q_BLOCK)

    @jax.checkpoint
    def rows(args):
        qb, lo = args
        s = common.result(jnp.einsum(
            "bhqd,bhkd->bhqk", common.operand(qb, precision),
            common.operand(k, precision)), precision) / (dn + dr) ** 0.5
        keep = (lo + jnp.arange(n))[:, None] >= jnp.arange(t)[None, :]
        pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return common.result(jnp.einsum(
            "bhqk,bhkd->bhqd", common.operand(pr, precision),
            common.operand(v, precision)), precision)

    blocks = q.reshape(bsz, h, t // n, n, dn + dr).transpose(2, 0, 1, 3, 4)
    o = jax.lax.map(rows, (blocks, jnp.arange(0, t, n)))  # [nb, B, H, n, dv]
    o = o.transpose(1, 0, 3, 2, 4).reshape(bsz, t, h * dv)  # [B, T, H, dv]
    return _linear(o, p["mla_out_proj_weight"], precision)


def _gated(precision, x, gate, up, down):
    g = _linear(x, gate, precision)
    u = _linear(x, up, precision)
    return _linear(jax.nn.silu(g) * u, down, precision)


def route(cfg, x, router_weight, bias):
    """(sel [.., k] expert ids, w [.., k] weights) of the tokens ``x``:
    float32 in every precision, as the program's router is."""
    fault = cfg.get("fault")
    k = cfg["num_experts_per_token"] - (fault == "top7")
    s = jax.nn.sigmoid(jnp.matmul(x, router_weight.T))
    _, sel = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel, w * (1.0 if fault == "scale_one"
                     else cfg["routed_scaling_factor"])


def routed_experts(cfg, precision, x, p):
    """The held experts' part of the layer's routed sum: every held expert
    on every token (a ``lax.scan`` over them, each under
    ``jax.checkpoint``), weighted by the token's weight for it (nought where
    the token did not select it)."""
    e, held, off = (cfg["num_experts"], cfg["num_experts_held"],
                    cfg["expert_offset"])
    sel, w = route(cfg, x, p["moe_router_weight"], p["moe_expert_bias"])
    weight_of = jnp.sum(jax.nn.one_hot(sel, e, dtype=x.dtype)
                        * w[..., None], axis=-2)                # [B,T,E]
    mine = jnp.moveaxis(weight_of[..., off:off + held], -1, 0)  # [held,B,T]

    @jax.checkpoint
    def one(y, xs):
        gate, up, down, wt = xs
        return y + wt[..., None] * _gated(precision, x, gate, up, down), None

    return jax.lax.scan(one, jnp.zeros_like(x), (
        p["moe_expert_gate_weight"], p["moe_expert_up_weight"],
        p["moe_expert_down_weight"], mine))[0]


def expert_layer(cfg, precision, x, p):
    """FFN_l, l >= 2, on the normed stream: this holder's routed part and
    the shared expert."""
    y = routed_experts(cfg, precision, x, p)
    if cfg.get("fault") != "no_shared":
        y = y + _gated(precision, x, p["shared_gate_weight"],
                       p["shared_up_weight"], p["shared_down_weight"])
    return y


def block(cfg, precision, kind, experts, x, p):
    """One block on the residual stream; ``p`` by the block's own names."""
    eps = cfg["rms_norm_eps"]
    mix = _kda if kind == "kda" else _mla
    h = x + mix(cfg, precision, _rms(x, p["ln1_gamma"], eps), p)
    f = _rms(h, p["ln2_gamma"], eps)
    if experts:
        return h + expert_layer(cfg, precision, f, p)
    return h + _gated(precision, f, p["ffn_gate_weight"], p["ffn_up_weight"],
                      p["ffn_down_weight"])


def _embed(p, ids):
    return p["tok_embedding_weight"][ids.astype(jnp.int32)]


def _head(cfg, precision, scale, p, x, targets):
    """(``scale`` x the sum of the cross-entropies of ``x``'s logits against
    ``targets``, each row's mean); ``p``: gamma and lm_head_weight.
    ``HEAD_ROWS`` positions at a time (``lax.map``), each under
    ``jax.checkpoint``."""
    bsz, t, d = x.shape
    n = math.gcd(t, HEAD_ROWS)

    @jax.checkpoint
    def rows(args):
        xb, tb = args
        logits = _linear(_rms(xb, p["gamma"], cfg["rms_norm_eps"]),
                         p["lm_head_weight"], precision)
        picked = jnp.take_along_axis(
            logits, tb.astype(jnp.int32)[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    def split(a):
        return jnp.moveaxis(a.reshape((bsz, t // n, n) + a.shape[2:]), 1, 0)

    ce = jnp.moveaxis(jax.lax.map(rows, (split(x), split(targets))), 0, 1)
    ce = ce.reshape(bsz, t)
    return scale * jnp.sum(ce), jnp.mean(ce, -1)


# ------------------------------------------------- stages, jitted, cached
def _block_params(cfg, params, l):
    prefix = "l%d_" % l
    return {name: params[prefix + name] for name, *_ in _block_spec(
        cfg, "", mixer(cfg, l), _is_expert_layer(cfg, l))}


def _stage_of(cfg, l):
    return "%s_%s" % (mixer(cfg, l),
                      "expert" if _is_expert_layer(cfg, l) else "dense")


@functools.lru_cache(maxsize=1)
def _stages(cfg_json, precision):
    """The model's stages as jitted (forward, backward) pairs; a backward
    is ``jax.vjp`` of the stage's own forward, computed again.  One
    configuration's at a time (a process that reads several lets the last
    one's programs go)."""
    cfg = json.loads(cfg_json)

    def pair(kind, experts):
        def f(p, x):
            return block(cfg, precision, kind, experts, x, p)

        def bwd(p, x, g):
            with jax.default_matmul_precision("highest"):
                return jax.vjp(f, p, x)[1](g)

        def fwd(p, x):
            with jax.default_matmul_precision("highest"):
                return f(p, x)
        return jax.jit(fwd), jax.jit(bwd)

    def head_grad(p, x, targets, scale):
        """(loss, rows, gradient of p, gradient of x)."""
        with jax.default_matmul_precision("highest"):
            (loss, rows), grads = jax.value_and_grad(
                lambda p, x: _head(cfg, precision, scale, p, x, targets),
                argnums=(0, 1), has_aux=True)(p, x)
        return loss, rows, grads[0], grads[1]

    stages = {"%s_%s" % (kind, "expert" if e else "dense"): pair(kind, e)
              for kind in ("kda", "mla") for e in (False, True)}
    stages["head"] = jax.jit(head_grad)
    stages["embed_grad"] = jax.jit(lambda p, ids, g: jax.vjp(
        lambda p: _embed(p, ids), p)[1](g)[0])
    return stages


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


def _into(grads, new):
    for k, v in new.items():
        grads[k] = v if k not in grads else _add(grads[k], v)


def _rows_loss_and_grad(cfg, precision, params, ids, labels, tokens):
    """(loss part, gradients, each row's mean cross-entropy) of one block
    of rows: the stages forward, then backward in reverse."""
    st = _stages(json.dumps(cfg, sort_keys=True), precision)
    x = _embed(params, ids)
    xs = [x]
    for l in range(cfg["num_hidden_layers"]):
        x = st[_stage_of(cfg, l)][0](_block_params(cfg, params, l), x)
        xs.append(x)
    loss, rows, g_head, g = st["head"](
        {"gamma": params["final_ln_gamma"],
         "lm_head_weight": params["lm_head_weight"]}, xs[-1], labels,
        1.0 / tokens)
    grads = {}
    _into(grads, {"final_ln_gamma": g_head["gamma"],
                  "lm_head_weight": g_head["lm_head_weight"]})
    for l in reversed(range(cfg["num_hidden_layers"])):
        g_blk, g = st[_stage_of(cfg, l)][1](_block_params(cfg, params, l),
                                            xs[l], g)
        _into(grads, {"l%d_%s" % (l, k): v for k, v in g_blk.items()})
        xs[l + 1] = None
    _into(grads, st["embed_grad"](
        {"tok_embedding_weight": params["tok_embedding_weight"]}, ids, g))
    return loss, grads, rows


def loss_and_grad(cfg, params, batch, precision="float32", rows=1):
    """(loss, its gradient, every row's mean cross-entropy), accumulated
    over blocks of ``rows`` rows."""
    ids, labels = batch
    n = ids.shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError("batch %d is not whole blocks of %d rows" % (n, rows))
    loss, grads, per_row = None, None, []
    for r in range(0, n, rows):
        l, g, rl = _rows_loss_and_grad(cfg, precision, params,
                                       ids[r:r + rows], labels[r:r + rows],
                                       float(ids.size))
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
    one layer: ``tokens x k x held / experts`` (even routing)."""
    return (wl["batch"] * wl["seq_len"] * cfg["num_experts_per_token"]
            * cfg["num_experts_held"] / cfg["num_experts"])


def causal_pairs(t):
    """(query, key) pairs one head's causal mask admits over ``t``
    positions."""
    return t * (t + 1) // 2


def _layers_of(cfg, kind):
    return sum(1 for l in range(cfg["num_hidden_layers"])
               if mixer(cfg, l) == kind)


def _kda_weights(cfg):
    """Multiply-adds a token of one KDA layer's products."""
    d = cfg["hidden_size"]
    h, dk, _ = _kda_dims(cfg)
    hd = h * dk
    return 3 * hd * d + 2 * (dk * d + hd * dk) + h * d + d * hd


def _mla_weights(cfg):
    d = cfg["hidden_size"]
    h, rkv, dn, dr, dv = _mla_dims(cfg)
    return h * (dn + dr) * d + (rkv + dr) * d + rkv * h * (dn + dv) \
        + h * dv * d


def fwd_flops(cfg, wl):
    """Operations one forward pass requires on one batch: two per
    multiply-add of every matmul; the latent layer's score and value
    products over the pairs the causal mask admits; the KDA recurrence's
    three products a head and position (``S'^T k``, ``k u^T``, ``S^T q``:
    6 dk dv, what ``kda_core_flops`` counts forward); the routed experts'
    products at the EXPECTED rows (``expert_rows``), not at the ``tokens x
    held`` rows the program computes; norms, convolutions, gates, decays and
    the embedding's lookup cost none."""
    d, f, fe, v = (cfg["hidden_size"], cfg["intermediate_size"],
                   cfg["moe_intermediate_size"], cfg["vocab_size"])
    b, t = wl["batch"], wl["seq_len"]
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    per_token = (_layers_of(cfg, "kda") * _kda_weights(cfg)
                 + _layers_of(cfg, "mla") * _mla_weights(cfg)
                 + dense * 3 * d * f
                 + sparse * (d * cfg["num_experts"]
                             + 3 * d * fe * cfg["num_shared_experts"])
                 + d * v)
    experts = sparse * 2 * expert_rows(cfg, wl) * 3 * d * fe
    return 2 * per_token * b * t + mla_attention_flops(cfg, wl) / 3 \
        + kda_core_flops(cfg, wl) / 3 + experts


def step_flops(cfg, wl):
    """Forward + backward: every product has a data and a weight gradient
    (the recurrence's backward is twice its forward too)."""
    return 3 * fwd_flops(cfg, wl)


def mla_attention_flops(cfg, wl):
    """What the latent layer's score and value products REQUIRE of one
    step, whatever implements them: the causal pairs a head, two products
    forward and four backward, two operations a multiply-add, over heads of
    ``d_n + d_r`` (scores) and ``d_v`` (values)."""
    h, _, dn, dr, dv = _mla_dims(cfg)
    return _layers_of(cfg, "mla") * 3 * 2 * wl["batch"] \
        * causal_pairs(wl["seq_len"]) * h * (dn + dr + dv)


def mla_attention_bytes(cfg, wl):
    """Bytes those products must move: q, k and their gradients over heads
    of ``d_n + d_r``, v, o and theirs over ``d_v``, bfloat16, each in or out
    once."""
    h, _, dn, dr, dv = _mla_dims(cfg)
    rows = wl["batch"] * wl["seq_len"] * h * 2
    return _layers_of(cfg, "mla") * rows * 4 * (dn + dr + dv)


def kda_core_flops(cfg, wl):
    """What the KDA core REQUIRES of one step, whatever implements it (the
    recurrence, a chunked form, a kernel): its three products a head and
    position, ``S'^T k`` (dk dv multiply-adds), ``k u^T`` (dk dv) and
    ``S^T q`` (dk dv), 6 dk dv operations forward and twice that backward;
    the decay's and the correction's elementwise work costs none.  A
    chunked form's extra in-chunk products (its pairwise decays, its
    triangular solve) are not required work.  4 layers x 16.5 M operations
    a position at T 4096 and 32 heads of 128: 0.155 TFLOP a step."""
    h, dk, _ = _kda_dims(cfg)
    return _layers_of(cfg, "kda") * 3 * 6 * dk * dk * h * wl["batch"] \
        * wl["seq_len"]


def kda_core_bytes(cfg, wl):
    """Bytes the KDA core must move in one step: q, k, v, o and their
    gradients once each at the compute dtype (bfloat16, 2 bytes), g (dk a
    head), beta (one a head) and their gradients once each at float32.  No
    state: it lives in fast memory, or is recomputed.  4 layers x 404 MB at
    T 4096 and 32 heads of 128: 1.61 GB a step, 1.97 ms at the HBM's peak
    against 0.79 at the MXU's for the operations: bound by bytes."""
    h, dk, _ = _kda_dims(cfg)
    per_head = 2 * 2 * (dk + dk + dk + dk) + 2 * 4 * (dk + 1)
    return _layers_of(cfg, "kda") * wl["batch"] * wl["seq_len"] * h \
        * per_head
