"""Plain reference of the decoder-only language model the ``gpt2_medium``
configuration states (Radford et al. 2019, the public ``gpt2-medium`` config),
with the two departures the configuration's file lists: the output head is not
tied to the embedding, and gelu is the exact (erf) form.

float32 ``jax.numpy``, matmuls at ``highest`` precision, no kernel, no cache.
Pre-norm blocks: ``x + Attn(LN(x))``, ``x + FFN(LN(x))``; learned positions
added to the token embedding; final LayerNorm; untied head without bias; the
loss is the mean cross-entropy over every token of the batch.  The gradient is
accumulated over blocks of rows (sequences are independent), one layer
rematerialised at a time, so it fits beside nothing else on one chip.
"""
import functools
import json

import jax
import jax.numpy as jnp

from . import common

LN_EPS = 1e-5


def param_spec(cfg):
    """[(name, shape, init, served dtype)] in the model's own order.  Weights
    are (out, in): ``y = x @ W.T``."""
    d, f, v, t = (cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"],
                  cfg["n_positions"])
    spec = [("tok_embedding_weight", (v, d), 0.02, "bfloat16"),
            ("pos_embedding_weight", (t, d), 0.01, "bfloat16")]
    for i in range(cfg["n_layer"]):
        p = "l%d_" % i
        spec += [(p + "ln1_gamma", (d,), "ones", "float32"),
                 (p + "ln1_beta", (d,), "zeros", "float32")]
        spec += [(p + "attn_%s_weight" % n, (d, d), 0.02, "bfloat16")
                 for n in ("query", "key", "value", "out_proj")]
        spec += [(p + "ln2_gamma", (d,), "ones", "float32"),
                 (p + "ln2_beta", (d,), "zeros", "float32"),
                 (p + "ffn_fc1_weight", (f, d), 0.02, "bfloat16"),
                 (p + "ffn_fc1_bias", (f,), "zeros", "bfloat16"),
                 (p + "ffn_down_weight", (d, f), 0.02, "bfloat16"),
                 (p + "ffn_down_bias", (d,), "zeros", "bfloat16")]
    spec += [("final_ln_gamma", (d,), "ones", "float32"),
             ("final_ln_beta", (d,), "zeros", "float32"),
             ("lm_head_weight", (v, d), 0.02, "bfloat16")]
    return spec


def init_params(cfg, seed):
    """The weights as served, from the seed (``common.init_from_spec``)."""
    return common.init_from_spec(param_spec(cfg), seed)


def exact_ids(vocab):
    """The token ids below ``vocab`` that bfloat16 holds exactly (the
    workload's ``assumed``: the program casts its input ids to bfloat16)."""
    ids = jnp.arange(vocab, dtype=jnp.float32)
    keep = ids.astype(jnp.bfloat16).astype(jnp.float32) == ids
    return jnp.nonzero(keep)[0].astype(jnp.int32)


def make_batches(cfg, wl, seed):
    """The seeded rotation: ``wl["rotation"]`` batches of (ids, next ids),
    float32 holding whole numbers as the program's iterator would hand them.
    Every row differs; every seed draws from the same set of sizes."""
    n, b, t = wl["rotation"], wl["batch"], wl["seq_len"]
    pool = exact_ids(cfg["vocab_size"]) if wl["ids"] == "bf16_exact" \
        else jnp.arange(cfg["vocab_size"], dtype=jnp.int32)

    @jax.jit
    def make(key):
        pick = jax.random.randint(key, (n, b, t + 1), 0, pool.shape[0])
        ids = pool[pick].astype(jnp.float32)
        return ids[:, :, :-1], ids[:, :, 1:]

    data, label = make(jax.random.fold_in(common.key_from_seed(seed), 7919))
    return [(data[i], label[i]) for i in range(n)]


def _ln(x, g, b):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _linear(x, w, precision, bias=None):
    y = common.result(jnp.matmul(common.operand(x, precision),
                                 common.operand(w, precision).T), precision)
    return y if bias is None else y + bias


def _block(cfg, precision, x, p):
    bsz, t, d = x.shape
    h = cfg["n_head"]
    a = _ln(x, p["ln1_gamma"], p["ln1_beta"])

    def heads(w):
        return _linear(a, w, precision).reshape(bsz, t, h, d // h) \
            .transpose(0, 2, 1, 3)

    q, k, v = (heads(p["attn_%s_weight" % n]) for n in ("query", "key", "value"))
    s = common.result(jnp.einsum(
        "bhqd,bhkd->bhqk", common.operand(q, precision),
        common.operand(k, precision)), precision) / (d // h) ** 0.5
    keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    o = common.result(jnp.einsum(
        "bhqk,bhkd->bhqd", common.operand(pr, precision),
        common.operand(v, precision)), precision)
    o = o.transpose(0, 2, 1, 3).reshape(bsz, t, d)
    x = x + _linear(o, p["attn_out_proj_weight"], precision)
    f = _ln(x, p["ln2_gamma"], p["ln2_beta"])
    f = _linear(f, p["ffn_fc1_weight"], precision, p["ffn_fc1_bias"])
    f = jax.nn.gelu(f, approximate=False)
    return x + _linear(f, p["ffn_down_weight"], precision, p["ffn_down_bias"])


def _summed_loss(cfg, precision, params, ids, labels):
    """(sum over the block's tokens of the cross-entropy, each row's mean)."""
    ids = ids.astype(jnp.int32)
    x = params["tok_embedding_weight"][ids] \
        + params["pos_embedding_weight"][jnp.arange(ids.shape[1])][None]
    block = jax.checkpoint(functools.partial(_block, cfg, precision))
    for i in range(cfg["n_layer"]):
        pre = "l%d_" % i
        x = block(x, {k[len(pre):]: v for k, v in params.items()
                      if k.startswith(pre)})
    x = _ln(x, params["final_ln_gamma"], params["final_ln_beta"])
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


def fwd_flops(cfg, wl):
    """Operations one forward pass requires on one batch: two per
    multiply-add of every matmul; the causal score and value products counted
    once (the half of the square that is not masked); gathers cost none."""
    d, f, v, layers = (cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"],
                       cfg["n_layer"])
    b, t = wl["batch"], wl["seq_len"]
    per_token = layers * (4 * d * d + 2 * d * f) + d * v
    attn = layers * 2 * (b * t * t * d)     # 2 products x 2 ops x half
    return 2 * per_token * b * t + attn


def step_flops(cfg, wl):
    """Forward + backward: every product has a data and a weight gradient."""
    return 3 * fwd_flops(cfg, wl)
