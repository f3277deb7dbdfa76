"""Plain reference of ResNet-50 v1 (He et al. 2015) as the ``resnet50_v1``
configuration states it: the bottleneck network of MXNet's Gluon model zoo
(stride on the first 1x1 convolution of a stage's first block; the 1x1
convolutions of a block carry a bias, the 3x3 and the shortcut do not),
224 px, 1000 classes, batch normalisation in training mode over the whole
batch, mean softmax cross-entropy.

float32 ``jax.numpy`` / ``lax`` convolutions at ``highest`` precision, NCHW,
no kernel.  Batch statistics couple the rows, so the batch is not cut into
blocks: each bottleneck block is rematerialised in the backward pass instead.
"""
import functools
import json

import jax
import jax.numpy as jnp
from jax import lax

from . import common

BN_EPS = 1e-5


def conv_layers(cfg):
    """[(name, out_ch, in_ch, kernel, stride, pad, has_bias, out_hw,
    needs_data_grad)] of every convolution, in order."""
    size = cfg["image_size"]
    hw = (size + 2 * 3 - 7) // 2 + 1
    out = [("conv0", cfg["stem_channels"], 3, 7, 2, 3, False, hw, False)]
    hw = (hw + 2 - 3) // 2 + 1                      # max pool 3x3 / 2
    cin = cfg["stem_channels"]
    for s, (blocks, ch) in enumerate(zip(cfg["layers"], cfg["channels"]), 1):
        k = 0
        for b in range(blocks):
            stride = 2 if (b == 0 and s > 1) else 1
            pre = "stage%d_conv" % s
            mid = ch // 4
            hw_out = hw // stride
            out.append((pre + str(k), mid, cin, 1, stride, 0, True, hw_out, True))
            out.append((pre + str(k + 1), mid, mid, 3, 1, 1, False, hw_out, True))
            out.append((pre + str(k + 2), ch, mid, 1, 1, 0, True, hw_out, True))
            k += 3
            if b == 0:
                out.append((pre + str(k), ch, cin, 1, stride, 0, False,
                            hw_out, True))
                k += 1
            cin, hw = ch, hw_out
    return out


def param_spec(cfg):
    """[(name, shape, init, served dtype)].  A convolution's ``init`` is the
    He deviation sqrt(2 / fan_in)."""
    spec = []
    layers = conv_layers(cfg)
    for at, (name, o, i, k, _, _, bias, _, _) in enumerate(layers):
        bn = name.replace("conv", "batchnorm")
        spec.append((name + "_weight", (o, i, k, k),
                     (2.0 / (i * k * k)) ** 0.5, "bfloat16"))
        if bias:
            spec.append((name + "_bias", (o,), "zeros", "bfloat16"))
        # the last normalisation of a residual branch (its third unit, a
        # biased 1x1 that follows the 3x3) starts small: see the file's
        # ``assumed``
        last = bias and at >= 2 and layers[at - 1][3] == 3
        spec += [(bn + "_gamma", (o,),
                  ("const", cfg["init_branch_gamma"]) if last else "ones",
                  "float32"),
                 (bn + "_beta", (o,), "zeros", "float32")]
    spec += [("dense0_weight", (cfg["classes"], cfg["channels"][-1]), 0.01,
              "bfloat16"),
             ("dense0_bias", (cfg["classes"],), "zeros", "bfloat16")]
    return spec


def init_params(cfg, seed):
    """The weights as served, from the seed (``common.init_from_spec``)."""
    return common.init_from_spec(param_spec(cfg), seed)


def make_batches(cfg, wl, seed):
    """The seeded rotation: ``wl["rotation"]`` batches of (images in
    bfloat16, uniform in [0, 1); labels as float32 whole numbers)."""
    n, b, size = wl["rotation"], wl["batch"], cfg["image_size"]

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, (n, b, 3, size, size), jnp.float32)
        y = jax.random.randint(ky, (n, b), 0, cfg["classes"])
        return x.astype(jnp.bfloat16), y.astype(jnp.float32)

    x, y = make(jax.random.fold_in(common.key_from_seed(seed), 7919))
    return [(x[i], y[i]) for i in range(n)]


def _conv(x, w, stride, pad, precision):
    return common.result(lax.conv_general_dilated(
        common.operand(x, precision), common.operand(w, precision),
        (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW")), precision)


def _bn(x, gamma, beta):
    mean = jnp.mean(x, (0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), (0, 2, 3), keepdims=True)
    return (x - mean) * lax.rsqrt(var + BN_EPS) * gamma[None, :, None, None] \
        + beta[None, :, None, None]


def _unit(p, layer, x, precision):
    name, _, _, _, stride, pad, bias, _, _ = layer
    y = _conv(x, p[name + "_weight"], stride, pad, precision)
    if bias:
        y = y + p[name + "_bias"][None, :, None, None]
    bn = name.replace("conv", "batchnorm")
    return _bn(y, p[bn + "_gamma"], p[bn + "_beta"])


def _bottleneck(layers, precision, x, p):
    y = jax.nn.relu(_unit(p, layers[0], x, precision))
    y = jax.nn.relu(_unit(p, layers[1], y, precision))
    y = _unit(p, layers[2], y, precision)
    short = _unit(p, layers[3], x, precision) if len(layers) == 4 else x
    return jax.nn.relu(y + short)


def _stem(layer, precision, x, p):
    y = jax.nn.relu(_unit(p, layer, x, precision))
    return lax.reduce_window(y, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                             [(0, 0), (0, 0), (1, 1), (1, 1)])


def _pick(params, layers):
    keys = set()
    for l in layers:
        bn = l[0].replace("conv", "batchnorm")
        keys |= {l[0] + "_weight", l[0] + "_bias", bn + "_gamma", bn + "_beta"}
    return {k: params[k] for k in keys if k in params}


def _summed_loss(cfg, precision, params, x, labels):
    """(cross-entropy summed over the rows, each row's): ``SoftmaxOutput``'s
    gradient is ``p - onehot`` per row, not divided by the batch; the
    optimizer's ``rescale_grad`` (1/batch) makes it the mean's."""
    layers = conv_layers(cfg)
    x = jax.checkpoint(functools.partial(_stem, layers[0], precision))(
        x.astype(jnp.float32), _pick(params, layers[:1]))
    at = 1
    for blocks in cfg["layers"]:
        for b in range(blocks):
            mine = layers[at:at + (4 if b == 0 else 3)]
            at += len(mine)
            x = jax.checkpoint(functools.partial(_bottleneck, mine, precision))(
                x, _pick(params, mine))
    x = jnp.mean(x, (2, 3))
    logits = common.result(jnp.matmul(
        common.operand(x, precision),
        common.operand(params["dense0_weight"], precision).T), precision) \
        + params["dense0_bias"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, labels.astype(jnp.int32)[:, None], axis=-1)[:, 0]
    rows = lse - picked
    return jnp.sum(rows), rows


@functools.lru_cache(maxsize=None)
def _whole(cfg_json, precision):
    """The jitted (mean loss, gradient of the summed loss), traced once."""
    cfg = json.loads(cfg_json)

    @jax.jit
    def whole(params, x, labels):
        with jax.default_matmul_precision("highest"):
            (total, rows), grads = jax.value_and_grad(
                lambda p: _summed_loss(cfg, precision, p, x, labels),
                has_aux=True)(params)
        return total / x.shape[0], grads, rows

    return whole


def loss_and_grad(cfg, params, batch, precision="float32", rows=None):
    """(mean loss over the batch, gradient of the summed loss — what the
    optimizer is handed before ``rescale_grad`` —, every row's loss).
    ``rows`` is not used: batch normalisation needs the whole batch at
    once."""
    del rows
    x, labels = batch
    return _whole(json.dumps(cfg, sort_keys=True), precision)(params, x, labels)


def fwd_flops(cfg, wl):
    """Operations one forward pass requires on one batch: two per
    multiply-add of every convolution and of the classifier."""
    macs = sum(o * i * k * k * hw * hw
               for _, o, i, k, _, _, _, hw, _ in conv_layers(cfg))
    macs += cfg["classes"] * cfg["channels"][-1]
    return 2 * macs * wl["batch"]


def step_flops(cfg, wl):
    """Forward, weight gradients and data gradients; the first convolution
    has no data gradient (its input is the image)."""
    no_dgrad = sum(2 * o * i * k * k * hw * hw
                   for _, o, i, k, _, _, _, hw, need in conv_layers(cfg)
                   if not need)
    return 3 * fwd_flops(cfg, wl) - no_dgrad * wl["batch"]
