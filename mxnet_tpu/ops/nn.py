"""Neural-network operators: Convolution, FullyConnected, Pooling, norms,
softmax family, Dropout, activations, UpSampling.

Reference analog: ``src/operator/nn/*`` (convolution.cc:476-519 is the
canonical registration; batch_norm.cc, pooling.cc, fully_connected.cc,
softmax.cc, dropout.cc, layer_norm.cc, lrn.cc, upsampling.cc) plus the cuDNN
fast paths (``src/operator/nn/cudnn/``).  TPU-native design: convolutions and
FC lower straight onto the MXU via ``lax.conv_general_dilated`` / ``dot``; the
cuDNN algo-selection machinery has no analog because XLA picks conv strategies
itself.  NCHW is kept as the user-facing layout (reference default); XLA
relayouts internally for the MXU.
"""
from __future__ import annotations

from functools import partial

import math

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register, param
from ..base import MXNetError
from .. import telemetry as _telemetry
# ``x @ w.T``; inside the mesh step on TPUs the weight's gradient comes
# through its ring, everywhere else it is the plain product
from ..parallel.mesh import matmul_wt as _matmul_wt

# Trace-time dispatch mix of the Convolution formulations (one inc per
# compiled specialization, not per step — executables are cached).  Lets
# /metrics answer "which conv path did this process actually take".
_CONV_DISPATCH = _telemetry.counter(
    "conv_dispatch_total",
    "Convolution dispatch decisions by formulation path (trace-time)",
    ("path",))


def _spatial_dims(kernel):
    return len(kernel)


def _conv_dnums(nd):
    sp = "DHW"[-nd:] if nd <= 3 else None
    return jax.lax.conv_dimension_numbers(
        (1, 1) + (1,) * nd, (1, 1) + (1,) * nd,
        ("NC" + sp, "OI" + sp, "NC" + sp))


_CONV_PARAMS = {
    "kernel": param("shape", (), required=True),
    "stride": param("shape", ()),
    "dilate": param("shape", ()),
    "pad": param("shape", ()),
    "num_filter": param(int, 0, required=True),
    "num_group": param(int, 1),
    "no_bias": param(bool, False),
    "workspace": param(int, 1024),      # accepted, ignored (XLA owns memory)
    "cudnn_tune": param(str, None),     # accepted, ignored on TPU
    "cudnn_off": param(bool, False),
    "layout": param(str, None),
}


def _stem_s2d_eligible(attrs, data, nd):
    """True for thin-input stride-2 2-D stems (e.g. ResNet 7x7s2 on RGB).

    The MXU pads the contraction dim to a full lane tile, so C_in=3 convs
    run at <25 TF while C_in>=64 convs reach 150+ TF (measured,
    docs/perf_analysis.md round 3).  Space-to-depth(2) rewrites the conv
    EXACTLY into a stride-1 conv on 4x the channels.
    """
    import os
    if os.environ.get("MXNET_TPU_STEM_S2D", "1") == "0":
        return False
    if nd != 2 or attrs["num_group"] != 1:
        return False
    stride = attrs["stride"] or (1,) * nd
    dilate = attrs["dilate"] or (1,) * nd
    k = attrs["kernel"]
    if stride != (2, 2) or dilate != (1, 1):
        return False
    if data.shape[1] > 4 or data.shape[2] % 2 or data.shape[3] % 2:
        return False
    return k[0] % 2 == 1 and k[1] % 2 == 1 and k[0] > 1


def _stem_s2d_conv(attrs, data, weight):
    """stride-2 kxk conv on (N,C,H,W) == stride-1 conv on space-to-depth(2).

    y[ho] = sum_dh x[2*ho + dh - pad]; writing dh - pad = 2e + p maps tap
    dh to s2d parity plane p at spatial offset e — a ceil(k/2)-tap
    stride-1 conv over the (N, 4C, H/2, W/2) s2d input (exact rewrite;
    the TPU-MLPerf ResNet stem trick).
    """
    k = attrs["kernel"]
    pad = attrs["pad"] or (0, 0)
    N, C, H, W = data.shape
    O = weight.shape[0]

    def tap_range(kk, p):
        e0 = -(p // 2) - (p % 2)            # floor((0 - p) / 2)
        e1 = (kk - 1 - p) // 2
        return e0, e1
    eh0, eh1 = tap_range(k[0], pad[0])
    ew0, ew1 = tap_range(k[1], pad[1])
    kh, kw = eh1 - eh0 + 1, ew1 - ew0 + 1

    # kernel transform is itself an inverse space-to-depth: shift w so tap
    # dh aligns with (2*e' + p), then fold each spatial parity into the
    # channel dim — layout (p, q, c) -> p*2C + q*C + c, matching x below
    lh, lw = -(2 * eh0 + pad[0]), -(2 * ew0 + pad[1])
    wp = jnp.pad(weight, ((0, 0), (0, 0),
                          (lh, 2 * kh - k[0] - lh),
                          (lw, 2 * kw - k[1] - lw)))
    w4 = wp.reshape(O, C, kh, 2, kw, 2)
    w4 = w4.transpose(0, 3, 5, 1, 2, 4).reshape(O, 4 * C, kh, kw)

    xs = data.reshape(N, C, H // 2, 2, W // 2, 2)
    xs = xs.transpose(0, 3, 5, 1, 2, 4).reshape(N, 4 * C, H // 2, W // 2)
    # high pad sized so the output length matches the strided original:
    # Ho = (H + 2p - k)//2 + 1
    ho = (H + 2 * pad[0] - k[0]) // 2 + 1
    wo = (W + 2 * pad[1] - k[1]) // 2 + 1
    return jax.lax.conv_general_dilated(
        xs, w4, window_strides=(1, 1),
        padding=[(-eh0, ho + kh - H // 2 + eh0 - 1),
                 (-ew0, wo + kw - W // 2 + ew0 - 1)],
        dimension_numbers=_conv_dnums(2))


def _is_3x3_same_unit(attrs, data, nd):
    """Shared shape predicate: 2-D / 3x3 kernel / stride 1 / dilate 1 /
    SAME pad / ungrouped — the class the Pallas "s1" kernel covers."""
    k = attrs["kernel"]
    return (nd == 2 and tuple(k) == (3, 3)
            and tuple(attrs["stride"] or (1, 1)) == (1, 1)
            and tuple(attrs["dilate"] or (1, 1)) == (1, 1)
            and tuple(attrs["pad"] or (0, 0)) == (1, 1)
            and attrs["num_group"] == 1 and data.ndim == 4)


def _pallas_conv_mode(attrs, data, nd):
    """Return "s1" / "s2" when the Pallas implicit-GEMM kernels
    (ops/pallas_conv.py) cover this conv, else None.

    "s1" = the `_is_3x3_same_unit` class with full lane tiles and a
    VMEM-feasible plan; "s2" = 3x3 / stride-2 / pad-1, run through the
    exact space-to-depth rewrite.  Gated by MXNET_TPU_PALLAS_CONV
    (default OFF — every prior hand-conv formulation won its isolated
    chain and lost e2e; see docs/perf_analysis.md round 6).  The flag is
    part of Convolution's jit-cache key, so toggling takes effect on the
    next call."""
    import os
    if os.environ.get("MXNET_TPU_PALLAS_CONV", "0") != "1":
        return None
    if nd != 2 or data.ndim != 4 or attrs["num_group"] != 1:
        return None
    from . import pallas_conv
    N, C, H, W = data.shape
    O = attrs["num_filter"]
    if _is_3x3_same_unit(attrs, data, nd):
        if pallas_conv.conv3x3_same_available(N, H, W, C, O, data.dtype):
            return "s1"
        return None
    if (tuple(attrs["kernel"]) == (3, 3)
            and tuple(attrs["stride"] or (1, 1)) == (2, 2)
            and tuple(attrs["dilate"] or (1, 1)) == (1, 1)
            and tuple(attrs["pad"] or (0, 0)) == (1, 1)
            and pallas_conv.conv3x3_s2_available(N, H, W, C, O, data.dtype)):
        return "s2"
    return None


@register("Convolution", nin=-1, aliases=("convolution", "Convolution_v1"),
          params=dict(_CONV_PARAMS),
          env_keys=("MXNET_TPU_PALLAS_CONV", "MXNET_TPU_STEM_S2D"))
def _convolution(attrs, data, weight, *maybe_bias):
    """N-D convolution on the MXU (ref: src/operator/nn/convolution.cc)."""
    k = attrs["kernel"]
    nd = len(k)
    stride = attrs["stride"] or (1,) * nd
    dilate = attrs["dilate"] or (1,) * nd
    pad = attrs["pad"] or (0,) * nd
    pallas_mode = _pallas_conv_mode(attrs, data, nd)
    if _stem_s2d_eligible(attrs, data, nd):
        path = "s2d_stem"
        out = _stem_s2d_conv(attrs, data, weight)
    elif pallas_mode is not None:
        from . import pallas_conv
        if pallas_mode == "s1":
            path = "pallas"
            out = pallas_conv.conv3x3_same(data, weight)
        else:
            path = "pallas_s2"
            out = pallas_conv.conv3x3_s2(data, weight)
    else:
        path = "lax"
        out = jax.lax.conv_general_dilated(
            data, weight,
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=_conv_dnums(nd),
            feature_group_count=attrs["num_group"])
    if _telemetry.enabled:
        # the dispatch path is a compile-time choice, so this bump fires
        # once per compiled conv variant — that IS the intended signal
        # graftlint: disable=GL002 -- counts compiled variants, not calls
        _CONV_DISPATCH.labels(path=path).inc()
    # NOTE: no preferred_element_type here — the MXU accumulates bf16 convs
    # in f32 natively, and an explicit f32 preference breaks the conv
    # transpose rule (mixed-dtype cotangents) under jax.vjp
    out = out.astype(data.dtype)
    if not attrs["no_bias"] and maybe_bias:
        bias = maybe_bias[0].reshape((1, -1) + (1,) * nd)
        out = out + bias
    return out


@register("Deconvolution", nin=-1, aliases=("deconvolution",),
          params={**_CONV_PARAMS, "adj": param("shape", ()),
                  "target_shape": param("shape", ())})
def _deconvolution(attrs, data, weight, *maybe_bias):
    """Transposed conv (ref: src/operator/nn/deconvolution.cc): gradient of
    Convolution w.r.t. its input, expressed with lhs dilation."""
    k = attrs["kernel"]
    nd = len(k)
    stride = attrs["stride"] or (1,) * nd
    dilate = attrs["dilate"] or (1,) * nd
    pad = attrs["pad"] or (0,) * nd
    adj = attrs["adj"] or (0,) * nd
    # output_size = stride*(in-1) + dilate*(k-1) + 1 - 2*pad + adj
    padding = [(dilate[i] * (k[i] - 1) - pad[i],
                dilate[i] * (k[i] - 1) - pad[i] + adj[i]) for i in range(nd)]
    # weight layout (in_c, out_c/g, *k) → IOHW spec with flipped spatial dims
    sp = "DHW"[-nd:]
    dnums = jax.lax.conv_dimension_numbers(
        data.shape, weight.shape, ("NC" + sp, "IO" + sp, "NC" + sp))
    out = jax.lax.conv_general_dilated(
        data, jnp.flip(weight, axis=tuple(range(2, 2 + nd))),
        window_strides=(1,) * nd,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dnums,
        feature_group_count=attrs["num_group"])
    out = out.astype(data.dtype)
    if not attrs["no_bias"] and maybe_bias:
        out = out + maybe_bias[0].reshape((1, -1) + (1,) * nd)
    return out


@register("FullyConnected", nin=-1, aliases=("fullyconnected", "FullyConnected_v1"),
          params={"num_hidden": param(int, 0, required=True),
                  "no_bias": param(bool, False),
                  "flatten": param(bool, True)})
def _fully_connected(attrs, data, weight, *maybe_bias):
    """y = x·Wᵀ + b on the MXU (ref: src/operator/nn/fully_connected.cc)."""
    if attrs["flatten"]:
        x = data.reshape(data.shape[0], -1)
    else:
        x = data
    out = _matmul_wt(x, weight)
    if not attrs["no_bias"] and maybe_bias:
        out = out + maybe_bias[0]
    return out


_POOL_PARAMS = {
    "kernel": param("shape", ()),
    "pool_type": param(["max", "avg", "sum", "lp"], "max"),
    "global_pool": param(bool, False),
    "kernel_layout": param(str, None),
    "cudnn_off": param(bool, False),
    "pooling_convention": param(["valid", "full", "same"], "valid"),
    "stride": param("shape", ()),
    "pad": param("shape", ()),
    "p_value": param(int, 2),
    "count_include_pad": param(bool, True),
}


@register("Pooling", nin=1, aliases=("pooling", "Pooling_v1"),
          params=dict(_POOL_PARAMS))
def _pooling(attrs, data):
    """Max/avg/sum pooling via windowed reduction on the VPU
    (ref: src/operator/nn/pooling.cc)."""
    nd = data.ndim - 2
    if attrs["global_pool"]:
        axes = tuple(range(2, data.ndim))
        if attrs["pool_type"] == "max":
            out = jnp.max(data, axis=axes, keepdims=True)
        elif attrs["pool_type"] == "sum":
            out = jnp.sum(data, axis=axes, keepdims=True)
        else:
            out = jnp.mean(data, axis=axes, keepdims=True)
        return out
    k = attrs["kernel"]
    stride = attrs["stride"] or (1,) * nd
    pad = attrs["pad"] or (0,) * nd
    window = (1, 1) + tuple(k)
    strides = (1, 1) + tuple(stride)
    pads = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if attrs["pooling_convention"] == "full":
        # ceil instead of floor for output size: add extra padding on the right
        extra = []
        for i in range(nd):
            in_sz = data.shape[2 + i] + 2 * pad[i]
            rem = (in_sz - k[i]) % stride[i]
            extra.append((stride[i] - rem) % stride[i] if rem else 0)
        pads = ((0, 0), (0, 0)) + tuple(
            (p, p + e) for p, e in zip(pad, extra))
    pt = attrs["pool_type"]
    if pt == "max":
        if jnp.issubdtype(data.dtype, jnp.floating):
            init = -jnp.inf
        else:  # typed scalar so reduce_window init matches operand dtype
            init = np.asarray(jnp.iinfo(data.dtype).min, data.dtype)[()]
        return jax.lax.reduce_window(data, init, jax.lax.max, window, strides, pads)
    ssum = jax.lax.reduce_window(data, 0.0, jax.lax.add, window, strides, pads)
    if pt == "sum":
        return ssum.astype(data.dtype)
    if pt == "lp":
        p = attrs["p_value"]
        sp = jax.lax.reduce_window(jnp.abs(data) ** p, 0.0, jax.lax.add,
                                   window, strides, pads)
        return (sp ** (1.0 / p)).astype(data.dtype)
    # avg
    if attrs["count_include_pad"]:
        denom = float(np.prod(k))
        return (ssum / denom).astype(data.dtype)
    ones = jnp.ones_like(data)
    counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, pads)
    return (ssum / counts).astype(data.dtype)


@register("Activation", nin=1, aliases=("activation",),
          params={"act_type": param(["relu", "sigmoid", "tanh", "softrelu",
                                     "softsign", "gelu", "silu"], "relu",
                                    required=True)})
def _activation(attrs, x):
    act = attrs["act_type"]
    if act == "relu":
        return jax.nn.relu(x)
    if act == "sigmoid":
        return jax.nn.sigmoid(x)
    if act == "tanh":
        return jnp.tanh(x)
    if act == "softrelu":
        return jnp.logaddexp(x, 0.0)
    if act == "gelu":
        # exact (erf) formulation: the tanh approximation would put the
        # fused and eager transformer steps on different curves
        return jax.nn.gelu(x, approximate=False)
    if act == "silu":
        return jax.nn.silu(x)
    return jax.nn.soft_sign(x)


@register("LeakyReLU", nin=-1, aliases=("leakyrelu",), needs_rng=True,
          train_aware=True,
          params={"act_type": param(["elu", "leaky", "prelu", "rrelu", "selu",
                                     "gelu"], "leaky"),
                  "slope": param(float, 0.25),
                  "lower_bound": param(float, 0.125),
                  "upper_bound": param(float, 0.334),
                  "__train__": param(bool, False)})
def _leaky_relu(attrs, key, x, *maybe_gamma):
    act = attrs["act_type"]
    if act == "leaky":
        return jnp.where(x > 0, x, attrs["slope"] * x)
    if act == "elu":
        return jnp.where(x > 0, x, attrs["slope"] * jnp.expm1(x))
    if act == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))
    if act == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act == "prelu":
        gamma = maybe_gamma[0]
        shape = [1] * x.ndim
        if gamma.ndim == 1 and x.ndim > 1:
            shape[1] = gamma.shape[0] if gamma.shape[0] > 1 else 1
        g = gamma.reshape(shape)
        return jnp.where(x > 0, x, g * x)
    # rrelu: random slope in [lower, upper] at train, mean at eval
    lo, hi = attrs["lower_bound"], attrs["upper_bound"]
    if attrs.get("__train__"):
        slope = jax.random.uniform(key, x.shape, x.dtype, lo, hi)
    else:
        slope = (lo + hi) / 2.0
    return jnp.where(x > 0, x, slope * x)


# --------------------------------------------------------------------------
# normalization
# --------------------------------------------------------------------------
_BN_PARAMS = {
    "eps": param(float, 1e-3),
    "momentum": param(float, 0.9),
    "fix_gamma": param(bool, True),
    "use_global_stats": param(bool, False),
    "output_mean_var": param(bool, False),
    "axis": param(int, 1),
    "cudnn_off": param(bool, False),
    "__train__": param(bool, False),
}


@register("BatchNorm", nin=5, aliases=("batchnorm", "BatchNorm_v1"),
          params=dict(_BN_PARAMS), train_aware=True, nout=3,
          aux_writeback={1: 3, 2: 4},
          visible=lambda a: 3 if a["output_mean_var"] else 1)
def _batch_norm(attrs, data, gamma, beta, moving_mean, moving_var):
    """BatchNorm (ref: src/operator/nn/batch_norm.cc).

    Outputs (out, new_moving_mean, new_moving_var); in training mode the
    dispatch layer writes outputs 1,2 back into the moving-stat aux arrays —
    the functional TPU expression of the reference's in-kernel aux mutation.
    """
    ax = attrs["axis"] % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    train = attrs.get("__train__") and not attrs["use_global_stats"]
    low_precision = data.dtype in (jnp.bfloat16, jnp.float16)
    if train:
        if low_precision:
            # bf16/f16 fast path: f32-ACCUMULATED stats straight off the
            # low-precision activations (no materialized f32 copy — the
            # square fuses into the reduction), one-pass variance.  The
            # activation-sized reads/writes stay 2 bytes/elt, halving the
            # HBM traffic of this memory-bound op (~17% ResNet-50 step
            # time on v5e).
            mean = jnp.mean(data, axis=red, dtype=jnp.float32)
            m2 = jnp.mean(jax.lax.square(data.astype(jnp.float32)),
                          axis=red)
            var = jnp.maximum(m2 - jax.lax.square(mean), 0.0)
        else:
            x32 = data.astype(jnp.float32)
            mean = jnp.mean(x32, axis=red)
            var = jnp.var(x32, axis=red)
        m = attrs["momentum"]
        new_mm = moving_mean * m + mean * (1 - m)
        new_mv = moving_var * m + var * (1 - m)
    else:
        mean, var = moving_mean, moving_var
        new_mm, new_mv = moving_mean, moving_var
    g = jnp.ones_like(gamma) if attrs["fix_gamma"] else gamma
    inv = jax.lax.rsqrt(var + attrs["eps"]) * g
    if low_precision:
        # normalize in the input dtype with the scale/shift folded into
        # two per-channel scalars (y = x*inv + (beta - mean*inv))
        shift = beta - mean * inv
        return (data * inv.astype(data.dtype).reshape(shape)
                + shift.astype(data.dtype).reshape(shape)), new_mm, new_mv
    out = (data - mean.reshape(shape)) * inv.reshape(shape) \
        + beta.reshape(shape)
    return out.astype(data.dtype), new_mm, new_mv


@register("LayerNorm", nin=3, aliases=("layernorm",),
          params={"axis": param(int, -1), "eps": param(float, 1e-5),
                  "output_mean_var": param(bool, False)}, nout=3,
          visible=lambda a: 3 if a["output_mean_var"] else 1)
def _layer_norm(attrs, data, gamma, beta):
    ax = attrs["axis"] % data.ndim
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=ax, keepdims=True)
    var = jnp.var(x32, axis=ax, keepdims=True)
    inv = jax.lax.rsqrt(var + attrs["eps"])
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    out = (x32 - mean) * inv * gamma.reshape(shape) + beta.reshape(shape)
    return (out.astype(data.dtype), jnp.squeeze(mean, ax), jnp.squeeze(var, ax))


def _rms_norm_last(x, gamma, eps):
    """``gamma * x / sqrt(mean(x^2) + eps)`` over the last axis: the mean
    and the scaling in float32 whatever ``x`` is stored in, the result in
    ``x``'s own type."""
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + eps)
    return (x32 * inv * gamma.astype(jnp.float32)).astype(x.dtype)


@register("RMSNorm", nin=2, aliases=("rmsnorm",),
          params={"eps": param(float, 1e-5)})
def _rms_norm(attrs, data, gamma):
    """Root-mean-square normalisation over the last axis (Zhang & Sennrich
    2019): ``gamma * x / sqrt(mean(x^2) + eps)``, no mean subtracted and no
    shift.  Statistics in float32 (``*_gamma`` stays float32 under the bf16
    policy, ``amp.type_dict_for``).  No reference analog: the 2018 reference
    has LayerNorm only."""
    return _rms_norm_last(data, gamma, attrs["eps"])


def yarn_frequencies(d, theta, yarn):
    """YaRN's ``d / 2`` rotary frequencies (Peng et al. 2023, as the public
    configs state them) from ``yarn`` = (factor, original positions,
    beta_fast, beta_slow): dimension ``i`` keeps ``theta^(-2i/d)`` below the
    band ``[low, high]``, takes it divided by ``factor`` above, and a linear
    blend between; the band's ends are the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over the original positions.
    Static: the same at every length.  float64 numpy, worked at trace
    time."""
    factor, original, beta_fast, beta_slow = yarn[:4]
    i = np.arange(d // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / d)

    def turns(b):
        return d * math.log(original / (2 * math.pi * b)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), d - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rotary(x, theta, yarn=(), period=0):
    """Rotary positions on [B,H,T,d] in the rotate-half pairing (dimension
    i turns with dimension i + d/2 by ``t * theta^(-2i/d)``), computed in
    float32 and stored in ``x``'s type.  ``yarn`` (factor, original
    positions, beta_fast, beta_slow, attention_factor): the frequencies are
    ``yarn_frequencies`` and cos and sin are both multiplied by the
    attention factor.  ``period`` > 0: position ``t`` turns as ``t mod
    period`` (the two copies of a block-diffusion sequence)."""
    T, d = x.shape[-2:]
    half = d // 2
    if yarn:
        inv = jnp.asarray(yarn_frequencies(d, theta, yarn), jnp.float32)
    else:
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    pos = (jnp.arange(T) % period).astype(jnp.float32) if period \
        else jnp.arange(T, dtype=jnp.float32)
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)               # [T, d/2]
    if yarn:
        cos, sin = cos * yarn[4], sin * yarn[4]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


_ATTN_DISPATCH = _telemetry.counter(
    "attention_dispatch_total",
    "MultiHeadAttention dispatch decisions by formulation path (trace-time)",
    ("path",))
_ATTN_KV_BLOCKS = _telemetry.counter(
    "attention_kv_blocks_total",
    "Key blocks at or under the diagonal that the flash forward kernel's "
    "loop bounds take in (visited) and a window lets them leave out "
    "(skipped), a head, a compiled attention variant; under the "
    "block-diffusion mask the tiles that hold a visible pair and the rest "
    "(trace-time)",
    ("kind", "fate"))


def _mha_reference(q, k, v, causal, scale, window=None, diffusion=None):
    """XLA reference attention, [B,H,T,d].  Same math contract as the
    Pallas flash kernel: f32 score/softmax/accumulate regardless of the
    input dtype, the causal mask admits position j<=i exactly, and a
    ``window`` on top of it the ``window`` positions i - window < j <= i;
    ``diffusion`` = (half, block) masks as the kernels' tiles do
    (``pallas_attention._bd_visible``, the whole square one tile)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if diffusion is not None:
        from . import pallas_attention as pa
        s = jnp.where(pa._bd_visible(s.shape[-2:], 0, 0, 0, *diffusion), s,
                      -jnp.inf)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        keep = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        if window is not None:
            keep &= jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :] < window
        s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


@partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _kernel_or_reference(q, k, v, causal, scale, interpret, window=None,
                         diffusion=None):
    """The kernel arm of ``MultiHeadAttention``, [B,H,T,d].  The platform
    is resolved at LOWERING time (advisor r03): of the two branches the one
    that does not match the target is pruned.  Jitted on its own because
    the choice is linearized branch by branch: a model calls this once a
    layer with the same shapes, and a jitted callee is traced, linearized
    and lowered once a program (2 s of every program's trace at
    GPT-2-medium's 24 layers otherwise)."""
    from . import pallas_attention as pa
    flash = partial(pa.flash_attention, causal=causal, scale=scale)
    reference = partial(_mha_reference, causal=causal, scale=scale)
    if window is not None:
        flash, reference = (partial(f, window=window)
                            for f in (flash, reference))
    if diffusion is not None:
        flash, reference = (partial(f, diffusion=diffusion)
                            for f in (flash, reference))
    if interpret:
        return flash(q, k, v)
    return jax.lax.platform_dependent(q, k, v, tpu=flash, default=reference)


#: what a head's keys and values may take of VMEM, double-buffered, for
#: ``MultiHeadAttention`` to call the flash kernels: 8 MB, 4,096 positions
#: at a head of 256 (latent attention's) and 8,192 at 128.  The kernels ask
#: for 32 MB of the v5e's 128 (``pallas_attention._PARALLEL``).  A forward
#: program holds one head's K and V whole (4 MB at that shape, twice for
#: the pipeline), a 512-block of q, o and their float32 accumulators; a
#: backward program (``flash_dqkv``) holds the head's Q and dO whole, the
#: same bytes, its dq in float32 (4 MB) and the dq block it writes: 18.5 MB
#: at a head of 256 by ``pallas_attention._bwd_vmem_bytes``, which raises
#: the kernel's limit where a shape inside this envelope needs more
#: (16,384 positions at a head of 64, whose lanes are padded to 128).
#: Ring attention, whose competitor is the scan and not a T x T tensor in
#: HBM, keeps ``kv_fits_vmem``'s own 5 MB.  Measured on the v5e
#: (``tools/bench_attention_arms.py``, PERF.md, PR 33 and PR 34).
MHA_KV_VMEM = 8 << 20


def mha_uses_kernel(B, H, T, d, dtype, dv=None):
    """MultiHeadAttention's own shape test: does the flash kernel beat
    ``_mha_reference`` on a device that holds ``B`` rows of ``H`` heads?
    (``pa.flash_attention_available`` answers for ring attention, whose
    competitor is the scan.)

    Forward + gradient, bf16, causal, device ms from a trace on the v5e
    (``tools/bench_attention_arms.py``; the XLA arm PR 26, the kernels,
    whose backward is one kernel, PR 34), XLA arm / kernel:

        B x H   d     T 256          T 512          T 1024         T 2048
        16      64    0.012 / 0.018  0.040 / 0.051  0.352 / 0.125  3.40 / 0.42
        64      64    0.041 / 0.070  0.362 / 0.203  3.57 / 0.501   13.5 / 1.67
        16      128   0.014 / 0.022  0.044 / 0.057  0.368 / 0.135  3.43 / 0.43
        64      128   0.048 / 0.074  0.385 / 0.205  3.53 / 0.499   13.6 / 1.64

    The XLA arm is quick while its float32 scores (B x H x T x T) stay in
    VMEM, 16 MB in every cell it wins, and pays HBM for them from 64 MB on,
    every cell it loses, whatever ``d``: the kernel takes the shapes whose
    scores reach 64 MB.  (Inside a training step the XLA arm also keeps the
    probabilities of every layer for backward, which no VMEM holds: at the
    GPT-2 cells' shape it cost 0.55 ms a layer there, not 0.35.)  ``dv``:
    values of a width of their own (0 or None: ``d``), counted apart in the
    keys' and values' VMEM."""
    from . import pallas_attention as pa
    dv = dv or d
    if d % 8 or dv % 8 or T % 128 \
            or not pa.kv_fits_vmem(T, d, dtype, MHA_KV_VMEM, dv=dv):
        return False
    # pa.INTERPRET is the tests' hook: any shape the kernel can run
    return pa.INTERPRET or B * H * T * T * 4 >= 64 << 20


@register("MultiHeadAttention", nin=-1, aliases=("multiheadattention",),
          params={"num_heads": param(int, 0, required=True),
                  "causal": param(bool, True),
                  "num_kv_heads": param(int, 0),
                  "qk_norm": param(bool, False),
                  "rope_theta": param(float, 0.0),
                  "eps": param(float, 1e-5),
                  "head_dim": param(int, 0),
                  "window": param(int, 0),
                  "rope_yarn": param("floats", ()),
                  "q_lora_rank": param(int, 0),
                  "kv_lora_rank": param(int, 0),
                  "qk_rope_head_dim": param(int, 0),
                  "v_head_dim": param(int, 0),
                  "diffusion_block": param(int, 0)})
def _multi_head_attention(attrs, data, *weights):
    """Decoder attention: QKV projections, scaled-dot-product over
    ``num_heads``, output projection.  No reference analog — the
    reference predates transformer first-class ops; the contract follows
    ``sym.FullyConnected`` conventions (weights are (out, in), y=x·Wᵀ).

    Variants, all off by default (GPT-2's graph keeps its five inputs and
    its program): ``num_kv_heads`` < ``num_heads`` gives grouped-query
    attention — ``key_weight`` / ``value_weight`` are
    ``(num_kv_heads * head, model_dim)`` and query head ``i`` attends to
    key/value head ``i // (num_heads / num_kv_heads)``; the heads are
    repeated in front of the score product, so a key/value head's gradient
    is the sum over its query heads by autodiff.  ``qk_norm`` adds two
    inputs, ``q_norm_gamma`` and ``k_norm_gamma`` of ``(head,)``: RMSNorm
    (``eps``) over each head of the queries and keys.  ``rope_theta`` > 0
    turns queries and keys by rotary positions (rotate-half pairing) after
    the normalisation.  All three happen in front of the arm's choice: the
    flash kernels and the XLA arm see normalised, rotated, repeated heads.
    ``head_dim`` > 0 gives the heads a size of their own, not
    ``model_dim / num_heads``: ``query_weight`` is then
    ``(num_heads * head_dim, model_dim)``, ``out_proj_weight``
    ``(model_dim, num_heads * head_dim)``.  ``window`` > 0 is sliding-window
    attention: position ``t`` sees the ``window`` positions
    ``t - window < s <= t``, its own among them; the kernels skip the key
    blocks no query of a block sees (``attention_kv_blocks_total``), the
    XLA arm masks them.  ``rope_yarn`` = (factor, original positions,
    beta_fast, beta_slow, attention_factor) turns the rotary frequencies
    into YaRN's (``yarn_frequencies``; needs ``rope_theta``).
    ``diffusion_block`` = b > 0 (``causal=False``) is block-diffusion
    attention (BD3-LM's, SDAR's): the T positions are a noised copy of a
    document in [0, T/2) and its clean copy in [T/2, T), both rotated by
    the positions 0 .. T/2 - 1, and the mask ``pallas_attention`` states
    says who sees whom in blocks of b (a power of two that divides T/2); the kernels
    visit only the tiles that hold a visible pair
    (``attention_kv_blocks_total{kind="block_diffusion"}``).

    Dispatch: the Pallas flash kernel (ops/pallas_attention.py) wherever
    ``mha_uses_kernel`` says the kernel beats the XLA arm at this shape;
    otherwise the XLA reference runs.  The kernel's calls follow the
    sharding of the batch and head dimensions (one sequence a chip under
    the mesh fused step's ``P('dp')``).

    Weight names are chosen so ``parallel.mesh.megatron_rules`` shards
    them with zero extra configuration: query/key/value_weight match the
    column-parallel rule (P(t, None)), out_proj_weight the row-parallel
    rule (P(None, t)).

    ``kv_lora_rank`` > 0 is latent attention (DeepSeek-V2's; GLM-4.7-Flash):
    other inputs and other projections in front of the same dispatch,
    ``_latent_heads``.
    """
    if data.ndim != 3:
        raise MXNetError(
            "MultiHeadAttention: data must be (batch, time, model_dim), "
            "got %s" % (data.shape,))
    if attrs.get("kv_lora_rank"):
        return _attend(attrs, *_latent_heads(attrs, data, *weights[:-1]),
                       weights[-1], None)
    query_weight, key_weight, value_weight, out_proj_weight, *qk_gammas = \
        weights
    B, T, D = data.shape
    H = attrs["num_heads"]
    d = attrs.get("head_dim") or 0
    if H <= 0 or (not d and D % H):
        raise MXNetError(
            "MultiHeadAttention: num_heads=%d must divide model_dim=%d"
            % (H, D))
    # the variants' attrs by .get: callers of the bare fn pass GPT-2's two
    Hkv = attrs.get("num_kv_heads") or H
    if H % Hkv:
        raise MXNetError(
            "MultiHeadAttention: num_kv_heads=%d must divide num_heads=%d"
            % (Hkv, H))
    qk_norm, theta = bool(attrs.get("qk_norm")), attrs.get("rope_theta") or 0
    if qk_norm != (len(qk_gammas) == 2):
        raise MXNetError(
            "MultiHeadAttention: qk_norm takes q_norm_gamma and "
            "k_norm_gamma, and only qk_norm does (got %d extra inputs)"
            % len(qk_gammas))
    d = d or D // H
    causal = attrs["causal"]
    window, yarn = attrs.get("window") or None, attrs.get("rope_yarn") or ()
    if window and not causal:
        raise MXNetError("MultiHeadAttention: a window needs causal=True")
    if yarn and (len(yarn) != 5 or not theta > 0):
        raise MXNetError(
            "MultiHeadAttention: rope_yarn is (factor, original positions, "
            "beta_fast, beta_slow, attention_factor) on top of rope_theta, "
            "got %r with rope_theta=%r" % (yarn, theta))
    if window is not None and window >= T:
        window = None           # every earlier position: plain causal
    block = attrs.get("diffusion_block") or 0
    if block and (causal or window or T % 2 or (T // 2) % block
                  or block & (block - 1)):
        raise MXNetError(
            "MultiHeadAttention: diffusion_block=%d is its own mask (causal="
            "False, no window) over an even T whose half it divides, a "
            "power of two; got causal=%r, window=%r, T=%d"
            % (block, causal, window, T))

    def proj(w, heads):
        y = _matmul_wt(data, w)                       # [B,T,heads*d]
        return y.reshape(B, T, heads, d).transpose(0, 2, 1, 3)

    q, k, v = proj(query_weight, H), proj(key_weight, Hkv), \
        proj(value_weight, Hkv)                       # [B,heads,T,d]
    if qk_norm:
        q = _rms_norm_last(q, qk_gammas[0], attrs.get("eps", 1e-5))
        k = _rms_norm_last(k, qk_gammas[1], attrs.get("eps", 1e-5))
    if theta > 0:
        period = T // 2 if block else 0
        q, k = (_rotary(a, theta, yarn, period) for a in (q, k))
    if Hkv != H:
        k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    return _attend(attrs, q, k, v, out_proj_weight, window)


def _attend(attrs, q, k, v, out_proj_weight, window):
    """The op's dispatch over finished heads [B,H,T,d] (``v`` may have a
    size of its own, on either arm), its counters, and the output
    projection."""
    from . import pallas_attention as pa
    B, H, T, d = q.shape
    causal, scale = attrs["causal"], 1.0 / (d ** 0.5)
    block = attrs.get("diffusion_block") or 0
    diffusion = (T // 2, block) if block else None
    if mha_uses_kernel(*pa.rows_per_device(B, H), T, d, q.dtype,
                       dv=v.shape[-1]):
        # test hook (pa.INTERPRET): force the interpreter on CPU
        path = ("flash_block_diffusion" if block else "flash"
                if window is None else "flash_window") \
            + ("_interpret" if pa.INTERPRET else "")
        out = _kernel_or_reference(q, k, v, causal, scale, pa.INTERPRET,
                                   window, diffusion)
        blocks = pa.kv_block_plan(T, T, causal, window, diffusion=diffusion)
    else:
        out = _mha_reference(q, k, v, causal, scale, window, diffusion)
        path, blocks = "reference", None
    if _telemetry.enabled:
        # one inc per compiled attention variant, not per step — the
        # dispatch is a trace-time choice, same contract as conv_dispatch
        # graftlint: disable=GL002 -- counts compiled variants, not calls
        _ATTN_DISPATCH.labels(path=path).inc()
        if blocks is not None:
            kind = "block_diffusion" if block else "sliding_attention" \
                if attrs.get("window") else "full_attention"
            for fate, count in zip(("visited", "skipped"), blocks):
                # graftlint: disable=GL002 -- counts compiled variants
                _ATTN_KV_BLOCKS.labels(kind=kind, fate=fate).inc(count)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, -1)     # [B,T,H*d]
    return _matmul_wt(out, out_proj_weight)


_ATTN_LATENT = _telemetry.counter(
    "attention_latent_total",
    "MultiHeadAttention layers compiled as latent attention: low-rank "
    "queries and keys/values, heads split into a non-rotary and a rotary "
    "part (trace-time)")


def _latent_heads(attrs, data, *weights):
    """Latent attention's heads (DeepSeek-V2, as GLM-4.7-Flash states it)
    in front of ``_attend``: (q, k, v), each [B,H,T,.].

    With ``r_q`` = ``q_lora_rank``, ``r_kv`` = ``kv_lora_rank``, a head of
    ``head_dim`` = ``d_n + d_r`` whose last ``d_r`` = ``qk_rope_head_dim``
    dims carry the position, values of ``v_head_dim`` (0: ``head_dim``):

        c_q = RMSNorm(x · Wqaᵀ; g_q)          q_a_weight (r_q, D)
        q   = c_q · Wqbᵀ                      q_b_weight (H * head_dim, r_q)
        [c_kv; k_r] = x · Wkvaᵀ               kv_a_weight (r_kv + d_r, D)
        c_kv = RMSNorm(c_kv; g_kv)            (k_r is not normalised)
        [k_n_i; v_i] = c_kv · Wkvbᵀ           kv_b_weight (H * (d_n + v), r_kv)
        q_i = [q_n_i; rope(q_r_i)],  k_i = [k_n_i; rope(k_r)]

    ``k_r`` is ONE rotated part of ``d_r`` dims that every head shares (its
    gradient is the sum over the heads, by autodiff through the
    broadcast); ``_rotary`` is applied to the ``d_r``-wide parts, so the
    frequencies are ``rope_theta^(-2j / d_r)``.  Scores are over the whole
    head, ``/ sqrt(head_dim)``.  Two other forms (Kimi Linear's latent
    layer): ``q_lora_rank`` 0 takes the queries from one full-rank product,
    ``q = x · Wqᵀ`` with ``query_weight`` (H * head_dim, D), in place of
    the first two lines and their three inputs; ``rope_theta`` 0 turns
    nothing (the ``d_r``-wide parts, the shared ``k_r`` among them, stay as
    they are).  Inputs, in order: ``q_a_weight, q_a_norm_gamma,
    q_b_weight`` (or ``query_weight``), ``kv_a_weight, kv_a_norm_gamma,
    kv_b_weight``.  Values of their own width go to the kernels as heads of
    any width do (``_attend``).  Under ``megatron_rules`` ``q_b_weight``,
    ``query_weight`` and ``kv_b_weight`` split by head (column-parallel),
    ``out_proj_weight`` (D, H * v_head_dim) by its inputs, the two ``_a_``
    matrices and the gains stay whole."""
    B, T, _ = data.shape
    H, d, dr = attrs["num_heads"], attrs.get("head_dim") or 0, \
        attrs.get("qk_rope_head_dim") or 0
    rq, rkv = attrs.get("q_lora_rank") or 0, attrs["kv_lora_rank"]
    theta, eps = attrs.get("rope_theta") or 0, attrs.get("eps", 1e-5)
    if H <= 0 or rq < 0 or not 0 < dr < d or dr % 2 or theta < 0 \
            or not attrs["causal"] or len(weights) != (6 if rq else 4):
        raise MXNetError(
            "MultiHeadAttention: latent attention takes num_heads, "
            "kv_lora_rank, head_dim (the whole head), an even "
            "qk_rope_head_dim inside it and causal=True, q_lora_rank and "
            "rope_theta of 0 or more, and its six (q_lora_rank 0: four) "
            "matrices and gains; got %r and %d inputs" % (
                {k: attrs.get(k) for k in (
                    "num_heads", "q_lora_rank", "kv_lora_rank", "head_dim",
                    "qk_rope_head_dim", "rope_theta", "causal")},
                len(weights)))
    if any(attrs.get(k) for k in ("num_kv_heads", "qk_norm", "window",
                                  "rope_yarn", "diffusion_block")):
        raise MXNetError(
            "MultiHeadAttention: latent attention has no grouped heads, "
            "per-head norms, window, YaRN frequencies or block-diffusion "
            "mask")
    dn, dv = d - dr, attrs.get("v_head_dim") or d
    kv_a_weight, kv_a_norm_gamma, kv_b_weight = weights[-3:]

    def heads(c, w, size):
        return _matmul_wt(c, w).reshape(B, T, H, size).transpose(0, 2, 1, 3)

    if rq:
        q_a_weight, q_a_norm_gamma, q_b_weight = weights[:3]
        q = heads(_rms_norm_last(_matmul_wt(data, q_a_weight),
                                 q_a_norm_gamma, eps), q_b_weight, d)
    else:
        q = heads(data, weights[0], d)
    kv_a = _matmul_wt(data, kv_a_weight)                  # [B,T,r_kv+d_r]
    c_kv = _rms_norm_last(kv_a[..., :rkv], kv_a_norm_gamma, eps)
    kv = heads(c_kv, kv_b_weight, dn + dv)
    k_r = kv_a[:, None, :, rkv:]                          # [B,1,T,d_r]
    if theta > 0:
        k_r = _rotary(k_r, theta)
        q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], theta)],
                            axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (B, H, T, dr))], axis=-1)
    if _telemetry.enabled:
        # graftlint: disable=GL002 -- counts compiled variants, not calls
        _ATTN_LATENT.inc()
    return q, k, kv[..., dn:]


def causal_depthwise_conv(u, taps):
    """``conv_t = sum_j taps[:, j] * u_{t-(L-1)+j}`` over ``u`` (batch, time,
    channels) with ``u`` zero before position 0: depthwise, causal, ``L``
    taps a channel (``taps`` (channels, L), the last on the current
    position), no bias.  ``L`` shifted multiply-adds that XLA fuses with
    what surrounds them, in ``u``'s type (callers pass float32; the taps
    are cast to it).  Shared by
    ``ShortConv`` and ``DeltaAttention``'s three convolutions."""
    T, L = u.shape[1], taps.shape[-1]
    u = jnp.pad(u, ((0, 0), (L - 1, 0), (0, 0)))
    taps = taps.astype(u.dtype)
    return sum(u[:, j:j + T, :] * taps[:, j] for j in range(L))


_SHORTCONV_DISPATCH = _telemetry.counter(
    "shortconv_dispatch_total",
    "ShortConv dispatch decisions by formulation path (trace-time)",
    ("path",))


@register("ShortConv", nin=4, aliases=("shortconv",),
          params={"kernel": param(int, 3)})
def _short_conv(attrs, data, in_proj_weight, conv_weight, out_proj_weight):
    """Gated short causal convolution (the LFM2 ``conv`` layer; Hasani et
    al. 2024): ``[b, c, x] = split3(data · W_inᵀ)``, ``u = b * x``,
    ``conv_t = sum_j k_j * u_{t-(L-1)+j}`` with ``u`` zero before position
    0 (depthwise, causal, ``L = kernel`` taps, no bias), and
    ``out = (c * conv) · W_outᵀ``.

    ``data`` is (batch, time, dim); ``in_proj_weight`` (3 dim, dim) and
    ``out_proj_weight`` (dim, dim) lie (out, in) as FullyConnected's do (the
    first column-parallel, the second row-parallel under
    ``megatron_rules``); ``conv_weight`` is (dim, kernel), one row of taps a
    channel, the last tap on the current position.  The ``L`` taps are
    ``L`` shifted multiply-adds (``causal_depthwise_conv``) that XLA fuses
    with the two gates (path
    ``shift`` of ``shortconv_dispatch_total``), accumulated in float32.  No
    reference analog."""
    if data.ndim != 3:
        raise MXNetError(
            "ShortConv: data must be (batch, time, dim), got %s"
            % (data.shape,))
    L = attrs["kernel"]
    if conv_weight.shape[-1] != L:
        raise MXNetError("ShortConv: conv_weight %s has not kernel=%d taps"
                         % (conv_weight.shape, L))
    b, c, x = jnp.split(jnp.matmul(data, in_proj_weight.T), 3, axis=-1)
    conv = causal_depthwise_conv((b * x).astype(jnp.float32), conv_weight)
    if _telemetry.enabled:
        # graftlint: disable=GL002 -- counts compiled variants, not calls
        _SHORTCONV_DISPATCH.labels(path="shift").inc()
    gated = (c.astype(jnp.float32) * conv).astype(data.dtype)
    return jnp.matmul(gated, out_proj_weight.T)


_MOE_DISPATCH = _telemetry.counter(
    "moe_dispatch_total",
    "SparseMoE dispatch decisions by formulation of the held experts' "
    "products (trace-time)", ("path",))
_MOE_SCORE = _telemetry.counter(
    "moe_score_total",
    "SparseMoE routers by the scoring of their experts (trace-time)",
    ("score",))


@register("SparseMoE", nin=-1, aliases=("sparsemoe",), nout=2, visible=1,
          aux_writeback={1: -1},
          params={"num_experts": param(int, 0, required=True),
                  "num_experts_per_tok": param(int, 0, required=True),
                  "num_hidden": param(int, 0, required=True),
                  "num_held": param(int, 0),
                  "expert_offset": param(int, 0),
                  "score": param(("sigmoid", "softmax"), "sigmoid"),
                  "routed_scaling": param(float, 1.0),
                  "weight_eps": param(float, 1e-6)})
def _sparse_moe(attrs, data, router_weight, *rest):
    """Sparse mixture of gated (SiLU) experts that is told which experts it
    holds, routed by sigmoid scores under a selection bias (the LFM2 /
    DeepSeek-V3 style) or by softmax scores (``score``).

    Inputs: ``data, router_weight, expert_bias, expert_gate_weight,
    expert_up_weight, expert_down_weight, expert_load``; under
    ``score="softmax"`` there is no ``expert_bias`` (six inputs: no leaf
    for an optimizer or a checkpoint to carry).

    Routing runs over all ``num_experts``: ``s = sigmoid(x · W_gᵀ)`` in
    float32, ``sel = top_k(s + expert_bias)`` (the bias, a buffer, takes no
    gradient and only steers the selection), weights ``w_e = s_e`` for
    ``e`` in ``sel``, divided by ``sum_sel s + weight_eps`` (the source's
    ``norm_topk_prob``; 1e-6 is LFM2's, GLM-4.7-Flash states 1e-20) and
    multiplied by ``routed_scaling`` (the source's
    ``routed_scaling_factor``: 1 leaves the program as it was, GLM's is
    1.8; it scales the softmax weights alike).  Under ``softmax``: ``s = softmax(x · W_gᵀ)``
    over all experts in float32, ``sel = top_k(s)``, ``w_e = s_e / sum_sel
    s`` (softmax, then top-k, then normalised over the selected).  The op
    HOLDS the
    experts ``expert_offset .. expert_offset + held`` (``held`` is the
    leading axis of the three expert weights; ``num_held`` only tells shape
    inference how many to allocate, 0 meaning all) and returns
    ``sum_{e in sel, e held} w_e * Expert_e(x)`` with
    ``Expert_e(x) = (silu(x · G_e) * (x · U_e)) · D_e``: its own experts'
    part of the layer's result.  What other holders' experts add is theirs
    to compute; summed over the holders the parts are the whole layer
    (``tests/test_lfm2_ops.py``).  On one chip there is no exchange.

    Shapes are static, no token is dropped and the cost does not follow the
    routing: every held expert runs over EVERY token, as one gated
    feed-forward of ``held x num_hidden`` columns (three dense products
    forward, six backward), and a token's column block of expert ``e`` is
    scaled by ``w_e`` where the token selected ``e`` and by nought where it
    did not (path ``dense`` of ``moe_dispatch_total``, the one formulation).
    That pays for ``tokens x held`` rows where ``tokens x k x held /
    num_experts`` are routed on average, ``num_experts / k`` times the
    required operations (16 times at LFM2's 64 / 4); in exchange there is no
    sort, no gather and no slot buffer, any imbalance costs the same, and a
    step's time is the same whatever the router has learnt.  A grouped
    product over the rows really routed is the next step once a balancing
    rule bounds the loads (ROADMAP R2).  The expert weights lie (expert, out,
    in), one ``FullyConnected`` matrix an expert as the source keeps them:
    ``expert_gate_weight`` and ``expert_up_weight`` (held, num_hidden, dim),
    ``expert_down_weight`` (held, dim, num_hidden); laid (expert, in, out)
    the optimizer's float32 state is copied into the gradient's layout and
    back every step.  ``router_weight`` (num_experts, dim) and
    ``expert_bias`` (num_experts,) stay float32 under the bf16 policy.

    ``expert_load`` (num_experts,) is an auxiliary state as BatchNorm's
    moving statistics are: in training it is overwritten with the number of
    selections each of the ``num_experts`` experts got in this step.  No
    reference analog; ``parallel/moe.py`` is the older functional layer
    (softmax gate, fixed capacity, drops tokens)."""
    score = attrs.get("score", "sigmoid")
    if len(rest) != 4 + (score == "sigmoid"):
        raise MXNetError(
            "SparseMoE: score=%r takes %d inputs (expert_bias only under "
            "sigmoid), got %d" % (score, 6 + (score == "sigmoid"),
                                  2 + len(rest)))
    expert_bias = rest[0] if score == "sigmoid" else None
    expert_gate_weight, expert_up_weight, expert_down_weight, expert_load = \
        rest[-4:]
    E, k = attrs["num_experts"], attrs["num_experts_per_tok"]
    held, off = expert_gate_weight.shape[0], attrs["expert_offset"]
    if not 0 < k <= E or off < 0 or off + held > E:
        raise MXNetError(
            "SparseMoE: experts %d..%d of %d, %d a token"
            % (off, off + held, E, k))
    D = data.shape[-1]
    x = data.reshape(-1, D)

    # ---- router: float32, all E experts
    logits = jnp.matmul(x.astype(jnp.float32),
                        router_weight.astype(jnp.float32).T,
                        precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)                      # [N, E]
        _, sel = jax.lax.top_k(s, k)                             # [N, k]
        w = jnp.take_along_axis(s, sel, axis=-1)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    else:
        s = jax.nn.sigmoid(logits)
        biased = s + jax.lax.stop_gradient(expert_bias.astype(jnp.float32))
        _, sel = jax.lax.top_k(biased, k)
        w = jnp.take_along_axis(s, sel, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True)
                 + attrs.get("weight_eps", 1e-6))
    if attrs.get("routed_scaling", 1.0) != 1.0:
        w = w * attrs["routed_scaling"]

    # ---- the held experts over every token; a token's weight for a held
    # expert it did not select is nought (one_hot of an index >= held)
    here = jax.nn.one_hot((sel - off) % E, held, dtype=w.dtype)  # [N, k, held]
    w_held = jnp.sum(w[..., None] * here, axis=1)                # [N, held]
    if _telemetry.enabled:
        # graftlint: disable=GL002 -- counts compiled variants, not calls
        _MOE_DISPATCH.labels(path="dense").inc()
        # graftlint: disable=GL002 -- counts compiled variants, not calls
        _MOE_SCORE.labels(score=score).inc()
    # (the benchmark's roofline metric finds the products by these subscripts,
    # which jnp.einsum leaves in the events' scope)
    gate = jnp.einsum("nd,efd->nef", x, expert_gate_weight)
    up = jnp.einsum("nd,efd->nef", x, expert_up_weight)
    h = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32) \
        * w_held[..., None]
    y = jnp.einsum("nef,edf->nd", h.astype(x.dtype), expert_down_weight)
    counts = jnp.sum(jax.nn.one_hot(sel, E, dtype=jnp.int32), axis=(0, 1))
    return y.reshape(data.shape), counts.astype(expert_load.dtype)


@register("InstanceNorm", nin=3, aliases=("instancenorm",),
          params={"eps": param(float, 1e-3)})
def _instance_norm(attrs, data, gamma, beta):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return ((data - mean) * jax.lax.rsqrt(var + attrs["eps"])
            * gamma.reshape(shape) + beta.reshape(shape))


@register("L2Normalization", nin=1,
          params={"eps": param(float, 1e-10),
                  "mode": param(["instance", "channel", "spatial"], "instance")})
def _l2_normalization(attrs, data):
    mode = attrs["mode"]
    if mode == "instance":
        red = tuple(range(1, data.ndim))
    elif mode == "channel":
        red = (1,)
    else:
        red = tuple(range(2, data.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True)
                    + attrs["eps"])
    return data / norm


@register("LRN", nin=1, aliases=("lrn",), nout=2, visible=1,
          params={"alpha": param(float, 1e-4), "beta": param(float, 0.75),
                  "knorm": param(float, 2.0), "nsize": param(int, 0, required=True)})
def _lrn(attrs, data):
    """Local response norm across channels (ref: src/operator/nn/lrn.cc)."""
    n = attrs["nsize"]
    half = n // 2
    sq = jnp.square(data)
    # sum over channel window via padded cumulative trick
    pad = [(0, 0)] * data.ndim
    pad[1] = (half, half)
    sqp = jnp.pad(sq, pad)
    window = [1] * data.ndim
    window[1] = n
    ssum = jax.lax.reduce_window(sqp, 0.0, jax.lax.add, tuple(window),
                                 (1,) * data.ndim, "valid")
    scale = (attrs["knorm"] + attrs["alpha"] * ssum / n) ** attrs["beta"]
    return data / scale, scale


# --------------------------------------------------------------------------
# softmax family
# --------------------------------------------------------------------------
@register("softmax", nin=1, params={"axis": param(int, -1),
                                    "temperature": param(float, None),
                                    "dtype": param("dtype", None)})
def _softmax(attrs, x):
    t = attrs["temperature"]
    if t is not None and t != 1.0:
        x = x / t
    out = jax.nn.softmax(x, axis=attrs["axis"])
    return out.astype(np.dtype(attrs["dtype"])) if attrs["dtype"] else out


@register("log_softmax", nin=1, params={"axis": param(int, -1),
                                        "temperature": param(float, None)})
def _log_softmax(attrs, x):
    t = attrs["temperature"]
    if t is not None and t != 1.0:
        x = x / t
    return jax.nn.log_softmax(x, axis=attrs["axis"])


@register("SoftmaxActivation", nin=1,
          params={"mode": param(["instance", "channel"], "instance")})
def _softmax_activation(attrs, x):
    axis = 1 if attrs["mode"] == "channel" else -1
    if attrs["mode"] == "instance" and x.ndim > 2:
        return jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)
    return jax.nn.softmax(x, axis=axis)


_SOFTMAX_OUT_PARAMS = {
    "grad_scale": param(float, 1.0),
    "ignore_label": param(float, -1.0),
    "multi_output": param(bool, False),
    "use_ignore": param(bool, False),
    "preserve_shape": param(bool, False),
    "normalization": param(["null", "batch", "valid"], "null"),
    "out_grad": param(bool, False),
    "smooth_alpha": param(float, 0.0),
}


def _softmax_output_impl(attrs, data, label):
    if attrs["multi_output"]:
        prob = jax.nn.softmax(data, axis=1)
    elif attrs["preserve_shape"]:
        prob = jax.nn.softmax(data, axis=-1)
    else:
        prob = jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1)
        prob = prob.reshape(data.shape)
    return prob


@register("SoftmaxOutput", nin=2, aliases=("softmaxoutput", "Softmax"),
          params=dict(_SOFTMAX_OUT_PARAMS))
def _softmax_output(attrs, data, label):
    """Softmax with implicit cross-entropy gradient
    (ref: src/operator/softmax_output.cc).  Forward = softmax(data); the
    backward is (p - onehot(label)) * grad_scale with ignore-label masking —
    expressed as a custom VJP so autograd/Symbol backward matches the
    reference exactly (the incoming head gradient is ignored, as in MXNet)."""

    @jax.custom_vjp
    def _fwd(d, l):
        return _softmax_output_impl(attrs, d, l)

    def _fwd_fwd(d, l):
        p = _softmax_output_impl(attrs, d, l)
        return p, (p, l)

    def _fwd_bwd(res, g):
        p, l = res
        axis = 1 if attrs["multi_output"] else -1
        if attrs["multi_output"]:
            lab = l.astype(jnp.int32)
            oh = jax.nn.one_hot(lab, p.shape[1], dtype=p.dtype, axis=1)
        else:
            flat_label = l.reshape(l.shape[0], -1) if l.ndim > 1 else l
            lab = flat_label.astype(jnp.int32)
            oh = jax.nn.one_hot(lab.reshape(p.shape[:-1]), p.shape[-1],
                                dtype=p.dtype)
        grad = (p - oh)
        if attrs["use_ignore"]:
            mask = (l != attrs["ignore_label"]).astype(p.dtype)
            mask = jnp.expand_dims(mask, 1 if attrs["multi_output"] else -1)
            grad = grad * mask
        scale = attrs["grad_scale"]
        if attrs["normalization"] == "batch":
            scale = scale / p.shape[0]
        elif attrs["normalization"] == "valid" and attrs["use_ignore"]:
            nvalid = jnp.maximum(jnp.sum(l != attrs["ignore_label"]), 1)
            scale = scale / nvalid
        return grad * scale, jnp.zeros_like(l)

    _fwd.defvjp(_fwd_fwd, _fwd_bwd)
    # loss head: low-precision logits go through the exp/sum reduction in
    # f32 (keyed on input dtype, never on env — GL002); output prob stays
    # f32 so downstream loss reduction is full precision.  The cast sits
    # OUTSIDE the custom VJP so its transpose re-casts the f32 head
    # gradient back to the logits' storage dtype automatically.
    if data.dtype in (jnp.bfloat16, jnp.float16):
        data = data.astype(jnp.float32)
    return _fwd(data, label)


def streaming_ce(logits, labels, axis=-1):
    """Per-example softmax cross-entropy via streaming logsumexp.

    ``logsumexp(logits) - logits[label]`` in f32 — mathematically identical
    to ``-log_softmax(logits)[label]`` (ref: python/mxnet/gluon/loss.py:304
    and src/operator/loss_binary_op.cc) but never materializes the
    ``(N, vocab)`` f32 log-softmax: only the two ``(N,)`` reductions leave
    registers.  The custom VJP emits ``(softmax - onehot)`` directly in the
    logits dtype, so the backward carries a bf16 — not f32 — ``(N, vocab)``
    intermediate.  Measured +23% tokens/s on the LSTM LM bench where the
    600 MB f32 intermediate was ~1/3 of the device step.
    """
    axis = axis % logits.ndim

    @jax.custom_vjp
    def _ce(lg, lab):
        return _fwd(lg, lab)[0]

    def _fwd(lg, lab):
        lgm = jnp.moveaxis(lg, axis, -1)
        lab_i = lab.astype(jnp.int32)
        # logsumexp unrolled so the f32 upcast feeds exactly ONE reduction:
        # max runs on the input dtype (max never rounds), leaving the
        # convert→sub→exp chain a single-consumer elementwise producer that
        # XLA fuses into the sum — no (N, V) f32 buffer is ever allocated
        # (jax.scipy logsumexp's f32 input feeds both reductions, which
        # makes XLA materialize the converted array)
        m = jnp.max(lgm, axis=-1)
        m32 = jnp.where(jnp.isfinite(m), m, 0).astype(jnp.float32)
        z = jnp.sum(jnp.exp(lgm.astype(jnp.float32) - m32[..., None]),
                    axis=-1)
        lse = m32 + jnp.log(z)
        picked = jnp.take_along_axis(lgm, lab_i[..., None], axis=-1)[..., 0]
        return lse - picked.astype(jnp.float32), (lgm, lab, lse)

    def _bwd(res, g):
        lgm, lab, lse = res
        # softmax recomputed in the logits dtype: exp(x - lse) fuses into
        # the one_hot subtraction, no f32 (N, V) buffer in the backward
        p = jnp.exp(lgm - lse.astype(lgm.dtype)[..., None])
        oh = jax.nn.one_hot(lab.astype(jnp.int32), lgm.shape[-1],
                            dtype=lgm.dtype)
        gm = g.astype(lgm.dtype)[..., None] * (p - oh)
        lab_ct = (jnp.zeros_like(lab)
                  if jnp.issubdtype(lab.dtype, jnp.inexact)
                  else jnp.zeros(lab.shape, jax.dtypes.float0))
        return jnp.moveaxis(gm, -1, axis), lab_ct

    _ce.defvjp(lambda lg, lab: _fwd(lg, lab), _bwd)
    return _ce(logits, labels)


@register("streaming_softmax_ce", nin=2,
          params={"axis": param(int, -1), "keepdims": param(bool, False)})
def _streaming_softmax_ce_op(attrs, data, label):
    """Registered form of :func:`streaming_ce` — the fused sparse-label CE
    used by ``gluon.loss.SoftmaxCrossEntropyLoss`` in place of the
    reference's log_softmax+pick composition."""
    out = streaming_ce(data, label, attrs["axis"])
    return jnp.expand_dims(out, attrs["axis"] % data.ndim) \
        if attrs["keepdims"] else out


@register("block_diffusion_ce", nin=3,
          params={"block": param(int, 0, required=True),
                  "mask_id": param(int, 0, required=True)})
def _block_diffusion_ce(attrs, data, label, noised):
    """The masked-diffusion objective over blocks (BD3-LM's; SDAR's), per
    token: with ``data`` the logits of the noised copy (B, L, V), ``label``
    the clean ids x_0 (B, L) and ``noised`` the noised ids x_t (B, L),

        out_i = (b / m) * CE(data_i, x_0[i])   where x_t[i] is ``mask_id``
        out_i = 0                              elsewhere

    with ``m`` the masked positions of i's block of ``block`` = b: the
    ELBO's 1 / t of a block masked at the rate t = m / b.  The mean of the
    result over the B x L positions is the loss.  The cross-entropy is
    ``streaming_ce``'s; the weights read ``noised`` and carry no
    gradient."""
    b = attrs["block"]
    B, L = noised.shape
    if b < 1 or L % b or label.shape != noised.shape \
            or data.shape[:2] != noised.shape:
        raise MXNetError(
            "block_diffusion_ce: logits (B, L, V), clean and noised ids "
            "(B, L), L whole blocks of block=%d; got %s, %s, %s"
            % (b, data.shape, label.shape, noised.shape))
    masked = (noised == attrs["mask_id"]).astype(jnp.float32)
    m = jnp.sum(masked.reshape(B, L // b, b), axis=-1, keepdims=True)
    weight = (masked.reshape(B, L // b, b) * (b / jnp.maximum(m, 1.0)))
    return streaming_ce(data, label, -1) * weight.reshape(B, L)


@register("softmax_cross_entropy", nin=2)
def _softmax_cross_entropy(attrs, data, label):
    """Total CE over the batch (ref: src/operator/loss_binary_op.cc),
    lowered to the streaming logsumexp formulation."""
    return jnp.sum(streaming_ce(data, label, -1)).astype(data.dtype)


@register("LinearRegressionOutput", nin=2, aliases=("linearregressionoutput",),
          params={"grad_scale": param(float, 1.0)})
def _linear_regression_output(attrs, data, label):
    @jax.custom_vjp
    def _fwd(d, l):
        return d

    def _f(d, l):
        return d, (d, l)

    def _b(res, g):
        d, l = res
        return ((d - l.reshape(d.shape)) * attrs["grad_scale"],
                jnp.zeros_like(l))

    _fwd.defvjp(_f, _b)
    return _fwd(data, label)


@register("LogisticRegressionOutput", nin=2, aliases=("logisticregressionoutput",),
          params={"grad_scale": param(float, 1.0)})
def _logistic_regression_output(attrs, data, label):
    @jax.custom_vjp
    def _fwd(d, l):
        return jax.nn.sigmoid(d)

    def _f(d, l):
        p = jax.nn.sigmoid(d)
        return p, (p, l)

    def _b(res, g):
        p, l = res
        return ((p - l.reshape(p.shape)) * attrs["grad_scale"], jnp.zeros_like(l))

    _fwd.defvjp(_f, _b)
    return _fwd(data, label)


@register("MAERegressionOutput", nin=2, aliases=("maeregressionoutput",),
          params={"grad_scale": param(float, 1.0)})
def _mae_regression_output(attrs, data, label):
    @jax.custom_vjp
    def _fwd(d, l):
        return d

    def _f(d, l):
        return d, (d, l)

    def _b(res, g):
        d, l = res
        return (jnp.sign(d - l.reshape(d.shape)) * attrs["grad_scale"],
                jnp.zeros_like(l))

    _fwd.defvjp(_f, _b)
    return _fwd(data, label)


# --------------------------------------------------------------------------
# dropout
# --------------------------------------------------------------------------
@register("Dropout", nin=1, aliases=("dropout",), needs_rng=True,
          train_aware=True, nout=2, visible=1,
          params={"p": param(float, 0.5),
                  "mode": param(["training", "always"], "training"),
                  "axes": param("shape", ()),
                  "cudnn_off": param(bool, False),
                  "__train__": param(bool, False)})
def _dropout(attrs, key, data):
    """Inverted dropout (ref: src/operator/nn/dropout.cc); returns
    (out, mask)."""
    p = attrs["p"]
    active = attrs.get("__train__") or attrs["mode"] == "always"
    if not active or p == 0.0:
        return data, jnp.ones_like(data)
    shape = data.shape
    if attrs["axes"]:
        shape = tuple(1 if i in attrs["axes"] else s
                      for i, s in enumerate(data.shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, shape).astype(data.dtype) / keep
    return data * mask, jnp.broadcast_to(mask, data.shape)


@register("UpSampling", nin=-1, aliases=("upsampling",),
          params={"scale": param(int, 1, required=True),
                  "num_filter": param(int, 0),
                  "sample_type": param(["nearest", "bilinear"], "nearest"),
                  "multi_input_mode": param(["concat", "sum"], "concat"),
                  "num_args": param(int, 1),
                  "workspace": param(int, 512)})
def _upsampling(attrs, *inputs):
    s = attrs["scale"]
    outs = []
    for x in inputs:
        if attrs["sample_type"] == "nearest":
            y = jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3)
        else:
            n, c, h, w = x.shape
            y = jax.image.resize(x, (n, c, h * s, w * s), method="bilinear")
        outs.append(y)
    if len(outs) == 1:
        return outs[0]
    if attrs["multi_input_mode"] == "sum":
        out = outs[0]
        for y in outs[1:]:
            out = out + y
        return out
    return jnp.concatenate(outs, axis=1)


@register("Crop", nin=-1, aliases=("crop_like",),
          params={"offset": param("shape", (0, 0)),
                  "h_w": param("shape", (0, 0)),
                  "num_args": param(int, 1),
                  "center_crop": param(bool, False)})
def _crop_op(attrs, data, *maybe_like):
    if maybe_like:
        th, tw = maybe_like[0].shape[2:4]
    else:
        th, tw = attrs["h_w"]
    h, w = data.shape[2:4]
    if attrs["center_crop"]:
        oy, ox = (h - th) // 2, (w - tw) // 2
    else:
        oy, ox = attrs["offset"]
    return data[:, :, oy:oy + th, ox:ox + tw]
