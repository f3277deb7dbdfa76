"""Pallas implicit-GEMM 2-D convolution for the ResNet bottleneck shapes.

docs/perf_analysis.md (rounds 2-5) established that ResNet-50 training is
bound by XLA's in-graph conv efficiency (~35-45 TF aggregate) while the
same chip sustains 125 TF on matmuls, and that both pure-XLA
reformulations (9-shifted-GEMM forward, per-tap GEMM wgrad) were
e2e-measured and rejected.  This module is the remaining lever — the
hand-written kernel path `ops/pallas_attention.py` / `ops/pallas_rnn.py`
already proved out — productionized from the round-3 probe prototype
(`tools/probe_pallas_conv.py`, measured 87-171 TF on the eligible
3x3 shapes, real chip).

Formulation: implicit GEMM over flattened padded row-frames.  The NHWC
activation is padded to (Hp, WP) per image and flattened to rows of C;
an output position k = h*WP + w then reads input row k + dh*WP + dw for
tap (dh, dw) — so each tap is ONE contiguous row-slice matmul
(TILE, C) @ (C, O) on the MXU, accumulated in f32 across the KH*KW taps
with no im2col materialization in HBM and zero in-kernel relayouts.
Images are laid out on a common 8-aligned frame stride L so NB of them
stack into one grid step (small-spatial shapes keep the MXU fed); the
input BlockSpec is element-indexed (``pl.Element``) because tap halos
overlap tiles.

Backward is a ``custom_vjp`` whose both arms are also Pallas kernels, one
a gradient (``flash_attention_bwd`` had that two-pass structure until its
two kernels became one, which shares the recomputed scores; dgrad and
wgrad share nothing to recompute):

  dgrad: dx = conv_s1(dy, flip(W)^T) — the SAME forward kernel on the
         cotangent with spatially-flipped, io-swapped taps (exact for
         stride-1 SAME).
  wgrad: dw[tap] = x_tap^T @ dy — one (TILE, C)^T @ (TILE, O) GEMM per
         tap per grid step, accumulated across the sequential TPU grid
         into a VMEM-resident (KH*KW, C, O) f32 output (the revisited-
         block reduction pattern).

Eligibility (`conv3x3_same_available` / `conv3x3_s2_available`) mirrors
``flash_attention_available``: env flag + lane/VMEM size gates only;
non-TPU platforms are ineligible unless ``INTERPRET`` (tests run the
same jaxpr on CPU via interpret mode).  The lane gate requires
C % 128 == 0: the round-3 probe measured the C=64 56px shape at 10 TF
(lane-starved contraction) vs 96-171 TF for the 128/256/512-channel
shapes.  Stride-2 3x3 convs ride the same stride-1 core through an
exact space-to-depth(2) rewrite (2x2 taps on 4C channels — the same
transform as ``ops/nn.py:_stem_s2d_conv``); their backward stays on
XLA's transposed-conv lowering.

``MXNET_TPU_PALLAS_CONV`` defaults OFF: every prior hand-conv probe
(r3 forward, r4 shifted-GEMM, r5 GEMM-wgrad) won isolated chains and
lost e2e to whole-graph scheduling, so per the repo's wire-and-re-bench
discipline the flag ships off until a chip session measures an e2e win
(tools/probe_pallas_conv.py emits the per-shape JSON for that session).
The flag is part of the Convolution jit-cache key (ops/registry.py), so
toggling it takes effect immediately — no cache clearing or process
restart.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["INTERPRET", "conv3x3_same", "conv3x3_same_available",
           "conv3x3_s2", "conv3x3_s2_available"]

#: tests flip this to run the kernels' jaxpr on CPU (same pattern as
#: pallas_attention.INTERPRET); it also lifts the TPU-platform gate.
INTERPRET = False

#: conservative per-kernel VMEM budget (the 16 MB scoped limit minus
#: headroom for Mosaic's own spills — same margin pallas_rnn uses).
_VMEM_BUDGET = 12 * 1024 * 1024

_PadsT = Tuple[Tuple[int, int], Tuple[int, int]]


def _align(v: int, m: int) -> int:
    return (v + m - 1) // m * m


class _Plan(NamedTuple):
    """Static frame geometry for one (shape, taps, pads) conv instance."""
    NB: int        # images stacked per grid step
    G: int         # grid size (N // NB)
    L: int         # 8-aligned per-image frame stride, rows of channels
    TILE: int      # output rows per grid step (NB * L)
    SLAB: int      # input rows fetched per grid step (TILE + tap halo)
    WP: int        # padded width (frame row length)
    Hp: int        # padded height
    Ho: int        # output height
    Wo: int        # output width
    F_in: int      # valid input frame rows (Hp * WP)
    F_out: int     # output frame rows (Ho * WP)
    total: int     # padded flat input length


def _frame_geometry(H, W, KH, KW, pads):
    (pt, pb), (pw_l, pw_r) = pads
    Hp, WP = H + pt + pb, W + pw_l + pw_r
    Ho, Wo = Hp - KH + 1, WP - KW + 1
    return Hp, WP, Ho, Wo


def _est_bytes(plan: _Plan, C, O, KH, KW, esize):
    """Worst-case VMEM residency across the fwd/dgrad/wgrad kernels:
    double-buffered input slab + output tile, f32 accumulator, and either
    the tap weights (fwd/dgrad) or the grid-resident wgrad accumulator."""
    cm = max(C, O)
    fwd = (2 * plan.SLAB * cm * esize + 2 * plan.TILE * cm * esize
           + plan.TILE * cm * 4 + KH * KW * C * O * esize)
    wgrad = (2 * plan.SLAB * C * esize + 2 * plan.TILE * O * esize
             + KH * KW * C * O * 4)
    return max(fwd, wgrad)


def _plan(N, H, W, C, O, KH, KW, pads: _PadsT, esize) -> Optional[_Plan]:
    """Largest batch-stacking NB whose VMEM estimate fits the budget."""
    Hp, WP, Ho, Wo = _frame_geometry(H, W, KH, KW, pads)
    F_in, F_out = Hp * WP, Ho * WP
    L = _align(max(F_in, F_out), 8)
    halo = (KH - 1) * WP + (KW - 1)
    for NB in (16, 8, 4, 2, 1):
        if N % NB:
            continue
        TILE = NB * L
        SLAB = _align(TILE + halo, 8)
        G = N // NB
        total = _align((G - 1) * TILE + SLAB, 8)
        p = _Plan(NB, G, L, TILE, SLAB, WP, Hp, Ho, Wo, F_in, F_out, total)
        if _est_bytes(p, C, O, KH, KW, esize) <= _VMEM_BUDGET:
            return p
    return None


def _flatten_frames(x, pads: _PadsT, plan: _Plan, total=None):
    """(N, H, W, C) -> (rows, C) padded row-frames on the L stride."""
    N = x.shape[0]
    C = x.shape[-1]
    (pt, pb), (pw_l, pw_r) = pads
    xp = jnp.pad(x, ((0, 0), (pt, pb), (pw_l, pw_r), (0, 0)))
    F = xp.shape[1] * xp.shape[2]
    xf = xp.reshape(N, F, C)
    xf = jnp.pad(xf, ((0, 0), (0, plan.L - F), (0, 0))).reshape(N * plan.L, C)
    if total is not None and total > N * plan.L:
        xf = jnp.pad(xf, ((0, total - N * plan.L), (0, 0)))
    return xf


# ------------------------------------------------------------------ kernels
def _taps_kernel(x_ref, w_ref, o_ref, *, TILE, WP, KH, KW):
    """Implicit-GEMM forward: one row-slice matmul per tap, f32 acc."""
    acc = None
    for dh in range(KH):
        for dw in range(KW):
            xs = x_ref[pl.ds(dh * WP + dw, TILE), :]
            p = jax.lax.dot_general(
                xs, w_ref[dh * KW + dw], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = p if acc is None else acc + p
    o_ref[:] = acc.astype(o_ref.dtype)


def _wgrad_kernel(x_ref, g_ref, o_ref, *, TILE, WP, KH, KW):
    """dw[tap] += x_tap^T @ dy, accumulated across the sequential grid
    into the VMEM-resident (KH*KW, C, O) f32 output block."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
    gt = g_ref[:]
    for dh in range(KH):
        for dw in range(KW):
            xs = x_ref[pl.ds(dh * WP + dw, TILE), :]
            o_ref[dh * KW + dw] += jax.lax.dot_general(
                xs, gt, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def _conv_s1(x, w_taps, pads: _PadsT, KH, KW, plan: _Plan = None):
    """Stride-1 implicit-GEMM conv.  x: (N, H, W, C) NHWC;
    w_taps: (KH*KW, C, O); returns (N, Ho, Wo, O) in x.dtype."""
    N, H, W, C = x.shape
    O = w_taps.shape[-1]
    p = plan or _plan(N, H, W, C, O, KH, KW, pads,
                      jnp.dtype(x.dtype).itemsize)
    if p is None:
        raise ValueError("pallas_conv: no VMEM-feasible plan for shape "
                         f"{x.shape} x {w_taps.shape}")
    xf = _flatten_frames(x, pads, p, total=p.total)
    kern = functools.partial(_taps_kernel, TILE=p.TILE, WP=p.WP,
                             KH=KH, KW=KW)
    out = pl.pallas_call(
        kern,
        grid=(p.G,),
        in_specs=[
            # element-indexed: tap halos make consecutive slabs overlap
            pl.BlockSpec((pl.Element(p.SLAB), pl.Element(C)),
                         lambda g, _p=p: (g * _p.TILE, 0)),
            pl.BlockSpec((KH * KW, C, O), lambda g: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((p.TILE, O), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((N * p.L, O), x.dtype),
        interpret=INTERPRET,
    )(xf, w_taps)
    return (out.reshape(N, p.L, O)[:, :p.F_out]
            .reshape(N, p.Ho, p.WP, O)[:, :, :p.Wo])


def _wgrad_s1(x, g, pads: _PadsT, KH, KW, plan: _Plan = None):
    """Per-tap GEMM weight gradient.  x: (N, H, W, C); g: (N, Ho, Wo, O)
    cotangent; returns (KH*KW, C, O) f32."""
    N, H, W, C = x.shape
    O = g.shape[-1]
    p = plan or _plan(N, H, W, C, O, KH, KW, pads,
                      jnp.dtype(x.dtype).itemsize)
    if p is None:
        raise ValueError("pallas_conv: no VMEM-feasible wgrad plan for "
                         f"shape {x.shape}")
    xf = _flatten_frames(x, pads, p, total=p.total)
    # the cotangent rides the SAME L-stride frame layout, zero outside
    # (Ho, Wo) — garbage input rows then multiply a zero cotangent row
    gp = jnp.pad(g, ((0, 0), (0, 0), (0, p.WP - p.Wo), (0, 0)))
    gf = gp.reshape(N, p.F_out, O)
    gf = jnp.pad(gf, ((0, 0), (0, p.L - p.F_out), (0, 0)))
    gf = gf.reshape(N * p.L, O)
    kern = functools.partial(_wgrad_kernel, TILE=p.TILE, WP=p.WP,
                             KH=KH, KW=KW)
    return pl.pallas_call(
        kern,
        grid=(p.G,),
        in_specs=[
            pl.BlockSpec((pl.Element(p.SLAB), pl.Element(C)),
                         lambda g_, _p=p: (g_ * _p.TILE, 0)),
            pl.BlockSpec((p.TILE, O), lambda g_: (g_, 0)),
        ],
        out_specs=pl.BlockSpec((KH * KW, C, O), lambda g_: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((KH * KW, C, O), jnp.float32),
        interpret=INTERPRET,
    )(xf, gf)


# -------------------------------------------------------------- eligibility
def _platform_ok() -> bool:
    """Mosaic kernels only lower on TPU; interpret mode runs anywhere."""
    return INTERPRET or jax.default_backend() == "tpu"


def _flag_on() -> bool:
    return os.environ.get("MXNET_TPU_PALLAS_CONV", "0") == "1"


def conv3x3_same_available(N, H, W, C, O, dtype=None) -> bool:
    """ENV/size eligibility for the 3x3 / stride-1 / SAME kernel class.

    Gates, each measured (docs/perf_analysis.md round 3/6):
    - lane gate C % 128 == 0 and O % 128 == 0 — the MXU pads the
      contraction/output dims to full lane tiles; C=64 measured 10 TF.
    - VMEM plan exists (slab + taps + accumulators within budget).
    """
    if not (_flag_on() and _platform_ok()):
        return False
    if C % 128 or O % 128:
        return False
    esize = jnp.dtype(dtype).itemsize if dtype is not None else 2
    return _plan(N, H, W, C, O, 3, 3, ((1, 1), (1, 1)), esize) is not None


def conv3x3_s2_available(N, H, W, C, O, dtype=None) -> bool:
    """Eligibility for 3x3 / stride-2 / pad-1 via the space-to-depth
    rewrite: even spatial dims, 4C lanes full, VMEM plan for the
    (2x2-tap, 4C-channel) stride-1 form on the halved grid."""
    if not (_flag_on() and _platform_ok()):
        return False
    if H % 2 or W % 2 or (4 * C) % 128 or O % 128:
        return False
    esize = jnp.dtype(dtype).itemsize if dtype is not None else 2
    return _plan(N, H // 2, W // 2, 4 * C, O, 2, 2,
                 ((1, 0), (1, 0)), esize) is not None


# ---------------------------------------------------- 3x3 / s1 / SAME class
_S1_PADS: _PadsT = ((1, 1), (1, 1))


def _nhwc(x):
    return jnp.transpose(x, (0, 2, 3, 1))


def _nchw(x):
    return jnp.transpose(x, (0, 3, 1, 2))


@jax.custom_vjp
def conv3x3_same(data, weight):
    """3x3 / stride-1 / SAME / ungrouped conv, NCHW data + OIHW weight,
    all three directions on Pallas implicit-GEMM kernels."""
    O = weight.shape[0]
    taps = weight.transpose(2, 3, 1, 0).reshape(9, weight.shape[1], O)
    out = _conv_s1(_nhwc(data), taps.astype(data.dtype), _S1_PADS, 3, 3)
    return _nchw(out)


def _c3s_fwd(data, weight):
    return conv3x3_same(data, weight), (data, weight)


def _c3s_bwd(res, g):
    data, weight = res
    O, C = weight.shape[:2]
    gh = _nhwc(g)
    # dgrad = the forward kernel on the cotangent with spatially-flipped,
    # io-swapped taps (exact for stride-1 SAME)
    taps_d = (jnp.flip(weight, (2, 3)).transpose(2, 3, 0, 1)
              .reshape(9, O, C))
    dx = _conv_s1(gh, taps_d.astype(g.dtype), _S1_PADS, 3, 3)
    # wgrad = per-tap GEMM kernel, f32 accumulation across the grid
    dwf = _wgrad_s1(_nhwc(data), gh, _S1_PADS, 3, 3)
    dw = dwf.reshape(3, 3, C, O).transpose(3, 2, 0, 1)
    return _nchw(dx).astype(data.dtype), dw.astype(weight.dtype)


conv3x3_same.defvjp(_c3s_fwd, _c3s_bwd)


# ------------------------------------------------- 3x3 / s2 / pad-1 class
def _s2d_data(x):
    """(N, C, H, W) -> (N, 4C, H/2, W/2), parity-major (p, q, c) layout
    (matches ops/nn.py:_stem_s2d_conv)."""
    N, C, H, W = x.shape
    xs = x.reshape(N, C, H // 2, 2, W // 2, 2)
    return xs.transpose(0, 3, 5, 1, 2, 4).reshape(N, 4 * C, H // 2, W // 2)


def _s2d_weight(w):
    """(O, C, 3, 3) stride-2 pad-1 kernel -> (O, 4C, 2, 2) stride-1
    equivalent with per-side pads ((1, 0), (1, 0)) on the s2d input."""
    O, C = w.shape[:2]
    wp = jnp.pad(w, ((0, 0), (0, 0), (1, 0), (1, 0)))
    w4 = wp.reshape(O, C, 2, 2, 2, 2)
    return w4.transpose(0, 3, 5, 1, 2, 4).reshape(O, 4 * C, 2, 2)


_S2_PADS: _PadsT = ((1, 0), (1, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def conv3x3_s2(data, weight):
    """3x3 / stride-2 / pad-1 / ungrouped conv via the exact s2d(2)
    rewrite: Pallas stride-1 forward on (2x2 taps, 4C channels);
    backward stays on XLA's transposed-conv lowering (the dilated dgrad
    shapes have no stride-1 implicit-GEMM form)."""
    w4 = _s2d_weight(weight)
    O, C4 = w4.shape[:2]
    taps = w4.transpose(2, 3, 1, 0).reshape(4, C4, O)
    out = _conv_s1(_nhwc(_s2d_data(data)), taps.astype(data.dtype),
                   _S2_PADS, 2, 2)
    return _nchw(out)


def _lax_s2_ref(data, weight):
    dn = jax.lax.conv_dimension_numbers(
        data.shape, weight.shape, ("NCHW", "OIHW", "NCHW"))
    return jax.lax.conv_general_dilated(
        data, weight, (2, 2), [(1, 1), (1, 1)], dimension_numbers=dn)


def _c3s2_fwd(data, weight):
    return conv3x3_s2(data, weight), (data, weight)


def _c3s2_bwd(res, g):
    data, weight = res
    _, vjp = jax.vjp(_lax_s2_ref, data, weight)
    dx, dw = vjp(g.astype(data.dtype))
    return dx.astype(data.dtype), dw.astype(weight.dtype)


conv3x3_s2.defvjp(_c3s2_fwd, _c3s2_bwd)
