#!/usr/bin/env python
"""Probe the three targeted conv fixes found by probe_resnet_step.py:

1. stem 7x7s2 C=3 -> space-to-depth(2) + 4x4s1 C=12 (exact rewrite)
2. strided 1x1 projection  -> slice x[::2,::2] then dense 1x1 matmul
3. 1x1 wgrad at 56x56 64<->256 -> Pallas reduction-GEMM kernel

Run:  python tools/probe_conv_fixes.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

REPS = 4


def time_chain(step, x0, chain):
    def build(n):
        @jax.jit
        def f(x):
            def body(c, _):
                return step(c) * jnp.bfloat16(0.25), None
            y, _ = jax.lax.scan(body, x, None, length=n)
            return jnp.sum(y.astype(jnp.float32))
        return f
    f1, f2 = build(chain), build(2 * chain)
    float(f1(x0)); float(f2(x0))
    best1 = best2 = 1e9
    for _ in range(REPS):
        t0 = time.perf_counter(); float(f1(x0))
        best1 = min(best1, time.perf_counter() - t0)
        t0 = time.perf_counter(); float(f2(x0))
        best2 = min(best2, time.perf_counter() - t0)
    return max(best2 - best1, 1e-9) / chain




def up2(y, H):
    """Exact 2x nearest upsample via broadcast (cheap, fusion-friendly)."""
    N, h, w, C = y.shape
    y = jnp.broadcast_to(y[:, :, None, :, None, :], (N, h, 2, w, 2, C))
    return y.reshape(N, 2 * h, 2 * w, C)

def conv(x, w, s=1, pad="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, (s, s), pad, dimension_numbers=("NHWC", "HWIO", "NHWC"))


def space_to_depth(x, b=2):
    N, H, W, C = x.shape
    x = x.reshape(N, H // b, b, W // b, b, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(N, H // b, W // b, b * b * C)


def stem_s2d_weights(w):
    """(7,7,3,64) -> (4,4,12,64) operating on space-to-depth(2) input.

    y[ho,wo] = sum_{dh,dw} x[2ho+dh-3, 2wo+dw-3] w[dh,dw].  Write
    dh-3 = 2e+p (p in {0,1}); then tap (e,p) multiplies s2d channel p at
    spatial offset ho+e, e in [-2,1] -> a 4x4 stride-1 conv over the
    (112,112,12) s2d input, padded by 2 low / 1 high.
    """
    w4 = np.zeros((4, 4, 12, w.shape[3]), np.float32)
    wn = np.asarray(w, np.float32)
    for dh in range(7):
        e_h, p_h = divmod(dh - 3, 2)       # x[2ho+dh-3] = s2d[ho+e_h, p_h]
        for dw in range(7):
            e_w, p_w = divmod(dw - 3, 2)
            # s2d channel layout: (p, q, c) -> p*2*3 + q*3 + c
            for c in range(3):
                w4[e_h + 2, e_w + 2, p_h * 6 + p_w * 3 + c] += wn[dh, dw, c]
    return jnp.asarray(w4, w.dtype)


def main():
    from mxnet_tpu import program_cache
    program_cache.place()       # the one decision on where compiles persist
    N = 128
    rng = np.random.default_rng(0)

    # ---------------- 1. stem --------------------------------------
    x = jnp.asarray(rng.standard_normal((N, 224, 224, 3)) * 0.1, jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((7, 7, 3, 64)) * 0.1, jnp.bfloat16)
    flops = 2 * N * 112 * 112 * 3 * 64 * 49
    mixw = jnp.asarray(rng.standard_normal((1, 1, 64, 3)) * 0.1, jnp.bfloat16)

    def stem_ref(c):
        y = jax.nn.relu(conv(c, w, 2))
        y = conv(y, mixw)
        return up2(y, 224)

    w4 = stem_s2d_weights(w)

    def stem_s2d(c):
        xs = space_to_depth(c, 2)                       # (N,112,112,12)
        xs = jnp.pad(xs, ((0, 0), (2, 1), (2, 1), (0, 0)))
        y = jax.nn.relu(conv(xs, w4, 1, "VALID"))
        y = conv(y, mixw)
        return up2(y, 224)

    ref = np.asarray(conv(x, w, 2).astype(jnp.float32))
    xs = jnp.pad(space_to_depth(x, 2), ((0, 0), (2, 1), (2, 1), (0, 0)))
    got = np.asarray(conv(xs, w4, 1, "VALID").astype(jnp.float32))
    err = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    t0 = time_chain(stem_ref, x, 64)
    t1 = time_chain(stem_s2d, x, 64)
    print(f"stem fwd: xla7x7 {t0*1e3:.3f}ms {flops/t0/1e12:.1f}TF | "
          f"s2d {t1*1e3:.3f}ms {flops/t1/1e12:.1f}TF  err={err:.0e}",
          flush=True)

    def train_ref(c):
        return jax.grad(lambda xx: jnp.sum(jax.nn.relu(
            conv(xx, w, 2)).astype(jnp.float32)))(c)

    def train_s2d(c):
        def f(xx):
            xs = space_to_depth(xx, 2)
            xs = jnp.pad(xs, ((0, 0), (2, 1), (2, 1), (0, 0)))
            return jnp.sum(jax.nn.relu(
                conv(xs, w4, 1, "VALID")).astype(jnp.float32))
        return jax.grad(f)(c)
    t0 = time_chain(train_ref, x, 64)
    t1 = time_chain(train_s2d, x, 64)
    print(f"stem f+d: xla7x7 {t0*1e3:.3f}ms | s2d {t1*1e3:.3f}ms", flush=True)

    # ---------------- 2. strided 1x1 projection --------------------
    x = jnp.asarray(rng.standard_normal((N, 56, 56, 256)) * 0.1, jnp.bfloat16)
    wp = jnp.asarray(rng.standard_normal((1, 1, 256, 512)) * 0.1, jnp.bfloat16)
    wb = jnp.asarray(rng.standard_normal((1, 1, 512, 256)) * 0.1, jnp.bfloat16)
    flops = 2 * N * 28 * 28 * 256 * 512

    def proj_ref(c):
        y = jax.nn.relu(conv(c, wp, 2))
        y = conv(y, wb)
        return up2(y, 56)

    def proj_slice(c):
        y = jax.nn.relu(conv(c[:, ::2, ::2, :], wp, 1))
        y = conv(y, wb)
        return up2(y, 56)

    t0 = time_chain(proj_ref, x, 96)
    t1 = time_chain(proj_slice, x, 96)
    print(f"proj1x1s2 fwd: conv-s2 {t0*1e3:.3f}ms {flops/t0/1e12:.1f}TF | "
          f"slice+mm {t1*1e3:.3f}ms {flops/t1/1e12:.1f}TF", flush=True)

    # ---------------- 3. Pallas wgrad GEMM for 1x1 -----------------
    H = W = 56
    Cs, Cl = 64, 256
    R = N * H * W                         # 401408 reduction rows
    x1 = jnp.asarray(rng.standard_normal((R, Cs)) * 0.1, jnp.bfloat16)
    g1 = jnp.asarray(rng.standard_normal((R, Cl)) * 0.1, jnp.bfloat16)
    flops = 2 * R * Cs * Cl

    def wgrad_xla(g):
        return jax.lax.dot_general(
            x1, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.bfloat16)

    TR = 4096

    def wgrad_kernel(x_ref, g_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)
        o_ref[:] += jax.lax.dot_general(
            x_ref[:], g_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def wgrad_pl(g):
        out = pl.pallas_call(
            wgrad_kernel,
            grid=(R // TR,),
            in_specs=[pl.BlockSpec((TR, Cs), lambda i: (i, 0)),
                      pl.BlockSpec((TR, Cl), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((Cs, Cl), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((Cs, Cl), jnp.float32),
        )(x1, g)
        return out.astype(jnp.bfloat16)

    ref = np.asarray(wgrad_xla(g1), np.float32)
    got = np.asarray(wgrad_pl(g1), np.float32)
    err = np.max(np.abs(got - ref)) / np.max(np.abs(ref))

    # chain over g's first Cs columns -> keep carry g-shaped: wrap
    def chain_xla(g):
        dw = wgrad_xla(g)                 # (Cs, Cl)
        return g + jnp.tile(dw, (R // Cs, 1)).astype(g.dtype) * 0

    # simpler honest chain: carry (Cs, Cl) seed mixed into g each step
    seed = jnp.zeros((Cs, Cl), jnp.bfloat16)

    def mk_chain(wgrad):
        def step(c):
            gg = g1 * (1 + c[0, 0])
            return wgrad(gg).astype(jnp.bfloat16)
        return step
    t0 = time_chain(mk_chain(wgrad_xla), seed, 128)
    t1 = time_chain(mk_chain(wgrad_pl), seed, 128)
    print(f"1x1 wgrad 56 64x256: xla {t0*1e3:.3f}ms {flops/t0/1e12:.1f}TF | "
          f"pallas {t1*1e3:.3f}ms {flops/t1/1e12:.1f}TF  err={err:.0e}",
          flush=True)


if __name__ == "__main__":
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
