#!/usr/bin/env python
"""Probe: decompose the ResNet-50 train-step conv time by shape x pass.

Round-3 finding (probe_pallas_conv.py): isolated forward convs run at
150-195 TF, yet the full train step implies ~35 TF aggregate.  This probe
times, per conv class: the forward chain (t_f), forward+input-grad chain
(t_fd), and forward+both-grads chain (t_fdw).  dgrad ~= t_fd - t_f and
wgrad ~= t_fdw - t_fd.  A relu sits after every conv so gradients are
input-dependent and nothing constant-folds.

Run:  python tools/probe_resnet_step.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

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


def main():
    from mxnet_tpu import program_cache
    program_cache.place()       # the one decision on where compiles persist
    N = 128
    rng = np.random.default_rng(0)

    def conv(x, w, s=1):
        return jax.lax.conv_general_dilated(
            x, w, (s, s), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    # (name, H, W, C, K, kh, stride, count) — 1x1s probed as up+down pairs
    classes = [
        ("stem7x7s2 3>64", 224, 224, 3, 64, 7, 2, 1),
        ("3x3s1 56 c64", 56, 56, 64, 64, 3, 1, 3),
        ("3x3s1 28 c128", 28, 28, 128, 128, 3, 1, 4),
        ("3x3s1 14 c256", 14, 14, 256, 256, 3, 1, 6),
        ("3x3s1 7 c512", 7, 7, 512, 512, 3, 1, 3),
        ("1x1pair 56 64/256", 56, 56, 64, 256, 1, 1, 3),
        ("1x1pair 28 128/512", 28, 28, 128, 512, 1, 1, 4),
        ("1x1pair 14 256/1k", 14, 14, 256, 1024, 1, 1, 6),
        ("1x1pair 7 512/2k", 7, 7, 512, 2048, 1, 1, 3),
        ("3x3s2 56>28 c128", 56, 56, 128, 128, 3, 2, 1),
        ("3x3s2 28>14 c256", 28, 28, 256, 256, 3, 2, 1),
        ("3x3s2 14>7 c512", 14, 14, 512, 512, 3, 2, 1),
        ("proj1x1s2 56 256>512", 56, 56, 256, 512, 1, 2, 1),
    ]
    tot = {"fwd": 0.0, "dgrad": 0.0, "wgrad": 0.0}
    flops_tot = 0.0
    print(f"{'class':>22} {'fwd_ms':>8} {'dgrad':>8} {'wgrad':>8} "
          f"{'fwdTF':>7} {'dTF':>6} {'wTF':>6}")
    for (name, H, W, C, K, kh, s, count) in classes:
        Ho, Wo = H // s, W // s
        x = jnp.asarray(rng.standard_normal((N, H, W, C)) * 0.1, jnp.bfloat16)
        pair = kh == 1 and s == 1
        if pair:
            w1 = jnp.asarray(rng.standard_normal((1, 1, C, K)) * 0.1,
                             jnp.bfloat16)
            w2 = jnp.asarray(rng.standard_normal((1, 1, K, C)) * 0.1,
                             jnp.bfloat16)

            def net(xx, ws):
                return jnp.sum(jax.nn.relu(conv(jax.nn.relu(
                    conv(xx, ws[0])), ws[1])).astype(jnp.float32))

            def f_only(c):
                return jax.nn.relu(conv(jax.nn.relu(conv(c, w1)), w2))
            ws = (w1, w2)
            flops = 2 * N * H * W * C * K * 2
        else:
            w1 = jnp.asarray(rng.standard_normal((kh, kh, C, K)) * 0.1,
                             jnp.bfloat16)
            # mixer restores carry shape for strided / channel-changing
            wm = jnp.asarray(rng.standard_normal((1, 1, K, C)) * 0.1,
                             jnp.bfloat16)

            def net(xx, ws):
                return jnp.sum(jax.nn.relu(
                    conv(xx, ws[0], s)).astype(jnp.float32))

            def f_only(c):
                y = jax.nn.relu(conv(c, w1, s))
                y = conv(y, wm)
                if s != 1:
                    y = jax.image.resize(y, (N, H, W, C), "nearest")
                return y
            ws = (w1,)
            flops = 2 * N * Ho * Wo * C * K * kh * kh

        chain = max(32, min(320, int(0.25 / (flops * 3 / 60e12)) // 2 * 2))

        t_f = time_chain(f_only, x, chain)

        def fd(c):
            return jax.grad(lambda xx: net(xx, ws))(c)
        t_fd = time_chain(fd, x, chain)

        def fdw(c):
            dx, dws = jax.grad(lambda xx, ww: net(xx, ww),
                               argnums=(0, 1))(c, ws)
            keep = sum(jnp.sum(d.astype(jnp.float32)) for d in
                       jax.tree_util.tree_leaves(dws))
            return dx * (1 + 1e-9 * keep).astype(dx.dtype)
        t_fdw = time_chain(fdw, x, chain)

        d_ms = max(t_fd - t_f, 1e-9)
        wg_ms = max(t_fdw - t_fd, 1e-9)
        print(f"{name:>22} {t_f*1e3:8.3f} {d_ms*1e3:8.3f} {wg_ms*1e3:8.3f} "
              f"{flops/t_f/1e12:7.1f} {flops/d_ms/1e12:6.1f} "
              f"{flops/wg_ms/1e12:6.1f}   x{count}", flush=True)
        tot["fwd"] += t_f * 1e3 * count
        tot["dgrad"] += d_ms * 1e3 * count
        tot["wgrad"] += wg_ms * 1e3 * count
        flops_tot += 3 * flops * count

    print("\nper-step conv totals (ms):",
          {k: round(v, 2) for k, v in tot.items()},
          " sum=", round(sum(tot.values()), 1),
          " aggregate TF=", round(flops_tot / sum(tot.values()) / 1e9, 1))


if __name__ == "__main__":
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
