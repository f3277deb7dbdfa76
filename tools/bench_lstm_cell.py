#!/usr/bin/env python
"""Microbench: Pallas fused LSTM recurrence vs lax.scan (fwd+bwd).

Reproduces the docs/perf_analysis.md round-3 number (isolated recurrence
at the LM shape T=35 B=128 H=650: scan 0.405 ms -> pallas 0.319 ms,
+21%).  Differential chained timing cancels the fixed per-call cost.

Run on TPU:  python tools/bench_lstm_cell.py [T B H]
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import pallas_rnn

REPS = 4
CHAIN = 100


def time_chain(step, x0):
    def build(n):
        @jax.jit
        def f(x):
            def body(c, _):
                return step(c) * jnp.bfloat16(0.25), None
            y, _ = jax.lax.scan(body, x, None, length=n)
            return jnp.sum(y.astype(jnp.float32))
        return f
    f1, f2 = build(CHAIN), build(2 * CHAIN)
    float(f1(x0)); float(f2(x0))
    best1 = best2 = 1e9
    for _ in range(REPS):
        t0 = time.perf_counter(); float(f1(x0))
        best1 = min(best1, time.perf_counter() - t0)
        t0 = time.perf_counter(); float(f2(x0))
        best2 = min(best2, time.perf_counter() - t0)
    return max(best2 - best1, 1e-9) / CHAIN


def main():
    from mxnet_tpu import program_cache
    program_cache.place()       # the one decision on where compiles persist
    T, B, H = (int(a) for a in sys.argv[1:4]) if len(sys.argv) > 3 \
        else (35, 128, 650)
    rng = np.random.default_rng(0)
    xproj = jnp.asarray(rng.standard_normal((T, B, 4 * H)) * 0.1,
                        jnp.bfloat16)
    h0 = jnp.zeros((B, H), jnp.bfloat16)
    c0 = jnp.zeros((B, H), jnp.bfloat16)
    R = jnp.asarray(rng.standard_normal((4 * H, H)) * 0.1, jnp.bfloat16)
    bR = jnp.asarray(rng.standard_normal((4 * H,)) * 0.1, jnp.bfloat16)

    def scan_ref(xp):
        def step(carry, x):
            h, c = carry
            g = x + h @ R.T + bR
            i, f, gg, o = jnp.split(g, 4, axis=-1)
            c2 = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(gg)
            h2 = jax.nn.sigmoid(o) * jnp.tanh(c2)
            return (h2, c2), h2
        _, ys = jax.lax.scan(step, (h0, c0), xp)
        return ys

    def pallas_fn(xp):
        ys, _, _ = pallas_rnn.lstm_scan(xp, h0, c0, R, bR)
        return ys

    for name, f in [("lax.scan", scan_ref), ("pallas", pallas_fn)]:
        def fwdbwd(c, f=f):
            return jax.grad(
                lambda xp: jnp.sum(f(xp).astype(jnp.float32) ** 2))(c)
        t = time_chain(fwdbwd, xproj)
        print(f"{name:9} recurrence fwd+bwd (T={T},B={B},H={H}): "
              f"{t*1e3:.3f} ms/window")


if __name__ == "__main__":
    main()
