#!/usr/bin/env python
"""Probe: decompose the ResNet-50 train step into fwd / dgrad / wgrad,
and isolate wgrad conv performance (VERDICT r4 item 2b).

Round-4 left wgrad as the last unprobed region of the "platform-bound at
~2,450 img/s" claim: forward convs run 150-195 TF isolated but the whole
step aggregates ~45 TF (in consistent 2-flops/MAC terms — see bench.py),
and prior probes only chained fwd or fwd+dgrad.  Two parts:

1. Three-way split of the real training step (resnet50_v1, batch 128,
   bf16, the same _Plan the bench's FusedTrainer compiles):
     t_fwd            — loss only
     t_fwd_dgrad      — grad wrt DATA (runs the full dgrad chain,
                        no weight gradients)
     t_full           — grad wrt PARAMS (fwd + dgrad + wgrad)
   differences give the per-pass share.  Windowed timing (python loop of
   the jitted step with a donated data-feedback chain, one D2H at the
   end) — the same windowed protocol bench.py uses.

2. Isolated wgrad at the four 3x3 bottleneck shapes (56/28/14/7 px), via
   jax.linear_transpose of the conv in w — the pure wgrad XLA program,
   no fwd needed (conv is linear in w).  Also a hand 9-shifted-GEMM
   formulation (dw[tap] = x_tap^T @ dy) to see whether a different
   lowering beats XLA's chosen one (>=10% -> wire it, VERDICT).

Run: python tools/probe_wgrad.py          (needs the TPU chip)
"""
import json
import statistics
import sys
import time

import numpy as np

REPS = 5
WINDOW = 12


def _win_time(fn, fetch, n):
    """One window: n async dispatches, one hard D2H fetch."""
    t0 = time.perf_counter()
    r = None
    for _ in range(n):
        r = fn()
    fetch(r)
    return time.perf_counter() - t0


def _per_call(fn, fetch):
    """Median of paired (2N - N) window differences -> seconds/call."""
    _win_time(fn, fetch, 2)                    # warm
    diffs = []
    for _ in range(REPS):
        d1 = _win_time(fn, fetch, WINDOW)
        d2 = _win_time(fn, fetch, 2 * WINDOW)
        diffs.append(d2 - d1)
    med = statistics.median(diffs)
    return med / WINDOW if med > 0 else None


def three_way_split():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import symbol as sym_mod
    from mxnet_tpu.executor import _Plan
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.ops.nn import streaming_ce

    batch, px = 128, 224
    net = vision.resnet50_v1()
    net.initialize(ctx=mx.tpu(0) if mx.context.num_tpus() else mx.cpu(0))
    x0 = mx.nd.random.uniform(shape=(batch, 3, px, px))
    net(x0).wait_to_read()
    net.hybridize()
    out_sym = net(sym_mod.var("data"))
    plan = _Plan(out_sym, train=True)
    params = net.collect_params()
    args = {n: jnp.asarray(params[n].data()._data, jnp.float32)
            for n in plan.arg_names if n != "data"}
    auxs = {n: jnp.asarray(params[n].data()._data, jnp.float32)
            for n in plan.aux_names}
    keys = jnp.zeros((max(1, plan.n_rng), 2), jnp.uint32)
    labels = jnp.asarray(np.random.randint(0, 1000, (batch,)))
    data = jnp.asarray(np.asarray(x0._data), jnp.bfloat16)

    def loss_of(a, d):
        a = {k: v.astype(jnp.bfloat16) for k, v in a.items()}
        outs, _ = plan.execute({**a, "data": d}, auxs, keys)
        return jnp.mean(streaming_ce(outs[0], labels))

    # each variant feeds a loss-dependent epsilon back into data so the
    # window's steps chain (nothing can be dead-code'd or reordered out)
    @jax.jit
    def f_fwd(d):
        return d + (loss_of(args, d) * 1e-12).astype(d.dtype)

    @jax.jit
    def f_dgrad(d):
        g = jax.grad(loss_of, 1)(args, d)
        return d + g.astype(d.dtype) * 1e-12

    @jax.jit
    def f_full(d):
        gs = jax.grad(loss_of, 0)(args, d)
        acc = sum(jnp.sum(v.astype(jnp.float32)) for v in gs.values())
        return d + (acc * 1e-12).astype(d.dtype)

    def fetch(d):
        np.asarray(jax.device_get(d[0, 0, 0, :1]))

    res = {}
    state = {"d": data}
    for name, f in (("fwd", f_fwd), ("fwd_dgrad", f_dgrad),
                    ("full", f_full)):
        def call(f=f):
            state["d"] = f(state["d"])
            return state["d"]
        t = _per_call(call, fetch)
        res[name + "_ms"] = round(t * 1e3, 2) if t else None
    if all(res.get(k) for k in ("fwd_ms", "fwd_dgrad_ms", "full_ms")):
        res["dgrad_ms"] = round(res["fwd_dgrad_ms"] - res["fwd_ms"], 2)
        res["wgrad_ms"] = round(res["full_ms"] - res["fwd_dgrad_ms"], 2)
        res["img_per_sec_full"] = round(batch / (res["full_ms"] / 1e3), 1)
    return res


# the four 3x3 bottleneck conv shapes of ResNet-50 at 224px (batch 128)
SHAPES = [
    ("stage1_56px", 128, 64, 64, 56),
    ("stage2_28px", 128, 128, 128, 28),
    ("stage3_14px", 128, 256, 256, 14),
    ("stage4_7px", 128, 512, 512, 7),
]


def isolated_wgrad():
    import jax
    import jax.numpy as jnp

    rows = []
    r = np.random.default_rng(0)
    for name, N, C, K, HW in SHAPES:
        x = jnp.asarray(r.standard_normal((N, C, HW, HW)) * 0.1,
                        jnp.bfloat16)
        dy = jnp.asarray(r.standard_normal((N, K, HW, HW)) * 0.1,
                         jnp.bfloat16)
        dn = jax.lax.conv_dimension_numbers(x.shape, (K, C, 3, 3),
                                            ("NCHW", "OIHW", "NCHW"))

        def conv_w(w):
            # bf16 out so the transpose takes the bf16 dy cotangent
            # (MXU still accumulates f32 internally)
            return jax.lax.conv_general_dilated(
                x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn)

        wt = jax.linear_transpose(
            conv_w, jax.ShapeDtypeStruct((K, C, 3, 3), jnp.bfloat16))

        @jax.jit
        def f_xla(g, wt=wt):
            (dw,) = wt(g)
            return g + jnp.mean(dw.astype(jnp.float32)).astype(g.dtype) \
                * 1e-12

        # hand formulation: dw for tap (dy,dx) = x_shifted^T @ dy as one
        # GEMM over (N*H*W) — nine of them, f32 accumulation
        xp = jnp.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)))

        @jax.jit
        def f_gemm(g, xp=xp, C=C, K=K, HW=HW, N=N):
            g2 = g.transpose(0, 2, 3, 1).reshape(N * HW * HW, K)
            acc = jnp.mean(g.astype(jnp.float32)) * 0.0
            for dy_ in range(3):
                for dx_ in range(3):
                    tap = xp[:, :, dy_:dy_ + HW, dx_:dx_ + HW] \
                        .transpose(0, 2, 3, 1).reshape(N * HW * HW, C)
                    dw = jax.lax.dot_general(
                        tap, g2, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    acc = acc + jnp.mean(dw)
            return g + acc.astype(g.dtype) * 1e-12

        def fetch(g):
            np.asarray(jax.device_get(g[0, 0, 0, :1]))

        flops = 2 * N * K * C * 9 * HW * HW
        row = {"shape": name}
        for nm, f in (("xla", f_xla), ("gemm9", f_gemm)):
            state = {"g": dy}

            def call(f=f):
                state["g"] = f(state["g"])
                return state["g"]
            t = _per_call(call, fetch)
            if t:
                row[nm + "_ms"] = round(t * 1e3, 3)
                row[nm + "_tf"] = round(flops / t / 1e12, 1)
        rows.append(row)
    return rows


def main():
    from mxnet_tpu import program_cache
    program_cache.place()       # the one decision on where compiles persist
    out = {"metric": "wgrad_probe"}
    if "--isolated-only" not in sys.argv:
        out["three_way_split"] = three_way_split()
    if "--split-only" not in sys.argv:
        out["isolated_wgrad"] = isolated_wgrad()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
