#!/usr/bin/env python
"""Probe: Pallas implicit-GEMM conv (ops/pallas_conv.py) vs lax.conv on
the ResNet-50 3x3 shapes, chained-block TFLOPS per shape as JSON.

Round 3 prototyped the implicit-GEMM framing in this file and measured
87-171 TF standalone (vs the 35-45 TF in-graph conv aggregate and
150-195 TF isolated XLA convs).  Round 6 moved the kernels into
``mxnet_tpu/ops/pallas_conv.py`` with a full Pallas VJP; this probe now
drives the LIBRARY kernels — the exact code the ``MXNET_TPU_PALLAS_CONV``
dispatch runs — so probe numbers and production numbers cannot drift.

Protocol: windowed timing with a
data-feedback chain — each jitted call folds a loss-dependent epsilon
back into its input so neither XLA nor the runtime can overlap, reorder
or dead-code the kernels; per-call time is the median of paired
(2N - N) window differences; DEPTH convs chain inside one executable to
amortize dispatch.  TFLOPS uses 2 flops/MAC over KH*KW*C contractions
(the consistent-currency convention bench.py fixed in round 3).

Run:  python tools/probe_pallas_conv.py            (needs the TPU chip)
      python tools/probe_pallas_conv.py --smoke    (CPU: tiny shapes in
          interpret mode, numerics only — the CI guard for this probe)

Output: one JSON object on stdout, {"shapes": [{shape, *_tf | *_err}]}.
"""
import json
import statistics
import sys
import time

import numpy as np

REPS = 5
WINDOW = 12
DEPTH = 4          # convs chained inside one executable

# ResNet-50/224 3x3 conv shapes, batch 128: (name, N, C, O, HW, stride).
# stage1 is lane-starved (C=64 < 128 lanes; r3 measured 10 TF) and
# gated OFF by conv3x3_same_available — probed anyway for the record.
SHAPES = [
    ("stage1_56px", 128, 64, 64, 56, 1),
    ("stage2_28px", 128, 128, 128, 28, 1),
    ("stage3_14px", 128, 256, 256, 14, 1),
    ("stage4_7px", 128, 512, 512, 7, 1),
    ("s2_28to14px", 128, 128, 256, 28, 2),
]
SMOKE_SHAPES = [
    ("smoke_s1", 2, 8, 8, 6, 1),
    ("smoke_s2", 2, 8, 8, 6, 2),
]


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


def probe_shape(name, N, C, O, HW, stride, smoke):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_conv as pc

    dtype = jnp.float32 if smoke else jnp.bfloat16
    r = np.random.default_rng(0)
    x = jnp.asarray(r.standard_normal((N, C, HW, HW)) * 0.1, dtype)
    w = jnp.asarray(r.standard_normal((O, C, 3, 3)) * 0.1, dtype)
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCHW", "OIHW", "NCHW"))

    def lax_conv(d, w_):
        return jax.lax.conv_general_dilated(
            d, w_, (stride, stride), [(1, 1), (1, 1)],
            dimension_numbers=dn)

    def pl_conv(d, w_):
        if stride == 1:
            return pc.conv3x3_same(d, w_)
        return pc.conv3x3_s2(d, w_)

    Ho = HW // stride
    flops = 2 * N * O * C * 9 * Ho * Ho          # 2 flops/MAC, 9 taps
    row = {"shape": name, "N": N, "C": C, "O": O, "hw": HW,
           "stride": stride}

    if smoke:
        # numerics guard: forward and both grads vs the lax lowering
        got = np.asarray(pl_conv(x, w).astype(jnp.float32))
        ref = np.asarray(lax_conv(x, w).astype(jnp.float32))
        row["pallas_fwd_err"] = float(np.max(np.abs(got - ref)))

        def loss(conv):
            return lambda d, w_: jnp.sum(conv(d, w_).astype(jnp.float32)
                                         ** 2)
        gp = jax.grad(loss(pl_conv), (0, 1))(x, w)
        gr = jax.grad(loss(lax_conv), (0, 1))(x, w)
        row["pallas_grad_err"] = float(max(
            np.max(np.abs(np.asarray(a) - np.asarray(b)))
            / (np.max(np.abs(np.asarray(b))) + 1e-9)
            for a, b in zip(gp, gr)))
        return row

    def chain_fwd(conv):
        @jax.jit
        def f(d):
            for _ in range(DEPTH):
                y = conv(d, w)
                d = d + (jnp.mean(y.astype(jnp.float32))
                         * 1e-12).astype(d.dtype)
            return d
        return f

    def chain_train(conv):
        def loss(d, w_):
            return 0.5 * jnp.sum(conv(d, w_).astype(jnp.float32) ** 2)

        @jax.jit
        def f(d):
            for _ in range(DEPTH):
                gd, gw = jax.grad(loss, (0, 1))(d, w)
                eps = jnp.mean(gw.astype(jnp.float32)) * 1e-12
                d = d + gd.astype(d.dtype) * 1e-12 + eps.astype(d.dtype)
            return d
        return f

    def fetch(d):
        np.asarray(jax.device_get(d[0, 0, 0, :1]))

    for impl, conv in (("pallas", pl_conv), ("lax", lax_conv)):
        for pass_, mk, nflops in (("fwd", chain_fwd, flops),
                                  ("train", chain_train, 3 * flops)):
            f = mk(conv)
            state = {"d": x}

            def call(f=f):
                state["d"] = f(state["d"])
                return state["d"]
            try:
                t = _per_call(call, fetch)
            except Exception as e:                     # noqa: BLE001
                row["%s_%s_error" % (impl, pass_)] = repr(e)[:200]
                continue
            if t:
                per_conv = t / DEPTH
                row["%s_%s_ms" % (impl, pass_)] = round(per_conv * 1e3, 3)
                row["%s_%s_tf" % (impl, pass_)] = round(
                    nflops / per_conv / 1e12, 1)
    return row


def main(argv):
    from mxnet_tpu import program_cache
    program_cache.place()       # the one decision on where compiles persist
    smoke = "--smoke" in argv
    import jax
    from mxnet_tpu.ops import pallas_conv as pc

    out = {"metric": "pallas_conv_probe", "smoke": smoke,
           "backend": jax.default_backend(), "depth": DEPTH}
    if smoke:
        pc.INTERPRET = True
    elif out["backend"] != "tpu":
        out["error"] = ("requires the TPU chip; use --smoke for the "
                        "CPU interpret-mode numerics guard")
        print(json.dumps(out))
        return 2
    rows = []
    for spec in (SMOKE_SHAPES if smoke else SHAPES):
        rows.append(probe_shape(*spec, smoke=smoke))
    out["shapes"] = rows
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main(sys.argv[1:]))
