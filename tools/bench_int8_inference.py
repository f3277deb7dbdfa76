#!/usr/bin/env python
"""int8 vs bf16 ResNet-50 inference on the chip (VERDICT r2 item 6).

Reference analog: docs/faq/perf.md:163-177 publishes fp16 inference at
1.9x fp32 on V100; the TPU equivalent claim is the MXU's native
s8xs8->s32 path.  This bench quantizes the model zoo ResNet-50 with the
calibration pass (contrib/quantization.py) and times both variants with
an in-jit data-dependent chain (each forward feeds a perturbation of the
previous logits back into the input, so steps serialize on-device),
measured differentially (2N vs N chains cancels the fixed per-call
dispatch + fetch cost).  Inference has no donated-state chain, so
bench.py's window protocol cannot serialize it — this is the honest
timing for forward-only workloads.  Each dtype variant runs in its own
subprocess (full-model chains at batch 128 exhaust HBM when both live in
one process); the parent never touches jax and runs the children one
after another, so exactly one process holds the chip at a time.  Without
a TPU a variant exits non-zero: nothing shrinks to fit a CPU.

Run:  python tools/bench_int8_inference.py
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPS = 3
CHAIN = 24


def chain_time(plan_fn, x0, chain=CHAIN):
    import jax
    import jax.numpy as jnp

    def build(n):
        @jax.jit
        def f(x):
            def body(c, _):
                out = plan_fn(c)
                eps = (jnp.sum(out.astype(jnp.float32)) * 1e-12).astype(
                    c.dtype)
                return c + eps, None
            y, _ = jax.lax.scan(body, x, None, length=n)
            return jnp.sum(y.astype(jnp.float32))
        return f

    f1, f2 = build(chain), build(2 * chain)
    float(f1(x0)); float(f2(x0))
    b1 = b2 = 1e9
    for _ in range(REPS):
        t0 = time.perf_counter(); float(f1(x0))
        b1 = min(b1, time.perf_counter() - t0)
        t0 = time.perf_counter(); float(f2(x0))
        b2 = min(b2, time.perf_counter() - t0)
    return max(b2 - b1, 1e-9) / chain


def run_variant(variant):
    """Executed in a subprocess: print one JSON line for the variant."""
    import numpy as np

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import symbol as S
    from mxnet_tpu.executor import _Plan
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.io import NDArrayIter
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        sys.exit("bench_int8_inference: no TPU visible (jax reports "
                 "platform %r)" % jax.devices()[0].platform)
    from mxnet_tpu import program_cache
    program_cache.place()
    ctx = mx.tpu(0)
    batch, size = 128, 224

    net = vision.resnet50_v1()
    net.initialize(ctx=ctx)
    net.hybridize()
    x = mx.nd.random.uniform(0, 1, shape=(batch, 3, size, size), ctx=ctx)
    net(x).wait_to_read()

    sym = net(S.var("data"))
    params = net.collect_params()
    args = {n: params[n].data()._data for n in sym.list_arguments()
            if n != "data"}
    auxs = {n: params[n].data()._data
            for n in sym.list_auxiliary_states()}

    if variant == "bf16":
        plan = _Plan(sym, train=False)
        vals = {n: v.astype(jnp.bfloat16) for n, v in args.items()}
        avals = {n: v.astype(jnp.bfloat16) for n, v in auxs.items()}
        keys = jnp.zeros((max(1, plan.n_rng), 2), jnp.uint32)

        def fwd(data):
            outs, _ = plan.execute({**vals, "data": data}, avals, keys)
            return outs[0]

        xb = x._data.astype(jnp.bfloat16)
        t = chain_time(fwd, xb)
        t2 = chain_time(fwd, xb)       # same-session repeat
        worst = max(t, t2)
        print(json.dumps({"variant": "bf16", "ms": worst * 1e3,
                          "ms_first": t * 1e3, "ms_repeat": t2 * 1e3,
                          "img_per_sec": batch / worst, "batch": batch}))
        return 0

    # int8
    import numpy as np
    # small calib batch: the calibration pass materializes every
    # conv/FC output at once (53 layers x batch) — batch 32 at 224px
    # exhausts HBM
    calib = NDArrayIter(data=x.asnumpy()[:8], batch_size=8)
    # fuse=True: the static-scale pipeline — BN folded into conv weights,
    # requantize+ReLU epilogues fused per conv, int8 residual adds
    # (round-3 verdict item 1: the unfused dynamic-range form measured
    # 0.80x bf16 because of per-layer min/max + f32 glue)
    qsym, qargs, qauxs = mx.contrib.quantization.quantize_model(
        sym, {n: mx.nd.array(np.asarray(v, np.float32))
              for n, v in args.items()},
        {n: mx.nd.array(np.asarray(v, np.float32))
         for n, v in auxs.items()},
        ctx=ctx, calib_mode="naive", calib_data=calib,
        num_calib_examples=8, fuse=True)
    qplan = _Plan(qsym, train=False)
    qvals = {n: (v._data if hasattr(v, "_data") else jnp.asarray(v))
             for n, v in qargs.items()}
    qaux = {n: (v._data if hasattr(v, "_data") else jnp.asarray(v))
            for n, v in qauxs.items()}
    qkeys = jnp.zeros((max(1, qplan.n_rng), 2), jnp.uint32)

    def fwdq(data):
        outs, _ = qplan.execute({**qvals, "data": data}, qaux, qkeys)
        return outs[0]

    t = chain_time(fwdq, x._data)
    t2 = chain_time(fwdq, x._data)   # same-session repeat: within-process
    worst = max(t, t2)
    ref = net(x).asnumpy().argmax(1)
    # jit: the eager per-op replay would hold every layer's s32
    # activations live at once and exhaust HBM at batch 128
    q_top1 = np.asarray(jax.jit(fwdq)(x._data)).argmax(1)
    agree = float((q_top1 == ref).mean())
    print(json.dumps({"variant": "int8", "ms": worst * 1e3,
                      "ms_first": t * 1e3, "ms_repeat": t2 * 1e3,
                      "img_per_sec": batch / worst,
                      "top1_agreement_vs_fp32": agree, "batch": batch}))
    return 0


def main():
    if len(sys.argv) > 1 and sys.argv[1] in ("bf16", "int8"):
        return run_variant(sys.argv[1])

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    n_runs = {"bf16": 3, "int8": 3}    # both variants: 3 processes x 2
    rows = {}                          # measurements — the bimodal
    for variant in ("bf16", "int8"):   # lowering lands on either side
        runs = []
        for _ in range(n_runs[variant]):
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), variant],
                env=env, capture_output=True, text=True, timeout=1500)
            if p.returncode != 0:
                runs.append({"error": p.stderr[-400:]})
                continue
            runs.append(json.loads(p.stdout.strip().splitlines()[-1]))
        ok = [r for r in runs if "error" not in r]
        if not ok:
            rows[variant] = runs[0]
            continue
        # headline = the CONSERVATIVE (slowest) clean observation,
        # consistent across ms and img_per_sec; all clean runs kept for
        # the variance story, failures counted
        rows[variant] = dict(max(ok, key=lambda r: r["ms"]))
        if len(runs) > 1:
            rows[variant]["all_ms_first"] = [r.get("ms_first") for r in ok]
            rows[variant]["all_ms_repeat"] = [r.get("ms_repeat")
                                              for r in ok]
            rows[variant]["failed_runs"] = len(runs) - len(ok)

    out = {"metric": "resnet50_int8_vs_bf16_inference"}
    out.update(rows)
    failed = [v for v in rows if "error" in rows[v]]
    if not failed:
        out["int8_speedup"] = round(rows["bf16"]["ms"]
                                    / rows["int8"]["ms"], 3)
    print(json.dumps(out))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    sys.exit(main())
