#!/usr/bin/env python
"""Ring-attention microbench (VERDICT r2 item 7).

Two parts:
1. single chip: long-context blockwise attention, XLA-scan formulation
   vs the Pallas flash kernel (ops/pallas_attention.py) — ms/call,
   tokens/s, achieved TF (differential chained timing).
2. 8-device virtual CPU mesh, in a CPU-pinned child that runs FIRST,
   before this process touches jax (one process holds the chip):
   ring_attention and ulysses_attention vs the single-device reference —
   max abs error, proving the sp decomposition is exact.

Run:  python tools/bench_ring_attention.py [--mesh-only|--chip-only]
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPS = 7
CHAIN = 30


def _time_chain(step, x0, chain):
    import jax
    import jax.numpy as jnp
    import statistics

    def build(n):
        @jax.jit
        def f(x):
            def body(c, _):
                o = step(c)
                eps = (jnp.sum(o.astype(jnp.float32)) * 1e-12)
                return c + eps.astype(c.dtype), None
            y, _ = jax.lax.scan(body, x, None, length=n)
            return jnp.sum(y.astype(jnp.float32))
        return f

    f1, f2 = build(chain), build(2 * chain)
    float(f1(x0)); float(f2(x0))
    # median of PAIRED (2N - N) differences: resists per-call latency
    # swings, which made min-of-mins go negative
    diffs = []
    for _ in range(REPS):
        t0 = time.perf_counter(); float(f1(x0))
        d1 = time.perf_counter() - t0
        t0 = time.perf_counter(); float(f2(x0))
        d2 = time.perf_counter() - t0
        diffs.append(d2 - d1)
    med = statistics.median(diffs)
    if med <= 0:
        # per-call jitter swamped the differential: flag instead of
        # clamping (a clamp fabricates astronomical TF rows)
        return None
    return med / chain


def chip_bench():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel.ring_attention import blockwise_attention


    results = []
    r = np.random.default_rng(0)
    B, H, D = 1, 8, 128
    for T in (4096, 8192, 16384):
        q = jnp.asarray(r.standard_normal((B, H, T, D)) * 0.3,
                        jnp.bfloat16)
        k = jnp.asarray(r.standard_normal((B, H, T, D)) * 0.3,
                        jnp.bfloat16)
        v = jnp.asarray(r.standard_normal((B, H, T, D)) * 0.3,
                        jnp.bfloat16)
        # causal attention FLOPs: 2 matmuls, half the score matrix
        flops = 2 * 2 * B * H * T * T * D / 2
        row = {"T": T}
        for name, use_pallas in (("xla_scan", False), ("pallas", True)):
            fn = lambda c, up=use_pallas: blockwise_attention(
                c, k, v, block_size=256, causal=True, use_pallas=up)
            # correctness cross-check once
            t = _time_chain(fn, q, CHAIN)
            if t is None:
                row[name + "_timing_suspect"] = True
                continue
            row[name + "_ms"] = round(t * 1e3, 3)
            row[name + "_tf"] = round(flops / t / 1e12, 1)
            row[name + "_tokens_per_sec"] = round(T / t, 0)
        ref = np.asarray(blockwise_attention(
            q, k, v, block_size=256, causal=True,
            use_pallas=False).astype(jnp.float32))
        got = np.asarray(blockwise_attention(
            q, k, v, block_size=256, causal=True,
            use_pallas=True).astype(jnp.float32))
        row["max_err"] = float(np.max(np.abs(got - ref)))
        if "xla_scan_ms" in row and "pallas_ms" in row:
            row["pallas_speedup"] = round(
                row["xla_scan_ms"] / max(row["pallas_ms"], 1e-6), 3)
        results.append(row)
    return results


def ring_chip_bench():
    """The RING path itself on the real chip (r03 verdict item 4): a
    1-device mesh runs the actual per-shard ring code — flash kernel
    emitting (acc, m, l) stats + the exact cross-shard combine — vs the
    scan formulation.  The per-shard VMEM gate sees T/n, so the ring
    decomposition is what keeps the kernel applicable at long T."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.ring_attention import ring_attention


    mesh = make_mesh({"sp": 1})
    r = np.random.default_rng(0)
    B, H, D = 1, 8, 128
    results = []
    import jax.numpy as _jnp

    def train_step_fn(use_pallas, k, v):
        # fwd+bwd wrt (q,k,v): the real training cost (VERDICT r4 #1 —
        # the ring backward now runs Pallas dq/dk/dv kernels)
        def loss(q, k, v):
            o = ring_attention(q, k, v, mesh, axis="sp", causal=True,
                               block_size=256, use_pallas=use_pallas)
            return _jnp.sum(o.astype(_jnp.float32) ** 2)

        def step(q):
            dq, dk, dv = jax.grad(loss, (0, 1, 2))(q, k, v)
            return dq + dk + dv
        return step
    # T here is the PER-SHARD sequence (the 1-device mesh runs one ring
    # step); an 8-way ring at global T = 8*T_loc runs exactly this per
    # step, so the T_loc=1024 row is the per-step cost of ring attention
    # at global T=8192.  T_loc=8192 single-chip exceeds the kernel's
    # resident-KV VMEM envelope and documents the scan fallback edge.
    for T in (1024, 2048, 4096, 8192):
        q, k, v = (jnp.asarray(r.standard_normal((B, H, T, D)) * 0.3,
                               jnp.bfloat16) for _ in range(3))
        flops = 2 * 2 * B * H * T * T * D / 2
        row = {"T_loc": T, "T_global_8way": 8 * T}
        for name, up in (("ring_scan", False), ("ring_flash", True)):
            fn = lambda c, u=up: ring_attention(
                c, k, v, mesh, axis="sp", causal=True, block_size=256,
                use_pallas=u)
            t = _time_chain(fn, q, CHAIN)
            if t is None:
                row[name + "_timing_suspect"] = True
                continue
            row[name + "_ms"] = round(t * 1e3, 3)
            row[name + "_tf"] = round(flops / t / 1e12, 1)
        # train step (fwd+bwd): 7 matmul-pairs vs the forward's 2
        tflops = 3.5 * flops
        for name, up in (("train_scan", False), ("train_flash", True)):
            t = _time_chain(train_step_fn(up, k, v), q, CHAIN)
            if t is None:
                row[name + "_timing_suspect"] = True
                continue
            row[name + "_ms"] = round(t * 1e3, 3)
            row[name + "_tf"] = round(tflops / t / 1e12, 1)
        ref = np.asarray(ring_attention(q, k, v, mesh, axis="sp",
                                        causal=True, block_size=256,
                                        use_pallas=False)
                         .astype(jnp.float32))
        got = np.asarray(ring_attention(q, k, v, mesh, axis="sp",
                                        causal=True, block_size=256)
                         .astype(jnp.float32))
        row["max_err"] = float(np.max(np.abs(got - ref)))
        if "ring_scan_ms" in row and "ring_flash_ms" in row:
            row["flash_speedup"] = round(
                row["ring_scan_ms"] / max(row["ring_flash_ms"], 1e-6), 3)
        if "train_scan_ms" in row and "train_flash_ms" in row:
            row["train_flash_speedup"] = round(
                row["train_scan_ms"] / max(row["train_flash_ms"], 1e-6), 3)
        results.append(row)
    return results


def mesh_check():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO
    code = r"""
import json
import numpy as np
import jax, jax.numpy as jnp
from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.parallel.ring_attention import (
    blockwise_attention, ring_attention, ulysses_attention)

mesh = make_mesh({"sp": 8})
r = np.random.default_rng(0)
B, H, T, D = 2, 8, 256, 32
q, k, v = (jnp.asarray(r.standard_normal((B, H, T, D)) * 0.3, jnp.float32)
           for _ in range(3))
from jax.sharding import NamedSharding, PartitionSpec as P
sh = NamedSharding(mesh, P(None, None, "sp", None))
qs, ks, vs = (jax.device_put(a, sh) for a in (q, k, v))
ref = np.asarray(blockwise_attention(q, k, v, causal=True,
                                     use_pallas=False))
ring = np.asarray(ring_attention(qs, ks, vs, mesh, axis="sp",
                                 causal=True, block_size=32))
uly = np.asarray(ulysses_attention(qs, ks, vs, mesh, axis="sp",
                                   causal=True))

# the PALLAS ring path (interpret mode) on the 8-way mesh: per-shard
# flash kernel + cross-shard stats combine must be exact too
from mxnet_tpu.ops import pallas_attention as pa
T2 = 1024                      # T_loc = 128 satisfies the lane gate
q2, k2, v2 = (jnp.asarray(r.standard_normal((B, H, T2, D)) * 0.3,
                          jnp.float32) for _ in range(3))
ref2 = np.asarray(blockwise_attention(q2, k2, v2, causal=True,
                                      use_pallas=False))
pa.INTERPRET = True
try:
    ring_fl = np.asarray(ring_attention(
        *(jax.device_put(a, sh) for a in (q2, k2, v2)),
        mesh, axis="sp", causal=True, block_size=128))
finally:
    pa.INTERPRET = False
print(json.dumps({
    "devices": 8,
    "ring_max_err": float(np.max(np.abs(ring - ref))),
    "ulysses_max_err": float(np.max(np.abs(uly - ref))),
    "ring_flash_max_err": float(np.max(np.abs(ring_fl - ref2))),
}))
"""
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        return {"error": out.stderr[-500:]}
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    result = {"metric": "ring_attention_microbench"}
    if "--chip-only" not in sys.argv:
        result["virtual_mesh"] = mesh_check()
    if "--mesh-only" not in sys.argv:
        from mxnet_tpu import program_cache
        program_cache.place()
        result["single_chip"] = chip_bench()
        result["ring_path_chip"] = ring_chip_bench()
    print(json.dumps(result))
    return 1 if "error" in result.get("virtual_mesh", {}) else 0


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    sys.exit(main())
