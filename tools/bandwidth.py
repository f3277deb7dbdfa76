#!/usr/bin/env python
"""Measure kvstore/collective communication bandwidth.

Reference analog: ``tools/bandwidth/`` (SURVEY.md §6 benchmark harnesses) —
measures the gradient-aggregation path's throughput.  Here: the XLA
all-reduce over the device mesh (ICI) and, under a multi-process launch,
the cross-process DCN all-reduce used by dist_sync.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def bench_device_allreduce(size_mb: float, iters: int) -> float:
    """All-reduce over all local devices via psum (the kvstore 'device'
    path); returns GB/s of algorithmic bandwidth."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map

    devs = jax.local_devices()
    n = len(devs)
    if n < 2:
        raise SystemExit("device all-reduce needs >= 2 devices (have %d); "
                         "use XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N on CPU" % n)
    elems = int(size_mb * 1e6 / 4)
    mesh = Mesh(np.asarray(devs), ("d",))
    x = jnp.ones((n, elems), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("d")))
    f = jax.jit(shard_map(lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                          in_specs=P("d"), out_specs=P("d")))
    f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(x)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    # ring all-reduce moves 2(n-1)/n of the data per device
    gbytes = iters * elems * 4 * 2 * (n - 1) / n / 1e9
    return gbytes / dt


def bench_dist_allreduce(size_mb: float, iters: int) -> float:
    """Cross-process all-reduce (the dist_sync path); run under
    tools/launch.py -n W."""
    from mxnet_tpu.parallel import process_group
    import jax.numpy as jnp

    pg = process_group()
    if pg.size < 2:
        raise SystemExit("dist all-reduce needs >= 2 processes — run under "
                         "tools/launch.py -n W (single-process allreduce "
                         "is an identity; there is nothing to measure)")
    elems = int(size_mb * 1e6 / 4)
    x = jnp.ones((elems,), jnp.float32)
    pg.allreduce(x)                       # warm the compiled collective
    t0 = time.perf_counter()
    for _ in range(iters):
        out = pg.allreduce(x)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    n = pg.size
    gbytes = iters * elems * 4 * 2 * max(n - 1, 1) / max(n, 1) / 1e9
    return gbytes / dt


def bench_ps(iters: int):
    """Parameter-server push/pull throughput vs payload size (VERDICT r4
    item 4: the dist_async wire had no measured number).  In-process
    server on loopback — measures the codec + TCP + server-apply path,
    an upper bound on what a real NIC would see."""
    import json

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.kvstore_server import KVStoreServer

    srv = KVStoreServer(num_workers=1).start()
    os.environ["MXNET_PS_URI"] = "127.0.0.1"
    os.environ["MXNET_PS_PORT"] = str(srv.port)
    os.environ["DMLC_NUM_WORKER"] = "1"
    rows = []
    try:
        kv = mx.kv.create("dist_async")
        for size_mb in (0.25, 1.0, 4.0, 16.0, 64.0):
            n = int(size_mb * 1e6 / 4)
            key = "k%g" % size_mb
            x = nd.array(np.ones(n, np.float32))
            kv.init(key, x)
            out = nd.zeros((n,))
            row = {"size_mb": size_mb}
            for name, fn in (("push", lambda: kv.push(key, x)),
                             ("pull", lambda: kv.pull(key, out=out))):
                fn()                                   # warm
                t0 = time.perf_counter()
                for _ in range(iters):
                    fn()
                dt = time.perf_counter() - t0
                row[name + "_gbps"] = round(
                    iters * n * 4 / dt / 1e9, 3)
            # compressed push: same logical payload, 1/16 wire bytes
            kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
            kv.push(key, x)
            t0 = time.perf_counter()
            for _ in range(iters):
                kv.push(key, x)
            dt = time.perf_counter() - t0
            row["push_2bit_logical_gbps"] = round(
                iters * n * 4 / dt / 1e9, 3)
            kv.set_gradient_compression(None)          # off for next size
            rows.append(row)
        kv.close()
    finally:
        srv.shutdown()
    print(json.dumps({"metric": "ps_bandwidth", "iters": iters,
                      "rows": rows}))


def main():
    from mxnet_tpu import program_cache
    program_cache.place()       # the one decision on where compiles persist
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=float, default=64.0)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--mode", choices=["device", "dist", "ps"],
                    default="device")
    args = ap.parse_args()
    if args.mode == "device":
        bw = bench_device_allreduce(args.size_mb, args.iters)
        print("device all-reduce (%g MB x %d): %.2f GB/s"
              % (args.size_mb, args.iters, bw))
    elif args.mode == "ps":
        bench_ps(args.iters)
    else:
        bw = bench_dist_allreduce(args.size_mb, args.iters)
        print("dist all-reduce (%g MB x %d): %.2f GB/s"
              % (args.size_mb, args.iters, bw))


if __name__ == "__main__":
    main()
