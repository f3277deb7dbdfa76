#!/usr/bin/env python
"""Probe: the multi-model SLO serving gateway, end to end, in-process.

Exercises the whole ISSUE-14 surface on virtual devices: a
:class:`ModelRegistry` hosting two models — one single-chip, one
mesh-sharded (``tp=2`` over virtual CPU devices) — with two SLO classes
under deterministic saturation.  Asserts the contracts the gateway
exists for:

1. **mesh parity** — the tp=2 model's outputs are bit-identical to a
   single-chip Predictor over the same (integer-valued) weights;
2. **shed before deadline-miss** — with the queue saturated past the
   shed thresholds, ``batch`` traffic is rejected with
   :class:`AdmissionError` (the 429 path) while every admitted
   ``realtime`` request completes within its deadline: zero ``deadline``
   outcomes, nonzero ``shed`` outcomes;
3. **zero post-warmup compiles** — mixed traffic across both models and
   every bucket never compiles after warmup (per-server verdict AND the
   global Executor::Forward miss counter);
4. **per-model attribution** — each model's bucket programs appear
   under its own ``serving:<model>:b<bucket>:`` namespace on /programz;
5. **bf16 params serve cleanly** — a model registered with bf16 weights
   (integer-valued, so promotion is exact) answers bit-identically to
   its fp32 twin and never compiles after warmup: the param dtype joins
   the serving program cache key, so bf16 and fp32 registrations of the
   same architecture are distinct programs, each compiled exactly once.

Usage:
    python tools/serving_probe.py --smoke    # CI-sized (same coverage)
    python tools/serving_probe.py
"""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# virtual devices BEFORE jax import: the mesh model needs >= 2 chips
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def build_int_mlp(seed):
    """FC16-relu-FC4 with small integer-valued float32 weights: every
    matmul partial sum is exact, so mesh vs single-chip must be
    bit-identical."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    S = mx.symbol
    x = S.var("data")
    h = S.Activation(S.FullyConnected(x, num_hidden=16, name="fc1"),
                     act_type="relu")
    out = S.FullyConnected(h, num_hidden=4, name="fc2")
    rng = np.random.RandomState(seed)
    shapes, _, _ = out.infer_shape(data=(1, 8))
    params = {n: nd.array(rng.randint(-2, 3, s).astype(np.float32))
              for n, s in zip(out.list_arguments(), shapes) if n != "data"}
    return out, params


def main(argv):
    from mxnet_tpu import program_cache
    program_cache.place()       # the one decision on where compiles persist
    smoke = "--smoke" in argv
    import jax
    from mxnet_tpu import health, telemetry
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving import (AdmissionError, ModelRegistry,
                                   QueueFullError)

    telemetry.enable()
    health.enable()
    health.reset()

    devices = jax.devices()
    assert len(devices) >= 2, "need >=2 (virtual) devices, have %d" \
        % len(devices)
    mesh = make_mesh({"tp": 2}, devices=devices[:2])

    reg = ModelRegistry()
    sym1, p1 = build_int_mlp(seed=11)
    sym2, p2 = build_int_mlp(seed=22)
    # rt: plain single-chip; bulk: the SAME architecture sharded tp=2
    reg.register("rt", sym1.tojson(), p1, {"data": (8,)},
                 max_batch_size=4, batch_timeout_ms=1, queue_depth=8,
                 start=False)
    reg.register("bulk", sym2.tojson(), p2, {"data": (8,)}, mesh=mesh,
                 max_batch_size=4, batch_timeout_ms=1)
    rt = reg.get("rt")
    rt.warmup()                      # compiled, but no workers yet
    result = {"probe": "serving", "smoke": smoke}

    try:
        # -- 1. mesh parity ------------------------------------------------
        rng = np.random.RandomState(0)
        rounds = 4 if smoke else 16
        for n in (1, 2, 4):
            X = rng.randint(-2, 3, (n, 8)).astype(np.float32)
            want = Predictor(sym2.tojson(), p2,
                             input_shapes={"data": (n, 8)}) \
                .forward(data=X)[0].asnumpy()
            got = reg.predict({"data": X}, model="bulk")[0]
            assert np.array_equal(got, want), \
                "mesh output diverged from single-chip at rows=%d" % n
        result["mesh_parity"] = True
        result["mesh"] = reg.get("bulk").stats()["mesh"]

        # -- 2. deterministic saturation: shed before deadline-miss --------
        X1 = np.zeros((1, 8), np.float32)
        admitted = []
        for _ in range(4):           # 4/8 occupancy -> shed level 1
            admitted.append(rt.submit({"data": X1}, deadline_ms=30000,
                                      slo_class="realtime"))
        shed = 0
        try:
            rt.submit({"data": X1}, slo_class="batch")
        except AdmissionError:
            shed += 1
        assert shed == 1, "batch traffic was admitted past the shed level"
        for _ in range(4):           # realtime rides to a full queue
            try:
                admitted.append(rt.submit({"data": X1}, deadline_ms=30000,
                                          slo_class="realtime"))
            except QueueFullError:
                break
        rt.start(warmup=False)       # workers drain the saturated queue
        for r in admitted:
            r.result(timeout=60.0)
        assert all(r.outcome == "ok" for r in admitted)
        misses = telemetry.value("serving_requests_total",
                                 outcome="deadline")
        assert misses == 0, "deadline misses under saturation: %r" % misses
        assert telemetry.value("serving_shed_total", slo_class="batch") >= 1
        result["shed_before_deadline_miss"] = True
        result["admitted_realtime"] = len(admitted)
        result["shed_batch"] = int(telemetry.value(
            "serving_shed_total", slo_class="batch"))

        # -- 3. zero post-warmup compiles across the registry --------------
        warm = telemetry.value("op_jit_cache_misses_total",
                               op="Executor::Forward")
        for i in range(rounds):
            n = int(rng.choice([1, 2, 3, 4]))
            X = rng.randint(-2, 3, (n, 8)).astype(np.float32)
            reg.predict({"data": X}, model=("rt", "bulk")[i % 2],
                        slo_class=("realtime", "standard")[i % 2])
        after = telemetry.value("op_jit_cache_misses_total",
                                op="Executor::Forward")
        assert after == warm, "post-warmup compiles: %d" % (after - warm)
        for name in ("rt", "bulk"):
            hc = reg.get(name).health()
            assert hc["post_warmup_compiles"] == 0, (name, hc)
        result["post_warmup_compiles"] = 0

        # -- 4. per-model /programz attribution ----------------------------
        progs = health.programs()
        for m in ("rt", "bulk"):
            for b in (1, 2, 4):
                key = "serving:%s:b%d:forward" % (m, b)
                assert key in progs, "missing %s on /programz" % key
        result["programs"] = sorted(
            n for n in progs if n.startswith("serving:"))

        # -- 5. bf16 params: exact parity, zero post-warmup compiles -------
        from mxnet_tpu import amp
        p1_bf16 = {n: v.astype(amp.compute_dtype()) for n, v in p1.items()}
        reg.register("rt16", sym1.tojson(), p1_bf16, {"data": (8,)},
                     max_batch_size=4, batch_timeout_ms=1)
        for n in (1, 2, 4):
            X = rng.randint(-2, 3, (n, 8)).astype(np.float32)
            want = reg.predict({"data": X}, model="rt")[0]
            got = reg.predict({"data": X}, model="rt16")[0]
            assert np.array_equal(got, want), \
                "bf16 integer weights diverged from fp32 at rows=%d" % n
        warm = telemetry.value("op_jit_cache_misses_total",
                               op="Executor::Forward")
        for i in range(rounds):
            n = int(rng.choice([1, 2, 4]))
            X = rng.randint(-2, 3, (n, 8)).astype(np.float32)
            reg.predict({"data": X}, model="rt16")
        after = telemetry.value("op_jit_cache_misses_total",
                                op="Executor::Forward")
        assert after == warm, \
            "bf16 post-warmup compiles: %d" % (after - warm)
        assert reg.get("rt16").health()["post_warmup_compiles"] == 0
        result["bf16_parity"] = True
        result["bf16_post_warmup_compiles"] = 0
    finally:
        reg.stop_all()
        health.disable()

    result["ok"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
