"""Continuous training health monitor (mxnet_tpu/health.py).

Covers the shared MFU helpers bench.py now delegates to, lowering-only
program cost accounting (XLA cost analysis + runtime donation audit),
step-phase verdict attribution, the EWMA+MAD anomaly trip with its
flight-recorder dump,
the KVStore wire health header (worker -> server straggler table, loud
validation), the serving /healthz verdict, the metric-name lint against
docs/observability.md, and the 2-worker dist straggler acceptance run.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx
from mxnet_tpu import health, nd, telemetry, tracing
from mxnet_tpu.base import MXNetError
from mxnet_tpu.kvstore_server import (KVStoreServer, _check_health_ctx,
                                      recv_msg_full, send_msg)

S = mx.symbol


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    health.reset()
    yield
    health.disable()
    telemetry.disable()
    telemetry.reset()
    health.reset()


# ---------------------------------------------------------------------------
# shared MFU helpers (the code bench.py's two hand-rolled blocks became)
# ---------------------------------------------------------------------------
class TestHelpers:
    @pytest.mark.parametrize("dtype,kind,want", [
        ("bfloat16", "TPU v5 lite", 197.0),     # published rows
        ("int8", "TPU v5 lite", 393.0),
        ("float32", "cpu", 0.25),               # CPU row: any dtype, a
        ("bfloat16", "cpu", 0.25),              # convention, not a peak
        ("bfloat16", None, 0.25),               # None -> jax's kind (cpu)
    ])
    def test_peak_table(self, monkeypatch, dtype, kind, want):
        monkeypatch.delenv("MXNET_HEALTH_PEAK_TFLOPS", raising=False)
        monkeypatch.delenv("BENCH_PEAK_TFLOPS", raising=False)
        assert health.peak_tflops(dtype, device_kind=kind) == want

    @pytest.mark.parametrize("dtype,kind", [
        ("bfloat16", "TPU v9 imaginary"),       # unknown kind: no default
        ("bfloat16", "tpu"),                    # a platform is not a kind
        ("float32", "TPU v5 lite"),             # no published f32 figure
    ])
    def test_peak_unknown_raises(self, monkeypatch, dtype, kind):
        monkeypatch.delenv("MXNET_HEALTH_PEAK_TFLOPS", raising=False)
        monkeypatch.delenv("BENCH_PEAK_TFLOPS", raising=False)
        with pytest.raises(KeyError):
            health.peak_tflops(dtype, device_kind=kind)

    def test_peak_rows_name_their_source(self):
        for kind, row in health._PEAK_TFLOPS.items():
            assert row["source"], kind

    @pytest.mark.parametrize("env,want", [
        ({"BENCH_PEAK_TFLOPS": "123.0"}, 123.0),
        # the health-specific knob wins over the bench one
        ({"BENCH_PEAK_TFLOPS": "123.0",
          "MXNET_HEALTH_PEAK_TFLOPS": "7.5"}, 7.5),
    ])
    def test_peak_env_overrides(self, monkeypatch, env, want):
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        # the override answers even for a kind the table does not know
        assert health.peak_tflops("bfloat16", "TPU v9 imaginary") == want

    def test_achieved_and_fraction(self):
        # 1000 items/s at 1 GFLOP/item = 1 TFLOP/s; 50% of a 2-TFLOP peak
        assert health.achieved_tflops(1000.0, 1e9) == pytest.approx(1.0)
        assert health.mfu_fraction(1000.0, 1e9, 2.0) == pytest.approx(0.5)
        assert health.mfu_fraction(1000.0, 1e9, 0.0) == 0.0

    def test_mfu_impossible(self):
        assert health.mfu_impossible(1.3, "tpu")
        assert not health.mfu_impossible(1.1, "tpu")
        # CPU peaks are a convention, not a measurement: never "impossible"
        assert not health.mfu_impossible(5.0, "cpu")


# ---------------------------------------------------------------------------
# program cost accounting
# ---------------------------------------------------------------------------
class TestProgramRegistration:
    def test_disabled_is_noop(self):
        import jax.numpy as jnp
        import jax
        fn = jax.jit(lambda a: a + 1)
        assert not health.enabled
        assert health.register_program("p", fn, (jnp.ones((4,)),)) is None
        assert health.programs() == {}

    def test_non_jitted_fn_skipped(self):
        health.enable()
        assert health.register_program("p", lambda a: a, (1,)) is None

    def test_cost_and_memory_metrics(self):
        import jax
        import jax.numpy as jnp
        health.enable()
        fn = jax.jit(lambda a, b: a @ b)
        a = jnp.ones((64, 64), jnp.float32)
        pc = health.register_program("matmul", fn, (a, a))
        assert pc is not None
        # 64x64x64 MACs at 2 flops each
        assert pc.flops == pytest.approx(2 * 64 ** 3, rel=0.5)
        assert pc.arg_bytes == 2 * 64 * 64 * 4
        assert pc.out_bytes == 64 * 64 * 4
        # default mode is lowering-only: temp accounting needs the
        # MXNET_HEALTH_DEEP opt-in (it pays an extra compile)
        assert pc.temp_bytes is None
        assert telemetry.value("program_flops", program="matmul") == pc.flops
        assert telemetry.value("program_hbm_bytes", program="matmul",
                               kind="args") == pc.arg_bytes
        assert telemetry.value("program_hbm_bytes", program="matmul",
                               kind="output") == pc.out_bytes
        # registration never compiles; the normal call right after still
        # works and produces the same numbers
        np.testing.assert_allclose(np.asarray(fn(a, a)), np.full((64, 64),
                                   64.0), rtol=1e-5)

    def test_deep_mode_reports_temp_bytes(self, monkeypatch):
        import jax
        import jax.numpy as jnp
        monkeypatch.setenv("MXNET_HEALTH_DEEP", "1")
        health.enable()
        fn = jax.jit(lambda a, b: (a @ b) @ (a + b))
        a = jnp.ones((32, 32), jnp.float32)
        pc = health.register_program("deep", fn, (a, a))
        assert pc is not None
        assert pc.temp_bytes is not None and pc.temp_bytes >= 0
        assert telemetry.value("program_hbm_bytes", program="deep",
                               kind="temp") == pc.temp_bytes

    def test_program_flops_total_sums_tuple(self):
        import jax
        import jax.numpy as jnp
        health.enable()
        x = jnp.ones((8, 8), jnp.float32)
        health.register_program("pa", jax.jit(lambda a: a @ a), (x,))
        health.register_program("pb", jax.jit(lambda a: a @ a), (x,))
        fa = health.program_flops_total("pa")
        assert fa > 0
        assert health.program_flops_total(("pa", "pb")) == pytest.approx(
            2 * fa)
        assert health.program_flops_total(("pa", "missing")) == fa
        assert health.program_flops_total(None) == 0.0

    def test_donation_audit_honored(self):
        # runtime truth: a donated jit call invalidates the donated input,
        # the audit sees freed bytes and no leak
        import jax
        import jax.numpy as jnp
        health.enable()
        fn = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
        a = jnp.ones((16, 16), jnp.float32)
        b = jnp.ones((16, 16), jnp.float32)
        health.register_program("don_ok", fn, (a, b), donated=True)
        fn(a, b).block_until_ready()
        freed, leaked = health.audit_donation("don_ok", (a,))
        assert freed == 16 * 16 * 4 and leaked == 0
        pc = health.programs()["don_ok"]
        assert pc.donated_bytes == freed
        assert not pc.donation_leak
        assert telemetry.value("program_donated_bytes",
                               program="don_ok") == freed
        assert telemetry.value("program_donation_leaks_total",
                               program="don_ok") == 0.0

    def test_donation_audit_flags_leak(self):
        # a program that never consumed its "donated" inputs: every byte
        # survives execution, the counter trips
        import jax
        import jax.numpy as jnp
        health.enable()
        fn = jax.jit(lambda a, b: a + b)  # no donation actually wired
        a = jnp.ones((8, 8), jnp.float32)
        b = jnp.ones((8, 8), jnp.float32)
        health.register_program("don_leak", fn, (a, b), donated=True)
        fn(a, b).block_until_ready()
        freed, leaked = health.audit_donation("don_leak", (a,))
        assert freed == 0 and leaked == 8 * 8 * 4
        pc = health.programs()["don_leak"]
        assert pc.donation_leak
        assert telemetry.value("program_donation_leaks_total",
                               program="don_leak") == 1.0


# ---------------------------------------------------------------------------
# step monitor: verdict attribution, MFU, anomaly trip
# ---------------------------------------------------------------------------
class TestStepMonitor:
    def test_verdict_attribution(self):
        health.enable()
        m = health.monitor
        m.note_phase("input", 0.08)
        m.observe_step(0.1)
        assert telemetry.value("step_health_verdict",
                               cause="input_bound") == 1.0
        assert telemetry.value("step_health_verdict",
                               cause="compute_bound") == 0.0
        # phase accumulators reset per window: the next quiet window is
        # compute-bound again
        m.observe_step(0.1)
        assert telemetry.value("step_health_verdict",
                               cause="compute_bound") == 1.0
        m.note_phase("sync", 0.09)
        m.observe_step(0.1)
        assert telemetry.value("step_health_verdict",
                               cause="sync_bound") == 1.0

    def test_mfu_gauge_sane_on_cpu(self):
        import jax
        import jax.numpy as jnp
        health.enable()
        a = jnp.ones((64, 64), jnp.float32)
        health.register_program("step", jax.jit(lambda x: x @ x), (a,))
        health.monitor.observe_step(0.05, program="step")
        mfu = telemetry.value("step_mfu_pct")
        # 524288 flops over 50ms against the 0.25-TFLOP cpu convention:
        # tiny but strictly positive, and nowhere near impossible
        assert 0.0 < mfu < 120.0
        snap = health.monitor.snapshot()
        assert snap["mfu_pct"] == pytest.approx(mfu)
        assert snap["samples"] == 1

    def test_anomaly_trip_and_flight_dump(self, tmp_path, monkeypatch):
        dump = str(tmp_path / "flight.json")
        monkeypatch.setenv("MXNET_FLIGHT_RECORDER_PATH", dump)
        health.enable()
        m = health.monitor
        for _ in range(20):
            m.observe_step(0.01)
        assert telemetry.value("health_anomalies_total",
                               cause="compute_bound") == 0.0
        m.observe_step(0.1)        # 10x the EWMA: way past band and 2x
        assert telemetry.value("health_anomalies_total",
                               cause="compute_bound") == 1.0
        assert os.path.exists(dump)
        events = json.load(open(dump))["events"]
        anom = [e for e in events if e.get("name") == "Health::Anomaly"]
        assert anom and anom[0]["args"]["cause"] == "compute_bound"
        assert anom[0]["args"]["step_seconds"] == pytest.approx(0.1)
        assert telemetry.value("flight_recorder_dumps_total",
                               reason="health_anomaly") == 1.0
        # ledger marks the anomalous window
        assert health.monitor.snapshot()["ledger"][-1]["anomaly"]

    def test_anomaly_debounced(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MXNET_FLIGHT_RECORDER_PATH",
                           str(tmp_path / "f.json"))
        health.enable()
        m = health.monitor
        for _ in range(20):
            m.observe_step(0.01)
        m.observe_step(0.1)
        m.observe_step(0.1)        # inside the 5s debounce: no second trip
        assert telemetry.value("health_anomalies_total",
                               cause="compute_bound") == 1.0

    def test_steady_steps_never_trip(self):
        health.enable()
        m = health.monitor
        for _ in range(50):
            m.observe_step(0.01 + np.random.uniform(-0.0005, 0.0005))
        fam = telemetry.registry().get("health_anomalies_total")
        assert all(v == 0.0 for _, v in fam.samples())

    def test_ewma_tracks_step_time(self):
        health.enable()
        for _ in range(30):
            health.monitor.observe_step(0.02)
        assert telemetry.value("step_seconds_ewma") == pytest.approx(
            0.02, rel=0.05)


# ---------------------------------------------------------------------------
# worker straggler table + wire header
# ---------------------------------------------------------------------------
class TestWorkerTable:
    def test_straggler_band(self):
        health.enable()
        w = health.workers
        w.update("0", 0.01)
        # single rank: no verdict possible
        assert "straggler" not in w.snapshot()["0"]
        w.update("1", 0.2)         # 0.2 > 1.75 * median(0.105)
        snap = w.snapshot()
        assert snap["0"]["straggler"] is False
        assert snap["1"]["straggler"] is True
        assert telemetry.value("worker_step_seconds", rank="1") == 0.2
        assert telemetry.value("worker_straggler_verdict", rank="1") == 1.0
        assert telemetry.value("worker_straggler_verdict", rank="0") == 0.0

    def test_close_ranks_not_flagged(self):
        health.enable()
        w = health.workers
        w.update("0", 0.010)
        w.update("1", 0.012)       # 20% apart: inside the 1.75x band
        snap = w.snapshot()
        assert not snap["0"]["straggler"] and not snap["1"]["straggler"]


class TestWireHealthHeader:
    def test_check_health_ctx_accepts(self):
        assert _check_health_ctx({"r": "3", "st": 0.25}) == \
            {"r": "3", "st": 0.25}

    @pytest.mark.parametrize("hc", [
        "notadict",
        {"r": "0"},                          # missing st
        {"r": "0", "st": 0.1, "x": 1},       # unknown key
        {"r": "", "st": 0.1},                # empty rank
        {"r": "abc", "st": 0.1},             # non-digit rank
        {"r": "1" * 17, "st": 0.1},          # rank too long
        {"r": "0", "st": -1.0},              # negative step
        {"r": "0", "st": 1e7},               # absurd step
        {"r": "0", "st": True},              # bool is not a number here
    ])
    def test_check_health_ctx_loud_rejects(self, hc):
        telemetry.enable()
        before = telemetry.value("kvstore_frame_errors_total")
        with pytest.raises(MXNetError):
            _check_health_ctx(hc)
        assert telemetry.value("kvstore_frame_errors_total") == before + 1

    def test_header_roundtrip_in_process(self, monkeypatch):
        """Worker with health on piggybacks its step time; the in-process
        server lands it in the (shared) WorkerTable."""
        health.enable()
        srv = KVStoreServer(num_workers=1).start()
        monkeypatch.setenv("MXNET_PS_URI", "127.0.0.1")
        monkeypatch.setenv("MXNET_PS_PORT", str(srv.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_WORKER_ID", "0")
        try:
            kv = mx.kv.create("dist_async")
            health.monitor.observe_step(0.042)   # the latest closed window
            kv.init("w", nd.ones((4,)))
            out = nd.zeros((4,))
            kv.pull("w", out=out)
            kv.close()
        finally:
            srv.shutdown()
        assert telemetry.value("worker_step_seconds",
                               rank="0") == pytest.approx(0.042)

    def test_no_header_before_first_step(self, monkeypatch):
        """Health on but no step observed yet: nothing to report, the
        frame stays headerless for `h` and the table stays empty."""
        health.enable()
        srv = KVStoreServer(num_workers=1).start()
        monkeypatch.setenv("MXNET_PS_URI", "127.0.0.1")
        monkeypatch.setenv("MXNET_PS_PORT", str(srv.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_WORKER_ID", "0")
        try:
            kv = mx.kv.create("dist_async")
            kv.init("w", nd.ones((4,)))
            kv.close()
        finally:
            srv.shutdown()
        assert health.workers.snapshot() == {}


# ---------------------------------------------------------------------------
# /statusz
# ---------------------------------------------------------------------------
class TestStatusz:
    def test_snapshot_shape(self):
        import jax
        import jax.numpy as jnp
        health.enable()
        a = jnp.ones((8, 8), jnp.float32)
        health.register_program("step", jax.jit(lambda x: x @ x), (a,))
        health.monitor.observe_step(0.03, program="step")
        health.workers.update("0", 0.03)
        doc = json.loads(json.dumps(health.statusz()))   # JSON-able
        assert doc["enabled"] is True
        assert doc["platform"] == "cpu"
        assert doc["peak_tflops"] > 0
        assert "step" in doc["programs"]
        assert doc["programs"]["step"]["flops"] > 0
        assert doc["step"]["cause"] == "compute_bound"
        assert doc["workers"]["0"]["step_seconds"] == pytest.approx(0.03)

    def test_statusz_http_endpoint(self):
        health.enable()
        import urllib.request
        port = telemetry.start_http_server(port=0)
        try:
            body = urllib.request.urlopen(
                "http://127.0.0.1:%d/statusz" % port, timeout=5).read()
            doc = json.loads(body)
            assert doc["enabled"] is True
            assert "programs" in doc and "step" in doc and "workers" in doc
        finally:
            telemetry.stop_http_server()


# ---------------------------------------------------------------------------
# live training-step integration: on_step wiring + program registration
# ---------------------------------------------------------------------------
class TestTrainingIntegration:
    def test_fused_trainer_registers_and_steps(self):
        from mxnet_tpu import gluon
        health.enable()
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(8, activation="relu"))
        net.add(gluon.nn.Dense(4))
        net.initialize()
        net.hybridize()
        x = nd.array(np.random.rand(4, 6).astype(np.float32))
        y = nd.array(np.random.randint(0, 4, (4,)))
        net(x).wait_to_read()
        ft = mx.FusedTrainer(net, "softmax_cross_entropy", "sgd",
                             {"learning_rate": 0.1})
        for _ in range(3):
            ft.step(x, y)
        progs = health.programs()
        assert "fused_trainer_step" in progs
        assert progs["fused_trainer_step"].flops > 0
        # whole-step program donates its state buffers; the runtime audit
        # after the first dispatch must see them actually invalidated
        # (a leak here is the broken-donation-chain bug)
        assert progs["fused_trainer_step"].donation_requested
        assert progs["fused_trainer_step"].donated_bytes is not None
        assert progs["fused_trainer_step"].donated_bytes > 0
        assert not progs["fused_trainer_step"].donation_leak
        # two closed windows from three dispatches
        assert health.monitor.snapshot()["samples"] == 2

    def test_module_step_records_program(self):
        from mxnet_tpu.module import Module
        health.enable()
        data = S.var("data")
        net = S.FullyConnected(data, num_hidden=4, name="fc")
        net = S.SoftmaxOutput(net, name="softmax")
        mod = Module(net, context=mx.cpu())
        mod.bind(data_shapes=[("data", (4, 6))],
                 label_shapes=[("softmax_label", (4,))])
        mod.init_params()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        from mxnet_tpu.io import DataBatch
        batch = DataBatch(data=[nd.array(np.random.rand(4, 6))],
                          label=[nd.array(np.zeros(4))])
        for _ in range(3):
            mod.forward(batch)
            mod.backward()
            mod.update()
        assert health.monitor.snapshot()["samples"] >= 1
        # some step program (fused single-device or split) was registered
        assert health.programs()


# ---------------------------------------------------------------------------
# serving /healthz verdict
# ---------------------------------------------------------------------------
def _tiny_server(**kwargs):
    x = S.var("data")
    out = S.FullyConnected(x, num_hidden=4, no_bias=True, name="fc")
    params = {"fc_weight": nd.array(np.ones((4, 8), np.float32))}
    from mxnet_tpu.serving import ModelServer
    kwargs.setdefault("max_batch_size", 8)
    kwargs.setdefault("batch_timeout_ms", 5)
    return ModelServer(out.tojson(), params,
                       example_shapes={"data": (8,)}, **kwargs)


class TestServingHealth:
    def test_fresh_server_is_serving(self):
        srv = _tiny_server()
        doc = srv.health()
        assert doc["status"] == "serving"
        assert doc["causes"] == []
        assert doc["queue_saturation"] == 0.0
        assert doc["post_warmup_compiles"] is None   # not warmed yet

    def test_deadline_miss_rate_degrades(self):
        srv = _tiny_server()
        for _ in range(15):
            srv._recent_outcomes.append("deadline")
        assert srv.health()["status"] == "serving"   # < 20 samples
        for _ in range(10):
            srv._recent_outcomes.append("deadline")
        doc = srv.health()
        assert doc["status"] == "degraded"
        assert "deadline_misses" in doc["causes"]
        assert doc["deadline_miss_rate"] == 1.0

    def test_mixed_outcomes_below_threshold(self):
        srv = _tiny_server()
        for _ in range(30):
            srv._recent_outcomes.append("ok")
        for _ in range(10):
            srv._recent_outcomes.append("deadline")
        assert srv.health()["status"] == "serving"   # 25% < 50%

    def test_stopped_degrades(self):
        srv = _tiny_server()
        srv.start(warmup=False)
        srv.stop(drain=False)
        doc = srv.health()
        assert doc["status"] == "degraded"
        assert "stopped" in doc["causes"]

    def test_healthz_http_codes(self):
        import urllib.error
        import urllib.request
        from mxnet_tpu import serving
        srv = _tiny_server()
        port = serving.start_http_server(srv, port=0)
        try:
            r = urllib.request.urlopen(
                "http://127.0.0.1:%d/healthz" % port, timeout=5)
            assert r.status == 200
            assert json.loads(r.read())["status"] == "serving"
            for _ in range(25):
                srv._recent_outcomes.append("deadline")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    "http://127.0.0.1:%d/healthz" % port, timeout=5)
            assert ei.value.code == 503
            doc = json.loads(ei.value.read())
            assert doc["status"] == "degraded"
            assert "deadline_misses" in doc["causes"]
        finally:
            serving.stop_http_server()


# metric-name lint moved to graftlint GL005 (tools/graftlint, exercised by
# tests/test_graftlint.py): the static scan covers EVERY telemetry
# instrument in the tree, not just the modules an import list remembers.


# ---------------------------------------------------------------------------
# probe smoke (slow: runs the whole bench in a subprocess)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_probe_health_smoke():
    import subprocess
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "probe_health.py"),
         "--smoke"],
        cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["ok"] is True and rec["probe"] == "health"


# ---------------------------------------------------------------------------
# 2-worker dist straggler acceptance run
# ---------------------------------------------------------------------------
class TestDistStraggler:
    def test_two_worker_straggler_verdict(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import launch

        snap_path = str(tmp_path / "health_snapshot.json")
        worker = os.path.join(REPO, "tests", "dist_health_worker.py")
        rc = launch.launch_local(
            2, [sys.executable, worker],
            env_extra={"JAX_PLATFORMS": "cpu", "MXNET_TEST_PLATFORM": "cpu",
                       "MXNET_HEALTH": "1",
                       "MXNET_HEALTH_SNAPSHOT_PATH": snap_path},
            num_servers=1)
        assert rc == 0
        # the server writes between serve_forever returning and launcher
        # cleanup; give the race a moment
        deadline = time.time() + 10
        while not os.path.exists(snap_path) and time.time() < deadline:
            time.sleep(0.1)
        assert os.path.exists(snap_path)
        table = json.load(open(snap_path))["workers"]
        assert set(table) == {"0", "1"}
        assert table["0"]["step_seconds"] == pytest.approx(0.01)
        assert table["1"]["step_seconds"] == pytest.approx(0.2)
        # rank 1 reports 20x rank 0: far past the 1.75x-median band
        assert table["1"]["straggler"] is True
        assert table["0"]["straggler"] is False
