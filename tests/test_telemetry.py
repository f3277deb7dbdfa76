"""Runtime telemetry: registry semantics, exporters, instrumentation.

Covers the metrics registry (labels, histogram buckets, thread safety
under the ThreadedEngine worker pool), the Prometheus/JSON exporters, the
disabled-by-default no-op path, and the end-to-end acceptance flow: a
2-worker dist_async KVStore session plus one NDArrayIter epoch must leave
non-zero engine, kvstore and io series in ``telemetry.snapshot()``, and
``telemetry.prometheus_text()`` must parse line-by-line as valid
text-exposition.
"""
import os
import re
import json
import struct
import sys
import threading
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.telemetry.registry import (MetricRegistry, log_buckets,
                                          DEFAULT_TIME_BUCKETS)
from mxnet_tpu.telemetry import export as tex


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts from zeroed samples and ends disabled."""
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.stop_http_server()
    telemetry.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_inc_and_get(self):
        r = MetricRegistry()
        c = r.counter("c_total", "help text")
        assert c.get() == 0
        c.inc()
        c.inc(2.5)
        assert c.get() == 3.5

    def test_counter_rejects_negative(self):
        r = MetricRegistry()
        c = r.counter("c_total")
        with pytest.raises(MXNetError, match="cannot decrease"):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        r = MetricRegistry()
        g = r.gauge("depth")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.get() == 6

    def test_labels_create_independent_series(self):
        r = MetricRegistry()
        c = r.counter("ops_total", "", ("engine",))
        c.labels(engine="a").inc(3)
        c.labels(engine="b").inc(4)
        assert c.labels(engine="a").get() == 3
        assert c.labels(engine="b").get() == 4
        # same label values -> same child object (cached)
        assert c.labels(engine="a") is c.labels(engine="a")

    def test_label_set_is_strict(self):
        r = MetricRegistry()
        c = r.counter("ops_total", "", ("engine",))
        with pytest.raises(MXNetError, match="takes labels"):
            c.labels(wrong="x")
        with pytest.raises(MXNetError, match="takes labels"):
            c.labels()
        with pytest.raises(MXNetError, match="bind them"):
            c.inc()  # labelled family has no default child

    def test_name_and_label_validation(self):
        r = MetricRegistry()
        with pytest.raises(MXNetError, match="invalid metric name"):
            r.counter("0bad")
        with pytest.raises(MXNetError, match="invalid label name"):
            r.counter("ok_total", "", ("le-gal",))
        with pytest.raises(MXNetError, match="invalid label name"):
            r.counter("ok2_total", "", ("__reserved",))

    def test_get_or_create_is_shared_and_type_checked(self):
        r = MetricRegistry()
        a = r.counter("shared_total")
        b = r.counter("shared_total")
        assert a is b
        with pytest.raises(MXNetError, match="already registered as"):
            r.gauge("shared_total")
        with pytest.raises(MXNetError, match="already registered with"):
            r.counter("shared_total", "", ("extra",))

    def test_histogram_buckets_cumulative(self):
        r = MetricRegistry()
        h = r.histogram("lat_seconds", "", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        data = h.get()
        assert data["buckets"] == {"0.1": 2, "1": 3, "10": 4, "+Inf": 5}
        assert data["count"] == 5
        assert data["sum"] == pytest.approx(55.6)

    def test_histogram_le_semantics_on_boundary(self):
        # le is inclusive: a sample exactly on a bound lands in that bucket
        r = MetricRegistry()
        h = r.histogram("b_seconds", "", buckets=(1.0, 2.0))
        h.observe(1.0)
        assert h.get()["buckets"]["1"] == 1

    def test_histogram_drops_nan(self):
        r = MetricRegistry()
        h = r.histogram("n_seconds", "", buckets=(1.0,))
        h.observe(float("nan"))
        assert h.get()["count"] == 0

    def test_histogram_rejects_bad_buckets(self):
        r = MetricRegistry()
        with pytest.raises(MXNetError, match="sorted and unique"):
            r.histogram("h1_seconds", "", buckets=(2.0, 1.0))
        with pytest.raises(MXNetError, match="sorted and unique"):
            r.histogram("h2_seconds", "", buckets=(1.0, 1.0))

    def test_log_buckets_shape(self):
        b = log_buckets(1e-3, 1.0, per_decade=1)
        assert b == (1e-3, 1e-2, 1e-1, 1.0)
        assert DEFAULT_TIME_BUCKETS[0] == 1e-6
        assert DEFAULT_TIME_BUCKETS[-1] >= 10.0

    def test_reset_keeps_bound_children_live(self):
        """Module-level cached bindings (engine.py style) must survive a
        registry reset: zeroed, not orphaned."""
        r = MetricRegistry()
        bound = r.counter("live_total", "", ("k",)).labels(k="x")
        bound.inc(7)
        r.reset()
        assert bound.get() == 0
        bound.inc()
        assert r.counter("live_total", "", ("k",)).labels(k="x").get() == 1

    def test_concurrent_increments_from_threads(self):
        r = MetricRegistry()
        c = r.counter("race_total")
        h = r.histogram("race_seconds", "", buckets=(0.5,))

        def hammer():
            for _ in range(1000):
                c.inc()
                h.observe(0.1)

        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.get() == 8000
        assert h.get()["count"] == 8000

    def test_concurrent_increments_from_threaded_engine(self):
        """Increments pushed through the ThreadedEngine worker pool all
        land (the family lock is the only synchronization)."""
        from mxnet_tpu import engine
        r = MetricRegistry()
        c = r.counter("eng_total")
        eng = engine.ThreadedEngine(num_workers=4)
        try:
            for _ in range(200):
                eng.push(lambda: c.inc(), [], [])
            eng.wait_for_all()
        finally:
            eng.stop()
        assert c.get() == 200

    def test_value_accessor(self):
        telemetry.counter("acc_total", "", ("k",)).labels(k="a").inc(2)
        assert telemetry.value("acc_total", k="a") == 2
        assert telemetry.value("acc_total", k="never") == 0
        assert telemetry.value("no_such_metric") == 0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
# One text-exposition line: comment, or `name{labels} value`.
_PROM_COMMENT = re.compile(
    r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*( .*)?$")
_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})?'
    r" -?(\d+(\.\d+)?([eE][+-]?\d+)?|Inf|NaN)$")


def _assert_valid_prometheus(text):
    assert text.endswith("\n")
    for line in text.splitlines():
        assert _PROM_COMMENT.match(line) or _PROM_SAMPLE.match(line), \
            "invalid exposition line: %r" % line


class TestExporters:
    def test_counter_and_gauge_text(self):
        r = MetricRegistry()
        r.counter("c_total", "a counter").inc(3)
        r.gauge("g", "a gauge", ("ctx",)).labels(ctx="cpu(0)").set(1.5)
        text = tex.prometheus_text(r)
        assert "# HELP c_total a counter\n" in text
        assert "# TYPE c_total counter\n" in text
        assert "\nc_total 3\n" in text
        assert '\ng{ctx="cpu(0)"} 1.5\n' in text
        _assert_valid_prometheus(text)

    def test_histogram_text_series(self):
        r = MetricRegistry()
        h = r.histogram("lat_seconds", "", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = tex.prometheus_text(r)
        assert '\nlat_seconds_bucket{le="0.1"} 1\n' in text
        assert '\nlat_seconds_bucket{le="1"} 2\n' in text
        assert '\nlat_seconds_bucket{le="+Inf"} 2\n' in text
        assert "\nlat_seconds_count 2\n" in text
        assert re.search(r"\nlat_seconds_sum 0\.55\d*\n", text)
        _assert_valid_prometheus(text)

    def test_label_escaping(self):
        r = MetricRegistry()
        r.counter("e_total", "", ("p",)).labels(p='a"b\\c\nd').inc()
        text = tex.prometheus_text(r)
        assert '{p="a\\"b\\\\c\\nd"}' in text
        _assert_valid_prometheus(text)

    def test_snapshot_structure_and_json(self):
        r = MetricRegistry()
        r.counter("c_total", "hh", ("k",)).labels(k="v").inc(2)
        r.histogram("h_seconds", "", buckets=(1.0,)).observe(0.5)
        snap = tex.snapshot(r)
        assert snap["c_total"]["type"] == "counter"
        assert snap["c_total"]["help"] == "hh"
        assert snap["c_total"]["samples"] == [
            {"labels": {"k": "v"}, "value": 2.0}]
        hs = snap["h_seconds"]["samples"][0]
        assert hs["count"] == 1 and hs["buckets"]["+Inf"] == 1
        # round-trips through json
        assert json.loads(tex.snapshot_json(r)) == json.loads(
            json.dumps(snap))

    def test_http_endpoint(self):
        telemetry.counter("http_total").inc(4)
        port = telemetry.start_http_server(port=0)
        try:
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/metrics" % port, timeout=5) as resp:
                assert resp.headers["Content-Type"].startswith("text/plain")
                body = resp.read().decode()
            assert "http_total 4" in body
            _assert_valid_prometheus(body)
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/metrics.json" % port,
                    timeout=5) as resp:
                data = json.loads(resp.read().decode())
            assert data["http_total"]["samples"][0]["value"] == 4
        finally:
            telemetry.stop_http_server()


# ---------------------------------------------------------------------------
# disabled-by-default no-op
# ---------------------------------------------------------------------------
class TestDisabledNoop:
    def test_disabled_leaves_builtin_metrics_untouched(self):
        assert telemetry.enabled is False
        from mxnet_tpu import engine
        eng = engine.ThreadedEngine(num_workers=2)
        try:
            for _ in range(10):
                eng.push(lambda: None, [], [])
            eng.wait_for_all()
        finally:
            eng.stop()
        it = mx.io.NDArrayIter(np.ones((8, 2)), np.zeros(8), batch_size=4)
        for _ in it:
            pass
        assert telemetry.value("engine_ops_pushed_total",
                               engine="threaded") == 0
        assert telemetry.value("io_batches_total", iter="NDArrayIter") == 0

    def test_enable_disable_roundtrip(self):
        telemetry.enable()
        assert telemetry.enabled is True
        telemetry.disable()
        assert telemetry.enabled is False


# ---------------------------------------------------------------------------
# instrumentation sites
# ---------------------------------------------------------------------------
class TestInstrumentation:
    def test_engine_counters_and_dispatch_histogram(self):
        from mxnet_tpu import engine
        telemetry.enable()
        eng = engine.ThreadedEngine(num_workers=2)
        try:
            for _ in range(25):
                eng.push(lambda: None, [], [])
            eng.wait_for_all()
        finally:
            eng.stop()
        assert telemetry.value("engine_ops_pushed_total",
                               engine="threaded") == 25
        assert telemetry.value("engine_ops_completed_total",
                               engine="threaded") == 25
        assert telemetry.value("engine_dispatch_latency_seconds",
                               engine="threaded") == 25
        # queue fully drained by wait_for_all
        assert telemetry.value("engine_queue_depth", engine="threaded") == 0

    def test_executor_histograms_via_profiler_span(self):
        telemetry.enable()
        x = mx.sym.Variable("x")
        y = mx.sym.FullyConnected(x, num_hidden=3, name="fc")
        ex = y.simple_bind(mx.cpu(), x=(2, 5))
        ex.forward(is_train=True, x=nd.ones((2, 5)))
        ex.backward()
        assert telemetry.value("executor_forward_dispatch_seconds") >= 1
        assert telemetry.value("executor_backward_dispatch_seconds") >= 1

    def test_profiler_counter_bridges_to_gauge(self):
        telemetry.enable()
        from mxnet_tpu import profiler
        c = profiler.Domain("train").new_counter("samples", 10)
        c.increment(5)
        assert telemetry.value("profiler_counter", domain="train",
                               counter="samples") == 15

    def test_trainer_step_and_sync_metrics(self):
        telemetry.enable()
        from mxnet_tpu.gluon import nn, Trainer
        net = nn.Dense(2, in_units=3)
        net.initialize()
        # a real local kvstore (the "local" string resolves to None for a
        # single device) so the grad-sync path actually runs
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1},
                          kvstore=mx.kv.create("local"),
                          update_on_kvstore=False)
        from mxnet_tpu import autograd
        data = nd.ones((4, 3))
        with autograd.record():
            loss = net(data).sum()
        loss.backward()
        trainer.step(4)
        assert telemetry.value("trainer_steps_total") == 1
        assert telemetry.value("trainer_grad_sync_seconds") == 1
        assert telemetry.value("kvstore_push_total", type="local") >= 1


# ---------------------------------------------------------------------------
# kvstore wire-frame validation (bounds checks + frame-error counter)
# ---------------------------------------------------------------------------
class _FakeSock:
    def __init__(self, data: bytes):
        self._data = data
        self._pos = 0

    def recv(self, n):
        chunk = self._data[self._pos:self._pos + n]
        self._pos += len(chunk)
        return chunk


def _frame(payload: bytes) -> bytes:
    return struct.pack("<Q", len(payload)) + payload


class TestWireFrameValidation:
    def _errors(self):
        return telemetry.value("kvstore_frame_errors_total")

    def test_valid_roundtrip(self):
        from mxnet_tpu import kvstore_server as ps
        sent = []

        class Cap:
            def sendall(self, b):
                sent.append(b)

        ps.send_msg(Cap(), ("push", "k", np.arange(3, dtype=np.float32)))
        msg = ps.recv_msg(_FakeSock(b"".join(sent)))
        assert msg[0] == "push" and msg[1] == "k"
        np.testing.assert_array_equal(np.asarray(msg[2]), [0, 1, 2])

    def test_truncated_frame(self):
        from mxnet_tpu.kvstore_server import recv_msg
        before = self._errors()
        with pytest.raises(MXNetError, match="shorter than"):
            recv_msg(_FakeSock(_frame(b"\x01\x02")))
        assert self._errors() == before + 1

    def test_header_length_overrun(self):
        from mxnet_tpu.kvstore_server import recv_msg
        before = self._errors()
        payload = struct.pack("<I", 1000) + b"x"
        with pytest.raises(MXNetError, match="overruns"):
            recv_msg(_FakeSock(_frame(payload)))
        assert self._errors() == before + 1

    def test_blob_length_field_overrun(self):
        from mxnet_tpu.kvstore_server import recv_msg
        hdr = json.dumps(["ping"]).encode()
        # declares 1 blob but provides no 8-byte length field
        payload = (struct.pack("<I", len(hdr)) + hdr
                   + struct.pack("<I", 1))
        with pytest.raises(MXNetError, match="blob length field"):
            recv_msg(_FakeSock(_frame(payload)))

    def test_blob_data_overrun(self):
        from mxnet_tpu.kvstore_server import recv_msg
        hdr = json.dumps(["ping"]).encode()
        payload = (struct.pack("<I", len(hdr)) + hdr
                   + struct.pack("<I", 1) + struct.pack("<Q", 50) + b"xy")
        before = self._errors()
        with pytest.raises(MXNetError, match="blob of 50 bytes overruns"):
            recv_msg(_FakeSock(_frame(payload)))
        assert self._errors() == before + 1

    def test_trailing_garbage(self):
        from mxnet_tpu.kvstore_server import recv_msg
        hdr = json.dumps(["ping"]).encode()
        payload = (struct.pack("<I", len(hdr)) + hdr
                   + struct.pack("<I", 0) + b"zz")
        with pytest.raises(MXNetError, match="trailing bytes"):
            recv_msg(_FakeSock(_frame(payload)))


# ---------------------------------------------------------------------------
# ImageRecordIter workspace lifecycle (close/reset regression)
# ---------------------------------------------------------------------------
class TestWorkspaceLifecycle:
    def _make_iter(self, tmp_path):
        cv2 = pytest.importorskip("cv2")
        root = tmp_path / "imgs"
        root.mkdir()
        for i in range(4):
            cv2.imwrite(str(root / ("%d.jpg" % i)),
                        np.full((20, 20, 3), i * 40, np.uint8))
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import im2rec
        finally:
            sys.path.pop(0)
        prefix = str(tmp_path / "ws")
        im2rec.make_list(prefix, str(root), shuffle=False)
        im2rec.pack(prefix, str(root))
        return mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                                     data_shape=(3, 16, 16), batch_size=2)

    def test_close_releases_and_reset_reacquires(self, tmp_path):
        it = self._make_iter(tmp_path)
        assert it.next().data[0].shape == (2, 3, 16, 16)
        it.close()
        # post-close use without reset() is an error, not a silent
        # lazy re-acquisition
        with pytest.raises(MXNetError, match="after close"):
            it._workspace
        # reset() is the sanctioned way back: workspace + producer return
        it.reset()
        n = sum(1 for _ in it)
        assert n == 2
        it.close()

    def test_double_close_is_idempotent(self, tmp_path):
        it = self._make_iter(tmp_path)
        it.close()
        it.close()


# ---------------------------------------------------------------------------
# end-to-end acceptance: 2-worker dist kvstore + NDArrayIter epoch
# ---------------------------------------------------------------------------
class TestEndToEnd:
    def test_snapshot_nonzero_and_prometheus_parses(self, monkeypatch):
        from mxnet_tpu.kvstore_server import KVStoreServer
        from mxnet_tpu import engine
        telemetry.enable()

        srv = KVStoreServer(num_workers=2).start()
        monkeypatch.setenv("MXNET_PS_URI", "127.0.0.1")
        monkeypatch.setenv("MXNET_PS_PORT", str(srv.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "2")
        # the workers below write their rank into the process's environment:
        # recorded here so that it is put back, and no later test file on
        # this xdist worker (test_fleet, test_runlog) reads rank 1
        monkeypatch.setenv("DMLC_WORKER_ID", "0")
        try:
            errs = []

            def worker(rank):
                try:
                    os.environ["DMLC_WORKER_ID"] = str(rank)
                    kv = mx.kv.create("dist_async")
                    kv.init("w", nd.ones((4, 2)))
                    kv.push("w", nd.ones((4, 2)) * (rank + 1))
                    out = nd.zeros((4, 2))
                    kv.pull("w", out=out)
                    kv.close()
                except Exception as e:  # noqa: BLE001 - reraised below
                    errs.append(e)

            # worker 0 inits first so rank 1 never races an uninit'd key
            worker(0)
            t = threading.Thread(target=worker, args=(1,))
            t.start()
            t.join(timeout=60)
            assert not t.is_alive() and not errs, errs
        finally:
            srv.shutdown()

        # one NDArrayIter epoch
        it = mx.io.NDArrayIter(np.ones((12, 3), np.float32),
                               np.zeros(12, np.float32), batch_size=4)
        nbatches = sum(1 for _ in it)
        assert nbatches == 3

        # explicit engine workload (the engine is driven explicitly, not
        # by imperative ops)
        eng = engine.ThreadedEngine(num_workers=2)
        try:
            for _ in range(8):
                eng.push(lambda: None, [], [])
            eng.wait_for_all()
        finally:
            eng.stop()

        snap = telemetry.snapshot()

        def total(name):
            fam = snap.get(name, {"samples": []})
            return sum(s.get("value", s.get("count", 0))
                       for s in fam["samples"])

        # acceptance: non-zero engine, kvstore and io series
        assert total("engine_ops_pushed_total") > 0
        assert total("engine_ops_completed_total") > 0
        assert total("kvstore_push_total") >= 2
        assert total("kvstore_pull_total") >= 2
        assert total("kvstore_push_latency_seconds") >= 2
        assert total("kvstore_bytes_sent_total") > 0
        assert total("kvstore_server_requests_total") > 0
        assert total("io_batches_total") == nbatches

        # acceptance: the exposition output parses line-by-line
        _assert_valid_prometheus(telemetry.prometheus_text())


# ---------------------------------------------------------------------------
# quantile overflow + the shared windowed-rate definition (PR 11)
# ---------------------------------------------------------------------------
class TestQuantileOverflow:
    def test_overflow_bucket_returns_inf(self):
        reg = MetricRegistry()
        h = reg.histogram("ovf_seconds", "", buckets=(0.1, 1.0))
        h.observe(50.0)                       # beyond the top finite bound
        assert h.quantile(0.5) == float("inf")
        assert h.quantile(0.99) == float("inf")

    def test_tail_in_overflow_head_still_finite(self):
        reg = MetricRegistry()
        h = reg.histogram("tail_seconds", "", buckets=(0.1, 1.0))
        for _ in range(99):
            h.observe(0.05)
        h.observe(50.0)
        assert h.quantile(0.5) <= 0.1         # median still on scale
        assert h.quantile(0.999) == float("inf")

    def test_empty_returns_zero_not_inf(self):
        reg = MetricRegistry()
        h = reg.histogram("empty_seconds", "", buckets=(0.1, 1.0))
        assert h.quantile(0.5) == 0.0


class TestWindowedRate:
    def test_first_observation_has_no_window(self):
        r = telemetry.WindowedRate()
        assert r.observe(10.0, now=100.0) is None

    def test_steady_rate(self):
        r = telemetry.WindowedRate()
        r.observe(0.0, now=100.0)
        assert r.observe(50.0, now=110.0) == pytest.approx(5.0)
        assert r.observe(50.0, now=111.0) == pytest.approx(0.0)

    def test_counter_reset_reports_zero_not_negative(self):
        r = telemetry.WindowedRate()
        r.observe(1000.0, now=100.0)
        assert r.observe(3.0, now=101.0) == 0.0       # reset, not -997/s
        # and the window restarts from the post-reset value
        assert r.observe(13.0, now=102.0) == pytest.approx(10.0)

    def test_zero_length_window_returns_none(self):
        r = telemetry.WindowedRate()
        r.observe(1.0, now=100.0)
        assert r.observe(2.0, now=100.0) is None
        assert r.observe(2.0, now=99.0) is None       # clock went backwards
