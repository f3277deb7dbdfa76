"""Causal tracing layer: engine flow events, cross-process KVStore trace
propagation + merge_traces round-trip, jit-cache observability, and the
flight recorder (see docs/observability.md "Tracing")."""
import glob
import io
import json
import os
import signal
import struct
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import mxnet_tpu as mx
from mxnet_tpu import nd, profiler, telemetry, tracing
from mxnet_tpu import engine as engine_mod
from mxnet_tpu import kvstore_server as kvs
from mxnet_tpu.base import MXNetError
from mxnet_tpu.kvstore_server import KVStoreServer
from mxnet_tpu.ops import registry as op_registry
import mxnet_tpu as _mx
from mxnet_tpu import symbol as sym

import merge_traces


@pytest.fixture(autouse=True)
def _clean_tracing():
    telemetry.reset()
    tracing.disable()
    profiler.set_state("stop")
    with profiler._lock:
        profiler._events.clear()
    tracing.flight.clear()
    yield
    tracing.disable()
    telemetry.disable()
    profiler.set_state("stop")
    with profiler._lock:
        profiler._events.clear()
    tracing.flight.clear()
    telemetry.reset()


def _events():
    with profiler._lock:
        return list(profiler._events)


def _assert_flows_well_formed(events):
    """Every flow step/end has a matching start; start ids are unique."""
    starts = [e["id"] for e in events if e["ph"] == "s"]
    assert len(starts) == len(set(starts)), "duplicate flow-start ids"
    sset = set(starts)
    for e in events:
        if e["ph"] in ("t", "f"):
            assert e["id"] in sset, "dangling flow %s id %r" % (e["ph"],
                                                                e["id"])


# ---------------------------------------------------------------------------
# engine causality
# ---------------------------------------------------------------------------
class TestEngineFlows:
    def test_threaded_engine_flow_events(self):
        tracing.enable()
        profiler.set_state("run")
        eng = engine_mod.ThreadedEngine(2)
        a, b = eng.new_variable("a"), eng.new_variable("b")
        eng.push(lambda: None, mutable_vars=(a,), name="write_a")
        eng.push(lambda: None, const_vars=(a,), mutable_vars=(b,),
                 name="read_a_write_b")
        eng.wait_for_all()
        profiler.set_state("stop")
        ev = _events()
        _assert_flows_well_formed(ev)
        # one full s/t/f triple per push
        for ph in "stf":
            assert len([e for e in ev if e["ph"] == ph]) >= 2
        # the op span carries the Var names it waited on
        op = [e for e in ev if e["name"] == "read_a_write_b"][0]
        assert op["cat"] == "engine_op"
        assert op["args"]["const_vars"] == ["a"]
        assert op["args"]["mutable_vars"] == ["b"]
        # s, t and f of one flow share an id spanning push/exec/complete
        push = [e for e in ev if e["ph"] == "s"
                and e["id"] == op["args"]["flow_id"]]
        fin = [e for e in ev if e["ph"] == "f"
               and e["id"] == op["args"]["flow_id"]]
        assert push and fin
        eng.stop()

    def test_nested_push_joins_parent_trace(self):
        tracing.enable()
        profiler.set_state("run")
        eng = engine_mod.ThreadedEngine(2)
        v = eng.new_variable("outer_v")

        def outer():
            # pushed from the worker thread inside the outer op's span:
            # must inherit its trace
            eng.push(lambda: None, name="inner_op")

        eng.push(outer, mutable_vars=(v,), name="outer_op")
        eng.wait_for_all()
        profiler.set_state("stop")
        ev = _events()
        outer_span = [e for e in ev if e["name"] == "outer_op"][0]
        inner_span = [e for e in ev if e["name"] == "inner_op"][0]
        assert (inner_span["args"]["trace_id"]
                == outer_span["args"]["trace_id"])
        assert (inner_span["args"]["parent_id"]
                == outer_span["args"]["span_id"])
        eng.stop()

    def test_naive_engine_spans(self):
        tracing.enable()
        profiler.set_state("run")
        eng = engine_mod.NaiveEngine()
        v = eng.new_variable("nv")
        eng.push(lambda: None, mutable_vars=(v,), name="naive_op")
        profiler.set_state("stop")
        ev = _events()
        _assert_flows_well_formed(ev)
        op = [e for e in ev if e["name"] == "naive_op"][0]
        assert op["args"]["mutable_vars"] == ["nv"]

    def test_native_engine_flow_events(self):
        try:
            eng = engine_mod.NativeThreadedEngine(2)
        except RuntimeError:
            pytest.skip("native engine unavailable")
        tracing.enable()
        profiler.set_state("run")
        v = eng.new_variable("natv")
        eng.push_sync(lambda: None, mutable_vars=(v,), name="native_op")
        profiler.set_state("stop")
        ev = _events()
        _assert_flows_well_formed(ev)
        op = [e for e in ev if e["name"] == "native_op"][0]
        assert op["args"]["mutable_vars"] == ["natv"]
        assert [e for e in ev if e["ph"] == "f"
                and e["id"] == op["args"]["flow_id"]]
        eng.stop()

    def test_disabled_tracing_adds_no_events(self):
        profiler.set_state("run")
        eng = engine_mod.ThreadedEngine(2)
        v = eng.new_variable("q")
        eng.push(lambda: None, mutable_vars=(v,), name="quiet")
        eng.wait_for_all()
        profiler.set_state("stop")
        assert not [e for e in _events() if e["ph"] in "stf"]
        eng.stop()


# ---------------------------------------------------------------------------
# cross-process propagation: wire format
# ---------------------------------------------------------------------------
class _FakeSock:
    def __init__(self, data=b""):
        self._rx = io.BytesIO(data)
        self.sent = bytearray()

    def sendall(self, b):
        self.sent.extend(b)

    def recv(self, n):
        return self._rx.read(n)


def _frame_with_header(hdr_obj):
    header = json.dumps(hdr_obj).encode()
    payload = struct.pack("<I", len(header)) + header + struct.pack("<I", 0)
    return struct.pack("<Q", len(payload)) + payload


class TestWireTraceContext:
    def test_trace_ctx_roundtrip(self):
        s = _FakeSock()
        kvs.send_msg(s, ("push", "k", np.arange(3.0)),
                     trace_ctx={"t": "a.1", "s": "a.2"})
        msg, tc = kvs.recv_msg_tc(_FakeSock(bytes(s.sent)))
        assert msg[0] == "push" and msg[1] == "k"
        np.testing.assert_array_equal(msg[2], np.arange(3.0))
        assert tc == {"t": "a.1", "s": "a.2"}

    def test_old_format_frames_still_parse(self):
        # untraced send produces the original wire format: header is the
        # bare message list, not the {"m":..., "tc":...} wrapper
        s = _FakeSock()
        kvs.send_msg(s, ("pull", "k"))
        hlen = struct.unpack_from("<I", s.sent, 8)[0]
        assert isinstance(json.loads(bytes(s.sent[12:12 + hlen])), list)
        msg, tc = kvs.recv_msg_tc(_FakeSock(bytes(s.sent)))
        assert msg == ["pull", "k"] and tc is None
        # and the tc-dropping legacy API still works
        assert kvs.recv_msg(_FakeSock(bytes(s.sent))) == ["pull", "k"]

    @pytest.mark.parametrize("hdr", [
        {"m": ["pull", "k"], "tc": {"t": "x", "s": "y", "evil": "z"}},
        {"m": ["pull", "k"], "tc": {"t": "x" * 65, "s": "y"}},
        {"m": ["pull", "k"], "tc": {"t": ""}},
        {"m": ["pull", "k"], "tc": {"t": 5}},
        {"m": ["pull", "k"], "tc": ["not-a-dict"]},
        {"tc": {"t": "x"}},
        {"m": ["pull", "k"], "unknown_key": 1},
    ])
    def test_malformed_trace_ctx_rejected(self, hdr):
        before = telemetry.value("kvstore_frame_errors_total")
        with pytest.raises(MXNetError):
            kvs.recv_msg_tc(_FakeSock(_frame_with_header(hdr)))
        assert telemetry.value("kvstore_frame_errors_total") == before + 1

    def test_in_process_kv_propagation(self, monkeypatch):
        tracing.enable()
        profiler.set_state("run")
        srv = KVStoreServer(num_workers=1).start()
        monkeypatch.setenv("MXNET_PS_URI", "127.0.0.1")
        monkeypatch.setenv("MXNET_PS_PORT", str(srv.port))
        monkeypatch.setenv("DMLC_NUM_WORKER", "1")
        monkeypatch.setenv("DMLC_WORKER_ID", "0")
        try:
            kv = mx.kv.create("dist_async")
            kv.init("w", nd.array(np.ones(4, np.float32)))
            kv.push("w", nd.array(np.full(4, 2.0, np.float32)))
            out = nd.zeros(4)
            kv.pull("w", out=out)
            kv.close()
        finally:
            srv.shutdown()
        profiler.set_state("stop")
        ev = _events()
        _assert_flows_well_formed(ev)
        client = [e for e in ev if e["name"] == "KVStore::push"][0]
        server = [e for e in ev if e["name"] == "Server::push"][0]
        # handler adopted the worker's context: same trace, parent link,
        # and its flow-end matches the client span's flow-start
        assert server["args"]["trace_id"] == client["args"]["trace_id"]
        assert server["args"]["parent_id"] == client["args"]["span_id"]
        fins = [e for e in ev if e["ph"] == "f"
                and e["id"] == client["args"]["span_id"]]
        assert fins and fins[0]["bp"] == "e"


# ---------------------------------------------------------------------------
# 2-worker dist run + merge round-trip (acceptance scenario)
# ---------------------------------------------------------------------------
class TestDistTraceMerge:
    def test_two_worker_trace_merge(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import launch

        trace_dir = str(tmp_path / "traces")
        worker = os.path.join(REPO, "tests", "dist_trace_worker.py")
        rc = launch.launch_local(
            2, [sys.executable, worker],
            env_extra={"JAX_PLATFORMS": "cpu", "MXNET_TEST_PLATFORM": "cpu",
                       "MXNET_TRACING": "1", "MXNET_TRACE_DIR": trace_dir},
            num_servers=1)
        assert rc == 0
        files = [os.path.join(trace_dir, f)
                 for f in ("trace_worker0.json", "trace_worker1.json",
                           "trace_server.json")]
        # the server dumps between serve_forever returning and launcher
        # cleanup; give the race a moment
        deadline = time.time() + 10
        while (not all(os.path.exists(f) for f in files)
               and time.time() < deadline):
            time.sleep(0.1)
        assert all(os.path.exists(f) for f in files), os.listdir(trace_dir)

        merged_path = str(tmp_path / "merged.json")
        assert merge_traces.main(["-o", merged_path] + files) == 0
        assert merge_traces.main(["--validate", merged_path]) == 0
        merged = merge_traces.load_trace(merged_path)
        ev = merged["traceEvents"]

        # per-process rows keyed by rank/role
        names = {e["args"]["name"] for e in ev
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert names == {"server", "worker 0", "worker 1"}

        # at least one worker push span flow-linked to a server handler
        # span: the client flow-start id reappears as a server-side
        # flow-end on the server's pid
        server_pid = [e["pid"] for e in ev if e["ph"] == "M"
                      and e["name"] == "process_name"
                      and e["args"]["name"] == "server"][0]
        push_spans = [e for e in ev if e["ph"] == "X"
                      and e["name"] == "KVStore::push"
                      and e["pid"] != server_pid]
        assert push_spans
        server_fins = {e["id"] for e in ev if e["ph"] == "f"
                       and e["pid"] == server_pid}
        linked = [e for e in push_spans
                  if e["args"]["span_id"] in server_fins]
        assert linked, "no worker push span flow-linked to a server span"
        handler_spans = [e for e in ev if e["ph"] == "X"
                         and e["name"] == "Server::push"
                         and e["pid"] == server_pid]
        assert handler_spans

    def test_merge_clock_alignment(self, tmp_path):
        def trace(t0, role, rank, ts):
            return {"traceEvents": [{"name": "x", "cat": "c", "ph": "X",
                                     "ts": ts, "dur": 1.0, "pid": 7,
                                     "tid": 1}],
                    "metadata": {"t0_unix_us": t0, "pid": 7,
                                 "rank": rank, "role": role}}

        # worker started 1000us after the server: its events shift +1000
        merged = merge_traces.merge([trace(5000.0, "server", 0, 10.0),
                                     trace(6000.0, "worker", 0, 10.0)])
        xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
        by_pid = {e["pid"]: e["ts"] for e in xs}
        assert by_pid[1] == 10.0        # server is the earliest origin
        assert by_pid[100] == 1010.0    # worker shifted by the t0 delta

    def test_validate_catches_bad_flows(self, tmp_path):
        good = {"traceEvents": [
            {"name": "a", "cat": "c", "ph": "s", "id": "1", "ts": 1.0,
             "pid": 1, "tid": 1},
            {"name": "a", "cat": "c", "ph": "f", "id": "1", "ts": 2.0,
             "pid": 1, "tid": 1}]}
        assert merge_traces.validate_trace(good) == []
        bad = {"traceEvents": [
            {"name": "a", "cat": "c", "ph": "f", "id": "orphan", "ts": 1.0,
             "pid": 1, "tid": 1},
            {"name": "b", "cat": "c", "ph": "X", "ts": 1.0, "pid": 1,
             "tid": 1}]}  # X missing dur + orphan flow-end
        errs = merge_traces.validate_trace(bad)
        assert any("no matching start" in e for e in errs)
        assert any("dur" in e for e in errs)

        bad_path = str(tmp_path / "bad.json")
        with open(bad_path, "w") as f:
            json.dump(bad, f)
        assert merge_traces.main(["--validate", bad_path]) == 1


class TestValidateFlightDump:
    """--validate also schema-checks flight-recorder dumps (PR 11)."""

    def _dump(self):
        return {"reason": "test", "role": "local", "rank": "0",
                "unix_time": 1000.0, "pid": 1, "t0_unix_us": 0.0,
                "events": [{"name": "op", "ts_us": 1.0, "dur_us": 2.0,
                            "cat": "engine", "tid": 7, "args": None}],
                "programs": {"step": {"flops": 1e9, "arg_bytes": 8.0,
                                      "out_bytes": 8.0, "env": None}},
                "atlas": {"step": {"coverage_pct": 97.0,
                                   "scopes": [{"scope": "dense",
                                               "flops": 5e8}]}},
                "timeseries": {"window_seconds": 120.0, "interval": 1.0,
                               "series": {"g:value": {
                                   "metric": "g", "stat": "value",
                                   "labels": {},
                                   "points": [[999.0, 1.0],
                                              [1000.0, None]]}}}}

    def test_dispatch_and_clean_dump(self, tmp_path):
        doc = self._dump()
        assert merge_traces.is_flight_dump(doc)
        assert not merge_traces.is_flight_dump({"traceEvents": []})
        assert merge_traces.validate_flight_dump(doc) == []
        p = str(tmp_path / "flight.json")
        with open(p, "w") as f:
            json.dump(doc, f)
        assert merge_traces.main(["--validate", p]) == 0

    def test_blocks_are_optional(self):
        doc = self._dump()
        for block in ("programs", "atlas", "timeseries"):
            del doc[block]
        assert merge_traces.validate_flight_dump(doc) == []

    def test_corrupted_blocks_reported_precisely(self, tmp_path):
        doc = self._dump()
        doc["programs"]["step"]["flops"] = "many"
        doc["atlas"]["step"]["scopes"][0]["flops"] = None
        doc["timeseries"]["series"]["g:value"]["points"][0] = [1.0]
        doc["events"][0].pop("dur_us")
        errs = merge_traces.validate_flight_dump(doc)
        assert any("programs[step]" in e and "flops" in e for e in errs)
        assert any("atlas[step].scopes[0]" in e for e in errs)
        assert any("timeseries[g:value].points[0]" in e for e in errs)
        assert any("events[0]" in e and "dur_us" in e for e in errs)
        p = str(tmp_path / "bad_flight.json")
        with open(p, "w") as f:
            json.dump(doc, f)
        assert merge_traces.main(["--validate", p]) == 1


# ---------------------------------------------------------------------------
# compile observability
# ---------------------------------------------------------------------------
class TestJitCacheObservability:
    @pytest.fixture
    def temp_op(self):
        name = "_test_tracing_identity"

        @op_registry.register(name, env_keys=("MXNET_TRACING_TEST_FLAG",))
        def _identity(attrs, x):
            return x * 1.0

        yield op_registry.get_op(name)
        op_registry.OPS.pop(name, None)

    def test_hit_miss_counters_around_env_toggle(self, temp_op, monkeypatch):
        telemetry.enable()
        name = temp_op.name
        attrs = temp_op.parse_attrs({})
        x = np.ones(3, np.float32)

        monkeypatch.delenv("MXNET_TRACING_TEST_FLAG", raising=False)
        temp_op(attrs, x)
        assert telemetry.value("op_jit_cache_misses_total", op=name) == 1
        assert telemetry.value("op_jit_cache_hits_total", op=name) == 0
        entries0 = telemetry.value("op_jit_cache_entries")
        # first invocation observed into the compile-duration histogram
        assert telemetry.value("op_compile_seconds", op=name) == 1

        temp_op(attrs, x)
        assert telemetry.value("op_jit_cache_hits_total", op=name) == 1
        assert telemetry.value("op_jit_cache_misses_total", op=name) == 1

        # env_keys toggle: new cache key -> miss + new entry
        monkeypatch.setenv("MXNET_TRACING_TEST_FLAG", "1")
        temp_op(attrs, x)
        assert telemetry.value("op_jit_cache_misses_total", op=name) == 2
        assert telemetry.value("op_jit_cache_entries") == entries0 + 1
        assert telemetry.value("op_compile_seconds", op=name) == 2

        # toggling back serves the original (still-live) entry
        monkeypatch.delenv("MXNET_TRACING_TEST_FLAG")
        temp_op(attrs, x)
        assert telemetry.value("op_jit_cache_hits_total", op=name) == 2
        assert telemetry.value("op_jit_cache_misses_total", op=name) == 2

    def test_jit_metrics_in_metrics_scrape(self, temp_op):
        telemetry.enable()
        temp_op(temp_op.parse_attrs({}), np.ones(2, np.float32))
        text = telemetry.prometheus_text()
        assert 'op_jit_cache_misses_total{op="%s"} 1' % temp_op.name in text
        assert "op_jit_cache_hits_total" in text
        assert "op_jit_cache_entries" in text
        assert 'op_compile_seconds_count{op="%s"} 1' % temp_op.name in text

    def test_compile_span_recorded(self, temp_op):
        profiler.set_state("run")
        temp_op(temp_op.parse_attrs({}), np.ones(2, np.float32))
        temp_op(temp_op.parse_attrs({}), np.ones(2, np.float32))
        profiler.set_state("stop")
        spans = [e for e in _events()
                 if e["name"] == "XLA::Compile %s" % temp_op.name]
        assert len(spans) == 1  # only the first invocation compiles
        assert spans[0]["cat"] == "compile"

    def test_executor_first_run_flag(self):
        profiler.set_state("run")
        a = sym.var("a")
        ex = sym.exp(a).bind(mx.cpu(), {"a": nd.ones((2, 2))})
        ex.forward()
        ex.forward()
        profiler.set_state("stop")
        spans = [e for e in _events()
                 if e["name"] == "Executor::ForwardDispatch"]
        assert spans[0]["args"]["first_run"] is True
        assert spans[1]["args"]["first_run"] is False


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_ring_is_always_warm(self):
        # profiler stopped, tracing disabled: spans still land in the ring
        assert not profiler.is_running()
        profiler.record_span("warm_span", 0.0, 5.0, "test")
        assert len(tracing.flight) == 1
        assert not _events()  # but not in the (stopped) profiler stream

    def test_dump_on_injected_engine_exception(self, tmp_path, monkeypatch):
        path = str(tmp_path / "flight.json")
        monkeypatch.setenv("MXNET_FLIGHT_RECORDER_PATH", path)
        profiler.record_span("pre_crash_work", 0.0, 3.0, "test")
        eng = engine_mod.ThreadedEngine(2)
        v = eng.new_variable("crash_var")

        def boom():
            raise ValueError("injected op failure")

        eng.push(boom, mutable_vars=(v,), name="crash_op")
        eng.wait_for_all()
        doc = json.load(open(path))
        assert doc["reason"] == "engine_crash"
        names = [e["name"] for e in doc["events"]]
        assert "pre_crash_work" in names  # ring context preceding the crash
        crash = [e for e in doc["events"]
                 if e["name"] == "CRASH crash_op"][0]
        assert "injected op failure" in crash["args"]["error"]
        assert crash["args"]["wait_on"] == ["crash_var"]
        with pytest.raises(ValueError):
            eng.wait_for_var(v)
        eng.stop()

    def test_dump_on_mxnet_error(self, tmp_path, monkeypatch):
        path = str(tmp_path / "err.json")
        monkeypatch.setenv("MXNET_FLIGHT_RECORDER_PATH", path)
        before = telemetry.value("flight_recorder_dumps_total",
                                 reason="mxnet_error")
        MXNetError("boom for the recorder")
        doc = json.load(open(path))
        assert doc["reason"] == "mxnet_error"
        assert any("boom for the recorder" in str(e.get("args"))
                   for e in doc["events"])
        assert telemetry.value("flight_recorder_dumps_total",
                               reason="mxnet_error") == before + 1
        # debounce: an immediate second error does not re-dump
        os.remove(path)
        MXNetError("again")
        assert not os.path.exists(path)

    def test_disabled_recorder_is_inert(self, tmp_path, monkeypatch):
        path = str(tmp_path / "no.json")
        monkeypatch.setenv("MXNET_FLIGHT_RECORDER_PATH", path)
        monkeypatch.setattr(tracing.flight, "enabled", False)
        profiler.record_span("gone", 0.0, 1.0)
        assert len(tracing.flight) == 0
        MXNetError("ignored")
        tracing.flight.on_engine_crash("op", ValueError("x"))
        assert not os.path.exists(path)

    @pytest.mark.skipif(not hasattr(signal, "SIGUSR2"),
                        reason="no SIGUSR2 on this platform")
    def test_dump_on_sigusr2(self, tmp_path, monkeypatch):
        path = str(tmp_path / "sig.json")
        monkeypatch.setenv("MXNET_FLIGHT_RECORDER_PATH", path)
        tracing._install_sigusr2()
        profiler.record_span("before_signal", 0.0, 1.0, "test")
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.time() + 5
        while not os.path.exists(path) and time.time() < deadline:
            time.sleep(0.05)
        doc = json.load(open(path))
        assert doc["reason"] == "sigusr2"
        assert any(e["name"] == "before_signal" for e in doc["events"])


# ---------------------------------------------------------------------------
# profiler satellites: event cap + atomic dump semantics
# ---------------------------------------------------------------------------
class TestProfilerSatellites:
    def test_event_cap_and_dropped_counter(self, monkeypatch):
        monkeypatch.setattr(profiler, "_max_events", 5)
        profiler.set_state("run")
        for i in range(9):
            profiler.record_span("spam_%d" % i, 0.0, 1.0)
        profiler.set_state("stop")
        assert len(_events()) == 5
        assert telemetry.value("profiler_events_dropped_total") == 4

    def test_dump_atomic_and_finished_false_keeps_events(self, tmp_path):
        profiler.set_state("run")
        profiler.record_span("keepme", 0.0, 5.0)
        profiler.set_state("stop")
        path = str(tmp_path / "prof.json")
        assert profiler.dump(finished=False, filename=path) == path
        doc = json.load(open(path))
        assert any(e["name"] == "keepme" for e in doc["traceEvents"])
        meta = doc["metadata"]
        assert meta["pid"] == os.getpid() and meta["t0_unix_us"] > 0
        # snapshot dump did not clear, and left no temp residue
        assert any(e["name"] == "keepme" for e in _events())
        assert os.listdir(str(tmp_path)) == ["prof.json"]
        profiler.dump(finished=True, filename=path)
        assert not _events()

    def test_dump_process_trace_keyed_by_role(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MXNET_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("DMLC_WORKER_ID", "3")
        profiler.set_state("run")
        profiler.record_span("w", 0.0, 1.0)
        profiler.set_state("stop")
        path = tracing.dump_process_trace(role="worker")
        assert os.path.basename(path) == "trace_worker3.json"
        assert merge_traces.validate_trace(
            merge_traces.load_trace(path)) == []


# ---------------------------------------------------------------------------
# the ring's reader, and the program's spans in jax's own trace
# ---------------------------------------------------------------------------
class TestFlightRecords:
    def test_records_on_the_host_clock(self):
        before = time.perf_counter()
        with profiler.span("Step::a", "step", args={"n": 1}):
            pass
        with profiler.span("Step::b", "step"):
            pass
        after = time.perf_counter()
        records, wrapped = tracing.flight.records()
        assert not wrapped
        assert [r.name for r in records] == ["Step::a", "Step::b"]
        a, b = records
        assert before <= a.begin_s <= a.end_s <= b.begin_s <= b.end_s <= after
        assert a.cat == "step" and a.args == {"n": 1} and b.args is None
        only_b, _ = tracing.flight.records(names=["Step::b"])
        assert [r.name for r in only_b] == ["Step::b"]
        late, _ = tracing.flight.records(since_s=b.begin_s)
        assert [r.name for r in late] == ["Step::b"]

    def test_a_wrapped_ring_is_reported(self, monkeypatch):
        monkeypatch.setenv("MXNET_FLIGHT_RECORDER_SIZE", "16")
        ring = tracing.FlightRecorder()
        t0 = profiler._t0
        for i in range(16):
            ring.record("s%d" % i, "step", i * 1e6, i * 1e6 + 5e5)
        records, wrapped = ring.records()
        assert len(records) == 16 and not wrapped     # full, nothing lost
        ring.record("s16", "step", 16e6, 16.5e6)
        records, wrapped = ring.records()
        assert [r.name for r in records][0] == "s1" and wrapped
        # the oldest record kept ended at 1.5 s: a stretch that starts
        # later is whole, one that starts earlier may have lost records
        assert ring.records(since_s=t0 + 2.0)[1] is False
        assert ring.records(since_s=t0 + 1.0)[1] is True
        assert [r.name for r in ring.records(since_s=t0 + 15.0)[0]] == \
            ["s15", "s16"]
        ring.clear()
        ring.record("again", "step", 0.0, 1.0)
        records, wrapped = ring.records()
        assert [r.name for r in records] == ["again"] and not wrapped

    def test_dump_is_built_on_records(self, tmp_path, monkeypatch):
        path = str(tmp_path / "ring.json")
        monkeypatch.setenv("MXNET_FLIGHT_RECORDER_PATH", path)
        profiler.record_span("kept", 10.0, 25.0, "step", args={"k": 1})
        tracing.flight.dump("manual")
        ev = json.load(open(path))["events"]
        assert len(ev) == 1 and ev[0]["name"] == "kept"
        assert ev[0]["cat"] == "step" and ev[0]["args"] == {"k": 1}
        assert ev[0]["ts_us"] == pytest.approx(10.0, abs=1e-3)
        assert ev[0]["dur_us"] == pytest.approx(15.0, abs=1e-3)

    def test_loop_wait_span_around_each_tail(self):
        from mxnet_tpu.train_loop import OverlappedLoop
        loop = OverlappedLoop(2)
        ran = []
        for i in range(3):
            loop.push(lambda i=i: ran.append(i))
        assert ran == [0]
        loop.drain()
        assert ran == [0, 1, 2]
        waits, _ = tracing.flight.records(names=["Loop::wait"])
        assert len(waits) == 3
        assert all(r.cat == "step" and r.args == {"depth": 2} for r in waits)


def test_step_spans_in_a_jax_trace(tmp_path):
    """While any jax trace is on, the program's step spans are events of
    its host plane, named ``mx:<span>``."""
    import jax
    data = sym.var("data")
    net = sym.SoftmaxOutput(sym.FullyConnected(data, num_hidden=4, name="fc"),
                            sym.var("softmax_label"), name="softmax")
    mod = _mx.mod.Module(net, context=[mx.cpu()])
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd")
    batch = _mx.io.DataBatch(data=[nd.ones((8, 10))], label=[nd.zeros((8,))])

    def step():
        mod.forward_backward(batch)
        mod.update()
        mod.get_outputs()[0].asnumpy()

    step()                                    # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        step()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert files
    space = jax.profiler.ProfileData.from_file(files[-1])
    names = {}
    for plane in space.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mx:"):
                    names.setdefault(ev.name, []).append(
                        (plane.name, ev.start_ns, ev.duration_ns))
    for want in ("mx:Step::stage", "mx:Step::update", "mx:Step::launch",
                 "mx:Step::writeback"):
        assert len(names.get(want, ())) == 1, (want, sorted(names))
    (plane, start, dur), = names["mx:Step::launch"]
    assert plane == "/host:CPU"
    (_, ustart, udur), = names["mx:Step::update"]
    assert ustart <= start and start + dur <= ustart + udur
    # the same span in the ring, to a tenth of a millisecond
    ring, _ = tracing.flight.records(names=["Step::launch"])
    assert abs((ring[-1].end_s - ring[-1].begin_s) * 1e9 - dur) < 1e5
