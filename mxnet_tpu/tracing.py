"""Causal tracing: trace contexts, flow events, and the flight recorder.

The telemetry registry answers "what is the process doing right now" with
aggregates; profiler.py answers "where did this window of time go" with
isolated spans.  Neither shows *causality* — which push produced which
execution on which worker thread, which Var dependency serialized two
ops, which worker's KVStore push a server handler span belongs to.  This
module is that layer, in the Dapper mold, unified with the profiler's
Chrome-trace event stream:

- **Trace contexts.**  A span carries ``(trace_id, span_id)``; a
  thread-local stack links nested spans parent→child, and the engine and
  KVStore carry contexts across threads and processes explicitly.  Ids
  embed the pid (``"<pid-hex>.<seq-hex>"``) so they stay unique after a
  multi-process merge with no remapping.
- **Flow events.**  Engine pushes emit Chrome-trace flow events
  (``ph: s/t/f`` sharing an ``id``) linking the pushing thread's
  ``Engine::Push`` span to the worker's execution span and its
  completion; op spans are annotated with the Var names they waited on,
  so the dependency graph is visible in Perfetto.
- **Wire propagation.**  ``kvstore_server.send_msg(..., trace_ctx=...)``
  carries a compact ``{"t": trace_id, "s": span_id}`` context in the
  frame header; server handler spans adopt it, and
  ``tools/merge_traces.py`` merges per-process trace files into one
  clock-aligned trace keyed by rank.
- **Flight recorder.**  A fixed-size ring of the last N span records that
  stays warm even with the profiler stopped, dumped to JSON on
  ``MXNetError``, an engine worker crash, or ``SIGUSR2`` — post-mortem
  context for dist flakes.

Cost model (same discipline as telemetry): every built-in site is gated
by a single attribute check (``tracing.enabled`` / ``flight.enabled``) on
the disabled path.  Tracing is off by default (``MXNET_TRACING=1`` turns
it on; events are collected while the profiler runs).  The flight
recorder defaults ON because its steady-state cost is one ring append per
*recorded* span: the executor's dispatch spans and the step timeline
(``Step::*``, about a dozen a training step) always record; per-op and
engine spans only while the profiler or tracing is active.
``flight.records()`` reads the ring in-process, on ``perf_counter``'s clock.

Env knobs (see docs/observability.md "Tracing"): ``MXNET_TRACING``,
``MXNET_TRACE_DIR``, ``MXNET_FLIGHT_RECORDER``,
``MXNET_FLIGHT_RECORDER_SIZE``, ``MXNET_FLIGHT_RECORDER_PATH``,
``MXNET_FLIGHT_RECORDER_DEBOUNCE_SEC``, ``MXNET_PROFILER_MAX_EVENTS``.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import signal
import tempfile
import threading
import time
from typing import NamedTuple, Optional

from . import base as _base
from . import profiler as _profiler
from . import telemetry as _telemetry
from .base import get_env

__all__ = ["enabled", "enable", "disable", "span", "server_span",
           "current", "engine_push", "flight", "FlightRecorder",
           "FlightRecord", "dump_process_trace"]

#: single-attribute gate read by every built-in instrumentation site
enabled = False


def enable():
    global enabled
    enabled = True


def disable():
    global enabled
    enabled = False


_FLIGHT_DUMPS = _telemetry.counter(
    "flight_recorder_dumps_total",
    "Flight-recorder ring dumps, by trigger", ("reason",))


# ---------------------------------------------------------------------------
# ids and thread-local context
# ---------------------------------------------------------------------------
_id_lock = threading.Lock()
_id_n = 0


def _new_id() -> str:
    """Process-unique id: ``"<pid-hex>.<seq-hex>"``.

    Baking in the pid keeps flow/span ids collision-free across the
    processes of a dist run, so merge_traces.py never has to remap ids —
    a worker's flow-start and the server's flow-end keep matching."""
    global _id_n
    with _id_lock:
        _id_n += 1
        n = _id_n
    return "%x.%x" % (os.getpid() & 0xFFFFFFFF, n)


class SpanCtx(NamedTuple):
    trace_id: str
    span_id: str


_tls = threading.local()


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current() -> Optional[SpanCtx]:
    """The innermost active span context on this thread, or None."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


def _tid():
    return threading.get_ident() % 100000


def _emit_flow(ph, flow_id, name, cat, ts=None, bind_enclosing=False):
    """Append one Chrome flow event (``s``/``t``/``f``).

    Flow events bind by (cat, name, id), so all events of one flow use
    identical name/cat.  ``bind_enclosing`` sets ``"bp": "e"`` — the
    flow-end attaches to the slice enclosing its timestamp."""
    if not _profiler.is_running():
        return
    ev = {"name": name, "cat": cat, "ph": ph, "id": flow_id,
          "ts": _profiler._now_us() if ts is None else ts,
          "pid": os.getpid(), "tid": _tid()}
    if bind_enclosing:
        ev["bp"] = "e"
    _profiler._append_event(ev)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
class _TraceSpan:
    """A traced span: records an X event with trace/span/parent ids in
    ``args`` and maintains the thread-local context stack.

    ``parent`` may be another span/SpanCtx, a wire context dict
    (``{"t": trace_id, "s": span_id}``), or None (inherit from the
    thread's current context, else start a new trace)."""

    __slots__ = ("name", "cat", "extra", "trace_id", "span_id",
                 "parent_id", "_begin")

    def __init__(self, name, cat="trace", parent=None, args=None):
        self.name = name
        self.cat = cat
        self.extra = args
        if parent is None:
            parent = current()
        if isinstance(parent, dict):          # wire trace context
            self.trace_id = parent.get("t") or _new_id()
            self.parent_id = parent.get("s")
        elif parent is not None:              # SpanCtx or another span
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
        else:
            self.trace_id = _new_id()
            self.parent_id = None
        self.span_id = _new_id()

    def __enter__(self):
        self._begin = _profiler._now_us()
        _stack().append(SpanCtx(self.trace_id, self.span_id))
        return self

    def __exit__(self, *exc):
        st = _stack()
        if st:
            st.pop()
        args = {"trace_id": self.trace_id, "span_id": self.span_id}
        if self.parent_id:
            args["parent_id"] = self.parent_id
        if self.extra:
            args.update(self.extra)
        _profiler.record_span(self.name, self._begin, _profiler._now_us(),
                              self.cat, args=args)
        return False

    def flow_out(self, name="kvstore_flow"):
        """Start a flow from this span; returns the wire trace context
        to embed in an outgoing message."""
        _emit_flow("s", self.span_id, name, self.cat, ts=self._begin)
        return {"t": self.trace_id, "s": self.span_id}

    def wire_ctx(self):
        return {"t": self.trace_id, "s": self.span_id}


def span(name, cat="trace", parent=None, args=None) -> _TraceSpan:
    """Context manager for a traced span (see :class:`_TraceSpan`)."""
    return _TraceSpan(name, cat, parent=parent, args=args)


class _ServerSpan(_TraceSpan):
    """Handler-side span that adopts an incoming wire trace context and
    terminates the sender's flow inside itself."""

    __slots__ = ("_in_flow",)

    def __init__(self, name, tc, cat="kvstore"):
        super().__init__(name, cat, parent=tc if tc else None)
        self._in_flow = tc.get("s") if tc else None

    def __enter__(self):
        super().__enter__()
        if self._in_flow:
            # bp=e binds the flow-end to this (enclosing) handler slice
            _emit_flow("f", self._in_flow, "kvstore_flow", self.cat,
                       bind_enclosing=True)
        return self


def server_span(name, tc, cat="kvstore") -> _ServerSpan:
    """Span adopting a wire trace context ``{"t":..., "s":...}`` (or
    None); emits the matching flow-end for the sender's flow-start."""
    return _ServerSpan(name, tc, cat=cat)


# ---------------------------------------------------------------------------
# engine causality: push → execute → complete flows
# ---------------------------------------------------------------------------
def _var_name(v):
    n = getattr(v, "name", None)
    return n if n else "var@%x" % (id(v) & 0xFFFFFF)


class _EngineFlow:
    """One engine op's causal record, created on the pushing thread and
    completed on the worker thread.  Emits:

    - ``Engine::Push`` span + flow-start (``s``) on the pushing thread,
    - flow-step (``t``) + the op's execution span (annotated with the Var
      names it waited on and its trace/span/parent ids) on the worker,
    - ``Engine::OnComplete`` span + flow-end (``f``) at completion.
    """

    __slots__ = ("name", "trace_id", "parent_id", "flow_id", "span_id",
                 "const_names", "mutable_names", "_t_push", "_t_exec")

    def pushed(self):
        """Record the push span + flow-start (pushing thread)."""
        end = _profiler._now_us()
        _emit_flow("s", self.flow_id, "engine_flow", "engine",
                   ts=self._t_push)
        _profiler.record_span(
            "Engine::Push", self._t_push, end, "engine",
            args={"op": self.name, "trace_id": self.trace_id,
                  "flow_id": self.flow_id})

    def exec_begin(self):
        """Worker thread enters the op: flow-step + context push."""
        self.span_id = _new_id()
        self._t_exec = _profiler._now_us()
        _emit_flow("t", self.flow_id, "engine_flow", "engine",
                   ts=self._t_exec)
        _stack().append(SpanCtx(self.trace_id, self.span_id))

    def exec_end(self, error=None):
        """Worker thread leaves the op: record the execution span."""
        st = _stack()
        if st:
            st.pop()
        end = _profiler._now_us()
        args = {"trace_id": self.trace_id, "span_id": self.span_id,
                "flow_id": self.flow_id,
                "const_vars": self.const_names,
                "mutable_vars": self.mutable_names}
        if self.parent_id:
            args["parent_id"] = self.parent_id
        if error is not None:
            args["error"] = "%s: %s" % (type(error).__name__, error)
        _profiler.record_span(self.name, self._t_exec, end, "engine_op",
                              args=args)

    def completed(self):
        """Dependency release: tiny span + flow-end bound to it."""
        b = _profiler._now_us()
        _emit_flow("f", self.flow_id, "engine_flow", "engine", ts=b,
                   bind_enclosing=True)
        _profiler.record_span("Engine::OnComplete", b, _profiler._now_us(),
                              "engine",
                              args={"op": self.name, "flow_id": self.flow_id})


def engine_push(name, const_vars=(), mutable_vars=()) -> _EngineFlow:
    """Begin a push→execute→complete flow (call on the pushing thread).

    Inherits the pushing thread's current span context, so ops pushed
    from inside a traced span (or from inside another engine op's fn)
    join that trace with a parent link."""
    cur = current()
    fl = _EngineFlow()
    fl.name = name or "engine_op"
    fl.trace_id = cur.trace_id if cur is not None else _new_id()
    fl.parent_id = cur.span_id if cur is not None else None
    fl.flow_id = _new_id()
    fl.span_id = None
    fl.const_names = [_var_name(v) for v in const_vars]
    fl.mutable_names = [_var_name(v) for v in mutable_vars]
    fl._t_push = _profiler._now_us()
    fl._t_exec = 0.0
    return fl


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class FlightRecord(NamedTuple):
    """One record of the ring, as :meth:`FlightRecorder.records` hands it
    out: begin and end in seconds on ``time.perf_counter()``'s clock."""
    name: str
    cat: str
    begin_s: float
    end_s: float
    tid: int
    args: Optional[dict]


class FlightRecorder:
    """Fixed-size ring of the last N span records, always warm.

    ``profiler.record_span`` feeds it regardless of profiler state (one
    deque append per recorded span; ``maxlen`` handles eviction in C).
    Dumped to JSON on MXNetError construction (debounced — the test
    suite raises MXNetError intentionally all over), on an engine worker
    crash or SIGUSR2 (both forced), or manually via :meth:`dump`."""

    def __init__(self):
        self.enabled = get_env("MXNET_FLIGHT_RECORDER", True, bool)
        size = max(16, get_env("MXNET_FLIGHT_RECORDER_SIZE", 1024, int))
        self._ring = collections.deque(maxlen=size)
        self._seq = itertools.count()   # a record's number since clear()
        self._dump_lock = threading.Lock()
        self._last_error_dump = 0.0
        self.error_debounce = get_env(
            "MXNET_FLIGHT_RECORDER_DEBOUNCE_SEC", 1.0, float)

    # -- recording ---------------------------------------------------------
    def record(self, name, category, begin_us, end_us, args=None):
        self._ring.append((begin_us, end_us - begin_us, name, category,
                           _tid(), args, next(self._seq)))

    def clear(self):
        self._ring.clear()
        self._seq = itertools.count()
        self._last_error_dump = 0.0

    # -- reading -----------------------------------------------------------
    def records(self, names=None, since_s=None):
        """``(records, wrapped)``: the ring's records, oldest first, as
        :class:`FlightRecord` — those named in ``names`` (all when None)
        that begin at or after ``since_s`` (``time.perf_counter()`` seconds;
        all when None).  ``wrapped`` says that the answer may be partial:
        the ring has dropped records, and the oldest it still holds ended
        at or after ``since_s`` (records are appended as they end, so what
        was dropped may have begun inside the stretch asked for)."""
        ring = list(self._ring)
        t0 = _profiler._t0
        wrapped = bool(ring) and ring[0][6] > 0 and (
            since_s is None
            or t0 + (ring[0][0] + ring[0][1]) * 1e-6 >= since_s)
        if names is not None:
            names = frozenset(names)
        out = []
        for ts, dur, name, cat, tid, args, _ in ring:
            begin = t0 + ts * 1e-6
            if (names is None or name in names) and \
                    (since_s is None or begin >= since_s):
                out.append(FlightRecord(name, cat, begin,
                                        begin + dur * 1e-6, tid, args))
        return out, wrapped

    def __len__(self):
        return len(self._ring)

    # -- dumping -----------------------------------------------------------
    def path(self):
        """Dump path, resolved at dump time so tests can redirect it."""
        return (os.environ.get("MXNET_FLIGHT_RECORDER_PATH")
                or os.path.join(tempfile.gettempdir(),
                                "mxnet_flight_recorder_%d.json" % os.getpid()))

    def dump(self, reason="manual"):
        """Write the ring to JSON atomically; returns the path (or None —
        a post-mortem dump must never raise into the failing path)."""
        _FLIGHT_DUMPS.labels(reason=reason).inc()
        with self._dump_lock:
            try:
                t0 = _profiler._t0
                events = [{"ts_us": (r.begin_s - t0) * 1e6,
                           "dur_us": (r.end_s - r.begin_s) * 1e6,
                           "name": r.name, "cat": r.cat, "tid": r.tid,
                           "args": r.args}
                          for r in self.records()[0]]
                doc = {"reason": reason,
                       "unix_time": time.time(),
                       "pid": os.getpid(),
                       "rank": os.environ.get("DMLC_WORKER_ID", "0"),
                       "role": os.environ.get("DMLC_ROLE", "worker"),
                       "t0_unix_us": time.time() * 1e6 - _profiler._now_us(),
                       "events": events}
                # post-mortem program context: which cached XLA programs
                # were live (cost + the env flags that built them), plus
                # the atlas per-scope tables when available.  The programs
                # block does not depend on atlas being enabled.
                try:
                    from . import health as _health
                    progs = {n: pc.as_dict()
                             for n, pc in _health.programs().items()}
                    if progs:
                        doc["programs"] = progs
                except Exception:
                    pass
                try:
                    from . import atlas as _atlas
                    at = _atlas.snapshot(top_k=10)
                    if at:
                        doc["atlas"] = at
                except Exception:
                    pass
                # trailing metric history: the minutes *leading up to*
                # the trip, not just the spans after it (empty until the
                # time-series sampler has run at least once).
                try:
                    from .telemetry import timeseries as _ts
                    win = get_env("MXNET_FLIGHT_TS_WINDOW", 120.0, float)
                    tsdoc = _ts.trailing(window_seconds=win)
                    if tsdoc.get("series"):
                        doc["timeseries"] = tsdoc
                except Exception:
                    pass
                # fleet context: when this process runs the fleet
                # collector, its dump carries the merged target table,
                # derived aggregates and alert state (per-rank evidence
                # lives in the offending rank's own dump).
                try:
                    from .telemetry import fleet as _fleet
                    if _fleet.running():
                        blk = _fleet.flight_block()
                        if blk:
                            doc["fleet"] = blk
                except Exception:
                    pass
                # memory forensics: the owner-tagged ledger, the leak
                # suspects table and the last registered program's
                # footprint (the oom_risk / reason=oom evidence).
                try:
                    from . import memwatch as _memwatch
                    if _memwatch.enabled:
                        doc["memwatch"] = _memwatch.forensics()
                except Exception:
                    pass
                path = self.path()
                tmp = "%s.tmp.%d" % (path, os.getpid())
                with open(tmp, "w") as f:
                    json.dump(doc, f, default=str)
                os.replace(tmp, path)
                return path
            except Exception:
                return None

    # -- triggers ----------------------------------------------------------
    def on_engine_crash(self, name, exc, wait_on=None):
        """Forced dump when an engine op's fn raised (the crash origin,
        not downstream ops poisoned by dependency propagation)."""
        if not self.enabled:
            return
        args = {"error": "%s: %s" % (type(exc).__name__, exc)}
        if wait_on:
            args["wait_on"] = list(wait_on)
        now = _profiler._now_us()
        self.record("CRASH " + (name or "engine_op"), "crash", now, now, args)
        self.dump("engine_crash")

    def _on_mxnet_error(self, exc):
        """base.MXNetError construction hook (debounced)."""
        if not self.enabled:
            return
        now = time.monotonic()
        if now - self._last_error_dump < self.error_debounce:
            return
        self._last_error_dump = now
        now = _profiler._now_us()
        self.record("MXNetError", "error", now, now, {"error": str(exc)})
        self.dump("mxnet_error")


flight = FlightRecorder()


def _install_sigusr2():
    """kill -USR2 <pid> dumps the ring of a live process (main thread
    only — signal.signal raises elsewhere, e.g. under some test runners)."""
    if not hasattr(signal, "SIGUSR2"):
        return
    try:
        if threading.current_thread() is not threading.main_thread():
            return
        prev = signal.getsignal(signal.SIGUSR2)

        def _handler(signum, frame):
            flight.dump("sigusr2")
            if callable(prev) and prev not in (signal.SIG_IGN, signal.SIG_DFL):
                prev(signum, frame)

        signal.signal(signal.SIGUSR2, _handler)
    except (ValueError, OSError):
        pass


# ---------------------------------------------------------------------------
# per-process trace files for dist runs
# ---------------------------------------------------------------------------
def dump_process_trace(role=None, directory=None):
    """Dump this process's profiler events to ``$MXNET_TRACE_DIR`` under a
    rank/role-keyed name (``trace_server.json`` / ``trace_worker<r>.json``)
    for ``tools/merge_traces.py``.  No-op when no directory is configured."""
    directory = directory or os.environ.get("MXNET_TRACE_DIR")
    if not directory:
        return None
    role = role or os.environ.get("DMLC_ROLE") or "worker"
    if role == "server":
        fname = "trace_server.json"
    else:
        fname = "trace_%s%s.json" % (
            role, os.environ.get("DMLC_WORKER_ID", "0") or "0")
    os.makedirs(directory, exist_ok=True)
    return _profiler.dump(filename=os.path.join(directory, fname))


# ---------------------------------------------------------------------------
# wiring
# ---------------------------------------------------------------------------
_profiler._flight = flight
_base._ERROR_HOOK = flight._on_mxnet_error
_install_sigusr2()

if get_env("MXNET_TRACING", False, bool):
    enable()
