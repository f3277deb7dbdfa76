"""Profiler (parity: ``python/mxnet/profiler.py`` over SURVEY.md N16/§5.1).

Reference analog: ``src/profiler/profiler.{h,cc}`` + ``c_api_profile.cc`` —
Chrome-trace JSON of per-op spans recorded by the engine
(``ProfileOperator`` wraps each executed op, threaded_engine.h:80), an
in-memory aggregate table (``aggregate_stats.cc``), and user-defined
Domain/Task/Frame/Event/Counter/Marker objects.

TPU-native design: the host-side dispatch layer (imperative ``invoke`` and
the Executor) is where op spans are recorded — device-side XLA timing comes
from ``jax.profiler`` (start/stop a TensorBoard trace alongside when
``profile_device`` is requested), keeping the reference's "profile
everything through the scheduler" shape with XLA as the device half.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from . import telemetry as _telemetry
from .base import get_env

__all__ = ["set_config", "set_state", "pause", "resume", "dump", "dumps",
           "profiler_set_config", "profiler_set_state",
           "Domain", "Task", "Frame", "Event", "Counter", "Marker"]

# user-defined profiler counters mirrored into the telemetry registry so a
# /metrics scrape sees the same values a Chrome trace would
_PROF_GAUGE = _telemetry.gauge(
    "profiler_counter", "Latest value of each profiler.Counter",
    ("domain", "counter"))

# the in-memory event list is capped (long runs used to grow it until OOM);
# drops are counted unconditionally — losing trace data is an error signal
_DROPPED = _telemetry.counter(
    "profiler_events_dropped_total",
    "Profiler events dropped by the in-memory cap (MXNET_PROFILER_MAX_EVENTS)")

_lock = threading.Lock()
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": True,
    "aggregate_stats": False,
    "profile_device": False,
    "tensorboard_dir": None,
}
_state = "stop"          # 'run' | 'stop'
_paused = False
_events: List[dict] = []
_max_events = get_env("MXNET_PROFILER_MAX_EVENTS", 1_000_000, int)
_t0 = time.perf_counter()
_jax_trace_active = False

# set by mxnet_tpu.tracing at import: its FlightRecorder, fed every span that
# goes through record_span even when the profiler is stopped
_flight = None


# span categories that are also written into jax's own trace (host plane,
# event name "mx:<span>"): there they lie on the clock of the device's
# ``XLA Ops`` lines, so a device idle gap can be laid to a program span
_ANNOTATED = frozenset(("step", "executor"))


def _now_us():
    return (time.perf_counter() - _t0) * 1e6


def is_running():
    return _state == "run" and not _paused


def _append_event(ev: dict):
    """Capped append shared by spans, counters, markers and flow events."""
    with _lock:
        if len(_events) >= _max_events:
            _DROPPED.inc()
            return
        _events.append(ev)


def record_span(name: str, begin_us: float, end_us: float,
                category: str = "operator", args: Optional[dict] = None):
    """Append one complete span (the ProfileOperator analog).

    Also feeds the flight-recorder ring (tracing.flight) when that is on —
    the ring stays warm even with the profiler stopped, so a post-mortem
    dump has the last N spans regardless of collection state."""
    fl = _flight
    if fl is not None and fl.enabled:
        fl.record(name, category, begin_us, end_us, args)
    if not is_running():
        return
    ev = {"name": name, "cat": category, "ph": "X",
          "ts": begin_us, "dur": end_us - begin_us,
          "pid": os.getpid(),
          "tid": threading.get_ident() % 100000}
    if args:
        ev["args"] = args
    _append_event(ev)


class span:
    """Context manager used by the dispatch layer around each op.

    ``histogram`` (a telemetry Histogram or bound child) receives the same
    wall-clock measurement in seconds when telemetry is enabled, so one
    timing path feeds both the Chrome trace and the metrics registry.
    ``args`` is read at exit: a caller may fill the dict inside the span.

    Spans of the categories ``step`` and ``executor`` also enter a
    ``jax.profiler.TraceAnnotation("mx:" + name)``: while any jax trace is
    on they are events of its host plane.  Nothing here asks whether one
    is; the annotation's own idle cost (under a microsecond) is the cost."""

    __slots__ = ("name", "cat", "begin", "hist", "args", "note")

    def __init__(self, name, category="operator", histogram=None, args=None):
        self.name = name
        self.cat = category
        self.hist = histogram
        self.args = args
        self.note = None

    def __enter__(self):
        if self.cat in _ANNOTATED:
            self.note = _TraceAnnotation("mx:" + self.name)
            self.note.__enter__()
        self.begin = _now_us()
        return self

    def __exit__(self, *exc):
        end = _now_us()
        if self.note is not None:
            self.note.__exit__(None, None, None)
        record_span(self.name, self.begin, end, self.cat, args=self.args)
        if self.hist is not None and _telemetry.enabled:
            self.hist.observe((end - self.begin) * 1e-6)
        return False


def set_config(**kwargs):
    """Configure the profiler (parity: profiler.py:28 set_config)."""
    for k, v in kwargs.items():
        if k not in _config:
            # tolerate reference-only knobs silently (e.g. continuous_dump)
            continue
        _config[k] = v


profiler_set_config = set_config  # legacy alias (reference keeps both)


def set_state(state="stop"):
    """'run' starts collection; 'stop' ends it (parity: set_state)."""
    global _state, _jax_trace_active
    if state not in ("run", "stop"):
        raise ValueError("profiler state must be 'run' or 'stop'")
    if state == "run" and _state != "run":
        if _config["profile_device"] and _config["tensorboard_dir"]:
            import jax
            jax.profiler.start_trace(_config["tensorboard_dir"])
            _jax_trace_active = True
    if state == "stop" and _state == "run" and _jax_trace_active:
        import jax
        jax.profiler.stop_trace()
        _jax_trace_active = False
    _state = state


profiler_set_state = set_state


def pause():
    global _paused
    _paused = True


def resume():
    global _paused
    _paused = False


def dump(finished=True, filename=None):
    """Write the Chrome-trace JSON file (parity: Profiler::DumpProfile).

    ``finished=False`` keeps the event buffer intact (mid-run snapshot);
    only ``finished=True`` clears it.  The write is atomic (temp file +
    rename) so a crash mid-dump can never leave a truncated trace.  The
    ``metadata`` block carries what ``tools/merge_traces.py`` needs to
    clock-align and label per-process traces from a dist run."""
    with _lock:
        events = list(_events)
        if finished:
            _events.clear()
    path = filename or _config["filename"]
    meta = {
        # unix epoch (us) of this process's ts origin: merge_traces.py uses
        # the per-file difference to shift events onto one clock
        "t0_unix_us": time.time() * 1e6 - _now_us(),
        "pid": os.getpid(),
        "rank": int(os.environ.get("DMLC_WORKER_ID", "0") or 0),
        "role": os.environ.get("DMLC_ROLE", "worker"),
    }
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "us",
                   "metadata": meta}, f)
    os.replace(tmp, path)
    return path


def dumps(reset=False):
    """Aggregate-stats table as a string
    (parity: MXAggregateProfileStatsPrint)."""
    with _lock:
        events = list(_events)
        if reset:
            _events.clear()
    agg: Dict[str, List[float]] = defaultdict(list)
    for e in events:
        if "dur" in e:  # complete spans only (not counters/markers)
            agg[e["name"]].append(e["dur"])
    lines = ["%-40s %8s %12s %12s %12s %12s" %
             ("Name", "Calls", "Total(us)", "Min(us)", "Max(us)", "Avg(us)")]
    for name in sorted(agg, key=lambda n: -sum(agg[n])):
        durs = agg[name]
        lines.append("%-40s %8d %12.1f %12.1f %12.1f %12.1f" %
                     (name, len(durs), sum(durs), min(durs), max(durs),
                      sum(durs) / len(durs)))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# user-defined profiling objects (parity: profiler.py Domain/Task/Frame/...)
# ---------------------------------------------------------------------------
class Domain:
    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Span:
    def __init__(self, domain, name, category):
        self.domain = domain
        self.name = name
        self._cat = category
        self._begin = None

    def start(self):
        self._begin = _now_us()

    def stop(self):
        if self._begin is not None:
            record_span("%s::%s" % (self.domain.name, self.name),
                        self._begin, _now_us(), self._cat)
            self._begin = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class Task(_Span):
    def __init__(self, domain, name):
        super().__init__(domain, name, "task")


class Frame(_Span):
    def __init__(self, domain, name):
        super().__init__(domain, name, "frame")


class Event(_Span):
    def __init__(self, name):
        super().__init__(Domain("event"), name, "event")


class Counter:
    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self._value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        self._value = value
        if _telemetry.enabled:
            _PROF_GAUGE.labels(domain=self.domain.name,
                               counter=self.name).set(value)
        if is_running():
            _append_event({"name": "%s::%s" % (self.domain.name, self.name),
                           "cat": "counter", "ph": "C",
                           "ts": _now_us(), "pid": os.getpid(),
                           "args": {"value": value}})

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self


class Marker:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        if is_running():
            _append_event({"name": "%s::%s" % (self.domain.name, self.name),
                           "cat": "marker", "ph": "i", "ts": _now_us(),
                           "pid": os.getpid(), "s": scope[0]})
