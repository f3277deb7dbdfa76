"""Always-on runtime telemetry: metrics registry + exporters.

Reference analog: the reference profiles everything through the scheduler
(``ProfileOperator`` in ``threaded_engine.h`` plus the aggregate tables of
``aggregate_stats.cc``).  This package is that idea rebuilt in the
Prometheus/Dapper mold: a process-wide registry of ``Counter`` / ``Gauge``
/ ``Histogram`` instruments with label support, wired into the engine,
KVStore, data pipeline, executor and trainer, and exported as Prometheus
text exposition, a JSON snapshot, or an optional stdlib HTTP endpoint.

Relation to :mod:`mxnet_tpu.profiler`: the profiler answers "what happened
during this trace window" (Chrome-trace spans, bounded collection); the
telemetry registry answers "what is the process doing right now" (cheap
monotonic aggregates, safe to leave on in production).  They share one
timing path — ``profiler.span`` feeds a telemetry histogram when asked,
and ``profiler.Counter`` bridges its values into a registry gauge.

Cost model: the built-in instrumentation sites are gated by the module
attribute :data:`enabled` — a single attribute check on the disabled
(default) fast path, so bench numbers are unaffected.  Enable with
``MXNET_TELEMETRY=1`` in the environment or :func:`enable`; set
``MXNET_TELEMETRY_PORT`` to additionally serve ``/metrics``.

    from mxnet_tpu import telemetry
    telemetry.enable()
    ...train...
    print(telemetry.prometheus_text())
    telemetry.snapshot()["engine_ops_completed_total"]
"""
from __future__ import annotations

import contextlib as _contextlib
import sys as _sys

from ..base import get_env
from .registry import (Counter, Gauge, Histogram, MetricRegistry,
                       WindowedRate, DEFAULT_TIME_BUCKETS, log_buckets)
from . import export as _export

__all__ = ["enabled", "enable", "disable", "counter", "gauge", "histogram",
           "registry", "snapshot", "snapshot_json", "prometheus_text",
           "value", "quantile", "reset", "start_http_server",
           "stop_http_server", "timeseries",
           "Counter", "Gauge", "Histogram", "MetricRegistry",
           "WindowedRate", "DEFAULT_TIME_BUCKETS", "log_buckets"]

# The process-wide default registry.  Always live: instruments can be
# created and driven regardless of `enabled` (the flag only gates the
# built-in hot-path instrumentation sites).
_registry = MetricRegistry()

#: single-attribute-check gate read by the instrumentation sites
#: (``if _telemetry.enabled: ...``); default off.
enabled: bool = False


def registry() -> MetricRegistry:
    return _registry


def counter(name, help="", labelnames=()) -> Counter:  # noqa: A002
    """Get-or-create a counter in the default registry."""
    return _registry.counter(name, help, labelnames)


def gauge(name, help="", labelnames=()) -> Gauge:  # noqa: A002
    return _registry.gauge(name, help, labelnames)


def histogram(name, help="", labelnames=(),  # noqa: A002
              buckets=None) -> Histogram:
    return _registry.histogram(name, help, labelnames, buckets=buckets)


def enable():
    """Turn the built-in instrumentation on; starts the /metrics endpoint
    when ``MXNET_TELEMETRY_PORT`` is set and the time-series sampler
    unless ``MXNET_TELEMETRY_TS=0``.  With ``MXNET_FLEET_DIR`` also set,
    the bound endpoint is announced in the fleet directory so a fleet
    collector can discover and scrape this process (see telemetry/fleet
    and docs/observability.md "Fleet")."""
    global enabled
    enabled = True
    port = get_env("MXNET_TELEMETRY_PORT", None, int)
    if port is not None:
        bound = start_http_server(port)
        if get_env("MXNET_FLEET_DIR", None):
            from . import fleet as _fleet
            _fleet.register_endpoint(bound)
    if get_env("MXNET_TELEMETRY_TS", True, bool):
        timeseries.start()


@_contextlib.contextmanager
def paused():
    """The built-in counters off inside: for a trace that compiles no
    program (shape inference runs every op under ``jax.eval_shape`` in
    float32, and the trace-time dispatch counters count compiled
    variants, not shapes asked about)."""
    global enabled
    was, enabled = enabled, False
    try:
        yield
    finally:
        enabled = was


def disable():
    global enabled
    enabled = False
    timeseries.stop()
    if "mxnet_tpu.telemetry.fleet" in _sys.modules:
        _sys.modules["mxnet_tpu.telemetry.fleet"].unregister_endpoint()


def snapshot():
    """JSON-able dict of every metric (see export.snapshot)."""
    return _export.snapshot(_registry)


def snapshot_json(**kwargs) -> str:
    return _export.snapshot_json(_registry, **kwargs)


def prometheus_text() -> str:
    return _export.prometheus_text(_registry)


def value(name, **labels):
    """Convenience accessor: current value of one series (counters and
    gauges return the value; histograms return the observation count).
    Returns 0 for never-touched series so callers can test deltas."""
    fam = _registry.get(name)
    if fam is None:
        return 0
    child = fam.labels(**labels)
    data = child.get()
    if isinstance(data, dict):
        return data["count"]
    return data


def quantile(name, q, **labels):
    """Estimated q-quantile of one histogram series (bucket-interpolated;
    see _HistogramChild.quantile).  Returns 0.0 for unknown/never-observed
    series so callers can report without existence checks."""
    fam = _registry.get(name)
    if fam is None:
        return 0.0
    child = fam.labels(**labels)
    if not hasattr(child, "quantile"):
        return 0.0
    return child.quantile(q)


def reset():
    """Zero every recorded sample (test isolation)."""
    _registry.reset()


def start_http_server(port=None, host=None):
    """Explicitly start the /metrics endpoint (also reached via
    ``MXNET_TELEMETRY_PORT`` + enable()).  Returns the bound port."""
    if port is None:
        port = get_env("MXNET_TELEMETRY_PORT", 0, int)
    if host is None:
        host = get_env("MXNET_TELEMETRY_HOST", "127.0.0.1")
    return _export.start_http_server(int(port), _registry, host=host)


def stop_http_server():
    _export.stop_http_server()


# imported after _registry exists (timeseries.store() binds to it lazily)
from . import timeseries  # noqa: E402


if get_env("MXNET_TELEMETRY", False, bool):
    enable()
