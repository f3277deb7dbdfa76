"""Continuous training health monitor.

The perf story so far (bench rounds r02-r06) lives offline in ``bench.py``:
MFU, phase breakdowns and scaling numbers are bench artifacts, invisible to
a production run that silently degrades.  This module turns them into
runtime signals built from three pieces:

**Program cost accounting** — every cached step/forward program registers
itself here right before its first invocation.  We lower it (trace only —
on this jax the AOT ``.compile()`` does NOT share the executable cache
with the normal call path, so compiling here would double every program's
XLA compile) and read ``Lowered.cost_analysis()`` for the FLOP count plus
the in/out avals for the HBM footprint: ``program_flops{program}``,
``program_hbm_bytes{program,kind=args|output}``.  ``MXNET_HEALTH_DEEP=1``
opts into a real AOT compile per registered program for XLA's
``memory_analysis()`` temp-buffer figure (``kind=temp``) — explicitly
paying one extra compile each.  The donation audit is runtime truth
rather than a compiler report: after a donated program's first execution
the call site hands back the donated inputs (:func:`audit_donation`) and
any buffer jax did NOT invalidate means XLA dropped the alias
(``program_donation_leaks_total`` — the r04 donation chain silently
broke).

**Step-phase attribution** — :class:`StepMonitor` stitches a per-step
ledger from the existing hooks: ``io.py`` prefetch waits feed the *input*
phase, KVStore push/pull latencies feed *sync*, and deltas of
``op_jit_cache_misses_total`` / ``op_compile_seconds`` feed *compile*.
Each dispatch-to-dispatch window is classified input-bound / compute-bound
/ compile-bound / sync-bound (``step_health_verdict{cause}``) and a live
``step_mfu_pct`` gauge is computed as measured step rate x registered
program FLOPs / per-platform peak — replacing the two hand-counted FLOP
models ``bench.py`` used to carry.

**Anomaly + straggler detection** — a rolling EWMA plus a MAD band over
step time; a debounced trip bumps ``health_anomalies_total{cause}`` and
dumps the flight recorder (PR 3) so the evidence window around the bad
step survives.  In dist mode each worker piggybacks ``{rank, step_seconds}``
on the KVStore wire header (same pattern as the trace context) and the
server aggregates ``worker_step_seconds{rank}`` plus a straggler verdict.

Everything is gated on the module attribute :data:`enabled` (default OFF;
``MXNET_HEALTH=1`` or :func:`enable` — which implies telemetry — turns it
on), so the disabled path stays a single attribute check and executor
builds in the test suite never pay the AOT lowering cost.
"""
from __future__ import annotations

import collections
import os
import threading
import time

from . import telemetry as _telemetry
from .base import get_env

__all__ = ["enabled", "enable", "disable", "peak_tflops", "achieved_tflops",
           "mfu_fraction", "mfu_impossible", "register_program",
           "audit_donation", "programs", "program_flops_total", "monitor",
           "workers", "statusz", "healthz", "StepMonitor", "WorkerTable",
           "CAUSES"]

#: single-attribute gate read by every hook site; default off.
enabled: bool = False

# -- metrics ----------------------------------------------------------------

_PROG_FLOPS = _telemetry.gauge(
    "program_flops",
    "XLA cost_analysis flops of a registered compiled program",
    ("program",))
_PROG_HBM = _telemetry.gauge(
    "program_hbm_bytes",
    "XLA memory_analysis footprint of a registered program by kind",
    ("program", "kind"))
_PROG_DONATED = _telemetry.gauge(
    "program_donated_bytes",
    "donated input bytes actually invalidated by the first execution",
    ("program",))
_DONATION_LEAKS = _telemetry.counter(
    "program_donation_leaks_total",
    "donated programs whose inputs all survived execution (alias dropped)",
    ("program",))
_MFU = _telemetry.gauge(
    "step_mfu_pct",
    "live model-flops-utilization: program flops / (step time * peak)")
_STEP_EWMA = _telemetry.gauge(
    "step_seconds_ewma",
    "exponentially weighted moving average of the step interval")
_VERDICT = _telemetry.gauge(
    "step_health_verdict",
    "1 on the cause currently attributed to the step window, 0 elsewhere",
    ("cause",))
_ANOMALIES = _telemetry.counter(
    "health_anomalies_total",
    "debounced step-time anomaly trips by attributed cause",
    ("cause",))
_WORKER_STEP = _telemetry.gauge(
    "worker_step_seconds",
    "per-worker step time aggregated by the KVStore server",
    ("rank",))
_STRAGGLER = _telemetry.gauge(
    "worker_straggler_verdict",
    "1 when this rank's step time exceeds the straggler band",
    ("rank",))

#: ``oom_risk`` is set by memwatch's pre-flight (not by the step-window
#: classifier); listing it here lets on_step zero it once the risky
#: program's window passes.
CAUSES = ("compute_bound", "input_bound", "sync_bound", "compile_bound",
          "oom_risk")

# -- peak FLOPS model (shared with bench.py) --------------------------------

# Dense matmul peaks of one device in TFLOP/s, keyed by the ``device_kind``
# jax reports, each row with its published source.  A kind or dtype that is
# not here is an error, never a default: add the row with its source.
# Override with MXNET_HEALTH_PEAK_TFLOPS (or bench's BENCH_PEAK_TFLOPS).
_PEAK_TFLOPS = {
    "TPU v5 lite": {
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16 and 393 TOP/s int8 per chip",
        "tflops": {"bfloat16": 197.0, "int8": 393.0},
    },
    "cpu": {
        "source": "not a device peak: a convention that keeps the live "
                  "MFU gauge finite on CPU test runs",
        "tflops": {None: 0.25},
    },
}


#: MFU is quoted against the device's bf16 matmul peak whatever dtype a
#: program stores in: it is the dense figure the sources publish, and the
#: convention model-FLOP/s utilization is compared under
MFU_DTYPE = "bfloat16"


def peak_tflops(dtype=MFU_DTYPE, device_kind=None):
    """Published peak in TFLOP/s of one ``device_kind`` device for
    ``dtype`` (env-overridable).  ``device_kind=None`` asks jax for the
    first device's kind.  Raises ``KeyError`` for a kind or dtype the
    table has no sourced row for."""
    for key in ("MXNET_HEALTH_PEAK_TFLOPS", "BENCH_PEAK_TFLOPS"):
        raw = os.environ.get(key)
        if raw:
            return float(raw)
    if device_kind is None:
        device_kind = _device_kind()
    row = _PEAK_TFLOPS.get(device_kind)
    if row is None:
        raise KeyError(
            "no published peak for device_kind %r (known: %s); add a "
            "sourced row to health._PEAK_TFLOPS or set "
            "MXNET_HEALTH_PEAK_TFLOPS" % (device_kind,
                                          sorted(_PEAK_TFLOPS)))
    tflops = row["tflops"]
    if None in tflops:
        return tflops[None]
    if str(dtype) not in tflops:
        raise KeyError("no published %s peak for device_kind %r (%s)"
                       % (dtype, device_kind, row["source"]))
    return tflops[str(dtype)]


def achieved_tflops(rate, flops_per_item):
    """items/s x flops/item in TFLOP/s."""
    return float(rate) * float(flops_per_item) / 1e12


def mfu_fraction(rate, flops_per_item, peak):
    """Achieved / peak as a fraction (bench multiplies by 100 to report)."""
    if peak <= 0:
        return 0.0
    return achieved_tflops(rate, flops_per_item) / float(peak)


def mfu_impossible(mfu, platform):
    """The bench sanity check: >120% MFU on a real chip means the FLOP
    model or the clock is wrong.  CPU runs are exempt (their peak is a
    convention, not a measurement)."""
    return platform != "cpu" and float(mfu) > 1.2


def _platform():
    import jax
    return jax.devices()[0].platform


def _device_kind():
    import jax
    return jax.devices()[0].device_kind


# -- program cost accounting ------------------------------------------------

class ProgramCost(object):
    """Cost snapshot of one registered program.

    ``temp_bytes`` is None unless deep mode compiled the program;
    ``donated_bytes`` / ``donation_leak`` are filled in by
    :func:`audit_donation` after the first execution.  ``env`` is the
    registering site's snapshot of the env flags in the program's cache
    key ({key: value-at-build}), so post-mortem dumps can tie a cached
    program back to the formulation flags that built it."""

    __slots__ = ("name", "flops", "arg_bytes", "out_bytes", "temp_bytes",
                 "donated_bytes", "donation_requested", "donation_leak",
                 "env")

    def __init__(self, name, flops, arg_bytes, out_bytes, temp_bytes,
                 donation_requested, env=None):
        self.name = name
        self.flops = flops
        self.arg_bytes = arg_bytes
        self.out_bytes = out_bytes
        self.temp_bytes = temp_bytes
        self.donated_bytes = None
        self.donation_requested = donation_requested
        self.donation_leak = False
        self.env = dict(env or {})

    def as_dict(self):
        return {"flops": self.flops, "arg_bytes": self.arg_bytes,
                "out_bytes": self.out_bytes, "temp_bytes": self.temp_bytes,
                "donated_bytes": self.donated_bytes,
                "donation_requested": self.donation_requested,
                "donation_leak": self.donation_leak,
                "env": self.env}


_programs = {}
_programs_lock = threading.Lock()


def _leaf_bytes(leaf):
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    try:
        import numpy as np
        return n * np.dtype(dtype).itemsize
    except Exception:
        return 0


def _tree_bytes(tree):
    import jax
    return sum(_leaf_bytes(leaf) for leaf in jax.tree_util.tree_leaves(tree))


def register_program(name, fn, args, kwargs=None, donated=False, env=None):
    """Analyze a jitted callable right before its first invocation.

    Lowering only (trace, no XLA compile — on this jax an AOT
    ``.compile()`` does not share the normal call path's executable cache,
    so it would compile every program twice): FLOPs come from
    ``Lowered.cost_analysis()``, argument/output bytes from the avals.
    With ``MXNET_HEALTH_DEEP=1`` the program IS additionally AOT-compiled
    for ``memory_analysis()`` temp bytes — one extra XLA compile each,
    opt-in.  ``env`` (a {cache-key env var: value} snapshot from the
    registering site) is stored on the cost record for post-mortem dumps.
    Returns the :class:`ProgramCost` or None (disabled, non-jitted fn, or
    any analysis failure — health must never break the training step).
    """
    if not enabled or not hasattr(fn, "lower"):
        return None
    try:
        lowered = fn.lower(*args, **(kwargs or {}))
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float((cost or {}).get("flops", 0.0) or 0.0)
        arg_b = _tree_bytes((args, kwargs or {}))
        out_b = _tree_bytes(getattr(lowered, "out_info", None))
        tmp_b = None
        if get_env("MXNET_HEALTH_DEEP", False, bool):
            mem = lowered.compile().memory_analysis()
            tmp_b = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    except Exception:
        return None
    pc = ProgramCost(name, flops, arg_b, out_b, tmp_b, donated, env=env)
    with _programs_lock:
        _programs[name] = pc
    try:
        from . import atlas as _atlas
        _atlas.analyze(name, lowered, cost_flops=flops)
    except Exception:
        pass
    try:
        from . import runlog as _runlog
        if _runlog.enabled():
            _runlog.note_topology()  # jax is initialized by now
            digest = None
            try:
                from . import atlas as _atlas
                snap = _atlas.snapshot(top_k=1).get(name)
                if snap:
                    digest = {"coverage_pct": snap.get("coverage_pct"),
                              "n_scopes": snap.get("n_scopes"),
                              "n_instructions": snap.get("n_instructions")}
            except Exception:
                pass
            _runlog.event("program_registered", program=name, flops=flops,
                          arg_bytes=arg_b, out_bytes=out_b, temp_bytes=tmp_b,
                          donated=bool(donated), env=env, atlas=digest)
    except Exception:
        pass
    _PROG_FLOPS.labels(program=name).set(flops)
    _PROG_HBM.labels(program=name, kind="args").set(arg_b)
    _PROG_HBM.labels(program=name, kind="output").set(out_b)
    if tmp_b is not None:
        _PROG_HBM.labels(program=name, kind="temp").set(tmp_b)
    try:
        # OOM pre-flight: every registration site gets the projection for
        # free; memwatch gates itself and must never break registration.
        from . import memwatch as _memwatch
        _memwatch.preflight(pc)
    except Exception:
        pass
    return pc


def audit_donation(name, donated):
    """Runtime donation audit, called by the owning site right AFTER the
    program's first execution with the inputs it donated: jax invalidates
    donated buffers the executable actually aliased, so any survivor
    means XLA silently dropped the alias and HBM use doubled.  Returns
    (freed_bytes, leaked_bytes) or None when disabled."""
    if not enabled:
        return None
    try:
        import jax
        freed = leaked = 0
        for leaf in jax.tree_util.tree_leaves(donated):
            if not hasattr(leaf, "is_deleted"):
                continue
            nbytes = _leaf_bytes(leaf)
            if leaf.is_deleted():
                freed += nbytes
            else:
                leaked += nbytes
    except Exception:
        return None
    leak = bool(freed == 0 and leaked > 0)
    with _programs_lock:
        pc = _programs.get(name)
        if pc is not None:
            pc.donated_bytes = freed
            pc.donation_leak = leak
    _PROG_DONATED.labels(program=name).set(freed)
    if leak:
        _DONATION_LEAKS.labels(program=name).inc()
    return freed, leaked


def programs():
    """Snapshot of every registered program's cost record."""
    with _programs_lock:
        return dict(_programs)


def program_flops_total(names):
    """Summed flops of the named programs (unknown names contribute 0).

    ``names`` may be a single program name or a tuple — split paths
    (eager fwdbwd + update program) sum their pieces."""
    if names is None:
        return 0.0
    if isinstance(names, str):
        names = (names,)
    with _programs_lock:
        return float(sum(_programs[n].flops for n in names
                         if n in _programs))


# -- compile activity (deltas of the PR 3 compile observability metrics) ----

def _compile_totals():
    """(total jit-cache misses, total compile seconds) across every op."""
    misses = 0.0
    fam = _telemetry.registry().get("op_jit_cache_misses_total")
    if fam is not None:
        misses = sum(v for _, v in fam.samples())
    secs = 0.0
    fam = _telemetry.registry().get("op_compile_seconds")
    if fam is not None:
        secs = sum(v["sum"] for _, v in fam.samples())
    return misses, secs


# -- step monitor -----------------------------------------------------------

class StepMonitor(object):
    """Per-step ledger: phase attribution, live MFU, anomaly trips.

    ``on_step(program)`` is called once per optimization step at the
    dispatch site; the elapsed time since the previous dispatch is the step
    window.  ``note_phase`` accumulates input/sync wall time contributed by
    the io/kvstore hooks inside that window.
    """

    #: EWMA smoothing factor over step intervals.
    ALPHA = 0.15
    #: a phase owns the verdict once it exceeds this share of the window.
    SHARE_THRESHOLD = 0.3
    #: anomaly needs at least this many samples of history.
    WARMUP = 8

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with getattr(self, "_lock", threading.Lock()):
            self._last_dispatch = None
            self._ewma = None
            self._window = collections.deque(maxlen=64)
            self._input_s = 0.0
            self._sync_s = 0.0
            self._misses_seen, self._compile_seen = _compile_totals()
            self._last_trip = 0.0
            self._ledger = collections.deque(maxlen=128)
            self._last_dt = None
            self._cause = None
            self._mfu = None

    # -- hooks -------------------------------------------------------------

    def note_phase(self, phase, seconds):
        """Attribute ``seconds`` of the current window to ``phase``
        (``"input"`` or ``"sync"``)."""
        if not enabled:
            return
        with self._lock:
            if phase == "input":
                self._input_s += float(seconds)
            elif phase == "sync":
                self._sync_s += float(seconds)

    def on_step(self, program=None):
        """Mark one step dispatched; closes the previous window."""
        if not enabled:
            return
        now = time.perf_counter()
        with self._lock:
            last, self._last_dispatch = self._last_dispatch, now
        if last is None:
            return  # first dispatch: no window to attribute yet
        self.observe_step(now - last, program=program, now=now)

    def observe_step(self, dt, program=None, now=None):
        """Account one closed step window of length ``dt`` seconds.

        Split out from :meth:`on_step` so tests can inject synthetic
        windows (e.g. a 10x slow step) without sleeping."""
        if not enabled or dt <= 0:
            return
        now = time.perf_counter() if now is None else now
        misses, compile_s = _compile_totals()
        with self._lock:
            input_s, self._input_s = self._input_s, 0.0
            sync_s, self._sync_s = self._sync_s, 0.0
            miss_d = misses - self._misses_seen
            compile_d = compile_s - self._compile_seen
            self._misses_seen, self._compile_seen = misses, compile_s
            prior_ewma = self._ewma
            window = tuple(self._window)

        shares = {
            "input": min(1.0, input_s / dt),
            "sync": min(1.0, sync_s / dt),
            "compile": min(1.0, compile_d / dt) if miss_d > 0 else 0.0,
        }
        cause = "compute_bound"
        top = max(shares, key=shares.get)
        if shares[top] > self.SHARE_THRESHOLD:
            cause = top + "_bound"

        flops = program_flops_total(program)
        mfu = None
        if flops > 0:
            peak = peak_tflops(MFU_DTYPE)
            if peak > 0:
                mfu = 100.0 * flops / (dt * peak * 1e12)
                _MFU.set(mfu)

        tripped = False
        if prior_ewma is not None and len(window) >= self.WARMUP:
            med = _median(window)
            mad = _median([abs(x - med) for x in window])
            k = get_env("MXNET_HEALTH_ANOMALY_K", 6.0, float)
            band = prior_ewma + k * 1.4826 * max(mad, 1e-9)
            debounce = get_env("MXNET_HEALTH_ANOMALY_DEBOUNCE", 5.0, float)
            if dt > band and dt > 2.0 * prior_ewma:
                with self._lock:
                    ok = now - self._last_trip >= debounce
                    if ok:
                        self._last_trip = now
                if ok:
                    tripped = True
                    _ANOMALIES.labels(cause=cause).inc()
                    self._flight_dump(dt, prior_ewma, cause, shares)

        ewma = dt if prior_ewma is None else (
            (1.0 - self.ALPHA) * prior_ewma + self.ALPHA * dt)
        _STEP_EWMA.set(ewma)
        for c in CAUSES:
            _VERDICT.labels(cause=c).set(1.0 if c == cause else 0.0)

        entry = {"unix_time": time.time(), "step_seconds": dt,
                 "cause": cause, "shares": shares, "mfu_pct": mfu,
                 "programs": list(program) if isinstance(program, tuple)
                 else program, "anomaly": tripped,
                 "compile_misses": miss_d}
        with self._lock:
            prev_cause = self._cause
            self._ewma = ewma
            self._window.append(dt)
            self._last_dt = dt
            self._cause = cause
            self._mfu = mfu
            self._ledger.append(entry)
        if cause != prev_cause:
            # durable record of every verdict transition (not every step:
            # the ledger is an event log, not a metrics store)
            try:
                from . import runlog as _runlog
                _runlog.event("health_verdict", cause=cause,
                              prev_cause=prev_cause, step_seconds=dt,
                              shares=shares, mfu_pct=mfu,
                              ewma_seconds=ewma)
            except Exception:
                pass

    def _flight_dump(self, dt, ewma, cause, shares):
        """Record the anomaly into the flight ring and dump it; evidence
        capture must never raise into the step."""
        try:
            from . import tracing as _tracing
            from . import profiler as _profiler
            end_us = _profiler._now_us()
            _tracing.flight.record(
                "Health::Anomaly", "health",
                end_us - dt * 1e6, end_us,
                args={"step_seconds": dt, "ewma_seconds": ewma,
                      "cause": cause, "shares": shares})
            dump_path = _tracing.flight.dump(reason="health_anomaly")
            try:
                from . import runlog as _runlog
                _runlog.event("anomaly", step_seconds=dt,
                              ewma_seconds=ewma, cause=cause,
                              shares=shares, flight_dump=dump_path)
            except Exception:
                pass
        except Exception:
            pass

    def drop_window(self):
        """Discard the open window (e.g. after a disabled span) so the next
        dispatch starts a fresh interval instead of attributing the gap."""
        with self._lock:
            self._last_dispatch = None

    # -- readers -----------------------------------------------------------

    def last_step_seconds(self):
        with self._lock:
            return self._last_dt

    def snapshot(self):
        with self._lock:
            return {"ewma_seconds": self._ewma,
                    "last_step_seconds": self._last_dt,
                    "cause": self._cause,
                    "mfu_pct": self._mfu,
                    "samples": len(self._window),
                    "ledger": list(self._ledger)[-16:]}


def _median(values):
    vals = sorted(values)
    n = len(vals)
    if not n:
        return 0.0
    mid = n // 2
    if n % 2:
        return vals[mid]
    return 0.5 * (vals[mid - 1] + vals[mid])


# -- per-worker straggler table (server side) -------------------------------

class WorkerTable(object):
    """KVStore-server aggregate of per-worker step times.

    Workers piggyback ``{"r": rank, "st": step_seconds}`` on the wire
    header (the trace-context pattern); the server records the latest
    report per rank and flags ranks beyond the straggler band."""

    #: a rank is a straggler past this multiple of the median (>= 2 ranks).
    BAND = 1.75

    def __init__(self):
        self._lock = threading.Lock()
        self._workers = {}
        self._flags = {}  # rank -> bool, for transition-edge ledger events

    def update(self, rank, step_seconds):
        rank = str(rank)
        step_seconds = float(step_seconds)
        with self._lock:
            self._workers[rank] = (step_seconds, time.time())
            snap = {r: s for r, (s, _) in self._workers.items()}
        _WORKER_STEP.labels(rank=rank).set(step_seconds)
        if len(snap) >= 2:
            med = _median(list(snap.values()))
            transitions = []
            with self._lock:
                for r, s in snap.items():
                    flag = bool(med > 0 and s > self.BAND * med)
                    if self._flags.get(r, False) != flag:
                        transitions.append((r, flag, s))
                    self._flags[r] = flag
            for r, s in snap.items():
                _STRAGGLER.labels(rank=r).set(
                    1.0 if (med > 0 and s > self.BAND * med) else 0.0)
            if transitions:
                try:
                    from . import runlog as _runlog
                    for r, flag, s in transitions:
                        _runlog.event("straggler", worker_rank=r,
                                      straggler=flag, step_seconds=s,
                                      median_seconds=med)
                except Exception:
                    pass

    def snapshot(self):
        with self._lock:
            table = {r: {"step_seconds": s, "unix_time": t}
                     for r, (s, t) in self._workers.items()}
        if len(table) >= 2:
            med = _median([v["step_seconds"] for v in table.values()])
            for v in table.values():
                v["straggler"] = bool(
                    med > 0 and v["step_seconds"] > self.BAND * med)
        return table

    def clear(self):
        with self._lock:
            self._workers.clear()
            self._flags.clear()


#: process-wide singletons driven by the hook sites.
monitor = StepMonitor()
workers = WorkerTable()


# -- /statusz ---------------------------------------------------------------

def statusz():
    """JSON-able health snapshot served by telemetry/export.py."""
    from . import program_cache as _program_cache
    return {
        "enabled": enabled,
        "platform": _platform(),
        "device_kind": _device_kind(),
        "peak_tflops": peak_tflops(MFU_DTYPE),
        "peak_dtype": MFU_DTYPE,
        "programs": {n: pc.as_dict() for n, pc in programs().items()},
        "step": monitor.snapshot(),
        "workers": workers.snapshot(),
        "program_cache": _program_cache.stats(),
    }


def healthz():
    """Process-level liveness/degradation verdict for scrape consumers
    (served on ``/healthz`` and bundled into ``/allz``).  ``degraded``
    when the step window is attributed to oom_risk or an anomaly tripped
    within the last 60 s; a reachable process is otherwise ``ok`` even
    with the health hooks off (liveness and health are different
    questions)."""
    snap = monitor.snapshot()
    causes = []
    if snap["cause"] == "oom_risk":
        causes.append("oom_risk")
    now = time.time()
    for entry in reversed(snap["ledger"]):
        if entry.get("anomaly") and now - entry.get("unix_time", 0.0) <= 60.0:
            causes.append("recent_anomaly")
            break
    return {"status": "degraded" if causes else "ok", "enabled": enabled,
            "causes": causes, "cause": snap["cause"],
            "mfu_pct": snap["mfu_pct"],
            "ewma_seconds": snap["ewma_seconds"]}


# -- gates ------------------------------------------------------------------

def enable():
    """Turn the health hooks on (implies telemetry — the signals are
    exported through the registry)."""
    global enabled
    _telemetry.enable()
    enabled = True
    # re-baseline compile counters so pre-enable compilation isn't
    # attributed to the first monitored window
    monitor._misses_seen, monitor._compile_seen = _compile_totals()


def disable():
    global enabled
    enabled = False


def reset():
    """Test isolation: drop program records, monitor state, worker table."""
    with _programs_lock:
        _programs.clear()
    monitor.reset()
    workers.clear()


if get_env("MXNET_HEALTH", False, bool):
    enable()
