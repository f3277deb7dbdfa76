"""Durable run ledger: append-only JSONL of structured run events.

Everything the observability stack computes today — health verdicts,
anomaly trips, program registrations with atlas digests, serving
``/healthz`` transitions, bench results — evaporates with the process.
This module is the durable record: one JSONL file per process, one JSON
object per line, every line stamped with a shared **run id**, a
monotonically increasing per-process ``seq``, and the process's
rank/role, so the ledgers of a multi-process run merge into a single
ordered timeline (:func:`merge`) and ``tools/sentinel.py`` can replay
the bench trajectory mechanically.

Write discipline: a line is serialized *outside* the ledger lock, then
appended with a single ``write()+flush`` on an ``O_APPEND`` stream —
POSIX keeps concurrent same-file appends line-atomic, and a torn final
line (power loss) damages only itself: readers skip unparseable lines.
Rotation (``MXNET_RUNLOG_MAX_BYTES``, default 8 MiB) atomically
``os.replace``-renames the full file to ``<path>.1`` and starts fresh.
A ledger write must never take training down: failures increment
``runlog_write_errors_total`` and drop the event.

Activation: off by default.  Set ``MXNET_RUNLOG_DIR`` (per-process file
name derived from role/rank/pid — safe for dist launches sharing one
directory) or ``MXNET_RUNLOG_PATH`` (exact file — single process only),
or call :func:`enable` programmatically.  On enable, a ``run_start``
event snapshots argv and the MXNET_*/DMLC_*/JAX_* environment including
the step cache-key env flags (``executor.STEP_ENV_KEYS``).

Device topology is recorded *lazily* (:func:`note_topology`, called
from ``health.register_program`` and ``bench.py``): touching
``jax.devices()`` at import/enable time would initialize the backend
before test/apps configure platforms.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

from .base import get_env
from . import telemetry as _telemetry

__all__ = ["enable", "disable", "enabled", "event", "run_id", "path",
           "note_topology", "merge", "RunLog"]

_EVENTS = _telemetry.counter(
    "runlog_events_total", "events appended to the run ledger",
    labelnames=("event",))
_WRITE_ERRORS = _telemetry.counter(
    "runlog_write_errors_total",
    "ledger events dropped because the append failed")

#: env prefixes worth snapshotting at run start (config surface of the
#: runtime + launcher + jax, nothing secret-bearing).
_ENV_PREFIXES = ("MXNET_", "DMLC_", "JAX_", "XLA_")


def _gen_run_id() -> str:
    return "%x-%d-%04x" % (int(time.time()), os.getpid(),
                           int.from_bytes(os.urandom(2), "big"))


def _env_snapshot() -> Dict[str, str]:
    snap = {k: v for k, v in os.environ.items()
            if k.startswith(_ENV_PREFIXES)}
    # the step cache-key flags are part of the snapshot even when unset:
    # "unset" is itself a config state the sentinel may need to compare.
    try:
        from .executor import STEP_ENV_KEYS
        keys = tuple(STEP_ENV_KEYS)
    except Exception:
        # executor may not be importable yet (ledger enabled during
        # package init); fall back to the known cache-key flags.
        keys = ("MXNET_TPU_FUSED_STEP",)
    # program-cache location/size join for the same reason: a warm deploy
    # and a cold one differ ONLY in these (plus the artifacts on disk)
    keys = keys + ("MXNET_PROGRAM_CACHE_DIR", "MXNET_PROGRAM_CACHE_MAX_BYTES")
    for k in keys:
        snap.setdefault(k, os.environ.get(k, ""))
    return snap


class RunLog:
    """One process's append-only JSONL ledger.

    Each line: ``{"ts": unix_s, "run_id", "seq", "rank", "role",
    "event": <type>, ...payload}``.  ``seq`` orders events within one
    process even when wall clocks tie; (ts, run_id, seq) orders the
    merged multi-process timeline.
    """

    def __init__(self, path: str, run_id: Optional[str] = None,
                 max_bytes: Optional[int] = None):
        self._path = path
        self._run_id = run_id or os.environ.get("MXNET_RUN_ID") \
            or _gen_run_id()
        self._max_bytes = (get_env("MXNET_RUNLOG_MAX_BYTES",
                                   8 * 1024 * 1024, int)
                           if max_bytes is None else int(max_bytes))
        self._lock = threading.Lock()
        self._seq = 0
        self._fh = None
        self._rank = os.environ.get("DMLC_WORKER_ID", "0")
        self._role = os.environ.get("DMLC_ROLE", "local")

    @property
    def path(self) -> str:
        return self._path

    @property
    def run_id(self) -> str:
        return self._run_id

    def _open(self):
        d = os.path.dirname(self._path)
        if d:
            os.makedirs(d, exist_ok=True)
        # O_APPEND via mode "a": concurrent appends land whole-line.
        self._fh = open(self._path, "a", encoding="utf-8")

    def _rotate_locked(self):
        try:
            if self._fh is not None:
                self._fh.close()
            os.replace(self._path, self._path + ".1")
        except OSError:
            pass
        self._fh = None

    def event(self, event_type: str, **payload) -> bool:
        """Append one event; returns False (and counts the drop) on any
        failure.  Serialization happens before the lock; the locked
        region is seq assignment + one write."""
        rec = {"ts": round(time.time(), 6), "run_id": self._run_id,
               "rank": self._rank, "role": self._role,
               "event": str(event_type)}
        for k, v in payload.items():
            if k not in rec:
                rec[k] = v
        try:
            with self._lock:
                rec["seq"] = self._seq
                self._seq += 1
                line = json.dumps(rec, default=str) + "\n"
                if self._fh is None:
                    self._open()
                if self._max_bytes and \
                        self._fh.tell() + len(line) > self._max_bytes:
                    self._rotate_locked()
                    self._open()
                self._fh.write(line)
                self._fh.flush()
        except Exception:
            _WRITE_ERRORS.inc()
            return False
        _EVENTS.labels(event=str(event_type)).inc()
        return True

    def close(self):
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except Exception:
                    pass
                self._fh = None


# ---------------------------------------------------------------------------
# module-level ledger (the one the built-in hooks write to)
# ---------------------------------------------------------------------------
_log: Optional[RunLog] = None
_state_lock = threading.Lock()
_topology_noted = False


def _default_path() -> Optional[str]:
    explicit = os.environ.get("MXNET_RUNLOG_PATH")
    if explicit:
        return explicit
    directory = os.environ.get("MXNET_RUNLOG_DIR")
    if not directory:
        return None
    role = os.environ.get("DMLC_ROLE", "local")
    rank = os.environ.get("DMLC_WORKER_ID", "0")
    return os.path.join(directory,
                        "runlog_%s%s_%d.jsonl" % (role, rank, os.getpid()))


def enable(path: Optional[str] = None,
           run_id: Optional[str] = None) -> Optional[RunLog]:
    """Open the process ledger and write the ``run_start`` event.
    Idempotent (returns the existing ledger if already enabled); returns
    None when no path is given and no env var names one."""
    global _log, _topology_noted
    with _state_lock:
        if _log is not None:
            return _log
        p = path or _default_path()
        if not p:
            return None
        _log = RunLog(p, run_id=run_id)
        _topology_noted = False
        log = _log
    # cache identity without forcing jax backend init: directory and
    # fingerprint are known only once program_cache.enable() ran (which
    # then also logs a full "program_cache_start" event)
    from . import program_cache as _program_cache
    log.event("run_start",
              argv=list(sys.argv),
              env=_env_snapshot(),
              python="%d.%d.%d" % sys.version_info[:3],
              pid=os.getpid(),
              program_cache_dir=_program_cache.cache_dir(),
              program_cache_fingerprint=_program_cache.fingerprint())
    return log


def disable():
    """Write ``run_end`` and close the ledger.  Idempotent."""
    global _log
    with _state_lock:
        log, _log = _log, None
    if log is not None:
        log.event("run_end")
        log.close()


def enabled() -> bool:
    return _log is not None


def run_id() -> Optional[str]:
    log = _log
    return log.run_id if log is not None else None


def path() -> Optional[str]:
    log = _log
    return log.path if log is not None else None


def event(event_type: str, **payload) -> bool:
    """Append to the process ledger; no-op (False) when disabled."""
    log = _log
    if log is None:
        return False
    return log.event(event_type, **payload)


def note_topology() -> bool:
    """Record the device topology once per ledger.  Deferred from
    enable() on purpose: calling ``jax.devices()`` at import time would
    initialize the backend before callers configure platforms — this is
    invoked from the first ``health.register_program`` and from bench.py,
    both safely after jax is in use."""
    global _topology_noted
    log = _log
    if log is None:
        return False
    with _state_lock:
        if _topology_noted:
            return False
        _topology_noted = True
    try:
        import jax
        devs = jax.devices()
        payload = {"platform": devs[0].platform if devs else "none",
                   "n_devices": len(devs),
                   "process_index": getattr(jax, "process_index",
                                            lambda: 0)(),
                   "devices": [str(d) for d in devs[:64]]}
    except Exception as exc:
        payload = {"error": str(exc)}
    return log.event("device_topology", **payload)


# ---------------------------------------------------------------------------
# merge: many per-process ledgers -> one ordered timeline
# ---------------------------------------------------------------------------
def merge(paths: List[str]) -> List[dict]:
    """Merge ledger files into one timeline ordered by (ts, run_id, seq,
    source).  Unparseable lines (torn tails) are skipped, not fatal —
    the whole point of line-framed JSONL.  Each record gains a
    ``source`` field naming the file it came from."""
    records = []
    for p in paths:
        try:
            with open(p, "r", encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(rec, dict):
                        rec.setdefault("source", os.path.basename(p))
                        records.append(rec)
        except OSError:
            continue
    records.sort(key=lambda r: (r.get("ts", 0.0), str(r.get("run_id", "")),
                                r.get("seq", 0), str(r.get("source", ""))))
    return records


def main(argv=None):
    """CLI: ``python -m mxnet_tpu.runlog merge <files...>`` prints the
    merged timeline as JSONL on stdout."""
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] != "merge" or len(argv) < 2:
        sys.stderr.write(
            "usage: python -m mxnet_tpu.runlog merge FILE [FILE...]\n")
        return 2
    for rec in merge(argv[1:]):
        sys.stdout.write(json.dumps(rec) + "\n")
    return 0


if get_env("MXNET_RUNLOG_DIR", None) or get_env("MXNET_RUNLOG_PATH", None):
    enable()


if __name__ == "__main__":
    sys.exit(main())
