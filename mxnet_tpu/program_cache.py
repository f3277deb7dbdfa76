"""Persistent compiled-program cache: restarts become a disk read.

Every process restart re-pays full XLA compilation today: serving warmup
compiles the whole bucket ladder, training recompiles the whole-step
program before step 1 — minutes of dead chip time per process at fleet
scale.  This module makes the (now sound — PRs 8/9) program cache key
*durable* by persisting compiled XLA executables on disk and loading
them on the next process's first call.

Design constraint (verified on this jax, see health.py): AOT
``lower().compile()`` objects do NOT share the jit call cache, so
serializing AOT executables cannot warm the call path.  Instead this
module hooks the **call-path compilation cache**: jax's
``compile_or_get_cached`` consults a pluggable persistent cache keyed by
the canonicalized HLO module + compile options + jax/jaxlib version +
device topology *before* invoking ``backend_compile``.  We install our
own :class:`CacheInterface` implementation there, so the exact trace the
call path builds — same donation, same shardings, same env-flag
formulation baked in by the sound cache-key contract — is the unit of
persistence, and a warm process reaches steady state with **zero** XLA
compiles.

Layered keying:

- **memory** tier: the in-process program caches (``Executor._jitted``,
  ``Operator._jit_cache``, ``CachedOp._jitted``) keyed by the sound
  contract — mesh_sig + ``STEP_ENV_KEYS`` + plan-wide op-env union.
- **disk** tier: jax's cache key (canonical HLO + compile options +
  jax/jaxlib version + devices).  The env flags are *baked into the
  traced HLO*, so a flag flip changes the traced program and therefore
  the disk key — stale programs cannot be served by construction.
- **environment fingerprint**: every entry embeds a digest of the
  jax/jaxlib versions, backend platform and device topology.  An
  artifact shipped from a mismatched environment quarantines instead of
  deserializing.  Entries sit directly in the cache directory: the
  directory is the caller's to place, and this module never moves it.

Entry format (``*.mxpc``): ``b"MXPC1\\0"`` magic + 16-byte fingerprint
digest + 32-byte SHA-256 of the payload + payload (jax's compressed
``(executable, compile_time)`` blob).  Loads are checksum-validated;
any corruption (truncation, bit rot, foreign fingerprint) moves the file
to ``quarantine/``, counts ``program_cache_errors_total{kind}``, and
falls back to a fresh compile — a poisoned artifact can never take a
run down.

Placement: :func:`resolve_dir` is the one function that decides where
the cache lives.  ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it
stands and wins over ``MXNET_PROGRAM_CACHE_DIR``; with neither set the
cache sits at one fixed path inside the checkout (:data:`DEFAULT_DIR`).

Activation: the program's entry points (``chip_smoke.py``, ``bench.py``,
the ``main`` of each tool, the serving tools among them; a user's own
server script does the same) call :func:`place` before their first
compile.  A process that only imports the library — the test suite, or
``ModelServer`` used as a class — gets a cache only if ``MXNET_PROGRAM_CACHE_DIR`` is set (the compile
sites call :func:`ensure_enabled` lazily on their first miss) or it calls
:func:`enable` itself.  ``MXNET_PROGRAM_CACHE_MAX_BYTES`` (default
4 GiB) bounds the directory with LRU eviction (mtime = recency, bumped
on every hit).  ``MXNET_PROGRAM_CACHE=0`` force-disables everywhere.
Deploy prefill: ``tools/cache_prefill.py`` compiles a model's bucket
ladder + training step into the cache dir once; ship the directory with
the model artifact and every replica restarts warm.
"""
from __future__ import annotations

import atexit
import hashlib
import json
import os
import sys
import threading
import time
from typing import Any, Dict, Optional

from .base import get_env
from . import telemetry as _telemetry

__all__ = ["enable", "disable", "enabled", "ensure_enabled", "stats",
           "note_memory_hit", "fingerprint", "fingerprint_info",
           "cache_dir", "resolve_dir", "place", "DiskProgramCache"]

ENV_DIR = "MXNET_PROGRAM_CACHE_DIR"
JAX_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
#: where the cache lives when no environment variable places it: one fixed
#: path inside the checkout (the path is part of jax's cache key, so a
#: directory that moved would never hit)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")
ENV_MAX_BYTES = "MXNET_PROGRAM_CACHE_MAX_BYTES"
ENV_GATE = "MXNET_PROGRAM_CACHE"

_MAGIC = b"MXPC1\0"
_FP_LEN = 16
_SHA_LEN = 32
_HEADER_LEN = len(_MAGIC) + _FP_LEN + _SHA_LEN
_SUFFIX = ".mxpc"
_QUARANTINE_DIR = "quarantine"
_QUARANTINE_CAP = 64

# Lookup tiers: `memory` = an in-process program-key lookup served from
# the live jit caches (per call site); `disk` / `miss` = an XLA compile
# request served from / missed by the persistent cache (per HLO module —
# one site miss can issue several).  The two granularities are
# documented in docs/observability.md.
_REQS = _telemetry.counter(
    "program_cache_requests_total",
    "Compiled-program lookups by serving tier (memory|disk|miss)",
    ("tier",))
# error paths count even with telemetry disabled (same convention as
# kvstore_frame_errors_total)
_ERRORS = _telemetry.counter(
    "program_cache_errors_total",
    "Cache artifacts rejected at load (truncated|magic|fingerprint|"
    "checksum|io) — rejected entries quarantine and recompile, never "
    "crash", ("kind",))
_EVICTIONS = _telemetry.counter(
    "program_cache_evictions_total",
    "Entries LRU-evicted to stay under MXNET_PROGRAM_CACHE_MAX_BYTES")
_COMPILES = _telemetry.counter(
    "program_cache_compiles_total",
    "Fresh XLA compiles persisted while the program cache was enabled "
    "(zero across a warm restart is the deploy-prefill contract)")
_BYTES = _telemetry.gauge(
    "program_cache_bytes", "Bytes in the program-cache directory on disk")
_ENTRIES = _telemetry.gauge(
    "program_cache_entries", "Entries in the program-cache directory")


def fingerprint_info() -> Dict[str, Any]:
    """Environment facts that must match for an executable to be safe to
    deserialize: jax/jaxlib versions, backend platform and version, and
    the device topology.  (The abstract arg signature and compile options
    are per-program and already part of jax's HLO cache key.)"""
    import jax
    info: Dict[str, Any] = {
        "jax": getattr(jax, "__version__", "?"),
        "jaxlib": "?",
        "platform": "?",
        "device_kind": "?",
        "n_devices": 0,
    }
    try:
        import jaxlib
        info["jaxlib"] = getattr(jaxlib, "version", jaxlib).__version__
    except Exception:
        pass
    try:
        devs = jax.devices()
        info["platform"] = devs[0].platform if devs else "none"
        info["device_kind"] = getattr(devs[0], "device_kind", "?") \
            if devs else "?"
        info["n_devices"] = len(devs)
        info["process_count"] = getattr(jax, "process_count", lambda: 1)()
    except Exception as e:  # backend init failed: still fingerprintable
        info["error"] = str(e)[:200]
    return info


def _digest_of(info: Dict[str, Any]) -> bytes:
    blob = json.dumps(info, sort_keys=True).encode()
    return hashlib.sha256(blob).digest()[:_FP_LEN]


def fingerprint() -> Optional[str]:
    """Hex fingerprint of the active cache's environment (None when
    disabled)."""
    c = _state.cache
    return c.fingerprint_hex if c is not None else None


def cache_dir() -> Optional[str]:
    """The active cache directory (None when disabled)."""
    c = _state.cache
    return c.directory if c is not None else None


class DiskProgramCache:
    """Checksum-validated, LRU-capped on-disk executable cache.

    Implements jax's ``CacheInterface`` contract (``get(key)`` /
    ``put(key, value)``) so it can be installed as the persistent
    compilation cache consulted by ``compile_or_get_cached`` on the jit
    call path.  All failures degrade to a miss: the caller compiles
    fresh and training/serving continues.
    """

    def __init__(self, directory: str, fp_digest: bytes,
                 max_bytes: int) -> None:
        self.directory = directory
        self.fp_digest = fp_digest
        self.fingerprint_hex = fp_digest.hex()
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        # jax's CacheInterface exposes _path; keep parity for any caller
        # that introspects it
        self._path = directory
        self.stats: Dict[str, int] = {
            "disk_hits": 0, "misses": 0, "puts": 0, "errors": 0,
            "evictions": 0,
        }
        os.makedirs(os.path.join(directory, _QUARANTINE_DIR), exist_ok=True)
        self._refresh_usage_locked()

    # -- naming ------------------------------------------------------------
    def _entry_path(self, key: str) -> str:
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in str(key))
        tag = hashlib.sha256(str(key).encode()).hexdigest()[:16]
        return os.path.join(self.directory,
                            "%s-%s%s" % (safe[:96], tag, _SUFFIX))

    def _entries_locked(self):
        """[(path, size, mtime)] for every live entry."""
        out = []
        try:
            with os.scandir(self.directory) as it:
                for de in it:
                    if not de.name.endswith(_SUFFIX) or not de.is_file():
                        continue
                    st = de.stat()
                    out.append((de.path, st.st_size, st.st_mtime))
        except OSError:
            pass
        return out

    def _refresh_usage_locked(self):
        entries = self._entries_locked()
        _BYTES.set(sum(e[1] for e in entries))
        _ENTRIES.set(len(entries))

    # -- error handling ----------------------------------------------------
    def _reject(self, path: str, kind: str) -> None:
        """Quarantine a bad artifact; never raises."""
        self.stats["errors"] += 1
        _ERRORS.labels(kind=kind).inc()
        qdir = os.path.join(self.directory, _QUARANTINE_DIR)
        try:
            os.makedirs(qdir, exist_ok=True)
            held = sorted(
                (de.path for de in os.scandir(qdir) if de.is_file()),
                key=lambda p: os.path.getmtime(p))
            for p in held[:max(0, len(held) - _QUARANTINE_CAP + 1)]:
                os.unlink(p)
            os.replace(path,
                       os.path.join(qdir, os.path.basename(path)))
        except OSError:
            try:
                os.unlink(path)
            except OSError:
                pass

    # -- CacheInterface ----------------------------------------------------
    def get(self, key: str) -> Optional[bytes]:
        path = self._entry_path(key)
        with self._lock:
            try:
                with open(path, "rb") as f:
                    raw = f.read()
            except FileNotFoundError:
                self.stats["misses"] += 1
                _REQS.labels(tier="miss").inc()
                return None
            except OSError:
                self.stats["misses"] += 1
                _ERRORS.labels(kind="io").inc()
                self.stats["errors"] += 1
                _REQS.labels(tier="miss").inc()
                return None
            if len(raw) < _HEADER_LEN:
                self._reject(path, "truncated")
            elif not raw.startswith(_MAGIC):
                self._reject(path, "magic")
            elif raw[len(_MAGIC):len(_MAGIC) + _FP_LEN] != self.fp_digest:
                self._reject(path, "fingerprint")
            else:
                payload = raw[_HEADER_LEN:]
                want = raw[len(_MAGIC) + _FP_LEN:_HEADER_LEN]
                if hashlib.sha256(payload).digest() != want:
                    self._reject(path, "checksum")
                else:
                    self.stats["disk_hits"] += 1
                    _REQS.labels(tier="disk").inc()
                    try:
                        os.utime(path)  # LRU recency
                    except OSError:
                        pass
                    return payload
            self.stats["misses"] += 1
            _REQS.labels(tier="miss").inc()
            return None

    def put(self, key: str, value: bytes) -> None:
        path = self._entry_path(key)
        blob = (_MAGIC + self.fp_digest
                + hashlib.sha256(value).digest() + value)
        tmp = "%s.tmp.%d.%x" % (path, os.getpid(),
                                threading.get_ident() & 0xffff)
        with self._lock:
            try:
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
            except OSError:
                self.stats["errors"] += 1
                _ERRORS.labels(kind="io").inc()
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                return
            self.stats["puts"] += 1
            _COMPILES.inc()
            self._evict_locked()
            self._refresh_usage_locked()

    def _evict_locked(self) -> None:
        if self.max_bytes <= 0:
            return
        entries = self._entries_locked()
        total = sum(e[1] for e in entries)
        if total <= self.max_bytes:
            return
        for path, size, _mtime in sorted(entries, key=lambda e: e[2]):
            if total <= self.max_bytes:
                break
            try:
                os.unlink(path)
            except OSError:
                continue
            total -= size
            self.stats["evictions"] += 1
            _EVICTIONS.inc()


# ---------------------------------------------------------------------------
# module state + jax call-path installation
# ---------------------------------------------------------------------------
class _State:
    def __init__(self) -> None:
        self.cache: Optional[DiskProgramCache] = None
        self.resolved = False          # env config read once
        self.prior_dir: Optional[str] = None  # jax's dir before enable()
        self.info: Optional[Dict[str, Any]] = None
        self.memory_hits = 0
        self.atexit_registered = False


_state = _State()
_lock = threading.Lock()


def enabled() -> bool:
    return _state.cache is not None


def put_count() -> Optional[int]:
    """Fresh-compile (put) count so far, or None when disabled.  Cheap
    enough for per-first-call deltas: the op-jit wrapper compares it
    across a first invocation to label the trace span ``XLA::Compile``
    (a real compile happened) vs ``XLA::Restore`` (every program the
    call needed came off disk)."""
    c = _state.cache
    return c.stats["puts"] if c is not None else None


def note_memory_hit() -> None:
    """An in-process program-key lookup was served from a live jit cache
    (Executor._jitted / Operator._jit_cache / CachedOp._jitted).  Called
    from the compile sites on their hit path; gated by
    ``telemetry.enabled`` there, so steady state pays one attribute
    check."""
    _state.memory_hits += 1
    _REQS.labels(tier="memory").inc()


def resolve_dir() -> str:
    """THE placement decision, made in one place: the directory compiled
    programs persist in.  ``JAX_COMPILATION_CACHE_DIR`` is used exactly as
    given (no sub-directory) and wins over ``MXNET_PROGRAM_CACHE_DIR``,
    with a line on stderr saying so; with neither set it is
    :data:`DEFAULT_DIR`, fixed inside the checkout."""
    jax_dir = os.environ.get(JAX_ENV_DIR)
    own_dir = os.environ.get(ENV_DIR)
    if jax_dir:
        if own_dir and os.path.abspath(own_dir) != os.path.abspath(jax_dir):
            sys.stderr.write(
                "program_cache: %s=%s wins; %s=%s is ignored\n"
                % (JAX_ENV_DIR, jax_dir, ENV_DIR, own_dir))
        return jax_dir
    return own_dir or DEFAULT_DIR


def place() -> Optional[str]:
    """Switch the persistent cache on at :func:`resolve_dir`.  Entry
    points call this before their first compile; returns the directory
    in use (None when ``MXNET_PROGRAM_CACHE=0``).  Idempotent."""
    if not get_env(ENV_GATE, True, bool):
        return None
    cache = enable(resolve_dir())
    return cache.directory if cache is not None else None


def ensure_enabled() -> bool:
    """The library's lazy switch: enable the cache if
    ``MXNET_PROGRAM_CACHE_DIR`` asks for one (at :func:`resolve_dir`, so
    jax's own variable still wins).  Called from every whole-graph
    compile site on its miss path — i.e. right before jax is about to
    trace+compile, so touching the backend here is safe.  Without that
    variable an importing process gets no cache from this module."""
    if _state.resolved:
        return _state.cache is not None
    with _lock:
        if _state.resolved:
            return _state.cache is not None
        if not os.environ.get(ENV_DIR) or not get_env(ENV_GATE, True, bool):
            _state.resolved = True
            return False
    # enable() takes _lock itself and sets resolved
    return enable(resolve_dir()) is not None


def _install_into_jax(cache: DiskProgramCache) -> None:
    """Point jax's persistent compilation cache at ``cache``: replace the
    module-level cache object in ``jax._src.compilation_cache`` so every
    ``compile_or_get_cached`` lookup flows through our checksum/
    quarantine/LRU layer.  One behaviour: if those internals move, this
    raises and the move is repaired here."""
    import jax
    from jax._src import compilation_cache as _cc
    jax.config.update("jax_enable_compilation_cache", True)
    if jax.config.jax_compilation_cache_dir != cache.directory:
        # never reached when JAX_COMPILATION_CACHE_DIR placed the cache:
        # jax read that variable itself and the directory is the same
        jax.config.update("jax_compilation_cache_dir", cache.directory)
    # persist everything: whole-step programs on CPU can compile in
    # <1s, and tiny glue programs (broadcasts, transfers) must load too
    # for the zero-compile contract to hold
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with _cc._cache_initialized_mutex:
        _cc._cache = cache
        _cc._cache_initialized = True
        # re-evaluate the one-shot "is the cache used" verdict in
        # case compiles already happened before enable()
        _cc._cache_checked = False
        _cc._cache_used = False


def _uninstall_from_jax(restore_dir: Optional[str]) -> None:
    import jax
    from jax._src import compilation_cache as _cc
    with _cc._cache_initialized_mutex:
        _cc._cache = None
        _cc._cache_initialized = False
        _cc._cache_checked = False
        _cc._cache_used = False
    if jax.config.jax_compilation_cache_dir != restore_dir:
        jax.config.update("jax_compilation_cache_dir", restore_dir)


def enable(root: Optional[str] = None,
           max_bytes: Optional[int] = None) -> Optional[DiskProgramCache]:
    """Enable the persistent program cache in ``root`` (default:
    :func:`resolve_dir`).  Idempotent: returns the live cache if already
    enabled.  Returns None when the directory is unusable."""
    with _lock:
        if _state.cache is not None:
            _state.resolved = True
            return _state.cache
        root = root or resolve_dir()
        _state.resolved = True
        if max_bytes is None:
            max_bytes = get_env(ENV_MAX_BYTES, 4 * 1024 ** 3, int)
        info = fingerprint_info()
        digest = _digest_of(info)
        try:
            os.makedirs(root, exist_ok=True)
            manifest = os.path.join(root, "manifest.json")
            if not os.path.exists(manifest):
                tmp = manifest + ".tmp.%d" % os.getpid()
                with open(tmp, "w", encoding="utf-8") as f:
                    json.dump({"fingerprint": digest.hex(), "info": info,
                               "created": round(time.time(), 3)}, f,
                              indent=1, sort_keys=True)
                os.replace(tmp, manifest)
            cache = DiskProgramCache(root, digest, int(max_bytes))
        except OSError:
            # unusable directory: stay disabled rather than crash
            _ERRORS.labels(kind="io").inc()
            return None
        import jax
        _state.prior_dir = jax.config.jax_compilation_cache_dir
        _install_into_jax(cache)
        _state.cache = cache
        _state.info = info
        if not _state.atexit_registered:
            _state.atexit_registered = True
            atexit.register(_log_summary)
    try:
        from . import runlog as _runlog
        _runlog.event("program_cache_start", dir=root,
                      fingerprint=digest.hex(),
                      max_bytes=int(max_bytes), info=info)
    except Exception:
        pass
    return _state.cache


def disable() -> None:
    """Detach from jax and drop the cache object (artifacts stay on
    disk).  Idempotent; also resets the env resolution so a later
    :func:`ensure_enabled` re-reads the environment (test isolation)."""
    with _lock:
        if _state.cache is None:
            _state.resolved = False
            _state.memory_hits = 0
            return
        _log_summary()
        _uninstall_from_jax(_state.prior_dir)
        _state.cache = None
        _state.prior_dir = None
        _state.info = None
        _state.resolved = False
        _state.memory_hits = 0


def stats() -> Dict[str, Any]:
    """JSON-able cache stats block (served on /statusz, logged by the
    runlog shutdown hook, embedded in bench results)."""
    c = _state.cache
    out: Dict[str, Any] = {
        "enabled": c is not None,
        "memory_hits": _state.memory_hits,
    }
    if c is None:
        return out
    entries = []
    try:
        with os.scandir(c.directory) as it:
            entries = [de.stat().st_size for de in it
                       if de.name.endswith(_SUFFIX) and de.is_file()]
    except OSError:
        pass
    out.update(c.stats)
    out.update({
        "dir": c.directory, "fingerprint": c.fingerprint_hex,
        "max_bytes": c.max_bytes,
        "bytes": sum(entries), "entries": len(entries),
    })
    return out


def _log_summary() -> None:
    """Shutdown hook: durable hit/miss/evict summary in the run ledger."""
    if _state.cache is None:
        return
    try:
        from . import runlog as _runlog
        _runlog.event("program_cache_summary", **stats())
    except Exception:
        pass
