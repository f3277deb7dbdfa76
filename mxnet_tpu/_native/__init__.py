"""Native runtime loader: builds (once) and loads the C++ shared library.

The C++ core (``src/engine.cc`` threaded dependency engine,
``src/recordio.cc`` RecordIO) is the native half of the runtime (SURVEY.md
N1/N14/N17).  Built lazily with ``make`` from the committed sources on
first use and cached beside this file; a library older than any source it
is built from is rebuilt, never loaded.  With no toolchain, or with
``MXNET_NO_NATIVE=1``, the Python fallbacks take over (``lib() -> None``)
and :func:`status` says why.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_LIB = None
_TRIED = False
_STATUS = "not loaded yet"
_LOCK = threading.Lock()

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libmxnet_tpu_native.so")
_SRC = os.path.normpath(os.path.join(_HERE, "..", "..", "src"))


def _sources():
    """Everything under ``src/`` the library is built from: the C++
    sources, the headers beside them, and the Makefile itself."""
    return [os.path.join(_SRC, f) for f in sorted(os.listdir(_SRC))
            if f.endswith((".cc", ".h")) or f == "Makefile"]


def _needs_build() -> bool:
    if not os.path.exists(_SO):
        return True
    so_m = os.path.getmtime(_SO)
    return any(os.path.getmtime(p) > so_m for p in _sources())


def _build() -> None:
    """``make`` the engine+RecordIO library only (the predict and C-API
    libraries embed CPython and are built by their own consumers), into a
    private name first so a concurrent loader never maps a half-written
    file."""
    tmp = "%s.build.%d" % (_SO, os.getpid())
    try:
        subprocess.run(["make", "-C", _SRC, "OUT=" + tmp, tmp],
                       check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    # engine
    lib.MXNativeEngineCreate.restype = c.c_void_p
    lib.MXNativeEngineCreate.argtypes = [c.c_int]
    lib.MXNativeEngineFree.argtypes = [c.c_void_p]
    lib.MXNativeEngineNewVar.restype = c.c_void_p
    lib.MXNativeEngineNewVar.argtypes = [c.c_void_p]
    lib.MXNativeEngineDeleteVar.argtypes = [c.c_void_p, c.c_void_p]
    lib.MXNativeEnginePush.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p,
        c.POINTER(c.c_void_p), c.c_int,
        c.POINTER(c.c_void_p), c.c_int, c.c_int]
    lib.MXNativeEngineWaitForVar.restype = c.c_int64
    lib.MXNativeEngineWaitForVar.argtypes = [c.c_void_p, c.c_void_p]
    lib.MXNativeEngineWaitForAll.argtypes = [c.c_void_p]
    # recordio
    lib.MXNativeRecordIOGetLastError.restype = c.c_char_p
    lib.MXNativeRecordIOWriterCreate.restype = c.c_void_p
    lib.MXNativeRecordIOWriterCreate.argtypes = [c.c_char_p]
    lib.MXNativeRecordIOWriterWrite.restype = c.c_int
    lib.MXNativeRecordIOWriterWrite.argtypes = [c.c_void_p, c.c_char_p,
                                                c.c_uint64]
    lib.MXNativeRecordIOWriterTell.restype = c.c_int64
    lib.MXNativeRecordIOWriterTell.argtypes = [c.c_void_p]
    lib.MXNativeRecordIOWriterClose.argtypes = [c.c_void_p]
    lib.MXNativeRecordIOReaderCreate.restype = c.c_void_p
    lib.MXNativeRecordIOReaderCreate.argtypes = [c.c_char_p]
    lib.MXNativeRecordIOReaderRead.restype = c.c_int
    # out pointer declared void* so ctypes doesn't NUL-truncate the buffer
    lib.MXNativeRecordIOReaderRead.argtypes = [
        c.c_void_p, ctypes.POINTER(c.c_void_p), ctypes.POINTER(c.c_uint64)]
    lib.MXNativeRecordIOReaderSeek.restype = c.c_int
    lib.MXNativeRecordIOReaderSeek.argtypes = [c.c_void_p, c.c_uint64]
    lib.MXNativeRecordIOReaderTell.restype = c.c_int64
    lib.MXNativeRecordIOReaderTell.argtypes = [c.c_void_p]
    lib.MXNativeRecordIOReaderClose.argtypes = [c.c_void_p]


def lib():
    """The loaded native library, or None when unavailable."""
    global _LIB, _TRIED, _STATUS
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("MXNET_NO_NATIVE", "") in ("1", "true"):
            _STATUS = "python fallback (MXNET_NO_NATIVE)"
            return None
        try:
            built = _needs_build()
            if built:
                _build()
            loaded = ctypes.CDLL(_SO)
            _declare(loaded)
            _LIB = loaded
            _STATUS = ("native (built from src/ by this process)" if built
                       else "native (up-to-date library found)")
        except (OSError, subprocess.SubprocessError, AttributeError) as e:
            # no toolchain, a failed compile, or a library missing a
            # symbol the sources declare: the Python fallbacks take over
            err = getattr(e, "stderr", None) or str(e)
            if isinstance(err, bytes):
                err = err.decode("utf-8", "replace")
            _STATUS = "python fallback (%s: %s)" % (
                type(e).__name__, err.strip()[-200:])
        return _LIB


def status() -> str:
    """One line on which runtime is active and why: ``native (...)`` or
    ``python fallback (...)``.  Tries the load if nothing has yet."""
    lib()
    return _STATUS
