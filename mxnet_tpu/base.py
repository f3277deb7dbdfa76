"""Foundational utilities for the mxnet_tpu framework.

TPU-native re-design of the reference's dmlc-core foundations (logging/CHECK
macros, ``dmlc::Parameter`` typed reflection, ``dmlc::GetEnv`` config, and the
error layer behind ``MXGetLastError`` in ``src/c_api/c_api_error.cc``).  There
is no C ABI waist here: the Python frontend talks directly to the JAX/XLA
runtime, so the "C API error ring" becomes a plain exception hierarchy.
"""
from __future__ import annotations

import os
import functools
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import numpy as np

__all__ = [
    "MXNetError",
    "NotSupportedForSparseNDArray",
    "get_env",
    "AttrDict",
    "Registry",
    "string_types",
    "numeric_types",
    "integer_types",
    "classproperty",
]

string_types = (str,)
numeric_types = (float, int, np.generic)
integer_types = (int, np.integer)


# Set by mxnet_tpu.tracing at import: called with each constructed MXNetError
# so the flight recorder can dump its ring for post-mortem context.  Must
# never interfere with raising the error itself.
_ERROR_HOOK: Optional[Callable] = None


class MXNetError(RuntimeError):
    """Top-level framework error (parity with ``mxnet.base.MXNetError``)."""

    def __init__(self, *args):
        super().__init__(*args)
        if _ERROR_HOOK is not None:
            try:
                _ERROR_HOOK(self)
            except Exception:
                pass


class NotSupportedForSparseNDArray(MXNetError):
    def __init__(self, function, alias, *args):
        extra = " ".join(repr(a) for a in args)
        super().__init__(
            "Function {} (alias {}) is not supported for SparseNDArray {}".format(
                function, alias, extra))


def get_env(name: str, default: Any = None, dtype: type = str) -> Any:
    """Typed environment config, the analog of ``dmlc::GetEnv``.

    The reference reads ~100 env knobs (SURVEY.md §5.6); we keep the same
    mechanism so e.g. ``MXNET_ENGINE_TYPE=NaiveEngine`` still works.
    """
    val = os.environ.get(name)
    if val is None:
        return default
    if dtype is bool:
        return val.lower() not in ("0", "false", "off", "")
    return dtype(val)


class AttrDict(dict):
    """A hashable, frozen-after-construction dict of op attributes.

    Op attributes must be hashable so that a ``jax.jit`` compile cache can be
    keyed on ``(op_name, attrs, input shapes/dtypes)`` — the TPU analog of the
    reference's per-op parameter structs (``dmlc::Parameter``) + cuDNN algo
    registry cache.
    Values should be scalars / strings / tuples only.
    """

    def __hash__(self):  # type: ignore[override]
        return hash(tuple(sorted(self.items())))

    def __setattr__(self, k, v):
        raise AttributeError("AttrDict is read-only")

    def __getattr__(self, k):
        try:
            return self[k]
        except KeyError as e:
            raise AttributeError(k) from e


class Registry:
    """Simple name → object registry with alias support.

    Replaces the reference's DMLC registries (``DMLC_REGISTRY_ENABLE`` used for
    ops, data iterators, optimizers, initializers, metrics).
    """

    def __init__(self, kind: str):
        self.kind = kind
        self._map: Dict[str, Any] = {}

    def register(self, name: Optional[str] = None, obj: Any = None, *,
                 aliases: Iterable[str] = ()):  # decorator or direct
        def _do(o, nm):
            key = nm.lower()
            self._map[key] = o
            for a in aliases:
                self._map[a.lower()] = o
            return o

        if obj is not None:
            return _do(obj, name or getattr(obj, "__name__", None))
        def deco(o):
            return _do(o, name or getattr(o, "__name__", None))
        return deco

    def get(self, name: str) -> Any:
        key = name.lower()
        if key not in self._map:
            raise MXNetError(
                "Cannot find %s '%s' in registry. Available: %s"
                % (self.kind, name, sorted(self._map)[:50]))
        return self._map[key]

    def find(self, name: str) -> Optional[Any]:
        return self._map.get(name.lower())

    def __contains__(self, name):
        return name.lower() in self._map

    def list(self):
        return sorted(self._map)


class classproperty:
    def __init__(self, f):
        self.f = f

    def __get__(self, obj, owner):
        return self.f(owner)


def c_array(ctype, values):  # pragma: no cover - legacy-compat shim
    """Kept for API-shape parity with ``mxnet.base``; no ctypes layer exists."""
    return list(values)


@functools.lru_cache(maxsize=None)
def _np_dtype(name_or_dtype) -> np.dtype:
    return np.dtype(name_or_dtype)


@functools.lru_cache(maxsize=None)
def dtype_name(dtype) -> str:
    """``np.dtype(dtype).name``, kept by the dtype asked of: numpy builds the
    name in Python on every read, and a train step asks it of every leaf
    (``amp.is_low_precision``, ``Executor._dtype_sig``)."""
    return np.dtype(dtype).name
