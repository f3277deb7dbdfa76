"""Operator registry: the TPU-native replacement for the NNVM op registry.

Reference analog: ``NNVM_REGISTER_OP`` sites across ``src/operator/**`` with
typed attributes (``include/mxnet/op_attr_types.h``): ``FCompute``,
``FInferShape/Type``, ``FGradient``, resource requests.  TPU-native design:

- Each op is ONE pure, jittable JAX function ``fn(attrs, *inputs) -> outputs``.
  Forward AND backward come from this single definition: gradients are derived
  with ``jax.vjp`` (the analog of FGradient), and shape/type inference is
  ``jax.eval_shape`` (the analog of FInferShape/FInferType) — one source of
  truth instead of four hand-written attribute functions per op.
- ``attrs`` is a hashable :class:`~mxnet_tpu.base.AttrDict` parsed by a typed
  parameter spec (the ``dmlc::Parameter`` analog), so compiled executables can
  be cached on ``(op, attrs)`` — XLA then caches per input shape under `jit`.
- Ops needing randomness declare ``needs_rng``; the dispatch layer threads an
  explicit threefry key (SURVEY.md §7.3 "RNG parity").

Eager dispatch cost (SURVEY.md §7.3): every op call goes through a
``jax.jit``-wrapped callable cached on ``(name, attrs)``; XLA executable reuse
across calls with equal shapes makes the imperative path cheap, and fused
multi-op regions come from CachedOp/Executor jitting whole graphs.
"""
from __future__ import annotations

import ast
import functools
import os
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from ..base import AttrDict, MXNetError
from .. import atlas as _atlas
from .. import profiler as _profiler
from .. import telemetry as _telemetry
from .. import program_cache as _program_cache

__all__ = ["Operator", "register", "get_op", "list_ops", "apply_op",
           "param", "OPS"]

OPS: Dict[str, "Operator"] = {}

# jit-cache observability: recompiles are the classic silent TPU perf bug
# (a drifting shape or env flag turns every step into a compile).  Hit/miss
# counts and the compile-duration histogram make them visible in a /metrics
# scrape; the XLA::Compile trace span makes them visible in Perfetto.
_JIT_HITS = _telemetry.counter(
    "op_jit_cache_hits_total",
    "Operator jit-cache lookups served by an existing entry", ("op",))
_JIT_MISSES = _telemetry.counter(
    "op_jit_cache_misses_total",
    "Operator jit-cache lookups that built a new entry", ("op",))
_JIT_ENTRIES = _telemetry.gauge(
    "op_jit_cache_entries", "Live operator jit-cache entries (all ops)")
_COMPILE_TIME = _telemetry.histogram(
    "op_compile_seconds",
    "First-invocation duration of a fresh jit-cache entry (where jax "
    "traces and XLA compiles — jax.jit construction itself is lazy)",
    ("op",))


# --------------------------------------------------------------------------
# typed parameter spec — the dmlc::Parameter analog
# --------------------------------------------------------------------------
class param:
    """One typed op parameter: ``param(type, default)``.

    type is one of: int, float, bool, str, 'shape' (tuple of ints),
    'dtype' (numpy dtype name).  Values arriving as strings (reference C-API
    convention; also what Symbol JSON stores) are coerced.
    """

    def __init__(self, ptype, default=None, required=False):
        self.ptype = ptype
        self.default = default
        self.required = required

    def coerce(self, v):
        t = self.ptype
        if v is None:
            return None
        if t == "shape":
            if isinstance(v, str):
                v = ast.literal_eval(v)
            if isinstance(v, (int, np.integer)):
                return (int(v),)
            return tuple(int(x) for x in v)
        if t == "floats":
            if isinstance(v, str):
                v = ast.literal_eval(v)
            if isinstance(v, (int, float, np.floating, np.integer)):
                return (float(v),)
            return tuple(float(x) for x in v)
        if t == "dtype":
            if v in (None, "None"):
                return None
            return np.dtype(v).name
        if t is bool:
            if isinstance(v, str):
                return v.lower() in ("1", "true", "yes", "on")
            return bool(v)
        if t is int:
            return int(v)
        if t is float:
            return float(v)
        if t is str:
            return str(v)
        if isinstance(t, (list, tuple)):  # enum
            v = str(v)
            if v not in t:
                raise MXNetError("invalid enum value %r (expected one of %s)" % (v, t))
            return v
        return v


class Operator:
    """A registered operator."""

    def __init__(self, name: str, fn: Callable, *,
                 params: Optional[Dict[str, param]] = None,
                 nin: Optional[int] = None, nout: Any = 1,
                 needs_rng: bool = False,
                 train_aware: bool = False,
                 aux_writeback: Optional[Dict[int, int]] = None,
                 arg_names: Optional[Sequence[str]] = None,
                 aliases: Sequence[str] = (),
                 mutate_inputs: Sequence[int] = (),
                 env_keys: Sequence[str] = (),
                 doc: str = ""):
        self.name = name
        self.fn = fn
        self.params = params or {}
        self.nin = nin          # None = from arg_names; -1 = variadic
        self.nout = nout        # int or callable(attrs)->int
        self.needs_rng = needs_rng
        # train_aware ops receive attrs['__train__'] from the dispatch layer
        # (the analog of the reference's OpContext.is_train, op_attr_types.h).
        self.train_aware = train_aware
        # {output_idx: input_idx}: the dispatch layer writes these outputs
        # back into the given inputs — how BatchNorm's moving-stat mutation
        # and optimizer-state updates are expressed functionally on TPU.
        self.aux_writeback = aux_writeback or {}
        # user-visible output count (reference FNumVisibleOutputs): int,
        # callable(attrs)->int, or None = all outputs visible.
        self.visible = None
        # indices of auxiliary inputs (reference FListAuxiliaryStates —
        # BatchNorm's moving stats): not gradient targets, not arguments.
        # A negative index counts from the last input, as in aux_writeback
        # (SparseMoE's load, behind a list of inputs its scoring decides).
        self.aux_inputs: Tuple[int, ...] = ()
        # partial shape inference hook: fn(attrs, in_shapes) -> in_shapes
        # with None entries filled (the FInferShape analog for inferring
        # parameter shapes from data shape, e.g. conv weights).
        self.shape_hint = None
        self.arg_names = list(arg_names) if arg_names else None
        self.aliases = tuple(aliases)
        self.mutate_inputs = tuple(mutate_inputs)  # e.g. optimizer update ops
        # env vars the op's fn reads at TRACE time (formulation flags like
        # MXNET_TPU_PALLAS_CONV).  Their current values join the jit-cache
        # key, so toggling a flag mid-process can never serve a stale
        # executable compiled under the old value.
        self.env_keys = tuple(env_keys)
        self.doc = doc
        self._jit_cache: Dict[Any, Callable] = {}

    # ---- attrs ----------------------------------------------------------
    def parse_attrs(self, kwargs: Dict[str, Any]) -> AttrDict:
        out = {}
        for k, spec in self.params.items():
            if k in kwargs:
                out[k] = spec.coerce(kwargs.pop(k))
            elif spec.required:
                raise MXNetError("op %s: required param %r missing" % (self.name, k))
            else:
                out[k] = spec.default
        # pass through unknown attrs untouched (reference tolerates extra
        # attrs like __layout__ on symbols); keep only hashable ones
        for k, v in list(kwargs.items()):
            if k.startswith("__") or k in ("name", "ctx", "out"):
                continue
            out[k] = tuple(v) if isinstance(v, list) else v
        return AttrDict(out)

    def num_outputs(self, attrs: AttrDict) -> int:
        return self.nout(attrs) if callable(self.nout) else self.nout

    def get_aux_writeback(self, attrs: AttrDict) -> Dict[int, int]:
        """aux_writeback may be a static dict or callable(attrs)->dict
        (ops like Custom whose aux count depends on attrs)."""
        wb = self.aux_writeback
        return wb(attrs) if callable(wb) else wb

    def num_visible_outputs(self, attrs: AttrDict) -> int:
        if self.visible is None:
            return self.num_outputs(attrs)
        return self.visible(attrs) if callable(self.visible) else self.visible

    # ---- execution ------------------------------------------------------
    def compiled(self, attrs: AttrDict) -> Callable:
        """jit-compiled entry for these attrs (shape-specialized by XLA).

        Cache key is ``attrs`` alone, or ``(attrs, env-values)`` when the
        op declares ``env_keys`` — trace-time formulation flags then take
        effect immediately instead of being baked into a stale executable.

        Observability: hit/miss counters and a per-op compile-duration
        histogram when telemetry is enabled.  jax.jit is lazy — tracing
        and XLA compilation happen at the first *invocation* — so a fresh
        entry is a self-replacing wrapper that times that first call and
        records an ``XLA::Compile`` span, then swaps in the raw jitted
        callable: steady state pays nothing beyond the cache lookup.
        """
        key = attrs if not self.env_keys else (
            attrs, tuple(os.environ.get(k) for k in self.env_keys))
        c = self._jit_cache.get(key)
        if c is not None:
            if _telemetry.enabled:
                _JIT_HITS.labels(op=self.name).inc()
                _program_cache.note_memory_hit()
            return c
        if _telemetry.enabled:
            _JIT_MISSES.labels(op=self.name).inc()
        _program_cache.ensure_enabled()
        fn = self.fn
        # Scope choke point: per-op jitted programs carry an anonymous
        # atlas scope ("<OpType>:~" — no graph node here) so single-op
        # lowerings attribute the same way fused plans do.
        scope = _atlas.scope_name(self.name)

        def _scoped(*arrays):
            with jax.named_scope(scope):
                return fn(attrs, *arrays)

        jfn = jax.jit(_scoped)
        name, cache = self.name, self._jit_cache

        def _first_call(*arrays):
            begin = _profiler._now_us()
            t0 = time.perf_counter()
            puts0 = _program_cache.put_count()
            try:
                return jfn(*arrays)
            finally:
                cache[key] = jfn
                if _telemetry.enabled:
                    _COMPILE_TIME.labels(op=name).observe(
                        time.perf_counter() - t0)
                # warm restart visibility: when the persistent program
                # cache served every module this call needed (no put),
                # the span is a restore, not a compile — zero
                # XLA::Compile spans is the deploy-prefill contract
                restored = (puts0 is not None
                            and _program_cache.put_count() == puts0)
                _profiler.record_span(
                    "XLA::%s %s" % ("Restore" if restored else "Compile",
                                    name),
                    begin, _profiler._now_us(), "compile")

        self._jit_cache[key] = _first_call
        if _telemetry.enabled:
            _JIT_ENTRIES.inc()
        return _first_call

    def __call__(self, attrs: AttrDict, *arrays):
        return self.compiled(attrs)(*arrays)

    def abstract_eval(self, attrs: AttrDict, *avals):
        """Shape/dtype inference = jax.eval_shape (replaces FInferShape/Type)."""
        fn = self.fn
        return jax.eval_shape(lambda *xs: fn(attrs, *xs), *avals)

    def __repr__(self):
        return "<Operator %s>" % self.name


def register(name: str, *, params=None, nin=None, nout=1, needs_rng=False,
             train_aware=False, aux_writeback=None, visible=None,
             arg_names=None, aliases=(), mutate_inputs=(), env_keys=(),
             doc=""):
    """Decorator: register a pure JAX function as an operator."""

    def deco(fn):
        op = Operator(name, fn, params=params, nin=nin, nout=nout,
                      needs_rng=needs_rng, train_aware=train_aware,
                      aux_writeback=aux_writeback, arg_names=arg_names,
                      aliases=aliases, mutate_inputs=mutate_inputs,
                      env_keys=env_keys,
                      doc=doc or (fn.__doc__ or ""))
        op.visible = visible
        OPS[name] = op
        for a in aliases:
            OPS[a] = op
        return fn

    return deco


def get_op(name: str) -> Operator:
    op = OPS.get(name)
    if op is None:
        raise MXNetError("Operator %r is not registered (have %d ops)"
                         % (name, len(OPS)))
    return op


def list_ops():
    return sorted(OPS)


def apply_op(name: str, *arrays, **kwargs):
    """Low-level functional invoke: parse attrs, run, return raw jax arrays."""
    op = get_op(name)
    attrs = op.parse_attrs(dict(kwargs))
    return op(attrs, *arrays)
