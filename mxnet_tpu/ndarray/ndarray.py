"""NDArray: the imperative tensor, backed by a jax.Array on TPU.

Reference analog: ``include/mxnet/ndarray.h:82-1001`` + ``src/ndarray/
ndarray.cc`` (async ref-counted chunk, engine-scheduled ops) and the Python
face ``python/mxnet/ndarray/ndarray.py``.

TPU-native design: the "chunk" is a ``jax.Array`` (PjRt buffer).  Asynchrony
is native — JAX dispatch is async and per-buffer ordering is maintained by the
runtime, so the reference's engine-var-per-chunk machinery maps onto PjRt
futures: ``wait_to_read`` = ``block_until_ready``.  Mutation (``x += y``,
``x[:] = v``, optimizer updates) swaps the underlying buffer — functionally
pure for XLA, in-place in API semantics.  Op dispatch goes through
:func:`invoke`, the analog of ``Imperative::Invoke`` →
``MXImperativeInvokeEx`` (``src/c_api/c_api_ndarray.cc:132``).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError, AttrDict, numeric_types, integer_types
from ..context import Context, current_context, cpu
from ..ops.registry import get_op, Operator
from .. import autograd as _autograd
from .. import random as _random

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange",
           "invoke", "concatenate", "save", "load", "imperative_invoke",
           "waitall", "moveaxis", "onehot_encode"]

class NDArray:
    """An imperative, mutable-by-buffer-swap tensor on a device."""

    __slots__ = ("_data", "_ctx", "_grad", "_grad_req", "_ag_leaf",
                 "_ag_entry", "__weakref__")

    def __init__(self, data: jax.Array, ctx: Optional[Context] = None):
        self._data = data
        self._ctx = ctx if ctx is not None else current_context()
        self._grad = None
        self._grad_req = "null"
        self._ag_leaf = False
        self._ag_entry = None

    # ---- basic properties ----------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._data.shape)

    @property
    def dtype(self):
        d = self._data.dtype
        if isinstance(d, np.dtype):     # a jax array's: what np.dtype(name)
            return d                    # would give, without building a name
        return np.dtype(d.name if hasattr(d, "name") else d)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def context(self) -> Context:
        return self._ctx

    ctx = context

    @property
    def stype(self) -> str:
        return "default"

    @property
    def T(self) -> "NDArray":
        return invoke("transpose", [self])

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._grad

    @property
    def dt_data(self):
        return self._data

    # ---- sync / host transfer ------------------------------------------
    def wait_to_read(self):
        """Block until pending writes complete (ref: NDArray::WaitToRead);
        re-raises async device errors here, matching the reference's
        exception-at-sync-point guarantee (SURVEY.md §5.2)."""
        self._data.block_until_ready()
        return self

    wait_to_write = wait_to_read

    def asnumpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        return "\n%s\n<NDArray %s @%s>" % (
            self.asnumpy(), "x".join(map(str, self.shape)), self._ctx)

    # ---- conversion -----------------------------------------------------
    def astype(self, dtype, copy=True) -> "NDArray":
        if not copy and np.dtype(dtype) == self.dtype:
            return self
        return NDArray(self._data.astype(np.dtype(dtype)), self._ctx)

    def copy(self) -> "NDArray":
        return NDArray(jnp.array(self._data), self._ctx)

    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        # device_put/astype return self._data UNCHANGED when device and
        # dtype already match — a genuine copy is required here, or the
        # "copy" aliases a buffer the fused step may later donate (and
        # XLA deletes donated buffers)
        if isinstance(other, Context):
            data = jax.device_put(self._data, other.jax_device)
            if data is self._data:
                data = jnp.array(data)
            return NDArray(data, other)
        data = jax.device_put(self._data, other._ctx.jax_device) \
            .astype(other._data.dtype)
        if data is self._data:
            data = jnp.array(data)
        other._data = data
        return other

    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    def as_in_ctx(self, ctx):
        return self.as_in_context(ctx)

    def detach(self) -> "NDArray":
        out = NDArray(self._data, self._ctx)
        return out

    def attach_grad(self, grad_req="write", stype=None):
        """Allocate a gradient buffer (ref: ndarray.py attach_grad →
        MarkVariables)."""
        grad = NDArray(jnp.zeros_like(self._data), self._ctx)
        _autograd.mark_variables([self], [grad], grad_req)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _autograd.backward([self], [out_grad] if out_grad is not None else None,
                           retain_graph=retain_graph, train_mode=train_mode)

    # ---- shape ops (method forms) --------------------------------------
    def reshape(self, *shape, **kwargs) -> "NDArray":
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        if not shape and "shape" in kwargs:
            shape = tuple(kwargs["shape"])
        return invoke("Reshape", [self], {"shape": shape,
                                          "reverse": kwargs.get("reverse", False)})

    def reshape_like(self, other) -> "NDArray":
        return invoke("reshape_like", [self, other])

    def expand_dims(self, axis) -> "NDArray":
        return invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None) -> "NDArray":
        return invoke("squeeze", [self], {"axis": axis})

    def transpose(self, *axes) -> "NDArray":
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return invoke("transpose", [self], {"axes": axes})

    def swapaxes(self, dim1, dim2) -> "NDArray":
        return invoke("SwapAxis", [self], {"dim1": dim1, "dim2": dim2})

    def flatten(self) -> "NDArray":
        return invoke("Flatten", [self])

    def broadcast_to(self, shape) -> "NDArray":
        cur = (1,) * (len(shape) - self.ndim) + self.shape
        return invoke("broadcast_to", [self.reshape(cur)], {"shape": shape})

    def broadcast_like(self, other) -> "NDArray":
        return self.broadcast_to(other.shape)

    def tile(self, reps):
        return invoke("tile", [self], {"reps": reps})

    def flip(self, axis):
        return invoke("reverse", [self], {"axis": axis})

    def sum(self, axis=None, keepdims=False, **kw):
        return invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def nansum(self, axis=None, keepdims=False, **kw):
        return invoke("nansum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False, **kw):
        return invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False, **kw):
        return invoke("prod", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False, **kw):
        return invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False, **kw):
        return invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, **kw):
        return invoke("norm", [self], kw)

    def argmax(self, axis=None, keepdims=False):
        return invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def argsort(self, **kw):
        return invoke("argsort", [self], kw)

    def sort(self, **kw):
        return invoke("sort", [self], kw)

    def topk(self, **kw):
        return invoke("topk", [self], kw)

    def clip(self, a_min, a_max):
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self):
        return invoke("abs", [self])

    def sign(self):
        return invoke("sign", [self])

    def sqrt(self):
        return invoke("sqrt", [self])

    def square(self):
        return invoke("square", [self])

    def exp(self):
        return invoke("exp", [self])

    def log(self):
        return invoke("log", [self])

    def relu(self):
        return invoke("relu", [self])

    def sigmoid(self):
        return invoke("sigmoid", [self])

    def tanh(self):
        return invoke("tanh", [self])

    def softmax(self, *args, **kw):
        return invoke("softmax", [self], kw)

    def log_softmax(self, *args, **kw):
        return invoke("log_softmax", [self], kw)

    def round(self):
        return invoke("round", [self])

    def floor(self):
        return invoke("floor", [self])

    def ceil(self):
        return invoke("ceil", [self])

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", [self],
                      {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def pick(self, index, axis=-1, keepdims=False):
        return invoke("pick", [self, index],
                      {"axis": axis, "keepdims": keepdims})

    def one_hot(self, depth, **kw):
        return invoke("one_hot", [self], {"depth": depth, **kw})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke("SliceChannel", [self],
                      {"num_outputs": num_outputs, "axis": axis,
                       "squeeze_axis": squeeze_axis})

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse as _sp
        return _sp.cast_storage(self, stype)

    def as_nd_ndarray(self):
        return self

    def asnumpy_or_none(self):
        return self.asnumpy()

    # ---- arithmetic dunders --------------------------------------------
    def _binary(self, other, op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            args = [other, self] if reverse else [self, other]
            return invoke(op, args)
        if isinstance(other, numeric_types):
            return invoke(scalar_op, [self], {"scalar": float(other)})
        return NotImplemented

    def __add__(self, other):
        return self._binary(other, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, other):
        if isinstance(other, numeric_types):
            return invoke("_rminus_scalar", [self], {"scalar": float(other)})
        return self._binary(other, "broadcast_sub", None, reverse=True)

    def __mul__(self, other):
        return self._binary(other, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, other):
        if isinstance(other, numeric_types):
            return invoke("_rdiv_scalar", [self], {"scalar": float(other)})
        return self._binary(other, "broadcast_div", None, reverse=True)

    def __mod__(self, other):
        return self._binary(other, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, other):
        if isinstance(other, numeric_types):
            return invoke("_rmod_scalar", [self], {"scalar": float(other)})
        return self._binary(other, "broadcast_mod", None, reverse=True)

    def __pow__(self, other):
        return self._binary(other, "broadcast_power", "_power_scalar")

    def __rpow__(self, other):
        if isinstance(other, numeric_types):
            return invoke("_rpower_scalar", [self], {"scalar": float(other)})
        return NotImplemented

    def __neg__(self):
        return invoke("negative", [self])

    def __abs__(self):
        return invoke("abs", [self])

    def __eq__(self, other):  # type: ignore[override]
        if other is None:
            return False
        return self._binary(other, "broadcast_equal", "_equal_scalar")

    def __ne__(self, other):  # type: ignore[override]
        if other is None:
            return True
        return self._binary(other, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return self._binary(other, "broadcast_greater", "_greater_scalar")

    def __ge__(self, other):
        return self._binary(other, "broadcast_greater_equal",
                            "_greater_equal_scalar")

    def __lt__(self, other):
        return self._binary(other, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, other):
        return self._binary(other, "broadcast_lesser_equal",
                            "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    # in-place forms: swap the underlying buffer
    def __iadd__(self, other):
        out = self.__add__(other)
        self._data = out._data.astype(self._data.dtype)
        return self

    def __isub__(self, other):
        out = self.__sub__(other)
        self._data = out._data.astype(self._data.dtype)
        return self

    def __imul__(self, other):
        out = self.__mul__(other)
        self._data = out._data.astype(self._data.dtype)
        return self

    def __itruediv__(self, other):
        out = self.__truediv__(other)
        self._data = out._data.astype(self._data.dtype)
        return self

    # ---- indexing -------------------------------------------------------
    def _canon_index(self, key):
        if isinstance(key, NDArray):
            return key._data.astype(jnp.int32) if \
                np.issubdtype(key.dtype, np.floating) else key._data
        if isinstance(key, tuple):
            return tuple(self._canon_index(k) if isinstance(k, NDArray) else k
                         for k in key)
        return key

    def __getitem__(self, key):
        if isinstance(key, integer_types):
            return NDArray(self._data[int(key)], self._ctx)
        key = self._canon_index(key)
        return NDArray(self._data[key], self._ctx)

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            v = value._data
        elif isinstance(value, numeric_types):
            v = value
        else:
            v = jnp.asarray(value)
        dev = next(iter(self._data.devices()))
        if isinstance(key, slice) and key == slice(None):
            if isinstance(v, (int, float)):
                self._data = jnp.full_like(self._data, v)
            else:
                val = jnp.broadcast_to(jnp.asarray(v, self._data.dtype),
                                       self.shape)
                self._data = jax.device_put(val, dev)
            return
        key = self._canon_index(key)
        # cast to the array dtype (reference semantics: assignment casts)
        # and pin to this array's device (cross-device assignment copies)
        v = jax.device_put(jnp.asarray(v, self._data.dtype), dev)
        self._data = self._data.at[key].set(v)

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    # numpy interop
    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a


# --------------------------------------------------------------------------
# the imperative dispatch — analog of Imperative::Invoke (imperative.cc:87)
# --------------------------------------------------------------------------
def invoke(op: Union[str, Operator], inputs: Sequence[NDArray],
           kwargs: Optional[Dict[str, Any]] = None,
           out: Optional[Union[NDArray, Sequence[NDArray]]] = None):
    """Execute one operator imperatively.

    Steps (mirroring the reference): parse attrs (param struct), pick
    compiled executable (cached per (op, attrs), shape-specialized by XLA),
    run async, optionally record on the autograd tape (RecordOp), apply
    aux/out writebacks.
    """
    if isinstance(op, str):
        op = get_op(op)
    kwargs = dict(kwargs or {})
    kwargs.pop("name", None)
    ctx = kwargs.pop("ctx", None)
    if out is None:
        out = kwargs.pop("out", None)
    else:
        kwargs.pop("out", None)
    # drop None-valued optional params so defaults apply
    kwargs = {k: v for k, v in kwargs.items() if v is not None}
    attrs = op.parse_attrs(kwargs)
    if op.train_aware:
        attrs = AttrDict({**attrs, "__train__": _autograd.is_training()})
    if op.nin == -1 and "num_args" in op.params:
        attrs = AttrDict({**attrs, "num_args": len(inputs)})

    arrays = []
    for a in inputs:
        if isinstance(a, NDArray):
            arrays.append(a._data)
        else:
            arrays.append(jnp.asarray(a))

    prefix = []
    if op.needs_rng:
        prefix = [_random.next_key()]

    recording = _autograd.is_recording() and any(
        _autograd._entry_of(a) is not None
        for a in inputs if isinstance(a, NDArray))

    from .. import profiler as _profiler
    _prof = _profiler.is_running()
    _pt0 = _profiler._now_us() if _prof else 0.0
    if recording:
        fn, _attrs, _prefix = op.fn, attrs, tuple(prefix)

        def pure(*xs):
            res = fn(_attrs, *_prefix, *xs)
            return res if isinstance(res, tuple) else (res,)

        outs, vjp_fn = jax.vjp(pure, *arrays)
        out_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs]

        def vjp_caller(cots, _v=vjp_fn, _av=out_avals):
            full = tuple(jnp.zeros(a.shape, a.dtype) if c is None else
                         jnp.asarray(c, a.dtype)
                         for c, a in zip(cots, _av))
            return _v(full)
    else:
        res = op.compiled(attrs)(*prefix, *arrays)
        outs = res if isinstance(res, tuple) else (res,)
        vjp_caller = None
    if _prof:
        # ProfileOperator analog (threaded_engine.h:80): span per dispatch
        _profiler.record_span(op.name, _pt0, _profiler._now_us())

    if ctx is not None and not isinstance(ctx, Context):
        ctx = Context(*ctx) if isinstance(ctx, tuple) else _parse_ctx(ctx)
    out_ctx = ctx or (inputs[0]._ctx if inputs and isinstance(inputs[0], NDArray)
                      else current_context())
    nd_outs = [NDArray(o, out_ctx) for o in outs]

    if recording:
        _autograd.record_op(op.name, vjp_caller,
                            [a for a in inputs if isinstance(a, NDArray)],
                            nd_outs)

    # aux writeback (BatchNorm moving stats, optimizer states)
    for oi, ii in op.get_aux_writeback(attrs).items():
        if ii < len(inputs) and isinstance(inputs[ii], NDArray):
            inputs[ii]._data = outs[oi]

    nvis = op.num_visible_outputs(attrs)
    nd_outs = nd_outs[:nvis]

    if out is not None:
        out_list = [out] if isinstance(out, NDArray) else list(out)
        for dst, src in zip(out_list, nd_outs):
            dst._data = src._data.astype(dst._data.dtype)
        return out if isinstance(out, NDArray) else out_list
    return nd_outs[0] if len(nd_outs) == 1 else nd_outs


def imperative_invoke(op_name, *args, **kwargs):
    """Generated-function entry (analog of _imperative_invoke,
    python/mxnet/_ctypes/ndarray.py:65).

    Positional NDArrays (or lists of them) are op inputs; positional
    scalars/tuples/strings fill the op's declared params in order —
    matching the generated-signature convention of the reference.
    """
    op = get_op(op_name)
    inputs = []
    scalars = []
    for a in args:
        if isinstance(a, NDArray):
            inputs.append(a)
        elif isinstance(a, (list, tuple)) and a and isinstance(a[0], NDArray):
            inputs.extend(a)
        elif isinstance(a, np.ndarray):
            inputs.append(array(a))
        elif isinstance(a, (int, float, str, tuple, list)):
            scalars.append(a)
        else:
            raise MXNetError("invalid positional argument %r to op %s"
                             % (type(a), op_name))
    # Array-valued keyword args are inputs placed by declared arg name
    # (reference generated signatures: F.LayerNorm(data, gamma=.., beta=..))
    kw_arrays = {}
    for k, v in kwargs.items():
        if k in ("out", "name", "ctx"):
            continue
        if isinstance(v, NDArray):
            kw_arrays[k] = v
        elif isinstance(v, np.ndarray):
            kw_arrays[k] = array(v)
    if kw_arrays:
        for k in kw_arrays:
            kwargs.pop(k)
        if op.arg_names:
            # the inputs this call's attrs leave switched on, as a Symbol
            # node of the op has them
            from ..symbol.symbol import _arg_names
            names = _arg_names(op, {k: v for k, v in kwargs.items()
                                    if k not in ("out", "name", "ctx")})
            if any(k in op.arg_names and k not in names for k in kw_arrays):
                names = op.arg_names
            slots = {n: i for i, n in enumerate(names)}
            hi = max((slots.get(k, -1) for k in kw_arrays), default=-1)
            ins = list(inputs) + [None] * max(0, hi + 1 - len(inputs))
            for k, v in kw_arrays.items():
                i = slots.get(k)
                if i is None:
                    ins.append(v)
                elif i < len(ins) and ins[i] is not None:
                    raise MXNetError(
                        "op %s: input %r given both positionally and by "
                        "keyword" % (op_name, k))
                else:
                    while len(ins) <= i:
                        ins.append(None)
                    ins[i] = v
            if any(v is None for v in ins):
                raise MXNetError(
                    "op %s: missing input(s) %s" % (op_name, [
                        names[i] if i < len(names) else i
                        for i, v in enumerate(ins) if v is None]))
            inputs = ins
        else:
            inputs.extend(kw_arrays.values())
    if scalars:
        for k in op.params:
            if not scalars:
                break
            if k in kwargs or k.startswith("__"):
                continue
            kwargs[k] = scalars.pop(0)
    return invoke(op, inputs, kwargs)


def _parse_ctx(s):
    if isinstance(s, Context):
        return s
    s = str(s)
    name, _, idx = s.partition("(")
    return Context(name.strip(), int(idx.rstrip(")")) if idx else 0)


# --------------------------------------------------------------------------
# creation
# --------------------------------------------------------------------------
def _put(value, ctx: Context):
    return jax.device_put(value, ctx.jax_device)


def array(source, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    ctx = ctx or current_context()
    if isinstance(source, NDArray):
        data = source._data
        if dtype is not None:
            data = data.astype(np.dtype(dtype))
        return NDArray(_put(data, ctx), ctx)
    src = np.asarray(source)
    if dtype is None:
        dtype = np.float32 if src.dtype == np.float64 else src.dtype
    # The astype copy is load-bearing even for same-dtype sources:
    # device_put zero-copy-aliases suitably aligned host arrays on the CPU
    # backend, and nd.array must never alias caller memory (callers reuse
    # staging buffers — the universal MXNet pattern).
    return NDArray(_put(src.astype(np.dtype(dtype)), ctx), ctx)


def empty(shape, ctx=None, dtype="float32") -> NDArray:
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype="float32", **kwargs) -> NDArray:
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_put(jnp.zeros(shape, np.dtype(dtype or "float32")), ctx), ctx)


def ones(shape, ctx=None, dtype="float32", **kwargs) -> NDArray:
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_put(jnp.ones(shape, np.dtype(dtype or "float32")), ctx), ctx)


def full(shape, val, ctx=None, dtype="float32", **kwargs) -> NDArray:
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_put(jnp.full(shape, val, np.dtype(dtype or "float32")), ctx), ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    ctx = ctx or current_context()
    out = jnp.arange(start, stop, step, np.dtype(dtype or "float32"))
    if repeat > 1:
        out = jnp.repeat(out, repeat)
    return NDArray(_put(out, ctx), ctx)


def concatenate(arrays, axis=0, always_copy=True):
    return invoke("Concat", list(arrays), {"dim": axis})


def moveaxis(tensor, source, destination):
    return NDArray(jnp.moveaxis(tensor._data, source, destination), tensor._ctx)


def onehot_encode(indices, out):
    depth = out.shape[1]
    res = invoke("one_hot", [indices], {"depth": depth})
    out._data = res._data.astype(out._data.dtype)
    return out


def waitall():
    """Block until all async work completes (ref: MXNDArrayWaitAll): the
    host engine's queue, ordered host effects, and every computation the
    device still owes a live array.  ``effects_barrier`` alone waits only
    for side-effecting programs, so a chain of pure train steps would
    still be running when it returned."""
    from .. import engine as _engine
    _engine.get().wait_for_all()
    jax.effects_barrier()
    for arr in jax.live_arrays():
        if not arr.is_deleted():
            arr.block_until_ready()


# --------------------------------------------------------------------------
# save / load — reference format semantics (ndarray.cc Save/Load):
# a file holds either a list of arrays or a dict of name → array.
# Implementation: npz container (TPU build keeps the artifact semantics,
# SURVEY.md §5.4, not the binary layout).
# --------------------------------------------------------------------------
def _save_entries(prefix, a):
    """Flatten one array into npz entries; sparse arrays (reference
    ndarray.cc Save handles all three stypes) store their components."""
    from .sparse import RowSparseNDArray, CSRNDArray
    if isinstance(a, RowSparseNDArray):
        return {prefix + "/rsp_data": np.asarray(a._sp_values),
                prefix + "/rsp_indices": a._sp_indices,
                prefix + "/rsp_shape": np.asarray(a.shape, np.int64)}
    if isinstance(a, CSRNDArray):
        return {prefix + "/csr_data": np.asarray(a._sp_values),
                prefix + "/csr_indices": a._sp_indices,
                prefix + "/csr_indptr": a._sp_indptr,
                prefix + "/csr_shape": np.asarray(a.shape, np.int64)}
    return {prefix: a.asnumpy()}


def _load_entry(z, prefix):
    from .sparse import RowSparseNDArray, CSRNDArray
    if prefix + "/rsp_data" in z:
        return RowSparseNDArray(z[prefix + "/rsp_data"],
                                z[prefix + "/rsp_indices"],
                                tuple(z[prefix + "/rsp_shape"]))
    if prefix + "/csr_data" in z:
        return CSRNDArray(z[prefix + "/csr_data"],
                          z[prefix + "/csr_indices"],
                          z[prefix + "/csr_indptr"],
                          tuple(z[prefix + "/csr_shape"]))
    return array(z[prefix])


def save(fname: str, data):
    entries = {}
    if isinstance(data, NDArray):
        entries.update(_save_entries("arr:0", data))
    elif isinstance(data, (list, tuple)):
        for i, a in enumerate(data):
            entries.update(_save_entries("arr:%d" % i, a))
    elif isinstance(data, dict):
        for k, v in data.items():
            entries.update(_save_entries("name:" + k, v))
    else:
        raise MXNetError("save expects NDArray, list, or dict")
    np.savez(_norm(fname), **entries)


_SPARSE_SUFFIXES = ("/rsp_data", "/rsp_indices", "/rsp_shape",
                    "/csr_data", "/csr_indices", "/csr_indptr", "/csr_shape")


def load(fname: str):
    with np.load(_norm(fname), allow_pickle=False) as z:
        prefixes = []
        for k in z.keys():
            p = k
            for suf in _SPARSE_SUFFIXES:
                if k.endswith(suf):
                    p = k[:-len(suf)]
                    break
            if p not in prefixes:
                prefixes.append(p)
        if all(p.startswith("arr:") for p in prefixes):
            items = sorted(prefixes, key=lambda k: int(k.split(":")[1]))
            return [_load_entry(z, p) for p in items]
        return {p.split(":", 1)[1]: _load_entry(z, p) for p in prefixes}


def _norm(fname):
    if not isinstance(fname, str):
        return fname  # file-like object (predictor bytes-params path)
    return fname if fname.endswith(".npz") else fname + ".npz"
