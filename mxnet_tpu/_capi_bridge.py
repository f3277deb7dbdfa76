"""Python side of the C API waist (SURVEY.md N17).

Reference analog: ``src/c_api/c_api.cc`` + ``c_api_ndarray.cc`` — the
C ABI's NDArray CRUD, imperative invoke, and op listing (Parts 0-2 of
``include/mxnet/c_api.h``).  ``src/c_api.cc`` embeds CPython (the same
pattern as the predict ABI, ``src/predict.cc``) and calls these functions;
each takes/returns only simple Python types + NDArray objects so the C
marshalling stays mechanical.

Reference dtype codes (``include/mxnet/tensor_blob.h`` / mshadow type
flags): 0=float32 1=float64 2=float16 3=uint8 4=int32 5=int8 6=int64;
12=bfloat16 is carried as the TPU-native extension.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from . import context as _context
from . import ndarray as nd
from .base import MXNetError
from .ndarray.ndarray import NDArray
from .ops import registry as _registry

_CODE2DT = {0: "float32", 1: "float64", 2: "float16", 3: "uint8",
            4: "int32", 5: "int8", 6: "int64", 12: "bfloat16"}
_DT2CODE = {v: k for k, v in _CODE2DT.items()}


def _ctx(dev_type: int, dev_id: int) -> _context.Context:
    name = _context.Context.devtype2str.get(int(dev_type))
    if name is None:
        raise MXNetError("unknown device type id %d" % dev_type)
    return _context.Context(name, int(dev_id))


def _np_dtype(code: int) -> np.dtype:
    try:
        name = _CODE2DT[int(code)]
    except KeyError:
        raise MXNetError("unknown dtype code %d" % code)
    if name == "bfloat16":
        import jax.numpy as jnp
        return jnp.bfloat16
    return np.dtype(name)


def create(shape: Sequence[int], dev_type: int, dev_id: int,
           dtype_code: int = 0, delay_alloc: int = 0) -> NDArray:
    """MXNDArrayCreate/CreateEx: an initialized (zero) array on a device.
    XLA has no uninitialized-alloc notion, so delay_alloc is accepted and
    ignored (allocation is lazy inside jax anyway)."""
    return nd.zeros(tuple(int(s) for s in shape), ctx=_ctx(dev_type, dev_id),
                    dtype=_np_dtype(dtype_code))


def copy_from_ptr(addr: int, size: int, handle: NDArray):
    """MXNDArraySyncCopyFromCPU: overwrite the handle's contents *in place*
    from a flat host buffer of ``size`` elements (reference contract:
    CHECK size == array size; the handle object keeps its identity so
    autograd marking and aliases survive)."""
    import ctypes
    if int(size) != handle.size:
        raise MXNetError("SyncCopyFromCPU: %d elements given, array has %d"
                         % (size, handle.size))
    nbytes = handle.size * np.dtype(handle.dtype).itemsize
    buf = (ctypes.c_ubyte * nbytes).from_address(int(addr))
    arr = np.frombuffer(buf, dtype=handle.dtype).reshape(handle.shape)
    # nd.array's astype copy materializes before the ctypes view dies
    handle._data = nd.array(arr, ctx=handle.context,
                            dtype=handle.dtype)._data


def copy_to_ptr(addr: int, size: int, handle: NDArray):
    """MXNDArraySyncCopyToCPU: write the array into a caller buffer of
    ``size`` elements (reference contract: CHECK size == array size — a
    short buffer must error, never overrun)."""
    import ctypes
    if int(size) != handle.size:
        raise MXNetError("SyncCopyToCPU: buffer holds %d elements, array "
                         "has %d" % (size, handle.size))
    src = np.ascontiguousarray(handle.asnumpy())
    ctypes.memmove(int(addr), src.ctypes.data, src.nbytes)


def shape_of(handle: NDArray) -> Tuple[int, ...]:
    return tuple(int(s) for s in handle.shape)


def dtype_code_of(handle: NDArray) -> int:
    name = np.dtype(handle.dtype).name   # 'bfloat16' via ml_dtypes
    code = _DT2CODE.get(name)
    if code is None:
        raise MXNetError("dtype %r has no reference code" % (name,))
    return code


def ctx_of(handle: NDArray) -> Tuple[int, int]:
    c = handle.context
    return int(c.device_typeid), int(c.device_id)


def wait_to_read(handle: NDArray):
    handle.wait_to_read()


def slice_(handle: NDArray, begin: int, end: int) -> NDArray:
    return handle[int(begin):int(end)]


def reshape(handle: NDArray, dims: Sequence[int]) -> NDArray:
    return handle.reshape(tuple(int(d) for d in dims))


def invoke(op_name: str, inputs: Sequence[NDArray],
           param_keys: Sequence[str], param_vals: Sequence[str],
           outs: Sequence[NDArray] = ()) -> List[NDArray]:
    """MXImperativeInvoke: run one registered operator on NDArray inputs
    with string-typed attrs (the reference passes every attr as a string;
    param.coerce parses them exactly like dmlc::Parameter).  Pre-supplied
    ``outs`` receive the results in place (the reference's non-NULL
    *outputs contract — how ``sgd_update(w, g, out=w)`` works over the
    ABI)."""
    from .ndarray.ndarray import invoke as _invoke
    kwargs: Dict[str, str] = dict(zip(param_keys, param_vals))
    out_arg = list(outs) if outs else None
    out = _invoke(op_name, list(inputs), kwargs, out=out_arg)
    if isinstance(out, NDArray):
        return [out]
    return list(out)


def list_ops() -> List[str]:
    """MXListAllOpNames."""
    return _registry.list_ops()


def save(fname: str, handles: Sequence[NDArray],
         keys: Sequence[str]):
    """MXNDArraySave (named dict when keys given, list format otherwise)."""
    if keys:
        nd.save(fname, dict(zip(keys, handles)))
    else:
        nd.save(fname, list(handles))


def load(fname: str) -> Tuple[List[NDArray], List[str]]:
    """MXNDArrayLoad -> (arrays, names); names empty for list format."""
    data = nd.load(fname)
    if isinstance(data, dict):
        # insertion order == save order (nd.load preserves it); the
        # reference MXNDArrayLoad keeps positional order for named saves,
        # so C consumers may rely on it (advisor r04)
        names = list(data)
        return [data[k] for k in names], names
    return list(data), []


def wait_all():
    """MXNDArrayWaitAll/MXEngineWaitAll."""
    from .ndarray.ndarray import waitall
    waitall()


def random_seed(seed: int):
    """MXRandomSeed."""
    from . import random as _random
    _random.seed(int(seed))


# ---- autograd (c_api.h Part 2: MXAutograd*) -------------------------------

def autograd_set_recording(flag: int) -> int:
    from . import autograd as _ag
    return int(_ag.set_recording(bool(flag)))


def autograd_set_training(flag: int) -> int:
    from . import autograd as _ag
    return int(_ag.set_training(bool(flag)))


def autograd_mark_variables(handles: Sequence[NDArray]):
    """MXAutogradMarkVariables (grad_req='write'; gradient storage is
    allocated by attach_grad, read back via get_grad)."""
    for h in handles:
        h.attach_grad()


def autograd_backward(heads: Sequence[NDArray], retain_graph: int):
    from . import autograd as _ag
    _ag.backward(list(heads), retain_graph=bool(retain_graph))


def get_grad(handle: NDArray) -> NDArray:
    """MXNDArrayGetGrad: the gradient buffer attached to a variable."""
    g = handle.grad
    if g is None:
        raise MXNetError("array has no gradient (call mark_variables first)")
    return g


# ---- symbol (c_api.h Part 3: MXSymbol*, reference c_api.h:1028) -----------

class _AtomicSymbol:
    """An op + attrs awaiting composition — the reference's
    MXSymbolCreateAtomicSymbol result before MXSymbolCompose fills the
    inputs (nnvm Symbol::CreateFunctor analog)."""

    __slots__ = ("op_name", "attrs")

    def __init__(self, op_name: str, attrs: Dict[str, str]):
        if op_name not in _registry.OPS:
            raise MXNetError("unknown operator %r" % op_name)
        self.op_name = op_name
        self.attrs = attrs


def symbol_create_atomic(op_name: str, keys: Sequence[str],
                         vals: Sequence[str]):
    """MXSymbolCreateAtomicSymbol: op + string attrs, inputs come later
    via compose."""
    return _AtomicSymbol(op_name, dict(zip(keys, vals)))


def symbol_create_variable(name: str):
    """MXSymbolCreateVariable."""
    from . import symbol as sym_mod
    return sym_mod.var(name)


def symbol_compose(handle, name: str, keys: Sequence[str], args):
    """MXSymbolCompose: fill an atomic symbol's inputs (positional when
    ``keys`` is empty, by arg name otherwise).  Returns the composed
    Symbol — the C side swaps it into the same handle (the reference
    mutates the nnvm symbol in place)."""
    from . import symbol as sym_mod
    if isinstance(handle, _AtomicSymbol):
        op = _registry.OPS[handle.op_name]
        fn = getattr(sym_mod, handle.op_name)
        kwargs = dict(handle.attrs)
        if name:
            kwargs["name"] = name
        if keys:
            known = set(op.arg_names or [])
            for k in keys:
                # reference contract: keyword args must name declared
                # inputs ("Keyword argument name not found")
                if known and k not in known:
                    raise MXNetError(
                        "compose %s: keyword argument %r is not an input "
                        "(have %s)" % (handle.op_name, k, sorted(known)))
            kwargs.update(zip(keys, args))
            return fn(**kwargs)
        return fn(*args, **kwargs)
    # composing a full symbol substitutes its free variables
    if keys:
        handle(**dict(zip(keys, args)))
    else:
        handle(*args)
    return handle


def symbol_copy(handle):
    """MXSymbolCopy (deep copy via the JSON round-trip — node names are
    preserved, so bindings stay compatible)."""
    from . import symbol as sym_mod
    return sym_mod.load_json(handle.tojson())


def symbol_list_arguments(handle) -> List[str]:
    if isinstance(handle, _AtomicSymbol):
        return []
    return list(handle.list_arguments())


def symbol_list_outputs(handle) -> List[str]:
    if isinstance(handle, _AtomicSymbol):
        return []
    return list(handle.list_outputs())


def symbol_list_aux(handle) -> List[str]:
    if isinstance(handle, _AtomicSymbol):
        return []
    return list(handle.list_auxiliary_states())


def symbol_get_name(handle) -> str:
    if isinstance(handle, _AtomicSymbol):
        return ""
    return handle.name or ""


def symbol_tojson(handle) -> str:
    return handle.tojson()


def symbol_from_json(js: str):
    from . import symbol as sym_mod
    return sym_mod.load_json(js)


def symbol_infer_shape(handle, keys: Sequence[str], shapes,
                       partial: int = 0):
    """MXSymbolInferShape(Partial) -> (arg_shapes, out_shapes, aux_shapes)
    as lists of int tuples, ordered like list_arguments/outputs/aux."""
    kwargs = {k: tuple(int(d) for d in s) for k, s in zip(keys, shapes)}
    if partial:
        a, o, x = handle.infer_shape_partial(**kwargs)
    else:
        a, o, x = handle.infer_shape(**kwargs)
    conv = lambda ss: [tuple(int(d) for d in (s or ())) for s in ss]
    return conv(a), conv(o), conv(x)


def op_info(op_name: str):
    """MXSymbolGetAtomicSymbolInfo: (description, input arg names,
    param names, param type strings, required flags) — feeds both the C
    introspection call and the cpp-package wrapper generator."""
    op = _registry.OPS[op_name]
    arg_names = list(op.arg_names or [])
    if not arg_names and op.nin not in (None, -1):
        arg_names = ["data%d" % i for i in range(op.nin)] \
            if op.nin > 1 else ["data"]
    pnames, ptypes, preq = [], [], []
    for k, spec in op.params.items():
        if k.startswith("__"):
            continue
        pnames.append(k)
        t = spec.ptype
        if isinstance(t, (list, tuple)):        # enum of string choices
            ptypes.append("{%s}" % ",".join("'%s'" % c for c in t))
        else:
            ptypes.append(t if isinstance(t, str) else t.__name__)
        preq.append(1 if spec.required else 0)
    # key_var_num_args marks ops taking a homogeneous variadic input list:
    # either declared via a literal num_args param (Concat style) or
    # nin==-1 with no named args (add_n/khatri_rao style) — NOT merely
    # optional trailing inputs like FullyConnected's bias (which has
    # arg_names and therefore a fixed wrapper signature)
    variadic = "num_args" in op.params or (op.nin == -1 and not arg_names)
    return (op.doc or "", arg_names, pnames, ptypes, preq,
            1 if variadic else 0)


# ---- executor (c_api.h Part 4: MXExecutor*, reference c_api.h:1483) -------

_GRAD_REQ = {0: "null", 1: "write", 2: "write", 3: "add"}  # OpReqType


def executor_bind(handle, dev_type: int, dev_id: int,
                  arg_handles, grad_handles, grad_req_codes,
                  aux_handles):
    """MXExecutorBind: positional arrays ordered like list_arguments /
    list_auxiliary_states; grad storage handles may contain None (grad_req
    null).  Gradients are written INTO the supplied grad arrays in place,
    so the caller's handles observe them (reference GraphExecutor
    contract)."""
    arg_names = handle.list_arguments()
    aux_names = handle.list_auxiliary_states()
    if len(arg_handles) != len(arg_names):
        raise MXNetError("bind: %d args given, symbol has %d (%s)"
                         % (len(arg_handles), len(arg_names), arg_names))
    if len(aux_handles) != len(aux_names):
        raise MXNetError("bind: %d aux given, symbol has %d"
                         % (len(aux_handles), len(aux_names)))
    args = dict(zip(arg_names, arg_handles))
    req = {n: _GRAD_REQ.get(int(c), "null")
           for n, c in zip(arg_names, grad_req_codes)}
    grads = {n: g for n, g in zip(arg_names, grad_handles)
             if g is not None and req.get(n) != "null"}
    auxs = dict(zip(aux_names, aux_handles))
    return handle.bind(_ctx(dev_type, dev_id), args=args, args_grad=grads,
                       grad_req=req, aux_states=auxs)


def executor_forward(ex, is_train: int):
    ex.forward(is_train=bool(is_train))


def executor_outputs(ex) -> List[NDArray]:
    return list(ex.outputs)


def executor_backward(ex, head_grads):
    ex.backward(out_grads=list(head_grads) if head_grads else None)
