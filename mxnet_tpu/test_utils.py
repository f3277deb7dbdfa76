"""Testing utilities (parity: ``python/mxnet/test_utils.py``, 1,955 LoC in
the reference — the numeric-gradient checker, tolerance asserts, random
tensors for all stypes, and backend cross-checking used throughout
``tests/python/unittest``).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from .base import MXNetError
from . import context as _context

_DEFAULT_CTX = None


def default_context():
    """The context tests run on (reference default_context(), env-switchable
    via MXNET_TEST_DEVICE)."""
    global _DEFAULT_CTX
    if _DEFAULT_CTX is None:
        import os
        dev = os.environ.get("MXNET_TEST_DEVICE", "")
        if dev.startswith("tpu"):
            _DEFAULT_CTX = _context.tpu(0)
        elif dev.startswith("gpu"):
            _DEFAULT_CTX = _context.gpu(0)
        else:
            _DEFAULT_CTX = _context.current_context()
    return _DEFAULT_CTX


def set_default_context(ctx):
    global _DEFAULT_CTX
    _DEFAULT_CTX = ctx


def same(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


def almost_equal(a, b, rtol=1e-5, atol=1e-20, equal_nan=False):
    return np.allclose(np.asarray(a), np.asarray(b), rtol=rtol, atol=atol,
                       equal_nan=equal_nan)


def assert_almost_equal(a, b, rtol=1e-5, atol=1e-20, names=("a", "b"),
                        equal_nan=False):
    """Tolerance assert with a useful message (reference
    assert_almost_equal)."""
    from .ndarray import NDArray
    a = a.asnumpy() if isinstance(a, NDArray) else np.asarray(a)
    b = b.asnumpy() if isinstance(b, NDArray) else np.asarray(b)
    if not np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan):
        err = np.abs(a - b)
        rel = err / (np.abs(b) + 1e-12)
        raise AssertionError(
            "%s and %s differ: max abs err %g, max rel err %g "
            "(rtol=%g atol=%g)" % (names[0], names[1], err.max(), rel.max(),
                                   rtol, atol))


def rand_shape_2d(dim0=10, dim1=10):
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1),
            np.random.randint(1, dim2 + 1))


def rand_shape_nd(num_dim, dim=10, allow_zero_size=False):
    """Random shape of ``num_dim`` dims, each in [1, dim] (or [0, dim] when
    zero-size edge shapes are wanted) — reference rand_shape_nd."""
    low = 0 if allow_zero_size else 1
    return tuple(np.random.randint(low, dim + 1, size=num_dim).tolist())


def rand_coord_2d(x_low, x_high, y_low, y_high):
    """A random 2-D coordinate (reference rand_coord_2d)."""
    return (np.random.randint(x_low, x_high),
            np.random.randint(y_low, y_high))


def rand_ndarray(shape, stype="default", density=None, dtype=None,
                 ctx=None):
    """Random NDArray of any storage type (reference rand_ndarray)."""
    from . import ndarray as nd
    dtype = dtype or np.float32
    if stype == "default":
        return nd.array(np.random.uniform(-1, 1, shape).astype(dtype),
                        ctx=ctx)
    return rand_sparse_ndarray(shape, stype, density=density, dtype=dtype)


def rand_sparse_ndarray(shape, stype, density=None, dtype=None):
    from .ndarray import sparse
    dtype = dtype or np.float32
    density = 0.5 if density is None else density
    dense = np.random.uniform(-1, 1, shape).astype(dtype)
    mask = np.random.uniform(0, 1, (shape[0],) if stype == "row_sparse"
                             else shape) <= density
    if stype == "row_sparse":
        dense = dense * mask.reshape((-1,) + (1,) * (len(shape) - 1))
    else:
        dense = dense * mask
    from . import ndarray as nd
    return nd.array(dense).tostype(stype)


def _executor_for(sym, location, aux_states, grad_req, ctx):
    from . import ndarray as nd
    args = {k: (v if isinstance(v, nd.NDArray) else nd.array(v))
            for k, v in location.items()}
    grads = {k: nd.zeros(v.shape, dtype=v.dtype) for k, v in args.items()
             if grad_req.get(k, "write") != "null"}
    aux = {k: (v if isinstance(v, nd.NDArray) else nd.array(v))
           for k, v in (aux_states or {}).items()}
    return sym.bind(ctx, args, args_grad=grads, grad_req=grad_req,
                    aux_states=aux)


def check_numeric_gradient(sym, location, aux_states=None, numeric_eps=1e-3,
                           rtol=1e-2, atol=None, grad_nodes=None, ctx=None,
                           dtype=np.float64):
    """Finite-difference gradient check of a symbol's backward
    (reference check_numeric_gradient).

    location: dict arg name -> np.ndarray/NDArray.  The symbol's outputs are
    reduced with a fixed random projection to a scalar; analytic grads from
    backward are compared to central differences of the forward.
    """
    from . import ndarray as nd
    ctx = ctx or default_context()
    # dtype governs host-side perturbation/difference arithmetic; device
    # execution stays in each arg's own dtype
    location = {k: np.asarray(v.asnumpy() if isinstance(v, nd.NDArray)
                              else v, dtype)
                for k, v in location.items()}
    grad_nodes = list(grad_nodes or location.keys())
    grad_req = {k: ("write" if k in grad_nodes else "null")
                for k in location}

    ex = _executor_for(sym, location, aux_states, grad_req, ctx)
    outs = ex.forward(is_train=True)
    rng = np.random.RandomState(0)
    projections = [rng.normal(0, 1, o.shape).astype(np.float32)
                   for o in outs]

    def loss_at(loc):
        for k, v in loc.items():
            ex.arg_dict[k][:] = v
        outs = ex.forward(is_train=True)
        return sum(float((o.asnumpy().astype(np.float64) * p).sum())
                   for o, p in zip(outs, projections))

    ex.forward(is_train=True)
    ex.backward([nd.array(p) for p in projections])
    analytic = {k: ex.grad_dict[k].asnumpy().copy() for k in grad_nodes}

    atol = rtol if atol is None else atol
    for name in grad_nodes:
        base = location[name]
        num = np.zeros_like(base, np.float64)
        flat = base.ravel()
        numf = num.ravel()
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + numeric_eps
            fp = loss_at(location)
            flat[i] = orig - numeric_eps
            fm = loss_at(location)
            flat[i] = orig
            numf[i] = (fp - fm) / (2 * numeric_eps)
        loss_at(location)  # restore
        a, n = analytic[name], num
        denom = np.maximum(np.abs(n), np.abs(a))
        bad = np.abs(a - n) > (atol + rtol * denom)
        if bad.any():
            raise AssertionError(
                "numeric gradient check failed for %r: analytic %s vs "
                "numeric %s" % (name, a.ravel()[bad.ravel()][:5],
                                n.ravel()[bad.ravel()][:5]))


def check_symbolic_forward(sym, location, expected, rtol=1e-4, atol=None,
                           aux_states=None, ctx=None):
    """Compare symbol forward outputs against expected arrays
    (reference check_symbolic_forward)."""
    from . import ndarray as nd
    ctx = ctx or default_context()
    if isinstance(location, (list, tuple)):
        location = dict(zip(sym.list_arguments(), location))
    grad_req = {k: "null" for k in location}
    ex = _executor_for(sym, location, aux_states, grad_req, ctx)
    outs = ex.forward(is_train=False)
    for o, e in zip(outs, expected):
        assert_almost_equal(o.asnumpy(), e, rtol=rtol,
                            atol=rtol if atol is None else atol)
    return outs


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-4,
                            atol=None, aux_states=None, grad_req="write",
                            ctx=None):
    """Compare symbol backward gradients against expected arrays
    (reference check_symbolic_backward)."""
    from . import ndarray as nd
    ctx = ctx or default_context()
    if isinstance(location, (list, tuple)):
        location = dict(zip(sym.list_arguments(), location))
    if isinstance(expected, (list, tuple)):
        expected = dict(zip(sym.list_arguments(), expected))
    reqs = {k: grad_req for k in location} if isinstance(grad_req, str) \
        else grad_req
    ex = _executor_for(sym, location, aux_states, reqs, ctx)
    ex.forward(is_train=True)
    ex.backward([g if isinstance(g, nd.NDArray) else nd.array(g)
                 for g in out_grads])
    for k, e in expected.items():
        if reqs.get(k) == "null":
            continue
        assert_almost_equal(ex.grad_dict[k].asnumpy(), e, rtol=rtol,
                            atol=rtol if atol is None else atol,
                            names=("grad(%s)" % k, "expected"))
    return ex


# per-dtype default tolerances (reference check_consistency tol table:
# fp16 1e-1, fp32 1e-3, fp64 1e-5, int types exact; bfloat16 has a coarser
# mantissa than fp16 so it shares the loose tier)
_DTYPE_RTOL = {"float16": 1e-1, "bfloat16": 1e-1, "float32": 1e-3,
               "float64": 1e-5}
_DTYPE_ATOL = {"float16": 1e-1, "bfloat16": 1e-1, "float32": 1e-4,
               "float64": 1e-7}
# precision ranking by mantissa bits (bf16 < fp16 < fp32 < fp64); numpy
# reports bfloat16 (an ml_dtypes extension type) as kind 'V', so rank by
# name, not itemsize/kind
_MANTISSA_BITS = {"bfloat16": 8, "float16": 10, "float32": 23,
                  "float64": 52}


def _float_rank(dtype):
    """Mantissa bits of a float-ish dtype, or None for non-floats."""
    return _MANTISSA_BITS.get(np.dtype(dtype).name)


def _entry_dtypes(entry, names):
    td = entry.get("type_dict", {})
    return {k: np.dtype(td.get(k, np.float32)) for k in names}


def check_consistency(sym, ctx_list, scale=1.0, rtol=None, atol=None,
                      grad_req="write", equal_nan=False):
    """Run one symbol across contexts *and dtypes* and require matching
    forward outputs and backward gradients (reference check_consistency —
    the CPU/GPU + fp16-grid cross-check pattern; here contexts are CPU
    interpreter vs TPU and the dtype grid covers fp16/bf16/fp32/fp64).

    ctx_list entries: ``{'ctx': Context, <arg name>: shape, ...,
    'type_dict': {arg name: dtype}}``.  Ground truth is the
    highest-precision entry; every other entry is compared against it with
    tolerances keyed to the lower-precision dtype of the pair (overridable
    via rtol/atol).  With ``grad_req != 'null'``, backward runs with a
    fixed random head gradient and argument gradients must match too.
    """
    from . import ndarray as nd
    if not ctx_list:
        return
    arg_shapes = {k: v for k, v in ctx_list[0].items()
                  if k not in ("ctx", "type_dict")}
    names = list(arg_shapes)
    rng = np.random.RandomState(0)
    base = {k: rng.normal(0, scale, s).astype(np.float64)
            for k, s in arg_shapes.items()}
    reqs = ({k: grad_req for k in names} if isinstance(grad_req, str)
            else dict(grad_req))
    run_backward = any(r != "null" for r in reqs.values())

    results = []   # (min_dtype, outputs, grads)
    head_grads = None
    for entry in ctx_list:
        dtypes = _entry_dtypes(entry, names)
        location = {k: base[k].astype(dtypes[k]) for k in names}
        ex = _executor_for(sym, location, None, reqs, entry["ctx"])
        outs = ex.forward(is_train=run_backward)
        grads = {}
        if run_backward:
            if head_grads is None:
                head_grads = [rng.normal(0, 1, o.shape)
                              .astype(np.float64) for o in outs]
            ex.backward([nd.array(h.astype(o.dtype))
                         for h, o in zip(head_grads, outs)])
            grads = {k: ex.grad_dict[k].asnumpy()
                     for k in names if reqs.get(k) != "null"}
        ranks = [_float_rank(dt) for dt in dtypes.values()]
        ranks = [r for r in ranks if r is not None] or \
            [_MANTISSA_BITS["float32"]]
        min_rank = min(ranks)
        results.append((min_rank, [o.asnumpy() for o in outs], grads))

    # ground truth: the entry whose lowest-precision dtype is widest
    gt_idx = max(range(len(results)), key=lambda i: results[i][0])
    gt_rank, gt_outs, gt_grads = results[gt_idx]
    rank2name = {v: k for k, v in _MANTISSA_BITS.items()}
    for i, (rank, outs, grads) in enumerate(results):
        if i == gt_idx:
            continue
        pair_name = rank2name[min(rank, gt_rank)]
        r = _DTYPE_RTOL.get(pair_name, 1e-3) if rtol is None else rtol
        a = _DTYPE_ATOL.get(pair_name, 1e-4) if atol is None else atol
        for o, e in zip(outs, gt_outs):
            assert_almost_equal(np.asarray(o, np.float64),
                                np.asarray(e, np.float64), rtol=r, atol=a,
                                equal_nan=equal_nan,
                                names=("ctx[%d] output" % i, "ground truth"))
        for k in grads:
            assert_almost_equal(np.asarray(grads[k], np.float64),
                                np.asarray(gt_grads[k], np.float64),
                                rtol=r, atol=a, equal_nan=equal_nan,
                                names=("ctx[%d] grad(%s)" % (i, k),
                                       "ground truth"))
    return [outs for _, outs, _ in results]


def check_speed(sym, location=None, ctx=None, n=20, grad_req="null",
                typ="whole", **arg_shapes):
    """Median seconds per execution (reference check_speed).  ``typ``:
    'whole' = forward+backward when grad_req allows it, 'forward' =
    forward only regardless of grad_req."""
    import time
    from . import ndarray as nd
    if typ not in ("whole", "forward"):
        raise MXNetError("check_speed typ must be 'whole' or 'forward'")
    ctx = ctx or default_context()
    if location is None:
        rng = np.random.RandomState(0)
        location = {k: rng.normal(0, 1, s).astype(np.float32)
                    for k, s in arg_shapes.items()}
    reqs = {k: grad_req for k in location}
    ex = _executor_for(sym, location, None, reqs, ctx)
    run_backward = grad_req != "null" and typ == "whole"

    def once():
        outs = ex.forward(is_train=run_backward)
        if run_backward:
            ex.backward([nd.ones(o.shape, dtype=o.dtype) for o in outs])
            for g in ex.grad_dict.values():
                g.asnumpy()
        else:
            for o in outs:
                o.asnumpy()

    once()  # compile
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        once()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def list_gpus():
    return []


def list_tpus():
    import jax
    try:
        return [d.id for d in jax.devices() if d.platform == "tpu"]
    except RuntimeError:
        return []
