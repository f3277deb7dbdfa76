"""Runtime kernel compilation (parity: ``python/mxnet/rtc.py`` over
SURVEY.md N21).

Reference analog: ``CudaModule``/``CudaKernel`` (include/mxnet/rtc.h:39-118,
src/common/rtc.cc) — the user supplies CUDA C source at runtime, NVRTC
compiles it, and the kernel launches on NDArrays from Python.

TPU-native equivalent: the user supplies **Pallas** kernel source (Python,
using ``jax.experimental.pallas``) — the TPU's runtime-compilation path.
``PallasModule(source).get_kernel(name, out_shape=..., out_dtype=...)``
returns a launchable kernel; ``kernel.launch(args, grid=...)`` runs it on
NDArrays, compiling on first use (XLA/Mosaic), exactly the CudaModule
ergonomics with the vendor compiler swapped for Mosaic.  ``CudaModule`` is
kept as a hard-erroring alias so reference code fails with a clear message.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

from .base import MXNetError

__all__ = ["PallasModule", "PallasKernel", "CudaModule"]


class PallasModule:
    """A module of Pallas kernels compiled from Python source or given as
    callables (the CudaModule analog)."""

    def __init__(self, source=None, exports=(), functions=None):
        self._fns: Dict[str, object] = {}
        if functions:
            self._fns.update(functions)
        if source is not None:
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            ns = {"jax": jax, "jnp": jnp, "pl": pl, "np": np}
            try:
                from jax.experimental.pallas import tpu as pltpu
                ns["pltpu"] = pltpu
            except ImportError:
                pass
            preset = set(ns)
            exec(compile(source, "<pallas_module>", "exec"), ns)
            names = list(exports) if exports else \
                [k for k, v in ns.items()
                 if k not in preset and not k.startswith("_")
                 and callable(v)]
            for name in names:
                if name not in ns or not callable(ns[name]):
                    raise MXNetError("exported kernel %r not found in "
                                     "module source" % name)
                self._fns[name] = ns[name]

    def get_kernel(self, name, out_shape=None, out_dtype=np.float32,
                   grid=None, signature=None):
        """Get a launchable kernel.  ``signature`` (the CUDA C prototype in
        the reference) is accepted and ignored; shapes come from
        ``out_shape``/``launch``."""
        fn = self._fns.get(name)
        if fn is None:
            raise MXNetError("kernel %r not found (have %s)"
                             % (name, sorted(self._fns)))
        return PallasKernel(fn, name, out_shape, out_dtype, grid)


class PallasKernel:
    """One launchable Pallas kernel (the CudaKernel analog)."""

    def __init__(self, fn, name, out_shape=None, out_dtype=np.float32,
                 grid=None):
        self._fn = fn
        self.name = name
        self._out_shape = out_shape
        self._out_dtype = out_dtype
        self._grid = grid
        self._compiled = {}

    def launch(self, args: Sequence, ctx=None, grid=None, out_shape=None,
               out_dtype=None, interpret: Optional[bool] = None):
        """Run the kernel on NDArray inputs, returning an NDArray.

        Compiles per input-shape on first launch (the reference's per-device
        module load + launch, rtc.py CudaKernel.launch — grid/block become
        the Pallas ``grid``).
        """
        import jax
        from jax.experimental import pallas as pl
        from . import ndarray as nd

        arrays = [a._data if isinstance(a, nd.NDArray) else
                  jax.numpy.asarray(a) for a in args]
        oshape = out_shape or self._out_shape
        if oshape is None:
            if not arrays:
                raise MXNetError("PallasKernel.launch: out_shape is "
                                 "required for zero-argument kernels")
            oshape = arrays[0].shape
        oshape = tuple(oshape)
        odtype = np.dtype(out_dtype or self._out_dtype)
        g = grid if grid is not None else self._grid
        if g is not None and not isinstance(g, int):
            g = tuple(g)
        if interpret is None:
            # Mosaic compiles for TPU only: interpret wherever the
            # operands do not live on one
            dev = (next(iter(arrays[0].devices())) if arrays
                   else jax.devices()[0])
            interpret = dev.platform != "tpu"
        key = (tuple((a.shape, str(a.dtype)) for a in arrays), oshape,
               str(odtype), g, interpret)
        call = self._compiled.get(key)
        if call is None:
            kw = {"out_shape": jax.ShapeDtypeStruct(oshape, odtype),
                  "interpret": interpret}
            if g is not None:
                kw["grid"] = g
            call = jax.jit(pl.pallas_call(self._fn, **kw))
            self._compiled[key] = call
        out = call(*arrays)
        octx = args[0]._ctx if args and isinstance(args[0], nd.NDArray) \
            else None
        return nd.NDArray(out, octx)


class CudaModule:
    """Reference-API stub: CUDA RTC does not exist on TPU."""

    def __init__(self, *a, **kw):
        raise MXNetError(
            "CudaModule (NVRTC) is a GPU feature; on TPU use "
            "mx.rtc.PallasModule with a Pallas kernel — same "
            "runtime-compilation workflow, Mosaic instead of NVRTC")
