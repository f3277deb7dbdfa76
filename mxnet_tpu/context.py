"""Device context, TPU-first.

Re-design of the reference's ``Context`` (``python/mxnet/context.py``,
``include/mxnet/base.h`` device enum).  The device enum gains ``tpu`` as the
primary accelerator type; ``gpu`` is accepted for source compatibility and is
aliased to the platform accelerator so reference scripts that say
``mx.gpu(0)`` run unchanged on a TPU host.

Mapping to hardware: a ``Context`` resolves to a concrete ``jax.Device``.
``cpu(i)`` maps to host platform device *i* (with
``--xla_force_host_platform_device_count=N`` the host exposes N virtual
devices, which is how multi-device unit tests run without a pod).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus"]


class Context:
    """Device context holding device type and id.

    Parity target: ``mxnet.context.Context`` — usable as a scope
    (``with mx.tpu(0):``), comparable, hashable.
    """

    # devtype enum kept numerically compatible with the reference
    # (include/mxnet/base.h: kCPU=1, kGPU=2, kCPUPinned=3) + kTPU=4.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu", 5: "cpu_shared"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None

    @property
    def device_type(self) -> str:
        return self.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = self._old_ctx

    # ---- JAX device resolution -------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete jax.Device.

        cpu → host platform device; tpu/gpu → platform accelerator.  In a
        run with no accelerator at all (the CPU test suite) ``tpu(i)`` and
        ``gpu(i)`` resolve to host devices, so reference scripts and tests
        run unchanged.  Where the accelerator IS present a device id past
        its last device raises: ``tpu(3)`` on a one-chip host is an error,
        never chip 0.  Callers that must know where an array landed ask
        the array (``arr.devices()``), not the context.
        """
        dev_type = self.device_type
        if dev_type in ("cpu_pinned", "cpu_shared"):
            dev_type = "cpu"
        if dev_type == "gpu":  # alias: accelerator of the platform
            dev_type = _accelerator_platform()
        # multi-process: a context addresses THIS process's devices (the
        # reference's per-worker device numbering)
        try:
            devs = jax.local_devices(backend=dev_type)
        except RuntimeError:
            devs = jax.local_devices()
        if devs[0].platform != "cpu" and self.device_id >= len(devs):
            raise MXNetError(
                "%s: this process addresses %d %s device(s)"
                % (self, len(devs), devs[0].platform))
        return devs[self.device_id % len(devs)]

    def empty_cache(self):
        """Parity with Context.empty_cache; XLA manages HBM pools itself."""
        return None


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    """Source-compat alias: ``mx.gpu(i)`` targets the platform accelerator."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def _accelerator_platform() -> str:
    import os
    allowed = os.environ.get("JAX_PLATFORMS", "")
    allowed = [p.strip() for p in allowed.split(",") if p.strip()] or None
    for p in ("tpu", "gpu"):
        if allowed is not None and p not in allowed:
            continue
        try:
            if jax.devices(p):
                return p
        except RuntimeError:
            continue
    return "cpu"


def num_gpus() -> int:
    """Number of accelerator devices THIS process addresses (reference:
    mx.context.num_gpus — per-worker device count, matching jax_device's
    local resolution)."""
    plat = _accelerator_platform()
    if plat == "cpu":
        return 0
    return len(jax.local_devices(backend=plat))


def num_tpus() -> int:
    """Number of TPU devices this process addresses; 0 without a TPU."""
    try:
        return len(jax.local_devices(backend="tpu"))
    except RuntimeError:
        return 0


def current_context() -> Context:
    ctx = getattr(Context._default_ctx, "value", None)
    if ctx is None:
        # default context is the accelerator if present, else cpu —
        # TPU-first: unlike the reference (cpu default), an available TPU
        # is the default compute device.
        ctx = cpu(0) if _accelerator_platform() == "cpu" else tpu(0)
        Context._default_ctx.value = ctx
    return ctx


Context.default_ctx = property(lambda self: current_context())
