"""bf16 mixed-precision policy for the fused training stack.

The TPU's MXU multiplies natively in bfloat16: storing params and
activations in bf16 halves their HBM footprint (visible on the memwatch
owner ledger) and roughly doubles effective matmul throughput on real
chips.  This module is the single source of the dtype policy, gated by
``MXNET_TPU_BF16`` (default OFF):

- params, activations and gradients are bf16;
- every trained low-precision weight carries a master-fp32 copy in its
  optimizer state (``Optimizer.create_state_multi_precision``), the
  update runs in fp32 against the master, and the bf16 weight is re-cast
  from the new master (``Optimizer.fused_update_mp`` on the fused path,
  the generic ``update_multi_precision`` as the eager parity oracle);
- loss reduction, softmax, batchnorm statistics and normalization
  scale/shift (``*_gamma``/``*_beta``) stay fp32, and so does what decides
  a sparse expert layer's routing (``*_router_weight``, ``*_expert_bias``):
  a selection that flips with bf16 rounding sends a token to other experts.

The flag is read at BIND time (it decides array dtypes) and joins every
fused-program jit-cache key through ``Executor.STEP_ENV_KEYS`` (GL001),
so a mid-process toggle recompiles instead of serving a stale program.
Traced code never reads it — op-level behavior is driven purely by input
dtypes (GL002), e.g. BatchNorm's f32-accumulated-stats fast path keys on
``data.dtype``.
"""
from __future__ import annotations

import os

import numpy as np

from .base import dtype_name

__all__ = ["ENV_FLAG", "enabled", "is_low_precision", "compute_dtype",
           "type_dict_for"]

ENV_FLAG = "MXNET_TPU_BF16"

# dtypes that carry a master-fp32 copy through the optimizer
_LOW_PRECISION = ("bfloat16", "float16")

# arguments that stay fp32 under the policy, by the end of their name:
# normalization gains/shifts, SparseMoE's router and selection bias
_FP32_SUFFIXES = ("_gamma", "_beta", "_router_weight", "_expert_bias")


def enabled():
    """MXNET_TPU_BF16 gate; default OFF."""
    return os.environ.get(ENV_FLAG, "0").lower() not in \
        ("0", "false", "off", "")


def is_low_precision(dtype):
    """Whether ``dtype`` is a storage dtype that needs an fp32 master."""
    try:
        return dtype_name(dtype) in _LOW_PRECISION
    except TypeError:
        return False


def compute_dtype():
    """The low-precision storage/compute dtype of the policy (bf16 —
    ml_dtypes registers it with numpy, so ``np.dtype`` round-trips)."""
    import jax.numpy as jnp
    return np.dtype(jnp.bfloat16)


def type_dict_for(symbol, data_names, label_names):
    """Binding ``type_dict`` for a symbol under the bf16 policy.

    Data and weights go bf16 (grads inherit the arg dtype at bind, so
    backward runs bf16 too); labels stay fp32 (the loss head reduces in
    fp32) as do ``*_gamma``/``*_beta`` normalization params — their
    per-channel scale math is fp32-accumulated regardless of activation
    dtype, and keeping them fp32 costs nothing (channel-sized) — and a
    sparse expert layer's router and selection bias (``_FP32_SUFFIXES``).
    Aux states (moving stats, expert load) are fp32 by ``infer_type``
    default.
    """
    bf16 = compute_dtype()
    label_set = set(label_names or ())
    td = {}
    for n in symbol.list_arguments():
        if n in label_set or n.endswith(_FP32_SUFFIXES):
            td[n] = np.float32
        else:
            td[n] = bf16
    return td
