"""Optimizers (parity: python/mxnet/optimizer.py — registry at :35,112, the
SGD..Nadam zoo at :444-1446, and the ``Updater`` with state (de)serialization
at :1464).  Each dense update dispatches to a fused op from
``ops/optimizer_ops.py`` — one XLA fusion per parameter, matching the
reference's fused optimizer kernels (src/operator/optimizer_op.cc)."""
from __future__ import annotations

import math
import pickle
from typing import Any, Dict, Optional

import numpy as np

from .base import Registry, MXNetError
from . import amp as _amp
from . import ndarray as nd
from .ndarray.ndarray import NDArray

__all__ = ["Optimizer", "SGD", "Signum", "FTML", "DCASGD", "NAG", "SGLD",
           "Adam", "AdaGrad", "RMSProp", "AdaDelta", "Ftrl", "Adamax",
           "Nadam", "LBSGD", "Test", "Updater", "get_updater", "create",
           "register"]

_registry = Registry("optimizer")


def register(klass):
    _registry.register(klass.__name__, klass)
    return klass


class Optimizer:
    """Base optimizer (ref optimizer.py:Optimizer)."""

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count: Dict[int, int] = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.aggregate_num = 0
        self.param_dict = param_dict or {}
        self.idx2name = dict(param_idx2name or {})
        self.sym_info = ()
        if sym is not None:
            self.sym_info = (sym.attr_dict(), sym.list_arguments())
        # reference Optimizer.__init__ applies symbol-attr multipliers
        self.set_lr_mult({})
        self.set_wd_mult({})

    create_optimizer = None  # set below

    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and _amp.is_low_precision(weight.dtype):
            w32 = weight.astype(np.float32)
            state = (self.create_state(index, w32), w32)
        else:
            state = self.create_state(index, weight)
        from . import memwatch as _memwatch
        if _memwatch.enabled and state is not None:
            # every update path (eager Updater, fused step, Trainer mesh)
            # funnels state creation through here — the one ledger hook
            _memwatch.tag("opt_state", state)
        return state

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        """Generic multi-precision step (the fused path's parity oracle):
        the fp32 update runs against the master copy with the fp32-cast
        gradient, then the low-precision weight is re-cast from the new
        master.  Optimizers with dedicated mp kernels (SGD) override."""
        if self._mp_state(weight, state):
            inner, w32 = state
            self.update(index, w32, grad.astype(np.float32), inner)
            w32.copyto(weight)
            return
        self.update(index, weight, grad, state)

    def _mp_state(self, weight, state):
        """Whether ``state`` is the eager multi-precision layout
        ``(inner_state, master_fp32)`` for this low-precision weight."""
        return (self.multi_precision and _amp.is_low_precision(weight.dtype)
                and isinstance(state, tuple) and len(state) == 2
                and isinstance(state[1], NDArray)
                and state[1].dtype == np.float32)

    def set_learning_rate(self, lr):
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr
        name = self.idx2name.get(index, index)
        if name in self.param_dict:
            lr *= self.param_dict[name].lr_mult
        elif name in self.lr_mult:
            lr *= self.lr_mult[name]
        return lr

    def _get_wd(self, index):
        wd = self.wd
        name = self.idx2name.get(index, index)
        if name in self.param_dict:
            wd *= self.param_dict[name].wd_mult
        elif name in self.wd_mult:
            wd *= self.wd_mult[name]
        return wd

    def _common_kwargs(self, index):
        kw = {"lr": self._get_lr(index), "wd": self._get_wd(index),
              "rescale_grad": self.rescale_grad}
        if self.clip_gradient is not None:
            kw["clip_gradient"] = self.clip_gradient
        return kw

    # ---- (param, device) slot resolution --------------------------------
    # The eager updater keys its state (and therefore lr_mult/wd_mult
    # lookups through ``idx2name``) by a flattened (param, device) slot.
    # Both the eager call sites and the fused step must agree on this
    # layout or per-name multipliers silently stop applying on replicas.

    @staticmethod
    def slot_index(param_idx, num_device=1, device=0):
        """Flattened updater-state slot for param ``param_idx`` on device
        ``device`` when weights are replicated over ``num_device`` devices."""
        return param_idx * num_device + device

    @staticmethod
    def build_idx2name(param_names, num_device=1):
        """``idx2name`` covering every (param, device) slot, so
        ``_get_lr``/``_get_wd`` resolve the same name for all replicas."""
        idx2name = {}
        for i, name in enumerate(param_names):
            for k in range(num_device):
                idx2name[Optimizer.slot_index(i, num_device, k)] = name
        return idx2name

    # ---- functional (traceable) core for the fused train step -----------
    # ``fused_update`` is the jit-traceable twin of ``update``: pure jax
    # arrays in, (new_weight, new_state_leaves) out, no NDArray wrappers,
    # no count/lr bookkeeping (the driver resolves lr/wd/t per slot and
    # passes them in, traced, so one compiled program serves every step).

    def supports_fused(self, weight):
        """Whether ``update`` has a traceable twin for this weight."""
        return False

    def fused_state_arity(self):
        """Number of state leaves ``fused_update`` expects/returns."""
        return None

    def fused_update(self, weight, grad, state, lr, wd, rescale, t):
        """Pure update: ``(w, g, state_leaves, lr, wd, rescale, t)`` ->
        ``(new_w, new_state_leaves)``.  All array args are jax values."""
        raise MXNetError("%s has no fused update" % type(self).__name__)

    def fused_mp(self, weight):
        """Whether this weight rides the fused path in multi-precision
        form: low-precision storage with a master-fp32 leaf PREPENDED to
        its flat state tuple, updated via ``fused_update_mp``."""
        return self.multi_precision and _amp.is_low_precision(weight.dtype)

    def fused_update_mp(self, weight, grad, state, lr, wd, rescale, t):
        """Multi-precision twin of ``fused_update``: ``state[0]`` is the
        master-fp32 copy, the rest are the optimizer's own leaves.  The
        update runs in fp32 against the master (grad up-cast first) and
        the low-precision weight is re-cast from the new master — the
        traced mirror of the eager ``update_multi_precision`` oracle."""
        import jax.numpy as jnp
        master = state[0]
        new_master, inner = self.fused_update(
            master, grad.astype(jnp.float32), tuple(state[1:]),
            lr, wd, rescale, t)
        return (new_master.astype(weight.dtype),
                (new_master,) + tuple(inner))

    def fused_slot_lr(self, lr, t):
        """Per-slot learning rate with any host-side correction folded in
        (Adam's f64 bias correction).  The fused drivers capture lr
        through this hook so the traced programs see exactly the lr the
        eager update computes on the host — the master-fp32 trajectory
        stays bit-identical to the eager oracle."""
        return lr

    def atlas_scope_name(self):
        """Name the atlas uses for this optimizer's update stage inside
        fused programs (``Optimizer::<name>``).  Override to disambiguate
        wrappers/subclasses that share a class name."""
        return type(self).__name__

    def _fused_dtype_ok(self, weight):
        # fp32 weights always; low-precision weights only in
        # multi-precision mode, where the update runs in f32 against the
        # master leaf prepended to the state tuple (fused_update_mp).
        # Low-precision WITHOUT a master stays on the eager oracle:
        # traced f32 scalars (lr/wd/t) would promote fp16 arithmetic to
        # f32 where eager weak python floats keep it in fp16.
        return weight.dtype == np.float32 or self.fused_mp(weight)

    def _fused_attrs(self, lr, wd, rescale):
        # clip_gradient must stay a static python float: _prep_grad branches
        # on ``>= 0`` at trace time (-1.0 is the kernels' "disabled" value)
        return {"lr": lr, "wd": wd, "rescale_grad": rescale,
                "clip_gradient": -1.0 if self.clip_gradient is None
                else float(self.clip_gradient)}

    def _update_rows(self, index, weight, grad, state):
        """Lazy update for a row_sparse gradient (reference: the sparse
        FComputeEx optimizer kernels, src/operator/optimizer_op.cc — only
        rows present in ``grad.indices`` are touched): slice the occupied
        rows, run this optimizer's *dense* update on the row block (one XLA
        gather → fused update → scatter), write the rows back."""
        import numpy as _np
        import jax.numpy as jnp
        from .ndarray.sparse import RowSparseNDArray
        idx = grad._sp_indices
        if len(idx) == 0:
            self._update_count(index)
            return
        sparse_weight = isinstance(weight, RowSparseNDArray)
        if sparse_weight:
            # map grad rows to positions inside the weight's value block;
            # every grad row must be present (reference requires the weight's
            # occupancy to cover pushed rows — kvstore pulls them first)
            pos = _np.searchsorted(weight._sp_indices, idx)
            if (pos >= len(weight._sp_indices)).any() or \
                    (weight._sp_indices[_np.minimum(
                        pos, len(weight._sp_indices) - 1)] != idx).any():
                raise MXNetError("row_sparse weight is missing rows present "
                                 "in the gradient; row_sparse_pull them "
                                 "first")
            jidx_w = jnp.asarray(pos)
            w_block = weight._sp_values
        else:
            jidx_w = jnp.asarray(idx)
            w_block = weight._data
        # states are dense full-shape arrays indexed by row id
        jidx = jnp.asarray(idx)

        def rows(a):
            return NDArray(a._data[jidx], a.context) \
                if isinstance(a, NDArray) else a

        w_rows = NDArray(w_block[jidx_w], weight.context)
        g_rows = NDArray(grad._sp_values.astype(weight.dtype), weight.context)
        s_rows = tuple(rows(s) for s in state) if isinstance(state, tuple) \
            else rows(state)
        self.update(index, w_rows, g_rows, s_rows)
        if sparse_weight:
            weight._sp_values = weight._sp_values.at[jidx_w].set(w_rows._data)
        else:
            weight._data = weight._data.at[jidx_w].set(w_rows._data)
        states = state if isinstance(state, tuple) else (state,)
        srows = s_rows if isinstance(s_rows, tuple) else (s_rows,)
        for s, sr in zip(states, srows):
            if isinstance(s, NDArray):
                s._data = s._data.at[jidx].set(sr._data)

    @staticmethod
    def _is_row_sparse(grad):
        from .ndarray.sparse import RowSparseNDArray
        return isinstance(grad, RowSparseNDArray)


@register
class SGD(Optimizer):
    """SGD with momentum and optional multi-precision (ref optimizer.py:444)."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd.zeros(weight.shape, weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        if self._is_row_sparse(grad):
            return self._update_rows(index, weight, grad, state)
        self._update_count(index)
        kw = self._common_kwargs(index)
        if state is not None and isinstance(state, tuple):
            mom, w32 = state
            if mom is not None:
                nd.mp_sgd_mom_update(weight, grad, mom, w32, out=weight,
                                     momentum=self.momentum, **kw)
            else:
                nd.mp_sgd_update(weight, grad, w32, out=weight, **kw)
        elif state is not None:
            nd.sgd_mom_update(weight, grad, state, out=weight,
                              momentum=self.momentum, **kw)
        else:
            nd.sgd_update(weight, grad, out=weight, **kw)

    update_multi_precision = update

    def supports_fused(self, weight):
        return self._fused_dtype_ok(weight)

    def fused_state_arity(self):
        return 1 if self.momentum != 0.0 else 0

    def fused_update(self, weight, grad, state, lr, wd, rescale, t):
        from .ops import optimizer_ops as _ops
        attrs = self._fused_attrs(lr, wd, rescale)
        if state:
            attrs["momentum"] = self.momentum
            w, m = _ops._sgd_mom_update(attrs, weight, grad, state[0])
            return w, (m,)
        return _ops._sgd_update(attrs, weight, grad), ()


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd.zeros(weight.shape, weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        if state is not None:
            nd.signum_update(weight, grad, state, out=weight,
                             momentum=self.momentum, wd_lh=self.wd_lh, **kw)
        else:
            nd.signsgd_update(weight, grad, out=weight, **kw)


@register
class NAG(Optimizer):
    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd.zeros(weight.shape, weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        if state is not None:
            nd.nag_mom_update(weight, grad, state, out=weight,
                              momentum=self.momentum, **kw)
        else:
            nd.sgd_update(weight, grad, out=weight, **kw)

    def supports_fused(self, weight):
        return self._fused_dtype_ok(weight)

    def fused_state_arity(self):
        return 1 if self.momentum != 0.0 else 0

    def fused_update(self, weight, grad, state, lr, wd, rescale, t):
        from .ops import optimizer_ops as _ops
        attrs = self._fused_attrs(lr, wd, rescale)
        if state:
            attrs["momentum"] = self.momentum
            w, m = _ops._nag_mom_update(attrs, weight, grad, state[0])
            return w, (m,)
        return _ops._sgd_update(attrs, weight, grad), ()


@register
class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.context, dtype=weight.dtype),
                nd.zeros(weight.shape, weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        if self._is_row_sparse(grad):
            return self._update_rows(index, weight, grad, state)
        self._update_count(index)
        t = self._index_update_count[index]
        kw = self._common_kwargs(index)
        # bias correction folded into lr (reference Adam.update)
        kw["lr"] *= math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)
        mean, var = state
        nd.adam_update(weight, grad, mean, var, out=weight,
                       beta1=self.beta1, beta2=self.beta2,
                       epsilon=self.epsilon, **kw)

    def supports_fused(self, weight):
        return self._fused_dtype_ok(weight)

    def fused_state_arity(self):
        return 2

    def fused_slot_lr(self, lr, t):
        # bias correction folded into lr exactly as the eager update does
        # it — host-side f64, so the traced program and the eager oracle
        # consume bit-identical lr values.  t is a per-slot host count at
        # capture time; the correction never enters the trace.
        return lr * math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)

    def fused_update(self, weight, grad, state, lr, wd, rescale, t):
        from .ops import optimizer_ops as _ops
        attrs = self._fused_attrs(lr, wd, rescale)
        attrs.update(beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon)
        mean, var = state
        w, m, v = _ops._adam_update(attrs, weight, grad, mean, var)
        return w, (m, v)


@register
class FTML(Optimizer):
    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        z = nd.zeros(weight.shape, weight.context, dtype=weight.dtype)
        return (nd.zeros(weight.shape, weight.context, dtype=weight.dtype),
                nd.zeros(weight.shape, weight.context, dtype=weight.dtype), z)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        kw = self._common_kwargs(index)
        d, v, z = state
        nd.ftml_update(weight, grad, d, v, z, out=weight, beta1=self.beta1,
                       beta2=self.beta2, epsilon=self.epsilon, t=t, **kw)


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.epsilon = epsilon
        self.centered = centered
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        z = lambda: nd.zeros(weight.shape, weight.context, dtype=weight.dtype)
        if self.centered:
            return (z(), z(), z())
        return (z(),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common_kwargs(index)
        if self.clip_weights:
            kw["clip_weights"] = self.clip_weights
        if self.centered:
            n, g, delta = state
            nd.rmspropalex_update(weight, grad, n, g, delta, out=weight,
                                  gamma1=self.gamma1, gamma2=self.gamma2,
                                  epsilon=self.epsilon, **kw)
        else:
            (n,) = state
            nd.rmsprop_update(weight, grad, n, out=weight, gamma1=self.gamma1,
                              epsilon=self.epsilon, **kw)

    def supports_fused(self, weight):
        return self._fused_dtype_ok(weight)

    def fused_state_arity(self):
        return 3 if self.centered else 1

    def fused_update(self, weight, grad, state, lr, wd, rescale, t):
        from .ops import optimizer_ops as _ops
        attrs = self._fused_attrs(lr, wd, rescale)
        attrs.update(gamma1=self.gamma1, epsilon=self.epsilon,
                     clip_weights=-1.0 if not self.clip_weights
                     else float(self.clip_weights))
        if self.centered:
            attrs["gamma2"] = self.gamma2
            n, g, delta = state
            w, nn, ng, ndelta = _ops._rmspropalex_update(
                attrs, weight, grad, n, g, delta)
            return w, (nn, ng, ndelta)
        (n,) = state
        w, nn = _ops._rmsprop_update(attrs, weight, grad, n)
        return w, (nn,)


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.context, dtype=weight.dtype),
                nd.zeros(weight.shape, weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        if self._is_row_sparse(grad):
            return self._update_rows(index, weight, grad, state)
        self._update_count(index)
        kw = self._common_kwargs(index)
        z, n = state
        nd.ftrl_update(weight, grad, z, n, out=weight, lamda1=self.lamda1,
                       beta=self.beta, **kw)


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return nd.zeros(weight.shape, weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        if self._is_row_sparse(grad):
            return self._update_rows(index, weight, grad, state)
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        g = g + wd * weight
        state += g * g
        weight -= lr * g / (state.sqrt() + self.float_stable_eps)


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.context),
                nd.zeros(weight.shape, weight.context))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        acc_g, acc_delta = state
        acc_g[:] = self.rho * acc_g + (1 - self.rho) * g * g
        delta = (acc_delta + self.epsilon).sqrt() / \
            (acc_g + self.epsilon).sqrt() * g
        acc_delta[:] = self.rho * acc_delta + (1 - self.rho) * delta * delta
        weight[:] = weight - delta - wd * weight


@register
class Adamax(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.context),
                nd.zeros(weight.shape, weight.context))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index) / (1.0 - self.beta1 ** t)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        m, u = state
        m[:] = self.beta1 * m + (1 - self.beta1) * g
        u[:] = nd.maximum(self.beta2 * u, g.abs())
        weight -= lr * m / u


@register
class Nadam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.context),
                nd.zeros(weight.shape, weight.context))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        mt = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        mtn = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule *= mt
        sched_next = self.m_schedule * mtn
        m, v = state
        m[:] = self.beta1 * m + (1 - self.beta1) * g
        v[:] = self.beta2 * v + (1 - self.beta2) * g * g
        g_prime = g / (1 - self.m_schedule)
        m_prime = m / (1 - sched_next)
        v_prime = v / (1 - self.beta2 ** t)
        weight -= lr * (mtn * m_prime + (1 - mt) * g_prime) / \
            (v_prime.sqrt() + self.epsilon)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (ref optimizer.py SGLD)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        weight[:] = weight - lr / 2 * (g + wd * weight) + \
            nd.random.normal(0, math.sqrt(lr), shape=weight.shape,
                             dtype=weight.dtype)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (ref optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (nd.zeros(weight.shape, weight.context), weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        mom, prev = state
        comp = g + wd * weight + self.lamda * g * g * (weight - prev)
        if mom is not None:
            mom[:] = self.momentum * mom - lr * comp
            update = mom
        else:
            update = -lr * comp
        prev[:] = weight
        weight += update


@register
class LBSGD(SGD):
    """Large-batch SGD with LARS-style warmup (ref optimizer.py LBSGD);
    dense path delegates to SGD with the layer-wise-scaled lr."""

    def __init__(self, warmup_strategy="linear", warmup_epochs=5,
                 batch_scale=1, updates_per_epoch=32, begin_epoch=0,
                 num_epochs=60, **kwargs):
        super().__init__(**kwargs)
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = batch_scale
        self.updates_per_epoch = updates_per_epoch


@register
class Test(Optimizer):
    def create_state(self, index, weight):
        return nd.zeros(weight.shape, weight.context)

    def update(self, index, weight, grad, state):
        weight += grad * self.rescale_grad
        state[:] = weight


def fused_state_leaves(state, mp=False):
    """Flatten an updater state into a tuple of NDArray leaves for the
    fused step (``None`` -> ``()``); returns ``None`` when the structure
    isn't fusable, signalling fallback to the eager oracle.

    With ``mp=True`` the state must be the eager multi-precision layout
    ``(inner_state, master_fp32)``; the flat fused layout PREPENDS the
    master — ``(master, *inner_leaves)`` — matching what
    ``fused_update_mp`` consumes and returns.  (The master can't ride
    LAST: ``fused_update_mp`` slices ``state[1:]`` for the wrapped
    optimizer, and a positional convention keeps the slot shape
    independent of the inner arity.)
    """
    if mp:
        if not (isinstance(state, (tuple, list)) and len(state) == 2
                and isinstance(state[1], NDArray)):
            return None
        inner = fused_state_leaves(state[0])
        if inner is None:
            return None
        return (state[1],) + inner
    if state is None:
        return ()
    if isinstance(state, NDArray):
        return (state,)
    if isinstance(state, (tuple, list)):
        leaves = []
        for s in state:
            if not isinstance(s, NDArray):
                return None
            leaves.append(s)
        return tuple(leaves)
    return None


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    return _registry.get(name)(**kwargs)


Optimizer.create_optimizer = staticmethod(create)


class Updater:
    """Callable (index, grad, weight) applying the optimizer with per-index
    state, (de)serializable (ref optimizer.py:1464)."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states: Dict[Any, Any] = {}
        self.states_synced: Dict[Any, bool] = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index, weight)
            self.states_synced[index] = True
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])
        from . import memwatch as _memwatch
        if _memwatch.enabled:
            # eager updates repoint weight/state handles at fresh program
            # outputs each step — re-ledger them or the tags die with the
            # old buffers
            _memwatch.tag("params", weight)
            if self.states[index] is not None:
                _memwatch.tag("opt_state", self.states[index])

    def set_states(self, states):
        states = pickle.loads(states) if isinstance(states, bytes) else states
        if isinstance(states, tuple) and len(states) == 2:
            states, self.optimizer.num_update = states

        def to_nd(s):
            if isinstance(s, np.ndarray):
                return nd.array(s)
            if isinstance(s, (tuple, list)):
                return type(s)(to_nd(x) for x in s)
            return s

        self.states = {k: to_nd(v) for k, v in states.items()}
        self.states_synced = {k: True for k in self.states}

    def get_states(self, dump_optimizer=False):
        def to_np(s):
            if isinstance(s, NDArray):
                return s.asnumpy()
            if isinstance(s, (tuple, list)):
                return type(s)(to_np(x) for x in s)
            return s
        states = {k: to_np(v) for k, v in self.states.items()}
        return pickle.dumps((states, self.optimizer.num_update)
                            if dump_optimizer else states)


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)
