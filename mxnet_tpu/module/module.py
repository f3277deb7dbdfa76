"""Module: the symbolic training harness over executor groups + KVStore.

Reference analog: ``python/mxnet/module/module.py`` (bind:364,
init_optimizer:473, update:643 — SURVEY.md §3.1): binds a Symbol on a list
of contexts, slices batches, reduces gradients through KVStore, applies the
optimizer either locally or on the kvstore (``update_on_kvstore``).
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional

import numpy as np

from ..base import MXNetError
from .. import ndarray as nd
from .. import optimizer as opt
from .. import kvstore as kvs
from .. import fused_step as _fused
from .. import telemetry as _telemetry
from .. import health as _health
from .. import profiler as _profiler
from ..context import Context, cpu, current_context
from ..initializer import InitDesc
from .base_module import BaseModule
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]

# SparseMoE's auxiliary state (selections each expert got in the last
# step), published where the Module copies auxiliary states to the host
# anyway (get_params): reading it there costs no sync of its own.
_EXPERT_LOAD_SUFFIX = "_expert_load"
_MOE_EXPERT_LOAD = _telemetry.gauge(
    "moe_expert_load",
    "Selections each expert of a SparseMoE layer received in the last "
    "training step (from the op's expert_load auxiliary state, as of the "
    "last Module.get_params)", ("layer", "expert"))


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None,
                 compression_params=None, mesh_axes=None,
                 sharding_rules=None):
        super().__init__(logger)
        if context is None:
            context = [current_context()]
        if isinstance(context, Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list
        self._symbol = symbol
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        arg_names = symbol.list_arguments()
        self._param_names = [n for n in arg_names
                             if n not in self._data_names
                             and n not in self._label_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._arg_params = None
        self._aux_params = None
        self._exec_group: Optional[DataParallelExecutorGroup] = None
        self._optimizer = None
        self._kvstore = None
        self._updater = None
        self._update_on_kvstore = False
        self._grad_req = "write"
        self._group2ctxs = group2ctxs
        self._fused_step = None
        # mesh layout for the GSPMD multi-device fused step: axis sizes
        # (e.g. {"dp": 4, "tp": 2}; default pure-DP over all contexts) and
        # optional parallel.mesh.ShardingRules for the params
        self._mesh_axes = dict(mesh_axes) if mesh_axes else None
        self._sharding_rules = sharding_rules

    def set_mesh(self, mesh_axes, sharding_rules=None):
        """Select the device-mesh layout (axis-name → size) and optional
        parameter ShardingRules for the multi-device fused step.  Takes
        effect on the next update(); the step program is re-specialised
        (new jit-cache key) for the new layout."""
        self._mesh_axes = dict(mesh_axes) if mesh_axes else None
        self._sharding_rules = sharding_rules
        if self._fused_step is not None:
            self._fused_step.on_mesh_change()

    # ---- info -----------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._symbol.list_outputs()

    @property
    def data_shapes(self):
        return self._exec_group.data_shapes

    @property
    def label_shapes(self):
        return self._exec_group.label_shapes

    @property
    def output_shapes(self):
        shapes = {d.name: d.shape for d in self._exec_group.data_shapes}
        for l in (self._exec_group.label_shapes or []):
            shapes[l.name] = l.shape
        _, out_shapes, _ = self._symbol.infer_shape(**shapes)
        return list(zip(self._symbol.list_outputs(), out_shapes))

    # ---- bind / init ----------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if self.binded and not force_rebind:
            return
        from ..io import DataDesc
        data_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                       for d in data_shapes]
        if label_shapes:
            label_shapes = [l if isinstance(l, DataDesc) else DataDesc(*l)
                            for l in label_shapes]
        self._grad_req = grad_req
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        from .. import amp
        type_dict = None
        if amp.enabled():
            # bind-time dtype policy: params/data bf16, labels and
            # normalization scale/shift fp32 (see amp.type_dict_for)
            type_dict = amp.type_dict_for(
                self._symbol, self._data_names,
                [l.name for l in (label_shapes or [])])
        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list, data_shapes,
            label_shapes, self._param_names, for_training, inputs_need_grad,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req,
            group2ctxs=self._group2ctxs, type_dict=type_dict)
        self.binded = True
        if self._arg_params is not None:
            self._exec_group.set_params(self._arg_params, self._aux_params,
                                        allow_extra=True)

    def init_params(self, initializer="__default__", arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before init_params"
        if initializer == "__default__":
            # reference default (base_module.py:629): Uniform(0.01) — a bare
            # init_params() must NOT leave weights at zero (relu nets would
            # never break symmetry); name-based dispatch in Initializer
            # still zeroes biases and sets moving stats correctly
            from .. import initializer as init_mod
            initializer = init_mod.Uniform(0.01)
        ex = self._exec_group.execs[0]
        self._arg_params = {n: ex.arg_dict[n].copyto(cpu())
                            for n in self._param_names}
        self._aux_params = {n: ex.aux_dict[n].copyto(cpu())
                            for n in self._aux_names}
        attrs = self._symbol.attr_dict()

        def _fill(params, source):
            for name, arr in params.items():
                if source is not None and name in source:
                    source[name].copyto(arr)
                elif source is not None and not allow_missing:
                    # reference semantics: a provided param source must cover
                    # every parameter unless allow_missing
                    raise MXNetError("parameter %r missing from provided "
                                     "params (allow_missing=False)" % name)
                elif initializer is not None:
                    initializer(InitDesc(name, attrs.get(name, {})), arr)

        _fill(self._arg_params, arg_params)
        _fill(self._aux_params, aux_params)
        self._exec_group.set_params(self._arg_params, self._aux_params,
                                    allow_extra=allow_extra)
        from .. import memwatch as _memwatch
        if _memwatch.enabled:
            # ledger: the device-resident parameter buffers (every exec's
            # arg/aux dicts) plus the host master copies above — both are
            # live jax buffers and both belong to the params budget
            for e in self._exec_group.execs:
                _memwatch.tag("params", (e.arg_dict, e.aux_dict))
            _memwatch.tag("params", (self._arg_params, self._aux_params),
                          detail="host_master")
        self.params_initialized = True

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return
        if isinstance(optimizer, str):
            batch_size = self._exec_group.batch_size
            if not isinstance(kvstore, str) and kvstore is not None and \
                    "dist" in kvstore.type and "_sync" in kvstore.type:
                batch_size *= kvstore.num_workers
            params = dict(optimizer_params)
            # reference default (module.py init_optimizer): grads are
            # batch-summed, so rescale by 1/batch unless caller overrides
            params.setdefault("rescale_grad", 1.0 / batch_size)
            # one updater-state slot per (param, device); the shared
            # resolver keeps this layout in lockstep with the update paths
            idx2name = opt.Optimizer.build_idx2name(
                self._param_names, len(self._context))
            optimizer = opt.create(optimizer, sym=self._symbol,
                                   param_idx2name=idx2name, **params)
        self._optimizer = optimizer
        kv = kvstore
        if isinstance(kvstore, str):
            kv = kvs.create(kvstore) if kvstore else None
        self._kvstore = kv
        # update_on_kvstore decision (ref model.py:_create_kvstore):
        # dist stores apply updates kvstore-side
        self._update_on_kvstore = bool(kv) and kv.type.startswith("dist")
        self._updater = None if self._update_on_kvstore \
            else opt.get_updater(optimizer)
        if kv:
            if self._update_on_kvstore:
                kv.set_optimizer(optimizer)
            for i, name in enumerate(self._param_names):
                kv.init(name, self._arg_params[name])
                # sync back: on dist stores rank 0's init wins, so every
                # rank must start from the store's value (reference
                # model.py _initialize_kvstore pulls after init)
                if kv.type.startswith("dist"):
                    weights = self._exec_group.param_arrays[i]
                    kv.pull(name, out=weights)
                    kv.pull(name, out=self._arg_params[name])
        self.optimizer_initialized = True
        preload = getattr(self, "_preload_opt_states", None)
        if preload is not None and self._updater is not None:
            with open(preload, "rb") as f:
                self._updater.set_states(f.read())
            self._preload_opt_states = None
        self._fused_step = _fused.ModuleFusedStep(self) \
            if self._updater is not None else None

    # ---- step -----------------------------------------------------------
    def _fused(self):
        """Fused-step driver, recreated after a force_rebind (the driver's
        donation pools and cached programs belong to one executor group)."""
        fs = self._fused_step
        if fs is not None and fs.stale():
            fs = self._fused_step = _fused.ModuleFusedStep(self)
        return fs

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        fs = self._fused()
        if fs is not None:
            fs.flush_eager()
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        fs = self._fused()
        if fs is not None:
            fs.flush_eager()
        self._exec_group.backward(out_grads)

    def forward_backward(self, data_batch):
        fs = self._fused()
        if fs is not None and fs.eligible():
            # defer: update() fuses this batch's fwd+bwd with the
            # optimizer update into one donated XLA program.  Only an
            # un-consumed previous batch forces an eager replay — an
            # unconditional flush would also de-mesh between every pair
            # of mesh steps, breaking the donation chain
            with _profiler.span("Step::stage", "step"):
                if fs.pending:
                    fs.flush_eager()
                fs.stage(data_batch)
            return
        if fs is not None:
            fs.flush_eager()
        self._exec_group.forward_backward(data_batch)

    def update(self):
        """KVStore reduce + optimizer (ref module.py:643-670 + SURVEY 3.1).

        With MXNET_TPU_FUSED_STEP (default ON) and a local updater this
        dispatches the fused whole-step program staged by
        forward_backward; the per-param loop below is the OFF fallback and
        parity oracle.  Note the fused path does not materialize gradients
        in grad_dict (they live only inside the program).

        The whole of it is the span ``Step::update`` (``args.path``: the
        path that served the step), which also feeds
        ``step_update_seconds``: one timing path."""
        assert self.optimizer_initialized
        args = {}
        with _profiler.span("Step::update", "step",
                            histogram=_fused.STEP_TIME, args=args):
            path = args["path"] = self._update()
            if path != "eager":
                args["step"] = self._fused_step.steps
        if _telemetry.enabled:
            _fused.STEP_DISPATCH.labels(path=path).inc()
        if _health.enabled:
            _health.monitor.on_step(
                "mesh_step" if path == "mesh_fused" else
                ("fwdbwd",) if path == "eager" else "step")

    def _update(self):
        """The body of ``update``; returns the path taken (``fused``,
        ``mesh_fused`` or ``eager``)."""
        fs = self._fused()
        if fs is not None and fs.pending and fs.eligible():
            path = fs.step()
            if path:
                return path
        if fs is not None:
            fs.flush_eager()
        eg = self._exec_group
        ndev = len(self._context)
        if self._kvstore is not None:
            # batched push/pull: one call over all param names lets the
            # dist_async wire layer coalesce messages into buckets
            live = [i for i, g in enumerate(eg.grad_arrays) if g]
            names = [self._param_names[i] for i in live]
            grads_l = [eg.grad_arrays[i] for i in live]
            weights_l = [eg.param_arrays[i] for i in live]
            if names:
                self._kvstore.push(names, grads_l)
                if self._update_on_kvstore:
                    self._kvstore.pull(names, out=weights_l)
                else:
                    # pull the reduced gradient back into each device grad
                    self._kvstore.pull(names, out=grads_l)
            if not self._update_on_kvstore:
                for i, grads, weights in zip(live, grads_l, weights_l):
                    for k, (w, g) in enumerate(zip(weights, grads)):
                        # per-device optimizer state, slot resolvable
                        # through idx2name (shared resolver)
                        self._updater(
                            opt.Optimizer.slot_index(i, ndev, k), g, w)
        else:
            for i, (name, grads, weights) in enumerate(
                    zip(self._param_names, eg.grad_arrays, eg.param_arrays)):
                for k, (w, g) in enumerate(zip(weights, grads)):
                    self._updater(
                        opt.Optimizer.slot_index(i, ndev, k), g, w)
        from .. import memwatch as _memwatch
        if _memwatch.enabled:
            # kvstore pull / eager ops repoint grad buffers at fresh
            # program outputs — re-ledger them or the tags die with the
            # old buffers
            for grads in eg.grad_arrays:
                for g in grads or ():
                    _memwatch.tag("activations", g)
        return "eager"

    def get_outputs(self, merge_multi_context=True):
        fs = self._fused()
        if fs is not None:
            outs = fs.mesh_outputs()
            if outs is not None:
                # the mesh step produced full-batch outputs directly — no
                # per-device concat needed (and the per-exec outputs are
                # stale, the program never ran per device)
                return outs if merge_multi_context else [[o] for o in outs]
            fs.flush_eager()
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        fs = self._fused()
        if fs is not None:
            fs.flush_eager()
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        fs = self._fused()
        if fs is not None:
            outs = fs.mesh_outputs()
            if outs is not None:
                eval_metric.update(list(labels), outs)
                return
            fs.flush_eager()
        self._exec_group.update_metric(eval_metric, labels)

    def defer_metric_update(self, eval_metric, labels):
        """Capture this step's outputs/labels and return a zero-arg
        closure performing the metric update LATER — the overlapped fit
        loop (train_loop.OverlappedLoop) runs it a few steps behind
        dispatch so the metric's hard D2H never stalls the next step.
        Returns None when deferring would not be equivalent (multi-device
        eager group, whose outputs are rebound per step)."""
        fs = self._fused()
        if fs is not None:
            outs = fs.mesh_outputs()
            if outs is not None:
                labels = list(labels)
                return lambda: eval_metric.update(labels, outs)
            fs.flush_eager()
        eg = self._exec_group
        if len(eg.execs) != 1:
            return None
        lab = [l[eg.slices[0]] for l in labels]
        outs = list(eg.execs[0].outputs)
        return lambda: eval_metric.update(lab, outs)

    def get_params(self):
        assert self.binded and self.params_initialized
        fs = self._fused()
        if fs is not None:
            # mesh globals back to per-device replicas so the averaging
            # below never mixes 8-device and single-device commitments
            fs.demesh()
        arg, aux = {}, {}
        self._exec_group.get_params(arg, aux)
        if _telemetry.enabled:
            for name, load in aux.items():
                if name.endswith(_EXPERT_LOAD_SUFFIX):
                    layer = name[:-len(_EXPERT_LOAD_SUFFIX)]
                    for e, n in enumerate(load.asnumpy()):
                        _MOE_EXPERT_LOAD.labels(
                            layer=layer, expert=str(e)).set(float(n))
        return arg, aux

    def install_monitor(self, mon):
        for ex in self._exec_group.execs:
            mon.install(ex)

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        from ..model import save_checkpoint
        arg, aux = self.get_params()
        save_checkpoint(prefix, epoch, self._symbol, arg, aux)
        if save_optimizer_states and self._updater is not None:
            with open("%s-%04d.states" % (prefix, epoch), "wb") as f:
                f.write(self._updater.get_states())

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        from ..model import load_checkpoint
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        # loaded params count as initialized (reference module.py:160) —
        # a later fit()/init_params() must NOT re-randomize them
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod
