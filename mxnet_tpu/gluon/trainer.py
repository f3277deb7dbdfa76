"""Gluon Trainer: imperative data-parallel optimization.

Reference analog: ``python/mxnet/gluon/trainer.py`` (``Trainer:27``, kvstore
init ``:153``, ``step:217``, ``_allreduce_grads:267-275``, ``_update:310``).

TPU-native notes: on a single host the cross-device gradient reduce rides
XLA (KVStore ``device`` = add-chain the compiler lowers to ICI all-reduce on
a pod mesh); the fused-optimizer update kernels are the ``optimizer_op.cc``
analogs in :mod:`mxnet_tpu.ops.optimizer_ops`, executed one XLA program per
parameter.
"""
from __future__ import annotations

import time

from ..base import MXNetError
from .. import optimizer as opt
from .. import kvstore as kvs
from .. import telemetry as _telemetry
from .. import fused_step as _fused
from .. import health as _health
from .parameter import ParameterDict, Parameter

__all__ = ["Trainer"]

_STEPS = _telemetry.counter(
    "trainer_steps_total", "Optimization steps taken by gluon.Trainer")
_SYNC_LAT = _telemetry.histogram(
    "trainer_grad_sync_seconds",
    "Gradient push/pull (allreduce) latency per Trainer step")


class Trainer:
    """Applies an Optimizer on a set of Parameters."""

    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, got %s."
                % type(params))
        self._params = []
        for param in params:
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    "got list of %s." % type(param))
            self._params.append(param)
        self._compression_params = compression_params
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._contexts = self._check_contexts()
        self._init_optimizer(optimizer, optimizer_params)
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = update_on_kvstore
        self._kvstore_name = kvstore
        self._fused_update = None
        self._mesh_update = None

    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx()
            if contexts is not None and contexts != ctx:
                raise ValueError(
                    "All Parameters must be initialized on the same set of "
                    "contexts, but Parameter %s is initialized on %s while "
                    "previous Parameters are initialized on %s." % (
                        param.name, str(ctx), str(contexts)))
            contexts = ctx
        return contexts

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            if optimizer_params and list(optimizer_params) != ["rescale_grad"]:
                raise ValueError(
                    "optimizer_params must be None if optimizer is an "
                    "instance of Optimizer instead of str")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(
                optimizer, param_dict=param_dict, **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)
                          for _ in self._contexts]

    def _init_kvstore(self):
        config = self._kvstore_name
        if config is None or (isinstance(config, str) and config == "None"):
            kvstore = None
            update_on_kvstore = False
        elif isinstance(config, kvs.KVStore):
            kvstore = config
            update_on_kvstore = self._update_on_kvstore
        else:
            arg_arrays = {}
            kvstore, update_on_kvstore = _create_kvstore(
                config, len(self._contexts), arg_arrays)
            if self._update_on_kvstore is not None:
                update_on_kvstore = self._update_on_kvstore
        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            if update_on_kvstore is None:
                update_on_kvstore = "dist" in kvstore.type
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
            for i, param in enumerate(self._params):
                param_arrays = param.list_data()
                kvstore.init(i, param_arrays[0])
                if update_on_kvstore:
                    kvstore.pull(i, param_arrays, priority=-i)
        else:
            update_on_kvstore = False
        self._kvstore = kvstore
        self._update_on_kvstore = bool(update_on_kvstore)
        self._kv_initialized = True

    @property
    def learning_rate(self):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning(
                "Optimizer has to be defined before its learning rate can "
                "be accessed.")
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning(
                "Optimizer has to be defined before its learning rate is "
                "mutated.")
        self._optimizer.lr = lr

    def step(self, batch_size, ignore_stale_grad=False):
        """Make one optimization step: allreduce grads then update.

        On local multi-device (a local kvstore, distinct devices) the
        two phases fuse into ONE GSPMD program over a ``dp`` mesh — raw
        per-device gradients are adopted zero-copy as batch shards and XLA
        inserts the all-reduce — so the host-side kvstore push/pull never
        runs; the KVStore remains the cross-host transport only."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        if self._mesh_update is None:
            self._mesh_update = _fused.TrainerMeshUpdate(self)
        mu = self._mesh_update
        if mu.eligible():
            tel = _telemetry.enabled
            t0 = time.perf_counter() if tel else 0.0
            if mu.step():
                if tel:
                    _fused.STEP_DISPATCH.labels(path="mesh_fused").inc()
                    _fused.STEP_TIME.observe(time.perf_counter() - t0)
                    _STEPS.inc()
                if _health.enabled:
                    _health.monitor.on_step("trainer_mesh_update")
                return
        self._allreduce_grads()
        self._update(ignore_stale_grad)
        if _telemetry.enabled:
            _STEPS.inc()
        if _health.enabled:
            _health.monitor.on_step("trainer_update")

    def fit_epoch(self, data_iter, step_fn, block_fn=None, depth=None):
        """Drive one epoch with dispatch and blocking tails overlapped
        (train_loop.run_epoch): ``step_fn(batch)`` runs fwd/bwd +
        ``self.step`` and returns an async handle (e.g. the loss);
        ``block_fn(handle, i)`` — optional — is the hard-blocking tail
        (loss D2H, logging), deferred ``depth`` steps behind dispatch so
        the device pipeline stays full.  Returns batches consumed.

        When ``MXNET_CKPT_DIR``/``MXNET_CKPT_EVERY_N_STEPS`` are set the
        step is wrapped with donation-safe async checkpointing: on the
        first call the latest committed checkpoint (if any) is restored,
        and thereafter every due step snapshots params + optimizer state
        to host memory before the next step can donate the buffers.  A
        SIGTERM (preemption notice) triggers a final synchronous
        checkpoint followed by a clean ``SystemExit(0)``."""
        from ..train_loop import run_epoch
        from .. import chaos as _chaos
        from .. import checkpoint as _ckpt
        if not hasattr(self, "_ft_ckpt"):
            self._ft_ckpt = _ckpt.TrainCheckpointer.from_env()
            self._global_step = 0
            if self._ft_ckpt is not None:
                _ckpt.install_preempt_handler()
                latest = self._ft_ckpt.latest()
                if latest is not None:
                    tree, meta, blobs = self._ft_ckpt.load(latest)
                    self._ft_restore(tree, meta, blobs)
                    self._global_step = int(meta.get("global_step", 0))
        ckpt = self._ft_ckpt
        if ckpt is None and not _chaos.active():
            return run_epoch(data_iter, step_fn, block_fn=block_fn,
                             depth=depth)

        def _step(batch):
            out = step_fn(batch)
            self._global_step += 1
            gstep = self._global_step
            _chaos.step(gstep)
            if ckpt is not None:
                if _ckpt.preempted():
                    ckpt.save_sync(gstep, *self._ft_snapshot(gstep))
                    ckpt.close()
                    raise SystemExit(0)
                if ckpt.due(gstep):
                    ckpt.maybe_save(gstep, *self._ft_snapshot(gstep))
            return out

        return run_epoch(data_iter, _step, block_fn=block_fn, depth=depth)

    # ---- fault-tolerant training state ----------------------------------
    def _ft_snapshot(self, gstep):
        """Host-side copy of params + optimizer state for the async
        checkpointer.  Safe against donation: TrainerMeshUpdate scatters
        updated shards back to per-device arrays after every step, and
        ``asnumpy`` below forces the D2H copy before the next dispatch."""
        tree = {}
        for i, param in enumerate(self._params):
            tree["param/%d/%s" % (i, param.name)] = \
                param.list_data()[0].asnumpy()
        meta = {"global_step": int(gstep)}
        blobs = {}
        if not self._update_on_kvstore and getattr(self, "_updaters", None):
            blobs["opt_states.bin"] = self._updaters[0].get_states(
                dump_optimizer=False)
            # per-slot update counts are not part of get_states; without
            # them an Adam resume restarts bias correction at t=0
            meta["index_update_count"] = {
                str(k): int(v)
                for k, v in self._optimizer._index_update_count.items()}
            meta["num_update"] = int(self._optimizer.num_update)
        return tree, meta, blobs

    def _ft_restore(self, tree, meta, blobs):
        from .. import ndarray as _nd
        for i, param in enumerate(self._params):
            key = "param/%d/%s" % (i, param.name)
            if key not in tree:
                raise MXNetError(
                    "checkpoint is missing parameter %r" % key)
            cur = param.list_data()[0]
            restored = tree[key]
            if tuple(restored.shape) != tuple(cur.shape):
                raise MXNetError(
                    "checkpoint shape mismatch for %r: saved %s, model %s"
                    % (key, tuple(restored.shape), tuple(cur.shape)))
            param.set_data(_nd.array(restored, dtype=restored.dtype))
        states = (blobs or {}).get("opt_states.bin")
        if states is not None and getattr(self, "_updaters", None):
            for updater in self._updaters:
                updater.set_states(states)
                updater.optimizer = self._updaters[0].optimizer
            self._optimizer = self._updaters[0].optimizer
            counts = meta.get("index_update_count") or {}
            self._optimizer._index_update_count = {
                (int(k) if str(k).lstrip("-").isdigit() else k): int(v)
                for k, v in counts.items()}
            if "num_update" in meta:
                self._optimizer.num_update = int(meta["num_update"])

    def allreduce_grads(self):
        """Reduce gradients over devices only (then call update())."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise AssertionError(
                "allreduce_grads() when parameters are updated on kvstore "
                "is not supported. Try setting `update_on_kvstore` to False "
                "when creating trainer.")
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        tel = _telemetry.enabled
        t0 = time.perf_counter() if tel else 0.0
        # batched push/pull over every live param: one call lets the
        # dist_async wire layer coalesce per-key traffic into buckets
        live = [i for i, p in enumerate(self._params)
                if p.grad_req != "null"]
        if live:
            grads = [self._params[i].list_grad() for i in live]
            self._kvstore.push(live, grads)
            if not self._update_on_kvstore:
                self._kvstore.pull(live, out=grads)
        if tel:
            _SYNC_LAT.observe(time.perf_counter() - t0)
            if _health.enabled:
                _health.monitor.note_phase(
                    "sync", time.perf_counter() - t0)

    def update(self, batch_size, ignore_stale_grad=False):
        """Update parameters only (after allreduce_grads)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise AssertionError(
                "update() when parameters are updated on kvstore is not "
                "supported. Try setting `update_on_kvstore` to False when "
                "creating trainer.")
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        tel = _telemetry.enabled
        t0 = time.perf_counter() if tel else 0.0
        if not self._update_on_kvstore:
            if self._fused_update is None:
                self._fused_update = _fused.TrainerFusedUpdate(self)
            fu = self._fused_update
            if fu.eligible() and fu.step():
                if tel:
                    _fused.STEP_DISPATCH.labels(path="fused").inc()
                    _fused.STEP_TIME.observe(time.perf_counter() - t0)
                return
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if self._update_on_kvstore:
                self._kvstore.pull(i, param.list_data(), priority=-i)
                continue
            for upd, arr, grad in zip(
                    self._updaters, param.list_data(), param.list_grad()):
                upd(i, grad, arr)
        if tel:
            _fused.STEP_DISPATCH.labels(path="eager").inc()
            _fused.STEP_TIME.observe(time.perf_counter() - t0)

    def save_states(self, fname):
        """Save optimizer (updater) states to a file."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        """Load optimizer (updater) states from a file."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
        else:
            with open(fname, "rb") as f:
                states = f.read()
            for updater in self._updaters:
                updater.set_states(states)
                updater.optimizer = self._updaters[0].optimizer
            self._optimizer = self._updaters[0].optimizer


def _create_kvstore(kvstore, num_device, arg_params):
    """Create kvstore from str config (analog of model._create_kvstore)."""
    update_on_kvstore = False
    if kvstore is None:
        kv = None
    elif isinstance(kvstore, kvs.KVStore):
        kv = kvstore
    elif isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore:
            kv = None
        else:
            kv = kvs.create(kvstore)
            if "dist" in kvstore:
                update_on_kvstore = True
    else:
        raise TypeError("kvstore must be KVStore, str or None")
    return kv, update_on_kvstore
