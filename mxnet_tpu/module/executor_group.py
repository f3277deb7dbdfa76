"""DataParallelExecutorGroup: per-device executors + batch slicing.

Reference analog: ``python/mxnet/module/executor_group.py`` (_split_input_
slice/_load_data, SURVEY.md §3.1).  On TPU, single-device groups dominate
(multi-chip goes through ``parallel.DataParallelTrainer``'s one-pjit-step
path instead), but the multi-context slicing semantics are kept so
``Module(context=[...])`` and KVStore-based updates behave like the
reference on N devices.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..base import MXNetError
from .. import ndarray as nd
from ..context import Context
from ..io import DataDesc

__all__ = ["DataParallelExecutorGroup", "_split_input_slice"]


def _split_input_slice(batch_size: int, work_load_list: Sequence[float]):
    """Split [0, batch_size) into per-device slices (ref executor_group.py)."""
    total = sum(work_load_list)
    slices = []
    start = 0
    for i, w in enumerate(work_load_list):
        n = int(round(batch_size * w / total)) if i < len(work_load_list) - 1 \
            else batch_size - start
        slices.append(slice(start, start + n))
        start += n
    return slices


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts: List[Context], workload,
                 data_shapes, label_shapes, param_names,
                 for_training, inputs_need_grad, shared_group=None,
                 fixed_param_names=None, grad_req="write", state_names=None,
                 group2ctxs=None, type_dict=None):
        self.symbol = symbol
        self.contexts = contexts
        self.workload = workload or [1.0] * len(contexts)
        self.param_names = param_names
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = set(fixed_param_names or [])
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.data_names = [d.name for d in data_shapes]
        self.label_names = [l.name for l in (label_shapes or [])]
        self.batch_size = data_shapes[0].shape[0]
        self.slices = _split_input_slice(self.batch_size, self.workload)
        self.execs = []
        req = {}
        for n in self.arg_names:
            if n in self.data_names:
                req[n] = "write" if inputs_need_grad else "null"
            elif n in self.label_names or n in self.fixed_param_names \
                    or not for_training:
                req[n] = "null"
            else:
                req[n] = grad_req if isinstance(grad_req, str) \
                    else grad_req.get(n, "write")
        self.grad_req = req
        # group2ctxs: coarse model-parallel placement per data-parallel
        # replica (ref module.py:31 + AssignContext) — a dict applies to
        # every replica, a list gives one dict per context
        if isinstance(group2ctxs, dict) or group2ctxs is None:
            group2ctxs = [group2ctxs] * len(contexts)
        assert len(group2ctxs) == len(contexts), \
            "group2ctxs must match the number of contexts"
        inferred = {}   # replicas of one slice size share one graph walk
        for ctx, slc, g2c in zip(contexts, self.slices, group2ctxs):
            n_i = slc.stop - slc.start
            shapes = {d.name: (n_i,) + d.shape[1:] for d in data_shapes}
            for l in (label_shapes or []):
                shapes[l.name] = (n_i,) + l.shape[1:]
            if n_i not in inferred:
                inferred[n_i] = symbol.infer_shape(**shapes)
            self.execs.append(symbol.simple_bind(ctx=ctx, grad_req=req,
                                                 group2ctx=g2c,
                                                 type_dict=type_dict,
                                                 _inferred=inferred[n_i],
                                                 **shapes))
        self.data_shapes = data_shapes
        self.label_shapes = label_shapes

    # ---- param plumbing -------------------------------------------------
    def set_params(self, arg_params, aux_params, allow_extra=False):
        for ex in self.execs:
            ex.copy_params_from(arg_params, aux_params,
                                allow_extra_params=allow_extra)

    def get_params(self, arg_params, aux_params):
        """Average params over devices into the given dicts (ref behavior)."""
        for name in self.param_names:
            arrs = [ex.arg_dict[name] for ex in self.execs]
            acc = arrs[0].copy()
            for a in arrs[1:]:
                acc += a.as_in_context(acc.context)
            arg_params[name] = acc / len(arrs)
        for name in self.aux_names:
            arrs = [ex.aux_dict[name] for ex in self.execs]
            acc = arrs[0].copy()
            for a in arrs[1:]:
                acc += a.as_in_context(acc.context)
            aux_params[name] = acc / len(arrs)

    # ---- execution ------------------------------------------------------
    def _load_batch(self, data_batch):
        data = data_batch.data
        label = data_batch.label or []
        # single-device fast path: no slicing — a batch the producer
        # already placed on the right device (PrefetchingIter double
        # buffering) passes through untouched (as_in_context is a no-op
        # when the context matches), so the step pays no re-put
        whole = len(self.slices) == 1
        feeds = []
        for i, slc in enumerate(self.slices):
            feed = {}
            for name, arr in zip(self.data_names, data):
                feed[name] = (arr if whole else
                              arr[slc]).as_in_context(self.contexts[i])
            for name, arr in zip(self.label_names, label):
                feed[name] = (arr if whole else
                              arr[slc]).as_in_context(self.contexts[i])
            feeds.append(feed)
        return feeds

    def forward(self, data_batch, is_train=None):
        is_train = self.for_training if is_train is None else is_train
        for ex, feed in zip(self.execs, self._load_batch(data_batch)):
            ex.forward(is_train=is_train, **feed)

    def forward_backward(self, data_batch):
        """Fused path: one XLA program per device per step."""
        for ex, feed in zip(self.execs, self._load_batch(data_batch)):
            ex.forward_backward(**feed)

    def backward(self, out_grads=None):
        for ex in self.execs:
            ex.backward(out_grads)

    def get_outputs(self, merge_multi_context=True):
        if len(self.execs) == 1:
            return self.execs[0].outputs
        if not merge_multi_context:
            return [[ex.outputs[i] for ex in self.execs]
                    for i in range(len(self.execs[0].outputs))]
        out = []
        for i in range(len(self.execs[0].outputs)):
            parts = [ex.outputs[i].as_in_context(self.contexts[0])
                     for ex in self.execs]
            out.append(nd.concatenate(parts, axis=0))
        return out

    def get_input_grads(self, merge_multi_context=True):
        grads = []
        for name in self.data_names:
            parts = [ex.grad_dict.get(name) for ex in self.execs]
            if merge_multi_context and len(parts) > 1:
                grads.append(nd.concatenate(
                    [p.as_in_context(self.contexts[0]) for p in parts], axis=0))
            else:
                grads.append(parts[0] if len(parts) == 1 else parts)
        return grads

    def update_metric(self, eval_metric, labels):
        for i, (ex, slc) in enumerate(zip(self.execs, self.slices)):
            lab = [l[slc] for l in labels]
            eval_metric.update(lab, ex.outputs)

    @property
    def grad_arrays(self):
        """Per-param list of per-device grad arrays (kvstore push format)."""
        return [[ex.grad_dict[n] for ex in self.execs
                 if n in ex.grad_dict] for n in self.param_names]

    @property
    def param_arrays(self):
        return [[ex.arg_dict[n] for ex in self.execs]
                for n in self.param_names]

    @property
    def aux_arrays(self):
        return [[ex.aux_dict[n] for ex in self.execs]
                for n in self.aux_names]
