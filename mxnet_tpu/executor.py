"""Executor: a bound symbolic graph, compiled whole by XLA.

Reference analog: ``include/mxnet/executor.h`` + ``src/executor/
graph_executor.cc`` (GraphExecutor::Init/Forward/Backward, SURVEY.md N6).

TPU-native design: binding builds ONE pure function over the graph and
``jax.jit``s it — XLA takes over everything GraphExecutor did by hand:
memory planning (PlanMemory pass → XLA buffer assignment), op fusion (bulk
exec segments → XLA fusion), layout, and stream scheduling.  The backward
graph is ``jax.vjp`` of that function (the nnvm Gradient pass analog); the
fused ``forward_backward`` entry used by Module.fit compiles forward+backward
into a single XLA program so training steps are one device launch.
Monitor callbacks (GraphExecutor::SetMonitorCallback, graph_executor.cc:123)
run through an un-jitted eager replay of the same plan.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError, AttrDict, dtype_name
from .context import Context
from . import atlas as _atlas
from . import random as _random
from . import telemetry as _telemetry
from . import health as _health
from . import memwatch as _memwatch
from . import program_cache as _program_cache

__all__ = ["Executor"]

# wall-time histograms fed through profiler.span so the Chrome trace and
# the metrics registry share one measurement per call.  These measure the
# python DISPATCH of the (async) jitted program — on the fused/mesh paths
# the device executes long after the span closes — hence the _dispatch_
# names; device-side attribution lives in atlas.py / health.py
_FWD_TIME = _telemetry.histogram(
    "executor_forward_dispatch_seconds",
    "Executor.forward dispatch wall time (async: excludes device execution)")
_BWD_TIME = _telemetry.histogram(
    "executor_backward_dispatch_seconds",
    "Executor.backward dispatch wall time (async: excludes device execution)")
_FWDBWD_TIME = _telemetry.histogram(
    "executor_forward_backward_dispatch_seconds",
    "Fused Executor.forward_backward dispatch wall time (async: excludes "
    "device execution)")

# whole-graph program observability: the executor's jitted forward is one
# XLA program per (mode, input-shape signature), so its cache lookups join
# the SAME compile metrics ops/registry.py feeds for per-op entries — a
# serving bucket set that stays within its declared programs shows exactly
# len(buckets) misses here and nothing but hits afterwards.
_PROG_HITS = _telemetry.counter(
    "op_jit_cache_hits_total",
    "Operator jit-cache lookups served by an existing entry", ("op",))
_PROG_MISSES = _telemetry.counter(
    "op_jit_cache_misses_total",
    "Operator jit-cache lookups that built a new entry", ("op",))
# Which way a mesh step program with its state split over ``dp`` exchanges
# the gradients (``parallel.mesh.exchange_path``); one inc per program
# built, nothing on one device.
_GRAD_EXCHANGE = _telemetry.counter(
    "grad_exchange_total",
    "Mesh step programs built, by the way their split leaves' gradients "
    "are exchanged (trace-time)", ("path",))
# How a mesh step program reads each table held split over ``dp`` that an
# ``Embedding`` reads (``parallel.mesh.lookup_path``); one inc a table per
# program built.
_EMBEDDING_LOOKUP = _telemetry.counter(
    "embedding_lookup_total",
    "Held-split Embedding tables of the mesh step programs built, by how "
    "the program reads them (trace-time)", ("path",))


class _Plan:
    """Precomputed execution plan for a symbol graph."""

    def __init__(self, symbol, train: bool):
        from .symbol.symbol import _Node  # noqa: F401

        self.topo = symbol._topo()
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.out_entries = [(id(n), i) for n, i in symbol._outputs]
        aux_ids = {}
        for node in self.topo:
            if node.is_var and node.name in self.aux_names:
                aux_ids[id(node)] = node.name
        self.steps = []
        self.n_rng = 0
        for node in self.topo:
            if node.is_var:
                continue
            attrs = node.parsed_attrs()
            if node.op.train_aware:
                attrs = AttrDict({**attrs, "__train__": train})
            if node.op.nin == -1 and "num_args" in node.op.params:
                attrs = AttrDict({**attrs, "num_args": len(node.inputs)})
            rng_slot = None
            if node.op.needs_rng:
                rng_slot = self.n_rng
                self.n_rng += 1
            # aux writeback: map op output index -> aux name
            wb = {}
            if train:
                for oi, ii in node.op.get_aux_writeback(attrs).items():
                    if ii < len(node.inputs):
                        src = node.inputs[ii][0]
                        if id(src) in aux_ids:
                            wb[oi] = aux_ids[id(src)]
            self.steps.append((node, attrs, rng_slot, wb))
        # trace-time formulation flags of every op in the graph: whole-graph
        # programs call node.op.fn directly (bypassing the per-op cache in
        # ops/registry.py compiled()), so the values of these flags are baked
        # into the traced program and must join the PROGRAM's cache key
        env_union = set()
        for node, _a, _r, _w in self.steps:
            env_union.update(node.op.env_keys)
        self.env_keys = tuple(sorted(env_union))
        self._uses = None

    def uses(self) -> Dict[str, List[Tuple[str, int]]]:
        """{variable: the (op, input index) pairs that read it}; a head of
        the graph reads as ``("output", 0)``."""
        if self._uses is None:
            self._uses = {}
            for node in self.topo:
                for ii, (src, _) in enumerate(node.inputs):
                    if src.is_var:
                        self._uses.setdefault(src.name, []).append(
                            (node.op.name, ii))
            heads = {nid for nid, _ in self.out_entries}
            for node in self.topo:
                if node.is_var and id(node) in heads:
                    self._uses.setdefault(node.name, []).append(("output", 0))
        return self._uses

    def execute(self, arg_vals: Dict[str, Any], aux_vals: Dict[str, Any],
                keys, monitor=None, placements=None):
        """Run the plan on jax values (traceable under jit).

        ``placements`` maps node ids to jax devices (coarse model parallel,
        the AssignContext pass of graph_executor.cc:315): inputs of a placed
        node are device_put there first — the reference's
        ``kCrossDeviceCopy`` nodes become explicit transfers.  Only valid in
        eager execution (one XLA program runs on one device).
        """
        import jax as _jax

        env: Dict[Tuple[int, int], Any] = {}
        for node in self.topo:
            if node.is_var:
                if node.name in arg_vals:
                    env[(id(node), 0)] = arg_vals[node.name]
                elif node.name in aux_vals:
                    env[(id(node), 0)] = aux_vals[node.name]
                else:
                    raise MXNetError("unbound variable %r" % node.name)
        new_aux = dict(aux_vals)
        for node, attrs, rng_slot, wb in self.steps:
            ins = [env[(id(p), i)] for p, i in node.inputs]
            if placements and id(node) in placements:
                # device_put is traceable (works on vjp tracers) and a
                # no-op for values already on the target device
                dev = placements[id(node)]
                ins = [_jax.device_put(x, dev) for x in ins]
            if rng_slot is not None:
                ins = [keys[rng_slot]] + ins
            # atlas scope: the node's identity survives into the lowered
            # module's debug locations (and through vjp as jvp/transpose
            # wrappers), so fused-program instructions attribute per layer
            with _jax.named_scope(
                    _atlas.scope_name(node.op.name, node.name)):
                res = node.op.fn(attrs, *ins)
            outs = res if isinstance(res, tuple) else (res,)
            for i, o in enumerate(outs):
                env[(id(node), i)] = o
            for oi, aux_name in wb.items():
                new_aux[aux_name] = outs[oi]
            if monitor is not None:
                if getattr(monitor, "monitor_all", False):
                    for i, (p, pi) in enumerate(node.inputs):
                        monitor("%s_input%d" % (node.name, i),
                                env[(id(p), pi)])
                for i in range(node.num_visible()):
                    monitor(node.name + "_output", outs[i])
        outputs = [env[e] for e in self.out_entries]
        return outputs, new_aux

    # -- coarse model parallel: segment bulking ---------------------------
    def build_segments(self, placements, default_device):
        """Partition the step list into contiguous same-device segments
        (the reference's engine bulking, graph_executor.cc:1455): each
        segment compiles into ONE jitted XLA program on its device, so a
        2-group model dispatches 2 programs per pass instead of one per
        op.  Unplaced nodes inherit the running segment's device
        (AssignContext propagation, graph_executor.cc:315)."""
        segments = []
        cur_dev, cur_steps = None, []
        for step in self.steps:
            node = step[0]
            dev = placements.get(id(node),
                                 cur_dev if cur_dev is not None
                                 else default_device)
            if cur_steps and dev is not cur_dev:
                segments.append([cur_dev, cur_steps])
                cur_steps = []
            cur_dev = dev
            cur_steps.append(step)
        if cur_steps:
            segments.append([cur_dev, cur_steps])

        out_set = set(self.out_entries)
        built = []
        for si, (dev, steps) in enumerate(segments):
            local = {id(node) for (node, _, _, _) in steps}
            ins, seen = [], set()
            for (node, _, _, _) in steps:
                for p, i in node.inputs:
                    e = (id(p), i)
                    if id(p) not in local and e not in seen:
                        seen.add(e)
                        ins.append(e)
            # exports: exactly the demanded entries whose producer is local
            consumers_after = set()
            for sj in range(si + 1, len(segments)):
                for (node, _, _, _) in segments[sj][1]:
                    for p, i in node.inputs:
                        consumers_after.add((id(p), i))
            outs = sorted(
                {e for e in (consumers_after | out_set) if e[0] in local},
                key=lambda e: e[1])
            built.append(_Segment(dev, steps, ins, outs))
        return built

    def execute_bulked(self, arg_vals, aux_vals, keys, segments):
        """execute() with per-segment jit (coarse model parallel)."""
        import jax as _jax

        env = {}
        for node in self.topo:
            if node.is_var:
                if node.name in arg_vals:
                    env[(id(node), 0)] = arg_vals[node.name]
                elif node.name in aux_vals:
                    env[(id(node), 0)] = aux_vals[node.name]
                else:
                    raise MXNetError("unbound variable %r" % node.name)
        new_aux = dict(aux_vals)
        for seg in segments:
            ins = [_jax.device_put(env[e], seg.device)
                   for e in seg.in_entries]
            outs, aux_updates = seg.fn(ins, keys)
            for e, v in zip(seg.out_entries, outs):
                env[e] = v
            new_aux.update(aux_updates)
        outputs = [env[e] for e in self.out_entries]
        return outputs, new_aux


class _Segment:
    """One bulked same-device slice of a plan, compiled as one program."""

    def __init__(self, device, steps, in_entries, out_entries):
        import jax as _jax

        self.device = device
        self.steps = steps
        self.in_entries = list(in_entries)
        self.out_entries = list(out_entries)
        in_entries = self.in_entries
        out_entries = self.out_entries

        def fn(ins, keys):
            env = dict(zip(in_entries, ins))
            aux_updates = {}
            for (node, attrs, rng_slot, wb) in steps:
                vals = [env[(id(p), i)] for p, i in node.inputs]
                if rng_slot is not None:
                    vals = [keys[rng_slot]] + vals
                with _jax.named_scope(
                        _atlas.scope_name(node.op.name, node.name)):
                    res = node.op.fn(attrs, *vals)
                outs = res if isinstance(res, tuple) else (res,)
                for i, o in enumerate(outs):
                    env[(id(node), i)] = o
                for oi, aux_name in wb.items():
                    aux_updates[aux_name] = outs[oi]
            return [env[e] for e in out_entries], aux_updates

        self.fn = _jax.jit(fn)


def build_update_program(update_fns, donate_params=True):
    """One donated XLA program applying every parameter's optimizer update.

    ``gvals[i]`` is the list of per-replica gradients for param ``i``;
    replicas are summed in-trace (the local-kvstore reduce), so the whole
    update phase — reduce + N optimizer kernels — is a single device
    launch.  ``donate_params=False`` keeps the weight inputs alive for
    callers whose autograd tape may still reference them (gluon Trainer);
    opt-state is always donated (it never escapes the updater).
    """
    update_fns = tuple(update_fns)

    def fn(pvals, svals, gvals, lrs, wds, ts, rescale):
        new_p, new_s = [], []
        for i, upd in enumerate(update_fns):
            with jax.named_scope(_atlas.GRAD_SYNC):
                g = gvals[i][0]
                for extra in gvals[i][1:]:
                    g = g + extra
            with jax.named_scope(_atlas.optimizer_scope(upd)):
                w, s = upd(pvals[i], g, svals[i], lrs[i], wds[i], rescale,
                           ts[i])
            new_p.append(w)
            new_s.append(s)
        return new_p, new_s

    return jax.jit(fn, donate_argnums=(0, 1) if donate_params else (1,))


class Executor:
    """A bound executor (parity: mxnet.executor.Executor)."""

    # env flags that select a different fused-step program; they join the
    # program cache key so a toggle takes effect without a rebind (same
    # contract as ops/registry.py env_keys).  MXNET_TPU_BF16 decides array
    # dtypes at BIND time, but it also selects per-slot mp update_fns
    # closure-captured by the step program — a mid-process flip must
    # recompile, not reuse.
    STEP_ENV_KEYS = ("MXNET_TPU_FUSED_STEP", "MXNET_TPU_BF16")

    def __init__(self, symbol, ctx: Context, args: Dict[str, Any],
                 args_grad: Dict[str, Any], grad_req: Dict[str, str],
                 aux_states: Dict[str, Any], group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx
        self._group2ctx = dict(group2ctx or {})
        self.arg_dict = args
        self.grad_dict = args_grad
        self.aux_dict = aux_states
        self._grad_req = grad_req
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()
        missing = [n for n in self.arg_names if n not in self.arg_dict]
        if missing:
            raise MXNetError("bind: missing arguments %s" % missing)
        self._plans: Dict[bool, _Plan] = {}
        self._jitted: Dict[Any, Any] = {}
        self.outputs_nd: List[Any] = []
        self._last_keys = None
        self._monitor = None
        # mesh-sharded callers (serving mesh Predictor) set _mesh_sig —
        # (mesh shape, sharding specs) — so forward programs specialised
        # for one layout are never reused for another (PR 6 / GL001
        # contract: everything that selects a program joins its cache
        # key).  _program_prefix namespaces health.register_program names
        # (e.g. "serving:<model>:b<bucket>:") so N models/buckets get N
        # distinct /programz entries instead of overwriting "forward".
        self._mesh_sig = None
        self._program_prefix = ""
        self._grad_args = [n for n in self.arg_names
                           if grad_req.get(n, "null") != "null"]

    # -- helpers ----------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    def _plan(self, train: bool) -> _Plan:
        if train not in self._plans:
            self._plans[train] = _Plan(self._symbol, train)
        return self._plans[train]

    def _placements(self, plan: _Plan):
        """node-id -> jax.Device from ctx_group attrs + the bind-time
        group2ctx map (AssignContext, graph_executor.cc:315,:1176)."""
        if not self._group2ctx:
            return None
        out = {}
        for node in plan.topo:
            if node.is_var:
                continue
            group = node.attrs.get("ctx_group")
            if group is not None and group in self._group2ctx:
                out[id(node)] = self._group2ctx[group].jax_device
        return out or None

    def _keys(self, plan: _Plan):
        if plan.n_rng == 0:
            return jnp.zeros((0, 2), np.uint32)
        ks = [_random.next_key() for _ in range(plan.n_rng)]
        return jnp.stack(ks)

    def _segments(self, plan, placements):
        """Cached bulked segments for a placed plan (engine bulking)."""
        key = ("segs", id(plan)) + self._plan_env_of(plan)
        if key not in self._jitted:
            self._jitted[key] = plan.build_segments(
                placements, self._ctx.jax_device)
        return self._jitted[key]

    def _mesh_key(self):
        """Cache-key suffix for the bound mesh layout (empty off-mesh so
        existing single-device keys are unchanged)."""
        return (self._mesh_sig,) if self._mesh_sig is not None else ()

    def _dtype_sig(self):
        """Bound-argument dtype signature.  Joins forward program cache
        keys next to mesh_sig: dtypes are fixed per binding (every
        adoption path casts to the bound dtype), but serving hot-swap
        re-points ``_arg_params`` and a bf16-weights binding must never
        share a program slot with an fp32 one."""
        return tuple([dtype_name(self.arg_dict[n].dtype)
                      for n in self.arg_names])

    def _fwd_key(self, train: bool):
        return ("fwd", bool(train)) + self._plan_env(train) \
            + self._mesh_key() + self._dtype_sig()

    def _fwd_fn(self, train: bool):
        key = self._fwd_key(train)
        if key not in self._jitted:
            plan = self._plan(train)
            arg_names, aux_names = plan.arg_names, plan.aux_names
            placements = self._placements(plan)

            if placements:
                # coarse model parallel: one XLA program per same-device
                # SEGMENT (reference bulking, graph_executor.cc:1455) —
                # transfers only at group boundaries, not per op
                segments = self._segments(plan, placements)

                def fn(arg_list, aux_list, keys):
                    outs, new_aux = plan.execute_bulked(
                        dict(zip(arg_names, arg_list)),
                        dict(zip(aux_names, aux_list)), keys, segments)
                    return outs, [new_aux[n] for n in aux_names]

                self._jitted[key] = fn
            else:
                def fn(arg_list, aux_list, keys):
                    outs, new_aux = plan.execute(
                        dict(zip(arg_names, arg_list)),
                        dict(zip(aux_names, aux_list)), keys)
                    return outs, [new_aux[n] for n in aux_names]

                self._jitted[key] = jax.jit(fn)
        elif _telemetry.enabled:
            _program_cache.note_memory_hit()
        return self._jitted[key]

    def _fwdbwd_key(self):
        return ("fwdbwd",) + self._plan_env(True) + self._mesh_key() \
            + self._dtype_sig()

    def _fwd_bwd_fn(self):
        """Single compiled program: forward + vjp-backward (+aux update)."""
        key = self._fwdbwd_key()
        if key not in self._jitted:
            plan = self._plan(True)
            arg_names, aux_names = plan.arg_names, plan.aux_names
            grad_args = self._grad_args
            placements = self._placements(plan)

            segments = (self._segments(plan, placements)
                        if placements else None)

            def fn(arg_list, aux_list, keys, ograds):
                base = dict(zip(arg_names, arg_list))

                def pure(gvals):
                    av = dict(base)
                    av.update(dict(zip(grad_args, gvals)))
                    if segments is not None:
                        outs, new_aux = plan.execute_bulked(
                            av, dict(zip(aux_names, aux_list)), keys,
                            segments)
                    else:
                        outs, new_aux = plan.execute(
                            av, dict(zip(aux_names, aux_list)), keys)
                    return outs, [new_aux[n] for n in aux_names]

                gvals = [base[n] for n in grad_args]
                (outs, new_aux), vjp = jax.vjp(
                    lambda *g: pure(list(g)), *gvals)
                cots = (list(ograds),
                        [jnp.zeros_like(a) for a in new_aux])
                grads = vjp(cots)
                return outs, new_aux, list(grads)

            self._jitted[key] = fn if placements else jax.jit(fn)
        elif _telemetry.enabled:
            _program_cache.note_memory_hit()
        return self._jitted[key]

    def _step_env(self):
        import os
        return tuple(os.environ.get(k) for k in self.STEP_ENV_KEYS)

    @staticmethod
    def _plan_env_of(plan: "_Plan"):
        """Current values of the plan's op env flags (``_Plan.env_keys``);
        joins every whole-graph program cache key so toggling e.g.
        MXNET_TPU_PALLAS_CONV after the first forward rebuilds the program
        instead of serving one with the old formulation baked in."""
        import os
        return tuple(os.environ.get(k) for k in plan.env_keys)

    def _plan_env(self, train: bool = True):
        return self._plan_env_of(self._plan(train))

    def _program_env(self, plan: Optional["_Plan"] = None):
        """{env key: current value} snapshot of everything in a program's
        cache key — recorded with health registrations so flight-recorder
        dumps can tie a crash back to the formulation flags that built the
        live programs."""
        keys = self.STEP_ENV_KEYS + (plan.env_keys if plan is not None
                                     else ())
        import os
        return {k: os.environ.get(k) for k in keys}

    def _step_key(self, mesh_sig=None):
        """Cache key of the fused whole-step program — also the first_run
        probe used by fused_step drivers, so key shape changes stay in ONE
        place."""
        return ("step",) + ((mesh_sig,) if mesh_sig is not None else ()) \
            + self._step_env() + self._plan_env(True)

    def step_program(self, pnames, update_fns, mesh_sig=None,
                     param_shardings=None, state_shardings=None,
                     name="step"):
        """Whole-step program: forward + vjp-backward + optimizer update in
        ONE ``jax.jit`` with params and opt-state donated — weights update
        in place on device, zero per-param python dispatch.

        ``pnames`` are the trainable args (vjp is taken w.r.t. exactly
        these); ``update_fns[i]`` is the param's bound
        ``Optimizer.fused_update``.  Both are closure-captured at first
        build, so callers must drop cached ``("step", ...)`` entries when
        the optimizer binding changes (fused_step.ModuleFusedStep does).
        Per-slot lr/wd/t and rescale_grad arrive as traced scalars: one
        compiled program serves every step.

        ``mesh_sig`` (mesh shape + input sharding signature) joins the
        cache key for the GSPMD variant: the traced body is identical —
        partitioning comes entirely from the input shardings — but a mesh
        or rule change must not reuse a program specialised for the old
        layout.  ``param_shardings`` (aligned with ``pnames``) pins each
        updated param and its opt-state to the INPUT's sharding: without
        the constraint GSPMD may pick a different output layout (e.g.
        shard a small bias), which would silently break the take/give
        donation chain on the next step.  ``state_shardings`` (aligned
        too; ``parallel.mesh.state_sharding``) is given where the opt-state
        has a layout of its own, split over ``dp``.  A param whose state
        sharding is not its own is then HELD in the state's layout between
        steps: it arrives, donated, as each replica's part, is all-gathered
        to the param's sharding at the TOP of the program (under
        ``GradSync``; a temporary of the step, never an output and never
        an alias of an input, so forward runs beside the gathers and no
        donated weight is copied), and the vjp is taken w.r.t. the gathered
        value.  The gradient is constrained to the state's layout BEFORE
        the update, so the partitioner reduce-scatters the partial sums
        where they are made (and does not all-reduce and slice), each
        replica updates its part of the leaf from the part of the weight it
        holds, and the new weight and the state leave in the layout they
        were taken in.  A leaf whose two shardings are the same object
        (small, ``dp`` of 1, a spec that names ``dp``) is untouched.  A
        table that only ``Embedding``s read, split along its columns, is
        not gathered on the ``async`` exchange: each chip looks it up in
        its own columns (``lookup_paths``, ``parallel.mesh.lookup``).

        ``name`` is the program's: its compile phases are recorded as
        ``XLA::* <name>`` (``program_cache``'s observer).
        """
        key = self._step_key(mesh_sig)
        fn = self._jitted.get(key)
        if fn is not None:
            if _telemetry.enabled:
                _program_cache.note_memory_hit()
            return fn
        plan = self._plan(True)
        arg_names, aux_names = plan.arg_names, plan.aux_names
        pnames = tuple(pnames)
        update_fns = tuple(update_fns)
        pset = set(pnames)
        other_names = [n for n in arg_names if n not in pset]
        from .parallel import mesh as _mesh
        exchange = _mesh.exchange_path(param_shardings, state_shardings)
        lookups = self.lookup_paths(pnames, param_shardings, state_shardings,
                                    exchange)
        if exchange is not None and _telemetry.enabled:
            _GRAD_EXCHANGE.labels(path=exchange).inc()
            for how in filter(None, lookups):
                _EMBEDDING_LOOKUP.labels(path=how).inc()

        def fn(pvals, svals, others, auxs, keys, ograds, lrs, wds, ts,
               rescale):
            base = dict(zip(other_names, others))

            def pure(gvals):
                av = dict(base)
                av.update(zip(pnames, gvals))
                outs, new_aux = plan.execute(
                    av, dict(zip(aux_names, auxs)), keys)
                return outs, [new_aux[n] for n in aux_names]

            pin = jax.lax.with_sharding_constraint
            gathered = pvals
            if state_shardings is not None:
                # a weight held as the replica's part of it: gathered here,
                # ahead of forward, where the products hide the exchange
                # (a table looked up in its own columns never is)
                with jax.named_scope(_atlas.GRAD_SYNC):
                    gathered = [p if ssh is psh or how == "own_columns"
                                else pin(p, psh)
                                for p, psh, ssh, how in zip(
                                    pvals, param_shardings, state_shardings,
                                    lookups)]
            # on TPUs a split weight's gradient leaves the product that
            # makes it around a ring of asynchronous permutes, not through
            # a blocking reduce-scatter behind backward
            with (_mesh.grad_exchange(state_shardings[0].mesh, "dp")
                  if exchange == "async" else contextlib.nullcontext()):
                (outs, new_aux), vjp = jax.vjp(
                    lambda *g: pure(list(g)), *gathered)
                grads = vjp((list(ograds),
                             [jnp.zeros_like(a) for a in new_aux]))
                if exchange == "async":
                    grads = _mesh.ended(grads)
            new_p, new_s = [], []
            # weights and state leave in the layout they were taken in
            out_shardings = state_shardings or param_shardings
            for i, upd in enumerate(update_fns):
                g = grads[i]
                if state_shardings is not None:
                    g = pin(g, state_shardings[i])
                with jax.named_scope(_atlas.optimizer_scope(upd)):
                    w, s = upd(pvals[i], g, svals[i],
                               lrs[i], wds[i], rescale, ts[i])
                    if state_shardings is not None:
                        # the update ends, cast to the weight's dtype
                        # included, on the replica's own part, and the
                        # weight stays there: its fusion keeps this scope
                        w = pin(w, out_shardings[i])
                if state_shardings is None and param_shardings is not None:
                    w = pin(w, out_shardings[i])
                if out_shardings is not None:
                    osh = out_shardings[i]
                    s = jax.tree_util.tree_map(lambda a: pin(a, osh), s)
                new_p.append(w)
                new_s.append(s)
            return new_p, new_s, outs, new_aux

        fn.__name__ = fn.__qualname__ = name
        fn = jax.jit(fn, donate_argnums=(0, 1))
        self._jitted[key] = fn
        return fn

    def lookup_paths(self, pnames, param_shardings, state_shardings,
                     exchange):
        """[how the mesh step program reads each of ``pnames`` where it is
        a table held split over ``dp`` that an ``Embedding`` reads:
        ``"own_columns"`` or ``"gathered"`` (``parallel.mesh.lookup_path``,
        from its layout, its uses in the graph and the ``exchange``, the
        layout's ``exchange_path``); None for every other]"""
        if state_shardings is None:
            return [None] * len(pnames)
        from .parallel import mesh as _mesh
        uses = self._plan(True).uses()
        return [_mesh.lookup_path(self.arg_dict[n].shape, uses.get(n, ()),
                                  psh, ssh, exchange)
                for n, psh, ssh in zip(pnames, param_shardings,
                                       state_shardings)]

    def _gather(self):
        args = [self.arg_dict[n]._data for n in self.arg_names]
        auxs = [self.aux_dict[n]._data for n in self.aux_names]
        return args, auxs

    def _ograds_for(self, shapes):
        """Ones head-gradients for a {arg_name: shape} dict (cached
        shape+dtype inference).  The mesh step passes full-batch shapes
        here; the bound per-device shapes come from ``_default_ograds``.
        Output shapes and dtypes come from ONE abstract evaluation of the
        plan under the bound argument dtypes — ``jax.vjp`` requires
        cotangent dtype == output dtype, and bf16 bindings produce bf16
        heads (fp32 for heads that reduce in fp32, e.g. SoftmaxOutput on
        low-precision input)."""
        shape_key = tuple(tuple(shapes[n]) for n in self.arg_names)
        key = ("oshapes", shape_key, self._dtype_sig())
        cached = self._jitted.get(key)
        if cached is None:
            plan = self._plan(True)
            avals = {n: jax.ShapeDtypeStruct(tuple(shapes[n]),
                                             np.dtype(self.arg_dict[n].dtype))
                     for n in self.arg_names}
            aux_avals = {n: jax.ShapeDtypeStruct(
                self.aux_dict[n].shape, np.dtype(self.aux_dict[n].dtype))
                for n in self.aux_names}
            kstruct = jax.ShapeDtypeStruct((plan.n_rng, 2), np.uint32)
            outs = jax.eval_shape(
                lambda a, x, k: plan.execute(a, x, k)[0],
                avals, aux_avals, kstruct)
            cached = [(tuple(o.shape), o.dtype) for o in outs]
            self._jitted[key] = cached
        return [jnp.ones(s, dt) for s, dt in cached]

    def _default_ograds(self):
        """Ones head-gradients with shapes from (cached) shape inference."""
        return self._ograds_for(
            {n: self.arg_dict[n].shape for n in self.arg_names})

    def _wrap_outputs(self, outs):
        from .ndarray.ndarray import NDArray
        self.outputs_nd = [NDArray(o, self._ctx) for o in outs]
        if _memwatch.enabled:
            _memwatch.tag("activations", outs)
        return self.outputs_nd

    def _writeback_aux(self, new_aux):
        for n, v in zip(self.aux_names, new_aux):
            self.aux_dict[n]._data = v

    # -- public API -------------------------------------------------------
    def forward(self, is_train: bool = False, **kwargs):
        from .ndarray.ndarray import NDArray
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown input %r" % k)
            dst = self.arg_dict[k]
            if isinstance(v, NDArray):
                # adopt pre-placed producer batches as-is (PrefetchingIter
                # device double buffering): no re-put, no same-dtype astype
                src = v._data
                dst._data = src if src.dtype == dst.dtype \
                    else src.astype(dst.dtype)
            else:
                dst._data = jnp.asarray(v, dst.dtype)
            if _memwatch.enabled:
                # adopted input batches are io-owned on the ledger (the
                # device-resident staging side of the data pipeline)
                _memwatch.tag("io", dst._data)
        from . import profiler as _profiler
        plan = self._plan(bool(is_train))
        keys = self._keys(plan)
        self._last_keys = keys
        # first_run marks the trace+compile invocation of this (mode,
        # shape-set) so recompiles stand out from steady-state iterations
        plan_env = self._plan_env_of(plan)
        first_run = self._fwd_key(is_train) not in self._jitted
        if _telemetry.enabled:
            # count per input-shape signature, not per _fwd_fn build: the
            # jitted fn silently recompiles on a new shape, and THAT is
            # the event a shape-bucketing layer must see (an env-flag
            # toggle recompiles too — plan_env keeps the counter truthful,
            # and a mesh-layout change is a recompile the same way)
            skey = ("fwdsig", bool(is_train),
                    tuple(self.arg_dict[n].shape
                          for n in self.arg_names)) + plan_env \
                + self._mesh_key() + self._dtype_sig()
            if skey in self._jitted:
                _PROG_HITS.labels(op="Executor::Forward").inc()
            else:
                self._jitted[skey] = True
                _PROG_MISSES.labels(op="Executor::Forward").inc()
        # dispatch-only span: the jitted call returns before the device
        # finishes (async dispatch), so this is NOT an execution timing
        with _profiler.span("Executor::ForwardDispatch", "executor",
                            histogram=_FWD_TIME,
                            args={"first_run": first_run}):
            if self._monitor is not None:
                args, auxs = self._gather()
                outs, new_aux = plan.execute(
                    dict(zip(self.arg_names, args)),
                    dict(zip(self.aux_names, auxs)), keys,
                    monitor=self._monitor)
                new_aux = [new_aux[n] for n in self.aux_names]
            else:
                fwd = self._fwd_fn(bool(is_train))
                args, auxs = self._gather()
                if first_run and _health.enabled:
                    # lowering-only analysis: the call below still owns
                    # the one and only compilation
                    _health.register_program(
                        self._program_prefix + "forward", fwd,
                        (args, auxs, keys), env=self._program_env(plan))
                try:
                    outs, new_aux = fwd(args, auxs, keys)
                except Exception as e:
                    if _memwatch.enabled and _memwatch.is_oom(e):
                        _memwatch.on_oom(
                            e, site="executor",
                            program=self._program_prefix + "forward")
                    raise
        if is_train:
            self._writeback_aux(new_aux)
        return self._wrap_outputs(outs)

    def backward(self, out_grads=None, is_train=True):
        """Gradients w.r.t. args with grad_req != null.  Recomputes the
        forward inside one fused XLA program (rematerialization — the TPU
        analog of MXNET_BACKWARD_DO_MIRROR, trading FLOPs for HBM)."""
        from .ndarray.ndarray import NDArray
        plan = self._plan(True)
        if out_grads is None:
            ogs = [jnp.ones(self.outputs_nd[i].shape,
                            self.outputs_nd[i].dtype)
                   for i in range(len(plan.out_entries))]
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            ogs = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                   for g in out_grads]
        keys = self._last_keys if self._last_keys is not None \
            else self._keys(plan)
        args, auxs = self._gather()
        from . import profiler as _profiler
        first_run = self._fwdbwd_key() not in self._jitted
        with _profiler.span("Executor::BackwardDispatch", "executor",
                            histogram=_BWD_TIME,
                            args={"first_run": first_run}):
            fb = self._fwd_bwd_fn()
            if first_run and _health.enabled:
                _health.register_program(
                    self._program_prefix + "fwdbwd", fb,
                    (args, auxs, keys, ogs), env=self._program_env(plan))
            try:
                outs, new_aux, grads = fb(args, auxs, keys, ogs)
            except Exception as e:
                if _memwatch.enabled and _memwatch.is_oom(e):
                    _memwatch.on_oom(e, site="executor",
                                     program=self._program_prefix + "fwdbwd")
                raise
            self._apply_grads(grads)
        return

    def forward_backward(self, out_grads=None, **kwargs):
        """Fused train step: one XLA program for fwd+bwd (+aux update)."""
        from .ndarray.ndarray import NDArray
        for k, v in kwargs.items():
            if k in self.arg_dict:
                dst = self.arg_dict[k]
                if isinstance(v, NDArray):
                    src = v._data
                    dst._data = src if src.dtype == dst.dtype \
                        else src.astype(dst.dtype)
                else:
                    dst._data = jnp.asarray(v, dst.dtype)
                if _memwatch.enabled:
                    _memwatch.tag("io", dst._data)
        plan = self._plan(True)
        keys = self._keys(plan)
        self._last_keys = keys
        args, auxs = self._gather()
        if out_grads is None:
            ogs = self._default_ograds()
        else:
            ogs = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                   for g in out_grads]
        from . import profiler as _profiler
        first_run = self._fwdbwd_key() not in self._jitted
        with _profiler.span("Executor::ForwardBackwardDispatch", "executor",
                            histogram=_FWDBWD_TIME,
                            args={"first_run": first_run}):
            fb = self._fwd_bwd_fn()
            if first_run and _health.enabled:
                _health.register_program(
                    self._program_prefix + "fwdbwd", fb,
                    (args, auxs, keys, ogs), env=self._program_env(plan))
            try:
                outs, new_aux, grads = fb(args, auxs, keys, ogs)
            except Exception as e:
                if _memwatch.enabled and _memwatch.is_oom(e):
                    _memwatch.on_oom(e, site="executor",
                                     program=self._program_prefix + "fwdbwd")
                raise
            self._writeback_aux(new_aux)
            self._apply_grads(grads)
        return self._wrap_outputs(outs)

    def _apply_grads(self, grads):
        for n, g in zip(self._grad_args, grads):
            if n not in self.grad_dict:
                continue
            dst = self.grad_dict[n]
            if self._grad_req.get(n) == "add":
                dst._data = dst._data + g.astype(dst.dtype)
            else:
                dst._data = g.astype(dst.dtype)
            if _memwatch.enabled:
                # gradient buffers persist across steps; ledger them with
                # the step-transient products so the leak sentinel stays
                # quiet about them
                _memwatch.tag("activations", dst._data)

    @property
    def outputs(self):
        return self.outputs_nd

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self.arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self.arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self.aux_names]

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in (arg_params or {}).items():
            if k in self.arg_dict:
                self.arg_dict[k][:] = v
            elif not allow_extra_params:
                raise MXNetError("unknown parameter %r" % k)
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                self.aux_dict[k][:] = v
            elif not allow_extra_params:
                raise MXNetError("unknown aux state %r" % k)

    def set_monitor_callback(self, callback, monitor_all=False):
        """Install a per-node-output callback (runs the un-jitted plan);
        ``monitor_all`` additionally reports every node INPUT
        (reference SetMonitorCallback monitor_all semantics)."""
        if callback is None:
            self._monitor = None
            return

        def mon(name, arr):
            from .ndarray.ndarray import NDArray
            callback(name, NDArray(arr, self._ctx))

        mon.monitor_all = bool(monitor_all)
        self._monitor = mon

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new shapes (compile cache keyed on shapes by jit)."""
        from . import ndarray as nd
        new_shapes, _, new_aux_shapes = self._symbol.infer_shape(**kwargs)
        args = {}
        for n, s in zip(self.arg_names, new_shapes):
            cur = self.arg_dict[n]
            args[n] = cur if cur.shape == s else nd.zeros(s, ctx=self._ctx,
                                                          dtype=cur.dtype)
        auxs = {}
        for n, s in zip(self.aux_names, new_aux_shapes):
            cur = self.aux_dict[n]
            auxs[n] = cur if cur.shape == s else nd.zeros(s, ctx=self._ctx,
                                                          dtype=cur.dtype)
        grads = {n: nd.zeros(a.shape, ctx=self._ctx, dtype=a.dtype)
                 for n, a in args.items() if n in self.grad_dict}
        return Executor(self._symbol, self._ctx, args, grads,
                        self._grad_req, auxs)
