"""The loop kind ``module_fit``: the body of ``BaseModule.fit`` — 
``Module.forward_backward(batch)``, ``Module.update()``, and the step's
output fetched to the host behind ``OverlappedLoop`` at the workload's depth
(2, fit's default) — over a small seeded rotation of batches already on the
device (the reference's own ``train_imagenet.py --benchmark 1``).

Set-up builds ONE Module, gives it weights made on the device from the seed
(the reference's own generator), drives it through its first three steps by
the very call the window uses, and hands the same object to the window.
After the window the program's state is freed and the plain reference
follows those three steps in float32; `correct` compares each step's loss,
the norm of the first gradient as the optimizer got it (from its state after
one step) and the norm of the parameters' change after the three, by the
worst leaf (``perf/refs/train.py``).
"""
import gc
import os
import shutil
import time

import numpy as np

from perf import harness
from perf.refs import common, train

PROOF_STEPS = train.STEPS


def _state(mod, leaf_name):
    """({leaf: first moment}, {leaf: float32 master}) of the program's
    training state, read where MXNet keeps it: the updater's per-slot states
    ((inner, float32 master) for a low-precision weight, inner alone for a
    float32 one; inner is the momentum, or Adam's (mean, variance)) and the
    executor's weights."""
    states = mod._updater.states
    ndev = len(mod._context)
    weights = mod._exec_group.execs[0].arg_dict
    moments, masters = {}, {}
    for i, name in enumerate(mod._param_names):
        st = states[i * ndev]
        master = weights[name]
        if master.dtype != np.float32:      # low precision: (inner, master)
            st, master = st
        first = st[0] if isinstance(st, (tuple, list)) else st
        moments[leaf_name(name)] = first._data
        masters[leaf_name(name)] = master._data
    return moments, masters


def _norms(tree):
    import jax
    return jax.jit(common.leaf_norms)(tree)


def _change_norms(masters, start):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda a, b: common.leaf_norms(
        {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32)
         for k in a}))(masters, start)


class Fit:
    """One Module, built and proved: its set-up, its step (the window's own
    call and feed) and the readings of its first three steps."""

    def __init__(self, cell, devices, seed):
        import mxnet_tpu as mx
        from mxnet_tpu.train_loop import OverlappedLoop
        cfg, wl, builder = cell.config, cell.workload, cell.builder
        self.cell, self.ref, self.seed = cell, builder.ref, seed
        self.spans = harness.Spans()
        make_ctx = mx.tpu if devices[0].platform == "tpu" else mx.cpu
        ctxs = [make_ctx(i) for i in range(wl["chips"])]
        data_shapes, label_shapes = builder.shapes(cfg, wl)
        mod = mx.mod.Module(builder.symbol(cfg, wl),
                            data_names=tuple(data_shapes),
                            label_names=tuple(label_shapes), context=ctxs)
        mod.bind(data_shapes=list(data_shapes.items()),
                 label_shapes=list(label_shapes.items()))
        served = self.ref.init_params(cfg, seed)
        mod.init_params(
            mx.init.Uniform(0.01),  # auxiliary states only: moving mean/var
            arg_params={n: mx.nd.NDArray(served[builder.leaf_name(n)], ctxs[0])
                        for n in mod._param_names})
        del served
        mod.init_optimizer(kvstore="local", optimizer=wl["optimizer"],
                           optimizer_params=dict(wl["optimizer_params"]))
        self.mod = mod
        self.batches = [mx.io.DataBatch(data=[mx.nd.NDArray(x, ctxs[0])],
                                        label=[mx.nd.NDArray(y, ctxs[0])])
                        for x, y in self.ref.make_batches(cfg, wl, seed)]
        self.labels = [np.asarray(b.label[0]._data) for b in self.batches]
        self.loop = OverlappedLoop(wl["fetch_depth"])
        self.done = []          # (step, host time its fetch completed, output)

    def _fetch(self, step, out, keep):
        with self.spans("fetch"):
            got = out.asnumpy()
        self.done.append((step, time.perf_counter(),
                          got if keep else bool(np.all(np.isfinite(got)))))

    def one_step(self, step, keep=False):
        with self.spans("next_batch"):
            batch = self.batches[step % len(self.batches)]
        with self.spans("dispatch"):
            self.mod.forward_backward(batch)
            self.mod.update()
        out = self.mod.get_outputs()[0]
        self.loop.push(lambda: self._fetch(step, out, keep))

    def prove(self):
        """The first three steps, through the window's own call and feed,
        and what `correct` compares of them; step 4 takes the state on."""
        cell = self.cell
        cfg, wl, builder = cell.config, cell.workload, cell.builder
        self.one_step(0, keep=True)
        # queued behind step 1, before step 2 donates the state
        grad_state = _norms(_state(self.mod, builder.leaf_name)[0])
        for step in range(1, PROOF_STEPS):
            self.one_step(step, keep=True)
        self.loop.drain()
        start = self.ref.init_params(cfg, self.seed)
        change = _change_norms(_state(self.mod, builder.leaf_name)[1], start)
        del start
        rows = builder.row_losses(self.done[0][2], self.labels[0])
        got = {
            "loss": [builder.step_loss(out, self.labels[s % len(self.labels)])
                     for s, _, out in self.done],
            "row_loss_step1": None if rows is None else [float(v) for v in rows],
            "grad_norm": {k: common.first_grad_from_state(
                wl["optimizer"], wl["optimizer_params"], float(v))
                for k, v in grad_state.items()},
            "change_norm": {k: float(v) for k, v in change.items()},
        }
        del self.done[:]
        self.one_step(PROOF_STEPS)
        self.loop.drain()
        del self.done[:]
        return got

    def free(self):
        """Drop the program's state, so that the reference fits."""
        self.mod = self.batches = self.loop = None
        gc.collect()


def prepare(cfg):
    """The precision policy and the program's compile cache and counters,
    before the first bind."""
    if cfg["dtype"] == "bfloat16":
        os.environ["MXNET_TPU_BF16"] = "1"
    elif cfg["dtype"] != "float32":
        raise SystemExit("no policy for dtype %r" % (cfg["dtype"],))
    from mxnet_tpu import program_cache, telemetry
    telemetry.enable()
    return program_cache.place()


def run(r):
    import jax
    cell, args = r.cell, r.args
    cfg, wl, builder = cell.config, cell.workload, cell.builder
    ref = builder.ref
    chips = wl["chips"]
    watch = harness.CompileWatch()
    cache_dir = prepare(cfg)
    from mxnet_tpu import telemetry

    # ------------------------------------------------------------- set-up
    fit = Fit(cell, r.devices, args.seed)
    got = fit.prove()
    spans, done, loop, one_step = fit.spans, fit.done, fit.loop, fit.one_step
    fused_path = "mesh_fused" if chips > 1 else "fused"
    fused0 = telemetry.value("step_dispatch_total", path=fused_path)
    compiles0 = watch.mark()
    setup_compile = watch.since((0, 0.0, 0))
    spans.rows.clear()
    setup_s = time.time() - r.t_start

    # ------------------------------------------------------------- window
    trace_dir = os.path.join(r.root, "perf", ".trace_%s" % cell.entry["name"])
    traced = None
    t0 = time.perf_counter()
    step = first = PROOF_STEPS + 1
    untraced_until = t0 + args.seconds * (0.5 if args.trace else 1.0)
    while time.perf_counter() < untraced_until:
        one_step(step)
        step += 1
    loop.drain()
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the python tracer slows the host
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        spans.annotate = True
        ta = time.perf_counter()
        for _ in range(wl["traced_steps"]):
            one_step(step)
            step += 1
        loop.drain()
        tb = time.perf_counter()
        spans.annotate = False
        jax.profiler.stop_trace()
        traced = (ta, tb, wl["traced_steps"])
    t1 = time.perf_counter()
    window_s = t1 - t0
    steps = step - first
    window_compiles = watch.since(compiles0)
    fused_steps = telemetry.value("step_dispatch_total", path=fused_path) - fused0
    peak = harness.memory_peak_bytes(r.devices)
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in r.devices]
    times = [t for _, t, _ in done]
    failed = sum(1 for _, _, ok in done if not ok)
    attempted = len(done)
    gaps_ms = [(b - a) * 1e3 for a, b in zip([t0] + times[:-1], times)]

    # ------------------------------------------- free the program, then check
    del loop, one_step
    fit.free()
    t_ref = time.perf_counter()
    want = train.run(ref, cfg, wl, args.seed)
    numbers = train.compare(got, want)
    ref_s = time.perf_counter() - t_ref
    check, correct = {}, (failed == 0 and attempted == steps and steps > 0)
    for name, (value, where) in numbers.items():
        limit = wl.get("limits", {}).get(name)
        if limit is None:
            continue
        check[name] = {"value": value, "limit": limit, "worst": where}
        correct = correct and bool(value <= limit)
    for s, (a, b) in enumerate(zip(got["loss"], want["loss"]), 1):
        check["loss_step%d" % s] = {"value": a, "reference": b}
    check["steps_not_finite"] = {"value": failed, "limit": 0}

    # ------------------------------------------------------------ the line
    dev0 = r.devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": chips, "memory_peak_bytes": peak}
    extra = {"info": {
        "steps": steps, "window_s": window_s, "reference_s": ref_s,
        "compile_cache_dir": cache_dir, "bytes_in_use_after_window": in_use,
        "setup_compile": setup_compile,
        "not_compared": {k: list(v) for k, v in numbers.items()
                         if k not in check}}}
    breakdown = None
    if args.trace:
        from perf import trace
        ta, tb, n = traced
        reduced = trace.reduce(trace_dir, chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = tb - ta
        ctx = {
            "trace": reduced, "traced_steps": n, "traced_window_s": tb - ta,
            "spans": spans, "window_from": t0, "window_to": t1,
            "steps": steps, "counters": {"fused_steps": fused_steps},
            "window_compiles": window_compiles,
            "step_flops": builder.step_flops(cfg, wl), "chips": chips,
            "peaks": harness.peaks(r.root, "TPU v5 lite" if args.rehearse
                                   else dev0.device_kind),
            "builder": builder, "config": cfg, "workload": wl,
        }
        metrics = harness.per_layer_metrics(r, ctx)
        breakdown = trace.breakdown(reduced)
    else:
        items = steps * builder.items_per_step(cfg, wl)
        metrics = {
            "train_items_per_s": {"value": items / window_s,
                                  "unit": "items/s"},
            "step_p95_ms": {"value": harness.percentile(gaps_ms, 95),
                            "unit": "ms", "samples": len(gaps_ms),
                            "median": harness.percentile(gaps_ms, 50)},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        metrics = {m["name"]: metrics[m["name"]]
                   for m in cell.manifest["end_to_end"]
                   if m["name"] in metrics and (
                       "workloads" not in m
                       or cell.entry["name"] in m["workloads"])}
    if args.rehearse:
        extra["info"]["rehearsal"] = ("CPU, tiny size: no device metric; "
                                      "read: " + " ".join(sorted(metrics)))
        metrics, breakdown = {}, None
        device.pop("busy_s", None)
        device.pop("window_s", None)
    harness.emit(correct=correct, attempted=attempted, failed=failed,
                 metrics=metrics, device=device, check=check,
                 breakdown=breakdown, extra=extra)
    return 0
