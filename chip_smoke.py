#!/usr/bin/env python
"""chip_smoke.py: the quickest proof that the program still starts on the chip.

One process drives the main path once, through the entry points a user
calls (``gluon`` + ``mx.FusedTrainer``, ``mx.mod.Module``,
``mxnet_tpu.serving``), at the full width of ResNet-50 (224 px, batch 128,
bf16 compute with f32 masters), with weights and data made from ``--seed``:

  fused_trainer  gluon ResNet-50 through ``mx.FusedTrainer.step``
  module_step    the same network as a Symbol through ``mx.mod.Module``
                 under ``MXNET_TPU_BF16`` (must dispatch the fused step)
  sync           what ``wait_to_read`` / ``nd.waitall`` / a host fetch wait for
  kernels        the two default-on Pallas kernels against their XLA
                 references, then the ``RNN`` / ``MultiHeadAttention`` ops
                 themselves (must take the kernel where the size gate says so)
  serving        ``serving.ModelServer`` over ResNet-50 bf16 inference, two
                 batch buckets, answers against a direct forward

It needs a TPU: with any other ``jax.devices()[0].platform`` it exits
non-zero before any phase, and it never re-executes elsewhere, shrinks a
shape because of the device, or catches a phase's failure to go on.  The
first stdout line says what it runs on (device, versions, compile-cache
directory, native runtime); every phase prints one JSON line; the last
line is ``{"ok": true, "device": {"platform", "kind", "count"}}`` with the
values observed and the device count the run used.

``--multichip`` runs ONLY the four-chip phase and what it is compared with
(the driver never passes it; it fails without four TPU devices): Module
over ``[mx.tpu(0..3)]`` against ``[mx.tpu(0)]``, ``DataParallelTrainer`` on
a dp=2 x tp=2 mesh and one ``ring_attention`` call, each against its
unsharded computation.

The phase functions take their sizes (and the device) as arguments so that
``tests/test_chip_smoke.py`` can rehearse the control flow on CPU at a tiny
size; there is no command-line switch around the device check.
"""
import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

#: bf16 keeps 8 significant bits: one rounding is a relative error of 2^-8
BF16_EPS = 2.0 ** -8


def emit(phase, **fields):
    """One JSON line per phase (``passed``, never ``ok``: only the last
    line of a passing run may say ``"ok": true``)."""
    print(json.dumps({"phase": phase, "passed": True, **fields}), flush=True)


class CompileWatch:
    """Counts what jax itself reports: every XLA compile request of the
    process (fresh or restored from the persistent cache), the seconds
    they took, and the persistent-cache hits among them."""

    _REQUEST = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.requests = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, secs, **_):
        if event == self._REQUEST:
            self.requests += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.hits += 1

    def mark(self):
        return (self.requests, self.seconds, self.hits)

    def since(self, mark):
        """{requests, fresh, cache_hits, seconds} since ``mark``."""
        req = self.requests - mark[0]
        hits = self.hits - mark[2]
        return {"compile_requests": req, "fresh_compiles": req - hits,
                "cache_hits": hits,
                "compile_seconds": round(self.seconds - mark[1], 3)}


def _ctx(mx, device, index=0):
    return mx.tpu(index) if device.platform == "tpu" else mx.cpu(index)


def _on_device(array, device):
    """Where the buffer really is, asked of the array and not the context."""
    return set(array.devices()) == {device}


def _peak_bytes(device):
    stats = device.memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def _batch(seed, batch, data_shape, classes):
    rs = np.random.RandomState(seed)
    x = rs.uniform(size=(batch,) + tuple(data_shape)).astype(np.float32)
    y = rs.randint(0, classes, (batch,)).astype(np.float32)
    return x, y


def _mean_ce(probs, labels):
    p = np.asarray(probs, np.float64).reshape(len(labels), -1)
    picked = p[np.arange(len(labels)), labels.astype(int)]
    return float(np.mean(-np.log(np.maximum(picked, 1e-30))))


def resnet50():
    from mxnet_tpu.gluon.model_zoo import vision
    return vision.resnet50_v1()


# ---------------------------------------------------------------- phase 1
def phase_fused_trainer(device, watch, seed, net_fn=resnet50, batch=128,
                        data_shape=(3, 224, 224), classes=1000, steps=8,
                        warmup=2, dtype="bfloat16"):
    """README quick-start model through ``mx.FusedTrainer.step``.  Returns
    the live trainer and its fixed batch for the sync phase."""
    import mxnet_tpu as mx

    ctx = _ctx(mx, device)
    mx.random.seed(seed)
    net = net_fn()
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()
    x_np, y_np = _batch(seed, batch, data_shape, classes)
    x, y = mx.nd.array(x_np, ctx=ctx), mx.nd.array(y_np, ctx=ctx)
    net(x).wait_to_read()                      # materialize parameters
    ft = mx.FusedTrainer(net, "softmax_cross_entropy", "sgd",
                         {"learning_rate": 0.01, "momentum": 0.9},
                         dtype=dtype)

    mark = watch.mark()
    t0 = time.perf_counter()
    loss_first = float(ft.step(x, y).asnumpy())
    first_step_s = time.perf_counter() - t0
    first = watch.since(mark)
    for _ in range(warmup):
        float(ft.step(x, y).asnumpy())

    mark = watch.mark()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = ft.step(x, y)
    loss_last = float(loss.asnumpy())          # host fetch ends the window
    steady_ms = (time.perf_counter() - t0) / steps * 1e3
    steady = watch.since(mark)

    assert np.isfinite(loss_first) and np.isfinite(loss_last), \
        (loss_first, loss_last)
    assert loss_last < loss_first, \
        "loss did not fall on the fixed batch: %r -> %r" % (loss_first,
                                                           loss_last)
    assert steady["compile_requests"] == 0, steady
    assert _on_device(loss._data, device), loss._data.devices()
    ft.sync_params()
    stray = [n for n, p in net.collect_params().items()
             if not _on_device(p.data()._data, device)]
    assert not stray, "parameters not on %s: %s" % (device, stray[:5])

    emit("fused_trainer", model=net_fn.__name__, batch=batch,
         data_shape=list(data_shape), dtype=dtype, steps=steps,
         loss_first=round(loss_first, 4), loss_last=round(loss_last, 4),
         first_step_seconds=round(first_step_s, 3),
         first_step_compile_seconds=first["compile_seconds"],
         first_step_cache_hits=first["cache_hits"],
         first_step_fresh_compiles=first["fresh_compiles"],
         steady_step_ms=round(steady_ms, 3),
         post_warmup_compile_requests=steady["compile_requests"],
         n_params=len(net.collect_params()),
         peak_bytes_in_use=_peak_bytes(device))
    return ft, x, y


# ---------------------------------------------------------------- phase 2
def resnet50_symbol(mx):
    out = resnet50()(mx.sym.var("data"))
    return mx.sym.SoftmaxOutput(out, mx.sym.var("softmax_label"),
                                name="softmax")


def _module(mx, sym, ctxs, seed, batch, data_shape, lr):
    mod = mx.mod.Module(sym, data_names=("data",),
                        label_names=("softmax_label",), context=ctxs)
    mod.bind(data_shapes=[("data", (batch,) + tuple(data_shape))],
             label_shapes=[("softmax_label", (batch,))])
    mx.random.seed(seed)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
    mod.init_optimizer(kvstore="local", optimizer="sgd",
                       optimizer_params={"learning_rate": lr,
                                         "momentum": 0.9,
                                         "multi_precision": True})
    return mod


class _Batch:
    def __init__(self, x, y):
        self.data, self.label = [x], [y]


@contextlib.contextmanager
def _bf16_policy():
    """``MXNET_TPU_BF16=1`` for the binds inside (PR 19 reads it at bind
    time), the caller's setting afterwards."""
    was = os.environ.get("MXNET_TPU_BF16")
    os.environ["MXNET_TPU_BF16"] = "1"
    try:
        yield
    finally:
        if was is None:
            os.environ.pop("MXNET_TPU_BF16", None)
        else:
            os.environ["MXNET_TPU_BF16"] = was


def _module_losses(mod, batch, y_np, steps):
    """``steps`` fused Module steps on the fixed batch; the per-step mean
    CE of the step's own (pre-update) softmax output, fetched to the host."""
    losses = []
    for _ in range(steps):
        mod.forward_backward(batch)
        mod.update()
        losses.append(_mean_ce(mod.get_outputs()[0].asnumpy(), y_np))
    return losses


def phase_module_step(device, watch, seed, symbol_fn=resnet50_symbol,
                      batch=128, data_shape=(3, 224, 224), classes=1000,
                      steps=8, warmup=2):
    """The same network as a Symbol through ``mx.mod.Module`` under
    ``MXNET_TPU_BF16`` (PR 19): every step must take the fused path."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    telemetry.enable()
    with _bf16_policy():
        mod = _module(mx, symbol_fn(mx), [_ctx(mx, device)], seed, batch,
                      data_shape, lr=0.01)
        name = mod._param_names[0]
        weight = mod._exec_group.execs[0].arg_dict[name]
        assert str(weight.dtype) == "bfloat16", weight.dtype
        x_np, y_np = _batch(seed, batch, data_shape, classes)
        b = _Batch(mx.nd.array(x_np), mx.nd.array(y_np))

        mark = watch.mark()
        t0 = time.perf_counter()
        head = _module_losses(mod, b, y_np, 1 + warmup)
        first_s = time.perf_counter() - t0
        first = watch.since(mark)

        fused0 = telemetry.value("step_dispatch_total", path="fused")
        eager0 = telemetry.value("step_dispatch_total", path="eager")
        mark = watch.mark()
        t0 = time.perf_counter()
        for _ in range(steps - 1):
            mod.forward_backward(b)
            mod.update()
        tail = _module_losses(mod, b, y_np, 1)  # host fetch ends the window
        steady_ms = (time.perf_counter() - t0) / steps * 1e3
        steady = watch.since(mark)
        fused = telemetry.value("step_dispatch_total", path="fused") - fused0
        eager = telemetry.value("step_dispatch_total", path="eager") - eager0
        weight = mod._exec_group.execs[0].arg_dict[name]
        placed = _on_device(weight._data, device)

    assert fused == steps and eager == 0, \
        "step_dispatch_total grew fused=%s eager=%s over %d steps" \
        % (fused, eager, steps)
    assert np.isfinite(head[0]) and np.isfinite(tail[0]), (head, tail)
    assert tail[0] < head[0], \
        "loss did not fall on the fixed batch: %r -> %r" % (head[0], tail[0])
    assert steady["compile_requests"] == 0, steady
    assert placed, "Module weights are not on %s" % (device,)

    emit("module_step", batch=batch, data_shape=list(data_shape),
         weight_dtype="bfloat16", steps=steps,
         step_dispatch={"fused": int(fused), "eager": int(eager)},
         loss_first=round(head[0], 4), loss_last=round(tail[0], 4),
         warmup_seconds=round(first_s, 3),
         warmup_compile_seconds=first["compile_seconds"],
         warmup_cache_hits=first["cache_hits"],
         steady_step_ms=round(steady_ms, 3),
         post_warmup_compile_requests=steady["compile_requests"],
         peak_bytes_in_use=_peak_bytes(device))


# ---------------------------------------------------------------- phase 3
def phase_sync(ft, x, y, steps=8):
    """What each NDArray sync waits for, on the fused-trainer step: the
    same ``steps`` steps ended by nothing (enqueue only), by
    ``wait_to_read()``, by ``nd.waitall()`` and by a host fetch of the
    loss.  A sync that returned while the device still owed the steps
    would show a time near the enqueue time and far below the fetch."""
    import mxnet_tpu as mx

    def window(end):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = ft.step(x, y)
        end(loss)
        dt = time.perf_counter() - t0
        float(loss.asnumpy())      # drain outside the window, whatever `end`
        return dt * 1e3

    window(lambda l: float(l.asnumpy()))           # settle
    enqueue = window(lambda l: None)
    wait = window(lambda l: l.wait_to_read())
    waitall = window(lambda l: mx.nd.waitall())
    fetch = window(lambda l: float(l.asnumpy()))

    assert wait >= 0.5 * fetch, \
        "wait_to_read returned before the device was done: %.1f ms vs " \
        "%.1f ms for a host fetch" % (wait, fetch)
    assert waitall >= 0.5 * fetch, \
        "nd.waitall returned before the device was done: %.1f ms vs " \
        "%.1f ms for a host fetch" % (waitall, fetch)
    emit("sync", steps=steps, enqueue_only_ms=round(enqueue, 3),
         wait_to_read_ms=round(wait, 3), waitall_ms=round(waitall, 3),
         host_fetch_ms=round(fetch, 3))


# ---------------------------------------------------------------- phase 4
def _rel_err(got, ref):
    """max |got - ref| over max |ref|, in f32."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-30))


def _kernel_vs_reference(kernel, reference, args, low):
    """Forward and gradient of ``kernel`` against ``reference`` on ``args``
    cast to ``low`` precision.  The yardstick is the reference formula run
    in f32 (matmuls at highest precision) on the same rounded inputs; the
    tolerance says the kernel may be at most 3x as far from it as XLA's
    own ``low``-precision run of the reference is, plus one rounding of
    the output: both round to ``low`` at the same points and differ only
    in accumulation order."""
    import jax
    import jax.numpy as jnp

    def scalar(fn):
        def loss(*a):
            outs = jax.tree_util.tree_leaves(fn(*a))
            return sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in outs)
        return loss

    lo = tuple(a.astype(low) for a in args)
    hi = tuple(a.astype(jnp.float32) for a in lo)
    argnums = tuple(range(len(args)))
    report = {}
    for what, run in (
            ("forward", lambda f, a: jax.tree_util.tree_leaves(
                jax.jit(f)(*a))),
            ("gradient", lambda f, a: jax.tree_util.tree_leaves(
                jax.jit(jax.grad(scalar(f), argnums))(*a)))):
        with jax.default_matmul_precision("highest"):
            truth = run(reference, hi)
        got = run(kernel, lo)
        xla = run(reference, lo)
        err_kernel = max(_rel_err(g, t) for g, t in zip(got, truth))
        err_xla = max(_rel_err(g, t) for g, t in zip(xla, truth))
        tol = 3.0 * err_xla + BF16_EPS
        assert all(np.all(np.isfinite(np.asarray(g, np.float32)))
                   for g in got), what
        assert err_kernel <= tol, \
            "%s: kernel is %.3g from the f32 reference, XLA's %s run is " \
            "%.3g, tolerance %.3g" % (what, err_kernel,
                                      jnp.dtype(low).name, err_xla, tol)
        report[what] = {"kernel_err": float("%.3g" % err_kernel),
                        "xla_err": float("%.3g" % err_xla),
                        "tolerance": float("%.3g" % tol)}
    return report


def _op_hlo(name, attrs, arrays):
    """Compiled HLO of the registered op's function under these attrs,
    called as ``ndarray.invoke`` calls it (train flag, rng key): the
    program a ``mx.nd.<op>`` call runs."""
    import jax
    from mxnet_tpu import random as _random
    from mxnet_tpu.base import AttrDict
    from mxnet_tpu.ops.registry import get_op
    op = get_op(name)
    parsed = op.parse_attrs(dict(attrs))
    if op.train_aware:
        parsed = AttrDict({**parsed, "__train__": False})
    prefix = [_random.next_key()] if op.needs_rng else []
    return jax.jit(lambda *a: op.fn(parsed, *a)).lower(
        *prefix, *arrays).compile().as_text()


def phase_kernels(device, seed, lstm_tbh=(35, 128, 650),
                  attn_bhtd=((8, 12, 2048, 64), (1, 16, 1024, 64)),
                  dtype="bfloat16"):
    """The two default-on Pallas kernels against the repo's XLA references,
    then the ops that dispatch to them.  On a TPU an op whose size gate
    says "kernel" must have the Mosaic custom call in its compiled HLO; off
    the TPU (the CPU rehearsal) the same op must have lowered without it.
    Attention runs at every shape of ``attn_bhtd``: a long sequence, and
    one chip's rows of the benchmark's GPT-2 cells."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import pallas_attention, pallas_rnn
    from mxnet_tpu.ops.nn import _mha_reference, mha_uses_kernel
    from mxnet_tpu.ops.rnn import _lstm_scan_xla, rnn_param_size

    on_tpu = device.platform == "tpu"
    low = jnp.dtype(dtype)
    rs = np.random.RandomState(seed)
    ctx = _ctx(mx, device)

    def normal(*shape, scale=1.0):
        return jax.device_put(
            (rs.standard_normal(shape) * scale).astype(np.float32), device)

    # --- LSTM recurrence -------------------------------------------------
    T, B, H = lstm_tbh
    lstm_args = (normal(T, B, 4 * H), normal(B, H, scale=0.5),
                 normal(B, H, scale=0.5), normal(4 * H, H, scale=H ** -0.5),
                 normal(4 * H, scale=0.1))
    lstm = _kernel_vs_reference(pallas_rnn.lstm_scan, _lstm_scan_xla,
                                lstm_args, low)

    # --- flash attention, causal ------------------------------------------
    attn = []
    for Bq, Hq, Tq, D in attn_bhtd:
        scale = D ** -0.5
        qkv = tuple(normal(Bq, Hq, Tq, D) for _ in range(3))
        report = _kernel_vs_reference(
            lambda q, k, v: pallas_attention.flash_attention(q, k, v, True,
                                                             scale),
            lambda q, k, v: _mha_reference(q, k, v, True, scale), qkv, low)
        attn.append({"B_H_T_D": [Bq, Hq, Tq, D], "causal": True, **report})

    # --- the ops themselves ----------------------------------------------
    telemetry.enable()
    data = mx.nd.array(rs.standard_normal((T, B, H)), ctx=ctx, dtype=dtype)
    params = mx.nd.array(
        rs.standard_normal(rnn_param_size(1, H, H, False, "lstm"))
        * H ** -0.5, ctx=ctx, dtype=dtype)
    h0 = mx.nd.zeros((1, B, H), ctx=ctx, dtype=dtype)
    rnn_attrs = {"state_size": H, "num_layers": 1, "mode": "lstm",
                 "state_outputs": True}
    out = mx.nd.RNN(data, params, h0, h0, **rnn_attrs)[0]
    assert out.shape == (T, B, H) and np.all(np.isfinite(
        out.asnumpy().astype(np.float32)))
    rnn_gate = pallas_rnn.lstm_scan_available(B, H, low)
    rnn_hlo = _op_hlo("RNN", rnn_attrs,
                      [a._data for a in (data, params, h0, h0)])
    rnn_kernel = "tpu_custom_call" in rnn_hlo

    ops, mha = [("RNN", rnn_gate, rnn_kernel)], []
    for Bq, Hq, Tq, D in attn_bhtd:
        flash0 = telemetry.value("attention_dispatch_total", path="flash")
        ref0 = telemetry.value("attention_dispatch_total", path="reference")
        d_model = Hq * D
        xa = mx.nd.array(rs.standard_normal((Bq, Tq, d_model)), ctx=ctx,
                         dtype=dtype)
        ws = [mx.nd.array(rs.standard_normal((d_model, d_model))
                          * d_model ** -0.5, ctx=ctx, dtype=dtype)
              for _ in range(4)]
        mha_attrs = {"num_heads": Hq, "causal": True}
        ya = mx.nd.MultiHeadAttention(xa, *ws, **mha_attrs)
        assert ya.shape == (Bq, Tq, d_model) and np.all(np.isfinite(
            ya.asnumpy().astype(np.float32)))
        mha_gate = mha_uses_kernel(Bq, Hq, Tq, D, low)
        flash = telemetry.value("attention_dispatch_total",
                                path="flash") - flash0
        refd = telemetry.value("attention_dispatch_total",
                               path="reference") - ref0
        mha_hlo = _op_hlo("MultiHeadAttention", mha_attrs,
                          [xa._data] + [w._data for w in ws])
        mha_kernel = "tpu_custom_call" in mha_hlo
        if mha_gate and not pallas_attention.INTERPRET:
            assert flash >= 1 and refd == 0, \
                "attention_dispatch_total grew flash=%s reference=%s " \
                "where the gate says kernel" % (flash, refd)
        ops.append(("MultiHeadAttention", mha_gate, mha_kernel))
        mha.append({"B_H_T_D": [Bq, Hq, Tq, D], "size_gate": bool(mha_gate),
                    "tpu_custom_call": mha_kernel,
                    "attention_dispatch": {"flash": int(flash),
                                           "reference": int(refd)},
                    "arm": "flash" if mha_kernel else "reference"})

    for op, gate, kernel in ops:
        assert kernel == (gate and on_tpu), \
            "%s: size gate says %s on %s, compiled HLO %s the Mosaic " \
            "custom call" % (op, "kernel" if gate else "reference",
                             device.platform,
                             "has" if kernel else "does not have")

    emit("kernels", dtype=dtype,
         tolerance="kernel error vs the f32 reference <= 3 x the error of "
                   "XLA's own low-precision run + 2^-8",
         lstm_scan={"T_B_H": list(lstm_tbh), **lstm},
         flash_attention=attn,
         rnn_op={"size_gate": bool(rnn_gate),
                 "tpu_custom_call": rnn_kernel,
                 "arm": "pallas" if rnn_kernel else "lax.scan"},
         mha_op=mha)


# ---------------------------------------------------------------- phase 5
def phase_serving(device, watch, seed, net_fn=resnet50,
                  example_shape=(3, 224, 224), buckets=(8, 32),
                  request_rows=(3, 8, 19, 32, 3)):
    """``serving.ModelServer`` over bf16 inference (f32 in and out, the
    cast is in the served graph), two batch buckets.  Each answer is
    compared with a direct ``Executor`` forward of the same graph and
    weights at the request's own row count: no queue, no padding, no
    bucket.  Tolerance 5e-2 of the largest logit: the two programs differ
    in batch size only, so XLA may tile, and therefore round, differently
    in each of ~50 bf16 layers; a wrong row or a slicing fault is O(1)."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.serving import ModelServer

    ctx = _ctx(mx, device)
    mx.random.seed(seed)
    net = net_fn()
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()
    net(mx.nd.zeros((1,) + tuple(example_shape), ctx=ctx)).wait_to_read()
    body = net(mx.sym.Cast(mx.sym.var("data"), dtype="bfloat16"))
    sym = mx.sym.Cast(body, dtype="float32", name="logits")
    aux_names = set(sym.list_auxiliary_states())
    params = {}
    for n, p in net.collect_params().items():
        arr = p.data()._data
        if n not in aux_names and not n.endswith(("_gamma", "_beta")):
            arr = arr.astype(jnp.bfloat16)     # PR 19's storage policy
        params[("aux:" if n in aux_names else "arg:") + n] = \
            nd.NDArray(arr, ctx)

    server = ModelServer(sym.tojson(), params,
                         example_shapes={"data": tuple(example_shape)},
                         ctx=ctx, batch_buckets=tuple(buckets),
                         max_batch_size=max(buckets), name="resnet50")
    mark = watch.mark()
    server.start()                              # warm-up compiles buckets
    warm = watch.since(mark)
    try:
        rs = np.random.RandomState(seed)
        requests = [rs.uniform(size=(r,) + tuple(example_shape))
                    .astype(np.float32) for r in request_rows]
        mark = watch.mark()
        t0 = time.perf_counter()
        answers = [server.predict({"data": x}, timeout=120.0)[0]
                   for x in requests]
        serve_ms = (time.perf_counter() - t0) / len(requests) * 1e3
        served = watch.since(mark)
        health = server.health()
    finally:
        server.stop()

    args = {k[4:]: v for k, v in params.items() if k.startswith("arg:")}
    auxs = {k[4:]: v for k, v in params.items() if k.startswith("aux:")}
    errs = []
    for x, got in zip(requests, answers):
        ex = sym.bind(ctx, {**args, "data": mx.nd.array(x, ctx=ctx)},
                      grad_req="null", aux_states=auxs)
        ref = ex.forward(is_train=False)[0]
        assert _on_device(ref._data, device), ref._data.devices()
        assert got.shape == ref.shape == (len(x), ref.shape[1]), got.shape
        assert np.all(np.isfinite(got)), "non-finite answer"
        errs.append(_rel_err(got, ref.asnumpy()))
    assert max(errs) <= 5e-2, "answers differ from a direct forward: %s" \
        % errs
    assert served["compile_requests"] == 0, served
    assert health["post_warmup_compiles"] == 0, health

    emit("serving", model=net_fn.__name__, buckets=list(buckets),
         request_rows=list(request_rows),
         warmup_seconds=round(server.warmup_seconds, 3),
         warmup_compile_seconds=warm["compile_seconds"],
         warmup_cache_hits=warm["cache_hits"],
         mean_request_ms=round(serve_ms, 3),
         max_rel_err_vs_direct_forward=round(max(errs), 5), tolerance=5e-2,
         post_warmup_compile_requests=served["compile_requests"],
         peak_bytes_in_use=_peak_bytes(device))


# ------------------------------------------------------ four-chip phase
def phase_multichip_module(devices, watch, seed, symbol_fn=resnet50_symbol,
                           batch=128, data_shape=(3, 224, 224),
                           classes=1000, steps=4, tol=2e-2):
    """``mx.mod.Module`` over all of ``devices`` with a local kvstore (the
    mesh-fused step, PR 6) against the same steps from the same seed on
    ``devices[:1]``.  bf16 as in the one-chip phase; per-step losses must
    agree within ``tol`` relative: the mesh program computes the same
    global-batch math, but at a quarter of the batch per device XLA tiles
    the convolutions differently, each of which may round a bf16 result
    the other way, compounded over the steps."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    n = len(devices)
    make = mx.tpu if devices[0].platform == "tpu" else mx.cpu
    x_np, y_np = _batch(seed, batch, data_shape, classes)
    telemetry.enable()
    with _bf16_policy():
        mesh0 = telemetry.value("step_dispatch_total", path="mesh_fused")
        mod = _module(mx, symbol_fn(mx), [make(i) for i in range(n)], seed,
                      batch, data_shape, lr=0.01)
        b = _Batch(mx.nd.array(x_np), mx.nd.array(y_np))
        mark = watch.mark()
        t0 = time.perf_counter()
        mesh_losses = _module_losses(mod, b, y_np, steps)
        mesh_s = time.perf_counter() - t0
        mesh_compile = watch.since(mark)
        mesh_steps = telemetry.value("step_dispatch_total",
                                     path="mesh_fused") - mesh0

        name = mod._param_names[0]
        weight = mod._exec_group.execs[0].arg_dict[name]._data
        out = mod.get_outputs()[0]._data
        weight_devs = {s.device for s in weight.addressable_shards}
        out_devs = {s.device for s in out.addressable_shards}
        out_rows = sorted(s.data.shape[0] for s in out.addressable_shards)
        in_use = [d.memory_stats()["bytes_in_use"] if d.memory_stats()
                  else None for d in devices]
        del mod, weight, out

        one = _module(mx, symbol_fn(mx), [make(0)], seed, batch,
                      data_shape, lr=0.01)
        one_losses = _module_losses(one, b, y_np, steps)

    assert mesh_steps == steps, \
        "step_dispatch_total{path=mesh_fused} grew %s over %d steps" \
        % (mesh_steps, steps)
    assert weight_devs == set(devices) and out_devs == set(devices), \
        (weight_devs, out_devs)
    assert out_rows == [batch // n] * n, out_rows
    if in_use[0] is not None:
        assert min(in_use[1:]) >= 0.25 * in_use[0], \
            "devices 1.. hold little next to device 0: %s" % in_use
    rel = [abs(a - c) / abs(c) for a, c in zip(mesh_losses, one_losses)]
    assert max(rel) <= tol, \
        "mesh and one-device losses differ: %s vs %s" % (mesh_losses,
                                                         one_losses)
    emit("multichip_module", devices=n, batch=batch,
         step_dispatch_mesh_fused=int(mesh_steps),
         mesh_losses=[round(v, 4) for v in mesh_losses],
         one_device_losses=[round(v, 4) for v in one_losses],
         max_rel_loss_diff=round(max(rel), 5), tolerance=tol,
         bytes_in_use_per_device=in_use, output_rows_per_device=out_rows,
         mesh_steps_seconds=round(mesh_s, 3),
         mesh_compile_seconds=mesh_compile["compile_seconds"])


def phase_multichip_dp_tp(devices, seed, in_dim=512, hidden=2048,
                          classes=1024, batch=64, steps=4, tol=1e-3):
    """``DataParallelTrainer`` on a dp=2 x tp=2 mesh with
    ``megatron_rules`` (the MLP ``__graft_entry__._dryrun_body`` builds, at
    a width that gives each chip real matmuls) against the same trainer on
    one device.  f32: sharding changes only the order of f32 sums, so
    per-step losses agree to ``tol`` relative.  (lr 0.005: at this width
    0.05 diverges, identically on both, which proves nothing.)"""
    import mxnet_tpu as mx
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer
    from mxnet_tpu.parallel.mesh import make_mesh, megatron_rules

    data = sym.var("data")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=hidden,
                                          name="fc1"), act_type="relu")
    net = sym.SoftmaxOutput(sym.FullyConnected(h, num_hidden=classes,
                                               name="fc2"), name="softmax")
    x_np, y_np = _batch(seed, batch, (in_dim,), classes)

    def run(mesh, rules):
        mx.random.seed(seed)
        trainer = DataParallelTrainer(net, mesh, rules=rules, lr=0.005,
                                      data_names=("data",),
                                      label_names=("softmax_label",))
        trainer.init_params(data=(batch, in_dim))
        losses = [float(trainer.step({"data": x_np, "softmax_label": y_np}))
                  for _ in range(steps)]
        return losses, trainer

    mesh = make_mesh({"dp": len(devices) // 2, "tp": 2}, devices=devices)
    sharded, trainer = run(mesh, megatron_rules(mesh))
    w1 = trainer.params["fc1_weight"]
    shard_shapes = sorted({tuple(s.data.shape)
                           for s in w1.addressable_shards})
    shard_devs = {s.device for s in w1.addressable_shards}
    single, _ = run(make_mesh({"dp": 1}, devices=devices[:1]), None)

    assert shard_devs == set(devices), shard_devs
    assert shard_shapes == [(hidden // 2, in_dim)], shard_shapes
    assert sharded[-1] < sharded[0], sharded
    rel = [abs(a - c) / abs(c) for a, c in zip(sharded, single)]
    assert max(rel) <= tol, (sharded, single)
    emit("multichip_dp_tp", mesh={"dp": len(devices) // 2, "tp": 2},
         fc1_weight_shard_shapes=[list(s) for s in shard_shapes],
         sharded_losses=[round(v, 5) for v in sharded],
         one_device_losses=[round(v, 5) for v in single],
         max_rel_loss_diff=round(max(rel), 7), tolerance=tol)


def phase_multichip_ring(devices, seed, bhtd=(1, 8, 8192, 128),
                         dtype="bfloat16"):
    """One ``ring_attention`` call (forward and gradient) over all of
    ``devices`` against the unsharded ``_mha_reference``; the tolerance is
    ``_kernel_vs_reference``'s.  At T/4 = 2048 per shard the size gate
    selects the stats-emitting Pallas kernel on a TPU."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.ops import pallas_attention
    from mxnet_tpu.ops.nn import _mha_reference
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.ring_attention import ring_attention

    B, H, T, D = bhtd
    n = len(devices)
    mesh = make_mesh({"sp": n}, devices=devices)
    sh = NamedSharding(mesh, P(None, None, "sp", None))
    rs = np.random.RandomState(seed)
    qkv = tuple(jax.device_put(
        rs.standard_normal(bhtd).astype(np.float32), sh) for _ in range(3))
    scale = D ** -0.5
    block = min(512, T // n)

    def ring(q, k, v):
        return ring_attention(q, k, v, mesh, axis="sp", causal=True,
                              scale=scale, block_size=block)

    report = _kernel_vs_reference(
        ring, lambda q, k, v: _mha_reference(q, k, v, True, scale), qkv,
        jnp.dtype(dtype))
    lo = tuple(a.astype(dtype) for a in qkv)
    hlo = jax.jit(ring).lower(*lo).compile().as_text()
    gate = pallas_attention.flash_attention_available(B, H, T // n, T // n,
                                                      D, jnp.dtype(dtype))
    kernel = "tpu_custom_call" in hlo
    on_tpu = devices[0].platform == "tpu"
    assert kernel == (gate and on_tpu), (gate, kernel)
    assert "collective-permute" in hlo, "no ring collective in the HLO"
    emit("multichip_ring", devices=n, B_H_T_D=list(bhtd), dtype=dtype,
         per_shard_size_gate=bool(gate), tpu_custom_call=kernel, **report)


# ------------------------------------------------------------------- main
def describe_environment(devices_used):
    """The first line: what this run is on."""
    import importlib.metadata as md
    import jax
    import jaxlib
    from mxnet_tpu import _native, program_cache
    dev = jax.devices()[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "devices_used": devices_used,
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "python": sys.version.split()[0],
            "compile_cache_dir": program_cache.place(),
            "compile_cache_placed_by": (
                "JAX_COMPILATION_CACHE_DIR"
                if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                else "MXNET_PROGRAM_CACHE_DIR"
                if os.environ.get("MXNET_PROGRAM_CACHE_DIR")
                else "default (in checkout)"),
            "native_runtime": _native.status()}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and data are made from it")
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip phase (needs 4 TPU "
                         "devices; the driver never passes this)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    need = 4 if args.multichip else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(json.dumps({
            "ok": False,
            "error": "needs %d TPU device(s); jax reports %d %s device(s)"
                     % (need, len(devices), devices[0].platform)}))
        return 1
    sys.path.insert(0, REPO)

    watch = CompileWatch()
    print(json.dumps({"phase": "environment",
                      **describe_environment(need)}), flush=True)
    run0 = watch.mark()
    t0 = time.perf_counter()
    try:
        if args.multichip:
            four = devices[:4]
            phase_multichip_module(four, watch, args.seed)
            phase_multichip_dp_tp(four, args.seed)
            phase_multichip_ring(four, args.seed)
        else:
            dev = devices[0]
            ft, x, y = phase_fused_trainer(dev, watch, args.seed)
            phase_sync(ft, x, y)
            del ft, x, y
            phase_module_step(dev, watch, args.seed)
            phase_kernels(dev, args.seed)
            phase_serving(dev, watch, args.seed)
    except BaseException as e:
        # reported, never swallowed: the failing last line, then the raise
        print(json.dumps({"ok": False, "error": repr(e)[:500]}), flush=True)
        raise

    from mxnet_tpu import program_cache
    emit("compile_cache", seconds_total=round(time.perf_counter() - t0, 1),
         **watch.since(run0), dir=program_cache.cache_dir(),
         jax_compilation_cache_dir=jax.config.jax_compilation_cache_dir,
         entries=program_cache.stats().get("entries"))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": need}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
