#!/usr/bin/env python
"""Benchmark: ResNet-50 synthetic-ImageNet training throughput (img/s/chip).

Primary BASELINE metric (BASELINE.json / SURVEY.md §6): the reference's
published ResNet-50 training number is 363.69 img/s on 1xV100 at batch 128
(docs/faq/perf.md:208-218); ``vs_baseline`` is measured img/s / 363.69.

Runs the FusedTrainer path: the whole training step — ResNet-50 v1 forward,
softmax-CE loss, backward, SGD-momentum update over all parameters —
compiled into ONE donated-buffer XLA executable (mxnet_tpu/fused.py; the
TPU answer to the reference's engine bulking + CachedOp amortizers).
Default dtype on TPU is bfloat16 compute with f32 master weights
(FusedTrainer mixed precision; the reference's fp16 multi_precision analog).

SYNC, as measured on the installed runtime (chip_smoke.py ``sync`` phase,
TPU v5 lite, PR 21): ``wait_to_read()`` (``block_until_ready``),
``nd.waitall()`` and a host fetch all wait for the device — eight
ResNet-50 steps took 384 / 386 / 384 ms ended by each, against 25 ms to
enqueue them, and fetching one ready scalar costs 0.4 ms.  (Until PR 21
``nd.waitall()`` waited for the host engine and ``jax.effects_barrier()``
only and returned at the enqueue time.)  A timing that ends in none of them
measures the enqueue: an earlier capture recorded a physically impossible
70k img/s that way.

SELF-VALIDATING, on any runtime:
  - every timing window ends in ``float(loss.asnumpy())`` — an actual
    device->host copy of a value that data-depends (donated-state chain) on
    every step in the window; it cannot complete early;
  - per-step hard-blocked timings give the latency profile
    (``step_ms_median`` / spread);
  - the reported ``value`` is the steady-state windowed throughput,
    accepted only if doubling the window's step count scales wall time
    ~linearly (the 1-iter-vs-N-iter check: broken blocking would make both
    windows take the same time) — otherwise the conservative per-step
    number is reported with ``window_suspect``;
  - an achieved-TFLOPS / MFU line makes impossible results self-evident;
    >1.2x chip peak exits nonzero instead of reporting.

DEVICE CONTRACT: every mode measures on the TPU and exits non-zero, with
a message, when ``jax.devices()[0].platform`` is not ``"tpu"`` — it never
shrinks a shape, re-executes on CPU or swallows a phase's failure.  The
``--smoke`` forms (``--bf16 --smoke``, ``--transformer --smoke``,
``--multichip --smoke``, ``--smoke``) are schema checks at a tiny size
that run wherever jax runs; every result line carries ``platform``,
``device_kind`` and ``device_count`` as jax reports them, so a CPU count
can never pass for a device number.

Conv-formulation A/B runs: the Convolution dispatch honors the four env
flags tabulated in docs/perf_analysis.md round 6 (MXNET_TPU_PALLAS_CONV
etc.); they are part of the op's jit-cache key, so an A/B is just two
bench invocations with the flag flipped — same process or not.  Probe
the kernels standalone first with tools/probe_pallas_conv.py (JSON
TFLOPS per shape).
"""
import json
import os
import statistics
import sys
import time

import numpy as np

# FLOP convention (stated once, used everywhere): 1 MAC = 2 FLOPs, the
# same currency as the chip-peak denominator.  ResNet-50 forward at 224px
# is ~4.1 GMACs/img (the commonly quoted "4.1 GFLOPs" counts MACs); the
# train step is ~3x forward (fwd + dgrad + wgrad).  Round-4 verdict: the
# old 12.3 number was GMACs against a 2-op/MAC peak — a 2x understatement.
TRAIN_GMACS_PER_IMG = 12.3
TRAIN_GFLOPS_PER_IMG = 2 * TRAIN_GMACS_PER_IMG
# chip peak dense TFLOPS for the MFU line live in mxnet_tpu.health, keyed
# by device_kind with each row's source; BENCH_PEAK_TFLOPS still overrides.


def _device_stamp(smoke):
    """``platform`` / ``device_kind`` / ``device_count`` as jax reports
    them — the fields every result line carries.  A measuring
    (non-smoke) mode without a TPU stops here, non-zero.  Also the entry
    points' one call that places the compile cache."""
    import jax
    from mxnet_tpu import program_cache
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not smoke:
        sys.exit("bench.py: no TPU visible (jax reports platform %r). "
                 "Measurements run on the chip only; the --smoke forms "
                 "check the schema on any platform." % dev.platform)
    program_cache.place()
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _spread_stats(step_times):
    """(median, p90 spread, max-min spread): p90/median-1 is the headline
    (robust to a single slow step, where max-min is not); max-min kept
    for context."""
    med = statistics.median(step_times)
    if not med:
        return med, 0.0, 0.0
    p90 = float(np.percentile(step_times, 90))
    return (med, p90 / med - 1.0,
            (max(step_times) - min(step_times)) / med)


def _measure(step, fetch, batch_items, warmup, iters, window_iters=None):
    """Shared measurement protocol: per-step hard-blocked latencies, then
    windowed steady-state with the 2x linear-scaling validation.
    ``window_iters`` widens only the scaling windows (retry path)."""
    window_iters = window_iters or iters
    for _ in range(warmup):
        fetch(step())

    step_times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        lval = fetch(step())
        step_times.append(time.perf_counter() - t0)
    med, spread, spread_maxmin = _spread_stats(step_times)
    blocked_rate = batch_items / med

    def window(n):
        t0 = time.perf_counter()
        loss = None
        for _ in range(n):
            loss = step()
        lval = fetch(loss)
        return time.perf_counter() - t0, lval

    w1, lval = window(window_iters)
    w2, lval = window(2 * window_iters)
    scaling = w2 / w1 if w1 > 0 else 0.0
    scaling_ok = 1.55 <= scaling <= 2.6
    window_rate = batch_items * 3 * window_iters / (w1 + w2)
    rate = window_rate if scaling_ok else blocked_rate
    return {
        "rate": rate, "blocked_rate": blocked_rate,
        "step_ms_median_blocked": med * 1e3, "step_spread_pct": 100 * spread,
        "step_spread_maxmin_pct": 100 * spread_maxmin,
        "windowed_rate": window_rate,
        "window_scaling_ratio": scaling, "window_suspect": not scaling_ok,
        "last_loss": lval,
    }


def _phase_breakdown(mx, gluon, net, batch_size, image_size, ctx, iters=3):
    """Blocked per-phase medians on the eager gluon path: each phase ends
    in a real D2H fetch so the split is honest.  Hard-blocking serializes
    what steady-state training overlaps, so the phase sum exceeds a
    pipelined step by construction — read it for WHERE a step's time
    goes (data / fwdbwd / update), not for absolute throughput.  An
    MXNET_TPU_FUSED_STEP=0/1 A/B of this section isolates the optimizer
    dispatch cost the fused step removes."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    params = [p for p in net.collect_params().values()
              if p.grad_req != "null"]
    rs = np.random.RandomState(0)
    data_t, fb_t, upd_t = [], [], []
    for _ in range(iters + 1):   # +1: first iter pays compile, dropped
        t0 = time.perf_counter()
        x = mx.nd.array(rs.uniform(
            size=(batch_size, 3, image_size, image_size)).astype(np.float32),
            ctx=ctx)
        y = mx.nd.array(rs.randint(0, 1000, (batch_size,)), ctx=ctx)
        float(y.asnumpy().ravel()[0])
        t1 = time.perf_counter()
        with mx.autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        float(loss.asnumpy().ravel()[0])
        float(params[0].list_grad()[0].asnumpy().ravel()[0])
        t2 = time.perf_counter()
        trainer.step(batch_size)
        float(params[0].list_data()[0].asnumpy().ravel()[0])
        t3 = time.perf_counter()
        data_t.append(t1 - t0)
        fb_t.append(t2 - t1)
        upd_t.append(t3 - t2)
    return {
        "data_ms": round(statistics.median(data_t[1:]) * 1e3, 2),
        "fwdbwd_ms": round(statistics.median(fb_t[1:]) * 1e3, 2),
        "update_ms": round(statistics.median(upd_t[1:]) * 1e3, 2),
        "iters": iters,
        "fused_step_env": os.environ.get("MXNET_TPU_FUSED_STEP", "<unset>"),
    }


def _io_breakdown(mx, ctx, batches=6, epochs=3):
    """Synthetic fast-step probe of the input pipeline: a PrefetchingIter
    (worker pool + producer-side device_put) feeds a trivial consumer and
    the io_* telemetry series say how starved that consumer was.  A
    prefetch-wait p50 of ~0 means the pipeline keeps up at full step
    rate; the device-put total is host->device time the producer absorbed
    off the step's critical path."""
    from mxnet_tpu import telemetry
    was = telemetry.enabled
    telemetry.enable()
    batch = 32
    data = np.zeros((batches * batch, 8), np.float32)
    label = np.zeros((batches * batch,), np.float32)
    it = mx.io.PrefetchingIter(
        mx.io.NDArrayIter(data, label, batch_size=batch),
        device=ctx, num_workers=2)
    n = 0
    for _ in range(epochs):
        for b in it:
            float(b.data[0].asnumpy().ravel()[0])  # simulated fast step
            n += 1
        it.reset()
    put = telemetry.registry().get("io_device_put_seconds")
    put_sum = (put.labels(iter="PrefetchingIter").get()["sum"]
               if put is not None else 0.0)
    out = {
        "prefetch_wait_p50_ms": round(1e3 * telemetry.quantile(
            "io_prefetch_wait_seconds", 0.5, iter="PrefetchingIter"), 3),
        "prefetch_wait_p99_ms": round(1e3 * telemetry.quantile(
            "io_prefetch_wait_seconds", 0.99, iter="PrefetchingIter"), 3),
        "device_put_seconds": round(put_sum, 4),
        "pipeline_depth": int(telemetry.value(
            "io_pipeline_depth", iter="PrefetchingIter")),
        "pipeline_workers": int(telemetry.value(
            "io_pipeline_workers", iter="PrefetchingIter")),
        "batches": n,
    }
    if not was:
        telemetry.disable()
    return out


def bench_lstm_lm(ctx, dtype, peak_tflops, smoke=False):
    """BASELINE metric #2: Gluon LSTM LM training tokens/sec/chip
    (ref workload: example/gluon/word_language_model/train.py; the
    reference tree publishes no tokens/sec number — BASELINE.md — so
    vs_baseline is null and the absolute number is the record)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn, rnn

    vocab = int(os.environ.get("BENCH_LSTM_VOCAB", "33278"))  # wikitext-2
    embed = hidden = int(os.environ.get("BENCH_LSTM_HID", "650"))  # medium
    layers = 2
    bptt = int(os.environ.get("BENCH_LSTM_BPTT", "35"))
    batch = int(os.environ.get("BENCH_LSTM_BATCH", "128"))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    # longer window than the ResNet section: the LM step is ~10 ms on
    # device, so the per-window sync needs more steps to amortize before
    # the 2x-scaling validation has signal
    iters = int(os.environ.get("BENCH_LSTM_ITERS", "32"))
    if smoke:
        vocab, bptt, batch, iters = 512, 8, 8, 3

    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Embedding(vocab, embed))
        net.add(rnn.LSTM(hidden, num_layers=layers, dropout=0.2))
        net.add(nn.Dense(vocab, flatten=False))
    net.initialize(ctx=ctx)

    # token ids kept < 256 so they survive the bf16 input cast exactly
    # (embedding-row choice doesn't affect throughput)
    toks = np.random.randint(0, min(256, vocab), (bptt, batch))
    x = mx.nd.array(toks, ctx=ctx)
    y = mx.nd.array(toks, ctx=ctx)
    net(x).wait_to_read()   # eager once: resolves LSTM deferred shapes
    net.hybridize()

    # the PUBLIC loss API: gluon's SoftmaxCrossEntropyLoss lowers the
    # sparse path to the streaming logsumexp CE (ops/nn.py:streaming_ce),
    # so the bench now measures exactly what a user of gluon.loss gets
    # (the +23% streaming win is in the framework, not the bench)
    ft = mx.FusedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                         "sgd", {"learning_rate": 0.5}, dtype=dtype)

    def fetch(loss):
        return float(loss.asnumpy().ravel()[0])

    m = _measure(lambda: ft.step(x, y), fetch, bptt * batch, warmup, iters)
    retried = False
    if m["window_suspect"] and not smoke:
        # the scaling validation can flake when dispatch latency jitters;
        # one retry with doubled windows (blocked phase kept short) before
        # settling for the conservative blocked number — recorded in the
        # output so a passed retry is distinguishable from a clean pass
        retried = True
        m = _measure(lambda: ft.step(x, y), fetch, bptt * batch, 1,
                     iters, window_iters=2 * iters)
    if not np.isfinite(m["last_loss"]):
        return {"metric": "lstm_lm_train_tokens_per_sec", "value": 0.0,
                "unit": "tokens/s/chip", "error": "non-finite loss"}, 1

    # per-token train FLOPs = 3x forward; forward = 2 LSTM layers of
    # 2*4h*(in+h) + the h->vocab decoder GEMM
    flops_per_tok = 3 * (sum(2 * 4 * hidden * ((embed if l == 0 else hidden)
                                               + hidden)
                             for l in range(layers))
                         + 2 * hidden * vocab)
    from mxnet_tpu import health as _health
    achieved = _health.achieved_tflops(m["rate"], flops_per_tok)
    mfu = _health.mfu_fraction(m["rate"], flops_per_tok, peak_tflops)
    if _health.mfu_impossible(mfu, ctx.device_type):
        return {"metric": "lstm_lm_train_tokens_per_sec", "value": 0.0,
                "unit": "tokens/s/chip",
                "error": "impossible: %.0f%% MFU" % (100 * mfu)}, 1
    return {
        "metric": "lstm_lm_train_tokens_per_sec",
        "value": round(m["rate"], 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,  # no in-tree published tokens/sec (BASELINE.md)
        "config": "vocab=%d,hidden=%d,layers=%d,bptt=%d,batch=%d"
                  % (vocab, hidden, layers, bptt, batch),
        "step_ms_median_blocked": round(m["step_ms_median_blocked"], 2),
        "step_spread_pct": round(m["step_spread_pct"], 1),
        "step_spread_maxmin_pct": round(m["step_spread_maxmin_pct"], 1),
        "blocked_tokens_per_sec": round(m["blocked_rate"], 1),
        "windowed_tokens_per_sec": round(m["windowed_rate"], 1),
        "window_scaling_ratio": round(m["window_scaling_ratio"], 3),
        "window_suspect": m["window_suspect"],
        "window_retried": retried,
        "achieved_tflops": round(achieved, 2),
        "mfu_pct": round(100 * mfu, 2),
    }, 0


def _multichip_symbol(mx, model):
    """(symbol, data_shape_fn, label_name) for the multichip bench."""
    if model == "resnet50":
        from mxnet_tpu.gluon.model_zoo import vision
        net = vision.resnet50_v1()
        out = net(mx.sym.var("data"))
        return mx.sym.SoftmaxOutput(out, mx.sym.var("softmax_label"),
                                    name="softmax"), 1000
    # "mlp": small FC stack — probe_multichip --smoke / CI shape
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=16, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, mx.sym.var("softmax_label"),
                                name="softmax"), 16


def _multichip_run(mx, sym, ctxs, batch, data_shape, n_classes,
                   warmup, iters):
    """One Module training run over ``ctxs``; returns the _measure dict."""
    mod = mx.mod.Module(sym, data_names=("data",),
                        label_names=("softmax_label",), context=ctxs)
    mod.bind(data_shapes=[("data", (batch,) + data_shape)],
             label_shapes=[("softmax_label", (batch,))])
    mx.random.seed(7)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
    mod.init_optimizer(kvstore="local", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    rs = np.random.RandomState(3)
    x = mx.nd.array(rs.uniform(size=(batch,) + data_shape)
                    .astype(np.float32))
    y = mx.nd.array(rs.randint(0, n_classes, (batch,))
                    .astype(np.float32))

    class _B:
        data = [x]
        label = [y]

    def step():
        mod.forward_backward(_B)
        mod.update()
        return mod

    def fetch(m):
        # outputs live in the same donated-chain program as the update:
        # this D2H cannot complete before the steps it depends on
        return float(m.get_outputs()[0].asnumpy().ravel()[0])

    return _measure(step, fetch, batch, warmup, iters)


def bench_multichip():
    """Entry for ``bench.py --multichip``: mesh-fused Module throughput
    over every visible device (``BENCH_MULTICHIP_DEVICES`` to use fewer)
    + scaling efficiency vs one device.

    The tentpole metric: data-parallel ResNet-50 through mx.mod.Module with
    kvstore='local' — the mesh-fused GSPMD path dispatches automatically
    (step_dispatch_total{path="mesh_fused"}), and the number is honest by
    the same windowed + 2x-scaling protocol as the single-chip bench.
    Fewer than two devices, or fewer than asked for, is an error: nothing
    re-executes on virtual devices.  ``--smoke`` runs the tiny MLP and is
    what a CPU schema check launches with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    smoke = "--smoke" in sys.argv
    stamp = _device_stamp(smoke)
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    n_devices = int(os.environ.get("BENCH_MULTICHIP_DEVICES", "0")) \
        or stamp["device_count"]
    if not 2 <= n_devices <= stamp["device_count"]:
        sys.exit("bench.py --multichip: asked for %d device(s), jax "
                 "reports %d %s device(s); needs at least 2 and no more "
                 "than are visible" % (n_devices, stamp["device_count"],
                                       stamp["platform"]))
    model = "mlp" if smoke else os.environ.get("BENCH_MULTICHIP_MODEL",
                                               "resnet50")
    if model == "resnet50":
        image = int(os.environ.get("BENCH_MULTICHIP_IMAGE", "224"))
        batch = int(os.environ.get("BENCH_MULTICHIP_BATCH", "128"))
        data_shape = (3, image, image)
    else:
        batch = int(os.environ.get("BENCH_MULTICHIP_BATCH", "16"))
        data_shape = (10,)
    warmup = int(os.environ.get("BENCH_WARMUP", "1" if smoke else "3"))
    iters = int(os.environ.get("BENCH_MULTICHIP_ITERS",
                               "2" if smoke else "16"))
    batch -= batch % n_devices  # dp axis must divide the batch
    sym, n_classes = _multichip_symbol(mx, model)
    make_ctx = mx.tpu if stamp["platform"] == "tpu" else mx.cpu
    ctx = [make_ctx(i) for i in range(n_devices)]

    telemetry.enable()
    mesh0 = telemetry.value("step_dispatch_total", path="mesh_fused")
    m8 = _multichip_run(mx, sym, ctx, batch, data_shape, n_classes,
                        warmup, iters)
    mesh_steps = telemetry.value("step_dispatch_total",
                                 path="mesh_fused") - mesh0
    m1 = _multichip_run(mx, sym, ctx[:1], batch // n_devices, data_shape,
                        n_classes, warmup, iters)

    ips8, ips1 = m8["rate"], m1["rate"]
    # perfect linear scaling: n chips do n x the per-chip-batch work of 1
    scaling_eff = (ips8 / ips1) / n_devices if ips1 > 0 else 0.0
    ok = (np.isfinite(m8["last_loss"]) and mesh_steps > 0
          and ips8 > 0 and ips1 > 0)
    result = {
        "metric": "%s_%dchip_img_per_sec" % (model, n_devices),
        "value": round(ips8, 2),
        "img_per_sec": round(ips8, 2),
        "single_chip_img_per_sec": round(ips1, 2),
        "scaling_efficiency": round(scaling_eff, 4),
        "n_devices": n_devices,
        "mesh_fused_steps": int(mesh_steps),
        "batch": batch,
        "model": model,
        **stamp,
        "step_ms_median_blocked": round(m8["step_ms_median_blocked"], 2),
        "window_scaling_ratio": round(m8["window_scaling_ratio"], 3),
        "window_suspect": m8["window_suspect"],
        "smoke": smoke,
        "ok": bool(ok),
    }
    out = os.environ.get("MULTICHIP_OUT")
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
    # multichip rounds ride the same ledger when one is active
    # (MXNET_RUNLOG_DIR/_PATH in the launching environment)
    from mxnet_tpu import runlog as _runlog
    if _runlog.enabled():
        _runlog.note_topology()
        _runlog.event("bench_result", metric=result["metric"],
                      value=result["value"], result=result)
    print(json.dumps(result))
    return 0 if ok else 1


def _sentinel_verdict(result, source):
    """Compare ``result`` against the committed bench_history baseline:
    the table goes to stderr, the FAIL/WARN rows into the result.  The
    verdict never gates the bench (gating exits belong to
    tools/sentinel.py runs); an error in the comparison is an error."""
    from tools import sentinel as _sentinel
    if not os.path.exists(_sentinel.DEFAULT_BASELINE):
        return None
    with open(_sentinel.DEFAULT_BASELINE) as f:
        bdoc = json.load(f)
    cand = _sentinel.normalize(result, source)
    rows = _sentinel.compare(bdoc, cand)
    sys.stderr.write(_sentinel.markdown_table(rows, bdoc, cand))
    return {
        "regression": bool(_sentinel.verdict_exit(rows)),
        "baseline": bdoc.get("round") or bdoc.get("source"),
        "rows": [r for r in rows if r["verdict"] in ("FAIL", "WARN")],
    }


def bench_bf16():
    """Entry for ``bench.py --bf16``: fp32 vs bf16 mixed-precision A/B
    through the Module fused-step path (MXNET_TPU_BF16 + multi_precision
    SGD — master-fp32 trajectory, bf16 storage).

    The flag is read at BIND time, so the A/B flips it in-process between
    two Module builds — no subprocess.  Three claims, measured:
      - **memory**: params + activations owner bytes on the memwatch
        ledger at ~half the fp32 run's (bf16 storage), peak bytes down;
      - **matched convergence**: same seed, same batches, same step
        count — both loss curves descend and the bf16 final window ends
        inside (or below) the fp32 curve's trailing band;
      - **throughput**: img/s on the same windowed protocol.  The
        ``--smoke`` form (tiny MLP) is a schema + memory + convergence
        check; off the TPU XLA *emulates* bf16 (upcast-compute-downcast),
        so its throughput column says nothing about a chip
        (docs/perf_analysis.md round 19).
    """
    smoke = "--smoke" in sys.argv
    stamp = _device_stamp(smoke)
    import gc

    import mxnet_tpu as mx
    from mxnet_tpu import memwatch as _memwatch

    on_cpu = stamp["platform"] == "cpu"
    ctx = mx.cpu(0) if on_cpu else mx.tpu(0)
    model = "mlp" if smoke else os.environ.get("BENCH_BF16_MODEL",
                                               "resnet50")
    if model == "resnet50":
        image = int(os.environ.get("BENCH_IMAGE", "224"))
        batch = int(os.environ.get("BENCH_BATCH", "128"))
        data_shape = (3, image, image)
    else:
        batch = int(os.environ.get("BENCH_BATCH", "16"))
        data_shape = (10,)
    warmup = int(os.environ.get("BENCH_WARMUP", "1" if smoke else "3"))
    iters = int(os.environ.get("BENCH_ITERS", "2" if smoke else "16"))
    # run the convergence probe to its loss FLOOR: while the loss is
    # still dropping steeply, bf16 forward noise shows up as a one-step
    # lag that dwarfs the band; at the floor both runs flatten and the
    # residual gap is the actual precision cost
    loss_steps = int(os.environ.get("BENCH_BF16_LOSS_STEPS",
                                    "6" if smoke else "30"))
    # small enough that the fp32 trajectory DESCENDS on the repeated
    # batch: at blow-up lr the A/B compares divergence rates, not
    # precision (momentum 0.9 makes the effective step ~10x this)
    lr = float(os.environ.get("BENCH_BF16_LR", "0.01"))
    sym, n_classes = _multichip_symbol(mx, model)
    _memwatch.enable()

    rs = np.random.RandomState(3)
    x_np = rs.uniform(size=(batch,) + data_shape).astype(np.float32)
    y_np = rs.randint(0, n_classes, (batch,)).astype(np.float32)

    def run(bf16):
        # per-run ledger + allocator high-water: without the reset the
        # second run inherits the first's process-wide peak
        _memwatch.reset()
        _memwatch.enable()
        if bf16:
            os.environ["MXNET_TPU_BF16"] = "1"
        else:
            os.environ.pop("MXNET_TPU_BF16", None)
        mod = mx.mod.Module(sym, data_names=("data",),
                            label_names=("softmax_label",), context=[ctx])
        mod.bind(data_shapes=[("data", (batch,) + data_shape)],
                 label_shapes=[("softmax_label", (batch,))])
        mx.random.seed(7)
        mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": lr,
                                             "momentum": 0.9,
                                             "multi_precision": bf16})
        wdt = mod._exec_group.execs[0].arg_dict[
            mod._param_names[0]].dtype
        x = mx.nd.array(x_np)
        y = mx.nd.array(y_np)

        class _B:
            data = [x]
            label = [y]

        def step():
            mod.forward_backward(_B)
            mod.update()
            return mod

        def fetch(m):
            # mean CE of the step's own (pre-update) softmax output — a
            # real D2H that serializes the donated-state chain AND the
            # convergence signal
            p = m.get_outputs()[0].asnumpy().astype(np.float64)
            rows = p.reshape(len(y_np), -1)[np.arange(len(y_np)),
                                            y_np.astype(int)]
            return float(np.mean(-np.log(np.maximum(rows, 1e-30))))

        losses = [fetch(step()) for _ in range(loss_steps)]
        m = _measure(step, fetch, batch, warmup, iters)
        snap = _memwatch.census()
        owners = {o: rec["bytes"] for o, rec in snap["owners"].items()}
        out = {
            "weight_dtype": str(np.dtype(wdt)),
            "img_per_sec": round(m["rate"], 2),
            "step_ms_median_blocked": round(m["step_ms_median_blocked"], 2),
            "window_scaling_ratio": round(m["window_scaling_ratio"], 3),
            "window_suspect": m["window_suspect"],
            "loss_first": round(losses[0], 4),
            "loss_final_mean": round(float(np.mean(
                losses[-max(1, loss_steps // 3):])), 4),
            "losses": [round(l, 4) for l in losses],
            "params_bytes": owners.get("params", 0),
            "activations_bytes": owners.get("activations", 0),
            "opt_state_bytes": owners.get("opt_state", 0),
            "peak_bytes_in_use": max(
                (st["peak_bytes_in_use"]
                 for st in snap["devices"].values()), default=0),
        }
        del mod, x, y, _B
        gc.collect()
        return out

    r32 = run(False)
    r16 = run(True)
    assert r32["weight_dtype"] == "float32", r32["weight_dtype"]
    assert r16["weight_dtype"] == "bfloat16", r16["weight_dtype"]
    pa32 = r32["params_bytes"] + r32["activations_bytes"]
    pa16 = r16["params_bytes"] + r16["activations_bytes"]
    loss_delta = abs(r16["loss_final_mean"] - r32["loss_final_mean"])
    # matched convergence, curve-vs-band: identical batches from
    # identical init, but the one-batch probe is chaotic (BN + momentum
    # make fp32 itself bounce around its floor), so a point-delta of the
    # final windows measures luck, not precision.  The claim that holds:
    # both curves descend, and bf16 ends no WORSE than the fp32 curve's
    # own trailing band (ending lower than fp32 is not a failure).
    tail32 = r32["losses"][len(r32["losses"]) // 2:]
    band_hi = max(tail32) + max(
        0.15, 0.1 * max(abs(r32["loss_final_mean"]), 1e-6))
    # the descent gate only needs to catch a FLAT curve (updates not
    # landing, e.g. a stale-master bug): any real progress clears it
    descended = all(
        min(r["losses"]) <= r["losses"][0]
        - max(0.05, 0.02 * abs(r["losses"][0])) for r in (r32, r16))
    converged = descended and r16["loss_final_mean"] <= band_hi
    halved = pa32 > 0 and pa16 <= 0.65 * pa32
    ok = converged and halved
    result = {
        "metric": "%s_bf16_img_per_sec" % model,
        "value": r16["img_per_sec"],
        "unit": "img/s/chip",
        "model": model,
        "batch": batch,
        **stamp,
        # CPU has no bf16 ALU: XLA upcasts per op, so throughput there is
        # a regression canary, not a speedup claim (chip-pending)
        "throughput_chip_pending": on_cpu,
        "fp32": r32,
        "bf16": r16,
        "params_activations_ratio": round(pa16 / pa32, 4) if pa32 else None,
        "params_ratio": (round(r16["params_bytes"] / r32["params_bytes"], 4)
                         if r32["params_bytes"] else None),
        "peak_bytes_in_use": r16["peak_bytes_in_use"],
        "peak_ratio": (round(r16["peak_bytes_in_use"]
                             / r32["peak_bytes_in_use"], 4)
                       if r32["peak_bytes_in_use"] else None),
        "loss_delta": round(loss_delta, 4),
        "fp32_band_max": round(band_hi, 4),
        "matched_convergence": bool(converged),
        "footprint_halved": bool(halved),
        "ok": bool(ok),
    }
    if os.environ.get("BENCH_SENTINEL", "1") != "0" and not smoke:
        result["sentinel"] = _sentinel_verdict(result, "bench.py --bf16")
    print(json.dumps(result))
    return 0 if ok else 1


def bench_transformer():
    """Entry for ``bench.py --transformer``: decoder-LM training
    tokens/s + MFU through the Module fused-step path (ISSUE 20).

    The workload is ``models.transformer_lm`` on a ``models.configs``
    ladder entry, fed by ``io.SyntheticLMIter`` (deterministic
    next-token stream), trained with SGD — the whole step in one
    donated-buffer executable, attention dispatching to the Pallas
    flash kernel when the shape gates allow
    (``attention_dispatch_total{path=...}`` says which path this run
    actually compiled).  Reported alongside the throughput row:

      - **MFU** against the chip peak from ``health.peak_tflops`` using
        ``TransformerConfig.flops_per_token()`` (PaLM 6N+12LTd
        convention) — the honest denominator for cross-paper compares;
      - **atlas** per-layer flops/bytes table (which scopes own the MFU
        gap) + the min per-program coverage;
      - **memwatch** owner bytes (params / activations / opt_state) and
        per-device peak;
      - **post-warmup compiles**: jit-cache misses after the warmup
        steps — a nonzero count means something (env key churn, shape
        wobble) is recompiling inside the measurement window.

    ``--smoke`` runs the tiny config and GATES on the last two: zero
    post-warmup compiles and >=90%% atlas coverage (the verify-skill
    probe).  The full run writes the sentinel verdict like the other
    bench entries.
    """
    smoke = "--smoke" in sys.argv
    stamp = _device_stamp(smoke)

    import mxnet_tpu as mx
    from mxnet_tpu import health as _health
    from mxnet_tpu import memwatch as _memwatch
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import get_config
    from mxnet_tpu.models.transformer import transformer_lm

    ctx = mx.cpu(0) if stamp["platform"] == "cpu" else mx.tpu(0)
    cfg_name = os.environ.get("BENCH_TFM_CONFIG",
                              "tiny" if smoke else "gpt2-small")
    overrides = {}
    if os.environ.get("BENCH_TFM_SEQLEN"):
        overrides["seq_len"] = int(os.environ["BENCH_TFM_SEQLEN"])
    elif smoke:
        overrides["seq_len"] = 32
    cfg = get_config(cfg_name, **overrides)
    batch = int(os.environ.get("BENCH_TFM_BATCH", "4" if smoke else "16"))
    warmup = int(os.environ.get("BENCH_WARMUP", "1" if smoke else "3"))
    iters = int(os.environ.get("BENCH_ITERS", "2" if smoke else "16"))
    bf16 = os.environ.get("MXNET_TPU_BF16", "0") != "0"
    dtype = "bfloat16" if bf16 else "float32"
    peak_tflops = _health.peak_tflops()

    telemetry.enable()
    _health.enable()
    _memwatch.reset()
    _memwatch.enable()

    net = transformer_lm(cfg)
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",), context=[ctx])
    mod.bind(data_shapes=[("data", (batch, cfg.seq_len))],
             label_shapes=[("softmax_label", (batch, cfg.seq_len))])
    mx.random.seed(7)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
    mod.init_optimizer(kvstore="local", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9,
                                         "multi_precision": bf16})

    it = mx.io.SyntheticLMIter(cfg.vocab_size, cfg.seq_len,
                               batch_size=batch, num_batches=8, seed=0)

    def next_batch():
        try:
            return next(it)
        except StopIteration:
            it.reset()
            return next(it)

    def step():
        mod.forward_backward(next_batch())
        mod.update()
        return mod

    def fetch(m):
        # the make_loss head is the graph output: this D2H of the mean
        # CE data-depends on the whole donated step chain
        return float(m.get_outputs()[0].asnumpy().ravel()[0])

    # warmup OUTSIDE _measure so the post-warmup compile count brackets
    # exactly the measurement window (warmup pays all legitimate
    # compiles; anything after is a cache-key bug)
    for _ in range(warmup):
        fetch(step())
    misses0, _ = _health._compile_totals()
    tokens = batch * cfg.seq_len
    m = _measure(step, fetch, tokens, 0, iters)
    post_compiles = int(_health._compile_totals()[0] - misses0)

    flops_per_tok = cfg.flops_per_token()
    achieved = _health.achieved_tflops(m["rate"], flops_per_tok)
    mfu = _health.mfu_fraction(m["rate"], flops_per_tok, peak_tflops)
    if _health.mfu_impossible(mfu, stamp["platform"]):
        print(json.dumps({"metric": "transformer_tokens_per_sec",
                          "value": 0.0, "unit": "tokens/s/chip", **stamp,
                          "error": "impossible: %.0f%% MFU" % (100 * mfu)}))
        return 1

    from mxnet_tpu import atlas as _atlas
    atlas_snap = _atlas.snapshot(top_k=10)
    covs = [a.get("coverage_pct") for a in atlas_snap.values()
            if isinstance(a, dict) and a.get("coverage_pct") is not None]
    atlas_cov = min(covs) if covs else 0.0

    snap = _memwatch.census()
    owners = {o: rec["bytes"] for o, rec in snap["owners"].items()}
    paths = {}
    fam = telemetry.registry().get("attention_dispatch_total")
    if fam is not None:
        # samples() yields (label-values-tuple, value); sole label: path
        paths = {lv[0]: int(v) for lv, v in fam.samples()}

    finite = np.isfinite(m["last_loss"])
    gates_ok = post_compiles == 0 and atlas_cov >= 90.0
    ok = finite and (gates_ok if smoke else True)
    result = {
        "metric": "transformer_tokens_per_sec",
        "value": round(m["rate"], 1),
        "unit": "tokens/s/chip",
        "config": cfg.name,
        "vocab_size": cfg.vocab_size, "n_layers": cfg.n_layers,
        "d_model": cfg.d_model, "n_heads": cfg.n_heads,
        "d_ff": cfg.d_ff, "seq_len": cfg.seq_len, "batch": batch,
        "n_params": cfg.n_params(),
        "flops_per_token": flops_per_tok,
        "dtype": dtype,
        **stamp,
        "attention_dispatch": paths,
        "step_ms_median_blocked": round(m["step_ms_median_blocked"], 2),
        "step_spread_pct": round(m["step_spread_pct"], 1),
        "blocked_tokens_per_sec": round(m["blocked_rate"], 1),
        "windowed_tokens_per_sec": round(m["windowed_rate"], 1),
        "window_scaling_ratio": round(m["window_scaling_ratio"], 3),
        "window_suspect": m["window_suspect"],
        "last_loss": round(m["last_loss"], 4),
        "achieved_tflops": round(achieved, 3),
        "mfu_pct": round(100 * mfu, 2),
        "post_warmup_compiles": post_compiles,
        "atlas_coverage_min_pct": round(atlas_cov, 2),
        "atlas": atlas_snap,
        "params_bytes": owners.get("params", 0),
        "activations_bytes": owners.get("activations", 0),
        "opt_state_bytes": owners.get("opt_state", 0),
        "peak_bytes_in_use": max(
            (st["peak_bytes_in_use"]
             for st in snap["devices"].values()), default=0),
        "smoke": smoke,
        "zero_post_warmup_compiles": post_compiles == 0,
        "atlas_coverage_ok": atlas_cov >= 90.0,
        "ok": bool(ok),
    }
    if os.environ.get("BENCH_SENTINEL", "1") != "0" and not smoke:
        result["sentinel"] = _sentinel_verdict(result,
                                               "bench.py --transformer")
    out = dict(result)
    if smoke:  # keep the smoke line greppable; the full table is --full's
        out.pop("atlas", None)
    print(json.dumps(out))
    return 0 if ok else 1


def main():
    smoke = "--smoke" in sys.argv
    stamp = _device_stamp(smoke)
    # --smoke: the schema check (tools/probe_health.py --smoke) at a size
    # that finishes anywhere; chosen by the flag, never by the device
    batch_size = int(os.environ.get("BENCH_BATCH", "8" if smoke else "128"))
    image_size = int(os.environ.get("BENCH_IMAGE", "64" if smoke else "224"))
    warmup = int(os.environ.get("BENCH_WARMUP", "2" if smoke else "3"))
    iters = int(os.environ.get("BENCH_ITERS", "3" if smoke else "16"))
    path = os.environ.get("BENCH_PATH", "fused")

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision

    ctx = mx.cpu(0) if stamp["platform"] == "cpu" else mx.tpu(0)
    dtype = os.environ.get(
        "BENCH_DTYPE", "float32" if stamp["platform"] == "cpu"
        else "bfloat16")

    from mxnet_tpu import health as _health
    # one table keyed by device_kind (+ BENCH_PEAK_TFLOPS override), shared
    # with the runtime monitor; an unknown device is an error there
    peak_tflops = _health.peak_tflops()

    # live health monitor rides along by default: programs register at
    # their first_run probes (lowering-only analysis — zero extra
    # compiles) and the MFU/verdict gauges update per step
    health_on = os.environ.get("BENCH_HEALTH", "1") != "0"
    if health_on:
        _health.enable()

    # device-memory ledger rides along the same way (ISSUE 16): the
    # census thread samples owner/device gauges during the run and the
    # result carries a "memory" block plus the census A/B overhead
    from mxnet_tpu import memwatch as _memwatch
    memwatch_on = os.environ.get("BENCH_MEMWATCH", "1") != "0"
    if memwatch_on:
        _memwatch.enable()

    net = vision.resnet50_v1()
    net.initialize(ctx=ctx)
    net.hybridize()

    x = mx.nd.random.uniform(shape=(batch_size, 3, image_size, image_size),
                             ctx=ctx)
    y = mx.nd.array(np.random.randint(0, 1000, (batch_size,)), ctx=ctx)
    if memwatch_on:
        # the bench holds one synthetic batch for the whole run — ledger
        # it as input data or it ages into a leak suspect
        _memwatch.tag("io", (x, y), detail="bench_batch")

    if path == "fused":
        net(x).wait_to_read()          # materialize parameters
        ft = mx.FusedTrainer(net, "softmax_cross_entropy", "sgd",
                             {"learning_rate": 0.1, "momentum": 0.9},
                             dtype=dtype)

        def step():
            return ft.step(x, y)
    else:
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9})

        def step():
            with mx.autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(batch_size)
            return loss

    def fetch(loss):
        """End a timed region with a host fetch of the loss: it cannot
        complete before every step the value depends on has run."""
        return float(loss.asnumpy().ravel()[0])

    def window(n):
        """n steps, one D2H at the end (steady-state training pattern —
        the donated-state chain makes the final loss depend on them all)."""
        t0 = time.perf_counter()
        loss = None
        for _ in range(n):
            loss = step()
        lval = fetch(loss)
        return time.perf_counter() - t0, lval

    # cold-start currency: the first step owns trace + XLA compile (or a
    # program-cache restore when the cache directory is prefilled — the
    # deploy path tools/cache_prefill.py sets up).  The compile
    # component is isolated later as wall minus the steady-state serial
    # median, since one step's execution rides inside this wall time.
    t0 = time.perf_counter()
    fetch(step())
    first_step_wall = time.perf_counter() - t0
    for _ in range(max(0, warmup - 1)):
        fetch(step())

    from mxnet_tpu.train_loop import OverlappedLoop

    def blocked_phase(depth, n, step_fn=None):
        """Per-step wall times with every loss fetched via a real D2H,
        but `depth` steps in flight (train_loop overlapped window);
        depth=0 is the fully serial dispatch->block reference loop.
        Steady state: each iteration pays one dispatch + one (deferred)
        block, so n iterations still contain n hard fetches."""
        sf = step_fn or step
        loop = OverlappedLoop(depth)
        times, last = [], None
        for i in range(n + depth):
            t0 = time.perf_counter()
            loss = sf()
            out = loop.push(lambda l=loss: fetch(l))
            dt = time.perf_counter() - t0
            if i >= depth:     # prefill iterations ran no block: drop
                times.append(dt)
            if out is not None:
                last = out
        out = loop.drain()
        return times, (out if out is not None else last)

    # --- phase 1: per-step D2H-blocked latency, overlapped by default
    # (the pipelined train loop IS the product path now); depth=0 below
    # re-measures the old fully serial loop for the before/after delta
    overlap_depth = max(0, int(os.environ.get("BENCH_OVERLAP_DEPTH", "2")))
    step_times, lval = blocked_phase(overlap_depth, iters)
    med, spread, spread_maxmin = _spread_stats(step_times)
    blocked_ips = batch_size / med
    serial_times, _ = blocked_phase(0, iters)
    med_serial = statistics.median(serial_times)
    serial_ips = batch_size / med_serial

    # monitor overhead A/B on the same blocked protocol: the acceptance
    # bar is <1% on the step-time median with the hooks live
    overhead_pct = None
    if health_on:
        _health.disable()
        off_times, _ = blocked_phase(overlap_depth, iters)
        med_off = statistics.median(off_times)
        _health.enable()
        _health.monitor.drop_window()  # don't attribute the off-span
        if med_off > 0:
            overhead_pct = (med / med_off - 1.0) * 100.0

    # time-series sampler overhead A/B, same protocol and same <1% bar:
    # `med` above was measured with the sampler thread live (telemetry
    # enable starts it), this span re-measures with it stopped
    sampler_overhead_pct = None
    from mxnet_tpu import telemetry as _telemetry
    if health_on and _telemetry.timeseries.running():
        _telemetry.timeseries.stop()
        ts_off_times, _ = blocked_phase(overlap_depth, iters)
        _telemetry.timeseries.start()
        _health.monitor.drop_window()
        med_ts_off = statistics.median(ts_off_times)
        if med_ts_off > 0:
            sampler_overhead_pct = (med / med_ts_off - 1.0) * 100.0

    # memwatch A/B, same protocol and the same <1% noise bar: `med` was
    # measured with the ledger hooks + census thread live
    memwatch_overhead_pct = None
    if memwatch_on:
        _memwatch.disable()
        mw_off_times, _ = blocked_phase(overlap_depth, iters)
        _memwatch.enable()
        # the off-window's donated steps produced state buffers the
        # ledger never saw — one tagged step re-adopts them before the
        # steady-state census, or they read as a 100 MB "leak"
        fetch(step())
        if health_on:
            _health.monitor.drop_window()
        med_mw_off = statistics.median(mw_off_times)
        if med_mw_off > 0:
            memwatch_overhead_pct = (med / med_mw_off - 1.0) * 100.0

    # fleet-collector scrape overhead A/B, same protocol and the same
    # <1% noise bar: `med` above ran unscraped; this span re-measures
    # while a live collector scrapes this process's /allz every 0.5s —
    # 10x the production cadence — so the delta bounds the serve+scrape
    # cost from the training loop's point of view
    fleet_overhead_pct = None
    if health_on and os.environ.get("BENCH_FLEET", "1") != "0":
        import tempfile
        from mxnet_tpu.telemetry import fleet as _fleet
        with tempfile.TemporaryDirectory() as fleet_dir:
            _fleet.register_endpoint(_telemetry.start_http_server(0),
                                     fleet_dir=fleet_dir)
            _fleet.start_collector(fleet_dir=fleet_dir, interval=0.5)
            fl_times, _ = blocked_phase(overlap_depth, iters)
            _fleet.reset()
        _health.monitor.drop_window()
        med_fl = statistics.median(fl_times)
        if med > 0:
            fleet_overhead_pct = (med_fl / med - 1.0) * 100.0

    # checkpoint overhead A/B, same blocked protocol, <3% bar (ISSUE 13).
    # One TrainCheckpointer save cycle = host snapshot of every parameter
    # + off-thread async orbax write; its marginal cost (including the
    # write's CPU contention tail) is measured as the wall-time delta of
    # PAIRED off/on step blocks — sequential whole-window A/B is blind
    # here: machine drift on a shared-CPU box exceeds the ~1% effect
    # (the monitor A/B above wobbles ±10% on this protocol), while
    # pairing + a median over pairs cancels drift.  The per-save cost is
    # then amortized at the production-shaped cadence BENCH_CKPT_EVERY.
    checkpoint_overhead_pct = None
    ckpt_every = 0
    if os.environ.get("BENCH_CKPT", "1") != "0":
        import shutil
        import tempfile
        from mxnet_tpu.checkpoint import TrainCheckpointer
        ckpt_every = max(1, int(os.environ.get("BENCH_CKPT_EVERY", "20")))
        ck_pairs = max(2, int(os.environ.get("BENCH_CKPT_PAIRS", "3")))
        ck_blk = max(4, int(os.environ.get("BENCH_CKPT_BLOCK", "6")))
        ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
        ckpt = TrainCheckpointer(ckpt_dir, every_n_steps=ckpt_every, keep=1)
        params = net.collect_params()
        ck_iter = [0]
        ck_saves = [0]

        def ckpt_step():
            loss = step()
            ck_iter[0] += 1
            # fire exactly one save per ON block, on the first TIMED
            # iteration (past the overlap prefill) so the snapshot, the
            # submit and the write's contention tail all land in steps
            # the block actually times
            if ck_iter[0] == overlap_depth + 1 and not ckpt.busy():
                # snapshot AFTER step returns, BEFORE the next step's
                # donation — asnumpy forces the D2H while buffers are live
                tree = {k: v.data().asnumpy() for k, v in params.items()}
                ck_saves[0] += 1
                ckpt.maybe_save(ck_saves[0], tree)
            return loss

        try:
            deltas, off_means = [], []
            for _ in range(ck_pairs):
                off_t, _ = blocked_phase(overlap_depth, ck_blk)
                ck_iter[0] = 0
                on_t, _ = blocked_phase(overlap_depth, ck_blk,
                                        step_fn=ckpt_step)
                ckpt.wait()           # commit outside the timed region
                deltas.append(sum(on_t) - sum(off_t))
                off_means.append(sum(off_t) / len(off_t))
            ckpt.close()
            if health_on:
                _health.monitor.drop_window()
            save_cost = statistics.median(deltas)
            step_off = statistics.median(off_means)
            if step_off > 0 and ck_saves[0] == ck_pairs:
                checkpoint_overhead_pct = \
                    100.0 * save_cost / (ckpt_every * step_off)
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)

    # --- phase 2+3: windowed steady-state + linear-scaling validation
    w1, lval = window(iters)
    w2, lval = window(2 * iters)
    scaling = w2 / w1 if w1 > 0 else 0.0
    # honest async pipelines take ~2x for 2x steps; broken blocking
    # returns immediately for both (ratio ~1)
    scaling_ok = 1.55 <= scaling <= 2.6
    window_ips = batch_size * 3 * iters / (w1 + w2)

    if not np.isfinite(lval):
        print(json.dumps({"metric": "resnet50_train_img_per_sec",
                          "value": 0.0, "unit": "img/s/chip", **stamp,
                          "vs_baseline": 0.0, "error": "non-finite loss"}))
        return 1

    img_per_sec = window_ips if scaling_ok else blocked_ips
    flops_per_img = TRAIN_GFLOPS_PER_IMG * 1e9
    achieved_tflops = _health.achieved_tflops(img_per_sec, flops_per_img)
    mfu = _health.mfu_fraction(img_per_sec, flops_per_img, peak_tflops)
    if _health.mfu_impossible(mfu, stamp["platform"]):
        print(json.dumps({"metric": "resnet50_train_img_per_sec",
                          "value": round(img_per_sec, 2), **stamp,
                          "unit": "img/s/chip", "vs_baseline": 0.0,
                          "error": "impossible: %.0f%% MFU > chip peak"
                                   % (100 * mfu)}))
        return 1

    baseline = 363.69  # V100 batch-128 training img/s, docs/faq/perf.md
    result = {
        "metric": "resnet50_train_img_per_sec",
        "value": round(img_per_sec, 2),
        "unit": "img/s/chip",
        **stamp,
        "smoke": smoke,
        "vs_baseline": round(img_per_sec / baseline, 4),
        "step_ms_median_blocked": round(med * 1e3, 2),
        "step_spread_pct": round(100 * spread, 1),
        "step_spread_maxmin_pct": round(100 * spread_maxmin, 1),
        "blocked_img_per_sec": round(blocked_ips, 2),
        "overlap_depth": overlap_depth,
        "serial_img_per_sec": round(serial_ips, 2),
        "step_ms_median_serial": round(med_serial * 1e3, 2),
        "windowed_img_per_sec": round(window_ips, 2),
        "window_scaling_ratio": round(scaling, 3),
        "window_suspect": not scaling_ok,
        "dtype": dtype,
        "batch": batch_size,
        "achieved_tflops": round(achieved_tflops, 2),
        "mfu_pct": round(100 * mfu, 2),
        # both currencies published so neither can be misquoted: tmacs
        # counts each multiply-accumulate once, tflops counts 2 ops/MAC
        # (the chip-peak convention the MFU divides by)
        "achieved_tmacs": round(img_per_sec * TRAIN_GMACS_PER_IMG / 1e3, 2),
        "flop_convention": "2 flops per MAC; train = 3x fwd (4.1 GMAC/img)",
        # donation-safe async checkpointing (ISSUE 13): amortized per-step
        # cost with a live TrainCheckpointer at the stated cadence
        "checkpoint_overhead_pct": (round(checkpoint_overhead_pct, 2)
                                    if checkpoint_overhead_pct is not None
                                    else None),
        "checkpoint_every_n_steps": ckpt_every or None,
        "step_first_seconds": round(first_step_wall, 3),
        # trace + XLA-compile (or cache-restore) cost of the first step:
        # its wall time minus one steady-state serial step
        "step_first_compile_seconds": round(
            max(0.0, first_step_wall - med_serial), 3),
    }

    # persistent program-cache evidence (zero-cold-start deploys): tier
    # counts show whether this run compiled fresh or restored from disk
    from mxnet_tpu import program_cache as _program_cache
    if _program_cache.enabled():
        result["program_cache"] = _program_cache.stats()

    # live monitor evidence: XLA-counted program costs and the runtime
    # MFU/verdict gauges, as exported on /metrics during this very run
    if health_on:
        snap = _health.monitor.snapshot()
        progs = _health.programs()
        result["health"] = {
            "step_mfu_pct": (round(snap["mfu_pct"], 3)
                             if snap["mfu_pct"] is not None else None),
            "verdict": snap["cause"],
            "step_seconds_ewma": (round(snap["ewma_seconds"], 6)
                                  if snap["ewma_seconds"] is not None
                                  else None),
            "monitor_overhead_pct": (round(overhead_pct, 2)
                                     if overhead_pct is not None else None),
            "sampler_overhead_pct": (round(sampler_overhead_pct, 2)
                                     if sampler_overhead_pct is not None
                                     else None),
            "fleet_scrape_overhead_pct": (round(fleet_overhead_pct, 2)
                                          if fleet_overhead_pct is not None
                                          else None),
            "program_flops": {n: p.flops for n, p in sorted(progs.items())},
            "program_hbm_bytes": {
                n: {"args": p.arg_bytes, "output": p.out_bytes,
                    "temp": p.temp_bytes}
                for n, p in sorted(progs.items())},
            "donation_leaks": sorted(n for n, p in progs.items()
                                     if p.donation_leak),
        }

    # device-memory evidence (ISSUE 16): per-device peak bytes from the
    # allocator (census high-water on CPU), the steady-state owner
    # ledger and the measured census A/B overhead
    if memwatch_on:
        mw_snap = _memwatch.census()
        devices = mw_snap["devices"]
        result["memory"] = {
            "peak_bytes_in_use": max(
                (st["peak_bytes_in_use"] for st in devices.values()),
                default=0),
            "per_device": devices,
            "owner_bytes": {o: rec["bytes"]
                            for o, rec in mw_snap["owners"].items()},
            "coverage_pct": round(mw_snap["coverage_pct"], 2),
            "leak_suspects": len(mw_snap["suspects"]),
            "memwatch_overhead_pct": (
                round(memwatch_overhead_pct, 2)
                if memwatch_overhead_pct is not None else None),
        }

    # per-layer attribution (satellite, round 10): which scopes own the
    # MFU gap — top-10 flops/bytes shares per analyzed program, next to
    # the health aggregates above
    if "--atlas" in sys.argv or os.environ.get("BENCH_ATLAS", "0") != "0":
        from mxnet_tpu import atlas as _atlas
        result["atlas"] = _atlas.snapshot(top_k=10)

    # per-phase breakdown (satellite, round 7): where does a step's time
    # go; io pipeline block (satellite, round 11): prefetch-wait
    # quantiles + producer-side device-put time under a synthetic
    # fast-step load — tracks host-boundness round over round
    if os.environ.get("BENCH_PHASES", "0" if smoke else "1") != "0":
        result["phase_breakdown"] = _phase_breakdown(
            mx, gluon, net, batch_size, image_size, ctx)
        result["phase_breakdown"]["io"] = _io_breakdown(mx, ctx)

    # BASELINE metric #2: LSTM LM tokens/sec (nested so the driver still
    # sees ONE JSON line whose primary metric is the ResNet number); a
    # failure of the nested metric fails the run
    rc = 0
    if os.environ.get("BENCH_LSTM", "0" if smoke else "1") != "0":
        result["lstm"], rc = bench_lstm_lm(ctx, dtype, peak_tflops, smoke)

    # durable record + regression gate: append this round to the run
    # ledger and compare it against the committed bench_history baseline.
    # The verdict is embedded (and the table printed to stderr) but never
    # gates the bench — gating exits belong to tools/sentinel.py runs.
    if os.environ.get("BENCH_SENTINEL", "0" if smoke else "1") != "0":
        repo = os.path.dirname(os.path.abspath(__file__))
        from mxnet_tpu import runlog as _runlog
        if not _runlog.enabled():
            _runlog.enable(os.path.join(repo, "bench_history",
                                        "ledger.jsonl"))
        _runlog.note_topology()
        _runlog.event("bench_result", metric=result["metric"],
                      value=result["value"], result=result)
        result["sentinel"] = _sentinel_verdict(result, "bench.py")

    print(json.dumps(result))
    return rc


if __name__ == "__main__":
    if "--multichip" in sys.argv:
        sys.exit(bench_multichip())
    if "--bf16" in sys.argv:
        sys.exit(bench_bf16())
    if "--transformer" in sys.argv:
        sys.exit(bench_transformer())
    sys.exit(main())
