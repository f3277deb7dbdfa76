#!/usr/bin/env python
"""Deploy prefill for the persistent compiled-program cache.

Compiles a model's serving bucket ladder and (optionally) its fused
training step ONCE into the program-cache directory, so the cache
directory can ship with the model artifact and every replica restarts
warm: ready-to-serve / step-1 with **zero** XLA compiles, just disk
reads (see mxnet_tpu/program_cache.py and docs/serving.md "Deploy
prefill").  The directory is ``--cache-dir``, else wherever
``program_cache.resolve_dir()`` places it; a ``JAX_COMPILATION_CACHE_DIR``
in the environment wins over ``--cache-dir`` too, and the workers say so.
This process never touches jax: the cold and the warm worker run one
after the other and each has the device to itself.

Modes:

- default        — prefill: run the workload cold in a subprocess with
                   the cache enabled; artifacts land in ``--cache-dir``.
- ``--verify``   — after prefill, restart the same workload warm in a
                   fresh subprocess and assert zero fresh XLA compiles
                   (``program_cache`` puts == misses == 0); reports
                   cold/warm seconds and the speedup.
- ``--smoke``    — CI probe: tiny MLP, throwaway cache dir under /tmp
                   (a cold cache is what it tests, so jax's placement
                   variable is dropped from the workers' environment),
                   CPU pinned, prefill + verify + assertions; prints
                   ``{"probe": "cache_prefill", "ok": true, ...}``.
- ``--worker``   — internal: the subprocess entry that actually runs the
                   workload and prints one JSON result line.

The cold/warm boundary is a real process boundary (subprocess re-exec),
so the numbers are what a deploy sees, not an in-process approximation.

Run:  python tools/cache_prefill.py --cache-dir /models/m1/pcache --verify
"""
import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_symbol(args, mode):
    """(symbol, params, example_shapes, n_classes) for --model.

    ``mode="serve"`` heads with a plain softmax (no label input, what a
    Predictor binds); ``mode="train"`` heads with SoftmaxOutput so the
    Module path drives the fused whole-step program.  Both share the
    same backbone parameter names.
    """
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    S = mx.symbol
    if args.model == "resnet50":
        from mxnet_tpu.gluon.model_zoo import vision
        net = vision.resnet50_v1()
        body = net(S.var("data"))
        example = {"data": (3, args.image_size, args.image_size)}
        classes = 1000
    else:
        x = S.var("data")
        h = S.Activation(S.FullyConnected(x, num_hidden=args.hidden,
                                          name="fc1"), act_type="relu")
        h = S.Activation(S.FullyConnected(h, num_hidden=args.hidden,
                                          name="fc2"), act_type="relu")
        body = S.FullyConnected(h, num_hidden=args.classes, name="fc3")
        example = {"data": (args.in_dim,)}
        classes = args.classes
    if mode == "serve":
        sym = S.softmax(body, axis=1, name="prob")
    else:
        sym = S.SoftmaxOutput(body, S.var("softmax_label"),
                              name="softmax")
    rng = np.random.RandomState(0)
    feed = {"data": (1,) + example["data"]}
    if mode != "serve":
        feed["softmax_label"] = (1,)
    shapes, _, aux_shapes = sym.infer_shape(**feed)
    params = {n: nd.array(rng.uniform(-0.1, 0.1, s).astype(np.float32))
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    for n, s in zip(sym.list_auxiliary_states(), aux_shapes):
        # BN moving stats: identity-ish init keeps activations finite;
        # "aux:" prefix is the checkpoint convention Predictor parses
        fill = np.ones if n.endswith(("_var", "_running_var")) \
            else np.zeros
        params["aux:" + n] = nd.array(fill(s, np.float32))
    return sym, params, example, classes


def _serve_ladder(args):
    """Compile every declared bucket (ModelServer.warmup); returns the
    measured warmup seconds."""
    from mxnet_tpu.serving import ModelServer
    sym, params, example, _ = build_symbol(args, "serve")
    server = ModelServer(sym.tojson(), params, example_shapes=example,
                         batch_buckets=args.bucket_list,
                         max_batch_size=max(args.bucket_list))
    server.warmup()
    return server.warmup_seconds


def _train_step(args):
    """Fused whole-step program: first-step (compile/restore) seconds +
    op_jit miss delta across a REPEAT step (steady-state restore proof)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    sym, _, example, classes = build_symbol(args, "train")
    batch = args.batch
    data_shape = (batch,) + example["data"]
    mod = mx.mod.Module(sym, data_names=("data",),
                        label_names=("softmax_label",),
                        context=[mx.current_context()])
    mod.bind(data_shapes=[("data", data_shape)],
             label_shapes=[("softmax_label", (batch,))])
    mx.random.seed(7)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
    mod.init_optimizer(kvstore="local", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    rs = np.random.RandomState(3)
    x = mx.nd.array(rs.uniform(size=data_shape).astype(np.float32))
    y = mx.nd.array(rs.randint(0, classes, (batch,)).astype(np.float32))

    class _B:
        data = [x]
        label = [y]

    def step():
        mod.forward_backward(_B)
        mod.update()
        return float(mod.get_outputs()[0].asnumpy().ravel()[0])

    t0 = time.perf_counter()
    step()
    first = time.perf_counter() - t0

    def misses():
        fams = telemetry.registry().get("op_jit_cache_misses_total")
        if fams is None:
            return 0
        return sum(c.get() for c in fams._children.values())

    m0 = misses()
    t0 = time.perf_counter()
    step()
    repeat = time.perf_counter() - t0
    return first, max(0.0, first - repeat), misses() - m0


def run_worker(args):
    """Subprocess entry: run the workload with the cache (maybe) enabled
    and print one JSON line of measurements + cache stats."""
    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from mxnet_tpu import program_cache, telemetry
    telemetry.enable()
    out = {"cache_dir": program_cache.place(),
           "platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind}
    if args.serve:
        out["serving_warmup_seconds"] = round(_serve_ladder(args), 6)
    if args.train:
        first, compile_s, repeat_misses = _train_step(args)
        out["step_first_seconds"] = round(first, 6)
        # compile/restore component: first-step wall minus a repeat step
        out["step_first_compile_seconds"] = round(compile_s, 6)
        out["repeat_step_op_jit_misses"] = int(repeat_misses)
    s = program_cache.stats()
    out["program_cache"] = s
    # fresh XLA compiles while enabled == persistent-cache misses (every
    # call-path compile request flows through the installed cache)
    out["fresh_compiles"] = int(s.get("puts", 0))
    print(json.dumps(out))


def _spawn(args, extra_env, tag):
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--model", args.model, "--buckets", args.buckets,
           "--batch", str(args.batch), "--in-dim", str(args.in_dim),
           "--hidden", str(args.hidden), "--classes", str(args.classes),
           "--image-size", str(args.image_size)]
    if args.platform:
        cmd += ["--platform", args.platform]
    if not args.serve:
        cmd += ["--no-serve"]
    if not args.train:
        cmd += ["--no-train"]
    env = dict(os.environ)
    env.update(extra_env)
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=args.timeout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise SystemExit("cache_prefill: %s worker failed (rc=%d)"
                         % (tag, proc.returncode))
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cache-dir", default=None,
                    help="program-cache directory to prefill (default: "
                         "where program_cache.resolve_dir() places it)")
    ap.add_argument("--model", choices=("mlp", "resnet50"), default="mlp")
    ap.add_argument("--buckets", default="1,2,4,8",
                    help="serving bucket ladder (comma-separated)")
    ap.add_argument("--batch", type=int, default=8,
                    help="training-step batch size")
    ap.add_argument("--in-dim", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--platform", default=None,
                    help="jax platform override (smoke pins cpu)")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="per-subprocess timeout (s)")
    ap.add_argument("--no-serve", dest="serve", action="store_false",
                    help="skip the serving bucket ladder")
    ap.add_argument("--no-train", dest="train", action="store_false",
                    help="skip the fused training step")
    ap.add_argument("--verify", action="store_true",
                    help="after prefill, restart warm and assert zero "
                         "fresh compiles")
    ap.add_argument("--smoke", action="store_true",
                    help="CI probe: tiny model, /tmp cache, cpu, "
                         "prefill+verify+assert")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--out", help="write the JSON document here too")
    args = ap.parse_args(argv)
    args.bucket_list = tuple(sorted({int(b) for b in
                                     args.buckets.split(",") if b.strip()}))

    if args.worker:
        run_worker(args)
        return 0

    tmp = None
    if args.smoke:
        args.verify = True
        args.platform = args.platform or "cpu"
        args.model, args.batch = "mlp", 4
        args.in_dim, args.hidden, args.classes = 16, 32, 8
        args.buckets, args.bucket_list = "1,2", (1, 2)
        tmp = tempfile.mkdtemp(prefix="mxpc_smoke_")
        args.cache_dir = tmp
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    wenv = {}
    if args.cache_dir:
        os.makedirs(args.cache_dir, exist_ok=True)
        wenv["MXNET_PROGRAM_CACHE_DIR"] = args.cache_dir
    try:
        cold = _spawn(args, wenv, "prefill")
        doc = {"tool": "cache_prefill", "model": args.model,
               "buckets": list(args.bucket_list),
               "cache_dir": cold["cache_dir"], "cold": cold}
        if args.verify:
            warm = _spawn(args, wenv, "verify")
            doc["warm"] = warm
            doc["fresh_compiles_warm"] = warm["fresh_compiles"]
            doc["zero_compile_restart"] = (
                warm["fresh_compiles"] == 0
                and warm["program_cache"].get("misses", 1) == 0)
            for k in ("serving_warmup_seconds", "step_first_seconds",
                      "step_first_compile_seconds"):
                if k in cold and k in warm and warm[k] > 0:
                    doc.setdefault("speedup", {})[k] = round(
                        cold[k] / warm[k], 2)
        if args.smoke:
            ok = (cold["fresh_compiles"] > 0
                  and doc.get("zero_compile_restart") is True
                  and doc["warm"].get("repeat_step_op_jit_misses", 1) == 0)
            doc = {"probe": "cache_prefill", "ok": bool(ok),
                   "cold_compiles": cold["fresh_compiles"],
                   "warm_compiles": doc["warm"]["fresh_compiles"],
                   "speedup": doc.get("speedup", {})}
            print(json.dumps(doc))
            return 0 if ok else 1
        text = json.dumps(doc, indent=2)
        print(text)
        if args.out:
            with open(args.out, "w") as f:
                f.write(text + "\n")
        return 0 if doc.get("zero_compile_restart", True) else 1
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
