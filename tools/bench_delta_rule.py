#!/usr/bin/env python3
"""The gated delta rule's two forms side by side on a TPU, device time from
a trace:

    python3 tools/bench_delta_rule.py \
        --shapes 1x4096x32x128 --arms chunked,kernel [--heads 2,4]

Forward + gradient (q, k, v, g, beta) of ``ops.delta_rule`` at each
``BxTxHxd`` (keys and values ``d`` wide), q, k, v bfloat16, g and beta
float32, at jax's default precision:

  chunked  ``delta_rule._chunked_rule``, XLA's ``while`` over the chunks
  kernel   ``delta_rule._kernel_rule``, the Pallas kernels
           (``ops/pallas_delta_rule.py``); ``--heads`` runs it once per
           number of heads a grid step (in place of ``heads_per_block``)

Every arm runs under ``jax.named_scope("arm.<name>.<shape>")``; one profiler
trace holds ``--iters`` calls of each, and the arm's time is the union of the
device operations that carry its scope (``perf/trace.py``'s reader), divided
by the iterations.  One JSON line per (shape, arm): ``device_ms``, the worst
gradient gap to the chunked arm over its largest entry, the arm's four
longest operations and, in ``kernels_ms``, the time of each kernel by name
(``delta_rule_fwd``, ``delta_rule_bwd``).  Hand-run, not tier-1; without a
TPU it refuses unless ``--rehearse`` (CPU, kernels interpreted, times
meaningless: a control-flow check only).
"""
import argparse
import json
import os
import re
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_KERNEL = re.compile(r"%?(delta_rule_[a-z]+)")


def make_inputs(shape, seed, decay):
    """q, k (unit per head), v, g (log-decays: ``decay`` x softplus of a
    normal, half the channels held), beta, and the output's cotangent."""
    import jax
    import jax.numpy as jnp
    B, T, H, d = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    q, k = (jax.random.normal(keys[i], shape, jnp.float32) for i in (0, 1))
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = jax.random.normal(keys[2], shape, jnp.float32)
    g = -decay * jax.nn.softplus(2 * jax.random.normal(keys[3], shape))
    g = g * (jax.random.uniform(keys[4], shape) < 0.5)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (B, T, H)))
    co = jax.random.normal(keys[6], shape, jnp.float32)
    bf = jnp.bfloat16
    return q.astype(bf), k.astype(bf), v.astype(bf), g, beta, co.astype(bf)


def build_arm(name):
    from mxnet_tpu.ops import delta_rule as dr
    if name == "chunked":
        return lambda q, k, v, g, b: dr._chunked_rule(
            q, k, v, g, b, q.shape[-1] ** -0.5)
    if name == "kernel":
        return lambda q, k, v, g, b: dr._kernel_rule(
            q, k, v, g, b, q.shape[-1] ** -0.5, True)
    raise SystemExit("unknown arm %r" % name)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="1x4096x32x128")
    ap.add_argument("--arms", default="chunked,kernel")
    ap.add_argument("--heads", default="",
                    help="comma list: the kernel arm once per number of "
                         "heads a grid step")
    ap.add_argument("--decay", type=float, default=1.0)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_delta_rule as pdr
    from perf import trace as ptrace

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.stderr.write("bench_delta_rule: needs a TPU, jax reports %s\n"
                         % dev.platform)
        return 3
    if args.rehearse:
        pdr.INTERPRET = True
    shapes = [tuple(int(n) for n in s.split("x"))
              for s in args.shapes.split(",") if s]
    heads = [int(h) for h in args.heads.split(",") if h] or [None]
    arms = []
    for a in args.arms.split(","):
        if a == "kernel":
            arms += [("kernel" + ("" if h is None else "g%d" % h), a, h)
                     for h in heads]
        else:
            arms.append((a, a, None))

    jobs, skipped, gaps = [], [], {}
    for shape in shapes:
        q, k, v, g, beta, co = (jax.device_put(x, dev) for x in make_inputs(
            shape, args.seed, args.decay))
        base = None
        for label, arm, h in arms:
            tag = "arm.%s.%s" % (label, "x".join(map(str, shape)))
            if h is not None:       # read when the kernels are traced
                pdr.heads_per_block = lambda BH, h=h: h
                jax.clear_caches()
            fn = build_arm(arm)

            def step(q, k, v, g, beta, co, fn=fn, tag=tag):
                # graftlint: disable=GL006 -- a bench's own tag, no model
                with jax.named_scope(tag):
                    return jax.grad(lambda *a: jnp.vdot(
                        fn(*a).astype(jnp.float32), co.astype(jnp.float32)),
                        (0, 1, 2, 3, 4))(q, k, v, g, beta)
            xs = (q, k, v, g, beta, co)
            try:    # compiled now: a later arm's knob cannot reach it
                jitted = jax.jit(step).lower(*xs).compile()
                out = jax.block_until_ready(jitted(*xs))
            except Exception as e:  # noqa: BLE001 -- an arm that cannot run
                skipped.append({"shape": list(shape), "arm": label,
                                "error": "%s: %s" % (type(e).__name__,
                                                     str(e)[:300])})
                continue
            jobs.append((shape, label, tag, jitted, xs))
            if arm == "chunked":
                base = out
            elif base is not None:
                gaps[tag] = max(float(
                    jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32)))
                    / jnp.max(jnp.abs(b.astype(jnp.float32))))
                    for a, b in zip(out, base))

    trace_dir = tempfile.mkdtemp(prefix="delta_rule_arms_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _, _, _, jitted, xs in jobs:
        for _ in range(args.iters):
            out = jitted(*xs)
        jax.block_until_ready(out)
    jax.profiler.stop_trace()

    rows = []
    if dev.platform == "tpu":
        red = ptrace.reduce(trace_dir, 1)
        rows = red.devices[min(red.devices)] if red.devices else []
    lines = []
    for shape, label, tag, _, _ in jobs:
        mine = [r for r in rows if r[3] is not None
                and (tag + "/" in r[3] or tag + ")" in r[3])]
        ps = ptrace.covered((r[1], r[1] + r[2]) for r in mine)
        by_name = {}
        for r in mine:
            by_name[r[0]] = by_name.get(r[0], 0) + r[2]
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:4]
        kernels = {}
        for n, t in by_name.items():
            m = _KERNEL.match(n)
            if m:
                kernels[m.group(1)] = round(
                    kernels.get(m.group(1), 0) + t * 1e-9 / args.iters, 4)
        ms = ps * 1e-9 / args.iters
        lines.append({"shape": list(shape), "arm": label,
                      "device": dev.device_kind, "iters": args.iters,
                      "device_ms": round(ms, 4) if rows else None,
                      "grad_gap_to_chunked": gaps.get(tag),
                      "kernels_ms": kernels,
                      "top_ops_ms": [[n[:60], round(t * 1e-9 / args.iters, 4)]
                                     for n, t in top]})
    lines += skipped
    shutil.rmtree(trace_dir, ignore_errors=True)
    text = "\n".join(json.dumps(l) for l in lines)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
