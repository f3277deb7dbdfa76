#!/usr/bin/env python3
"""Attention arms side by side on the chip, device time from a trace:

    chiprun -- python3 tools/bench_attention_arms.py \
        --shapes 1x16x1024x64 --arms reference,flash,jax [--blocks 256x256]

Forward + gradient (q, k, v) of causal attention at each ``BxHxTxd``, bf16
(``--window W``: sliding-window attention, position t sees the W positions up
to and with t; the ``jax`` arms have no window and are left out):

  reference  ``ops.nn._mha_reference`` (the XLA arm of ``MultiHeadAttention``)
  flash      ``ops.pallas_attention.flash_attention`` (the repo's kernels;
             ``--blocks QxK`` runs it once per block choice, default: the
             kernel's own choice; ``--heads`` and ``--band`` sweep the heads
             a program and the diagonal's band size the same way)
  jax        ``jax.experimental.pallas.ops.tpu.flash_attention`` of the
             installed jax at its default blocks: the yardstick
  jax256     the same at 256-blocks

Every arm runs under ``jax.named_scope("arm.<name>.<shape>")``; one profiler
trace holds ``--iters`` calls of each, and the arm's time is the union of the
device operations that carry its scope (``perf/trace.py``'s reader), divided
by the iterations.  One JSON line per (shape, arm): ``device_ms``, the
achieved TFLOP/s on the operations a causal forward+backward REQUIRES (6
half-square products, recomputation not counted), the worst gradient gap to
the reference arm, the arm's four longest operations and, in ``kernels_ms``,
the time of each of the repo's kernels by name (``flash_fwd``,
``flash_dqkv``; ``flash_dq`` and ``flash_dkv`` on a tree from before PR 34).
The table in ``ops/nn.py`` ``mha_uses_kernel`` and ``PERF.md``'s
readings come from this script.  Hand-run, not tier-1; without a TPU it
refuses unless ``--rehearse`` (CPU, kernels interpreted, no ``jax`` arm,
times meaningless: a control-flow check only).
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


def required_flops(B, H, T, d, window=None):
    """Causal forward + backward: QK^T, PV forward; dV, dP, dQ, dK backward:
    six products over the visible half of the square, under a ``window`` the
    part of it within ``window`` positions of the diagonal."""
    w = min(window or T, T)
    return 6 * 2 * B * H * (w * (w + 1) // 2 + (T - w) * w) * d


_KERNEL = re.compile(r"%?(flash_[a-z]+)")


def build_arm(name, causal, scale, blocks, window=None):
    import jax
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops.nn import _mha_reference
    if name == "reference":
        return lambda q, k, v: _mha_reference(q, k, v, causal, scale, window)
    if name == "flash":
        return lambda q, k, v: pa.flash_attention(
            q, k, v, causal, scale, *(blocks or (None, None)), window)
    if name in ("jax", "jax256"):
        from jax.experimental.pallas.ops.tpu import flash_attention as jfa
        bs = None
        if name == "jax256":
            n = 256
            bs = jfa.BlockSizes(
                block_q=n, block_k_major=n, block_k=n, block_b=1,
                block_q_major_dkv=n, block_k_major_dkv=n, block_k_dkv=n,
                block_q_dkv=n, block_k_major_dq=n, block_k_dq=n,
                block_q_dq=n)
        return lambda q, k, v: jfa.flash_attention(
            q, k, v, causal=causal, sm_scale=scale, block_sizes=bs)
    raise SystemExit("unknown arm %r" % name)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="1x16x1024x64")
    ap.add_argument("--arms", default="reference,flash,jax")
    ap.add_argument("--blocks", default="",
                    help="comma list of QxK block choices for the flash arm")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--heads", default="",
                    help="comma list: the flash arm once per number of "
                         "heads a program (in place of the kernels' own "
                         "_heads_per_program)")
    ap.add_argument("--band", type=int, default=0,
                    help="one band size for all three kernels in this run")
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window attention: positions a query sees")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import pallas_attention as pa
    from perf import trace as ptrace

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.stderr.write("bench_attention_arms: needs a TPU, jax reports %s\n"
                         % dev.platform)
        return 3
    if args.rehearse:
        pa.INTERPRET = True
    if args.band:
        pa._BAND_FWD = pa._BAND_BWD = args.band
    shapes = [tuple(int(n) for n in s.split("x"))
              for s in args.shapes.split(",") if s]
    blocks = [tuple(int(n) for n in b.split("x"))
              for b in args.blocks.split(",") if b] or [None]
    heads = [int(h) for h in args.heads.split(",") if h] or [None]
    arms = []
    for a in args.arms.split(","):
        if a == "flash":
            arms += [("flash" + ("" if b is None else "%dx%d" % b)
                      + ("" if h is None else "g%d" % h), "flash", (b, h))
                     for b in blocks for h in heads]
        elif not ((args.rehearse or args.window) and a.startswith("jax")):
            arms.append((a, a, None))
    dtype = jnp.dtype(args.dtype)
    rs = np.random.RandomState(args.seed)

    jobs, skipped, gaps = [], [], {}
    for shape in shapes:
        B, H, T, d = shape
        scale = d ** -0.5
        q, k, v, co = (jax.device_put(jnp.asarray(
            rs.standard_normal(shape) * 0.5, dtype), dev) for _ in range(4))
        for label, arm, blk in arms:
            tag = "arm.%s.%s" % (label, "x".join(map(str, shape)))
            if arm == "flash":
                blk, h = blk
                if h is not None:       # read when the kernels are traced
                    pa._heads_per_program = lambda BH, T, itemsize, h=h: h
                    jax.clear_caches()
            fn = build_arm(arm, True, scale, blk, args.window or None)

            def step(q, k, v, co, fn=fn, tag=tag):
                # graftlint: disable=GL006 -- a bench's own tag, no model
                with jax.named_scope(tag):
                    return jax.grad(lambda *a: jnp.vdot(
                        fn(*a).astype(jnp.float32), co.astype(jnp.float32)),
                        (0, 1, 2))(q, k, v)
            try:    # compiled now: a later arm's knob cannot reach it
                jitted = jax.jit(step).lower(q, k, v, co).compile()
                jax.block_until_ready(jitted(q, k, v, co))
            except Exception as e:  # noqa: BLE001 -- an arm that cannot run
                skipped.append({"shape": list(shape), "arm": label,
                                "error": "%s: %s" % (type(e).__name__,
                                                     str(e)[:300])})
                continue
            jobs.append((shape, label, tag, jitted, (q, k, v, co)))
            if label == "reference":
                base = jitted(q, k, v, co)
            elif "reference" in args.arms.split(","):
                # worst gradient gap to the XLA arm, over its largest entry
                gaps[tag] = max(float(
                    jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32)))
                    / jnp.max(jnp.abs(b.astype(jnp.float32))))
                    for a, b in zip(jitted(q, k, v, co), base))

    trace_dir = tempfile.mkdtemp(prefix="attn_arms_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _, _, _, jitted, xs in jobs:
        for _ in range(args.iters):
            out = jitted(*xs)
        jax.block_until_ready(out)
    jax.profiler.stop_trace()

    lines = []
    if dev.platform == "tpu":
        red = ptrace.reduce(trace_dir, 1)
        # no device line: every arm was skipped
        rows = red.devices[min(red.devices)] if red.devices else []
    else:
        rows = []
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
        line = {"shape": list(shape), "arm": label, "dtype": args.dtype,
                "window": args.window or None,
                "device": dev.device_kind, "iters": args.iters,
                "device_ms": round(ms, 4) if rows else None,
                "required_tflops": (round(required_flops(
                    *shape, args.window or None) / ms * 1e-9, 2)
                    if ms else None),
                "grad_gap_to_reference": gaps.get(tag),
                "kernels_ms": kernels,
                "top_ops_ms": [[n[:60], round(t * 1e-9 / args.iters, 4)]
                               for n, t in top]}
        lines.append(line)
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
