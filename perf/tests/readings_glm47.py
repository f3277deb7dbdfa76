#!/usr/bin/env python3
"""The readings ``glm47flash_train_4k``'s limits are set from, beyond what
``readings.py`` takes for every cell: the six faults of the configuration's
mechanisms planted in the reference put in the program's place
(``no_shared``, ``rope_all``, ``no_kv_norm``, ``scale_one``, ``no_mtp``,
``top3``: ``perf/refs/glm_4_7_flash.py``), beside the fp8 control,
``half_batch`` and, with ``--witness``, a bfloat16 witness.

    python3 perf/tests/readings_glm47.py --seeds 1,2,3 \
        [--control-seeds 1,2] [--only top3,rope_all] [--witness] [--rehearse]

For every seed the program's first three steps against the reference; for
every control seed the control and the faults (``--only``: those named).
One JSON line a seed, a summary line last.  **Every reading is a process of
its own** (this one starts them one after another and never touches jax, so
the chip is free for each): ``perf/refs/train.py`` holds 14.1 GB of this
configuration's 706 M parameters in float32, and a second reading in the
process that made the first found 32 MB free (my chip runs, PR 33).  A child
leaves its numbers in ``--dir`` (the sound reference's, which the variants
are compared with, among them).  Run by hand on the chip; fails without a
TPU unless ``--rehearse``.
"""
import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "glm47flash_train_4k"


def child(args):
    """One reading: ``program`` (the program's three steps and the sound
    reference's) or one variant of the reference against the sound one."""
    from perf import harness
    if args.rehearse:
        harness.pin_cpu(1)
    cell = harness.load_cell(ROOT, CELL, rehearse=args.rehearse)
    devices = harness.devices_or_none(1, "cpu" if args.rehearse else "tpu")
    if devices is None:
        return 3
    from perf.loops import module_fit
    from perf.refs import common, train
    cfg, wl, ref = cell.config, cell.workload, cell.builder.ref
    sound = os.path.join(args.dir, "sound_%d.json" % args.seed)
    if args.child == "program":
        module_fit.prepare(cfg)
        fit = module_fit.Fit(cell, devices, args.seed)
        got = fit.prove()
        fit.free()
        del fit
        want = train.run(ref, cfg, wl, args.seed)
        with open(sound, "w") as f:
            json.dump(want, f)
        out = {"numbers": train.compare(got, want), "loss": got["loss"],
               "ref_loss": want["loss"]}
    else:
        with open(sound) as f:
            want = json.load(f)
        kw = {"control_fp8": dict(precision=common.CONTROL),
              "witness_bfloat16": dict(precision="bfloat16"),
              "half_batch": dict(fault="half_batch")}.get(args.child, {})
        faulted = cfg if kw else {**cfg, "fault": args.child}
        out = {"numbers": train.compare(
            train.run(ref, faulted, wl, args.seed, **kw), want)}
    with open(os.path.join(args.dir, "%s_%d.json"
                           % (args.child, args.seed)), "w") as f:
        json.dump(out, f)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--only", default="")
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--dir", default=os.path.join(ROOT, "chiprun_out",
                                                   "readings_glm47"))
    ap.add_argument("--child", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    os.makedirs(args.dir, exist_ok=True)
    if args.child:
        return child(args)
    from perf.refs import glm_4_7_flash as ref      # imports, starts no backend
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    only = [k for k in args.only.split(",") if k]
    kinds = ["control_fp8"] + ["witness_bfloat16"] * args.witness \
        + ["half_batch"] + list(ref.FAULTS)
    summary = {}

    def read(kind, seed):
        cmd = [sys.executable, os.path.abspath(__file__), "--child", kind,
               "--seed", str(seed), "--dir", args.dir] \
            + ["--rehearse"] * args.rehearse
        done = subprocess.run(cmd, cwd=ROOT)
        if done.returncode:
            return {"failed": done.returncode}
        with open(os.path.join(args.dir, "%s_%d.json" % (kind, seed))) as f:
            out = json.load(f)
        for k, (v, _) in out["numbers"].items():
            summary.setdefault(kind, {}).setdefault(k, []).append(v)
        return out

    for seed in seeds:
        got = read("program", seed)
        row = {"seed": seed, "program": got.get("numbers", got),
               "loss": got.get("loss"), "ref_loss": got.get("ref_loss")}
        if seed in control_seeds and "numbers" in got:
            for kind in kinds:
                if not only or kind in only:
                    row[kind] = read(kind, seed).get("numbers")
        print(json.dumps(row), flush=True)
    out = {"workload": CELL, "seeds": seeds}
    for kind, nums in summary.items():
        out[kind] = {k: {"min": min(v), "max": max(v), "n": len(v)}
                     for k, v in nums.items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
