#!/usr/bin/env python3
"""The readings a cell's limits are set from, beyond what ``readings.py``
takes: the faults of the configuration's own mechanisms planted in the
reference put in the program's place (``FAULTS`` of the configuration's
``perf/refs/<config>.py``), beside the fp8 control, ``half_batch`` and, with
``--witness``, a bfloat16 witness.

    python3 perf/tests/readings_faults.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2] [--only top7,no_delta] [--witness] \
        [--timeout 600] [--rehearse]

For every seed the program's first three steps against the reference; for
every control seed the control and the faults (``--only``: those named).
One JSON line a seed, a summary line last.  **Every reading is a process of
its own** (this one starts them one after another and never touches jax, so
the chip is free for each): ``perf/refs/train.py`` holds the parameters in
float32 beside the reference's gradient, 12 GB for a model of 600 M, and a
second reading in one process of such a model finds the device's memory
gone.  A child leaves its numbers in ``--dir`` (the sound reference's,
which the variants are compared with, among them); ``--timeout`` ends a
child that takes longer, and its reading counts as failed.  Run by hand on
the chip; fails without a TPU unless ``--rehearse``.
"""
import argparse
import importlib
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def child(args):
    """One reading: ``program`` (the program's three steps and the sound
    reference's) or one variant of the reference against the sound one."""
    from perf import harness
    if args.rehearse:
        harness.pin_cpu(harness.cell_entry(ROOT, args.workload)["chips"])
    cell = harness.load_cell(ROOT, args.workload, rehearse=args.rehearse)
    devices = harness.devices_or_none(cell.workload["chips"],
                                      "cpu" if args.rehearse else "tpu")
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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--only", default="")
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--timeout", type=float, default=None,
                    help="seconds a reading may take")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--dir", default="")
    ap.add_argument("--child", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    args.dir = args.dir or os.path.join(ROOT, "chiprun_out",
                                        "readings_" + args.workload)
    os.makedirs(args.dir, exist_ok=True)
    if args.child:
        return child(args)
    from perf import harness
    config = harness.cell_entry(ROOT, args.workload)["config"]
    # imports, starts no backend
    faults = getattr(importlib.import_module("perf.refs." + config),
                     "FAULTS", ())
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    only = [k for k in args.only.split(",") if k]
    kinds = ["control_fp8"] + ["witness_bfloat16"] * args.witness \
        + ["half_batch"] + list(faults)
    summary = {}

    def read(kind, seed):
        cmd = [sys.executable, os.path.abspath(__file__), "--child", kind,
               "--workload", args.workload, "--seed", str(seed),
               "--dir", args.dir] + ["--rehearse"] * args.rehearse
        try:
            done = subprocess.run(cmd, cwd=ROOT, timeout=args.timeout)
        except subprocess.TimeoutExpired:
            return {"failed": "timeout"}
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
                    got = read(kind, seed)
                    row[kind] = got.get("numbers", got)
        print(json.dumps(row), flush=True)
    out = {"workload": args.workload, "seeds": seeds}
    for kind, nums in summary.items():
        out[kind] = {k: {"min": min(v), "max": max(v), "n": len(v)}
                     for k, v in nums.items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
