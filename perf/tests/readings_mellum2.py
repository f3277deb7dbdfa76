#!/usr/bin/env python3
"""The readings ``mellum2moe_train_4k``'s limits are set from, beyond what
``readings.py`` takes for every cell: the four faults of the configuration's
mechanisms planted in the reference put in the program's place
(``no_window``, ``plain_rope``, ``top7``, ``drop_expert``:
``perf/refs/mellum2_12b_a2_5b.py``), beside the fp8 control, a bfloat16
witness and ``half_batch``.

    python3 perf/tests/readings_mellum2.py --seeds 1,2,3 \
        [--control-seeds 1,2] [--only top7,plain_rope] [--rehearse]

For every seed the program's first three steps against the reference; for
every control seed the control, the witness and the faults (``--only``:
those named).  One JSON line a seed, a summary line last.  Run by hand on the chip; fails without a TPU
unless ``--rehearse``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "mellum2moe_train_4k"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--only", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
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
    module_fit.prepare(cfg)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    only = [k for k in args.only.split(",") if k]
    summary = {}

    def note(kind, numbers):
        for k, (v, _) in numbers.items():
            summary.setdefault(kind, {}).setdefault(k, []).append(v)
        return numbers

    for seed in seeds:
        fit = module_fit.Fit(cell, devices, seed)
        got = fit.prove()
        fit.free()
        del fit
        want = train.run(ref, cfg, wl, seed)
        row = {"seed": seed, "program": note("program",
                                             train.compare(got, want)),
               "loss": got["loss"], "ref_loss": want["loss"]}
        if seed in control_seeds:
            runs = {"control_fp8": (cfg, dict(precision=common.CONTROL)),
                    "witness_bfloat16": (cfg, dict(precision="bfloat16")),
                    "half_batch": (cfg, dict(fault="half_batch"))}
            runs.update({f: ({**cfg, "fault": f}, {}) for f in ref.FAULTS})
            for kind, (c, kw) in runs.items():
                if only and kind not in only:
                    continue
                row[kind] = note(kind, train.compare(
                    train.run(ref, c, wl, seed, **kw), want))
        print(json.dumps(row), flush=True)
    out = {"workload": CELL, "seeds": seeds}
    for kind, nums in summary.items():
        out[kind] = {k: {"min": min(v), "max": max(v), "n": len(v)}
                     for k, v in nums.items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
