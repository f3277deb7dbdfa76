#!/usr/bin/env python3
"""The readings `correct`'s limits are set from, many seeds in one process:

    python3 perf/tests/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--rehearse]

For every seed: the program's first three steps (the loop's own ``Fit``)
against the reference -> the lower readings.  For every control seed: the
control (the reference in fp8 in the program's place) and the planted faults
(the reference with half of the batch left out, or, on four chips, without
the exchange) against the reference -> the upper readings.  One JSON line per
seed and a summary line last.  Run by hand on the chip (``chiprun -- python3
perf/tests/readings.py ...``); it fails without a TPU unless ``--rehearse``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--witness", default="",
                    help="also run the reference in this precision "
                         "(bfloat16: what the configurations state)")
    ap.add_argument("--dump", default="",
                    help="directory for every leaf's norms, a file a seed")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
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
    module_fit.prepare(cfg)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    summary = {}

    def note(kind, numbers):
        for k, (v, where) in numbers.items():
            summary.setdefault(kind, {}).setdefault(k, []).append(v)

    for seed in seeds:
        fit = module_fit.Fit(cell, devices, seed)
        got = fit.prove()
        fit.free()
        del fit
        want = train.run(ref, cfg, wl, seed)
        numbers = train.compare(got, want)
        note("program", numbers)
        row = {"seed": seed, "program": numbers, "loss": got["loss"],
               "ref_loss": want["loss"]}
        leaves = {"program": got, "reference": want}
        if seed in control_seeds:
            leaves["control_fp8"] = train.run(ref, cfg, wl, seed,
                                              precision=common.CONTROL)
            numbers = train.compare(leaves["control_fp8"], want)
            note("control_fp8", numbers)
            row["control_fp8"] = numbers
            if args.witness:
                leaves["witness"] = train.run(ref, cfg, wl, seed,
                                              precision=args.witness)
                numbers = train.compare(leaves["witness"], want)
                note("witness_" + args.witness, numbers)
                row["witness_" + args.witness] = numbers
            for fault in train.FAULTS[:2 if wl["chips"] > 1 else 1]:
                leaves[fault] = train.run(ref, cfg, wl, seed, fault=fault)
                numbers = train.compare(leaves[fault], want)
                note(fault, numbers)
                row[fault] = numbers
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, "%s_%d.json"
                                   % (args.workload, seed)), "w") as f:
                json.dump(leaves, f)
        print(json.dumps(row), flush=True)
    out = {"workload": args.workload, "seeds": seeds}
    for kind, nums in summary.items():
        out[kind] = {k: {"min": min(v), "max": max(v), "n": len(v)}
                     for k, v in nums.items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
