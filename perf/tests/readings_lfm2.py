#!/usr/bin/env python3
"""The readings ``lfm2moe_train_2k``'s limits are set from, beyond what
``readings.py`` takes for every cell: the two faults of the expert layer
planted in the reference put in the program's place (``drop_expert``: one
held expert's output left out; ``top3``: one expert fewer a token), and the
routing flips — of the ``tokens x k`` selections of every expert layer at
step 1, how many differ between a bfloat16 and a float32 pass.

    python3 perf/tests/readings_lfm2.py --seeds 1,2,3 [--control-seeds 1,2] \
        [--rehearse]

For every seed the program's first three steps against the reference; for
every control seed the fp8 control, a bfloat16 witness, ``half_batch``, the
two faults and the flips.  One JSON line a seed, a summary line last.  Run
by hand on the chip; fails without a TPU unless ``--rehearse``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "lfm2moe_train_2k"


def routing_flips(ref, cfg, wl, seed):
    """{layer: selections that differ} between the reference computed in
    bfloat16 and in float32 on the first batch, and the selections a layer
    makes."""
    import jax
    import jax.numpy as jnp
    params = {k: v.astype(jnp.float32)
              for k, v in ref.init_params(cfg, seed).items()}
    ids = ref.make_batches(cfg, wl, seed)[0][0]

    @jax.jit
    def selections(params, ids):
        with jax.default_matmul_precision("highest"):
            out = {}
            for precision in ("float32", "bfloat16"):
                seen = ref.hidden_states(cfg, precision, params, ids)
                for l in range(len(cfg["layer_types"])):
                    if l < cfg["num_dense_layers"]:
                        continue
                    p = {k[len("l%d_" % l):]: v for k, v in params.items()
                         if k.startswith("l%d_" % l)}
                    _, h = ref._mixed(cfg, precision, l, seen[l], p)
                    sel, _ = ref.route(cfg, h, p["moe_router_weight"],
                                       p["moe_expert_bias"])
                    out[(l, precision)] = jnp.sort(sel, axis=-1)
            return {l: jnp.sum(out[(l, "float32")] != out[(l, "bfloat16")])
                    for l, prec in out if prec == "float32"}

    flips = {"l%d" % l: int(v) for l, v in selections(params, ids).items()}
    return flips, int(ids.size * cfg["num_experts_per_tok"])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
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
            runs = {
                "control_fp8": dict(precision=common.CONTROL),
                "witness_bfloat16": dict(precision="bfloat16"),
                "half_batch": dict(fault="half_batch")}
            for kind, kw in runs.items():
                row[kind] = note(kind, train.compare(
                    train.run(ref, cfg, wl, seed, **kw), want))
            for fault in ref.FAULTS:
                row[fault] = note(fault, train.compare(
                    train.run(ref, {**cfg, "fault": fault}, wl, seed), want))
            row["routing_flips"], row["selections_a_layer"] = \
                routing_flips(ref, cfg, wl, seed)
        print(json.dumps(row), flush=True)
    out = {"workload": CELL, "seeds": seeds}
    for kind, nums in summary.items():
        out[kind] = {k: {"min": min(v), "max": max(v), "n": len(v)}
                     for k, v in nums.items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
