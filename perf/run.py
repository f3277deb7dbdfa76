#!/usr/bin/env python3
"""perf/run.py: one run of one cell of ``BENCHMARK.json``.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process per run.  The cell's files are found by the names in
``BENCHMARK.json``: ``perf/workloads/<cell>.json`` (the traffic),
``perf/configs/<config>.json`` with its builder ``perf/models/<config>.py``
and its plain reference ``perf/refs/<config>.py``, the loop
``perf/loops/<loop>.py`` the workload file names, and for ``--trace 1`` each
per-layer metric's ``perf/metrics/<metric>.json`` with the reader
``perf/reducers/<reducer>.py`` it names.  Adding a cell, a configuration or a
metric adds files and manifest entries and edits none.

The last line of standard output is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last the numbers compared, each beside its limit); the same numbers are the
last lines of standard error.  Without a TPU holding the chips the cell asks
for, or outside a checkout of the program, it exits non-zero and prints no
result.  ``--rehearse`` drives the same control flow on the CPU at the tiny
size the workload file gives and prints no device metric.
"""
import time
T_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny size, control flow only: no device metric")
    args = ap.parse_args(argv)

    from perf import harness
    if not os.path.isdir(os.path.join(ROOT, "mxnet_tpu")):
        sys.stderr.write("perf/run.py: no program (mxnet_tpu/) in %s\n" % ROOT)
        return 2
    if args.rehearse:       # before anything imports jax
        harness.pin_cpu(harness.cell_entry(ROOT, args.workload)["chips"])
    cell = harness.load_cell(ROOT, args.workload, rehearse=args.rehearse)
    devices = harness.devices_or_none(cell.workload["chips"],
                                      "cpu" if args.rehearse else "tpu")
    if devices is None:
        return 3
    loop = harness.by_name("loops", cell.workload["loop"])
    return loop.run(harness.Run(cell=cell, args=args, devices=devices,
                                t_start=T_START, root=ROOT))


if __name__ == "__main__":
    sys.exit(main())
