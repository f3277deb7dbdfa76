"""What the host does in a step of a benchmark cell, read without a chip.

Hand-run, on the CPU::

    python tools/step_host_profile.py --workload gpt2m_train_dp4 \\
        [--steps 200] [--profile cumulative|tottime]

Drives the cell's own loop (``perf/loops/module_fit.py``) at its
rehearsal's widths but with the configuration's whole depth (where the
rehearsal cuts it by one of ``DEPTH_KEYS``; another cell keeps its
rehearsal's), so the step walks as many leaves as on the chip (293
parameters, 1,172 state slots on four devices for ``gpt2m_train_dp4``), on as
many virtual CPU devices as the cell has chips.  Prints the main thread's
CPU time a step and the medians of the program's own ``Step::*`` spans
(``tracing.flight``); with ``--profile`` a ``cProfile`` table of the
measured steps instead (it slows Python several times over: read shares
there, not times).

The host's phases are Python and the launch's argument handling, which do
not know the device: on this sandbox they read within a fifth of what the
chip's traced runs read (PERF.md section 6, PR 32).  The step's pace here is
the CPU "device"'s and says nothing.  The main thread is pinned to a core of
its own so that XLA's CPU threads do not share it.
"""
import argparse
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPANS = ["Step::update", "Step::validate", "Step::feed", "Step::slots",
         "Step::gather", "Step::program", "Step::launch", "Step::writeback",
         "Loop::wait"]
# keys of a rehearsal's tiny configuration that cut the depth
DEPTH_KEYS = ("n_layer", "num_hidden_layers", "num_layers")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--profile", choices=("cumulative", "tottime"))
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) > 1:          # every thread started from here on: not core 0
        os.sched_setaffinity(0, set(cores[1:]))
    from perf import harness
    from perf.loops import module_fit
    chips = harness.cell_entry(ROOT, args.workload)["chips"]
    harness.pin_cpu(chips)
    whole = harness.load_cell(ROOT, args.workload).config
    cell = harness.load_cell(ROOT, args.workload, rehearse=True)
    for key in DEPTH_KEYS:      # the whole depth: the chip's count of leaves
        if key in whole and cell.config.get(key) != whole[key]:
            cell.config[key] = whole[key]
    from mxnet_tpu.parallel import mesh as pmesh
    pmesh.STATE_SHARD_MIN_ELEMENTS = 1 << 10    # tiny leaves split as large
    devices = harness.devices_or_none(chips, "cpu")
    module_fit.prepare(cell.config)
    fit = module_fit.Fit(cell, devices, 12345)
    fit.prove()
    from mxnet_tpu import tracing
    if len(cores) > 1:
        os.sched_setaffinity(0, {cores[0]})     # this thread alone
    for i in range(20):
        fit.one_step(100 + i)
    fit.loop.drain()
    del fit.done[:]
    profile = None
    if args.profile:
        import cProfile
        profile = cProfile.Profile()
        profile.enable()
    cpu0, t0 = time.thread_time(), time.perf_counter()
    for i in range(args.steps):
        fit.one_step(200 + i)
    fit.loop.drain()
    cpu1, t1 = time.thread_time(), time.perf_counter()
    if profile is not None:
        import pstats
        profile.disable()
        pstats.Stats(profile).sort_stats(args.profile).print_stats(40)
        return 0
    print("%s: %d parameters, %d steps on %d CPU device(s)"
          % (args.workload, len(fit.mod._param_names), args.steps, chips))
    print("main thread: %.3f ms of CPU a step (wall %.1f: the CPU device's)"
          % ((cpu1 - cpu0) / args.steps * 1e3, (t1 - t0) / args.steps * 1e3))
    got, _ = tracing.flight.records(names=SPANS, since_s=None)
    by = {}
    for r in got:
        by.setdefault(r.name, []).append((r.end_s - r.begin_s) * 1e3)
    for name in SPANS:
        if name in by:
            print("%-16s median %7.3f ms  (the last %d)"
                  % (name, statistics.median(by[name]), len(by[name])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
