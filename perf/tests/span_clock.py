#!/usr/bin/env python3
"""The program's step spans against the harness's, in one trace, by hand:

    python3 perf/tests/span_clock.py --workload <cell> --seed <n> [--rehearse]

Builds the cell's ``Fit``, proves it (every shape warm), traces
``traced_steps`` steps with the harness's ``perf:`` annotations on, and reads
the ``.xplane.pb`` with ``jax.profiler.ProfileData`` (names, starts and
durations are all this needs).  One JSON line:

- ``mx_events``: how many ``mx:<span>`` events each name left on the host
  plane, and ``planes``: where;
- ``outside_dispatch``: the ``mx:Step::*`` events that do NOT lie inside a
  ``perf:dispatch`` event of their thread (should be none);
- ``ring_gap_us``: per span name, the largest distance between an event's
  duration in the trace and the same span's duration in the program's ring
  (``tracing.flight.records``), matched in order;
- ``phase_ms``: the ring's milliseconds per traced step by span name, and
  ``dispatch_ms``: the harness's own span over the same steps;
- ``idle_cost_us``: what one ``profiler.span`` of the category ``step`` and
  one bare ``TraceAnnotation`` cost on this host with no trace on.

Run on the chip with ``chiprun``; it fails without a TPU unless
``--rehearse``.
"""
import argparse
import glob
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def idle_cost_us(n=200000):
    import jax
    from mxnet_tpu import profiler
    t0 = time.perf_counter()
    for _ in range(n):
        with profiler.span("Step::idle", "step"):
            pass
    t1 = time.perf_counter()
    for _ in range(n):
        with jax.profiler.TraceAnnotation("mx:Step::idle"):
            pass
    t2 = time.perf_counter()
    return {"profiler_span": (t1 - t0) / n * 1e6,
            "trace_annotation": (t2 - t1) / n * 1e6}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
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
    import jax
    from perf.loops import module_fit
    from mxnet_tpu import tracing
    module_fit.prepare(cell.config)
    fit = module_fit.Fit(cell, devices, args.seed)
    fit.prove()
    n = cell.workload["traced_steps"]
    step = module_fit.PROOF_STEPS + 1
    for _ in range(n):                      # a steady pipeline first
        fit.one_step(step)
        step += 1
    fit.loop.drain()
    trace_dir = os.path.join(ROOT, "perf", ".trace_span_clock")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    fit.spans.rows.clear()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    fit.spans.annotate = True
    since = time.perf_counter()
    for _ in range(n):
        fit.one_step(step)
        step += 1
    fit.loop.drain()
    fit.spans.annotate = False
    jax.profiler.stop_trace()
    ring, wrapped = tracing.flight.records(since_s=since)
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    space = jax.profiler.ProfileData.from_file(files[-1])
    mx, dispatch, planes = {}, {}, set()
    for plane in space.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mx:"):
                    planes.add(plane.name)
                    mx.setdefault(ev.name[3:], []).append(
                        (ev.start_ns, ev.duration_ns, line.name))
                elif ev.name == "perf:dispatch":
                    dispatch.setdefault(line.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    shutil.rmtree(trace_dir, ignore_errors=True)
    outside = []
    for name, evs in mx.items():
        if not name.startswith("Step::"):
            continue
        for start, dur, line in evs:
            if not any(a <= start and start + dur <= b
                       for a, b in dispatch.get(line, ())):
                outside.append([name, start, dur])
    by_name = {}
    for r in ring:
        by_name.setdefault(r.name, []).append((r.end_s - r.begin_s) * 1e9)
    gap_us = {}
    for name, evs in mx.items():
        durs = [d for _, d, _ in sorted(evs)]
        if len(durs) != len(by_name.get(name, ())):
            gap_us[name] = "trace %d, ring %d" % (
                len(durs), len(by_name.get(name, ())))
        else:
            gap_us[name] = max(abs(a - b) for a, b in
                               zip(durs, by_name[name])) * 1e-3
    rows = fit.spans.between("dispatch", since, time.perf_counter())
    print(json.dumps({
        "workload": args.workload, "traced_steps": n,
        "device": devices[0].device_kind, "ring_wrapped": wrapped,
        "mx_events": {k: len(v) for k, v in sorted(mx.items())},
        "planes": sorted(planes), "outside_dispatch": outside,
        "ring_gap_us": gap_us,
        "phase_ms": {k: sum(v) * 1e-6 / n for k, v in sorted(by_name.items())},
        "dispatch_ms": sum(e - s for s, e in rows) * 1e3 / n,
        "idle_cost_us": idle_cost_us()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
