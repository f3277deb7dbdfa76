#!/usr/bin/env python3
"""The device events under a scope, by name, by hand:

    python3 perf/tests/scope_events.py --workload <cell> --seed <n> \
        --scope 'SparseMoE:' [--scope 'ShortConv:' ...] \
        [--group '[data formatting]' ...] [--top 25]

Builds the cell's ``Fit``, proves it (every shape warm), traces
``traced_steps`` steps and reads the ``.xplane.pb`` with ``perf/trace.py``'s
own reader.  One JSON line: for every ``--scope`` pattern the device
milliseconds a step of the events whose scope matches, forward and backward
apart, by event name with the trailing instance number cut (``fusion.12`` ->
``fusion``) and the call count a step, the ``--top`` largest; and under
``all`` the same for the whole device line by the breakdown's groups; for
every ``--group`` (one of those groups' names) its events by name and result
shape, with one event's scope.  The
line is also written to ``chiprun_out/scope_events_<cell>_<seed>.json``.  What
PERF.md section 5 splits a layer's time from, and where a roofline metric's
``kernel`` pattern is read off.  Run on the chip with ``chiprun``; it fails
without a TPU.
"""
import argparse
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--scope", action="append", default=[])
    ap.add_argument("--group", action="append", default=[])
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    from perf import harness, trace
    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.devices_or_none(cell.workload["chips"], "tpu")
    if devices is None:
        return 3
    import jax
    from perf.loops import module_fit
    module_fit.prepare(cell.config)
    fit = module_fit.Fit(cell, devices, args.seed)
    fit.prove()
    steps = cell.workload["traced_steps"]
    for s in range(10):                       # a few steps of steady state
        fit.one_step(10 + s)
    fit.loop.drain()
    trace_dir = os.path.join(ROOT, "perf", ".trace_scope_events")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for s in range(steps):
        fit.one_step(100 + s)
    fit.loop.drain()
    jax.profiler.stop_trace()
    reduced = trace.reduce(trace_dir, cell.workload["chips"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    dev = max(reduced.busy_ps, key=reduced.busy_ps.get)
    rows = reduced.devices[dev]

    def table(keep, key):
        groups = {}
        for name, _start, dur, scope, cat in rows:
            if keep(scope):
                k = key(name, scope, cat)
                ms, n = groups.get(k, (0.0, 0))
                groups[k] = (ms + dur * 1e-9 / steps, n + 1)
        top = sorted(groups.items(), key=lambda kv: -kv[1][0])[:args.top]
        return [[k, round(ms, 4), n / steps] for k, (ms, n) in top]

    def by_name(name, scope, cat):
        # an event's name is the whole instruction: "%fusion.12 = bf16[..."
        short = re.sub(r"[.\d]+$", "", name.split(" = ")[0].lstrip("%"))
        return "%s %s" % ("bwd" if "transpose(" in (scope or "") else "fwd",
                          short)

    out = {"workload": args.workload, "seed": args.seed, "steps": steps,
           "busy_ms_a_step": reduced.busy_ps[dev] * 1e-9 / steps,
           "all": table(lambda s: True,
                        lambda n, s, c: trace.scope_group(s, c))}
    # the breakdown's bracketed groups (no op type read off the scope): one
    # event of each, name and scope as the trace has them
    odd = {}
    for name, _start, _dur, scope, cat in rows:
        g = trace.scope_group(scope, cat)
        if g.startswith("[") and g not in odd:
            odd[g] = {"name": name[:300], "scope": scope}
    out["bracketed"] = odd
    # what carries no scope at all, by name, with one whole name a group
    bare = table(lambda s: not s, by_name)
    sample = {}
    for name, _start, _dur, scope, cat in rows:
        if not scope:
            sample.setdefault(by_name(name, scope, cat), name[:400])
    out["unscoped"] = [row + [sample[row[0]]] for row in bare]
    for pattern in args.scope:
        rx = re.compile(pattern)
        out[pattern] = {
            "ms_a_step": reduced.scope_ps(pattern, dev) * 1e-9 / steps,
            "events": table(lambda s: s is not None and rx.search(s),
                            by_name),
            "a_scope": next((s for _, _, _, s, _ in rows
                             if s is not None and rx.search(s)
                             and "transpose(" in s), None)}
    for group in args.group:
        sums = {}
        for name, _start, dur, scope, cat in rows:
            if trace.scope_group(scope, cat) == group:
                head, _, rest = name.partition(" = ")
                k = "%s %s" % (re.sub(r"[.\d]+$", "", head.lstrip("%")),
                               rest.split("{")[0])
                ms, n, _ = sums.get(k, (0.0, 0, None))
                sums[k] = (ms + dur * 1e-9 / steps, n + 1, scope)
        top = sorted(sums.items(), key=lambda kv: -kv[1][0])[:args.top]
        out[group] = [[k, round(ms, 4), n / steps, scope]
                      for k, (ms, n, scope) in top]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "scope_events_%s_%d.json"
                           % (args.workload, args.seed)), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
