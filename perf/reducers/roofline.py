"""A kernel's share of its roofline: the least time the chip could take for
the kernel's calls (the larger of operations over peak FLOP/s and bytes over
peak bytes/s; ``params.ops`` and ``params.bytes`` name functions of the
configuration's builder, taking (config, workload) and giving the numbers for
one step) over the device time of the events whose name or scope matches
``params.kernel``.  No such event in the trace: nothing to read (never 0)."""
import re

from perf.trace import covered


def read(ctx, params):
    tr = ctx["trace"]
    rx = re.compile(params["kernel"])
    best = 0
    for rows in tr.devices.values():
        best = max(best, covered(
            (r[1], r[1] + r[2]) for r in rows
            if rx.search(r[0]) or (r[3] is not None and rx.search(r[3]))))
    if not best or not ctx["traced_steps"]:
        return None
    builder, cfg, wl = ctx["builder"], ctx["config"], ctx["workload"]
    ops = getattr(builder, params["ops"])(cfg, wl)
    byts = getattr(builder, params["bytes"])(cfg, wl)
    least_s = max(ops / ctx["peaks"][params.get("peak", "bf16_flops_per_s")],
                  byts / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (best * 1e-12 / ctx["traced_steps"])
