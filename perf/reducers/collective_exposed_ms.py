"""Per step, on the chip where it is longest: the time in collective
operations (those whose compiler category or name matches
``params.collective``, in flight or blocking) during which no other operation
runs on that chip's core.  No collective in the trace: nothing to read."""
import re

from perf.trace import subtract


def read(ctx, params):
    tr = ctx["trace"]
    rx = re.compile(params["collective"])
    if not ctx["traced_steps"]:
        return None

    def is_coll(r):
        return bool(rx.search(r[4] or "") or rx.search(r[0]))

    worst, seen = 0, False
    for d, rows in tr.devices.items():
        coll = [(r[1], r[1] + r[2]) for r in rows + tr.inflight.get(d, [])
                if is_coll(r)]
        if not coll:
            continue
        seen = True
        compute = [(r[1], r[1] + r[2]) for r in rows if not is_coll(r)]
        worst = max(worst, subtract(coll, compute))
    if not seen:
        return None
    return worst * 1e-9 / ctx["traced_steps"]
