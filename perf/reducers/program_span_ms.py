"""Host milliseconds per step of the program's own spans (``params.spans``,
names of ``mxnet_tpu.profiler.span``) over the traced steps, read from the
program's flight-recorder ring (``tracing.flight.records``).  With
``params.arg`` the sum of that key of the spans' ``args`` over the traced
steps instead (a count, not divided).

The traced steps are the last ``ctx["traced_steps"]`` of the harness's own
``dispatch`` spans; a record belongs to them when it begins at or after the
first of these begins.  Nothing to read (the metric is left out): a program
without the reader or without such a span, or a ring that has wrapped past
the stretch."""


def read(ctx, params):
    from mxnet_tpu import tracing
    records = getattr(tracing.flight, "records", None)
    n = ctx["traced_steps"]
    dispatched = [r for r in ctx["spans"].rows if r[0] == "dispatch"]
    if records is None or not n or len(dispatched) < n:
        return None
    got, wrapped = records(names=params["spans"], since_s=dispatched[-n][1])
    if wrapped or not got:
        return None
    if "arg" in params:
        return sum((r.args or {}).get(params["arg"], 0) for r in got)
    return sum(r.end_s - r.begin_s for r in got) * 1e3 / n
