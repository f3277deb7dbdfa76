"""1 - the union of the device operations' intervals over the traced window,
on the chip that was busy longest."""


def read(ctx, params):
    del params
    tr = ctx["trace"]
    if not tr.busy_ps or ctx["traced_window_s"] <= 0:
        return None
    busy = max(tr.busy_ps.values()) * 1e-12
    return 100.0 * (1.0 - busy / ctx["traced_window_s"])
