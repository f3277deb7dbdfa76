"""Mean host-clock milliseconds per step of one of the harness's spans
(``params.span``) over the window."""


def read(ctx, params):
    rows = ctx["spans"].between(params["span"], ctx["window_from"],
                                ctx["window_to"])
    if not rows or not ctx["steps"]:
        return None
    return sum(e - s for s, e in rows) * 1e3 / ctx["steps"]
