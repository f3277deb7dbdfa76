"""A program counter's growth over the window as a share of the window's
steps (``params.counter`` names it in ``ctx["counters"]``)."""


def read(ctx, params):
    if not ctx["steps"]:
        return None
    return 100.0 * ctx["counters"][params["counter"]] / ctx["steps"]
