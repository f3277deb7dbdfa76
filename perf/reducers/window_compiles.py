"""jax's own compile-request events inside the window (fresh or restored):
there should be none."""


def read(ctx, params):
    del params
    return ctx["window_compiles"]["requests"]
