"""The whole step's share of the chips' peak: the operations the forward and
backward passes require per step (the configuration's own shapes->operations
function, recomputed operations not counted) over the traced wall time per
step, over chips x the table's bf16 peak."""


def read(ctx, params):
    del params
    if not ctx["traced_steps"] or ctx["traced_window_s"] <= 0:
        return None
    per_step_s = ctx["traced_window_s"] / ctx["traced_steps"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * ctx["step_flops"] / per_step_s / peak
