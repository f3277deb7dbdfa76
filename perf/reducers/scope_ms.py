"""Device milliseconds per step of the operations whose scope (the ``tf_op``
the compiler keeps from ``jax.named_scope``; the program's atlas contract
names them ``<OpType>:<node>``, ``Optimizer::<name>``, ``GradSync``) matches
``params.pattern``.  Nothing matched: nothing to read."""


def read(ctx, params):
    tr = ctx["trace"]
    if not ctx["traced_steps"] or not tr.matched(params["pattern"]):
        return None
    return tr.scope_ps(params["pattern"]) * 1e-9 / ctx["traced_steps"]
