"""The reference's first three training steps, and the numbers `correct`
compares.  One driver for every configuration's reference module, which
gives ``init_params``, ``make_batches`` and ``loss_and_grad``.

``precision``: ``float32`` is the reference; ``fp8`` is the control (the
reference put in the program's place, computed one step below the bfloat16
the configurations state; ``perf/refs/common.py`` says how).  ``fault``: the
reference put in the program's place with one of the faults a training cell
can have planted in it.
"""
import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from . import common

STEPS = 3
FAULTS = ("half_batch", "no_exchange")


def _faulted(batch, fault, chips):
    """half_batch: the second half of the rows left out, the mean taken over
    the rest.  no_exchange: the gradient of one chip's rows only."""
    if fault is None:
        return batch
    if fault == "half_batch":
        share = 2
    elif fault == "no_exchange":
        share = max(chips, 2)
    else:
        raise ValueError("unknown fault %r" % (fault,))
    rows = batch[0].shape[0]
    if rows >= share:
        return tuple(a[:rows // share] for a in batch)
    # one row: its first positions stand for the rows kept (a causal model's
    # loss over them is what the whole row gives for them)
    return tuple(a[:, :a.shape[1] // share] for a in batch)


def run(ref, cfg, wl, seed, precision="float32", fault=None, rows=None):
    """{"loss": [3], "row_loss_step1": every row's loss at the first step,
    "grad_norm": {leaf: norm of the first effective gradient},
    "change_norm": {leaf: norm of the parameters' change after the three
    steps}} as python floats."""
    opt, hp = wl["optimizer"], dict(wl["optimizer_params"])
    hp.pop("multi_precision", None)
    params = {k: v.astype(jnp.float32)
              for k, v in ref.init_params(cfg, seed).items()}
    start = params
    batches = ref.make_batches(cfg, wl, seed)
    state = common.init_state(opt, params)
    rows = rows or wl.get("reference_rows", wl["batch"])

    @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(1, 2))
    def update(params, grads, state, t):
        new, st, geff = common.apply_update(opt, hp, params, grads, state, t)
        return new, st, common.leaf_norms(geff)

    losses, grad_norm = [], None
    for t in range(1, STEPS + 1):
        batch = _faulted(batches[(t - 1) % len(batches)], fault,
                         wl.get("chips", 1))
        loss, grads, row_loss = ref.loss_and_grad(
            cfg, params, batch, precision, min(rows, batch[0].shape[0]))
        losses.append(loss)
        if t == 1:
            first_rows = row_loss
        new, state, gn = update(params, grads, state, t)
        if t == 1:
            grad_norm = gn       # params (== start) must outlive this step
        else:
            jax.tree_util.tree_map(lambda a: a.delete(), params)
        params = new
    change = jax.jit(lambda a, b: common.leaf_norms(
        {k: a[k] - b[k] for k in a}))(params, start)
    return {"loss": [float(l) for l in losses],
            "row_loss_step1": [float(v) for v in np.asarray(first_rows)],
            "grad_norm": {k: float(v) for k, v in grad_norm.items()},
            "change_norm": {k: float(v) for k, v in change.items()}}


# ------------------------------------------------------------- comparison
#: a leaf whose reference gradient is under this share of the median leaf's
#: is nought to rounding and moves by round-off alone: left out of the change
NOUGHT_SHARE = 1e-3


def worst_leaf_gap(got, ref, leave_out=()):
    """The training bullet's measure: over the leaves, the largest gap
    between the program's norm and the reference's, against the reference's
    norm of that leaf or of the median leaf, whichever is larger.  Returns
    (gap, leaf)."""
    gaps = leaf_gaps(got, ref, leave_out)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def leaf_gaps(got, ref, leave_out=()):
    """{leaf: gap} by the training bullet's measure (``worst_leaf_gap``)."""
    med = statistics.median(ref.values())
    gaps = {}
    for k, r in ref.items():
        if k in leave_out:
            continue
        gap = abs(got[k] - r) / max(r, med)
        gaps[k] = gap if np.isfinite(gap) else float("inf")
    return gaps


def median_leaf_gap(got, ref, leave_out=()):
    """The median leaf's gap, and that leaf: steady where the worst leaf is
    one small leaf's noise (PERF.md section 4 says where and why)."""
    gaps = sorted(leaf_gaps(got, ref, leave_out).items(), key=lambda kv: kv[1])
    return gaps[len(gaps) // 2][1], gaps[len(gaps) // 2][0]


def compare(got, ref):
    """{number: (value, worst leaf or step)} of a program's (or control's)
    readings against the reference's."""
    loss = [abs(g - r) / abs(r) if np.isfinite(g) else float("inf")
            for g, r in zip(got["loss"], ref["loss"])]
    med = statistics.median(ref["grad_norm"].values())
    nought = {k for k, v in ref["grad_norm"].items() if v < NOUGHT_SHARE * med}
    grad = worst_leaf_gap(got["grad_norm"], ref["grad_norm"])
    change = worst_leaf_gap(got["change_norm"], ref["change_norm"], nought)
    out = {}
    a, b = got.get("row_loss_step1"), ref.get("row_loss_step1")
    if a is not None and b is not None:
        # rows the two have in common (a fault may have left rows out): the
        # mean over the rows of the gap, which random rounding cannot cancel
        # as it does in the mean loss
        n = min(len(a), len(b))
        gaps = np.abs(np.asarray(a[:n], np.float64) - np.asarray(b[:n]))
        gap = float(np.mean(gaps) / abs(np.mean(b[:n])))
        out["row_loss_gap"] = (gap if np.isfinite(gap) else float("inf"),
                               "row%d" % int(np.argmax(gaps)))
    return {**out,
            "loss_gap": (max(loss), "step%d" % (1 + int(np.argmax(loss)))),
            "loss_step1_gap": (loss[0], "step1"),
            "grad_norm_gap": grad, "change_norm_gap": change,
            "grad_norm_median_gap": median_leaf_gap(
                got["grad_norm"], ref["grad_norm"]),
            "change_norm_median_gap": median_leaf_gap(
                got["change_norm"], ref["change_norm"], nought)}
