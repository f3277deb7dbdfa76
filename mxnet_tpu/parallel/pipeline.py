"""Pipeline parallelism: GPipe-style microbatching over mesh stages.

Beyond-parity feature (SURVEY.md §2.2: the reference has no pipeline
parallelism; the plan's phase-5+ stretch goal).  TPU-native design: stages
are sharded onto a ``pp`` mesh axis; the schedule is a ``lax.scan`` over
microbatches with a ``ppermute`` shift of activations between stage
neighbours each tick — the classic GPipe fill/drain pipeline expressed as
ONE compiled SPMD program (no host orchestration per tick).

Usage::

    mesh = make_mesh({"pp": 4})
    pp = Pipeline(stage_fn, num_stages=4, num_microbatches=8)
    out = pp(params_per_stage, x)        # inside shard_map over "pp"
    # or end-to-end:
    y = pipeline_apply(mesh, "pp", stage_fn, stage_params, x, n_micro=8)

``stage_fn(params, x) -> x`` is the per-stage computation; all stages must
share one activation shape (pad/project at stage boundaries otherwise).
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["Pipeline", "pipeline_apply"]


class Pipeline:
    """The inner SPMD pipeline body (call inside shard_map over the pp
    axis)."""

    def __init__(self, stage_fn: Callable, num_stages: int,
                 num_microbatches: int, axis: str = "pp"):
        if num_microbatches < 1:
            raise ValueError("need at least one microbatch")
        self.stage_fn = stage_fn
        self.num_stages = num_stages
        self.num_microbatches = num_microbatches
        self.axis = axis

    def __call__(self, stage_params, micro_in):
        """stage_params: this stage's params (already sharded);
        micro_in: (num_microbatches, mb, ...) microbatches, meaningful on
        stage 0.  Returns (num_microbatches, mb, ...) outputs, meaningful
        on the last stage."""
        s = self.num_stages
        m = self.num_microbatches
        stage_id = lax.axis_index(self.axis)
        ticks = m + s - 1
        mb_shape = micro_in.shape[1:]

        def tick(carry, t):
            outputs, prev_act = carry
            # stage 0 injects microbatch t (when still filling); others
            # consume the activation shifted from the left neighbour
            inj = micro_in[jnp.minimum(t, m - 1)]
            x = jnp.where(stage_id == 0, inj, prev_act)
            y = self.stage_fn(stage_params, x)
            # the last stage banks its finished microbatch (t - (s-1))
            out_idx = t - (s - 1)
            bank = (stage_id == s - 1) & (out_idx >= 0)
            slot = jnp.clip(out_idx, 0, m - 1)
            outputs = outputs.at[slot].set(
                jnp.where(bank, y, outputs[slot]))
            # shift activations one stage to the right over ICI
            nxt = lax.ppermute(y, self.axis,
                               [(i, (i + 1) % s) for i in range(s)])
            return (outputs, nxt), None

        outputs0 = jnp.zeros((m,) + mb_shape, micro_in.dtype)
        prev0 = jnp.zeros(mb_shape, micro_in.dtype)
        # carries vary per stage: mark them device-varying for shard_map
        outputs0, prev0 = lax.pcast((outputs0, prev0), (self.axis,),
                                    to="varying")
        (outputs, _), _ = lax.scan(tick, (outputs0, prev0),
                                   jnp.arange(ticks))
        return outputs


def pipeline_apply(mesh, axis: str, stage_fn: Callable, stage_params,
                   x, n_micro: int):
    """End-to-end GPipe forward: split x into microbatches, run the
    pipeline over ``mesh[axis]`` stages, gather the last stage's outputs.

    stage_params: pytree whose leaves have a leading stage axis of length
    ``num_stages`` (leaf shape (S, ...)); each stage sees its own slice.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    s = mesh.shape[axis]
    n = x.shape[0]
    if n % n_micro:
        raise ValueError("batch %d not divisible by %d microbatches"
                         % (n, n_micro))
    micro = x.reshape((n_micro, n // n_micro) + x.shape[1:])
    pipe = Pipeline(stage_fn, s, n_micro, axis)

    def body(params_slice, micro_all):
        # params_slice arrives with a leading length-1 stage axis
        my_params = jax.tree_util.tree_map(lambda p: p[0], params_slice)
        outs = pipe(my_params, micro_all)
        # only the last stage's bank is meaningful: keep it, zero others,
        # then psum so every stage returns the final outputs
        keep = (lax.axis_index(axis) == s - 1).astype(outs.dtype)
        return lax.psum(outs * keep, axis)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P()), out_specs=P())
    out = fn(stage_params, micro)
    return out.reshape((n,) + out.shape[2:])
