"""Tensor (model) parallelism helpers.

Reference analog: none — the reference only has coarse layer-placement model
parallelism via ``ctx_group``/``group2ctx`` (SURVEY.md §2.2).  TPU-native TP
is pure sharding: annotate weight PartitionSpecs (megatron column/row splits)
and let pjit insert the all-reduces.  These helpers give the explicit
shard_map formulation for cases where manual collectives beat pjit's choices.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["column_parallel_dense", "row_parallel_dense", "mlp_block"]


def column_parallel_dense(x, w, mesh: Mesh, axis: str = "tp"):
    """y_local = x @ w_local  where w is [in, out/n] on each chip.
    No collective needed; output stays sharded on features."""
    f = jax.shard_map(lambda xs, ws: jnp.dot(xs, ws), mesh=mesh,
                      in_specs=(P(), P(None, axis)),
                      out_specs=P(None, axis))
    return f(x, w)


def row_parallel_dense(x, w, mesh: Mesh, axis: str = "tp"):
    """y = psum_i(x_local @ w_local) where x is feature-sharded and w is
    [in/n, out]: one all-reduce over ICI at the end (megatron row layer)."""

    def f(xs, ws):
        return jax.lax.psum(jnp.dot(xs, ws), axis)

    g = jax.shard_map(f, mesh=mesh,
                      in_specs=(P(None, axis), P(axis, None)),
                      out_specs=P())
    return g(x, w)


def mlp_block(x, w1, w2, mesh: Mesh, axis: str = "tp", act=jax.nn.relu):
    """Column-parallel up-proj + row-parallel down-proj: exactly one
    all-reduce per MLP block (the megatron pattern)."""
    h = column_parallel_dense(x, w1, mesh, axis)

    def down(hs, ws):
        return jax.lax.psum(jnp.dot(act(hs), ws), axis)

    g = jax.shard_map(down, mesh=mesh,
                      in_specs=(P(None, axis), P(axis, None)),
                      out_specs=P())
    return g(h, w2)
