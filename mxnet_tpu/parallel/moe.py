"""Expert parallelism: Mixture-of-Experts layer sharded over a mesh axis.

Reference analog: none — the reference (2018) predates MoE; SURVEY.md §2.2
lists expert parallelism as the one optional strategy.  TPU-native design:
experts live sharded over the ``ep`` mesh axis; tokens are routed with a
top-k softmax gate and exchanged via ``all_to_all`` over ICI (the standard
GShard/Switch dispatch), with fixed expert capacity so every shape is
static for XLA.

Layout (per shard_map block, E experts over ``n`` chips, local E_l = E/n):
  1. gate: (T, E) logits -> top-k expert ids + combine weights
  2. dispatch: scatter tokens into a (E, C) capacity buffer (C tokens per
     expert; overflow dropped, the Switch-Transformer behavior)
  3. all_to_all: (E, C, D) -> (E_l, n*C, D) — each chip keeps only its
     local experts' slots but receives them from every chip
  4. expert FFN on the local (E_l, n*C, D) batch — dense matmuls on MXU
  5. all_to_all back + weighted combine into (T, D)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["top_k_gating", "moe_ffn", "MoEParams", "init_moe_params"]


def top_k_gating(logits, k: int):
    """Top-k softmax gate (GShard style): returns (weights, ids) with
    weights renormalized over the chosen k."""
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(gates, k)
    weights = weights / jnp.maximum(
        weights.sum(axis=-1, keepdims=True), 1e-9)
    return weights.astype(logits.dtype), ids


def _dispatch_mask(ids, weights, num_experts: int, capacity: int):
    """(T, k) routed ids -> dispatch one-hot (T, E, C) and combine weights.

    Position within each expert's capacity buffer is the token's rank among
    tokens routed to that expert (cumsum trick); tokens past capacity are
    dropped (their combine weight is zeroed) — static shapes throughout.
    """
    T, k = ids.shape
    flat_ids = ids.reshape(-1)                             # (T*k,)
    onehot = jax.nn.one_hot(flat_ids, num_experts,
                            dtype=jnp.int32)               # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                   # rank per expert
    pos = jnp.sum(pos * onehot, axis=-1)                   # (T*k,)
    keep = pos < capacity
    disp = (jax.nn.one_hot(flat_ids, num_experts, dtype=jnp.float32)
            [:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, 0), capacity,
                             dtype=jnp.float32)[:, None, :])
    disp = disp * keep[:, None, None].astype(jnp.float32)
    disp = disp.reshape(T, k, num_experts, capacity)
    w = weights.reshape(T, k, 1, 1).astype(jnp.float32)
    combine = jnp.sum(disp * w, axis=1)                    # (T, E, C)
    dispatch = jnp.sum(disp, axis=1)                       # (T, E, C)
    return dispatch, combine


class MoEParams:
    """Dense parameter bundle for an MoE FFN: gate + per-expert weights."""

    def __init__(self, wg, w1, w2):
        self.wg = wg      # (D, E)
        self.w1 = w1      # (E, D, H)
        self.w2 = w2      # (E, H, D)


def init_moe_params(rng: np.random.RandomState, d_model: int,
                    d_hidden: int, num_experts: int,
                    dtype=np.float32) -> MoEParams:
    s1 = 1.0 / np.sqrt(d_model)
    s2 = 1.0 / np.sqrt(d_hidden)
    return MoEParams(
        jnp.asarray(rng.uniform(-s1, s1, (d_model, num_experts))
                    .astype(dtype)),
        jnp.asarray(rng.uniform(-s1, s1,
                                (num_experts, d_model, d_hidden))
                    .astype(dtype)),
        jnp.asarray(rng.uniform(-s2, s2,
                                (num_experts, d_hidden, d_model))
                    .astype(dtype)))


def moe_ffn(x, params: MoEParams, mesh: Optional[Mesh] = None,
            axis: str = "ep", k: int = 2,
            capacity_factor: float = 1.25, act=jax.nn.relu):
    """MoE FFN layer: top-k routed expert MLPs.

    x: (T, D) tokens (flatten batch x seq first).  With ``mesh`` given,
    experts are sharded over mesh axis ``axis`` and tokens exchanged with
    two ``all_to_all`` collectives (expert parallelism over ICI); without
    a mesh, computes all experts locally (single-chip reference behavior,
    used by tests as ground truth).
    """
    E = params.wg.shape[1]
    T = x.shape[0]

    def gate_and_dispatch(xs, capacity):
        logits = xs @ params.wg.astype(xs.dtype)
        weights, ids = top_k_gating(logits, k)
        dispatch, combine = _dispatch_mask(ids, weights, E, capacity)
        # (E, C, D) expert inputs
        expert_in = jnp.einsum("tec,td->ecd",
                               dispatch.astype(xs.dtype), xs)
        return expert_in, combine

    def expert_mlp(expert_in, w1, w2):
        h = act(jnp.einsum("ecd,edh->ech", expert_in,
                           w1.astype(expert_in.dtype)))
        return jnp.einsum("ech,ehd->ecd", h, w2.astype(expert_in.dtype))

    if mesh is None:
        capacity = int(np.ceil(capacity_factor * k * T / E))
        expert_in, combine = gate_and_dispatch(x, capacity)
        expert_out = expert_mlp(expert_in, params.w1, params.w2)
        return jnp.einsum("tec,ecd->td", combine.astype(x.dtype),
                          expert_out)

    n = mesh.shape[axis]
    if E % n:
        raise ValueError("num_experts %d not divisible by %s=%d"
                         % (E, axis, n))
    # capacity is per chip: each shard dispatches its T/n local tokens, so
    # the slot budget must scale with the LOCAL token count or
    # capacity_factor silently inflates n-fold (and buffers with it)
    local_capacity = int(np.ceil(capacity_factor * k * (T // n) / E))

    def sharded(xs, w1_local, w2_local):
        # xs: (T/n, D) local tokens; w*_local: (E/n, ...) local experts
        expert_in, combine = gate_and_dispatch(xs, local_capacity)
        # exchange: every chip sends each expert's slots to its owner;
        # axis 0 splits experts, concat on capacity
        expert_in = jax.lax.all_to_all(expert_in, axis, split_axis=0,
                                       concat_axis=1, tiled=True)
        expert_out = expert_mlp(expert_in, w1_local, w2_local)
        expert_out = jax.lax.all_to_all(expert_out, axis, split_axis=1,
                                        concat_axis=0, tiled=True)
        return jnp.einsum("tec,ecd->td", combine.astype(xs.dtype),
                          expert_out)

    f = jax.shard_map(sharded, mesh=mesh,
                      in_specs=(P(axis, None), P(axis, None, None),
                                P(axis, None, None)),
                      out_specs=P(axis, None))
    return f(x, params.w1, params.w2)


def load_balancing_loss(logits, ids, num_experts: int):
    """Switch-Transformer auxiliary load-balancing loss: E * sum_e
    (fraction of tokens routed to e) * (mean gate prob of e)."""
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = gates.mean(axis=0)
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], num_experts,
                                 dtype=jnp.float32), axis=0)
    return num_experts * jnp.sum(me * ce)


__all__.append("load_balancing_loss")
