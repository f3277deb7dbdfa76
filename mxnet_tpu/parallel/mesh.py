"""Device mesh + sharding-rule helpers.

The scaling-book recipe: pick a mesh, annotate shardings, let XLA insert
collectives.  Axis conventions: ``dp`` (data/batch), ``tp`` (tensor/model),
``sp`` (sequence/context), ``pp`` (pipeline stage), ``ep`` (expert).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "data_parallel_sharding", "replicated_sharding",
           "state_sharding", "ShardingRules", "megatron_rules",
           "host_shard_hint", "P"]

#: a leaf under this many elements keeps its parameter's sharding: a
#: collective of its own would cost more than updating it on every chip
STATE_SHARD_MIN_ELEMENTS = 1 << 16


def host_shard_hint(mesh: Optional[Mesh] = None,
                    axis: str = "dp") -> Tuple[int, int]:
    """(rank, nranks) hint for per-host sharded data loading.

    Each process of a multi-host mesh should decode only the slice of the
    global batch that lands on its local devices; feeding this tuple to
    ``io.NDArrayIter(num_parts=nranks, part_index=rank)`` (or any reader
    honoring the same contract) does exactly that.  On a single-host mesh
    this is (0, 1): the host decodes everything and ``jax.device_put``
    against the batch sharding splits it across local chips.
    """
    return int(jax.process_index()), int(jax.process_count())


def make_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """Create a Mesh with named axes, e.g. make_mesh({'dp': 4, 'tp': 2}).

    Axis sizes must multiply to the device count; an axis size of -1 takes
    the remainder (like reshape).  Device order follows jax.devices(), which
    on TPU pods matches ICI adjacency for contiguous inner axes.
    """
    devices = list(devices if devices is not None else jax.devices())
    names = list(axes)
    sizes = list(axes.values())
    n = len(devices)
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1])) or 1
        sizes[sizes.index(-1)] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError("mesh %s does not fit %d devices" % (axes, n))
    arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, tuple(names))


def data_parallel_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Batch-dim sharding for inputs."""
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def state_sharding(param_sharding: NamedSharding, shape,
                   dp_axis: str = "dp") -> NamedSharding:
    """Where the optimizer's state of one parameter lives (its float32
    master, moments, momentum) and, between steps, the parameter itself:
    the parameter's own sharding, split further over the mesh's
    data-parallel axis, so that each replica updates ``1/dp`` of the leaf
    from a reduce-scattered gradient (Xu et al. 2020, cross-replica
    sharding of the weight update).  The mesh step holds, donates and
    gives back the weight in this layout and all-gathers it to the
    parameter's sharding at the top of its program, beside forward
    (``Executor.step_program``).

    The split goes along the largest axis that the parameter's spec leaves
    unsharded and whose extent divides by the ``dp`` size.  A leaf with no
    such axis, one under ``STATE_SHARD_MIN_ELEMENTS``, a spec that already
    names ``dp``, or a ``dp`` axis of size 1 keeps the parameter's
    sharding (the SAME object is returned: the step tells a split leaf by
    that) and is updated on every replica.
    """
    mesh = param_sharding.mesh
    n = mesh.shape.get(dp_axis, 1)
    shape = tuple(shape)
    if n == 1 or int(np.prod(shape)) < STATE_SHARD_MIN_ELEMENTS:
        return param_sharding
    spec = list(param_sharding.spec) + \
        [None] * (len(shape) - len(param_sharding.spec))
    used = {a for ax in spec if ax is not None
            for a in ((ax,) if isinstance(ax, str) else ax)}
    free = [i for i, (dim, ax) in enumerate(zip(shape, spec))
            if ax is None and dim % n == 0]
    if dp_axis in used or not free:
        return param_sharding
    spec[max(free, key=lambda i: shape[i])] = dp_axis
    while spec[-1] is None:     # as a program's result names it, so that
        spec.pop()              # the layout taken equals the layout given
    return NamedSharding(mesh, P(*spec))


class ShardingRules:
    """Name-pattern → PartitionSpec rules for parameter pytrees.

    Megatron-style TP defaults: FC/conv weights split on the output-feature
    axis, paired projections split on input; biases and norms replicated.
    Users override per-pattern (regex on parameter name).
    """

    def __init__(self, mesh: Mesh, rules: Optional[Sequence] = None,
                 default: P = P()):
        import re
        self.mesh = mesh
        self.rules = [(re.compile(pat), spec) for pat, spec in (rules or [])]
        self.default = default

    def spec_for(self, name: str, shape: Tuple[int, ...]) -> P:
        for pat, spec in self.rules:
            if pat.search(name):
                if self._fits(spec, shape):
                    return spec
        return self.default

    def sharding_for(self, name: str, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(name, tuple(shape)))

    def _fits(self, spec: P, shape) -> bool:
        if len(spec) > len(shape):
            return False
        for dim, ax in zip(shape, spec):
            if ax is None:
                continue
            size = self.mesh.shape[ax] if isinstance(ax, str) else \
                int(np.prod([self.mesh.shape[a] for a in ax]))
            if dim % size != 0:
                return False
        return True


def megatron_rules(mesh: Mesh, tp_axis: str = "tp") -> ShardingRules:
    """Default TP rules for our model zoo's parameter naming, and the
    expert axis: a ``SparseMoE``'s expert weights (expert, in, out) are
    split over the mesh's ``ep`` axis where it has one and replicated where
    it has none; its router and bias are replicated."""
    t = tp_axis
    experts = P("ep") if "ep" in mesh.axis_names else P()
    return ShardingRules(mesh, rules=[
        # the expert axis FIRST: expert_down_weight would otherwise match
        # the row-parallel rule on its (expert, in) axes
        (r"expert_(gate|up|down)_weight$", experts),
        (r"(router_weight|expert_bias)$", P()),
        # row-parallel (input-split) rule next: out_proj/fc2/down names
        # also end in proj_weight/fc2_weight, which the column rule below
        # would otherwise claim — first match wins in spec_for
        (r"(out_proj|fc2|down)\w*_weight$", P(None, t)),
        # column-parallel: the gated feed-forward's gate and up, and
        # ShortConv's in_proj, beside the projections that were there
        (r"(fc|dense|proj|query|key|value|gate|up)\d*_weight$", P(t, None)),
        (r"conv\w*_weight$", P(t, None, None, None)),
        (r"embedding\w*_weight$", P(None, t)),
    ])
