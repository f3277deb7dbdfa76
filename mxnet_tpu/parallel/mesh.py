"""Device mesh + sharding-rule helpers.

The scaling-book recipe: pick a mesh, annotate shardings, let XLA insert
collectives.  Axis conventions: ``dp`` (data/batch), ``tp`` (tensor/model),
``sp`` (sequence/context), ``pp`` (pipeline stage), ``ep`` (expert).
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "data_parallel_sharding", "replicated_sharding",
           "state_sharding", "exchange_path", "grad_exchange",
           "matmul_wt", "lookup", "lookup_path", "ShardingRules",
           "megatron_rules",
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


# -- the gradients' exchange of the mesh step --------------------------------
#
# A weight whose state is split over ``dp`` gets its gradient through a
# reduce-scatter of the replicas' partial sums.  Left to the partitioner that
# is an all-reduce and a slice, which the TPU compiler fuses into a BLOCKING
# ``all-reduce-scatter`` fusion: nothing else runs on the core while it
# exchanges (PR 32, compiled for a described v5e:2x2 with libtpu 0.0.34:
# ``xla_enable_async_reduce_scatter_fusion`` with
# ``xla_tpu_enable_async_collective_fusion_fuse_reduce_scatter`` does wrap the
# fusion in an async start / done pair and the scheduler does move the pairs
# apart, but no pass gives the pair a body, ``async-collective-merger`` turns
# each back into the same blocking fusion, tagged ``async_collective_name``,
# and with the merger off the backend refuses the module, ``Unsupported async
# call``; the same holds for all-reduces).  What the chip does overlap with
# its products are DMA-only collectives: all-gathers and collective
# permutes.  So on TPUs the products that make a split weight's gradient
# (``matmul_wt``'s backward) take each replica's partial product under
# ``shard_map`` and pass its quarters round a ring of collective permutes,
# both ways at once, adding as they go, each hop held beside a product of
# backward.  Found on the chips (PR 32, ``gpt2m_train_dp4``): the device
# busy 39.7 -> 29.6 ms a step, the exposed exchange 12.3 -> 3.5.

_exchange = threading.local()


def exchange_path(param_shardings, state_shardings) -> Optional[str]:
    """How the mesh step exchanges the gradients of its split leaves, from
    what can be seen of the layout: None where no state is split over
    ``dp`` (one device, small leaves); ``"async"`` where the weights are
    replicated over a mesh of TPUs that has no other extent than ``dp``,
    so that a replica's partial gradient is one local product (the ring of
    ``matmul_wt``); ``"row"`` elsewhere (a CPU mesh, a ``tp`` extent: the
    partitioner's blocking reduce-scatters in a row behind backward)."""
    if state_shardings is None:
        return None
    mesh = state_shardings[0].mesh
    if mesh.devices.flat[0].platform == "tpu" \
            and mesh.shape.get("dp") == mesh.devices.size \
            and not any(ax for sh in param_shardings for ax in sh.spec):
        return "async"
    return "row"


@contextlib.contextmanager
def grad_exchange(mesh: Mesh, dp_axis: str):
    """While tracing under it, ``matmul_wt`` makes the gradient of a weight
    that ``state_sharding`` splits over ``dp_axis`` in that layout, through
    the ring, and ``lookup`` reads a table split along its columns in each
    chip's own columns."""
    was = tuple(getattr(_exchange, k, None) for k in ("on", "last", "ended"))
    _exchange.on = (mesh, dp_axis, _ring_order(mesh, dp_axis))
    # the newest ring's (gradient, split axis), and {id(gradient):
    # (gradient, the same as its ring's last barrier hands it on)}
    _exchange.last, _exchange.ended = (), {}
    try:
        yield
    finally:
        _exchange.on, _exchange.last, _exchange.ended = was


def ended(grads):
    """Under ``grad_exchange``, after backward: every gradient that came
    out of a ring, as the barrier that ends its ring hands it on (the same
    values; taking them from there is what keeps the barrier's hold on the
    ring)."""
    return [_exchange.ended.get(id(g), (g, g))[1] for g in grads]


def _ring_order(mesh: Mesh, dp_axis: str):
    """The ``dp`` axis' indices in an order in which neighbours (the last
    and the first too) are neighbours on the chips' interconnect where the
    devices say where they sit (``coords``: a 2x2 host reads 0, 1, 3, 2),
    else as they come."""
    n = mesh.shape[dp_axis]     # the mesh's only extent (exchange_path)
    at = [getattr(d, "coords", None) for d in mesh.devices.reshape(-1)]
    if any(c is None for c in at) or len(set(map(tuple, at))) < n:
        return tuple(range(n))
    order, left = [0], set(range(1, n))
    while left:         # nearest first: a walk around a 2 x k block
        here = at[order[-1]]
        nxt = min(left, key=lambda i: (sum(abs(a - b) for a, b
                                           in zip(at[i], here)), i))
        order.append(nxt)
        left.remove(nxt)
    return tuple(order)


def _ring_reduce_scatter(part, axis, name, ring, beside_first=None):
    """Inside ``shard_map`` over ``name``: the sum over the replicas of
    ``part``, each replica left with its own slice along ``axis``.  The
    partial sums of a slice meet at its owner from both sides of the ring:
    one half of the slice gathers the replicas before the owner over the
    longer way round and those after it over the shorter, the other half
    the other way about, so that both directions of every link carry the
    same bytes and a slice is whole after ``n // 2`` hops, not ``n - 1``
    (four chips: two hops, the first with two thirds of the bytes).
    ``beside_first(received)`` is handed what the first hop's permutes
    yield and gives it back: the caller's place to tie their end to a
    product of its own."""
    n = len(ring)
    size = part.shape[axis] // n
    idx = jax.lax.axis_index(name)
    far, near = n // 2, (n - 1) // 2    # the two ways round to the owner

    def chain(ring, hops, lo, width, own):
        # over ``hops`` hops along ``ring``: the slice as the ``hops``
        # replicas before its owner hold it and, with ``own``, the owner
        pos = jnp.asarray(np.argsort(ring), jnp.int32)[idx]
        ids = jnp.asarray(ring, jnp.int32)
        perm = [(ring[k], ring[(k + 1) % n]) for k in range(n)]

        def mine(ahead):
            # this replica's part of the slice of the replica ``ahead``
            # places on along the ring
            return jax.lax.dynamic_slice_in_dim(
                part, ids[(pos + ahead) % n] * size + lo, width, axis)

        return {"perm": perm, "hops": hops, "own": own, "mine": mine,
                "acc": mine(hops)}

    def both(ring, lo, width):
        # the longer way counts the owner in; two chips have no shorter
        ways = [chain(ring, far, lo, width, True)]
        if near:
            ways.append(chain(ring[::-1], near, lo, width, False))
        return ways

    half = size // 2
    halves = [both(ring, 0, size)] if size % 2 else \
        [both(ring, 0, half), both(ring[::-1], half, half)]
    chains = [c for ways in halves for c in ways]
    for hop in range(1, far + 1):
        live = [c for c in chains if hop <= c["hops"]]
        got = [jax.lax.ppermute(c["acc"], name, c["perm"]) for c in live]
        if hop == 1 and beside_first is not None:
            got = beside_first(got)
        for c, g in zip(live, got):
            c["acc"] = g + c["mine"](c["hops"] - hop) \
                if hop < c["hops"] or c["own"] else g
    return jnp.concatenate(
        [functools.reduce(jnp.add, (c["acc"] for c in ways))
         for ways in halves], axis)


def _split_axis(shape, on):
    """The axis along which ``state_sharding`` splits a replicated weight
    of ``shape`` over the exchange's ``dp`` axis; None where it does not."""
    mesh, dp, _ = on
    repl = NamedSharding(mesh, P())
    ssh = state_sharding(repl, shape, dp)
    return None if ssh is repl else list(ssh.spec).index(dp)


def _by_columns(shape, on):
    """Whether ``state_sharding`` splits a replicated table of ``shape``
    along its columns (its output axis: a vocabulary that does not divide
    by ``dp``), so that a chip holds every row of a part of the columns."""
    return len(shape) == 2 and _split_axis(shape, on) == 1


def lookup_path(shape, uses, param_sharding, state_sharding, exchange):
    """How the mesh step reads a parameter of ``shape`` that ``uses``, the
    (op, input) pairs of the graph that read it, name as an ``Embedding``'s
    table: None where it is not held split over ``dp`` or no ``Embedding``
    reads it.  ``"own_columns"`` on the ``async`` exchange where it is
    split along its columns and every use is an ``Embedding``'s table: the
    program leaves it split and each chip looks up its own columns
    (``lookup``).  ``"gathered"`` elsewhere: a tied head or a table split
    along its rows is gathered whole at the top of the step, as every
    other held-split weight."""
    if state_sharding is param_sharding or ("Embedding", 1) not in uses:
        return None
    if exchange == "async" and set(uses) == {("Embedding", 1)} \
            and _by_columns(shape, (state_sharding.mesh, "dp", None)):
        return "own_columns"
    return "gathered"


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _matmul_wt_ring(on, x, w):
    return jnp.matmul(x, w.T)


def _matmul_wt_fwd(on, x, w):
    return jnp.matmul(x, w.T), (x, w)


@functools.lru_cache(maxsize=None)
def _ring_backward(on, axis, held_axis):
    """``(dy, x, w, *held) -> (dx, dw, *held)`` of ``matmul_wt``'s backward
    for a weight split along ``axis``, ``held`` the gradient of the ring
    before (split along ``held_axis``; none for backward's first product).
    A ``jax.jit`` callee, so that a model's hundred products of five shapes
    are traced and lowered five times, not a hundred.

    The compiler's scheduler, left to itself, runs every product first and
    the permutes in one chain behind them, so the order is held by
    barriers (they last until the schedule is made): the weight's product
    first; then the ring's first hop beside the input's product, whose
    result is handed on only with what the hop received; the second hop
    beside the NEXT product of backward, which may not start its input's
    product before the ring before it has ended."""
    _, dp, ring = on

    def local(dy, x, w, *held):
        part = jax.lax.dot_general(
            dy.reshape(-1, dy.shape[-1]), x.reshape(-1, x.shape[-1]),
            (((0,), (0,)), ((), ())))
        dy, part, *held = jax.lax.optimization_barrier((dy, part, *held))
        dx = []

        def beside(received):
            ahead, received = jax.lax.optimization_barrier(
                (jnp.matmul(dy, w), received))
            dx.append(ahead)
            return received

        dw = _ring_reduce_scatter(part, axis, dp, ring, beside)
        return (dx[0], dw, *held)

    def split(a):       # a weight's gradient, in its state's layout
        return P(*(dp if i == a else None for i in range(2)))

    rows = P(dp)        # the batch's rows, as the mesh step feeds them
    held = () if held_axis is None else (split(held_axis),)
    return jax.jit(jax.shard_map(
        local, in_specs=(rows, rows, P(), *held),
        out_specs=(rows, split(axis), *held),
        axis_names={dp}, check_vma=False))


def _matmul_wt_bwd(on, res, dy):
    """The input's gradient as autodiff makes it; the weight's as each
    replica's own product ``dy^T x`` over its rows, [out, in], reduced
    around the ring into the layout the weight's state is held in."""
    x, w = res
    axis = _split_axis(w.shape, on)
    before = _exchange.last             # the ring of the product before
    dx, dw, *handed = _ring_backward(on, axis, *before[1:] or (None,))(
        dy, x, w, *before[:1])
    if handed:
        _exchange.ended[id(before[0])] = (before[0], handed[0])
    _exchange.last = (dw, axis)
    return dx, dw


_matmul_wt_ring.defvjp(_matmul_wt_fwd, _matmul_wt_bwd)


def matmul_wt(x, w):
    """``x @ w.T`` for a weight laid (out, in), as ``FullyConnected`` and
    the attention projections use it.  Under ``grad_exchange``, where the
    weight's state is split over ``dp``, its gradient is made in that layout
    through the ring; anywhere else this IS ``jnp.matmul(x, w.T)``."""
    on = getattr(_exchange, "on", None)
    if on is None or w.ndim != 2 or x.ndim < 2 \
            or x.shape[0] % len(on[2]) or _split_axis(w.shape, on) is None:
        return jnp.matmul(x, w.T)
    return _matmul_wt_ring(on, x, w)


# -- a table's lookup on the chip's own columns --------------------------------
#
# A token table whose rows do not divide by ``dp`` (GPT-2's 50,257) has its
# state, and between steps itself, split along its columns.  Read whole, it
# is gathered at the top of the step, ahead of forward's first op, where
# nothing hides the gather (103 MB in ``gpt2m_train_dp4``).  Instead each
# chip looks up its own columns for every chip's ids and one all-to-all
# hands each chip its own rows' every column (2 MB there); the gradient
# comes back the same way and is scattered into the chip's own columns.

@functools.lru_cache(maxsize=None)
def _own_columns(on, rows):
    """``(forward, backward)`` of ``lookup`` on a table of ``rows`` rows
    split along its columns: ``forward(ids, table) -> (out, every)``, the
    batch's rows of ``ids`` split over ``dp`` as the mesh step feeds them
    and ``every`` every chip's ids, the residual; ``backward(every, dy) ->
    the table's gradient`` in the table's layout.  ``jax.jit`` callees, as
    the ring's."""
    _, dp, _ = on

    def forward(ids, part):
        every = jax.lax.all_gather(ids, dp)             # [n, *ids]
        got = jnp.take(part, every, axis=0)             # [n, *ids, d]
        # chip i's ids leave for chip i; the columns arrive in chip order
        out = jax.lax.all_to_all(got, dp, 0, got.ndim - 1, tiled=True)
        return out[0], every

    def backward(every, dy):
        # the mirror image: the chip's columns of every chip's rows
        cols = jax.lax.all_to_all(dy[None], dp, dy.ndim, 0, tiled=True)
        zero = jax.ShapeDtypeStruct((rows, cols.shape[-1]), cols.dtype)
        return jax.linear_transpose(
            lambda t: jnp.take(t, every, axis=0), zero)(cols)[0]

    split = P(None, dp)
    return (jax.jit(jax.shard_map(forward, in_specs=(P(dp), split),
                                  out_specs=(P(dp), P()), axis_names={dp},
                                  check_vma=False)),
            jax.jit(jax.shard_map(backward, in_specs=(P(), P(dp)),
                                  out_specs=split, axis_names={dp},
                                  check_vma=False)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lookup_own_columns(on, rows, ids, table):
    return _own_columns(on, rows)[0](ids, table)[0]


def _lookup_fwd(on, rows, ids, table):
    return _own_columns(on, rows)[0](ids, table)


def _lookup_bwd(on, rows, every, dy):
    return None, _own_columns(on, rows)[1](every, dy)


_lookup_own_columns.defvjp(_lookup_fwd, _lookup_bwd)


def lookup(table, ids):
    """``table``'s rows at the integer ``ids`` (``jnp.take`` along axis 0),
    as ``Embedding`` reads its table.  Under ``grad_exchange``, where the
    table's state is split over ``dp`` along its columns, each chip looks
    up its own columns and the activations cross the chips, not the
    table, and the gradient is made in the state's layout; anywhere else
    this IS ``jnp.take(table, ids, axis=0)``."""
    on = getattr(_exchange, "on", None)
    if on is None or ids.ndim < 1 or ids.shape[0] % len(on[2]) \
            or not _by_columns(table.shape, on):
        return jnp.take(table, ids, axis=0)
    return _lookup_own_columns(on, table.shape[0], ids, table)


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
        # latent attention: the matrices that make the heads split by head
        # (the low-rank bottlenecks, q_a / kv_a, match nothing: whole)
        (r"(q_b|kv_b)_weight$", P(t, None)),
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
