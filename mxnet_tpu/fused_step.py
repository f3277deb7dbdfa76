"""Fused whole-step training dispatch.

The paper's "fast as the hardware allows" step has three launches on the
eager path: one fwdbwd XLA program plus a python loop of per-param
optimizer kernels plus per-param KVStore round-trips.  This module drives
the fused alternative: ``Executor.step_program`` compiles forward + vjp +
every optimizer update into ONE ``jax.jit`` with params and opt-state
donated (``donate_argnums``), so a step is exactly one device launch and
weights update in place.  ``ModuleFusedStep.step`` is the one driver of
that program: on one device as it stands, on N devices as ONE GSPMD program
over a device ``Mesh`` (XLA inserts the gradient exchange from the
``P('dp')`` batch sharding).  The input placements make it a mesh program;
the driver's body is the same.

Gated by ``MXNET_TPU_FUSED_STEP`` (default ON for the local path); the
eager per-param loop remains both the OFF fallback and the parity oracle —
any structural surprise (monitor installed, sparse grads, exotic optimizer
state, kvstore-side update, N contexts that cannot host a mesh) falls back
per step, counted by ``step_dispatch_total{path=...}``.

Donation safety: XLA donation genuinely deletes the input buffer (also on
the CPU backend), while NDArray handles are freely re-pointed by python
callers (``set_params``, ``__setitem__``, ``set_states``).  ``DonationPool``
therefore tracks, per logical slot, the exact jax array the fused program
last produced; anything else found in the handle is defensively copied
before being donated, so no caller-held buffer is ever invalidated and no
donated buffer is ever double-used.  The copy takes the original's place
in the handle as it is made, so the original lives only as long as
whoever else holds it: the first step's peak is the state once and a leaf.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as _np

from . import atlas as _atlas
from . import telemetry as _telemetry
from . import health as _health
from . import memwatch as _memwatch
from . import profiler as _profiler

__all__ = ["enabled", "ModuleFusedStep",
           "TrainerFusedUpdate", "TrainerMeshUpdate", "DonationPool",
           "STEP_DISPATCH", "STEP_TIME", "ENV_FLAG"]

ENV_FLAG = "MXNET_TPU_FUSED_STEP"

STEP_DISPATCH = _telemetry.counter(
    "step_dispatch_total",
    "Training-step dispatches by path: fused one-program step vs eager "
    "per-param loop; bucketed vs per-key KVStore gradient traffic",
    ("path",))
STEP_TIME = _telemetry.histogram(
    "step_update_seconds",
    "Wall time of the train-step update phase (fused path: the whole "
    "fwd+bwd+update program; eager path: the per-param update loop)")


DONATION_COPIES = _telemetry.counter(
    "donation_copies_total",
    "Buffers DonationPool copied before donating them (a handle the pool "
    "did not own: every leaf on the first fused step and after set_params, "
    "none in steady state), by the step path that took them",
    ("path",))
DONATION_COPY_BYTES = _telemetry.counter(
    "donation_copy_bytes_total",
    "Bytes of the buffers counted by donation_copies_total",
    ("path",))
OPT_STATE_SHARDED_BYTES = _telemetry.gauge(
    "opt_state_sharded_bytes",
    "Global bytes of the optimizer-state leaves the mesh step holds in a "
    "layout split over the mesh's dp axis (each replica holds and updates "
    "1/dp of them; 0: every replica updates every leaf)",
    ("path",))


def _span(name, args=None):
    """One phase of the step timeline (docs/observability.md "Step
    timeline"), each opened once, in ``ModuleFusedStep.step``."""
    return _profiler.span(name, "step", args=args)


@contextlib.contextmanager
def _gather_span(pool):
    """``Step::gather``, with what ``pool`` copied inside it in its args;
    the caller fills in ``leaves``."""
    args = {"leaves": 0}
    copies0, bytes0 = pool.copies, pool.copy_bytes
    with _span("Step::gather", args):
        yield args
        args["copies"] = pool.copies - copies0
        args["copy_bytes"] = pool.copy_bytes - bytes0


def enabled():
    """MXNET_TPU_FUSED_STEP gate; default ON."""
    return os.environ.get(ENV_FLAG, "1").lower() not in \
        ("0", "false", "off", "")


def _env_tuple():
    from .executor import Executor
    return tuple(os.environ.get(k) for k in Executor.STEP_ENV_KEYS)


def _env_dict():
    """_env_tuple as {key: value} — the health/flight-dump snapshot form."""
    from .executor import Executor
    return {k: os.environ.get(k) for k in Executor.STEP_ENV_KEYS}


class DonationPool:
    """Ownership ledger for buffers the fused step donates.

    ``take`` returns a buffer safe to donate for a slot: the handle's
    current array if this pool produced it (nobody else can hold it — the
    program output went straight into the handle), else a fresh copy
    (externally written handles may share their buffer with caller-held
    arrays via no-op device_put/astype/broadcast_to), which the handle
    holds and the pool owns from then on (``_adopt``).  It has
    ``take_sharded``'s signature (its ``sharding`` is None: the array stays
    on its device), so a step binds one of the two once, outside its loops.
    ``give`` writes a
    program output back into the handle and records it as pool-owned.
    Every copy is counted (``copies``, ``copy_bytes``, and the telemetry
    pair ``donation_copies_total`` / ``donation_copy_bytes_total``): a copy
    in steady state costs a step's worth of HBM traffic and would show
    nowhere else.
    """

    def __init__(self):
        self._own = {}
        self.copies = 0
        self.copy_bytes = 0

    def count_copy(self, path, src):
        nbytes = int(getattr(src, "nbytes", 0))
        self.copies += 1
        self.copy_bytes += nbytes
        if _telemetry.enabled:
            DONATION_COPIES.labels(path=path).inc()
            DONATION_COPY_BYTES.labels(path=path).inc(nbytes)

    def _adopt(self, slot, handle, copy):
        """A copy the pool made of a handle it did not own goes into the
        handle and into the ledger AT ONCE: the original then dies with
        its last outside holder, leaf by leaf, and not at ``give``, after
        the step, when every leaf would have been held twice (the first
        fused step's peak was the weights and the whole optimizer state
        two times over).  The copy is waited for, so that the original's
        memory is free before the next leaf's copy asks for its own."""
        handle._data = self._own[slot] = copy
        copy.block_until_ready()
        return copy

    def take(self, slot, handle, sharding):
        cur = handle._data
        if self._own.get(slot) is not cur:
            self.count_copy("fused", cur)
            cur = self._adopt(slot, handle, jnp.array(cur))
        return cur

    def take_sharded(self, slot, handle, sharding):
        """Donation-safe buffer for a mesh slot: the handle's array when
        pool-owned AND already laid out as ``sharding``; otherwise a
        genuine copy placed onto the mesh, which the handle holds from
        then on (``_adopt``).  The copy must be
        ``jnp.array`` — ``jax.device_put`` may alias its input (even with
        ``may_alias=False`` on CPU), and donating an alias would delete
        the caller-held source buffer."""
        cur = handle._data
        if self._own.get(slot) is cur and \
                getattr(cur, "sharding", None) == sharding:
            return cur
        self.count_copy("mesh_fused", cur)
        return self._adopt(slot, handle,
                           jax.device_put(jnp.array(cur), sharding))

    def give(self, slot, handle, new_data):
        self._own[slot] = new_data
        handle._data = new_data
        if _memwatch.enabled:
            # Module-path slots are ("w", name)/("s", slot, j); Trainer
            # pools only ever hold donated opt-state (int-tuple slots).
            _memwatch.tag("params" if slot and slot[0] == "w"
                          else "opt_state", new_data)

    def disown(self, slot):
        """Forget a slot (its buffer escaped to non-pool code — e.g. the
        mesh global was re-placed per device): the next take copies."""
        self._own.pop(slot, None)


def _dense(arr):
    from .ndarray.sparse import BaseSparseNDArray
    return arr is not None and not isinstance(arr, BaseSparseNDArray)


def _copy_state_to(st, ctx):
    """Genuine per-device copy of an optimizer state pytree (None / NDArray
    / nested tuples-lists), used when de-meshing splits the single mesh
    state back into the per-device eager layout."""
    if st is None:
        return None
    if isinstance(st, (list, tuple)):
        return type(st)(_copy_state_to(s, ctx) for s in st)
    if hasattr(st, "copyto"):
        return st.copyto(ctx)
    return st


def _as_jax(arr):
    """Device array of an NDArray/array-like without a host bounce when
    the value is already device-resident."""
    data = getattr(arr, "_data", None)
    if data is not None:
        return data
    return jnp.asarray(_np.asarray(arr))


class _StagedBatch:
    """A staged (deferred) train batch, materialised lazily in whichever
    layout the consumer needs: ``feeds()`` gives the per-device sliced
    feeds for the eager replay, ``full()`` the whole batch's device arrays
    for the fused step — a mesh step never pays the per-device slice +
    placement work."""

    def __init__(self, eg, data_batch):
        self._eg = eg
        self._batch = data_batch
        self._feeds = None

    def feeds(self):
        if self._feeds is None:
            self._feeds = self._eg._load_batch(self._batch)
        return self._feeds

    def full(self, read):
        """{input name: ``read(array)``} (``_Placement.read``)."""
        eg = self._eg
        out = {}
        for name, arr in zip(eg.data_names, self._batch.data):
            out[name] = read(arr)
        for name, arr in zip(eg.label_names, self._batch.label or []):
            out[name] = read(arr)
        return out


# What differs between the fused step on one device and on a mesh, decided
# once per bind / mesh change (``ModuleFusedStep._placement``): the
# counters' label and the program's name with health; the mesh (None: one
# device); ``read(array)``, a staged input's device array; ``put_batch(v,
# handle)`` and ``put_other(v)``, how a batch input and a non-parameter
# input are placed; ``take``, the pool's method for a donated leaf;
# ``scope()``, the context the program is lowered and launched in;
# ``span_args``, what ``Step::program`` / ``Step::launch`` say of it;
# ``land(outs)``, which puts the outputs where ``get_outputs`` finds them
# and returns the step's ``mesh_outputs()``.
_Placement = collections.namedtuple(
    "_Placement", "path program mesh read put_batch put_other take scope "
    "span_args land")


class ModuleFusedStep:
    """Drives Module's fused train step.

    ``forward_backward`` stages the batch; ``update`` then dispatches ONE
    whole-step program (fwd + vjp + update, params/opt-state donated)
    through ``step``: on one device, or over the mesh of N devices.
    Gradients are not written back to ``grad_dict`` (they only exist
    inside the program); the flush hooks replay a staged batch through
    the eager oracle whenever outputs or input grads must be observable
    before ``update``.
    """

    def __init__(self, module):
        self._mod = module
        self._eg = module._exec_group
        self._pool = DonationPool()
        self._pending = None
        self._unsupported = False
        self._structural_ok = {}     # env tuple -> bool
        self._mesh_cache = None      # (key, (mesh, rules, dp_axis)|None)
        self._place = None           # _placement()'s (False: none), and
        self._layout = None          # _mesh_layout()'s, of that mesh
        self._exchange = None        # its exchange_path(): a mesh, split
        self._split = None           # _count_split()'s, of that layout
        self._meshed = False         # handles currently hold mesh globals
        self._mesh_outputs = None    # full-batch outputs of the last step
        self.steps = 0               # fused steps since bind (Step::update)
        # program closures capture the optimizer binding; a new driver
        # (new init_optimizer / rebind) must not reuse a predecessor's
        for ex in self._eg.execs:
            for k in [k for k in ex._jitted
                      if isinstance(k, tuple) and k and k[0] == "step"]:
                del ex._jitted[k]
        req = self._eg.grad_req
        self._pnames = [n for n in module._param_names
                        if req.get(n) == "write"]
        self._pset = set(self._pnames)
        self._has_add = any(req.get(n) == "add"
                            for n in module._param_names)

    # -- lifecycle --------------------------------------------------------
    def stale(self):
        return self._eg is not self._mod._exec_group

    @property
    def pending(self):
        return self._pending is not None

    def stage(self, data_batch):
        self._pending = _StagedBatch(self._eg, data_batch)
        self._mesh_outputs = None

    def flush_eager(self):
        """Replay a staged batch through the eager fwdbwd programs so
        outputs/grads/aux become observable exactly as if the batch had
        never been deferred.  Always de-meshes first: the per-device
        programs cannot consume 8-device globals.  Mesh outputs are
        invalidated unconditionally — the caller is about to run eager
        programs (e.g. ``score``'s eval forward), after which the last
        mesh step's outputs would be served stale by ``get_outputs`` /
        ``update_metric``."""
        self._mesh_outputs = None
        self._demesh()
        if self._pending is None:
            return
        staged, self._pending = self._pending, None
        for ex, feed in zip(self._eg.execs, staged.feeds()):
            ex.forward_backward(**feed)

    def mesh_outputs(self):
        """Full-batch outputs of the last mesh step, or None when a newer
        batch is pending / the last step was not mesh-dispatched."""
        return None if self.pending else self._mesh_outputs

    def demesh(self):
        """Public hook (Module.get_params / set_mesh): restore per-device
        handle layout without touching a staged batch."""
        self._demesh()

    # -- eligibility ------------------------------------------------------
    def eligible(self):
        """One answer for ``Module``: a fused step (one device, or a mesh)
        or the eager loop."""
        if not enabled() or self._unsupported:
            return False
        m = self._mod
        if m._updater is None:  # update_on_kvstore
            return False
        kv = m._kvstore
        if kv is not None and (kv.type.startswith("dist")
                               or kv._updater is not None
                               or kv._compression is not None):
            return False
        for ex in self._eg.execs:
            if ex._monitor is not None or ex._group2ctx:
                return False
        if self._placement() is None:   # N contexts, no mesh over them
            return False
        # keyed by the step env values (bound dtypes are fixed, but the
        # dtype gate in supports_fused depends on optimizer mp config and
        # a stale cached verdict must not survive an env flip)
        env = _env_tuple()
        ok = self._structural_ok.get(env)
        if ok is None:
            ok = self._structural_ok[env] = self._check_structure()
        return ok

    def _check_structure(self):
        m = self._mod
        if self._eg.inputs_need_grad or self._has_add or not self._pnames:
            return False
        opt_ = m._optimizer
        if opt_.fused_state_arity() is None:
            return False
        for ex in self._eg.execs:
            for n in self._pnames:
                w = ex.arg_dict[n]
                if not _dense(w) or not _dense(ex.grad_dict.get(n)) \
                        or not opt_.supports_fused(w):
                    return False
        return True

    def _states_fusable(self, ndev):
        """Validate any pre-existing (e.g. preloaded) updater states before
        touching counts or consuming the pending feed.  Expected layout is
        per-slot: a low-precision weight's state carries the master-fp32
        leaf on top of the optimizer's own arity."""
        from . import optimizer as _opt
        m = self._mod
        opt_ = m._optimizer
        arity = opt_.fused_state_arity()
        states = m._updater.states
        for slot, st in states.items():
            i, k = divmod(slot, ndev)
            if not (0 <= i < len(m._param_names) and k < ndev):
                return False
            if k and states.get(slot - k, states) is st:
                # a mesh step's sibling slot (``_slots``): the device-0
                # slot's own object, looked at there
                continue
            w = self._eg.execs[k].arg_dict.get(m._param_names[i])
            mp = w is not None and opt_.fused_mp(w)
            leaves = _opt.fused_state_leaves(st, mp)
            if leaves is None or len(leaves) != arity + (1 if mp else 0):
                return False
        return True

    # -- dispatch ---------------------------------------------------------
    def step(self):
        """Consume the staged batch with the fused program.  Returns the
        dispatch path taken ("fused" on one device, "mesh_fused" on a
        mesh, both truthy) or False (after replaying the batch eagerly)
        when the updater state turns out not to be fusable, so
        Module.update can run the eager loop.

        One device is the mesh step's degenerate case: no sibling execs,
        no shardings (``_step_key()`` and the program are the plain ones)
        and ``_placement()``'s callables, bound here outside the loops."""
        from . import optimizer as _opt
        m = self._mod
        opt_ = m._optimizer
        execs = self._eg.execs
        ex, rest = execs[0], execs[1:]
        with _span("Step::validate"):
            ok = self._states_fusable(len(execs))
        if not ok:
            self._unsupported = True
            self.flush_eager()
            return False
        self.steps += 1
        place = self._placement()
        pshardings, sshardings, mesh_sig = self._mesh_layout()
        # a param and its state are taken in ONE layout: the state's
        leaf_ssh = sshardings or pshardings or itertools.repeat(None)
        pool, take, pset = self._pool, place.take, self._pset
        states = m._updater.states
        mp_of, leaves_of = opt_.fused_mp, _opt.fused_state_leaves
        staged, self._pending = self._pending, None
        with _span("Step::feed"):
            full = staged.full(place.read) if staged is not None else {}
            put_batch, put_other = place.put_batch, place.put_other
            batch_names = set(self._eg.data_names) | \
                set(self._eg.label_names)
            others, shapes = [], {}
            for n in ex.arg_names:
                handle = ex.arg_dict[n]
                if n in pset:
                    shapes[n] = handle.shape
                    continue
                if n in batch_names:
                    v = full.get(n)
                    if v is None:       # replayed without a staged batch
                        v = handle._data
                    if v.dtype != handle.dtype:
                        v = v.astype(handle.dtype)
                    v = put_batch(v, handle)
                else:
                    v = put_other(handle._data)
                others.append(v)
                shapes[n] = tuple(v.shape)
            auxs = [put_other(ex.aux_dict[n]._data) for n in ex.aux_names]
        with _span("Step::slots", {"params": len(self._pnames)}):
            slots = self._slots(ex, len(execs))
        pvals, svals, taken = [], [], []    # taken: (handle, leaves, mp)
        # a copied leaf goes into its handle at once (``DonationPool._adopt``):
        # from here on the handles may hold mesh globals
        self._meshed = self._meshed or place.mesh is not None
        with _gather_span(pool) as args:
            placed = pool.copies
            arg_dict, views = ex.arg_dict, [e.arg_dict for e in rest]
            for (name, slot, _, _, _), ssh in zip(slots, leaf_ssh):
                handle = arg_dict[name]
                data = handle._data
                for view in views:
                    # all execs' views of one param must agree: where one
                    # was written from outside, the slot is copied
                    if view[name]._data is not data:
                        pool.disown(("w", name))
                        break
                # the weight is held between steps in its state's layout
                # (the program gathers it at its top): taken as given
                w = take(("w", name), handle, ssh)
                if w is not data:
                    # copied: every exec's view lets go of its original now
                    for view in views:
                        view[name]._data = w
                pvals.append(w)
                # mp slots: leaf 0 is the master-fp32 copy — same shape as
                # the param, so it takes the state's layout like every
                # moment
                mp = mp_of(handle)
                leaves = leaves_of(states[slot], mp)
                svals.append(tuple([take(("s", slot, j), leaf, ssh)
                                    for j, leaf in enumerate(leaves)]))
                taken.append((handle, leaves, mp))
            data = None     # the last leaf's original dies here too
            if self._split is None or pool.copies != placed:
                # counted when a leaf was placed (the first step, a state
                # set from outside), not on every step
                self._split = self._count_split(svals)
            args["leaves"] = len(pvals) + sum(len(sv) for sv in svals)
            args["held_split"], args["sharded"], args["sharded_bytes"] = \
                self._split
            # (lrs, wds, ts, rescale): four small host arrays, copied to
            # the device(s) by the launch itself
            lrs = _np.asarray([s[2] for s in slots], _np.float32)
            wds = _np.asarray([s[3] for s in slots], _np.float32)
            ts = _np.asarray([s[4] for s in slots], _np.float32)
            rescale = _np.asarray(opt_.rescale_grad, _np.float32)
        run = dict(place.span_args)
        if self._exchange is not None:
            # how the program exchanges its split leaves' gradients
            run["exchange"] = self._exchange
        with _span("Step::program", run):
            plan = ex._plan(True)
            keys = ex._keys(plan)
            ex._last_keys = keys
            ogs = ex._ograds_for(shapes)
            # per-slot traced update: the mp wrapper for low-precision
            # weights, the plain fused core for fp32 ones — mixed layouts
            # (bf16 conv weights + fp32 batchnorm scales) fuse into one
            # program
            update_fns = [opt_.fused_update_mp if mp else opt_.fused_update
                          for _, _, mp in taken]
            first_run = run["first_run"] = \
                ex._step_key(mesh_sig) not in ex._jitted
            fn = ex.step_program(
                [s[0] for s in slots], update_fns, mesh_sig=mesh_sig,
                param_shardings=pshardings, state_shardings=sshardings)
            if first_run and _health.enabled:
                # lowering-only analysis — the dispatch below still owns
                # the one and only compilation of this program
                with place.scope():
                    _health.register_program(
                        place.program, fn, (pvals, svals, others, auxs,
                                            keys, ogs, lrs, wds, ts,
                                            rescale),
                        donated=True, env=ex._program_env(plan))
        # on a mesh, traced under it: an op that must keep a custom call on
        # each device's own rows (MultiHeadAttention's flash kernel) finds
        # it in ``jax.sharding.get_abstract_mesh()``
        with _span("Step::launch", run), place.scope():
            new_p, new_s, outs, new_aux = fn(
                pvals, svals, others, auxs, keys, ogs, lrs, wds, ts, rescale)
        if first_run and _health.enabled:
            _health.audit_donation(place.program, (pvals, svals))
        with _span("Step::writeback"):
            give = pool.give
            views = [e.arg_dict for e in rest]
            for (name, slot, _, _, _), (handle, leaves, _), w, st in zip(
                    slots, taken, new_p, new_s):
                give(("w", name), handle, w)
                for view in views:
                    view[name]._data = w
                for j, (leaf, arr) in enumerate(zip(leaves, st)):
                    give(("s", slot, j), leaf, arr)
            for n, v in zip(ex.aux_names, new_aux):
                for e in execs:
                    e.aux_dict[n]._data = v
            self._mesh_outputs = place.land(outs)
            # the donated inputs' (now dead) array objects go here and not
            # at the function's end, outside every phase: a thousand leaves
            # take a millisecond to drop
            del pvals, svals, new_p, new_s
        self._meshed = place.mesh is not None
        return place.path

    def _slots(self, ex, ndev):
        """Create-missing-state + count + capture per-slot scalars, in the
        eager loop's parameter order: ONE logical state per param, held in
        the device-0 slot of the eager layout; on a mesh the sibling slots
        alias it so checkpoints (`get_states`) and the eager resume path
        keep seeing the layout they expect.  The count advances once per
        step — the program IS the single update."""
        m = self._mod
        opt_ = m._optimizer
        states, synced = m._updater.states, m._updater.states_synced
        counts, slot_index = opt_._index_update_count, opt_.slot_index
        pset = self._pset
        siblings = range(1, ndev)
        out = []
        for i, name in enumerate(m._param_names):
            if name not in pset:
                continue
            base = slot_index(i, ndev, 0)
            st = states.get(base, states)
            if st is states:
                st = states[base] = opt_.create_state_multi_precision(
                    base, ex.arg_dict[name])
                synced[base] = True
            opt_._update_count(base)
            cnt = counts[base]
            for k in siblings:
                sib = slot_index(i, ndev, k)
                states[sib] = st
                synced[sib] = True
                counts[sib] = cnt
            # host-side lr corrections (Adam's f64 bias fold) ride in the
            # captured lr so the traced program matches the eager oracle
            out.append((name, base,
                        opt_.fused_slot_lr(opt_._get_lr(base), cnt),
                        opt_._get_wd(base), cnt))
        return out

    # -- placement: one device, or a mesh ---------------------------------
    def on_mesh_change(self):
        """Module.set_mesh hook: drop the cached mesh so the next step
        re-derives shardings (and a new step-program cache key)."""
        self._demesh()
        self._mesh_cache = None

    def _mesh_setup(self):
        """(mesh, rules, dp_axis) over the module's contexts, or None when
        the context set cannot host one (duplicate devices, no dp axis,
        axis sizes not matching the device count)."""
        from .parallel.mesh import make_mesh
        m = self._mod
        axes = getattr(m, "_mesh_axes", None) or \
            {"dp": len(self._eg.execs)}
        rules = getattr(m, "_sharding_rules", None)
        key = (tuple(axes.items()), id(rules))
        if self._mesh_cache is not None and self._mesh_cache[0] == key:
            return self._mesh_cache[1]
        setup = None
        if "dp" in axes:
            devices = [c.jax_device for c in self._eg.contexts]
            if len({d.id for d in devices}) == len(devices):
                try:
                    mesh = make_mesh(dict(axes), devices=devices)
                    setup = (mesh, rules, "dp")
                except (ValueError, TypeError):
                    setup = None
        self._mesh_cache = (key, setup)
        self._place = self._layout = self._split = self._exchange = None
        return setup

    def _placement(self):
        """The ``_Placement`` of this bind: one device as it stands, or N
        contexts as one mesh.  None where N contexts cannot host a mesh —
        facts of the bind (no kvstore selected: intentionally unsynced
        replicas; ragged batch slices; an input whose batch axis is not 0)
        and of the mesh (duplicate devices, a batch that does not divide
        over ``dp``): ``eligible()`` says no and the step is the eager
        loop's."""
        eg = self._eg
        setup = self._mesh_setup() if len(eg.execs) > 1 else None
        if self._place is None:
            if len(eg.execs) == 1:
                self._place = self._place_on_device()
            elif setup is None or not self._batch_shards(setup):
                self._place = False
            else:
                self._place = self._place_on_mesh(setup)
        return self._place or None

    def _place_on_device(self):
        ex, ctx = self._eg.execs[0], self._eg.contexts[0]

        def adopt(v, handle):
            # pre-placed producer batches as they are (PrefetchingIter
            # device double buffering): no re-put
            handle._data = v
            return v

        def land(outs):
            ex._wrap_outputs(outs)      # no mesh outputs: the exec's own

        return _Placement(
            "fused", "step", None,
            lambda arr: arr.as_in_context(ctx)._data, adopt, lambda v: v,
            self._pool.take, contextlib.nullcontext, {}, land)

    def _place_on_mesh(self, setup):
        from .ndarray.ndarray import NDArray
        from .parallel.mesh import data_parallel_sharding, \
            replicated_sharding
        mesh, _, dp = setup
        ctx = self._eg.contexts[0]
        repl = replicated_sharding(mesh)
        bsh = data_parallel_sharding(mesh, dp)

        def shard(v, _handle):
            # producer-prefetched batches (PrefetchingIter with
            # sharding=batch_sharding()) arrive pre-sharded: the H2D +
            # shard already happened during the PREVIOUS step
            if getattr(v, "sharding", None) != bsh:
                v = jax.device_put(v, bsh)
            return v

        return _Placement(
            "mesh_fused", "mesh_step", mesh, _as_jax, shard,
            lambda v: jax.device_put(v, repl), self._pool.take_sharded,
            functools.partial(jax.set_mesh, mesh),
            {"mesh": str(dict(mesh.shape))},
            lambda outs: [NDArray(o, ctx) for o in outs])

    def _batch_shards(self, setup):
        """Local synced-DP semantics (a local kvstore selected) and a batch
        that shards evenly on axis 0 of every input."""
        eg = self._eg
        if self._mod._kvstore is None:
            return False
        if len({s.stop - s.start for s in eg.slices}) != 1:
            return False
        mesh, _, dp = setup
        bs = eg.batch_size
        if bs % mesh.shape[dp] != 0:
            return False
        from .io import DataDesc
        for d in list(eg.data_shapes) + list(eg.label_shapes or []):
            if d.shape[0] != bs or \
                    DataDesc.get_batch_axis(getattr(d, "layout", "NCHW")) != 0:
                return False
        return True

    def _mesh_layout(self):
        """(param shardings, state shardings, mesh signature) of the
        trainable params in ``_slots``' order, derived once per mesh from
        what can be seen: the mesh, the rules' spec of the param and its
        shape; three Nones on one device.  The opt-state's layout is the
        param's, split further over ``dp``
        (``parallel.mesh.state_sharding``), and between steps the weight
        is held in it too: ``step`` takes and gives both in the state's
        sharding and the program gathers the weight to the param's at its
        top.  Where no leaf is split (a
        mesh without a ``dp`` extent, small leaves only) the state
        shardings are None, the signature does not name them and the
        program is the one it was."""
        if self._layout is None:
            if self._placement().mesh is None:
                self._layout = (None, None, None)
                return self._layout
            mesh, rules, dp = self._mesh_setup()
            from .parallel.mesh import exchange_path, \
                replicated_sharding, state_sharding
            repl = replicated_sharding(mesh)
            ex = self._eg.execs[0]
            psh, ssh = [], []
            for name in self._mod._param_names:
                if name not in self._pset:
                    continue
                shape = ex.arg_dict[name].shape
                sh = repl if rules is None else \
                    rules.sharding_for(name, shape)
                psh.append(sh)
                ssh.append(state_sharding(sh, shape, dp))
            sig = (tuple(sorted(mesh.shape.items())),
                   tuple(str(sh.spec) for sh in psh))
            if ssh == psh:
                ssh = None
            else:
                sig += (tuple(str(sh.spec) for sh in ssh),)
            self._layout = (psh, ssh, sig)
            self._exchange = exchange_path(psh, ssh)
        return self._layout

    def _count_split(self, svals):
        """(params held split, their opt-state leaves, those leaves' global
        bytes): the params of the layout whose state sharding is not their
        own, so that between steps the weight and every state leaf in
        ``svals`` (``step``'s, aligned with the layout) are split over
        ``dp``.  ``Step::gather``'s ``held_split`` / ``sharded`` /
        ``sharded_bytes`` and the operators' gauge."""
        psh, ssh, _ = self._mesh_layout()
        split = [sv for sv, p, s in zip(svals, psh or (), ssh or psh or ())
                 if s is not p]
        nbytes = sum(leaf.nbytes for sv in split for leaf in sv)
        if _telemetry.enabled:
            OPT_STATE_SHARDED_BYTES.labels(
                path=self._placement().path).set(nbytes)
        return len(split), sum(len(sv) for sv in split), nbytes

    def _demesh(self):
        """Point every exec's handles back at per-device arrays (the mesh
        globals, a weight held split over ``dp`` among them, are assembled
        whole onto each context's device) and split
        the aliased mesh opt-state into genuine per-device copies, so the
        eager per-device programs and the local-kvstore reduce can resume
        seamlessly after any number of mesh steps."""
        if not self._meshed:
            return
        from . import optimizer as _opt
        m = self._mod
        execs = self._eg.execs
        ndev = len(execs)
        pool = self._pool
        opt_ = m._optimizer
        states = m._updater.states if m._updater is not None else {}
        for i, name in enumerate(m._param_names):
            if name not in self._pset:
                continue
            g = execs[0].arg_dict[name]._data
            for e in execs:
                e.arg_dict[name]._data = jax.device_put(
                    g, e._ctx.jax_device)
            pool.disown(("w", name))
            base = opt_.slot_index(i, ndev, 0)
            st = states.get(base)
            if st is None:
                continue
            mp = opt_.fused_mp(execs[0].arg_dict[name])
            leaves = _opt.fused_state_leaves(st, mp) or []
            for j, leaf in enumerate(leaves):
                leaf._data = jax.device_put(
                    leaf._data, execs[0]._ctx.jax_device)
                pool.disown(("s", base, j))
            cnt = opt_._index_update_count.get(base)
            for k in range(1, ndev):
                sib = opt_.slot_index(i, ndev, k)
                states[sib] = _copy_state_to(st, execs[k]._ctx)
                m._updater.states_synced[sib] = True
                if cnt is not None:
                    opt_._index_update_count[sib] = cnt
        for n in self._eg.aux_names:
            g = execs[0].aux_dict[n]._data
            for e in execs:
                e.aux_dict[n]._data = jax.device_put(g, e._ctx.jax_device)
        self._meshed = False


class TrainerFusedUpdate:
    """Fused update phase for gluon.Trainer: one donated program per
    device replaces the per-param updater loop.  Weights are NOT donated
    (the autograd tape and user code may hold live references to
    ``param.data()`` buffers); optimizer state — which never escapes the
    updater un-copied — is."""

    def __init__(self, trainer):
        self._tr = trainer
        self._pools = [DonationPool() for _ in trainer._contexts]
        self._programs = {}
        self._unsupported = False

    def eligible(self):
        if not enabled() or self._unsupported:
            return False
        tr = self._tr
        if tr._update_on_kvstore:
            return False
        opt_ = tr._optimizer
        if opt_.fused_state_arity() is None:
            return False
        for p in tr._params:
            if p.grad_req == "null":
                continue
            if getattr(p, "_stype", "default") != "default" or \
                    getattr(p, "_grad_stype", "default") != "default":
                return False
            if not opt_.supports_fused(p.list_data()[0]):
                return False
        return True

    def step(self):
        from . import optimizer as _opt
        tr = self._tr
        opt_ = tr._optimizer
        live = [(i, p) for i, p in enumerate(tr._params)
                if p.grad_req != "null"]
        if not live:
            return True
        arity = opt_.fused_state_arity()
        ncty = len(tr._contexts)
        per_dev = [{"p": [], "s": [], "g": [], "lr": [], "wd": [], "t": []}
                   for _ in range(ncty)]
        update_fns = []
        # eager order: param-major, device-minor — each device's updater
        # shares the optimizer, so the update count really does advance
        # once per (param, device) visit
        for i, p in live:
            datas, grads = p.list_data(), p.list_grad()
            mp = opt_.fused_mp(datas[0])
            update_fns.append(opt_.fused_update_mp if mp
                              else opt_.fused_update)
            for k, upd in enumerate(tr._updaters):
                w = datas[k]
                if i not in upd.states:
                    upd.states[i] = \
                        opt_.create_state_multi_precision(i, w)
                    upd.states_synced[i] = True
                leaves = _opt.fused_state_leaves(upd.states[i], mp)
                if leaves is None or len(leaves) != arity + (1 if mp else 0):
                    self._unsupported = True
                    return False
                opt_._update_count(i)
                d = per_dev[k]
                d["p"].append(w._data)
                d["s"].append(tuple(self._pools[k].take((i, j), leaf, None)
                                    for j, leaf in enumerate(leaves)))
                d["g"].append([grads[k]._data])
                d["lr"].append(opt_.fused_slot_lr(
                    opt_._get_lr(i), opt_._index_update_count[i]))
                d["wd"].append(opt_._get_wd(i))
                d["t"].append(opt_._index_update_count[i])
        rescale = jnp.asarray(opt_.rescale_grad, jnp.float32)
        env = _env_tuple()
        fn = self._programs.get(env)
        first_run = fn is None
        if fn is None:
            from .executor import build_update_program
            fn = build_update_program(update_fns, donate_params=False)
            self._programs[env] = fn
        if first_run and _health.enabled and per_dev:
            d0 = per_dev[0]
            _health.register_program(
                "trainer_update", fn,
                (d0["p"], d0["s"], d0["g"],
                 jnp.asarray(d0["lr"], jnp.float32),
                 jnp.asarray(d0["wd"], jnp.float32),
                 jnp.asarray(d0["t"], jnp.float32), rescale), donated=True,
                env=_env_dict())
        for k in range(ncty):
            d = per_dev[k]
            with _profiler.span("Trainer::FusedUpdate", "executor"):
                new_p, new_s = fn(
                    d["p"], d["s"], d["g"],
                    jnp.asarray(d["lr"], jnp.float32),
                    jnp.asarray(d["wd"], jnp.float32),
                    jnp.asarray(d["t"], jnp.float32), rescale)
            if first_run and k == 0 and _health.enabled:
                # only opt-state is donated here (donate_params=False)
                _health.audit_donation("trainer_update", d["s"])
            pool = self._pools[k]
            for (i, p), w, st in zip(live, new_p, new_s):
                p.list_data()[k]._data = w
                if _memwatch.enabled:
                    _memwatch.tag("params", w)
                leaves = _opt.fused_state_leaves(
                    tr._updaters[k].states[i], opt_.fused_mp(p.list_data()[k]))
                for j, (leaf, arr) in enumerate(zip(leaves, st)):
                    pool.give((i, j), leaf, arr)
        return True


def _adopt(shape, sharding, arrs):
    """Zero-copy global from per-device committed arrays (the sources stay
    alive; donating the adopted global deletes them)."""
    return jax.make_array_from_single_device_arrays(
        tuple(shape), sharding, list(arrs))


def build_mesh_update_program(update_fns, ndev, out_sharding):
    """Donated GSPMD update program for the Trainer mesh path.

    Inputs: replicated params/opt-state globals and per-device gradients
    adopted as ``P('dp')`` shards of a ``(ndev*d0, ...)`` global; the
    leading-axis reshape+sum below IS the gradient all-reduce — XLA lowers
    the reduction over the sharded axis to a collective over ICI.  Only
    opt-state (argument 1) is donated: weights and grads were adopted
    zero-copy from buffers the autograd tape / user code may still hold.
    ``out_sharding`` pins outputs replicated so every device holds a full
    shard for the per-device writeback.
    """
    update_fns = tuple(update_fns)

    def fn(pvals, svals, gvals, lrs, wds, ts, rescale):
        new_p, new_s = [], []
        for i, upd in enumerate(update_fns):
            with jax.named_scope(_atlas.GRAD_SYNC):
                g = gvals[i]
                g = g.reshape((ndev, g.shape[0] // ndev) + g.shape[1:]) \
                     .sum(0)
            with jax.named_scope(_atlas.optimizer_scope(upd)):
                w, s = upd(pvals[i], g, svals[i], lrs[i], wds[i], rescale,
                           ts[i])
            w = jax.lax.with_sharding_constraint(w, out_sharding)
            s = jax.tree_util.tree_map(
                lambda a: jax.lax.with_sharding_constraint(a, out_sharding),
                s)
            new_p.append(w)
            new_s.append(s)
        return new_p, new_s

    return jax.jit(fn, donate_argnums=(1,))


class TrainerMeshUpdate:
    """Mesh-native reduce+update phase for gluon.Trainer on local
    multi-device: per-device weight replicas and raw (un-reduced) gradient
    buffers are adopted zero-copy into globals over a ``dp`` mesh, and ONE
    GSPMD program does the gradient all-reduce plus every optimizer update
    — replacing the host-side KVStore push/pull reduce and the per-device
    update programs entirely.

    Update-count semantics follow the single-device step (one logical
    update per param per step), unlike the eager multi-device loop whose
    shared optimizer advances the count once per (param, device) visit.
    """

    def __init__(self, trainer):
        self._tr = trainer
        self._pools = [DonationPool() for _ in trainer._contexts]
        self._programs = {}
        self._unsupported = False
        self._mesh = None          # None = unprobed, False = cannot build
        self._devids = [c.jax_device.id for c in trainer._contexts]

    def _mesh_setup(self):
        from .parallel.mesh import make_mesh
        if self._mesh is None:
            devices = [c.jax_device for c in self._tr._contexts]
            if len({d.id for d in devices}) != len(devices):
                self._mesh = False
            else:
                self._mesh = make_mesh({"dp": len(devices)},
                                       devices=devices)
        return self._mesh or None

    def eligible(self):
        if not enabled() or self._unsupported:
            return False
        tr = self._tr
        if len(tr._contexts) <= 1 or tr._update_on_kvstore:
            return False
        kv = tr._kvstore
        # a local kvstore signals synced-DP semantics (the reduce we fuse
        # in-program); no kvstore means intentionally unsynced replicas
        if kv is None or kv.type.startswith("dist") \
                or getattr(kv, "_updater", None) is not None \
                or getattr(kv, "_compression", None) is not None:
            return False
        opt_ = tr._optimizer
        if opt_.fused_state_arity() is None:
            return False
        for p in tr._params:
            if p.grad_req == "null":
                continue
            if getattr(p, "_stype", "default") != "default" or \
                    getattr(p, "_grad_stype", "default") != "default":
                return False
            w0 = p.list_data()[0]
            if not opt_.supports_fused(w0) or len(w0.shape) == 0:
                return False
        return self._mesh_setup() is not None

    def step(self):
        from . import optimizer as _opt
        from jax.sharding import NamedSharding, PartitionSpec as P
        tr = self._tr
        opt_ = tr._optimizer
        mesh = self._mesh_setup()
        ndev = len(tr._contexts)
        live = [(i, p) for i, p in enumerate(tr._params)
                if p.grad_req != "null"]
        if not live:
            return True
        arity = opt_.fused_state_arity()
        repl = NamedSharding(mesh, P())
        gsh = NamedSharding(mesh, P("dp"))
        # validate/create every state BEFORE any adoption: a donation-bound
        # program must never launch with half-captured inputs
        mps = {i: opt_.fused_mp(p.list_data()[0]) for i, p in live}
        for i, p in live:
            nleaves = arity + (1 if mps[i] else 0)
            for k, upd in enumerate(tr._updaters):
                if i not in upd.states:
                    upd.states[i] = opt_.create_state_multi_precision(
                        i, p.list_data()[k])
                    upd.states_synced[i] = True
                leaves = _opt.fused_state_leaves(upd.states[i], mps[i])
                if leaves is None or len(leaves) != nleaves:
                    self._unsupported = True
                    return False
        pvals, svals, gvals, lrs, wds, ts = [], [], [], [], [], []
        try:
            for i, p in live:
                datas = [d._data for d in p.list_data()]
                grads = [g._data for g in p.list_grad()]
                pvals.append(_adopt(datas[0].shape, repl, datas))
                per_leaf = []
                for j in range(arity + (1 if mps[i] else 0)):
                    leaves_k = [_opt.fused_state_leaves(
                        tr._updaters[k].states[i], mps[i])[j]
                        for k in range(ndev)]
                    per_leaf.append(self._take_state((i, j), leaves_k, repl))
                svals.append(tuple(per_leaf))
                gshape = (ndev * grads[0].shape[0],) + grads[0].shape[1:]
                gvals.append(_adopt(gshape, gsh, grads))
        except (ValueError, TypeError):
            # adoption needs committed per-device buffers of equal shape;
            # anything else (uncommitted arrays, ragged replicas) falls
            # back to the per-device fused path for good
            self._unsupported = True
            return False
        for i, p in live:
            # one LOGICAL update per param per step: the global program IS
            # the single update (single-device count semantics)
            opt_._update_count(i)
            lrs.append(opt_.fused_slot_lr(
                opt_._get_lr(i), opt_._index_update_count[i]))
            wds.append(opt_._get_wd(i))
            ts.append(opt_._index_update_count[i])
        env = _env_tuple()
        key = (env, tuple(sorted(mesh.shape.items())), len(live))
        fn = self._programs.get(key)
        first_run = fn is None
        if fn is None:
            fn = build_mesh_update_program(
                [opt_.fused_update_mp if mps[i] else opt_.fused_update
                 for i, p in live], ndev, repl)
            self._programs[key] = fn
        if first_run and _health.enabled:
            _health.register_program(
                "trainer_mesh_update", fn,
                (pvals, svals, gvals,
                 jnp.asarray(lrs, jnp.float32), jnp.asarray(wds, jnp.float32),
                 jnp.asarray(ts, jnp.float32),
                 jnp.asarray(opt_.rescale_grad, jnp.float32)), donated=True,
                env=_env_dict())
        with _profiler.span("Trainer::MeshUpdate", "executor",
                            args={"mesh": str(dict(mesh.shape))}):
            new_p, new_s = fn(
                pvals, svals, gvals,
                jnp.asarray(lrs, jnp.float32), jnp.asarray(wds, jnp.float32),
                jnp.asarray(ts, jnp.float32),
                jnp.asarray(opt_.rescale_grad, jnp.float32))
        if first_run and _health.enabled:
            # only opt-state is donated here (weights/grads were adopted
            # zero-copy from buffers user code may still hold)
            _health.audit_donation("trainer_mesh_update", svals)
        for (i, p), w, st in zip(live, new_p, new_s):
            self._scatter(p.list_data(), w)
            for j in range(arity + (1 if mps[i] else 0)):
                leaves_k = [_opt.fused_state_leaves(
                    tr._updaters[k].states[i], mps[i])[j]
                    for k in range(ndev)]
                self._scatter_state((i, j), leaves_k, st[j])
        return True

    def _take_state(self, slot, leaves_k, sharding):
        """Opt-state global for donation: zero-copy adoption of the
        per-device leaves when every pool owns its device's buffer, else a
        genuine copy of device-0's value (the writeback re-syncs all
        devices)."""
        datas = [leaf._data for leaf in leaves_k]
        if all(self._pools[k]._own.get(slot) is datas[k]
               for k in range(len(datas))):
            return _adopt(datas[0].shape, sharding, datas)
        self._pools[0].count_copy("mesh_fused", datas[0])
        return jax.device_put(jnp.array(datas[0]), sharding)

    def _scatter(self, handles, global_arr):
        """Write a replicated program output back as per-device arrays."""
        shards = {s.device.id: s.data for s in global_arr.addressable_shards}
        for k, h in enumerate(handles):
            h._data = shards[self._devids[k]]
        if _memwatch.enabled:
            _memwatch.tag("params", list(shards.values()))

    def _scatter_state(self, slot, leaves_k, global_arr):
        shards = {s.device.id: s.data for s in global_arr.addressable_shards}
        for k, leaf in enumerate(leaves_k):
            self._pools[k].give(slot, leaf, shards[self._devids[k]])
