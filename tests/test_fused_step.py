"""Fused whole-step training (MXNET_TPU_FUSED_STEP).

Parity contract: the fused donated-buffer program must produce the SAME
numbers as the eager per-param oracle — params AND optimizer state — for
every optimizer with a ``fused_update``, on one device and on a
multi-device local-kvstore module, across a force_rebind.  Plus the
mechanics: donation genuinely frees the old buffers, the env flag is part
of the jit-cache key, and ineligible setups (monitor attached) fall back
to eager without error.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt
from mxnet_tpu import telemetry
from mxnet_tpu import fused_step as fused


def _build_module(ctxs=None, batch=8):
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    out = mx.sym.SoftmaxOutput(fc2, label, name="softmax")
    mod = mx.mod.Module(out, data_names=("data",),
                        label_names=("softmax_label",),
                        context=ctxs or [mx.cpu()])
    mod.bind(data_shapes=[("data", (batch, 10))],
             label_shapes=[("softmax_label", (batch,))])
    mx.random.seed(42)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
    return mod


class _Batch:
    def __init__(self, x, y):
        self.data = [mx.nd.array(x)]
        self.label = [mx.nd.array(y)]


def _batch(i, batch=8):
    rs = np.random.RandomState(100 + i)
    return _Batch(rs.randn(batch, 10).astype(np.float32),
                  rs.randint(0, 4, (batch,)).astype(np.float32))


def _run(monkeypatch, flag, opt_name, opt_kwargs, steps=4, ctxs=None,
         rebind_at=None, rebind_batch=12):
    monkeypatch.setenv(fused.ENV_FLAG, flag)
    mod = _build_module(ctxs=ctxs)
    mod.init_optimizer(optimizer=opt_name,
                       optimizer_params=dict(opt_kwargs))
    batch = 8
    for i in range(steps):
        if rebind_at is not None and i == rebind_at:
            args, auxs = mod.get_params()
            mod.bind(data_shapes=[("data", (rebind_batch, 10))],
                     label_shapes=[("softmax_label", (rebind_batch,))],
                     force_rebind=True)
            mod.set_params(args, auxs)
            batch = rebind_batch
        mod.forward_backward(_batch(i, batch))
        mod.update()
    args, _ = mod.get_params()
    states = {}
    if mod._updater is not None:
        for slot, st in mod._updater.states.items():
            leaves = opt.fused_state_leaves(st)
            states[slot] = [] if leaves is None else \
                [s.asnumpy() for s in leaves]
    return args, states


def _assert_parity(f, e, rtol=2e-5, atol=1e-6):
    a_f, s_f = f
    a_e, s_e = e
    assert sorted(a_f) == sorted(a_e)
    for k in a_e:
        np.testing.assert_allclose(a_f[k].asnumpy(), a_e[k].asnumpy(),
                                   rtol=rtol, atol=atol, err_msg=k)
    assert sorted(s_f) == sorted(s_e)
    for slot in s_e:
        assert len(s_f[slot]) == len(s_e[slot]), "state arity %r" % slot
        for j, (x, y) in enumerate(zip(s_f[slot], s_e[slot])):
            np.testing.assert_allclose(x, y, rtol=rtol, atol=atol,
                                       err_msg="state %r[%d]" % (slot, j))


OPT_CONFIGS = [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("sgd", {"learning_rate": 0.05}),
    ("nag", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("rmsprop", {"learning_rate": 0.01, "centered": True}),
]


class TestParity:
    @pytest.mark.parametrize("name,kwargs", OPT_CONFIGS,
                             ids=[c[0] + ("_c" if c[1].get("centered")
                                          else ("_m" if c[1].get("momentum")
                                                else ""))
                                  for c in OPT_CONFIGS])
    def test_single_device(self, monkeypatch, name, kwargs):
        f = _run(monkeypatch, "1", name, kwargs)
        e = _run(monkeypatch, "0", name, kwargs)
        _assert_parity(f, e)

    @pytest.mark.parametrize("name,kwargs",
                             [("sgd", {"learning_rate": 0.05,
                                       "momentum": 0.9, "wd": 1e-4}),
                              ("adam", {"learning_rate": 0.01})])
    def test_multi_device_local_kvstore(self, monkeypatch, name, kwargs):
        ctxs = [mx.cpu(0), mx.cpu(1)]
        f = _run(monkeypatch, "1", name, kwargs, ctxs=ctxs)
        e = _run(monkeypatch, "0", name, kwargs, ctxs=ctxs)
        _assert_parity(f, e)

    def test_rebind_after_shape_change(self, monkeypatch):
        kwargs = {"learning_rate": 0.05, "momentum": 0.9}
        f = _run(monkeypatch, "1", "sgd", kwargs, steps=5, rebind_at=2)
        e = _run(monkeypatch, "0", "sgd", kwargs, steps=5, rebind_at=2)
        _assert_parity(f, e)


class TestDispatchMechanics:
    def test_one_program_per_step_and_counters(self, monkeypatch):
        monkeypatch.setenv(fused.ENV_FLAG, "1")
        telemetry.enable()
        try:
            fused0 = telemetry.value("step_dispatch_total", path="fused")
            eager0 = telemetry.value("step_dispatch_total", path="eager")
            mod = _build_module()
            mod.init_optimizer(
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
            for i in range(4):
                mod.forward_backward(_batch(i))
                mod.update()
            assert telemetry.value("step_dispatch_total",
                                   path="fused") == fused0 + 4
            assert telemetry.value("step_dispatch_total",
                                   path="eager") == eager0
            # exactly ONE compiled step program served all 4 steps
            ex = mod._exec_group.execs[0]
            step_keys = [k for k in ex._jitted if k[0] == "step"]
            assert len(step_keys) == 1
        finally:
            telemetry.disable()

    def test_env_flag_in_jit_cache_key(self, monkeypatch):
        # regression: MXNET_TPU_FUSED_STEP participates in the step-program
        # cache key via STEP_ENV_KEYS, so a flag flip cannot silently reuse
        # a stale compiled closure
        monkeypatch.setenv(fused.ENV_FLAG, "1")
        mod = _build_module()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05})
        mod.forward_backward(_batch(0))
        mod.update()
        ex = mod._exec_group.execs[0]
        keys1 = {k for k in ex._jitted if k[0] == "step"}
        assert keys1 and all(fused.ENV_FLAG in str(k) or len(k) > 1
                             for k in keys1)
        # a different truthy spelling is a different cache entry
        monkeypatch.setenv(fused.ENV_FLAG, "yes")
        mod.forward_backward(_batch(1))
        mod.update()
        keys2 = {k for k in ex._jitted if k[0] == "step"}
        assert len(keys2) == 2 and keys1 < keys2
        # and "0" disables: no third entry appears
        monkeypatch.setenv(fused.ENV_FLAG, "0")
        mod.forward_backward(_batch(2))
        mod.update()
        keys3 = {k for k in ex._jitted if k[0] == "step"}
        assert keys3 == keys2

    def test_donation_frees_old_buffers(self, monkeypatch):
        monkeypatch.setenv(fused.ENV_FLAG, "1")
        mod = _build_module()
        mod.init_optimizer(
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
        ex = mod._exec_group.execs[0]
        mod.forward_backward(_batch(0))
        mod.update()
        old = ex.arg_dict["fc1_weight"]._data
        mod.forward_backward(_batch(1))
        mod.update()
        # the donated input buffer was genuinely consumed by XLA, not
        # copied: the old jax array is dead
        assert old.is_deleted()
        # while the LIVE weight is readable and finite
        w = ex.arg_dict["fc1_weight"].asnumpy()
        assert np.isfinite(w).all()

    def test_monitor_falls_back_to_eager(self, monkeypatch):
        monkeypatch.setenv(fused.ENV_FLAG, "1")
        telemetry.enable()
        try:
            eager0 = telemetry.value("step_dispatch_total", path="eager")
            mod = _build_module()
            mod.init_optimizer(optimizer="sgd",
                               optimizer_params={"learning_rate": 0.05})
            mod.forward_backward(_batch(0))
            mod.update()
            # a monitor holds live references into the executor's buffers:
            # donation would free what it watches, so the step must fall
            # back to the eager oracle
            mod._exec_group.execs[0]._monitor = object()
            mod.forward_backward(_batch(1))
            mod.update()
            assert telemetry.value("step_dispatch_total",
                                   path="eager") == eager0 + 1
            w = mod._exec_group.execs[0].arg_dict["fc1_weight"].asnumpy()
            assert np.isfinite(w).all()
        finally:
            telemetry.disable()


class TestTrainerFused:
    def _run(self, monkeypatch, flag, steps=3):
        from mxnet_tpu import autograd
        from mxnet_tpu.gluon import nn, Trainer
        monkeypatch.setenv(fused.ENV_FLAG, flag)
        mx.random.seed(11)
        net = nn.Sequential()
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
        net.initialize(ctx=mx.cpu())
        tr = Trainer(net.collect_params(), "adam",
                     {"learning_rate": 0.01, "wd": 1e-4})
        for i in range(steps):
            rs = np.random.RandomState(i)
            x = mx.nd.array(rs.randn(8, 10).astype(np.float32))
            with autograd.record():
                y = net(x)
                loss = (y * y).sum()
            loss.backward()
            tr.step(8)
        return [p.data().asnumpy() for p in net.collect_params().values()]

    def test_parity(self, monkeypatch):
        f = self._run(monkeypatch, "1")
        e = self._run(monkeypatch, "0")
        assert len(f) == len(e)
        for i, (x, y) in enumerate(zip(f, e)):
            np.testing.assert_allclose(x, y, rtol=2e-5, atol=1e-6,
                                       err_msg="param %d" % i)


class TestResolver:
    """The shared (param, device) -> slot resolver: lr_mult/wd_mult must
    resolve identically for every replica of a param (the old per-call
    ``i*num_device+k`` reimplementations could disagree)."""

    def test_slot_index_math(self):
        assert opt.Optimizer.slot_index(0, 1, 0) == 0
        assert opt.Optimizer.slot_index(3, 1, 0) == 3
        assert opt.Optimizer.slot_index(0, 4, 2) == 2
        assert opt.Optimizer.slot_index(3, 4, 1) == 13

    def test_build_idx2name_covers_all_replicas(self):
        names = ["w", "b", "g"]
        idx2name = opt.Optimizer.build_idx2name(names, 2)
        assert len(idx2name) == 6
        for i, name in enumerate(names):
            for k in range(2):
                assert idx2name[opt.Optimizer.slot_index(i, 2, k)] == name

    def test_lr_wd_mult_equal_across_replicas(self):
        names = ["fc_weight", "fc_bias"]
        ndev = 3
        o = opt.create("sgd", learning_rate=0.1, wd=0.01,
                       param_idx2name=opt.Optimizer.build_idx2name(
                           names, ndev))
        o.set_lr_mult({"fc_weight": 2.0})
        o.set_wd_mult({"fc_bias": 0.0})
        for i, name in enumerate(names):
            slots = [opt.Optimizer.slot_index(i, ndev, k)
                     for k in range(ndev)]
            lrs = {o._get_lr(s) for s in slots}
            wds = {o._get_wd(s) for s in slots}
            assert len(lrs) == 1, name
            assert len(wds) == 1, name
        assert o._get_lr(opt.Optimizer.slot_index(0, ndev, 1)) == \
            pytest.approx(0.2)
        assert o._get_wd(opt.Optimizer.slot_index(1, ndev, 2)) == 0.0


# ---------------------------------------------------------------------------
# the step timeline: spans where the work happens, donation copies counted
# ---------------------------------------------------------------------------
PHASES = ["Step::validate", "Step::feed", "Step::slots", "Step::gather",
          "Step::program", "Step::launch", "Step::writeback"]


def timeline_of_one_step(mod, batch):
    """The ``step`` records one staged + fused step leaves in the ring,
    in the order they began."""
    from mxnet_tpu import tracing
    tracing.flight.clear()
    mod.forward_backward(batch)
    mod.update()
    records, wrapped = tracing.flight.records()
    assert not wrapped
    return sorted((r for r in records if r.cat == "step"),
                  key=lambda r: r.begin_s)


def assert_one_timeline(recs, path):
    """Each span of the table exactly once, in order, the phases inside
    ``Step::update`` and no longer than it together."""
    assert [r.name for r in recs] == ["Step::stage", "Step::update"] + PHASES
    stage, update = recs[0], recs[1]
    assert stage.end_s <= update.begin_s
    assert update.args["path"] == path and update.args["step"] >= 1
    phases = recs[2:]
    for a, b in zip(phases, phases[1:]):
        assert a.end_s <= b.begin_s
    assert update.begin_s <= phases[0].begin_s
    assert phases[-1].end_s <= update.end_s
    assert sum(r.end_s - r.begin_s for r in phases) <= \
        update.end_s - update.begin_s
    by = {r.name: r for r in recs}
    assert by["Step::slots"].args["params"] > 0
    assert by["Step::launch"].args["first_run"] == \
        by["Step::program"].args["first_run"]
    return by


class TestStepTimeline:
    def _module(self, monkeypatch, ctxs=None):
        monkeypatch.setenv(fused.ENV_FLAG, "1")
        mod = _build_module(ctxs=ctxs)
        mod.init_optimizer(
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
        return mod

    @pytest.mark.parametrize(
        "ctxs,path", [(None, "fused"),
                      ([mx.cpu(i) for i in range(4)], "mesh_fused")],
        ids=["one_device", "mesh"])
    def test_each_span_once_in_order(self, monkeypatch, ctxs, path):
        """The one step body leaves the same spans, once each and in the
        same order, on one device and on a mesh."""
        mod = self._module(monkeypatch, ctxs)
        first = assert_one_timeline(
            timeline_of_one_step(mod, _batch(0)), path)
        assert first["Step::launch"].args["first_run"] is True
        assert ("mesh" in first["Step::launch"].args) == (ctxs is not None)
        second = assert_one_timeline(
            timeline_of_one_step(mod, _batch(1)), path)
        assert second["Step::launch"].args["first_run"] is False
        assert second["Step::update"].args["step"] == 2
        assert second["Step::gather"].args["copies"] == 0

    def test_donation_copies_first_step_and_after_set_params(
            self, monkeypatch):
        telemetry.enable()
        try:
            n0 = telemetry.value("donation_copies_total", path="fused")
            b0 = telemetry.value("donation_copy_bytes_total", path="fused")
            mod = self._module(monkeypatch)
            def gather_args(i):
                recs = timeline_of_one_step(mod, _batch(i))
                return [r for r in recs if r.name == "Step::gather"][0].args

            g = gather_args(0)
            # 4 weights, each with its momentum: every leaf is copied once
            assert g["leaves"] == 8 and g["copies"] == 8
            assert g["copy_bytes"] == 2 * 4 * (16 * 10 + 16 + 4 * 16 + 4)
            assert telemetry.value("donation_copies_total",
                                   path="fused") == n0 + 8
            assert telemetry.value("donation_copy_bytes_total",
                                   path="fused") == b0 + g["copy_bytes"]
            g = gather_args(1)
            assert g["leaves"] == 8 and g["copies"] == 0 \
                and g["copy_bytes"] == 0
            # handles written from outside are not the pool's any more
            args, auxs = mod.get_params()
            mod.set_params(args, auxs)
            g = gather_args(2)
            assert 0 < g["copies"] <= 8
            assert telemetry.value("donation_copies_total",
                                   path="fused") == n0 + 8 + g["copies"]
        finally:
            telemetry.disable()

    @pytest.mark.parametrize(
        "ctxs", [None, [mx.cpu(i) for i in range(4)]],
        ids=["one_device", "mesh"])
    def test_first_step_holds_the_state_once(self, monkeypatch, ctxs):
        """A handle the pool does not own is copied, and the copy takes the
        handle at once: when the first fused program is launched no
        pre-step buffer of a weight or of the optimizer's state is alive
        beside its copy, and what is alive is the state once plus a leaf."""
        import gc
        import weakref
        import jax
        # one eager step first: the optimizer's state then exists, made
        # outside the pool, as a checkpoint's set_states would leave it
        monkeypatch.setenv(fused.ENV_FLAG, "0")
        mod = _build_module(ctxs=ctxs)
        mod.init_optimizer(
            optimizer="adam", optimizer_params={"learning_rate": 0.01})
        mod.forward_backward(_batch(0))
        mod.update()
        monkeypatch.setenv(fused.ENV_FLAG, "1")
        execs = mod._exec_group.execs
        names = [n for n in mod._param_names]
        leaves = [e.arg_dict[n] for e in execs for n in names]
        leaves += [leaf for st in mod._updater.states.values()
                   for leaf in st]
        before = [weakref.ref(h._data) for h in leaves]
        largest = max(h._data.nbytes for h in leaves)
        seen = {}
        program = type(execs[0]).step_program

        def watched(ex, *a, **kw):
            fn = program(ex, *a, **kw)

            def launch(pvals, svals, *rest):
                gc.collect()
                seen["alive"] = sum(r() is not None for r in before)
                seen["donated"] = sum(
                    v.nbytes for v in list(pvals)
                    + [leaf for sv in svals for leaf in sv])
                seen["live"] = sum(a.nbytes for a in jax.live_arrays())
                return fn(pvals, svals, *rest)
            return launch

        monkeypatch.setattr(type(execs[0]), "step_program", watched)
        del leaves
        gc.collect()
        floor = sum(a.nbytes for a in jax.live_arrays())
        mod.forward_backward(_batch(1))
        mod.update()
        assert seen["alive"] == 0, "%d pre-step buffers outlived their " \
            "copies" % seen["alive"]
        # what was alive before, with every leaf once (a mesh lays the
        # per-device copies together: fewer bytes, never more) and the
        # batch: not the donated leaves a second time
        assert seen["live"] <= floor + largest + 4096, (seen, floor)
        assert seen["donated"] > 4 * largest    # the bound says something
        for e in execs:
            assert np.isfinite(e.arg_dict["fc1_weight"].asnumpy()).all()

    def test_caller_held_alias_survives_the_first_step(self, monkeypatch):
        """The buffer a caller still holds is the one thing the copy is
        for: it keeps its values while the step donates the copy."""
        mod = self._module(monkeypatch)
        ex = mod._exec_group.execs[0]
        alias = ex.arg_dict["fc1_weight"]._data
        want = np.asarray(alias).copy()
        for i in range(2):
            mod.forward_backward(_batch(i))
            mod.update()
        assert not alias.is_deleted()
        np.testing.assert_array_equal(np.asarray(alias), want)
        assert not np.array_equal(ex.arg_dict["fc1_weight"].asnumpy(), want)

    def test_recorder_off_records_nothing_and_trains_alike(
            self, monkeypatch):
        from mxnet_tpu import profiler, tracing
        monkeypatch.setenv("MXNET_FLIGHT_RECORDER", "0")
        assert tracing.FlightRecorder().enabled is False

        def three_steps():
            mod = self._module(monkeypatch)
            for i in range(3):
                mod.forward_backward(_batch(i))
                mod.update()
            return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

        on = three_steps()
        assert len(tracing.flight) > 0
        monkeypatch.setattr(tracing.flight, "enabled", False)
        tracing.flight.clear()
        assert not profiler.is_running()
        with profiler._lock:
            events0 = len(profiler._events)
        off = three_steps()
        assert len(tracing.flight) == 0
        with profiler._lock:
            assert len(profiler._events) == events0
        for k in on:
            assert np.array_equal(on[k], off[k]), k
