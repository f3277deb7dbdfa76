"""Mesh-native GSPMD fused training step (MXNET_TPU_MESH_STEP).

Parity contract: the mesh-fused global program — batch sharded ``P('dp')``,
params/opt-state placed per NamedSharding, all donated — must produce the
SAME numbers as the single-device fused step.  On the CPU harness (8
virtual devices via conftest's ``--xla_force_host_platform_device_count``)
we assert BIT-exactness, params AND optimizer state: the test data/weights
are integer-valued and every hyperparameter is dyadic, so each f32
intermediate is exactly representable and any reduction reordering the
mesh could introduce would show up as a 1-ulp diff.  ``nag``'s update
algebra is not reassociation-stable, so it (and adam/rmsprop, which divide)
get allclose instead.

Plus the mechanics: donation genuinely frees the previous mesh buffers,
the mesh signature participates in the step-program jit-cache key, DP×TP
``ShardingRules`` actually shard the parameter handles, the telemetry
counter says ``mesh_fused``, and the flag-off / mesh→eager interop paths
fall back seamlessly.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fused_step as fused
from mxnet_tpu import telemetry
from mxnet_tpu import optimizer as opt
from mxnet_tpu.optimizer import fused_state_leaves

NDEV = 8
CTX8 = [mx.cpu(i) for i in range(NDEV)]


class _Batch:
    def __init__(self, x, y):
        self.data = [mx.nd.array(x)]
        self.label = [mx.nd.array(y)]


def _build_module(ctxs, batch=8, feat=4, hid=4, out=2):
    """Tiny FC regression net in the exact-f32 regime: weights drawn from
    {-1, 0, 1} so every product/sum stays integer-valued for a few steps."""
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=hid, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=out, name="fc2")
    label = mx.sym.Variable("softmax_label")
    net = mx.sym.LinearRegressionOutput(fc2, label, name="lin")
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",), context=ctxs)
    mod.bind(data_shapes=[("data", (batch, feat))],
             label_shapes=[("softmax_label", (batch, out))])
    mod.init_params()
    rs = np.random.RandomState(42)
    args = {n: mx.nd.array(rs.randint(-1, 2, v.shape).astype(np.float32))
            for n, v in mod.get_params()[0].items()}
    mod.set_params(args, {})
    return mod


def _collect(mod):
    """(params, states-by-name) snapshots; the mesh path keeps sibling
    slots aliased to the base slot, so mapping through idx2name collapses
    both layouts to one comparable dict."""
    args = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    states = {}
    idx2name = mod._optimizer.idx2name
    for slot, st in sorted(mod._updater.states.items()):
        name = idx2name.get(slot)
        leaves = fused_state_leaves(st)
        if name and name not in states and leaves:
            states[name] = [np.asarray(l.asnumpy()) for l in leaves]
    return args, states


def _run(monkeypatch, ctxs, opt_name, okw, steps, mesh_flag="1",
         batch=8, feat=4, out=2, mesh_axes=None, rules_fn=None):
    monkeypatch.setenv(fused.ENV_FLAG, "1")
    monkeypatch.setenv(fused.MESH_ENV_FLAG, mesh_flag)
    mod = _build_module(ctxs, batch=batch, feat=feat, out=out)
    if mesh_axes is not None:
        rules = rules_fn(mod) if rules_fn is not None else None
        mod.set_mesh(mesh_axes, rules)
    okw = dict(okw)
    okw.setdefault("rescale_grad", 0.125)
    mod.init_optimizer(kvstore="local", optimizer=opt_name,
                       optimizer_params=okw)
    rs = np.random.RandomState(7)
    for _ in range(steps):
        x = rs.randint(0, 2, (batch, feat)).astype(np.float32)
        y = rs.randint(-1, 2, (batch, out)).astype(np.float32)
        mod.forward_backward(_Batch(x, y))
        mod.update()
    return mod


def _assert_bitexact(mod8, mod1):
    a8, s8 = _collect(mod8)
    a1, s1 = _collect(mod1)
    assert sorted(a8) == sorted(a1)
    for k in a1:
        assert np.array_equal(a8[k], a1[k]), \
            "param %s: maxdiff %g" % (k, np.abs(a8[k] - a1[k]).max())
    assert sorted(s8) == sorted(s1)
    for k in s1:
        assert len(s8[k]) == len(s1[k]), "state arity %s" % k
        for j, (x, y) in enumerate(zip(s8[k], s1[k])):
            assert np.array_equal(x, y), \
                "state %s[%d]: maxdiff %g" % (k, j, np.abs(x - y).max())


def _assert_close(mod8, mod1, rtol=2e-5, atol=1e-6):
    a8, s8 = _collect(mod8)
    a1, s1 = _collect(mod1)
    for k in a1:
        np.testing.assert_allclose(a8[k], a1[k], rtol=rtol, atol=atol,
                                   err_msg=k)
    for k in s1:
        for j, (x, y) in enumerate(zip(s8[k], s1[k])):
            np.testing.assert_allclose(x, y, rtol=rtol, atol=atol,
                                       err_msg="state %s[%d]" % (k, j))


# configs whose trajectories stay exactly representable in f32 for the
# step counts used (dyadic lr/momentum/wd, integer data/weights)
EXACT_CONFIGS = [
    ("sgd", {"learning_rate": 0.5, "momentum": 0.5}, 3),
    ("sgd", {"learning_rate": 0.25}, 2),
]
EXACT_CONFIGS_SLOW = [
    ("sgd", {"learning_rate": 0.25, "momentum": 0.5}, 2),
    ("sgd", {"learning_rate": 0.25, "momentum": 0.5, "wd": 0.25}, 2),
]
CLOSE_CONFIGS_SLOW = [
    ("nag", {"learning_rate": 0.25, "momentum": 0.5}, 3),
    ("adam", {"learning_rate": 0.01}, 3),
    ("rmsprop", {"learning_rate": 0.01}, 3),
]


class TestMeshParity:
    @pytest.mark.parametrize("name,kwargs,steps", EXACT_CONFIGS,
                             ids=["sgd_mom", "sgd"])
    def test_bitexact_vs_single_device(self, monkeypatch, name, kwargs,
                                       steps):
        telemetry.enable()
        try:
            mesh0 = telemetry.value("step_dispatch_total", path="mesh_fused")
            mod8 = _run(monkeypatch, CTX8, name, kwargs, steps)
            assert telemetry.value("step_dispatch_total",
                                   path="mesh_fused") == mesh0 + steps
        finally:
            telemetry.disable()
        mod1 = _run(monkeypatch, [mx.cpu(0)], name, kwargs, steps)
        _assert_bitexact(mod8, mod1)

    @pytest.mark.slow
    @pytest.mark.parametrize("name,kwargs,steps", EXACT_CONFIGS_SLOW,
                             ids=["sgd_mom_lr25", "sgd_mom_wd"])
    def test_bitexact_sweep(self, monkeypatch, name, kwargs, steps):
        mod8 = _run(monkeypatch, CTX8, name, kwargs, steps)
        mod1 = _run(monkeypatch, [mx.cpu(0)], name, kwargs, steps)
        _assert_bitexact(mod8, mod1)

    @pytest.mark.slow
    @pytest.mark.parametrize("name,kwargs,steps", CLOSE_CONFIGS_SLOW,
                             ids=["nag", "adam", "rmsprop"])
    def test_allclose_sweep(self, monkeypatch, name, kwargs, steps):
        mod8 = _run(monkeypatch, CTX8, name, kwargs, steps)
        mod1 = _run(monkeypatch, [mx.cpu(0)], name, kwargs, steps)
        _assert_close(mod8, mod1)


class TestMeshMechanics:
    def test_donation_frees_old_buffers(self, monkeypatch):
        mod = _run(monkeypatch, CTX8, "sgd",
                   {"learning_rate": 0.25, "momentum": 0.5}, steps=1)
        ex = mod._exec_group.execs[0]
        old_w = ex.arg_dict["fc1_weight"]._data
        base = mod._optimizer.slot_index(
            mod._param_names.index("fc1_weight"), NDEV, 0)
        old_s = fused_state_leaves(mod._updater.states[base])[0]._data
        rs = np.random.RandomState(9)
        mod.forward_backward(_Batch(
            rs.randint(0, 2, (8, 4)).astype(np.float32),
            rs.randint(-1, 2, (8, 2)).astype(np.float32)))
        mod.update()
        # the second mesh step donated the first step's outputs: both the
        # param and the opt-state buffer are genuinely dead, not copied
        assert old_w.is_deleted()
        assert old_s.is_deleted()
        assert np.isfinite(ex.arg_dict["fc1_weight"].asnumpy()).all()

    def test_flag_off_falls_back_to_fused(self, monkeypatch):
        telemetry.enable()
        try:
            mesh0 = telemetry.value("step_dispatch_total", path="mesh_fused")
            fused0 = telemetry.value("step_dispatch_total", path="fused")
            _run(monkeypatch, CTX8, "sgd", {"learning_rate": 0.25},
                 steps=2, mesh_flag="0")
            assert telemetry.value("step_dispatch_total",
                                   path="mesh_fused") == mesh0
            assert telemetry.value("step_dispatch_total",
                                   path="fused") == fused0 + 2
        finally:
            telemetry.disable()

    def test_mesh_then_eager_interop_bitexact(self, monkeypatch):
        """One mesh step, then (flag flipped off) one per-device step: the
        de-mesh restores per-device layout exactly — the combined
        trajectory matches two single-device fused steps bit-for-bit."""
        mod8 = _run(monkeypatch, CTX8, "sgd",
                    {"learning_rate": 0.25, "momentum": 0.5}, steps=1)
        monkeypatch.setenv(fused.MESH_ENV_FLAG, "0")
        rs = np.random.RandomState(7)
        rs.randint(0, 2, (8, 4)), rs.randint(-1, 2, (8, 2))  # step-1 draws
        x = rs.randint(0, 2, (8, 4)).astype(np.float32)
        y = rs.randint(-1, 2, (8, 2)).astype(np.float32)
        mod8.forward_backward(_Batch(x, y))
        mod8.update()
        mod1 = _run(monkeypatch, [mx.cpu(0)], "sgd",
                    {"learning_rate": 0.25, "momentum": 0.5}, steps=2)
        _assert_bitexact(mod8, mod1)

    def test_outputs_served_from_mesh_step(self, monkeypatch):
        mod = _run(monkeypatch, CTX8, "sgd", {"learning_rate": 0.25},
                   steps=1)
        outs = mod.get_outputs()
        assert len(outs) == 1 and outs[0].shape == (8, 2)
        assert np.isfinite(outs[0].asnumpy()).all()

    def test_mesh_change_is_new_cache_key(self, monkeypatch):
        from mxnet_tpu.parallel.mesh import make_mesh, megatron_rules
        mod = _run(monkeypatch, CTX8, "sgd", {"learning_rate": 0.25},
                   steps=1)
        ex = mod._exec_group.execs[0]
        keys1 = {k for k in ex._jitted if k[0] == "step"}
        assert len(keys1) == 1
        devices = [c.jax_device for c in CTX8]
        mesh = make_mesh({"dp": 4, "tp": 2}, devices=devices)
        mod.set_mesh({"dp": 4, "tp": 2}, megatron_rules(mesh))
        rs = np.random.RandomState(9)
        mod.forward_backward(_Batch(
            rs.randint(0, 2, (8, 4)).astype(np.float32),
            rs.randint(-1, 2, (8, 2)).astype(np.float32)))
        mod.update()
        # regression: a different mesh/sharding signature must be a NEW
        # compiled step program, never a silent reuse of the dp=8 closure
        keys2 = {k for k in ex._jitted if k[0] == "step"}
        assert len(keys2) == 2 and keys1 < keys2


class TestDpTp:
    def test_megatron_rules_shard_params(self, monkeypatch):
        from mxnet_tpu.parallel.mesh import make_mesh, megatron_rules
        from jax.sharding import PartitionSpec as P

        def rules(mod):
            devices = [c.jax_device for c in CTX8]
            return megatron_rules(make_mesh({"dp": 4, "tp": 2},
                                            devices=devices))

        telemetry.enable()
        try:
            mesh0 = telemetry.value("step_dispatch_total", path="mesh_fused")
            mod = _run(monkeypatch, CTX8, "sgd",
                       {"learning_rate": 0.25, "momentum": 0.5}, steps=2,
                       mesh_axes={"dp": 4, "tp": 2}, rules_fn=rules)
            assert telemetry.value("step_dispatch_total",
                                   path="mesh_fused") == mesh0 + 2
        finally:
            telemetry.disable()
        ex = mod._exec_group.execs[0]
        # fc weights really live sharded on tp; biases replicated
        assert ex.arg_dict["fc1_weight"]._data.sharding.spec == P("tp", None)
        assert ex.arg_dict["fc1_bias"]._data.sharding.spec == P()
        # and the DP×TP trajectory still matches the single-device oracle
        mod1 = _run(monkeypatch, [mx.cpu(0)], "sgd",
                    {"learning_rate": 0.25, "momentum": 0.5}, steps=2)
        _assert_bitexact(mod, mod1)


class TestTrainerMesh:
    def _run(self, monkeypatch, ctxs, steps=3):
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        monkeypatch.setenv(fused.ENV_FLAG, "1")
        monkeypatch.setenv(fused.MESH_ENV_FLAG, "1")
        mx.random.seed(3)
        np.random.seed(3)
        net = nn.Sequential()
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(4))
        net.initialize(mx.init.Xavier(), ctx=ctxs)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9},
                           kvstore="device")
        rs = np.random.RandomState(11)
        n = len(ctxs)
        for _ in range(steps):
            x = rs.uniform(-1, 1, (16, 10)).astype(np.float32)
            b = 16 // n
            xs = [mx.nd.array(x[k * b:(k + 1) * b], ctx=ctxs[k])
                  for k in range(n)]
            losses = []
            with autograd.record():
                for xk in xs:
                    out = net(xk)
                    losses.append((out * out).sum())
            for l in losses:
                l.backward()
            tr.step(16)
        return [p.list_data()[0].asnumpy()
                for _, p in sorted(net.collect_params().items())]

    def test_parity_and_dispatch(self, monkeypatch):
        telemetry.enable()
        try:
            mesh0 = telemetry.value("step_dispatch_total", path="mesh_fused")
            p8 = self._run(monkeypatch, CTX8)
            assert telemetry.value("step_dispatch_total",
                                   path="mesh_fused") == mesh0 + 3
        finally:
            telemetry.disable()
        p1 = self._run(monkeypatch, [mx.cpu(0)])
        assert len(p8) == len(p1)
        for i, (a, b) in enumerate(zip(p8, p1)):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                       err_msg="param %d" % i)


class TestIoSharding:
    def test_ndarrayiter_num_parts(self):
        x = np.arange(24, dtype=np.float32).reshape(12, 2)
        y = np.arange(12, dtype=np.float32)
        parts = []
        for r in range(3):
            it = mx.io.NDArrayIter(x, y, batch_size=2, num_parts=3,
                                   part_index=r)
            assert it.num_data == 4
            rows = np.concatenate([b.data[0].asnumpy()
                                   for b in it], axis=0)
            parts.append(rows)
        np.testing.assert_array_equal(np.concatenate(parts, axis=0), x)

    def test_ndarrayiter_part_index_validated(self):
        x = np.zeros((8, 2), dtype=np.float32)
        with pytest.raises(mx.base.MXNetError):
            mx.io.NDArrayIter(x, batch_size=2, num_parts=2, part_index=2)

    def test_prefetching_iter_places_on_sharding(self):
        from mxnet_tpu.parallel.mesh import make_mesh, data_parallel_sharding
        mesh = make_mesh({"dp": NDEV},
                         devices=[c.jax_device for c in CTX8])
        bsh = data_parallel_sharding(mesh)
        x = np.arange(32, dtype=np.float32).reshape(16, 2)
        base = mx.io.NDArrayIter(x, np.zeros(16, np.float32), batch_size=8)
        it = mx.io.PrefetchingIter(base, sharding=bsh)
        batch = next(it)
        # the producer thread landed the batch pre-sharded on the mesh
        assert batch.data[0]._data.sharding == bsh
        np.testing.assert_array_equal(batch.data[0].asnumpy(), x[:8])
        for _ in it:   # drain so the daemon producer exits cleanly
            pass

    def test_host_shard_hint_single_host(self):
        from mxnet_tpu.parallel.mesh import host_shard_hint
        assert host_shard_hint() == (0, 1)

    def test_dp_trainer_caches_batch_sharding(self):
        from mxnet_tpu.parallel.mesh import make_mesh
        from mxnet_tpu.parallel.data_parallel import DataParallelTrainer
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = make_mesh({"dp": NDEV},
                         devices=[c.jax_device for c in CTX8])
        data = mx.sym.var("data")
        fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc1")
        net = mx.sym.SoftmaxOutput(fc, mx.sym.var("softmax_label"),
                                   name="softmax")
        tr = DataParallelTrainer(net, mesh, lr=0.1,
                                 data_names=("data",),
                                 label_names=("softmax_label",))
        assert tr._batch_sharding == NamedSharding(mesh, P("dp"))
        tr.init_params(data=(16, 6))
        rs = np.random.RandomState(0)
        x = mx.nd.array(rs.uniform(size=(16, 6)).astype(np.float32))
        y = mx.nd.array(rs.randint(0, 4, (16,)).astype(np.float32))
        loss = tr.step({"data": x, "softmax_label": y})
        assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# the step timeline on the mesh path (4 virtual devices, as the four-chip
# cell of the benchmark binds them)
# ---------------------------------------------------------------------------
class TestMeshStepTimeline:
    def _step(self, mod, seed):
        """The ``step`` records one more mesh step leaves in the ring."""
        from test_fused_step import timeline_of_one_step
        rs = np.random.RandomState(seed)
        return timeline_of_one_step(mod, _Batch(
            rs.randint(0, 2, (8, 4)).astype(np.float32),
            rs.randint(-1, 2, (8, 2)).astype(np.float32)))

    def test_each_span_once_in_order_and_copies(self, monkeypatch):
        from test_fused_step import assert_one_timeline
        telemetry.enable()
        try:
            n0 = telemetry.value("donation_copies_total", path="mesh_fused")
            mod = _run(monkeypatch, CTX8[:4], "sgd",
                       {"learning_rate": 0.25, "momentum": 0.5}, steps=0)
            by = assert_one_timeline(self._step(mod, 1), "mesh_fused")
            assert by["Step::launch"].args["first_run"] is True
            assert by["Step::launch"].args["mesh"] == "{'dp': 4}"
            # 4 weights and their momenta: each placed onto the mesh once
            g = by["Step::gather"].args
            assert g["leaves"] == 8 and g["copies"] == 8
            assert g["copy_bytes"] == 2 * 4 * (4 * 4 + 4 + 2 * 4 + 2)
            by = assert_one_timeline(self._step(mod, 2), "mesh_fused")
            assert by["Step::launch"].args["first_run"] is False
            g = by["Step::gather"].args
            assert g["leaves"] == 8 and g["copies"] == 0
            assert telemetry.value("donation_copies_total",
                                   path="mesh_fused") == n0 + 8
            # set_params re-points the handles: the pool copies again
            args, auxs = mod.get_params()
            mod.set_params(args, auxs)
            by = assert_one_timeline(self._step(mod, 3), "mesh_fused")
            assert by["Step::gather"].args["copies"] > 0
        finally:
            telemetry.disable()
