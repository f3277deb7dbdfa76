"""Mesh-native GSPMD fused training step (N contexts under Module).

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
counter says ``mesh_fused``, N contexts that cannot host a mesh take the
eager oracle, and the mesh→eager interop paths fall back seamlessly.
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


def _run(monkeypatch, ctxs, opt_name, okw, steps, fused_flag="1",
         batch=8, feat=4, out=2, mesh_axes=None, rules_fn=None):
    monkeypatch.setenv(fused.ENV_FLAG, fused_flag)
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

    @pytest.mark.parametrize(
        "ctxs,sizes", [(CTX8[:3], [3, 3, 2]),
                       ([mx.cpu(0), mx.cpu(0)], [4, 4])],
        ids=["ragged_slices", "duplicate_devices"])
    def test_no_mesh_over_the_contexts_takes_the_eager_oracle(
            self, monkeypatch, ctxs, sizes):
        """N contexts under a local kvstore that cannot host a mesh (8
        rows in ragged slices; one device bound twice): every step is
        dispatched ``eager``, and the Module ends where it ends under
        ``MXNET_TPU_FUSED_STEP=0``, bit for bit."""
        okw = {"learning_rate": 0.25, "momentum": 0.5}
        telemetry.enable()
        try:
            before = {p: telemetry.value("step_dispatch_total", path=p)
                      for p in ("mesh_fused", "fused", "eager")}
            mod = _run(monkeypatch, ctxs, "sgd", okw, steps=2)
            after = {p: telemetry.value("step_dispatch_total", path=p)
                     for p in before}
        finally:
            telemetry.disable()
        assert [s.stop - s.start for s in mod._exec_group.slices] == sizes
        assert not mod._fused_step.eligible()
        assert after == dict(before, eager=before["eager"] + 2)
        oracle = _run(monkeypatch, ctxs, "sgd", okw, steps=2,
                      fused_flag="0")
        _assert_bitexact(mod, oracle)

    def test_mesh_then_eager_interop_bitexact(self, monkeypatch):
        """One mesh step, then (the fused step switched off) one eager
        per-device step: the de-mesh restores per-device layout exactly —
        the combined trajectory matches two single-device fused steps
        bit-for-bit."""
        mod8 = _run(monkeypatch, CTX8, "sgd",
                    {"learning_rate": 0.25, "momentum": 0.5}, steps=1)
        monkeypatch.setenv(fused.ENV_FLAG, "0")
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


# ---------------------------------------------------------------------------
# the optimizer state's own layout: split over ``dp`` (4 virtual devices)
# ---------------------------------------------------------------------------
CTX4 = CTX8[:4]
SGD_MOM = ("sgd", {"learning_rate": 0.5, "momentum": 0.5})


@pytest.fixture
def split_small(monkeypatch):
    """The test net's two matrices (16 and 8 elements) count as large
    leaves; its biases (4 and 2) stay small."""
    from mxnet_tpu.parallel import mesh as pmesh
    monkeypatch.setattr(pmesh, "STATE_SHARD_MIN_ELEMENTS", 8)


def _leaves_by_name(mod):
    """{param: its state's leaves, as the fused step lays them (the master
    first for a low-precision weight)} of the base slots."""
    ndev = len(mod._context)
    ex = mod._exec_group.execs[0]
    out = {}
    for i, name in enumerate(mod._param_names):
        st = mod._updater.states[mod._optimizer.slot_index(i, ndev, 0)]
        out[name] = fused_state_leaves(
            st, mod._optimizer.fused_mp(ex.arg_dict[name]))
    return out


class TestStateShardingRule:
    @pytest.mark.parametrize("axes,spec,shape,want", [
        # replicated parameter: the largest axis that divides by dp
        ({"dp": 4}, (), (4096, 1024), ("dp",)),
        ({"dp": 4}, (), (1024, 4096), (None, "dp")),
        ({"dp": 4}, (), (1024, 1024), ("dp",)),
        ({"dp": 4}, (), (512, 512, 3, 3), ("dp",)),
        # GPT-2's tables: 50,257 rows do not divide by 4
        ({"dp": 4}, (), (50257, 1024), (None, "dp")),
        # no axis divides, too small, a bias: the parameter's own
        ({"dp": 4}, (), (50257, 1023), ()),
        ({"dp": 4}, (), (128, 128), ()),
        ({"dp": 4}, (), (4096,), ()),
        ({"dp": 4}, (), (1 << 16,), ("dp",)),
        # under tp the state's axis is one the rule left free
        ({"dp": 4, "tp": 2}, ("tp", None), (4096, 1024), ("tp", "dp")),
        ({"dp": 4, "tp": 2}, (None, "tp"), (1024, 4096), ("dp", "tp")),
        ({"dp": 4, "tp": 2}, (None, "tp"), (50257, 1024), (None, "tp")),
        ({"dp": 2, "tp": 2}, ("tp", None, None, None), (512, 256, 3, 3),
         ("tp", "dp")),
        # a spec that already names dp, and a dp axis of one
        ({"dp": 4, "tp": 2}, ("dp", None), (4096, 1024), ("dp", None)),
        ({"dp": 1, "tp": 8}, ("tp", None), (4096, 1024), ("tp", None)),
    ])
    def test_spec(self, axes, spec, shape, want):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from mxnet_tpu.parallel.mesh import make_mesh, state_sharding
        n = int(np.prod(list(axes.values())))
        mesh = make_mesh(axes, devices=[c.jax_device for c in CTX8[:n]])
        own = NamedSharding(mesh, P(*spec))
        got = state_sharding(own, shape)
        assert got.spec == P(*want) and got.mesh is mesh
        if tuple(want) == tuple(spec):
            assert got is own       # the step tells a split leaf by this


class TestStateSplitOverDp:
    def _assert_layout(self, mod, large, small, ndev=4):
        """Between steps a split leaf's weight is held in its state's
        layout (a part a device, as every state leaf), every other leaf's
        in the parameter's own."""
        from jax.sharding import PartitionSpec as P
        ex = mod._exec_group.execs[0]
        leaves = _leaves_by_name(mod)
        for name in large:
            w = ex.arg_dict[name]._data
            for a in [w] + [leaf._data for leaf in leaves[name]]:
                assert "dp" in a.sharding.spec, (name, a.sharding.spec)
                assert a.sharding == w.sharding, name
                for s in a.addressable_shards:
                    assert s.data.size * ndev == a.size
        for name in small:
            w = ex.arg_dict[name]._data
            assert w.sharding.spec == P(), name      # the parameter's own
            assert len(w.addressable_shards) == ndev
            for leaf in leaves[name]:
                assert leaf._data.sharding.spec == P(), name
                assert leaf._data.addressable_shards[0].data.size == \
                    leaf._data.size

    @pytest.mark.parametrize("name,kwargs,steps",
                             [SGD_MOM + (3,),
                              ("sgd", {"learning_rate": 0.25}, 2)],
                             ids=["sgd_mom", "sgd"])
    def test_bitexact_vs_single_device(self, monkeypatch, split_small,
                                       name, kwargs, steps):
        mod4 = _run(monkeypatch, CTX4, name, kwargs, steps)
        if "momentum" in kwargs:
            self._assert_layout(mod4, ["fc1_weight", "fc2_weight"],
                                ["fc1_bias", "fc2_bias"])
        mod1 = _run(monkeypatch, [mx.cpu(0)], name, kwargs, steps)
        _assert_bitexact(mod4, mod1)

    def test_sgd_momentum_layout_and_replicated_layouts_numbers(
            self, monkeypatch):
        """A float32 weight without a master: the momentum is split, the
        weight gathered in float32; the numbers are the replicated
        layout's (the same mesh with every leaf counted small)."""
        repl = _run(monkeypatch, CTX4, *SGD_MOM, 3)
        self._assert_layout(repl, [], list(repl._param_names))
        from mxnet_tpu.parallel import mesh as pmesh
        monkeypatch.setattr(pmesh, "STATE_SHARD_MIN_ELEMENTS", 8)
        split = _run(monkeypatch, CTX4, *SGD_MOM, 3)
        self._assert_layout(split, ["fc1_weight", "fc2_weight"],
                            ["fc1_bias", "fc2_bias"])
        _assert_bitexact(split, repl)

    def test_adam_with_masters_layout_and_replicated_layouts_numbers(
            self, monkeypatch):
        """bf16 weights under Adam: master, mean and variance split, the
        bf16 weight in the parameter's sharding.  Adam divides, so a last
        bit may follow the order of the exchange's sum: allclose."""
        from mxnet_tpu import amp
        import jax.numpy as jnp
        monkeypatch.setenv(amp.ENV_FLAG, "1")
        okw = {"learning_rate": 0.01, "multi_precision": True}
        repl = _run(monkeypatch, CTX4, "adam", okw, 3)
        from mxnet_tpu.parallel import mesh as pmesh
        monkeypatch.setattr(pmesh, "STATE_SHARD_MIN_ELEMENTS", 8)
        split = _run(monkeypatch, CTX4, "adam", okw, 3)
        self._assert_layout(split, ["fc1_weight", "fc2_weight"],
                            ["fc1_bias", "fc2_bias"])
        ex = split._exec_group.execs[0]
        assert ex.arg_dict["fc1_weight"]._data.dtype == jnp.bfloat16
        leaves = _leaves_by_name(split)["fc1_weight"]
        assert [l._data.dtype for l in leaves] == [jnp.float32] * 3
        _assert_close(split, repl, rtol=1e-2)       # the bf16 weights
        for name, got in _leaves_by_name(split).items():
            want = _leaves_by_name(repl)[name]
            for j, (a, b) in enumerate(zip(got, want)):
                np.testing.assert_allclose(
                    a.asnumpy(), b.asnumpy(), rtol=2e-5, atol=1e-6,
                    err_msg="%s[%d]" % (name, j))

    def test_demesh_then_eager_step_bitexact(self, monkeypatch,
                                             split_small):
        """Two steps on split state, then (the fused step switched off)
        one eager per-device step: ``_demesh`` makes whole per-device
        copies again."""
        mod4 = _run(monkeypatch, CTX4, *SGD_MOM, 2)
        monkeypatch.setenv(fused.ENV_FLAG, "0")
        rs = np.random.RandomState(7)
        for _ in range(2):          # the draws of steps 1 and 2
            rs.randint(0, 2, (8, 4)), rs.randint(-1, 2, (8, 2))
        x = rs.randint(0, 2, (8, 4)).astype(np.float32)
        y = rs.randint(-1, 2, (8, 2)).astype(np.float32)
        mod4.forward_backward(_Batch(x, y))
        mod4.update()
        ex = mod4._exec_group.execs
        for k in range(4):          # per-device again, whole
            w = ex[k].arg_dict["fc1_weight"]._data
            assert w.devices() == {CTX4[k].jax_device}
            st = mod4._updater.states[
                mod4._optimizer.slot_index(0, 4, k)]
            assert st._data.devices() == {CTX4[k].jax_device}
            assert st.shape == (4, 4)
        mod1 = _run(monkeypatch, [mx.cpu(0)], *SGD_MOM, 3)
        _assert_bitexact(mod4, mod1)

    def test_get_params_and_states_read_whole_arrays(self, monkeypatch,
                                                     split_small):
        mod4 = _run(monkeypatch, CTX4, *SGD_MOM, 2)
        mod1 = _run(monkeypatch, [mx.cpu(0)], *SGD_MOM, 2)
        # the pickled states without a de-mesh first: np.asarray of a
        # global array with four shards
        import pickle
        states = pickle.loads(mod4._updater.get_states())
        assert states[0].shape == (4, 4)
        np.testing.assert_array_equal(
            states[0], mod1._updater.states[0].asnumpy())
        args4, _ = mod4.get_params()
        args1, _ = mod1.get_params()
        for k in args1:
            np.testing.assert_array_equal(args4[k].asnumpy(),
                                          args1[k].asnumpy())

    def test_caller_held_state_survives_the_split_placement(
            self, monkeypatch, split_small):
        """A state the pool does not own (``set_states``) reaches the split
        layout as a copy (``take_sharded``'s ``jnp.array`` before the put),
        so what the caller holds is neither deleted by the step's donation
        nor changed."""
        import pickle
        mod = _run(monkeypatch, CTX4, *SGD_MOM, 1)
        mod._updater.set_states(pickle.loads(mod._updater.get_states()))
        held = {k: st._data for k, st in mod._updater.states.items()}
        before = {k: np.asarray(a) for k, a in held.items()}
        rs = np.random.RandomState(3)
        mod.forward_backward(_Batch(
            rs.randint(0, 2, (8, 4)).astype(np.float32),
            rs.randint(-1, 2, (8, 2)).astype(np.float32)))
        mod.update()
        self._assert_layout(mod, ["fc1_weight", "fc2_weight"],
                            ["fc1_bias", "fc2_bias"])
        for k, a in held.items():
            assert not a.is_deleted(), k
            np.testing.assert_array_equal(np.asarray(a), before[k])
        new = _leaves_by_name(mod)["fc1_weight"][0]._data
        assert not np.array_equal(np.asarray(new), before[0])

    def test_checkpoint_from_split_run_into_one_device_module(
            self, monkeypatch, split_small, tmp_path):
        """``save_checkpoint(save_optimizer_states=True)`` after split
        steps, loaded into a Module on one device, which then takes the
        third step to the numbers of three steps on one device.  (The
        updater's states are keyed ``param * ndev + device``, as the
        reference's: the one-device Module takes the base slots.)"""
        import pickle
        mod4 = _run(monkeypatch, CTX4, *SGD_MOM, 2)
        prefix = str(tmp_path / "split")
        mod4.save_checkpoint(prefix, 2, save_optimizer_states=True)
        with open(prefix + "-0002.states", "rb") as f:
            saved = pickle.loads(f.read())
        assert sorted(saved) == list(range(4 * 4))
        for i in range(4):
            for k in range(1, 4):       # whole copies on every device
                np.testing.assert_array_equal(saved[4 * i],
                                              saved[4 * i + k])
        mod1 = _run(monkeypatch, [mx.cpu(0)], *SGD_MOM, 0)
        _, args, auxs = mx.model.load_checkpoint(prefix, 2)
        mod1.set_params(args, auxs)
        mod1._updater.set_states({i: saved[4 * i] for i in range(4)})
        for i in range(4):
            mod1._optimizer._index_update_count[i] = 2
        mod1._optimizer.num_update = 2
        rs = np.random.RandomState(7)
        for _ in range(2):
            rs.randint(0, 2, (8, 4)), rs.randint(-1, 2, (8, 2))
        batch = _Batch(rs.randint(0, 2, (8, 4)).astype(np.float32),
                       rs.randint(-1, 2, (8, 2)).astype(np.float32))
        mod1.forward_backward(batch)
        mod1.update()
        want = _run(monkeypatch, [mx.cpu(0)], *SGD_MOM, 3)
        _assert_bitexact(mod1, want)

    def test_train_checkpointer_snapshot_is_whole(self, monkeypatch,
                                                  split_small, tmp_path):
        """``_ft_snapshot`` (what ``TrainCheckpointer`` writes) of a split
        run restores into a fresh Module on the same devices."""
        from mxnet_tpu.checkpoint import TrainCheckpointer
        mod4 = _run(monkeypatch, CTX4, *SGD_MOM, 2)
        ckpt = TrainCheckpointer(str(tmp_path), every_n_steps=1)
        ckpt.save_sync(2, *mod4._ft_snapshot(0, 2, 2))
        tree, meta, blobs = ckpt.load(ckpt.latest())
        ckpt.close()
        fresh = _run(monkeypatch, CTX4, *SGD_MOM, 0)
        fresh._ft_restore(tree, meta, blobs)
        _assert_bitexact(fresh, mod4)
        rs = np.random.RandomState(11)
        batch = _Batch(rs.randint(0, 2, (8, 4)).astype(np.float32),
                       rs.randint(-1, 2, (8, 2)).astype(np.float32))
        for m in (fresh, mod4):
            m.forward_backward(batch)
            m.update()
        self._assert_layout(fresh, ["fc1_weight", "fc2_weight"],
                            ["fc1_bias", "fc2_bias"])
        _assert_bitexact(fresh, mod4)       # (get_params de-meshes)

    @pytest.mark.parametrize("name,kwargs,bf16", [
        SGD_MOM + (False,),
        ("adam", {"learning_rate": 0.01, "multi_precision": True}, True),
    ], ids=["sgd_mom", "adam_masters"])
    def test_weight_is_held_in_its_states_layout(self, monkeypatch,
                                                 split_small, name, kwargs,
                                                 bf16):
        """After two steps every split leaf's weight carries its state's
        sharding, every other leaf its parameter's, and what the step took
        is what the step before gave: the same array objects, nothing
        copied."""
        from mxnet_tpu import amp
        if bf16:
            monkeypatch.setenv(amp.ENV_FLAG, "1")
        mod = _run(monkeypatch, CTX4, name, kwargs, 2)
        self._assert_layout(mod, ["fc1_weight", "fc2_weight"],
                            ["fc1_bias", "fc2_bias"])
        fs, ex = mod._fused_step, mod._exec_group.execs[0]
        psh, ssh, _ = fs._mesh_layout()
        for n, p, s in zip(fs._pnames, psh, ssh):
            w = ex.arg_dict[n]._data
            assert w.sharding == s, n
            assert (s is p) == (n.endswith("bias")), n
            assert fs._pool._own[("w", n)] is w
            for e in mod._exec_group.execs[1:]:     # the siblings' views
                assert e.arg_dict[n]._data is w
        copies = fs._pool.copies
        rs = np.random.RandomState(3)
        mod.forward_backward(_Batch(
            rs.randint(0, 2, (8, 4)).astype(np.float32),
            rs.randint(-1, 2, (8, 2)).astype(np.float32)))
        mod.update()
        assert fs._pool.copies == copies

    @pytest.mark.parametrize("between", [
        "get_params", "set_params", "save_checkpoint", "snapshot",
        "demesh"])
    def test_reads_and_writes_between_steps_keep_the_layout(
            self, monkeypatch, split_small, tmp_path, between):
        """What reads or writes the weights between two mesh steps sees
        whole arrays, and the step after it takes every leaf in the layout
        it had and lands on one device's numbers."""
        mod = _run(monkeypatch, CTX4, *SGD_MOM, 2)
        want = {k: v.asnumpy() for k, v in
                _run(monkeypatch, [mx.cpu(0)], *SGD_MOM, 2)
                .get_params()[0].items()}
        if between == "get_params":
            got = mod.get_params()[0]
        elif between == "set_params":
            # a weight set from outside while the handles hold split
            # globals: no de-mesh in between
            ex = mod._exec_group.execs[0]
            got = {k: mx.nd.array(np.asarray(ex.arg_dict[k]._data))
                   for k in want}
            assert mod._fused_step._meshed
            mod.set_params(got, {})
        elif between == "save_checkpoint":
            prefix = str(tmp_path / "held")
            mod.save_checkpoint(prefix, 2, save_optimizer_states=True)
            got = mx.model.load_checkpoint(prefix, 2)[1]
        elif between == "snapshot":
            tree = mod._ft_snapshot(0, 2, 2)[0]
            got = {k: mx.nd.array(tree["param/" + k]) for k in want}
        else:
            mod._fused_step.demesh()
            got = {k: mod._exec_group.execs[3].arg_dict[k] for k in want}
            for k, v in got.items():
                assert v._data.devices() == {CTX4[3].jax_device}, k
        for k in want:
            assert got[k].shape == want[k].shape
            np.testing.assert_array_equal(got[k].asnumpy(), want[k])
        rs = np.random.RandomState(7)
        for _ in range(2):          # the draws of steps 1 and 2
            rs.randint(0, 2, (8, 4)), rs.randint(-1, 2, (8, 2))
        mod.forward_backward(_Batch(
            rs.randint(0, 2, (8, 4)).astype(np.float32),
            rs.randint(-1, 2, (8, 2)).astype(np.float32)))
        mod.update()
        self._assert_layout(mod, ["fc1_weight", "fc2_weight"],
                            ["fc1_bias", "fc2_bias"])
        _assert_bitexact(mod, _run(monkeypatch, [mx.cpu(0)], *SGD_MOM, 3))

    def test_megatron_dp2_tp2_state_axis_is_not_the_rules(
            self, monkeypatch, split_small):
        from mxnet_tpu.parallel.mesh import make_mesh, megatron_rules
        from jax.sharding import PartitionSpec as P

        def rules(mod):
            return megatron_rules(make_mesh(
                {"dp": 2, "tp": 2}, devices=[c.jax_device for c in CTX4]))

        mod = _run(monkeypatch, CTX4, *SGD_MOM, 2,
                   mesh_axes={"dp": 2, "tp": 2}, rules_fn=rules)
        ex = mod._exec_group.execs[0]
        leaves = _leaves_by_name(mod)
        psh, ssh, _ = mod._fused_step._mesh_layout()
        # column-parallel fc1 (tp on the rows): the state takes the columns,
        # and the weight is held with it
        assert psh[0].spec == P("tp", None)
        assert ex.arg_dict["fc1_weight"]._data.sharding.spec == \
            P("tp", "dp")
        assert leaves["fc1_weight"][0]._data.sharding.spec == P("tp", "dp")
        # row-parallel fc2 (tp on the columns): the state takes the rows
        assert psh[2].spec == P(None, "tp")
        assert ex.arg_dict["fc2_weight"]._data.sharding.spec == \
            P("dp", "tp")
        assert leaves["fc2_weight"][0]._data.sharding.spec == P("dp", "tp")
        assert ex.arg_dict["fc1_bias"]._data.sharding == psh[1]
        for s in leaves["fc1_weight"][0]._data.addressable_shards:
            assert s.data.shape == (2, 2)       # a quarter each
        assert leaves["fc1_bias"][0]._data.sharding.spec == P()
        mod1 = _run(monkeypatch, [mx.cpu(0)], *SGD_MOM, 2)
        _assert_bitexact(mod, mod1)

    def test_dp_of_one_keeps_the_parents_signature_and_program(
            self, monkeypatch, split_small):
        from mxnet_tpu.parallel.mesh import make_mesh, megatron_rules
        ctx2 = CTX8[:2]

        def rules(mod):
            return megatron_rules(make_mesh(
                {"dp": 1, "tp": 2}, devices=[c.jax_device for c in ctx2]))

        mod = _run(monkeypatch, ctx2, *SGD_MOM, 2,
                   mesh_axes={"dp": 1, "tp": 2}, rules_fn=rules)
        psh, ssh, sig = mod._fused_step._mesh_layout()
        assert ssh is None
        assert sig == ((("dp", 1), ("tp", 2)),
                       tuple(str(sh.spec) for sh in psh))
        ex = mod._exec_group.execs[0]
        assert [k for k in ex._jitted if k[0] == "step"][0][1] == sig
        for name, got in _leaves_by_name(mod).items():
            assert got[0]._data.sharding == \
                ex.arg_dict[name]._data.sharding
        mod1 = _run(monkeypatch, [mx.cpu(0)], *SGD_MOM, 2)
        _assert_bitexact(mod, mod1)

    @pytest.mark.parametrize("ctxs,split,pins", [
        ([mx.cpu(0)], False, 0),    # one device: no sharding is named
        (CTX4, False, 4 + 4),       # replicated: new weights and momenta
        # split: the two held matrices gathered at the top, every gradient
        # to its state's layout, new weights and momenta as they came
        (CTX4, True, 2 + 4 + 4 + 4),
    ], ids=["one_device", "replicated", "split"])
    def test_what_the_step_program_pins(self, monkeypatch, ctxs, split,
                                        pins):
        """Only a leaf whose state is split is gathered in the program (at
        its top, under ``GradSync``); without one the traced body names the
        shardings it named before the weights were held split, and on one
        device none."""
        import jax
        from mxnet_tpu.executor import Executor
        from mxnet_tpu.parallel import mesh as pmesh
        if split:
            monkeypatch.setattr(pmesh, "STATE_SHARD_MIN_ELEMENTS", 8)
        real, seen = Executor.step_program, []

        def step_program(self, *a, **k):
            fn = real(self, *a, **k)

            def call(*args):
                seen.append(str(jax.make_jaxpr(fn)(*args)))
                return fn(*args)
            return call

        monkeypatch.setattr(Executor, "step_program", step_program)
        _run(monkeypatch, ctxs, *SGD_MOM, 1)
        (text,) = seen
        assert text.count("sharding_constraint[") == pins
        if split:       # the two gathers: ahead of forward's first product
            eqns = text.splitlines()
            pinned = [i for i, ln in enumerate(eqns)
                      if "sharding_constraint[" in ln]
            assert pinned[1] < min(i for i, ln in enumerate(eqns)
                                   if "dot_general" in ln)

    def test_layout_change_is_a_new_program(self, monkeypatch):
        """The signature carries the state's layout: the same mesh and
        rules with another split is another step program."""
        from mxnet_tpu.parallel import mesh as pmesh
        mod = _run(monkeypatch, CTX4, *SGD_MOM, 1)
        ex = mod._exec_group.execs[0]
        keys1 = {k for k in ex._jitted if k[0] == "step"}
        assert len(keys1) == 1 and len(next(iter(keys1))[1]) == 2
        monkeypatch.setattr(pmesh, "STATE_SHARD_MIN_ELEMENTS", 8)
        mod._fused_step.on_mesh_change()
        rs = np.random.RandomState(9)
        mod.forward_backward(_Batch(
            rs.randint(0, 2, (8, 4)).astype(np.float32),
            rs.randint(-1, 2, (8, 2)).astype(np.float32)))
        mod.update()
        keys2 = {k for k in ex._jitted if k[0] == "step"}
        assert len(keys2) == 2 and keys1 < keys2
        (new,) = keys2 - keys1
        assert new[1][2] == ("PartitionSpec('dp',)", "PartitionSpec()",
                             "PartitionSpec(None, 'dp')", "PartitionSpec()")


def test_group_bind_walks_the_graph_once(monkeypatch):
    """Binding one graph on several devices infers its shapes once for the
    replicas of a slice size (it was once a device: 0.5 s each at
    GPT-2-medium's depth, in ``gpt2m_train_dp4``'s set-up), and every
    replica is bound as a lone ``simple_bind`` binds it."""
    walks = []
    real = mx.sym.Symbol.infer_shape
    monkeypatch.setattr(
        mx.sym.Symbol, "infer_shape",
        lambda self, *a, **k: walks.append(k) or real(self, *a, **k))
    mod = _build_module(CTX4)
    bound = [k for k in walks if k.get("data") == (2, 4)]
    assert len(bound) == 1, walks
    execs = mod._exec_group.execs
    lone = execs[0]._symbol.simple_bind(
        ctx=mx.cpu(0), data=(2, 4), softmax_label=(2, 2))
    for e in execs:
        assert {n: (a.shape, a.dtype) for n, a in e.arg_dict.items()} == \
            {n: (a.shape, a.dtype) for n, a in lone.arg_dict.items()}
        assert sorted(e.grad_dict) == sorted(execs[0].grad_dict)


class TestStateSplitTimeline:
    _step = TestMeshStepTimeline._step

    def test_gather_counts_sharded_and_copies_nothing(self, monkeypatch,
                                                      split_small):
        from test_fused_step import assert_one_timeline
        telemetry.enable()
        try:
            mod = _run(monkeypatch, CTX4, *SGD_MOM, steps=0)
            by = assert_one_timeline(self._step(mod, 1), "mesh_fused")
            g = by["Step::gather"].args
            # two matrices' momenta of 16 and 8 float32 elements
            assert g["leaves"] == 8 and g["copies"] == 8
            assert g["sharded"] == 2 and g["sharded_bytes"] == 4 * (16 + 8)
            assert g["held_split"] == 2     # the two matrices themselves
            for seed in (2, 3):     # the donation chain finds them there
                by = assert_one_timeline(self._step(mod, seed),
                                         "mesh_fused")
                g = by["Step::gather"].args
                assert g["copies"] == 0 and g["copy_bytes"] == 0
                assert g["sharded"] == 2 and g["held_split"] == 2
                assert by["Step::launch"].args["first_run"] is False
            assert telemetry.value("opt_state_sharded_bytes",
                                   path="mesh_fused") == 96
        finally:
            telemetry.disable()

    def test_count_is_retaken_when_a_state_is_placed_again(
            self, monkeypatch, split_small):
        """``sharded`` / ``sharded_bytes`` are counted in a step that
        places a leaf, not in every step: a state set from outside is
        copied (``copies`` > 0) and counted again."""
        import pickle
        mod = _run(monkeypatch, CTX4, *SGD_MOM, steps=0)
        fs = mod._fused_step
        counted = []
        real = fs._count_split
        monkeypatch.setattr(fs, "_count_split",
                            lambda sv: counted.append(1) or real(sv))
        for seed in (1, 2, 3):
            self._step(mod, seed)
        assert len(counted) == 1            # the first step's placement
        mod._updater.set_states(pickle.loads(mod._updater.get_states()))
        g = {r.name: r for r in self._step(mod, 4)}["Step::gather"].args
        assert len(counted) == 2 and g["copies"] > 0
        assert g["sharded"] == 2 and g["sharded_bytes"] == 4 * (16 + 8)
        assert g["held_split"] == 2

    def test_replicated_layout_counts_none(self, monkeypatch):
        mod = _run(monkeypatch, CTX4, *SGD_MOM, steps=0)
        g = {r.name: r for r in self._step(mod, 1)}["Step::gather"].args
        assert g["sharded"] == 0 and g["sharded_bytes"] == 0
        assert g["held_split"] == 0

    @pytest.mark.parametrize("ctxs,axes,want", [
        ([mx.cpu(0)], None, 0),
        (CTX8[:2], {"dp": 1, "tp": 2}, 0),
        (CTX4, {"dp": 2, "tp": 2}, 2),
        (CTX4, None, 2),
    ], ids=["one_device", "dp1_tp2", "dp2_tp2", "dp4"])
    def test_held_split_counts_the_params_taken_in_their_states_layout(
            self, monkeypatch, split_small, ctxs, axes, want):
        """``Step::gather``'s ``held_split``: 0 on one device and on a mesh
        whose ``dp`` is 1, the number of split params otherwise (a plain
        SGD weight has no state leaf and is still held split), with
        nothing copied from the second step on."""
        from mxnet_tpu.parallel.mesh import make_mesh, megatron_rules

        def rules(mod):
            return megatron_rules(make_mesh(
                axes, devices=[c.jax_device for c in ctxs]))

        mod = _run(monkeypatch, ctxs, "sgd", {"learning_rate": 0.25},
                   steps=0, mesh_axes=axes,
                   rules_fn=rules if axes else None)
        for seed in (1, 2, 3):
            g = {r.name: r for r in self._step(mod, seed)}[
                "Step::gather"].args
            assert g["held_split"] == want and g["sharded"] == 0
            assert (g["copies"] == 0) == (seed > 1)

    @pytest.mark.parametrize("metric,count", [
        ("state_sharded_leaves.train", 2),
        ("weights_held_split.train", 2),
    ])
    def test_the_benchmarks_metric_reads_it(self, monkeypatch,
                                            split_small, metric, count):
        """``state_sharded_leaves.train`` and ``weights_held_split.train``
        as ``perf/`` reads them: the file's reader and params over the
        program's own ring, two traced steps of three."""
        import json
        import os
        from mxnet_tpu import tracing
        from perf import harness
        from perf.reducers import program_span_ms
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "perf", "metrics",
                               metric + ".json")) as f:
            spec = json.load(f)
        assert spec["reducer"] == "program_span_ms"
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            entry = [m for m in json.load(f)["per_layer"]
                     if m["name"] == spec["name"]]
        assert entry == [{"name": metric,
                          "unit": "count", "better": "higher",
                          "source": "program_counter",
                          "layer": "collectives",
                          "moves": "train_items_per_s",
                          "workloads": ["gpt2m_train_dp4"]}]
        mod = _run(monkeypatch, CTX4, *SGD_MOM, steps=0)
        tracing.flight.clear()
        spans = harness.Spans()
        rs = np.random.RandomState(5)
        for _ in range(3):
            batch = _Batch(rs.randint(0, 2, (8, 4)).astype(np.float32),
                           rs.randint(-1, 2, (8, 2)).astype(np.float32))
            with spans("dispatch"):
                mod.forward_backward(batch)
                mod.update()
        ctx = {"spans": spans, "traced_steps": 2}
        assert program_span_ms.read(ctx, spec["params"]) == 2 * count


# ---------------------------------------------------------------------------
# how the split leaves' gradients are exchanged (PR 32): in a row behind
# backward on a CPU mesh, as the parent's program; around the ring of
# ``parallel.mesh.matmul_wt`` on TPUs (forced here, where a test wants it)
# ---------------------------------------------------------------------------
@pytest.fixture
def ring_on_cpu(monkeypatch):
    """``exchange_path`` answers ``async`` wherever it would answer ``row``
    (the steering is the test's: the program has no switch); yields what it
    was asked and the rings it traced."""
    from mxnet_tpu.parallel import mesh as pmesh
    real_path, real_ring = pmesh.exchange_path, pmesh._ring_reduce_scatter
    asked, rings = [], []

    def path(psh, ssh):
        asked.append(real_path(psh, ssh))
        return "async" if asked[-1] == "row" else asked[-1]

    def ring(part, axis, name, order, *beside):
        rings.append((tuple(part.shape), axis, order))
        return real_ring(part, axis, name, order, *beside)

    monkeypatch.setattr(pmesh, "exchange_path", path)
    monkeypatch.setattr(pmesh, "_ring_reduce_scatter", ring)
    return asked, rings


class TestGradExchange:
    _step = TestMeshStepTimeline._step

    @pytest.mark.parametrize("ctxs,split,want", [
        ([mx.cpu(0)], True, None),      # one device: nothing to exchange
        (CTX4, False, None),            # no leaf is split over dp
        (CTX4, True, "row"),            # split, on CPU devices
    ], ids=["one_device", "replicated", "split"])
    def test_counter_and_span_name_the_path(self, monkeypatch, ctxs, split,
                                            want):
        from mxnet_tpu.parallel import mesh as pmesh
        if split:
            monkeypatch.setattr(pmesh, "STATE_SHARD_MIN_ELEMENTS", 8)
        telemetry.enable()
        try:
            was = {p: telemetry.value("grad_exchange_total", path=p)
                   for p in ("row", "async")}
            mod = _run(monkeypatch, ctxs, *SGD_MOM, steps=0)
            by = {r.name: r for r in self._step(mod, 1)}
            self._step(mod, 2)          # the same program: counted once
            grew = {p: telemetry.value("grad_exchange_total", path=p)
                    - was[p] for p in was}
            assert grew == {"row": 1 if want else 0, "async": 0}
            for span in ("Step::program", "Step::launch"):
                assert by[span].args.get("exchange") == want, span
            assert mod._fused_step._exchange == want
        finally:
            telemetry.disable()

    def test_exchange_path_reads_the_layout(self):
        """``async`` needs TPUs under weights that are whole on every
        device; None where no state is split."""
        from types import SimpleNamespace as NS
        from jax.sharding import PartitionSpec as P
        from mxnet_tpu.parallel.mesh import exchange_path

        def sh(platform, whole, dp=4):
            mesh = NS(devices=np.array([NS(platform=platform)] * 4),
                      shape={"dp": dp, "tp": 4 // dp})
            return NS(mesh=mesh, spec=P() if whole else P("tp", None))

        assert exchange_path([sh("tpu", True)], None) is None
        assert exchange_path([sh("tpu", True)] * 2,
                             [sh("tpu", False)] * 2) == "async"
        assert exchange_path([sh("tpu", True), sh("tpu", False)],
                             [sh("tpu", False)] * 2) == "row"
        assert exchange_path([sh("tpu", True, 2)],
                             [sh("tpu", False, 2)]) == "row"
        assert exchange_path([sh("cpu", True)], [sh("cpu", False)]) == "row"

    @pytest.mark.parametrize("ctxs", [[mx.cpu(0)], CTX4],
                             ids=["one_device", "cpu_mesh"])
    def test_off_the_ring_the_product_is_the_plain_one(self, monkeypatch,
                                                       split_small, ctxs):
        """One device and a CPU mesh lower the program they lowered before
        ``matmul_wt`` stood in the ops: the text with the op's product put
        back to ``jnp.matmul(x, w.T)`` is the same text, and it holds no
        permute."""
        import hashlib
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.executor import Executor
        from mxnet_tpu.ops import nn as ops_nn
        real, texts = Executor.step_program, []

        def step_program(self, *a, **k):
            fn = real(self, *a, **k)

            def call(*args):
                texts.append(fn.lower(*args).as_text())
                return fn(*args)
            return call

        monkeypatch.setattr(Executor, "step_program", step_program)
        _run(monkeypatch, ctxs, *SGD_MOM, 1)
        monkeypatch.setattr(ops_nn, "_matmul_wt",
                            lambda x, w: jnp.matmul(x, w.T))
        _run(monkeypatch, ctxs, *SGD_MOM, 1)
        ours, plain = (hashlib.sha1(t.encode()).hexdigest() for t in texts)
        assert ours == plain
        assert "collective_permute" not in texts[0]

    @pytest.mark.parametrize("shape", [(8, 4, 2), (16, 8, 8)],
                             ids=["one_way", "both_ways"])
    def test_ring_ends_where_one_device_ends(self, monkeypatch, split_small,
                                             ring_on_cpu, shape):
        """The ring forced onto the CPU mesh: two steps of SGD with
        momentum end bit for bit where one device ends (sums of small
        integers, whatever their order), both matrices' gradients came
        round it, and the leaves lie as the row's program leaves them."""
        asked, rings = ring_on_cpu
        batch, feat, out = shape
        mod4 = _run(monkeypatch, CTX4, *SGD_MOM, 2, batch=batch, feat=feat,
                    out=out)
        assert set(asked) == {"row"}
        assert mod4._fused_step._exchange == "async"
        # fc2 [out, 4] first (backward's order), then fc1 [4, feat]: split
        # along the larger axis, slices of one row (one way) or two (both)
        assert [r[:2] for r in rings] == [
            ((out, 4), 0 if out >= 4 else 1), ((4, feat), 1 if feat > 4
                                               else 0)]
        large = ["fc1_weight", "fc2_weight"] + ["fc2_bias"] * (out >= 8)
        TestStateSplitOverDp()._assert_layout(
            mod4, large, sorted(set(mod4._param_names) - set(large)))
        mod1 = _run(monkeypatch, [mx.cpu(0)], *SGD_MOM, 2, batch=batch,
                    feat=feat, out=out)
        _assert_bitexact(mod4, mod1)

    def test_ring_under_adam_with_masters(self, monkeypatch, ring_on_cpu):
        """bf16 weights under Adam, the gradients round the ring in bf16:
        the state ends where the row's program ends, to the last bits of
        a bf16 sum taken in another order."""
        from mxnet_tpu import amp
        from mxnet_tpu.parallel import mesh as pmesh
        monkeypatch.setenv(amp.ENV_FLAG, "1")
        monkeypatch.setattr(pmesh, "STATE_SHARD_MIN_ELEMENTS", 8)
        okw = {"learning_rate": 0.01, "multi_precision": True}
        ring = _run(monkeypatch, CTX4, "adam", okw, 3)
        assert len(ring_on_cpu[1]) == 2
        monkeypatch.undo()
        monkeypatch.setenv(amp.ENV_FLAG, "1")
        monkeypatch.setattr(pmesh, "STATE_SHARD_MIN_ELEMENTS", 8)
        row = _run(monkeypatch, CTX4, "adam", okw, 3)
        assert row._fused_step._exchange == "row"
        _assert_close(ring, row, rtol=1e-2)
        for name, got in _leaves_by_name(ring).items():
            for j, (a, b) in enumerate(zip(got, _leaves_by_name(row)[name])):
                assert a._data.sharding == b._data.sharding, (name, j)
                np.testing.assert_allclose(     # a hop rounds to bf16
                    a.asnumpy(), b.asnumpy(), rtol=1e-2, atol=1e-6,
                    err_msg="%s[%d]" % (name, j))

    def test_ring_program_permutes_and_holds_each_ring(self, monkeypatch,
                                                       split_small,
                                                       ring_on_cpu):
        """The traced ring: a slice's halves meet at its owner over two
        hops one way and one the other (six ``ppermute`` a leaf on four
        devices), and two barriers a ring hold the order: the weight's
        product (and the ring before) ahead of the input's, and the first
        hop's end with the input's product."""
        import jax
        from mxnet_tpu.executor import Executor
        real, seen = Executor.step_program, []

        def step_program(self, *a, **k):
            fn = real(self, *a, **k)

            def call(*args):
                seen.append(str(jax.make_jaxpr(fn)(*args)))
                return fn(*args)
            return call

        monkeypatch.setattr(Executor, "step_program", step_program)
        _run(monkeypatch, CTX4, *SGD_MOM, 1, batch=16, feat=8, out=8)
        (text,) = seen
        assert text.count("ppermute[") == 2 * 2 * (2 + 1)
        assert text.count("optimization_barrier") == 2 * 2
        # every gradient reaches its update already in the state's layout:
        # no row of the parent's reduce-scatters is left to make
        assert text.count("shard_map[") == 2

    @pytest.mark.parametrize("coords,want", [
        ([(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)], (0, 1, 3, 2)),
        ([(0, 0, 0), (1, 0, 0), (1, 1, 0), (0, 1, 0)], (0, 1, 2, 3)),
        (None, (0, 1, 2, 3)),
    ], ids=["2x2", "2x2_in_ring_order", "no_coords"])
    def test_ring_order_follows_the_chips_links(self, coords, want):
        from types import SimpleNamespace as NS
        from mxnet_tpu.parallel.mesh import _ring_order
        devs = [NS(coords=c) if coords else NS() for c in coords or [0] * 4]
        mesh = NS(shape={"dp": 4}, axis_names=("dp",),
                  devices=np.array(devs, dtype=object))
        assert _ring_order(mesh, "dp") == want

    @pytest.mark.parametrize("shape,axis,order", [
        ((8, 6), 0, (0, 1, 2, 3)),      # slices of two rows: both ways
        ((4, 6), 0, (0, 1, 3, 2)),      # of one row: one way
        ((3, 16), 1, (0, 1, 3, 2)),
        ((5, 8), 1, (0, 3, 1, 2)),
        ((4, 3), 0, (1, 0)),            # two chips: one hop, no shorter way
        ((3, 6), 1, (2, 0, 1)),
        ((16, 3), 0, (0, 1, 3, 2, 6, 7, 5, 4)),     # eight: four hops
    ])
    def test_ring_reduce_scatter_is_the_sum(self, shape, axis, order):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from mxnet_tpu.parallel.mesh import _ring_reduce_scatter
        n = len(order)
        mesh = Mesh(np.array(jax.devices()[:n]), ("dp",))
        parts = np.random.RandomState(3).randint(
            -8, 9, (n,) + shape).astype(np.float32)
        got = jax.shard_map(
            lambda p: _ring_reduce_scatter(p[0], axis, "dp", order),
            mesh=mesh, in_specs=P("dp"),
            out_specs=P(*("dp" if i == axis else None for i in range(2))),
            check_vma=False)(jnp.asarray(parts))
        assert np.array_equal(np.asarray(got), parts.sum(0))


# ---------------------------------------------------------------------------
# the host side of the step: what it asks of every leaf on every step
# (PR 32: with the exchange beside backward the host's phases set the pace
# of the four-chip cell, and a run's p95 follows whatever the host does)
# ---------------------------------------------------------------------------
class TestHostSideOfTheStep:
    _step = TestMeshStepTimeline._step

    @pytest.mark.parametrize("ctxs", [[mx.cpu(0)], CTX4],
                             ids=["one_device", "mesh"])
    def test_validate_reads_each_state_once(self, monkeypatch, ctxs):
        """A mesh step's sibling slots hold the device-0 slot's own object:
        ``Step::validate`` looks at it there and not once a device."""
        mod = _run(monkeypatch, ctxs, *SGD_MOM, steps=1)
        fs, ndev = mod._fused_step, len(ctxs)
        states = mod._updater.states
        assert len(states) == 4 * ndev
        real, seen = opt.fused_state_leaves, []

        def counted(st, mp=False):
            seen.append(st)
            return real(st, mp)

        monkeypatch.setattr(opt, "fused_state_leaves", counted)
        assert fs._states_fusable(ndev)
        assert len(seen) == 4
        self._step(mod, 3)
        assert mod._fused_step._unsupported is False

    @pytest.mark.parametrize("slot,fusable", [
        (0, False),     # the device-0 slot itself
        (1, False),     # a sibling that is no longer the alias
        (None, True),
    ], ids=["base", "sibling", "untouched"])
    def test_validate_still_sees_a_foreign_state(self, monkeypatch, slot,
                                                 fusable):
        mod = _run(monkeypatch, CTX4, *SGD_MOM, steps=1)
        states = mod._updater.states
        assert states[1] is states[0]
        if slot is not None:
            states[slot] = "not a state"
        assert mod._fused_step._states_fusable(4) is fusable

    @pytest.mark.parametrize("ctxs", [[mx.cpu(0)], CTX4],
                             ids=["one_device", "mesh"])
    def test_small_inputs_are_host_arrays(self, monkeypatch, ctxs):
        """The per-slot rates, decays and counts and the rescale reach the
        program as float32 host arrays (the launch copies them; a device
        array made on one device would be spread by a callback)."""
        mod = _run(monkeypatch, ctxs, *SGD_MOM, steps=1)
        ex = mod._exec_group.execs[0]
        keys = [k for k in ex._jitted
                if isinstance(k, tuple) and k and k[0] == "step"]
        assert len(keys) == 1
        real, got = ex._jitted[keys[0]], []

        def spy(*args):
            got.append(args[6:])
            return real(*args)

        ex._jitted[keys[0]] = spy
        self._step(mod, 5)
        (lrs, wds, ts, rescale), = got
        for v, shape in ((lrs, (4,)), (wds, (4,)), (ts, (4,)),
                         (rescale, ())):
            assert type(v) is np.ndarray and v.dtype == np.float32
            assert v.shape == shape
        assert ts.tolist() == [2.0] * 4 and float(rescale) == 0.125
