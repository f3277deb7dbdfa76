"""bf16 mixed-precision training (MXNET_TPU_BF16).

Contract: with the flag on, params/activations/grads are stored bf16 and
every trained weight carries a master-fp32 leaf PREPENDED to its fused
opt-state tuple.  The fused program's fp32 master trajectory must be
BIT-IDENTICAL to the eager ``update_multi_precision`` oracle (same
kernels, grad up-cast, and host-side lr folding) for every fused
optimizer; the module-level fused step must track the eager bf16 loop
within bf16 tolerance on one device and on the mesh path.  Plus the
mechanics: mixed-dtype donation genuinely frees old buffers, the env
flag is part of the jit-cache key, astype/copyto never alias across a
dtype change, and ``create_state_multi_precision`` recognizes both fp16
and bf16.
"""
import os

import numpy as np
import pytest
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp
from mxnet_tpu import optimizer as opt
from mxnet_tpu import fused_step as fused
from mxnet_tpu.executor import build_update_program


BF16 = amp.compute_dtype()

OPT_CONFIGS = [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("sgd", {"learning_rate": 0.05}),
    ("nag", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("rmsprop", {"learning_rate": 0.01, "centered": True}),
]
OPT_IDS = [c[0] + ("_c" if c[1].get("centered")
                   else ("_m" if c[1].get("momentum") else ""))
           for c in OPT_CONFIGS]


def _bf16_weight(shape, seed):
    rs = np.random.RandomState(seed)
    return mx.nd.array(rs.randn(*shape).astype(np.float32)).astype(BF16)


def _grad_stream(shape, n, seed=7):
    rs = np.random.RandomState(seed)
    return [mx.nd.array(rs.randn(*shape).astype(np.float32)).astype(BF16)
            for _ in range(n)]


class TestCreateStateMultiPrecision:
    @pytest.mark.parametrize("dtype", ["float16", "bfloat16"])
    def test_low_precision_gets_master(self, dtype):
        o = opt.Adam(multi_precision=True)
        w = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3)) \
              .astype(np.dtype(dtype))
        state = o.create_state_multi_precision(0, w)
        assert isinstance(state, tuple) and len(state) == 2
        inner, w32 = state
        assert w32.dtype == np.float32
        np.testing.assert_array_equal(w32.asnumpy(),
                                      w.asnumpy().astype(np.float32))
        mean, var = inner
        assert mean.dtype == np.float32 and var.dtype == np.float32

    @pytest.mark.parametrize("dtype", ["float16", "bfloat16"])
    def test_sgd_low_precision(self, dtype):
        o = opt.SGD(momentum=0.9, multi_precision=True)
        w = mx.nd.ones((3,)).astype(np.dtype(dtype))
        inner, w32 = o.create_state_multi_precision(0, w)
        assert w32.dtype == np.float32 and inner.dtype == np.float32

    def test_fp32_weight_keeps_plain_state(self):
        o = opt.Adam(multi_precision=True)
        w = mx.nd.ones((3,))
        state = o.create_state_multi_precision(0, w)
        # no master for an already-fp32 weight
        assert isinstance(state, tuple) and len(state) == 2
        assert all(isinstance(s, mx.nd.NDArray) for s in state)

    def test_fused_state_leaves_mp_layouts(self):
        # SGD's mp state is flat (mom, w32); Adam's is nested
        # ((mean, var), w32) — both flatten with the master FIRST
        sgd = opt.SGD(momentum=0.9, multi_precision=True)
        adam = opt.Adam(multi_precision=True)
        w = mx.nd.ones((3,)).astype(BF16)
        st_s = sgd.create_state_multi_precision(0, w)
        st_a = adam.create_state_multi_precision(0, w)
        ls = opt.fused_state_leaves(st_s, mp=True)
        la = opt.fused_state_leaves(st_a, mp=True)
        assert len(ls) == 2 and ls[0] is st_s[1] and ls[1] is st_s[0]
        assert len(la) == 3 and la[0] is st_a[1]
        assert la[1] is st_a[0][0] and la[2] is st_a[0][1]


class TestOracleBitIdentity:
    """The fused update program's fp32 master must match the eager
    multi-precision oracle bit-for-bit over a long trajectory."""

    @pytest.mark.parametrize("name,kwargs", OPT_CONFIGS, ids=OPT_IDS)
    def test_master_trajectory(self, name, kwargs, steps=50):
        shape = (4, 5)
        grads = _grad_stream(shape, steps)

        # eager oracle
        opt_e = opt.create(name, multi_precision=True, **kwargs)
        w_e = _bf16_weight(shape, 3)
        st_e = opt_e.create_state_multi_precision(0, w_e)
        for g in grads:
            opt_e.update_multi_precision(0, w_e, g, st_e)

        # fused mp program (donated, like the module step)
        opt_f = opt.create(name, multi_precision=True, **kwargs)
        assert opt_f.supports_fused(_bf16_weight(shape, 3))
        w_f = _bf16_weight(shape, 3)
        st_f = opt_f.create_state_multi_precision(0, w_f)
        leaves = opt.fused_state_leaves(st_f, mp=True)
        assert leaves is not None
        assert len(leaves) == opt_f.fused_state_arity() + 1
        fn = build_update_program([opt_f.fused_update_mp])
        for g in grads:
            opt_f._update_count(0)
            t = opt_f._index_update_count[0]
            lr = opt_f.fused_slot_lr(opt_f._get_lr(0), t)
            new_p, new_s = fn(
                [w_f._data], [tuple(l._data for l in leaves)], [[g._data]],
                jnp.asarray([lr], jnp.float32),
                jnp.asarray([opt_f._get_wd(0)], jnp.float32),
                jnp.asarray([t], jnp.float32),
                jnp.asarray(opt_f.rescale_grad, jnp.float32))
            w_f._data = new_p[0]
            for leaf, arr in zip(leaves, new_s[0]):
                leaf._data = arr

        master_e = opt.fused_state_leaves(st_e, mp=True)[0]
        np.testing.assert_array_equal(leaves[0].asnumpy(), master_e.asnumpy())
        np.testing.assert_array_equal(w_f.asnumpy(), w_e.asnumpy())
        # inner leaves (moments) are part of the oracle contract too
        for j, (lf, le) in enumerate(zip(
                leaves[1:], opt.fused_state_leaves(st_e, mp=True)[1:])):
            np.testing.assert_array_equal(lf.asnumpy(), le.asnumpy(),
                                          err_msg="state leaf %d" % j)

    def test_mixed_dtype_donation_frees_old_buffers(self):
        o = opt.Adam(multi_precision=True)
        w = _bf16_weight((4, 5), 3)
        st = o.create_state_multi_precision(0, w)
        leaves = opt.fused_state_leaves(st, mp=True)
        fn = build_update_program([o.fused_update_mp])
        g = _grad_stream((4, 5), 1)[0]

        def step(wv, sv):
            return fn([wv], [sv], [[g._data]],
                      jnp.asarray([0.01], jnp.float32),
                      jnp.asarray([0.0], jnp.float32),
                      jnp.asarray([1.0], jnp.float32),
                      jnp.asarray(1.0, jnp.float32))

        # first call consumes host-committed arrays; the donation proof is
        # on the second call, whose inputs are device outputs of the first
        new_p, new_s = step(w._data, tuple(l._data for l in leaves))
        old_w, old_leaves = new_p[0], list(new_s[0])
        new_p, new_s = step(old_w, tuple(old_leaves))
        # the f32 master and every moment are genuinely consumed by XLA
        for buf in old_leaves:
            assert buf.is_deleted()
        # the bf16 weight only contributes its DTYPE to a pure update
        # program (the new weight is re-cast from the master), so XLA
        # cannot alias it here — it must still be readable, not corrupt
        assert not old_w.is_deleted()
        assert new_p[0].dtype == BF16
        assert new_s[0][0].dtype == jnp.float32

    def test_module_step_donates_mixed_dtype_state(self, monkeypatch):
        # full proof through the fused whole-step program, where the bf16
        # weight IS a used input (forward) and genuinely donated
        monkeypatch.setenv(amp.ENV_FLAG, "1")
        monkeypatch.setenv(fused.ENV_FLAG, "1")
        mod = _build_module()
        mod.init_optimizer(optimizer="adam",
                           optimizer_params={"learning_rate": 0.01,
                                             "multi_precision": True})
        mod.forward_backward(_batch(0))
        mod.update()
        ex = mod._exec_group.execs[0]
        old_w = ex.arg_dict["fc1_weight"]._data
        assert old_w.dtype == BF16
        slot = mod._param_names.index("fc1_weight")
        old_leaves = [l._data for l in opt.fused_state_leaves(
            mod._updater.states[slot], mp=True)]
        assert old_leaves[0].dtype == jnp.float32
        mod.forward_backward(_batch(1))
        mod.update()
        assert old_w.is_deleted()
        for buf in old_leaves:
            assert buf.is_deleted()


# ---- module-level -------------------------------------------------------

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


def _run_bf16(monkeypatch, fused_flag, opt_name, opt_kwargs, steps=4,
              ctxs=None):
    monkeypatch.setenv(amp.ENV_FLAG, "1")
    monkeypatch.setenv(fused.ENV_FLAG, fused_flag)
    mod = _build_module(ctxs=ctxs)
    ex0 = mod._exec_group.execs[0]
    assert ex0.arg_dict["fc1_weight"].dtype == BF16
    assert ex0.arg_dict["softmax_label"].dtype == np.float32
    mod.init_optimizer(optimizer=opt_name,
                       optimizer_params=dict(opt_kwargs,
                                             multi_precision=True))
    for i in range(steps):
        mod.forward_backward(_batch(i))
        mod.update()
    args, _ = mod.get_params()
    masters = {}
    if mod._updater is not None:
        for slot, st in mod._updater.states.items():
            leaves = opt.fused_state_leaves(st, mp=True)
            if leaves:
                masters[slot] = leaves[0].asnumpy()
    return args, masters


class TestModuleParity:
    @pytest.mark.parametrize("name,kwargs",
                             [("sgd", {"learning_rate": 0.05,
                                       "momentum": 0.9, "wd": 1e-4}),
                              ("adam", {"learning_rate": 0.01})])
    def test_fused_vs_eager_bf16(self, monkeypatch, name, kwargs):
        f_args, f_masters = _run_bf16(monkeypatch, "1", name, kwargs)
        e_args, e_masters = _run_bf16(monkeypatch, "0", name, kwargs)
        assert sorted(f_args) == sorted(e_args)
        for k in e_args:
            np.testing.assert_allclose(
                f_args[k].asnumpy().astype(np.float32),
                e_args[k].asnumpy().astype(np.float32),
                rtol=3e-2, atol=3e-3, err_msg=k)
        assert sorted(f_masters) == sorted(e_masters)
        for slot in e_masters:
            np.testing.assert_allclose(f_masters[slot], e_masters[slot],
                                       rtol=3e-2, atol=3e-3)

    def test_mesh_step_bf16(self, monkeypatch):
        ctxs = [mx.cpu(0), mx.cpu(1)]
        kwargs = {"learning_rate": 0.05, "momentum": 0.9}
        f = _run_bf16(monkeypatch, "1", "sgd", kwargs, ctxs=ctxs)
        e = _run_bf16(monkeypatch, "0", "sgd", kwargs, ctxs=ctxs)
        for k in e[0]:
            np.testing.assert_allclose(
                f[0][k].asnumpy().astype(np.float32),
                e[0][k].asnumpy().astype(np.float32),
                rtol=3e-2, atol=3e-3, err_msg=k)

    def test_loss_head_output_is_fp32(self, monkeypatch):
        monkeypatch.setenv(amp.ENV_FLAG, "1")
        monkeypatch.setenv(fused.ENV_FLAG, "1")
        mod = _build_module()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05,
                                             "multi_precision": True})
        mod.forward_backward(_batch(0))
        mod.update()
        out = mod.get_outputs()[0]
        assert out.dtype == np.float32
        p = out.asnumpy()
        np.testing.assert_allclose(p.sum(axis=-1), 1.0, rtol=1e-5)


class TestCacheKey:
    def test_env_flip_recompiles(self, monkeypatch):
        # fp32 module — the dtypes don't change, but the flag selects the
        # update_fns closure, so it MUST be part of the step-program key
        monkeypatch.setenv(fused.ENV_FLAG, "1")
        monkeypatch.delenv(amp.ENV_FLAG, raising=False)
        mod = _build_module()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05})
        mod.forward_backward(_batch(0))
        mod.update()
        ex = mod._exec_group.execs[0]
        keys0 = {k for k in ex._jitted if k[0] == "step"}
        assert len(keys0) == 1
        monkeypatch.setenv(amp.ENV_FLAG, "1")
        mod.forward_backward(_batch(1))
        mod.update()
        keys1 = {k for k in ex._jitted if k[0] == "step"}
        assert len(keys1) == 2, "flipping %s must recompile" % amp.ENV_FLAG

    def test_env_key_declared(self):
        from mxnet_tpu.executor import Executor
        assert amp.ENV_FLAG in Executor.STEP_ENV_KEYS


class TestAliasSafety:
    """bf16→fp32→bf16 round-trips must be genuine copies: donating or
    mutating one side never corrupts the other (PR 4 hazard, second
    dtype)."""

    def test_astype_round_trip_no_alias(self):
        a = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3)) \
              .astype(BF16)
        b = a.astype(np.float32)
        c = b.astype(BF16)
        ref_b, ref_c = b.asnumpy().copy(), c.asnumpy().copy()
        a[:] = 0.0
        np.testing.assert_array_equal(b.asnumpy(), ref_b)
        np.testing.assert_array_equal(c.asnumpy(), ref_c)
        b[:] = -1.0
        np.testing.assert_array_equal(c.asnumpy(), ref_c)
        assert a.asnumpy().max() == 0.0

    def test_copyto_cross_dtype_no_alias(self):
        a = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3)) \
              .astype(BF16)
        m = mx.nd.zeros((2, 3), dtype=np.float32)
        a.copyto(m)
        np.testing.assert_array_equal(m.asnumpy(),
                                      a.asnumpy().astype(np.float32))
        a[:] = 9.0
        assert m.asnumpy().max() == 5.0

    def test_master_survives_weight_donation(self):
        # the master built by astype must stay alive when the bf16 weight
        # buffer is donated into an update program
        o = opt.SGD(learning_rate=0.1, multi_precision=True)
        w = _bf16_weight((3, 3), 11)
        master = w.astype(np.float32)
        ref = master.asnumpy().copy()
        st = o.create_state_multi_precision(0, w)
        leaves = opt.fused_state_leaves(st, mp=True)
        fn = build_update_program([o.fused_update_mp])
        g = _grad_stream((3, 3), 1)[0]
        new_p, new_s = fn(
            [w._data], [tuple(l._data for l in leaves)], [[g._data]],
            jnp.asarray([0.1], jnp.float32), jnp.asarray([0.0], jnp.float32),
            jnp.asarray([1.0], jnp.float32), jnp.asarray(1.0, jnp.float32))
        assert not master._data.is_deleted()
        np.testing.assert_array_equal(master.asnumpy(), ref)


class TestServing:
    def test_predictor_accepts_bf16_params(self):
        from mxnet_tpu.predictor import Predictor
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        out = mx.sym.softmax(fc)
        rs = np.random.RandomState(0)
        # integer-valued weights are exact in bf16 → outputs must equal
        # the fp32 reference bit-for-bit after promotion
        wv = rs.randint(-3, 4, (4, 6)).astype(np.float32)
        bv = rs.randint(-3, 4, (4,)).astype(np.float32)
        x = rs.randint(-2, 3, (2, 6)).astype(np.float32)
        p32 = Predictor(out.tojson(),
                        {"fc_weight": mx.nd.array(wv),
                         "fc_bias": mx.nd.array(bv)},
                        input_shapes={"data": (2, 6)})
        p32.forward(data=x)
        ref = p32.get_output(0).asnumpy()
        p16 = Predictor(out.tojson(),
                        {"fc_weight": mx.nd.array(wv).astype(BF16),
                         "fc_bias": mx.nd.array(bv).astype(BF16)},
                        input_shapes={"data": (2, 6)})
        p16.forward(data=x)
        got = p16.get_output(0).asnumpy()
        np.testing.assert_array_equal(got.astype(np.float32),
                                      ref.astype(np.float32))

    def test_hot_swap_bf16_no_recompile(self):
        from mxnet_tpu.predictor import Predictor
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        out = mx.sym.softmax(fc)
        rs = np.random.RandomState(1)
        params = {"fc_weight": mx.nd.array(
                      rs.randn(4, 6).astype(np.float32)).astype(BF16),
                  "fc_bias": mx.nd.zeros((4,)).astype(BF16)}
        p = Predictor(out.tojson(), params, input_shapes={"data": (2, 6)})
        x = rs.randn(2, 6).astype(np.float32)
        p.forward(data=x)
        ex = p._executor
        before = {k for k in ex._jitted if k[0] == "fwd"}
        assert before
        # hot-swap f32 source values into the bf16-bound executor: the
        # copy casts at the boundary, dtypes (and so the program) persist
        p.copy_params_from({"fc_weight": mx.nd.array(
                                rs.randn(4, 6).astype(np.float32)),
                            "fc_bias": mx.nd.ones((4,))})
        p.forward(data=x)
        after = {k for k in ex._jitted if k[0] == "fwd"}
        assert before == after, "bf16 hot-swap must not recompile"
        assert ex.arg_dict["fc_weight"].dtype == BF16


# ---------------------------------------------------------------------------
# what the step asks of every weight on every step: its dtype, and whether
# that dtype carries a master (answers kept by dtype since PR 32)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,low", [
    ("float32", False), ("bfloat16", True), ("float16", True),
    ("int32", False), ("uint8", False),
])
def test_dtype_and_low_precision_by_every_spelling(dtype, low):
    want = np.dtype(dtype)
    arr = mx.nd.zeros((2, 3), dtype=dtype)
    assert arr.dtype == want and isinstance(arr.dtype, np.dtype)
    assert arr.dtype.name == dtype
    spellings = [dtype, want, want.type]
    if dtype == "bfloat16":
        spellings.append(jnp.bfloat16)
    for spelling in spellings:
        # asked twice: the first answer is kept, the second is the kept one
        assert amp.is_low_precision(spelling) is low
        assert amp.is_low_precision(spelling) is low
    assert amp.is_low_precision([dtype]) is False      # unhashable
    assert amp.is_low_precision("no_such_dtype") is False
