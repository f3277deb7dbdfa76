"""Pallas flash-attention kernels vs the scan blockwise reference.

Interpret mode on CPU (same jaxpr the TPU compiles).  Round 5: both
directions are hand-written kernels — the backward is ONE Pallas kernel
since PR 34 (``flash_dqkv``: p recomputed from saved lse, delta term,
causal loop bounds, dq summed over the key blocks in a VMEM scratch) and
must match differentiating the scan formulation and a dense XLA softmax
reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.parallel.ring_attention import blockwise_attention


@pytest.fixture(autouse=True)
def _interpret():
    pa.INTERPRET = True
    yield
    pa.INTERPRET = False


def _case(B=2, H=2, T=64, D=16, seed=0):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.standard_normal((B, H, T, D)) * 0.5, jnp.float32)
    k = jnp.asarray(r.standard_normal((B, H, T, D)) * 0.5, jnp.float32)
    v = jnp.asarray(r.standard_normal((B, H, T, D)) * 0.5, jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_blockwise(causal):
    q, k, v = _case()
    ref = blockwise_attention(q, k, v, block_size=32, causal=causal,
                              use_pallas=False)
    got = pa.flash_attention(q, k, v, causal, None, 16, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_backward_matches_blockwise():
    q, k, v = _case(seed=3)

    def loss_p(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v, True, None, 16, 32)
                       ** 2)

    def loss_r(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, block_size=32,
                                           causal=True,
                                           use_pallas=False) ** 2)

    gp = jax.grad(loss_p, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
    for a, b, n in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=n)


def test_blockwise_lowering_selects_scan_off_tpu():
    """Advisor r03 regression: with the size gate open and INTERPRET off,
    a CPU compilation of blockwise_attention must lower the scan branch
    (lax.platform_dependent), never the Mosaic kernel — which would error
    at CPU lowering, so compiling+running proves the selection.  Gradient
    must flow through the platform branch too."""
    pa.INTERPRET = False             # defeat the autouse interpret fixture
    q, k, v = _case(T=2048)          # above the non-interpret min-Tk gate
    assert pa.flash_attention_available(2, 2, 2048, 2048, 16)

    f = jax.jit(lambda q, k, v: blockwise_attention(
        q, k, v, block_size=128, causal=True))
    txt = f.lower(q, k, v).compile().as_text()
    assert "tpu_custom_call" not in txt and "Mosaic" not in txt
    got = f(q, k, v)
    ref = blockwise_attention(q, k, v, block_size=32, causal=True,
                              use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    g = jax.grad(lambda q: jnp.sum(blockwise_attention(
        q, k, v, block_size=128, causal=True) ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(blockwise_attention(
        q, k, v, block_size=32, causal=True, use_pallas=False) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-4, atol=1e-4)


def _full_ref(q, k, v, causal=False):
    d = q.shape[-1]
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) \
        / np.sqrt(d)
    if causal:
        t = s.shape[-2]
        mask = np.arange(t)[:, None] >= np.arange(t)[None, :]
        s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_scan_and_reference(causal):
    """Round-4 verdict item 4: the ring path dispatches the flash kernel
    per resident shard (interpret mode here), with the exact (m, l, acc)
    cross-shard combine.  T_loc = 512/4 = 128 satisfies the kernel's
    lane-size gate — the ring decomposition is what makes the kernel
    applicable at long T."""
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.ring_attention import ring_attention
    r = np.random.default_rng(0)
    B, H, T, D = 1, 2, 512, 16
    q, k, v = (jnp.asarray(r.standard_normal((B, H, T, D)) * 0.5,
                           jnp.float32) for _ in range(3))
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    got = ring_attention(q, k, v, mesh, axis="sp", causal=causal,
                         block_size=128)
    scan = ring_attention(q, k, v, mesh, axis="sp", causal=causal,
                          block_size=128, use_pallas=False)
    ref = _full_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(scan),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_gradient_matches_scan(causal):
    """Round-5: the ring backward runs the Pallas dq/dk/dv kernels per
    shard (dk/dv accumulators ride the ring with their K/V shard);
    gradients must match differentiating the scan ring to 1e-5."""
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.ring_attention import ring_attention
    r = np.random.default_rng(1)
    B, H, T, D = 1, 1, 512, 8
    q, k, v = (jnp.asarray(r.standard_normal((B, H, T, D)) * 0.5,
                           jnp.float32) for _ in range(3))
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])

    def loss(use_pallas):
        def f(q, k, v):
            out = ring_attention(q, k, v, mesh, axis="sp", causal=causal,
                                 block_size=128, use_pallas=use_pallas)
            return jnp.sum(out ** 2)
        return f

    gp = jax.grad(loss(True), (0, 1, 2))(q, k, v)
    gs = jax.grad(loss(False), (0, 1, 2))(q, k, v)
    for a, b, nme in zip(gp, gs, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=nme)


def test_flash_bwd_kernel_exact_vs_dense():
    """flash_attention grads vs a dense softmax reference differentiated
    by XLA — pins the dq/dk/dv kernel math (p from lse, delta term,
    causal bounds) independently of the scan formulation."""
    q, k, v = _case(B=1, H=2, T=128, D=16, seed=7)

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (q.shape[-1] ** 0.5)
        mask = jnp.tril(jnp.ones((q.shape[2], k.shape[2]), bool))
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    co = jnp.asarray(np.random.default_rng(9).standard_normal(
        q.shape), jnp.float32)
    gp = jax.grad(lambda *a: jnp.vdot(
        pa.flash_attention(*a, True, None, 32, 32), co), (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: jnp.vdot(dense(*a), co), (0, 1, 2))(q, k, v)
    for a, b, nme in zip(gp, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5, err_msg=nme)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("causal", [False, True])
def test_mha_op_flash_matches_reference(causal, dtype):
    """ISSUE 20: the MultiHeadAttention op's two dispatch arms agree.
    With INTERPRET on, the op runs the Pallas flash kernel (interpret
    mode); with it off on CPU, the Tk<2048 size gate closes and the op
    runs the dense XLA reference — same weights, both precisions, both
    mask modes.  This is the default-path parity the flash-by-default
    dispatch rests on."""
    from mxnet_tpu.ops.registry import OPS
    B, T, Dm, Hn = 2, 128, 64, 4
    r = np.random.default_rng(5)
    x = jnp.asarray(r.standard_normal((B, T, Dm)) * 0.5, dtype)
    ws = [jnp.asarray(r.standard_normal((Dm, Dm)) * 0.1, dtype)
          for _ in range(4)]
    attrs = {"num_heads": Hn, "causal": causal}
    fn = OPS["MultiHeadAttention"].fn

    got = fn(attrs, x, *ws)          # autouse fixture: flash (interpret)
    assert pa.flash_attention_available(B, Hn, T, T, Dm // Hn, dtype)
    pa.INTERPRET = False             # closes the size gate -> reference
    assert not pa.flash_attention_available(B, Hn, T, T, Dm // Hn, dtype)
    ref = fn(attrs, x, *ws)
    pa.INTERPRET = True

    assert got.dtype == x.dtype
    tol = {"rtol": 2e-5, "atol": 2e-5} if dtype == jnp.float32 else \
        {"rtol": 2e-2, "atol": 2e-2}
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), **tol)


def test_mha_op_flash_gradients_match_reference():
    """Op-level backward parity: d(loss)/d(all five inputs) through the
    flash (interpret) arm vs the reference arm."""
    from mxnet_tpu.ops.registry import OPS
    B, T, Dm, Hn = 1, 128, 32, 2
    r = np.random.default_rng(11)
    x = jnp.asarray(r.standard_normal((B, T, Dm)) * 0.5, jnp.float32)
    ws = [jnp.asarray(r.standard_normal((Dm, Dm)) * 0.1, jnp.float32)
          for _ in range(4)]
    fn = OPS["MultiHeadAttention"].fn

    def loss(*args):
        return jnp.sum(fn({"num_heads": Hn, "causal": True}, *args) ** 2)

    gf = jax.grad(loss, tuple(range(5)))(x, *ws)
    pa.INTERPRET = False
    gr = jax.grad(loss, tuple(range(5)))(x, *ws)
    pa.INTERPRET = True
    for a, b, nme in zip(gf, gr, ("x", "wq", "wk", "wv", "wo")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=nme)


def test_ring_flash_bwd_8way_mesh():
    """The done-criterion shape: 8-way virtual mesh, grads vs the scan
    ring to <=1e-5 rel (VERDICT r4 item 1)."""
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.ring_attention import ring_attention
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    r = np.random.default_rng(2)
    B, H, T, D = 2, 2, 1024, 16
    q, k, v = (jnp.asarray(r.standard_normal((B, H, T, D)) * 0.5,
                           jnp.float32) for _ in range(3))
    mesh = make_mesh({"sp": 8})

    def loss(use_pallas):
        def f(q, k, v):
            out = ring_attention(q, k, v, mesh, axis="sp", causal=True,
                                 block_size=128, use_pallas=use_pallas)
            return jnp.sum(out ** 2)
        return f

    gp = jax.grad(loss(True), (0, 1, 2))(q, k, v)
    gs = jax.grad(loss(False), (0, 1, 2))(q, k, v)
    for a, b, nme in zip(gp, gs, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=nme)


def _dense(q, k, v, causal):
    """Dense softmax attention in float32 at the highest matmul precision:
    the reference the re-blocked kernels are held to."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") / (q.shape[-1] ** 0.5)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


@pytest.mark.parametrize("blocks", [(None, None), (256, 256), (128, 256)],
                         ids=["one-block", "256x256", "128x256"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("causal", [False, True])
def test_reblocked_kernels_at_the_cells_shape(causal, dtype, blocks):
    """ISSUE 26: T 1024 / d 64, the GPT-2 cells' sequence.  ``one-block`` is
    the shape's own choice (one 1024-block whose diagonal is cut into
    bands); ``256x256`` skips whole blocks past the diagonal and masks only
    the diagonal block, in bands; ``128x256`` takes the general path (two
    loops, the second one masked).  Forward and q/k/v gradients against
    the dense float32 reference prove the bounds and the diagonal-only mask
    exact."""
    r = np.random.default_rng(26)
    q, k, v, co = (jnp.asarray(r.standard_normal((1, 2, 1024, 64)) * 0.5,
                               dtype) for _ in range(4))
    got_o = pa.flash_attention(q, k, v, causal, None, *blocks)
    got = jax.grad(lambda *a: jnp.vdot(
        pa.flash_attention(*a, causal, None, *blocks).astype(jnp.float32),
        co.astype(jnp.float32)), (0, 1, 2))(q, k, v)
    want_o = _dense(q, k, v, causal)
    want = jax.grad(lambda *a: jnp.vdot(_dense(*a, causal),
                                        co.astype(jnp.float32)),
                    (0, 1, 2))(q, k, v)
    assert got_o.dtype == dtype and all(g.dtype == dtype for g in got)
    # bf16: the kernel rounds p and ds to bf16 as MXU operands, results too
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for a, b, nme in zip((got_o,) + got, (want_o,) + want,
                         ("out", "dq", "dk", "dv")):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a, np.float32) / scale,
                                   np.asarray(b, np.float32) / scale,
                                   rtol=0, atol=tol, err_msg=nme)


@pytest.mark.parametrize("shape,kernel", [
    ((1, 16, 1024, 64), True),      # one chip's rows of both GPT-2 cells
    ((4, 16, 1024, 64), True),
    ((1, 64, 512, 64), True),       # 64 MB of scores: the smallest that wins
    ((1, 16, 2048, 128), True),
    ((1, 16, 512, 64), False),      # 16 MB of scores stay in VMEM: XLA wins
    ((1, 64, 256, 128), False),
    ((2, 4, 32, 16), False),        # the CPU rehearsals' T
    ((1, 16, 1024, 60), False),     # d off the sublane tiling
    ((1, 16, 16384, 128), False),   # K/V past the VMEM envelope
])
def test_mha_shape_test_is_the_measured_table(shape, kernel):
    """ISSUE 26: ``MultiHeadAttention`` chooses kernel or XLA arm from what
    it observes in its input, after the crossover measured with
    ``tools/bench_attention_arms.py`` — and ring attention's gate, which no
    cell judges, still says what it said."""
    from mxnet_tpu.ops.nn import mha_uses_kernel
    pa.INTERPRET = False            # the autouse hook admits every shape
    assert mha_uses_kernel(*shape, jnp.bfloat16) is kernel
    assert not pa.flash_attention_available(1, 16, 1024, 1024, 64,
                                            jnp.bfloat16)
    assert pa.flash_attention_available(1, 16, 2048, 2048, 64, jnp.bfloat16)


def test_mha_op_keeps_each_device_on_its_own_rows():
    """ISSUE 26, tentpole step 4: under ``jax.jit`` with the batch sharded
    ``P('dp')`` over four (virtual) devices and the mesh in context, as
    ``ModuleFusedStep.step`` traces its mesh program, the op's kernel
    calls run under ``shard_map``: value and gradients equal the unsharded
    run, and the compiled module gathers nothing."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.ops.registry import OPS
    B, T, Dm, Hn = 4, 128, 64, 4
    r = np.random.default_rng(5)
    x = jnp.asarray(r.standard_normal((B, T, Dm)) * 0.5, jnp.float32)
    ws = [jnp.asarray(r.standard_normal((Dm, Dm)) * 0.1, jnp.float32)
          for _ in range(4)]
    fn = OPS["MultiHeadAttention"].fn
    step = jax.value_and_grad(
        lambda *a: jnp.sum(fn({"num_heads": Hn, "causal": True}, *a) ** 2),
        tuple(range(5)))
    want = step(x, *ws)

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    xs = jax.device_put(x, NamedSharding(mesh, P("dp")))
    wr = [jax.device_put(w, NamedSharding(mesh, P())) for w in ws]
    with jax.set_mesh(mesh):
        assert pa.rows_per_device(B, Hn) == (1, Hn)
        jitted = jax.jit(step)
        got = jitted(xs, *wr)
        hlo = jitted.lower(xs, *wr).compile().as_text()
    assert pa.rows_per_device(B, Hn) == (B, Hn)      # no mesh, no split
    assert "all-gather" not in hlo and "all-to-all" not in hlo
    assert got[1][0].sharding.spec == P("dp")
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    for a, b, nme in zip(got[1], want[1], ("x", "wq", "wk", "wv", "wo")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4, err_msg=nme)


def _parts(r, B, H, T, dtype):
    """A head of 256 put together as GLM-4.7-Flash's latent attention does:
    192 dims without position and 64 rotary ones, the key's rotary part ONE
    for all heads."""
    q_nope, k_nope = (r.standard_normal((B, H, T, 192)) * 0.3
                      for _ in range(2))
    q_rope = r.standard_normal((B, H, T, 64)) * 0.3
    k_rope = r.standard_normal((B, 1, T, 64)) * 0.3
    return tuple(jnp.asarray(a, dtype)
                 for a in (q_nope, q_rope, k_nope, k_rope))


def _glm_heads(q_nope, q_rope, k_nope, k_rope):
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
    return q, k


# (q shape, key length, blocks, causal, window, dtype, out_dtype)
_FUSED = {
    "one-square-block-4-heads": ((1, 4, 256, 16), 256, (None, None), True,
                                 None, jnp.float32, None),
    "one-block-bf16": ((1, 4, 256, 16), 256, (None, None), True, None,
                       jnp.bfloat16, None),
    "four-blocks-causal": ((1, 2, 512, 16), 512, (128, 128), True, None,
                           jnp.float32, None),
    "oblong-blocks-causal": ((1, 2, 512, 16), 512, (128, 256), True, None,
                             jnp.float32, None),
    "window-inside-a-block": ((1, 2, 512, 16), 512, (128, 128), True, 96,
                              jnp.float32, None),
    "window-of-two-blocks": ((1, 2, 512, 16), 512, (128, 128), True, 256,
                             jnp.float32, None),
    "window-to-position-0": ((1, 2, 512, 16), 512, (128, 128), True, 512,
                             jnp.float32, None),
    "ring-off-diagonal-Tq-ne-Tk": ((1, 2, 256, 16), 512, (128, 128), False,
                                   None, jnp.float32, None),
    "bf16-out-float32": ((1, 2, 256, 16), 256, (128, 128), True, None,
                         jnp.bfloat16, jnp.float32),
    "bf16-out-bf16": ((1, 2, 256, 16), 256, (128, 128), True, None,
                      jnp.bfloat16, jnp.bfloat16),
    "head-of-256-as-glm": ((1, 2, 256, 256), 256, (128, 128), True, None,
                           jnp.float32, "glm"),
}


@pytest.mark.parametrize("case", list(_FUSED))
def test_fused_backward_matches_the_reference(case):
    """ISSUE 34: ``flash_dqkv``'s dq, dk and dv against the gradients of
    ``ops.nn._mha_reference`` — one block whose diagonal is all there is
    (dq from the bands), several blocks (dq summed over the key blocks'
    programs), the window's three loop shapes, the ring's off-diagonal
    shard (not causal, Tq != Tk), both result dtypes of
    ``flash_attention_bwd``, and a head of 256."""
    from mxnet_tpu.ops.nn import _mha_reference
    shape, Tk, blocks, causal, window, dtype, out = _FUSED[case]
    B, H, Tq, D = shape
    r = np.random.default_rng(34)
    scale = D ** -0.5
    f32 = jnp.float32

    def want_of(fn, *xs):
        return jax.grad(lambda *a: jnp.vdot(
            fn(*(x.astype(f32) for x in a)), co.astype(f32)),
            tuple(range(len(xs))))(*xs)

    if out == "glm":
        parts = _parts(r, B, H, Tq, dtype)
        v, co = (jnp.asarray(r.standard_normal(shape) * 0.5, dtype)
                 for _ in range(2))
        got = jax.grad(lambda *a: jnp.vdot(pa.flash_attention(
            *_glm_heads(*a[:4]), a[4], causal, None, *blocks), co),
            tuple(range(5)))(*parts, v)
        want = want_of(lambda *a: _mha_reference(
            *_glm_heads(*a[:4]), a[4], causal, scale), *parts, v)
        names = ("q_nope", "q_rope", "k_nope", "k_rope", "v")
    else:
        q, co = (jnp.asarray(r.standard_normal(shape) * 0.5, dtype)
                 for _ in range(2))
        k, v = (jnp.asarray(r.standard_normal((B, H, Tk, D)) * 0.5, dtype)
                for _ in range(2))
        want = want_of(lambda *a: _mha_reference(*a, causal, scale, window),
                       q, k, v)
        names = ("dq", "dk", "dv")
        if out is None:
            got = jax.grad(lambda *a: jnp.vdot(pa.flash_attention(
                *a, causal, None, *blocks, window).astype(f32),
                co.astype(f32)), (0, 1, 2))(q, k, v)
            assert all(g.dtype == dtype for g in got)
        else:       # the ring's call: statistics handed in, dtype named
            o, lse = pa._flash_fwd_call(q, k, v, causal, scale, *blocks,
                                        "lse")
            delta = jnp.sum(co.astype(f32) * o.astype(f32), axis=-1)
            got = pa.flash_attention_bwd(q, k, v, co, lse, delta, causal,
                                         scale, *blocks, out_dtype=out)
            assert all(g.dtype == out for g in got)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for a, b, nme in zip(got, want, names):
        size = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a, np.float32) / size,
                                   np.asarray(b, np.float32) / size,
                                   rtol=0, atol=tol, err_msg=nme)


def test_backward_counts_one_fused_program_a_build():
    """``attention_backward_total{form="fused"}`` rises once where
    ``flash_attention_bwd`` builds a kernel program, not once a call, and
    the family has no other form."""
    from mxnet_tpu import telemetry
    q, k, v = _case(B=1, H=3, T=128, D=24, seed=34)     # no other test's

    def grads():
        return jax.grad(lambda *a: jnp.sum(
            pa.flash_attention(*a, True, None, 64, 64) ** 2),
            (0, 1, 2))(q, k, v)

    telemetry.enable()
    try:
        fused0 = telemetry.value("attention_backward_total", form="fused")
        grads()
        assert telemetry.value("attention_backward_total",
                               form="fused") == fused0 + 1
        grads()
        assert telemetry.value("attention_backward_total",
                               form="fused") == fused0 + 1
        forms = {row["labels"]["form"] for row in telemetry.snapshot()[
            "attention_backward_total"]["samples"]}
        assert forms == {"fused"}
    finally:
        telemetry.disable()
