"""The chip's compiler, asked without the chip: every Pallas entry point of
the main path must compile for a described TPU v5e at the widths
``chip_smoke.py`` and the benchmark run them at.

A compile that passes here is not a chip run and says nothing about
results or times; it catches what interpret-mode tests cannot (a slice
off the tiling, too much VMEM, an API spelling the installed jax dropped).

This is the only file that describes a topology.  It does so inside a
module-scoped, non-autouse fixture, never at import, in a ``skipif`` or in
``parametrize``: only the process that runs these tests loads the TPU
library, and it compiles in-process.
"""
import collections
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip; keep it out of the way."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _custom_calls(fn, *avals):
    """Compile ``fn`` for the device the avals are placed on; the number
    of Mosaic kernels in the result."""
    return jax.jit(fn).lower(*avals).compile().as_text().count(
        "tpu_custom_call")


def _sq(fn):
    """Scalar loss over every output of ``fn`` (for ``jax.grad``)."""
    def loss(*args):
        return sum(jnp.sum(o.astype(jnp.float32) ** 2)
                   for o in jax.tree_util.tree_leaves(fn(*args)))
    return loss


def test_described_device_is_the_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    from mxnet_tpu import health
    assert health.peak_tflops("bfloat16", topo.devices[0].device_kind) == 197.0


@pytest.mark.parametrize("shape", [(8, 12, 2048, 64), (1, 16, 1024, 64)],
                         ids=["chip_smoke", "gpt2m_cells"])
@pytest.mark.parametrize("direction", ["forward", "forward+backward"])
def test_flash_attention_compiles(one_chip, direction, shape):
    """chip_smoke's attention width, and one chip's rows of the GPT-2
    cells: (1, 16, 1024, 64) bf16 causal, one 1024-block."""
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops.nn import mha_uses_kernel
    B, H, T, D = shape
    assert mha_uses_kernel(B, H, T, D, jnp.bfloat16)
    q = jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16, sharding=one_chip)
    fn = functools.partial(pa.flash_attention, causal=True)
    if direction == "forward":
        assert _custom_calls(fn, q, q, q) == 1
    else:       # forward-with-lse, and the one backward kernel
        assert _custom_calls(jax.grad(_sq(fn), (0, 1, 2)), q, q, q) == 2


def test_mha_op_compiles_for_four_chips_each_on_its_own_sequence(topo):
    """``gpt2m_train_dp4``'s attention, ahead of time: the op over
    (4, 1024, 1024) with the batch sharded over the four chips of the
    described host and the mesh in context, as the mesh fused step traces
    it.  Forward + backward hold two Mosaic calls, each over ONE
    sequence's 16 heads, and nothing is gathered: a bare ``pallas_call``
    would have every chip run all four."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.ops.registry import OPS
    B, T, Dm, Hn = 4, 1024, 1024, 16
    mesh = Mesh(np.array(topo.devices), ("dp",))
    x = jax.ShapeDtypeStruct((B, T, Dm), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))
    w = jax.ShapeDtypeStruct((Dm, Dm), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    fn = functools.partial(OPS["MultiHeadAttention"].fn,
                           {"num_heads": Hn, "causal": True})
    with jax.set_mesh(mesh):
        hlo = jax.jit(jax.grad(_sq(fn), (0, 1, 2, 3, 4))).lower(
            x, w, w, w, w).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2
    for ln in calls:            # results and operands: one sequence, 16 heads
        assert "bf16[16,1024,64]" in ln and "bf16[64,1024,64]" not in ln
    assert "all-gather" not in hlo and "all-to-all" not in hlo


def test_grouped_rotary_attention_reaches_the_kernels(one_chip):
    """``lfm2moe_train_2k``'s attention layer, ahead of time: 32 query heads
    over 8 key/value heads of 64 at T 2048, per-head norms and rotary
    positions.  Forward + backward hold the two Mosaic calls over 32
    heads in one 2048-block, and no T x T tensor."""
    from mxnet_tpu.ops.registry import OPS
    op = OPS["MultiHeadAttention"]
    attrs = op.parse_attrs(dict(num_heads=32, num_kv_heads=8, qk_norm=True,
                                rope_theta=1e6))

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds(1, 2048, 2048), sds(2048, 2048), sds(512, 2048),
            sds(512, 2048), sds(2048, 2048), sds(64, dtype=jnp.float32),
            sds(64, dtype=jnp.float32))
    fn = functools.partial(op.fn, attrs)
    hlo = jax.jit(jax.grad(_sq(fn), tuple(range(7)))).lower(
        *args).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2
    assert all("bf16[32,2048,64]" in ln for ln in calls)
    assert "[1,32,2048,2048]" not in hlo and "[32,2048,2048]" not in hlo


def test_sparse_moe_compiles_at_the_cells_widths(one_chip):
    """``lfm2moe_train_2k``'s expert layer, ahead of time: 2048 tokens, 64
    experts routed, 8 held of 1536, forward + backward.  The held experts'
    products are the compiler's own dense products over every token (nine,
    0.93 TFLOP a layer: nothing whose extent follows the routing, no kernel
    call, no grouped product), each with the einsum's subscripts in its scope,
    by which ``moe_experts_roofline_pct.train`` finds them, in well under a
    GB of temporaries."""
    from mxnet_tpu.ops.registry import OPS
    op = OPS["SparseMoE"]
    attrs = op.parse_attrs(dict(num_experts=64, num_experts_per_tok=4,
                                num_hidden=1536, num_held=8))

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds(1, 2048, 2048), sds(64, 2048, dtype=jnp.float32),
            sds(64, dtype=jnp.float32), sds(8, 1536, 2048),
            sds(8, 1536, 2048), sds(8, 2048, 1536),
            sds(64, dtype=jnp.float32))

    def loss(*a):
        with jax.named_scope("SparseMoE:tfm_l1_moe"):   # as the registry does
            return jnp.sum(op.fn(attrs, *a)[0].astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 3, 4, 5))).lower(
        *args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" not in hlo and "ragged-dot" not in hlo
    assert " while(" not in hlo and " conditional(" not in hlo
    reads = re.compile(
        r"SparseMoE:[^ ]*/(nd,efd->nef|nef,edf->nd)/dot_general")
    products = [ln for ln in hlo.splitlines()
                if " convolution(" in ln and reads.search(ln)]
    assert len(products) == 9
    assert 0.9e12 < compiled.cost_analysis()["flops"] < 1.0e12
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_mellum_attention_reaches_its_kernels(one_chip, kind):
    """``mellum2moe_train_4k``'s two kinds of attention layer, ahead of time:
    32 query heads of 128 over 4 key/value heads under a 2304-wide stream at
    T 4096, in 512-blocks; a window of 1024 with the default rope, or full
    attention under YaRN.  Forward + backward hold the two Mosaic calls
    over 32 heads, no T x T tensor, and the sliding layer's calls carry the
    scope ``window_flash_roofline_pct.train`` finds them by."""
    import json
    import os
    from mxnet_tpu.ops.registry import OPS
    op = OPS["MultiHeadAttention"]
    variant = dict(window=1024) if kind == "sliding_attention" else dict(
        rope_yarn=(16.0, 8192.0, 32.0, 1.0, 1.2772588722239782))
    attrs = op.parse_attrs(dict(num_heads=32, num_kv_heads=4, head_dim=128,
                                rope_theta=5e5, **variant))
    node = "tfm_l0_swa" if kind == "sliding_attention" else "tfm_l3_attn"

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    args = (sds(1, 4096, 2304), sds(4096, 2304), sds(512, 2304),
            sds(512, 2304), sds(2304, 4096))

    def loss(*a):
        with jax.named_scope("MultiHeadAttention:" + node):
            return jnp.sum(op.fn(attrs, *a).astype(jnp.float32) ** 2)

    hlo = jax.jit(jax.grad(loss, tuple(range(5)))).lower(
        *args).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2
    assert all("bf16[32,4096,128]" in ln for ln in calls)
    assert "[1,32,4096,4096]" not in hlo and "[32,4096,4096]" not in hlo
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "metrics",
                           "window_flash_roofline_pct.train.json")) as f:
        reads = re.compile(json.load(f)["params"]["kernel"])
    found = [bool(reads.search(ln)) for ln in calls]
    assert found == [kind == "sliding_attention"] * 2


def test_latent_attention_reaches_the_kernels_at_a_head_of_256(one_chip):
    """``glm47flash_train_4k``'s attention layer, ahead of time: 20 heads of
    192 + 64 (values of 256) under ranks 768 / 512 and a 2048-wide stream at
    T 4096.  A head's K and V are 4 MB, 8 MB double-buffered: past
    ``kv_fits_vmem``'s own 5 MB, inside ``MHA_KV_VMEM``.  Forward + backward
    hold the two Mosaic calls over 20 heads of 256 in 512-blocks, no
    T x T tensor, and the calls carry the scope
    ``mla_flash_roofline_pct.train`` finds them by."""
    import json
    import os
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops.nn import mha_uses_kernel
    from mxnet_tpu.ops.registry import OPS
    assert not pa.kv_fits_vmem(4096, 256, jnp.bfloat16)
    assert mha_uses_kernel(1, 20, 4096, 256, jnp.bfloat16)
    assert not mha_uses_kernel(1, 20, 8192, 256, jnp.bfloat16)
    op = OPS["MultiHeadAttention"]
    attrs = op.parse_attrs(dict(
        num_heads=20, head_dim=256, qk_rope_head_dim=64, q_lora_rank=768,
        kv_lora_rank=512, v_head_dim=256, rope_theta=1e6, eps=1e-5))

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds(1, 4096, 2048), sds(768, 2048), sds(768, dtype=jnp.float32),
            sds(5120, 768), sds(576, 2048), sds(512, dtype=jnp.float32),
            sds(8960, 512), sds(2048, 5120))

    def loss(*a):
        with jax.named_scope("MultiHeadAttention:tfm_l1_mla"):
            return jnp.sum(op.fn(attrs, *a).astype(jnp.float32) ** 2)

    hlo = jax.jit(jax.grad(loss, tuple(range(8)))).lower(
        *args).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2
    assert all("bf16[20,4096,256]" in ln for ln in calls)
    assert "[1,20,4096,4096]" not in hlo and "[20,4096,4096]" not in hlo
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "metrics",
                           "mla_flash_roofline_pct.train.json")) as f:
        reads = re.compile(json.load(f)["params"]["kernel"])
    assert all(reads.search(ln) for ln in calls)


@pytest.mark.parametrize("direction", ["forward", "backward",
                                       "backward-diagonal"])
def test_flash_attention_ring_variant_compiles(one_chip, direction):
    """The stats-emitting kernel ring attention runs per shard, and the
    backward it feeds with full-sequence stats: ONE kernel, ``flash_dqkv``,
    whose three results are float32, off the diagonal shard (not causal)
    and on it."""
    from mxnet_tpu.ops import pallas_attention as pa
    B, H, T, D = 8, 12, 2048, 64
    q = jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16, sharding=one_chip)
    stat = jax.ShapeDtypeStruct((B, H, T), jnp.float32, sharding=one_chip)
    if direction == "forward":
        fn = functools.partial(pa.flash_attention_stats, causal=True,
                               scale=D ** -0.5)
        assert _custom_calls(fn, q, q, q) == 1
        return
    fn = functools.partial(pa.flash_attention_bwd, scale=D ** -0.5,
                           causal=direction == "backward-diagonal")
    hlo = jax.jit(fn).lower(q, q, q, q, stat, stat).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "flash_dqkv" in calls[0]
    assert calls[0].count("f32[96,2048,64]") >= 3


@pytest.mark.parametrize("shape", [(1, 20, 4096, 256), (1, 4, 8192, 128),
                                   (1, 4, 16384, 64)],
                         ids=lambda s: "x".join(map(str, s)))
def test_fused_backward_fits_the_envelope_mha_admits(one_chip, shape):
    """The largest shapes ``mha_uses_kernel`` lets in (``MHA_KV_VMEM``: a
    head's K and V of 8 MB double-buffered) build ONE backward kernel that
    holds the head's Q, dO and float32 dq whole: 18.5 MB of buffers at
    GLM's head of 256 and at 8,192 x 128, inside the 32 MB the kernels ask
    for anyway, and 35 MB at 16,384 x 64, whose lanes are padded to 128:
    there the kernel asks for what ``_bwd_vmem_bytes`` reckons."""
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops.nn import mha_uses_kernel
    B, H, T, D = shape
    assert mha_uses_kernel(B, H, T, D, jnp.bfloat16)
    need = pa._bwd_vmem_bytes(1, T, 512, 512, D, 2, 2)
    assert (need + pa._TILE_ROOM > pa._VMEM_LIMIT) == (shape[2] == 16384)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    stat = jax.ShapeDtypeStruct((B, H, T), jnp.float32, sharding=one_chip)
    fn = functools.partial(pa.flash_attention_bwd, causal=True,
                           scale=D ** -0.5, block_q=None, block_k=None,
                           out_dtype=jnp.bfloat16)
    hlo = jax.jit(fn).lower(q, q, q, q, stat, stat).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "flash_dqkv" in calls[0]


@pytest.mark.parametrize("direction", ["forward", "forward+backward"])
def test_lstm_scan_compiles(one_chip, direction):
    from mxnet_tpu.ops import pallas_rnn
    T, B, H = 35, 128, 650                # the LSTM-LM benchmark width
    assert pallas_rnn.lstm_scan_available(B, H, jnp.bfloat16)

    def aval(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    args = (aval(T, B, 4 * H), aval(B, H), aval(B, H), aval(4 * H, H),
            aval(4 * H))
    if direction == "forward":
        assert _custom_calls(pallas_rnn.lstm_scan, *args) == 1
    else:
        grad = jax.grad(_sq(pallas_rnn.lstm_scan), (0, 1, 2, 3, 4))
        assert _custom_calls(grad, *args) == 2


def test_conv3x3_compiles(one_chip):
    """One ResNet-50 stage shape past the lane gate (stage 3: 256 channels,
    14 px, batch 128): forward, dgrad and wgrad are all Pallas."""
    from mxnet_tpu.ops import pallas_conv
    N, C, HW = 128, 256, 14
    assert pallas_conv._plan(N, HW, HW, C, C, 3, 3, ((1, 1), (1, 1)), 2)
    x = jax.ShapeDtypeStruct((N, C, HW, HW), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((C, C, 3, 3), jnp.bfloat16, sharding=one_chip)
    grad = jax.grad(_sq(pallas_conv.conv3x3_same), (0, 1))
    assert _custom_calls(grad, x, w) == 3


@pytest.mark.parametrize("exchange", ["row", "async"])
def test_mesh_step_splits_the_update_over_dp(topo, monkeypatch, exchange):
    """``gpt2m_train_dp4``'s step in small, ahead of time: a two-layer
    transformer's mesh fused step (Adam, bf16 weights with float32
    masters, the batch ``P('dp')``) compiled for the four described chips
    with the optimizer's state in the layout ``state_sharding`` gives it.
    Every large leaf's bf16 weight arrives as the quarter a chip holds and
    is all-gathered (under ``GradSync``) in the forward half of the
    program, ahead of the product that reads it: none after the first
    backward instruction, none after the last update fusion, and no
    ``copy`` of a gathered weight anywhere (the gathered weight is a
    temporary, never an alias of a donated input).  A chip updates its
    quarter (under ``Optimizer::Adam``) and the new weight leaves as that
    quarter; its gradient reaches the update through a reduce-scatter
    (the TPU compiler's ``all-reduce-scatter`` fusion; the token table's
    through an all-to-all of the rows' gradients), no all-reduce left in
    the program carries a large gradient, and nothing else is gathered:
    the constraint on the gradient does not pull an activation or a
    weight-gradient product into another partitioning.

    ``row`` is that program as CPU meshes and meshes with a ``tp`` extent
    build it (``exchange_path`` answered for here): the gradients'
    ``all-reduce-scatter`` fusions, which block the core, stand in a row
    behind the last backward product.  ``async`` is what the described
    TPUs get by themselves: no such fusion is left for a weight that
    ``matmul_wt`` multiplies; its gradient goes round the ring as
    ``collective-permute-start`` / ``-done`` pairs of bf16 eighths (half a
    quarter a way), six a leaf, nearly all of their bytes with a backward
    product between start and done, none begun behind the last backward
    product but the last ring's, and the updates follow their rings in
    among backward's products."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.models import transformer_lm
    from mxnet_tpu.models.configs import TransformerConfig
    from mxnet_tpu.parallel.mesh import (STATE_SHARD_MIN_ELEMENTS,
                                         state_sharding)
    from mxnet_tpu.parallel import mesh as pmesh
    monkeypatch.setenv(amp.ENV_FLAG, "1")
    asked = []
    real_path = pmesh.exchange_path
    monkeypatch.setattr(
        pmesh, "exchange_path",
        lambda *a: asked.append(real_path(*a)) or exchange)
    B, T, V, D = 4, 128, 1001, 256
    mod = mx.mod.Module(
        transformer_lm(TransformerConfig("small", V, 2, D, 2, 4 * D, T),
                       prefix="tfm_"),
        data_names=("data",), label_names=("softmax_label",),
        context=[mx.cpu(i) for i in range(4)])
    mod.bind(data_shapes=[("data", (B, T))],
             label_shapes=[("softmax_label", (B, T))])
    mod.init_params(mx.init.Uniform(0.01))
    mod.init_optimizer(kvstore="local", optimizer="adam",
                       optimizer_params={"learning_rate": 1e-4,
                                         "multi_precision": True})
    fs, ex, opt = mod._fused_step, mod._exec_group.execs[0], mod._optimizer
    pnames = fs._pnames
    mesh = Mesh(np.array(topo.devices), ("dp",))
    repl = NamedSharding(mesh, P())

    def sds(shape, dtype, sharding=repl):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    shapes = [ex.arg_dict[n].shape for n in pnames]
    ssh = [state_sharding(repl, s) for s in shapes]
    large = [s for s, sh in zip(shapes, ssh) if sh is not repl]
    assert len(large) == 14 and (V, D) in large     # table, head, 2 x 6
    assert all(int(np.prod(s)) >= STATE_SHARD_MIN_ELEMENTS for s in large)
    # matrices bf16 under a float32 master, LayerNorm's leaves float32
    mp = [opt.fused_mp(ex.arg_dict[n]) for n in pnames]
    assert all(m for m, sh in zip(mp, ssh) if sh is not repl)
    fn = ex.step_program(
        pnames, [opt.fused_update_mp if m else opt.fused_update for m in mp],
        mesh_sig=("described",), param_shardings=[repl] * len(pnames),
        state_shardings=ssh)
    # a weight is taken as it is held: in its state's layout
    pvals = [sds(s, ex.arg_dict[n].dtype, sh)
             for n, s, sh in zip(pnames, shapes, ssh)]
    svals = [tuple(sds(s, jnp.float32, sh) for _ in range(3 if m else 2))
             for s, sh, m in zip(shapes, ssh, mp)]
    ids = sds((B, T), ex.arg_dict["data"].dtype, NamedSharding(mesh, P("dp")))
    plan = ex._plan(True)
    keys = ex._keys(plan)
    ogs = ex._ograds_for({**{n: ex.arg_dict[n].shape for n in ex.arg_names},
                          "data": (B, T), "softmax_label": (B, T)})
    vec = sds((len(pnames),), jnp.float32)
    with jax.set_mesh(mesh):
        compiled = fn.lower(
            pvals, svals, [ids, ids], [], sds(keys.shape, keys.dtype),
            [sds(o.shape, o.dtype) for o in ogs], vec, vec, vec,
            sds((), jnp.float32)).compile()
    hlo = compiled.as_text()
    bodies = {m.group(1): m.group(0) for m in re.finditer(
        r"^(%[\w.\-]+) \([^\n]*\) -> [^\n]*\{\n.*?^\}", hlo, re.M | re.S)}

    def called(ln):
        """The text of the computation a fusion calls."""
        found = re.search(r"calls=(%[\w.\-]+)", ln)
        return bodies.get(found.group(1), "") if found else ""

    # the new weights leave as they came: the held quarter of a large leaf
    new_p = compiled.output_shardings[0]
    assert [sh.spec for sh in new_p] == [sh.spec for sh in ssh]
    entry = hlo[hlo.index("\nENTRY "):].splitlines()
    result = re.compile(r"= \(?((?:\w+\[[\d,]*\][^ ]* ?)+)\)? ")

    def results(ln):
        """[(dtype, shape)] of the instruction's result (a tuple's parts)."""
        return [(d, tuple(int(x) for x in dims.split(",") if x))
                for d, dims in re.findall(
                    r"(\w+)\[([\d,]*)\]",
                    result.search(re.sub(r"/\*.*?\*/", "", ln)).group(1))]

    # all-gathers by channel (the compiler clones one into the variants of
    # a fusion): the new bf16 weight of every large leaf, and beside them
    # only the token ids travel (for the table's gradient)
    gathered = {re.search(r"channel_id=(\d+)", ln).group(1): results(ln)[0]
                for ln in hlo.splitlines() if " all-gather(" in ln}
    weights = [shape for dt, shape in gathered.values() if dt == "bf16"]
    assert sorted(weights) == sorted(large), weights
    assert {dt for dt, _ in gathered.values()} <= {"bf16", "s32"}, gathered
    assert asked == ["async"]       # the described chips, by themselves
    # reduce-scatters: each chip's quarter of every large gradient (the
    # token table's may come through the all-to-all instead)
    scattered = sorted(
        int(np.prod(shape)) for ln in entry
        if "calls=%all-reduce-scatter" in ln or " reduce-scatter(" in ln
        for _, shape in results(ln))
    quarter = sorted(int(np.prod(s)) // 4 for s in large)
    if exchange == "row":
        if len(scattered) == len(large) - 1:
            assert " all-to-all(" in hlo
            quarter.remove(V * D // 4)
        assert len(scattered) == len(quarter), scattered
        assert all(q <= g <= 1.1 * q for g, q in zip(scattered, quarter))
        assert "collective-permute-start" not in hlo
    # the yardstick's scopes (``optimizer_ms.train`` reads the device time
    # under ``Optimizer::``): every update fusion, which writes a chip's
    # quarter of the float32 master, mean and variance of a large leaf,
    # carries the optimizer's scope, and no gather of a new weight does
    # (they run under ``GradSync``)
    def scope(ln):
        found = re.search(r'op_name="([^"]*)"', ln)
        return found.group(1) if found else ""

    quarters = {sh.shard_shape(s) for s, sh in zip(shapes, ssh)
                if sh is not repl}
    updates = [ln for ln in entry if " fusion(" in ln
               and sum(1 for dt, shape in results(ln)
                       if dt == "f32" and shape in quarters) >= 3]
    assert len(updates) == len(large), len(updates)
    assert all("Optimizer::Adam" in scope(ln) for ln in updates), \
        [scope(ln) for ln in updates]
    gathers = [ln for ln in hlo.splitlines() if " all-gather(" in ln
               and results(ln)[0][0] == "bf16"]
    assert all("Optimizer::" not in scope(ln) and "GradSync" in scope(ln)
               for ln in gathers), [scope(ln) for ln in gathers]
    # where the gathers sit in the schedule (the entry is printed in its
    # order): a gathered weight is whole, in the entry, where a plain
    # all-gather or the done-half of an asynchronous one (which runs
    # beside the forward fusions it is threaded through) yields it
    whole = {("bf16", tuple(s)) for s in large}
    at = {"gather": [], "forward": [], "backward": [], "update": []}
    for i, ln in enumerate(entry):
        if "GradSync" in scope(ln) and " parameter(" not in ln and (
                " all-gather(" in ln or "async-collective-done" in ln) \
                and results(ln)[0] in whole:
            at["gather"].append(i)
        elif ln in updates:
            at["update"].append(i)
        elif re.search(r"transpose\(jvp\((FullyConnected|MultiHead)", ln):
            if re.search(r" (fusion|convolution|custom-call)\(", ln) \
                    and results(ln)[0][0] != "s32":
                at["backward"].append(i)    # work, not a hoisted index
        elif re.search(r"jvp\((FullyConnected|MultiHeadAttention)", ln):
            at["forward"].append(i)
    assert len(at["gather"]) == len(large), at["gather"]
    assert max(at["gather"]) < min(at["backward"])
    if exchange == "row":
        assert min(at["backward"]) < min(at["update"])
    assert max(at["gather"]) < max(at["forward"])
    # every weight the forward products read is a gathered one
    names = {re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = ", entry[i]).group(1)
             for i in at["gather"]}
    read = {n for i in at["forward"] for n in names
            if re.search(re.escape(n) + r"[,)]", entry[i])}
    assert len(read) == len(large) - 1, (len(read), len(large))  # - the table
    # the parent copied every donated weight at entry and every gathered
    # one at the end (at this size the compiler may still move a gathered
    # table into fast memory ahead of forward: not that)
    copies = [ln for i, ln in enumerate(entry)
              if re.search(r" copy\(", ln) and results(ln)[0] in whole
              and (" copy(%param" in ln or i > min(at["update"]))]
    assert not copies, copies
    # what is still all-reduced whole is small: biases, norms, the loss
    reduced = [r for ln in entry if " all-reduce(" in ln
               for r in results(ln)]
    assert reduced and all(int(np.prod(shape)) < STATE_SHARD_MIN_ELEMENTS
                           for _, shape in reduced), reduced
    if exchange == "row":
        return
    # the ring: every weight ``matmul_wt`` multiplies (all but the two
    # embedding tables) sends and receives half a quarter a hop, bf16 as
    # the row's fusions exchange it, two hops one way and one the other
    ringed = [(s, sh) for n, s, sh in zip(pnames, shapes, ssh)
              if sh is not repl and "embedding" not in n]
    assert len(ringed) == len(large) - 1        # all but the token table
    assert len(scattered) <= 1, scattered
    eighth = collections.Counter()
    for s, sh in ringed:
        q = list(sh.shard_shape(s))
        q[list(sh.spec).index("dp")] //= 2
        eighth[("bf16", tuple(q))] += 6
    starts = {re.match(r"\s*%([\w.\-]+) = ", ln).group(1): (i, results(ln)[0])
              for i, ln in enumerate(entry)
              if " collective-permute-start(" in ln}
    assert collections.Counter(r for _, r in starts.values()) == eighth
    dones = {re.search(r"collective-permute-done\(%([\w.\-]+)\)",
                       ln).group(1): i
             for i, ln in enumerate(entry)
             if " collective-permute-done(" in ln}
    assert sorted(dones) == sorted(starts)
    products = [i for i, ln in enumerate(entry)
                if re.search(r"transpose\(jvp\((FullyConnected|MultiHead)",
                             ln)
                and (" custom-call(" in ln or " convolution(" in ln
                     or "convolution(" in called(ln))]
    nbytes = covered = late = 0
    for name, (i, (_, shape)) in starts.items():
        size = 2 * int(np.prod(shape))
        nbytes += size
        covered += size * any(i < j < dones[name] for j in products)
        late += size * (i > max(products))
    assert covered >= 0.8 * nbytes, (covered, nbytes)
    assert late <= 0.1 * nbytes, (late, nbytes)
    # ... and the updates follow their rings: in among backward's products
    assert any(min(products) < i < max(products) for i in at["update"])
