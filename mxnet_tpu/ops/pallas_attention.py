"""Pallas flash-attention: ``MultiHeadAttention``'s kernel arm and the
blockwise inner loop of ring attention.

SURVEY.md §7.1 maps ring attention's hot loop to a hand-written Pallas
kernel.  ``parallel/ring_attention.py``'s building block is a
``lax.scan`` of (Q-block x K-block) updates; this module is the same
math — online-softmax with running max/sum — as ONE Pallas kernel per
(a few heads, Q-block): K/V live in VMEM, the K-block loop runs on-core,
scores/accumulators never touch HBM.  Contract: the operands enter the
MXU in their own dtype (bf16 in the benchmark's cells), scores, running
max/sum and accumulators are float32, the probabilities are rounded to
the operands' dtype as the MXU operand of the value product, and the
logsumexp, T floats a head, is the only residual.

Backward: ONE hand-written Pallas kernel, ``flash_dqkv`` (the standard
two-pass flash backward, a dk/dv kernel and a dq kernel, computes every
tile's scores, exponentials and ``dO v^T`` twice: seven products a tile
where five are needed).  The forward saves the per-row logsumexp
``lse = m + log(l)``; the backward recomputes probabilities on-core as
``p = exp(s - lse)``, computes ``delta = rowsum(dO * O)`` once in XLA,
then, grid over KV blocks, loop over Q blocks, scores transposed:
  dv_j = sum_i p_ij dO_i
  dk_j = sum_i ds_ij q_i
  dq_i += ds_ij k_j               (summed over the KV blocks, which run in
                                   order, in a float32 VMEM scratch that
                                   holds the head's whole dq; written at
                                   the last KV block)
with ``ds = p * (dp - delta) * scale``, ``dp = dO v^T``.  On the v5e the one
kernel takes 0.70-0.77 of the pair's time (2.76 against 3.96 ms at
(1, 20, 4096, 256), 0.072 against 0.093 at (1, 16, 1024, 64); PERF.md,
PR 34).  The name matters: the benchmark's roofline metrics find the
kernels by the pattern ``flash_(fwd|dq|dkv)``, which ``flash_dqkv`` matches.

Causal: blocks past the diagonal are never visited, only blocks the
diagonal crosses are masked, and in square blocks over a self-attention
the diagonal block is computed in bands with static extents
(``_bands``).  A ``window`` W on top of it (position t sees the W positions
t - W < s <= t: sliding-window attention) bounds every loop from the other
side too: key blocks wholly below the window's lower edge are never visited
(``_window_bounds``; ``_window_q_bounds`` for the backward's query loop), only
the blocks that edge crosses carry its mask, and a window that reaches
position 0 from every query (W >= T) is plain causal, the same kernels
(``kv_block_plan`` counts what the forward loop takes in and leaves out).
Without named blocks a sequence of up to 2,048 positions is
ONE block (``default_blocks``): at T 1024 / d 64 that is 0.125 ms forward +
backward for 16 heads on the v5e against 0.177 in 512-blocks (PERF.md,
PR 34) and 0.352 for the XLA arm (PR 26).

Who reaches it.  ``MultiHeadAttention`` (``ops/nn.py``), on a TPU, at the
shapes its own test ``mha_uses_kernel`` admits: float32 scores of 64 MB or
more a device, e.g. GPT-2-medium's (1, 16, 1024, 64); smaller shapes and
every CPU run keep the XLA arm.  Under a mesh in context
(``jax.set_mesh``; the mesh fused step) its three calls run under
``shard_map`` over the mesh's ``dp`` (batch) and ``tp`` (heads) axes, each
device on its own rows (``_on_own_rows``).  ``parallel/ring_attention``
(``blockwise_attention``, the per-shard ``flash_attention_stats`` /
``flash_attention_bwd``), when ``flash_attention_available`` admits the
shard: Tk >= 2048 against the
scan, K/V within the VMEM envelope.  Reference analog: none (the 2018
reference predates flash attention); ref for the surrounding design:
SURVEY.md §5.7.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telemetry

__all__ = ["flash_attention", "flash_attention_available",
           "flash_attention_stats", "flash_attention_bwd", "kv_block_plan"]

INTERPRET = False


def flash_attention_available(B, H, Tq, Tk, D, dtype=None) -> bool:
    """SIZE eligibility only — would the kernel compile on a TPU.

    No platform check here: callers resolve TPU-vs-other at LOWERING time
    via ``jax.lax.platform_dependent`` (parallel/ring_attention.py), so
    CPU-committed arrays on a TPU host lower the scan formulation instead
    of Mosaic (advisor r03)."""
    if D % 8 or Tq % 8 or Tk % 128:
        return False
    if not INTERPRET and Tk < 2048:
        # ring attention's crossover against the SCAN
        # (tools/bench_ring_attention.py ring rows, B=1 H=8 D=128 bf16, the
        # kernels as they were before PR 26): XLA's fused scan hits ~89 TF
        # at Tk=1024 and beat the kernel 4x; the kernel wins ~2x from
        # Tk=2048 up to the VMEM envelope below.  MultiHeadAttention, whose
        # competitor is the dense XLA arm, decides by ops/nn.py
        # ``mha_uses_kernel`` (tools/bench_attention_arms.py), not here.
        return False
    return kv_fits_vmem(Tk, D, dtype)


def kv_fits_vmem(Tk, D, dtype=None, limit=5 * 1024 * 1024) -> bool:
    """K+V resident in VMEM per (b,h) program, double-buffered by the
    pipeline.  Measured crossover (tools/bench_ring_attention.py):
    the kernel wins 1.9x while K/V stream from VMEM comfortably
    (T=4096/D=128), loses once the resident set crowds the 16 MB
    scoped-vmem limit (T=8192: 0.84x; T=16384: compile failure) —
    larger shapes use the HBM-blocked lax.scan formulation instead.
    (That was against the scan and before the kernels asked for 32 MB:
    ``MultiHeadAttention``, whose other arm writes T x T scores to HBM,
    names a ``limit`` of its own, ``ops.nn.MHA_KV_VMEM``.)"""
    esize = jnp.dtype(dtype).itemsize if dtype is not None else 2
    kv_bytes = 2 * Tk * D * esize
    return 2 * kv_bytes <= limit


_NT = (((1,), (1,)), ((), ()))       # A @ B^T: the MXU loads B transposed
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))       # A^T @ B: the tile's rows contracted


def _fold_scale(scale):
    """True where ``scale`` is a power of two: ``x * scale`` is then exact
    in any float dtype, so the kernels fold it into the operand a program
    holds for its whole loop (one multiply a program, not one a score)."""
    return math.frexp(scale)[0] == 0.5


def _min_max(x):
    """(min, max) for bounds worked on python ints (``kv_block_plan``) or on
    a program's traced block index."""
    return (min, max) if isinstance(x, int) else (jnp.minimum, jnp.maximum)


def _causal_bounds(row0, rows, cols, n_cols):
    """For the ``rows`` query positions from ``row0`` against key blocks
    of ``cols``: (blocks wholly at or under the diagonal, blocks the
    diagonal reaches).  [0, full) needs no mask, [full, end) is masked,
    [end, n_cols) is never computed."""
    lesser, _ = _min_max(row0)
    full = lesser((row0 + 1) // cols, n_cols)
    end = lesser((row0 + rows + cols - 1) // cols, n_cols)
    return full, end


def _window_bounds(row0, rows, cols, window):
    """The lower edge of a ``window`` for the ``rows`` query positions from
    ``row0`` against key blocks of ``cols``: (first block any of them sees,
    first block all of them see whole).  [0, lo) is never computed, [lo,
    clear) carries the window's mask; ``clear`` may lie past the diagonal
    (a window narrower than a block)."""
    _, greater = _min_max(row0)
    lo = greater(row0 - window + 1, 0) // cols
    clear = greater(row0 + rows - window + cols - 1, 0) // cols
    return lo, clear


def _window_q_bounds(col0, cols, rows, window, n_rows):
    """The same edge seen from the ``cols`` keys from ``col0`` against
    query blocks of ``rows``: (first query block with a position past the
    window of key ``col0``, query blocks that reach the block at all).
    [edge, hi) carries the window's mask, [hi, n_rows) is never computed."""
    lesser, _ = _min_max(col0)
    edge = (col0 + window) // rows
    hi = lesser((col0 + cols + window - 2) // rows + 1, n_rows)
    return edge, hi


def _two_loops(lo, mid, hi, step, init, masked_first):
    """``step(i, carry, masked)`` over [lo, hi): one stretch masked, the
    other not, split at ``mid`` — the mask costs three VPU passes a score
    and only the blocks the diagonal crosses need it."""
    a = functools.partial(step, masked=masked_first)
    b = functools.partial(step, masked=not masked_first)
    return jax.lax.fori_loop(mid, hi, b, jax.lax.fori_loop(lo, mid, a, init))


def _three_loops(lo, a, b, hi, step, init):
    """``step`` over [lo, hi) with a mask on both ends: [lo, a) and [b, hi)
    masked, [a, b) not."""
    return jax.lax.fori_loop(
        b, hi, functools.partial(step, masked=True),
        _two_loops(lo, a, b, step, init, masked_first=True))


def _key_block_loop(body, init, qi, TQ, BK, n, square, window):
    """The causal K-block loop of one Q block of the forward:
    ``body(i, carry, masked)`` over the blocks the diagonal and, under a
    ``window``, its lower edge leave to visit, masked only where one of them
    crosses a block.  ``square``: up to the diagonal block, which the caller
    computes in bands."""
    if window is not None:
        lo, clear = _window_bounds(qi * TQ, TQ, BK, window)
    if square:
        if window is None:
            return jax.lax.fori_loop(
                0, qi, functools.partial(body, masked=False), init)
        # at least a block wide: the diagonal block lies all inside
        return _two_loops(lo, jnp.minimum(clear, qi), qi, body, init,
                          masked_first=True)
    full, end = _causal_bounds(qi * TQ, TQ, BK, n)
    if window is None:
        return _two_loops(0, full, end, body, init, masked_first=False)
    edge = jnp.minimum(clear, end)
    return _three_loops(lo, edge, jnp.maximum(edge, full), end, body, init)


def _heads_per_program(BH, T, itemsize=2):
    """Heads one program works through side by side: the K-block loop is
    a chain (product, row maximum, exp, product) that waits on itself, and
    a second head in the same body is independent work to fill the waits
    with (measured, PR 26: 0.65 -> 0.43 ms in 128-blocks, 0.159 -> 0.150
    in one 1024-block).  Each head keeps a band of scores alive, so fewer
    of them share a program as T grows."""
    g = max(1, min(4, 8192 // (T * itemsize)))
    while BH % g:
        g //= 2
    return g


def _each_head(G, step):
    """``step(g, i, carry, masked)`` for one head -> the loop body over all
    ``G`` heads of a program, carries in a tuple."""
    def body(i, carries, masked):
        return tuple(step(g, i, c, masked) for g, c in enumerate(carries))
    return body


def _bands(T, band):
    """(number, size) of the bands a square diagonal block is cut into:
    band r of a (T, T) block sees (r + 1) bands of keys, so of the block's
    square (R + 1) / 2R is computed and not all of it."""
    if band * T * 4 > 1 << 20:          # a band of f32 scores: 1 MB at most
        band = 128
    while band >= 128:                  # bands start on a lane tile
        if T % band == 0:
            return T // band, band
        band -= 128
    return 1, T


# measured at (1, 16, 1024, 64), one block: the forward (row statistics a
# band) is fastest in bands of 256, 0.049 against 0.058 ms; the backward (no
# reduction in the loop) in bands of 128 (PR 26, the two kernels it then
# was: 0.040 / 0.053 against 0.042 / 0.056)
_BAND_FWD, _BAND_BWD = 256, 128


def _visible(shape, row_axis, shift, window=None):
    """The causal mask of one tile: true where key <= query, the tile's
    first key lying ``shift`` positions after its first query; under a
    ``window`` also query - key < window."""
    rel = jax.lax.broadcasted_iota(jnp.int32, shape, row_axis) \
        - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - row_axis)
    if window is None:
        return rel >= shift
    return (rel >= shift) & (rel < shift + window)


def _online_softmax_loop(q_ref, k_ref, v_ref, *, G, TQ, BK, Tk, causal,
                         scale, square=False, window=None):
    """Shared kernel body: the online-softmax K-block loop over the ``G``
    heads of a program, returning a running (m, l, acc) a head with m, l
    of shape (TQ, 1) — finalized differently by the normalized-output
    kernel and the stats-emitting ring kernel.

    Causal: key blocks past the diagonal are never visited and only the
    blocks the diagonal crosses are masked; ``square`` (self-attention in
    square blocks) says block ``qi`` is the one diagonal block, which is
    then computed in bands (``_bands``).  Every row sees key 0 in the first
    block visited, so the running max is finite from the first step on and
    ``exp(m_old - m_new)`` needs no guard (``exp(-inf) == 0``).

    ``window`` (causal, narrower than the keys): the loop starts at the
    first block the window's lower edge reaches and masks up to the first
    block every row sees whole (``_window_bounds``).  A row may see nothing
    of the first blocks visited, so a masked step under a window keeps
    ``exp`` off ``-inf - -inf``."""
    qi = pl.program_id(1)
    D = q_ref.shape[-1]
    fold = _fold_scale(scale)
    qs = [q_ref[g] * jnp.asarray(scale, q_ref.dtype) if fold else q_ref[g]
          for g in range(G)]                         # (TQ, D) each

    def scores(qb, kblk):
        s = jax.lax.dot_general(qb, kblk, _NT,
                                preferred_element_type=jnp.float32)
        return s if fold else s * scale

    def update(carry, s, vblk, guard=False):
        m, l, acc = carry
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # a row that has seen no key yet: exp(-inf - 0) == 0, not nan
        at = jnp.where(m_new == -jnp.inf, 0.0, m_new) if guard else m_new
        p = jnp.exp(s - at)
        alpha = jnp.exp(m - at)
        l2 = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc2 = acc * alpha + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk, _NN,
            preferred_element_type=jnp.float32)
        return m_new, l2, acc2

    def step(g, i, carry, masked):
        off = pl.multiple_of(i * BK, BK)
        s = scores(qs[g], k_ref[g, pl.ds(off, BK), :])       # (TQ, BK)
        if masked:
            s = jnp.where(_visible((TQ, BK), 0, i * BK - qi * TQ, window), s,
                          -jnp.inf)
        return update(carry, s, v_ref[g, pl.ds(off, BK), :],
                      guard=masked and window is not None)

    def diagonal(g, carry):
        R, SB = _bands(TQ, _BAND_FWD)
        base = pl.multiple_of(qi * BK, BK)
        parts = []
        for r in range(R):
            rows, keys = slice(r * SB, (r + 1) * SB), (r + 1) * SB
            s = scores(qs[g][rows], k_ref[g, pl.ds(base, keys), :])
            s = jnp.where(_visible((SB, keys), 0, -r * SB), s, -jnp.inf)
            parts.append(update(tuple(c[rows] for c in carry), s,
                                v_ref[g, pl.ds(base, keys), :]))
        return tuple(jnp.concatenate(c, axis=0) for c in zip(*parts))

    body = _each_head(G, step)
    init = ((jnp.full((TQ, 1), -jnp.inf, jnp.float32),
             jnp.zeros((TQ, 1), jnp.float32),
             jnp.zeros((TQ, D), jnp.float32)),) * G
    n = Tk // BK
    if not causal:
        return jax.lax.fori_loop(0, n, functools.partial(body, masked=False),
                                 init)
    heads = _key_block_loop(body, init, qi, TQ, BK, n, square, window)
    if square:
        return tuple(diagonal(g, c) for g, c in enumerate(heads))
    return heads


def _row(col):
    """(TQ, 1) per-row values -> one lane-dense (1, TQ) row: what leaves
    the kernel is T floats a head, not T x 128."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], 128)))[0:1]


def _col(ref, g):
    """Head ``g`` of a (G, 1, 1, TQ) block of a lane-dense stats array ->
    (TQ, 1)."""
    return ref[g, 0, 0][:, None]


def _out_sds(shape, dtype, like):
    """ShapeDtypeStruct for a pallas_call output, inheriting the caller's
    varying-mesh-axes set — required when the kernel runs inside
    shard_map (the ring-attention per-shard pass)."""
    try:
        return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)
    except (AttributeError, TypeError):
        return jax.ShapeDtypeStruct(shape, dtype)


def default_blocks(Tq, Tk, window=None):
    """(block_q, block_k) from the shape, where the caller names none:
    one square block over the whole of a sequence up to 2,048 positions
    (0.42 ms against 0.55 in 512-blocks at T 2048), 512-blocks beyond, and
    under a ``window`` narrower than the sequence at any length: one block
    leaves nothing to skip, and its diagonal bands know the causal mask
    alone (not measured; the cell that has a window runs T 4096).
    Measured on the v5e with ``tools/bench_attention_arms.py`` (PERF.md,
    PR 34) at (1, 16, 1024, 64) bf16 causal: a loop step costs about the
    same whatever the block, so forward + backward take 0.31 ms in
    128-blocks, 0.20 in 256, 0.18 in 512 and 0.125 in one 1024-block, whose
    diagonal bands (``_bands``) are straight-line code with static extents
    and compute 56 % of the square.  (At a head of 256 and T 4096
    1,024-blocks read 3.87 ms against 4.17 in 512-blocks; not taken:
    PERF.md section 7.)"""
    side = min(Tq, Tk)
    if window is not None and window < Tk:
        side = min(side, 512)
    return (side, side) if side <= 2048 else (512, 512)


def _pick_blocks(Tq, Tk, block_q, block_k, window=None):
    if block_q is None or block_k is None:
        own_q, own_k = default_blocks(Tq, Tk, window)
        block_q, block_k = block_q or own_q, block_k or own_k
    TQ = min(block_q, Tq)
    while Tq % TQ:
        TQ //= 2
    BK = min(block_k, Tk)
    while Tk % BK:
        BK //= 2
    return TQ, BK


def _square(TQ, BK, Tq, Tk, window):
    """Self-attention in square blocks, the diagonal block computed in bands
    under the causal mask alone: a window at least a block wide."""
    return TQ == BK and Tq == Tk and (window is None or window >= TQ)


def _stats_spec(G, TQ):
    return pl.BlockSpec((G, 1, 1, TQ), lambda b, t: (b, t, 0, 0))


def _qkv_specs(G, TQ, Tk, D):
    return [pl.BlockSpec((G, TQ, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((G, Tk, D), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((G, Tk, D), lambda b, t: (b, 0, 0))]


# 32 MB of the v5e's 128 MB of VMEM: a 2048-block of f32 operands, double
# buffered, needs more than the 16 MB a kernel gets unasked
_VMEM_LIMIT, _VMEM_MOST = 32 << 20, 100 << 20
# what the 32 MB leave a step's score tiles at the largest shapes the cells
# run (18.5 MB of buffers at (1, 20, 4096, 256), 16.3 in one 2048-block)
_TILE_ROOM = 12 << 20
_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _flash_fwd_call(q, k, v, causal, scale, block_q, block_k, stats,
                    window=None):
    """One forward ``pallas_call`` over [B,H,T,D]: ``stats`` None -> out;
    "lse" -> (out, lse[B,H,Tq]); "ml" -> (acc f32, m, l) for the ring."""
    return _fwd_program(q, k, v, causal, scale, block_q, block_k, stats,
                        INTERPRET, window)


# The three programs are jitted on their own: a model calls them once a
# layer with the same shapes, and a jitted callee is traced and lowered to
# Mosaic once a program, not once a layer (GPT-2-medium's 72 kernel
# instances took 180 s of every start-up otherwise, compile cache or not).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _fwd_program(q, k, v, causal, scale, block_q, block_k, stats, interpret,
                 window=None):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    BH = B * H
    TQ, BK = _pick_blocks(Tq, Tk, block_q, block_k, window)
    nq = Tq // TQ

    G = _heads_per_program(BH, max(Tq, Tk), q.dtype.itemsize)

    def kern(q_ref, k_ref, v_ref, o_ref, *stat_refs):
        heads = _online_softmax_loop(
            q_ref, k_ref, v_ref, G=G, TQ=TQ, BK=BK, Tk=Tk, causal=causal,
            scale=scale, square=_square(TQ, BK, Tq, Tk, window),
            window=window)
        for g, (m, l, acc) in enumerate(heads):
            if stats == "ml":
                o_ref[g] = acc
                stat_refs[0][g, 0] = _row(m)
                stat_refs[1][g, 0] = _row(l)
                continue
            o_ref[g] = (acc / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)
            if stats == "lse":
                stat_refs[0][g, 0] = _row(lse_of(m, l))

    n_stats = {None: 0, "lse": 1, "ml": 2}[stats]
    outs = pl.pallas_call(
        kern,
        grid=(BH // G, nq),
        in_specs=_qkv_specs(G, TQ, Tk, D),
        out_specs=[pl.BlockSpec((G, TQ, D), lambda b, t: (b, t, 0))]
        + [_stats_spec(G, TQ)] * n_stats,
        out_shape=[_out_sds((BH, Tq, D),
                            jnp.float32 if stats == "ml" else q.dtype, q)]
        + [_out_sds((BH, nq, 1, TQ), jnp.float32, q)] * n_stats,
        compiler_params=_PARALLEL,
        name="flash_fwd",
        interpret=interpret,
    )(q.reshape(BH, Tq, D), k.reshape(BH, Tk, D), v.reshape(BH, Tk, D))
    return (outs[0].reshape(B, H, Tq, D),
            *(s.reshape(B, H, Tq) for s in outs[1:]))


def flash_attention_stats(q, k, v, causal, scale, block_q=512,
                          block_k=512):
    """Per-shard flash pass returning (acc, m, l) in f32: acc is the
    UNNORMALIZED output accumulator, (m, l) the online-softmax running
    max/sum.  Exact cross-shard merge (ring attention):

        m' = max(m_a, m_b);  l' = l_a*e^{m_a-m'} + l_b*e^{m_b-m'}
        acc' = acc_a*e^{m_a-m'} + acc_b*e^{m_b-m'};  out = acc'/l'
    """
    return _flash_fwd_call(q, k, v, causal, scale, block_q, block_k, "ml")


def lse_of(m, l):
    """logsumexp from online-softmax stats; +inf for fully-masked rows so
    the backward's ``p = exp(s - lse)`` is exactly 0 there."""
    return jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-37)), jnp.inf)


def _dqkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
                 dk_ref, dv_ref, dq_acc, *, G, TQ, BK, Tq, causal, scale,
                 square, window=None):
    """The whole backward for one KV block of ``G`` heads: loop over Q
    blocks.  Scores lie transposed, (BK, TQ) = K Q^T: lse and delta are
    lane-dense rows as they arrive, and the products are plain ``A @ B`` or
    ``A @ B^T`` with the score tile as the streamed operand; ``dsT`` of a
    tile serves ``dk`` and, contracted over its keys, the tile's share of
    ``dq``, which is summed over the KV blocks of the (sequential) second
    grid axis in ``dq_acc``, the head's whole dq in float32: zeroed at KV
    block 0, scaled, cast and written at the last.  Causal: start at the
    first Q block that can see this KV block, mask up to the last one the
    diagonal crosses; ``square``: that is block ``ki`` alone, computed in
    bands of keys (band r is seen by the queries from band r on).  Under a
    ``window`` the loop ends at the last Q block that reaches this KV block
    and masks from the first one with a position past it
    (``_window_q_bounds``)."""
    ki = pl.program_id(1)
    D = k_ref.shape[-1]
    fold = _fold_scale(scale)
    raw = [k_ref[g] for g in range(G)]                   # (BK, D) each
    ks = [kb * jnp.asarray(scale, kb.dtype) for kb in raw] if fold else raw
    vs = [v_ref[g] for g in range(G)]
    n = Tq // TQ

    def q_rows(i):
        return pl.ds(pl.multiple_of(i * TQ, TQ), TQ)

    def clear(i, _):
        for g in range(G):
            dq_acc[g, q_rows(i), :] = jnp.zeros((TQ, D), jnp.float32)

    @pl.when(ki == 0)
    def _():
        jax.lax.fori_loop(0, n, clear, None)

    def grad(carry, g, rows, kb, vb, kraw, qb, dob, lse, delta, mask):
        dk, dv = carry
        sT = jax.lax.dot_general(kb, qb, _NT,
                                 preferred_element_type=jnp.float32)
        pT = jnp.exp((sT if fold else sT * scale) - lse)
        if mask is not None:
            pT = jnp.where(mask, pT, 0.0)
        dv = dv + jax.lax.dot_general(
            pT.astype(dob.dtype), dob, _NN,
            preferred_element_type=jnp.float32)
        dpT = jax.lax.dot_general(vb, dob, _NT,
                                  preferred_element_type=jnp.float32)
        dsT = (pT * (dpT - delta)).astype(qb.dtype)
        dk = dk + jax.lax.dot_general(
            dsT, qb, _NN, preferred_element_type=jnp.float32)
        dq_acc[g, rows, :] += jax.lax.dot_general(
            dsT, kraw, _TN, preferred_element_type=jnp.float32)
        return dk, dv

    def step(g, i, carry, masked):
        rows = q_rows(i)
        mask = _visible((BK, TQ), 1, ki * BK - i * TQ, window) \
            if masked else None
        return grad(carry, g, rows, ks[g], vs[g], raw[g], q_ref[g, rows, :],
                    do_ref[g, rows, :], lse_ref[g, i], dl_ref[g, i], mask)

    def diagonal(g):
        R, SB = _bands(BK, _BAND_BWD)
        zero = jnp.zeros((SB, D), jnp.float32)
        parts = []
        for r in range(R):
            keys, n_q = slice(r * SB, (r + 1) * SB), TQ - r * SB
            rows = pl.ds(pl.multiple_of(ki * TQ + r * SB, SB), n_q)
            parts.append(grad(
                (zero, zero), g, rows, ks[g][keys], vs[g][keys],
                raw[g][keys], q_ref[g, rows, :], do_ref[g, rows, :],
                lse_ref[g, ki, :, r * SB:], dl_ref[g, ki, :, r * SB:],
                _visible((SB, n_q), 1, 0)))
        return tuple(jnp.concatenate(c, axis=0) for c in zip(*parts))

    body = _each_head(G, step)
    init = ((jnp.zeros((BK, D), jnp.float32),
             jnp.zeros((BK, D), jnp.float32)),) * G
    if not causal:
        grads = jax.lax.fori_loop(
            0, n, functools.partial(body, masked=False), init)
    elif square:
        if window is None:
            grads = jax.lax.fori_loop(
                ki + 1, n, functools.partial(body, masked=False),
                tuple(diagonal(g) for g in range(G)))
        else:
            edge, hi = _window_q_bounds(ki * BK, BK, TQ, window, n)
            grads = _two_loops(ki + 1, jnp.minimum(edge, hi), hi, body,
                               tuple(diagonal(g) for g in range(G)),
                               masked_first=False)
    else:
        lo = (ki * BK) // TQ
        # Q blocks from here on lie wholly under the diagonal
        clear_q = jnp.minimum((ki * BK + BK - 1 + TQ - 1) // TQ, n)
        if window is None:
            grads = _two_loops(lo, clear_q, n, body, init, masked_first=True)
        else:
            edge, hi = _window_q_bounds(ki * BK, BK, TQ, window, n)
            clear_q = jnp.minimum(clear_q, hi)
            grads = _three_loops(
                lo, clear_q, jnp.maximum(clear_q, jnp.minimum(edge, hi)), hi,
                body, init)
    for g, (dk, dv) in enumerate(grads):
        dk_ref[g] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[g] = dv.astype(dv_ref.dtype)

    def write(i, _):
        for g in range(G):
            dq_ref[g, q_rows(i), :] = (dq_acc[g, q_rows(i), :]
                                       * scale).astype(dq_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        jax.lax.fori_loop(0, n, write, None)


def _bwd_vmem_bytes(G, Tq, TQ, BK, D, itemsize, out_itemsize):
    """What one program of the backward keeps in VMEM between steps, the
    pipeline's second buffers and the lanes' padding counted: the head's
    whole Q and dO, a block of K, V, dk, dv, the statistics, the dq
    accumulator and the dq output block.  (The score tiles of a step come
    on top: ``_TILE_ROOM``.)"""
    lanes = -(-D // 128) * 128
    whole, block = G * Tq * lanes, G * BK * lanes
    stats = 2 * 2 * G * (Tq // TQ) * 8 * TQ * 4
    return (2 * 2 * whole * itemsize + 2 * 2 * block * itemsize
            + 2 * 2 * block * out_itemsize + stats + whole * 4
            + 2 * whole * out_itemsize)


_ATTN_BACKWARD = _telemetry.counter(
    "attention_backward_total",
    "Flash-attention backward kernel programs built, by form (trace-time): "
    "fused is one kernel that returns dq, dk and dv, the only form built; "
    "two_pass would name a shape kept on a dk/dv and a dq kernel",
    ("form",))


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12))
def _dqkv_program(q, k, v, do, lse, delta, causal, scale, block_q, block_k,
                  out_dtype, interpret, window=None):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    BH = B * H
    TQ, BK = _pick_blocks(Tq, Tk, block_q, block_k, window)
    nq = Tq // TQ
    G = _heads_per_program(BH, max(Tq, Tk), q.dtype.itemsize)
    if _telemetry.enabled:
        # graftlint: disable=GL002 -- counts programs built, not calls
        _ATTN_BACKWARD.labels(form="fused").inc()
    whole_q = pl.BlockSpec((G, Tq, D), lambda b, t: (b, 0, 0))
    kv_blk = pl.BlockSpec((G, BK, D), lambda b, t: (b, t, 0))
    whole_stats = pl.BlockSpec((G, nq, 1, TQ), lambda b, t: (b, 0, 0, 0))
    need = _bwd_vmem_bytes(G, Tq, TQ, BK, D, q.dtype.itemsize,
                           out_dtype.itemsize)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_dqkv_kernel, G=G, TQ=TQ, BK=BK, Tq=Tq,
                          causal=causal, scale=scale,
                          square=_square(TQ, BK, Tq, Tk, window),
                          window=window),
        grid=(BH // G, Tk // BK),
        in_specs=[whole_q, kv_blk, kv_blk, whole_q, whole_stats,
                  whole_stats],
        out_specs=[whole_q, kv_blk, kv_blk],
        out_shape=[_out_sds((BH, Tq, D), out_dtype, q)]
        + [_out_sds((BH, Tk, D), out_dtype, q)] * 2,
        scratch_shapes=[pltpu.VMEM((G, Tq, D), jnp.float32)],
        # the KV blocks of a head run in order: they share its dq
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(_VMEM_LIMIT, min(need + _TILE_ROOM,
                                                  _VMEM_MOST))),
        name="flash_dqkv",
        interpret=interpret,
    )(q.reshape(BH, Tq, D), k.reshape(BH, Tk, D), v.reshape(BH, Tk, D),
      do.reshape(BH, Tq, D), lse.reshape(BH, nq, 1, TQ),
      delta.reshape(BH, nq, 1, TQ))
    return (dq.reshape(B, H, Tq, D), dk.reshape(B, H, Tk, D),
            dv.reshape(B, H, Tk, D))


def flash_attention_bwd(q, k, v, do, lse, delta, causal, scale,
                        block_q=512, block_k=512, out_dtype=jnp.float32,
                        window=None):
    """Pallas flash backward, one kernel: (dq, dk, dv), in f32 unless the
    caller names another ``out_dtype`` (callers accumulating across ring
    steps keep full precision; the standalone VJP has the kernel cast).

    q/k/v/do: [B,H,T,D]; lse/delta: [B,H,Tq] f32 (global logsumexp and
    rowsum(dO*O) — for ring attention these are the FULL-sequence stats,
    making each per-shard call an exact partial contribution).  They
    enter the kernel lane-dense, T floats a head."""
    return _dqkv_program(q, k, v, do, lse.astype(jnp.float32),
                         delta.astype(jnp.float32), causal, scale, block_q,
                         block_k, jnp.dtype(out_dtype), INTERPRET, window)


# ------------------------------------------------------------ partitioning
# A bare ``pallas_call`` is a custom call the SPMD partitioner knows nothing
# about: inside a program whose batch is sharded (the mesh fused step, batch
# ``P('dp')``) it would gather q, k, v and run every sequence on every chip.
# (``jax.experimental.custom_partitioning`` would say it in one rule; libtpu
# has no emitter for it: "Custom emitter for CustomSPMDPartitioning not
# found".)  So where the program is traced under a mesh (``jax.set_mesh``, as
# ``ModuleFusedStep.step`` does on a mesh), the three calls of the op's arm run
# under ``shard_map``: batch rows follow the mesh's ``dp`` axis and heads its
# ``tp`` axis (``parallel.mesh``'s names), T and D stay whole, and each device
# runs the kernel on its own rows.  Without a mesh, on one device, or inside
# a ``shard_map`` that already holds those axes, the call is made as it is.
BATCH_AXIS, HEAD_AXIS = "dp", "tp"


def _split(B, H):
    """(mesh axis or None) for the batch and the head dimension: the
    ambient mesh's free ``dp`` / ``tp`` axis where it divides B / H."""
    mesh = jax.sharding.get_abstract_mesh()
    return tuple(
        name if name in mesh.auto_axes and mesh.shape[name] > 1
        and size % mesh.shape[name] == 0 else None
        for name, size in ((BATCH_AXIS, B), (HEAD_AXIS, H)))


def rows_per_device(B, H):
    """(B, H) as one device of the ambient mesh holds them."""
    mesh = jax.sharding.get_abstract_mesh()
    return tuple(size // mesh.shape[name] if name else size
                 for name, size in zip(_split(B, H), (B, H)))


def _on_own_rows(fn, *arrays):
    """``fn(*arrays)`` over [B, H, ...] arrays and results, each device on
    the rows and heads ``_split`` gives it."""
    over = _split(*arrays[0].shape[:2])
    if over == (None, None):
        return fn(*arrays)

    def spec(x):
        return P(*over, *(None,) * (x.ndim - 2))

    with _telemetry.paused():   # a shape asked about is no program built
        shapes = jax.eval_shape(fn, *arrays)
    return jax.shard_map(
        fn, in_specs=tuple(spec(a) for a in arrays),
        out_specs=jax.tree.map(spec, shapes),
        axis_names={a for a in over if a}, check_vma=False)(*arrays)


def _scale_of(q, scale):
    return scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)


def _window_of(window, causal, Tk):
    """The window the kernels are built for: None where it reaches position
    0 from every query (plain causal, the same programs)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError("a window of %r needs causal attention and at "
                         "least one position" % (window,))
    return None if window >= Tk else int(window)


def kv_block_plan(Tq, Tk, causal=True, window=None, block_q=None,
                  block_k=None):
    """(visited, skipped): of the key blocks at or under the diagonal, a
    head, how many the forward kernel's loop bounds take in and how many a
    ``window`` lets them leave out.  The bounds are the kernel's own, on
    python ints; nothing runs."""
    window = _window_of(window, causal, Tk)
    TQ, BK = _pick_blocks(Tq, Tk, block_q, block_k, window)
    visited = skipped = 0
    for row0 in range(0, Tq, TQ):
        end = _causal_bounds(row0, TQ, BK, Tk // BK)[1] if causal \
            else Tk // BK
        lo = 0 if window is None else _window_bounds(row0, TQ, BK, window)[0]
        visited, skipped = visited + end - lo, skipped + lo
    return visited, skipped


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, window=None):
    """[B,H,T,D] attention; Pallas kernels both directions, each device
    of an ambient mesh on its own batch rows and heads (``_on_own_rows``).
    Blocks default to ``default_blocks`` of the shape.  ``window`` (causal
    only): position t sees the ``window`` positions up to and with t."""
    sc = _scale_of(q, scale)
    window = _window_of(window, causal, k.shape[2])
    return _on_own_rows(
        lambda *qkv: _flash_fwd_call(*qkv, causal, sc, block_q, block_k,
                                     None, window)[0], q, k, v)


def _fa_vjp_fwd(q, k, v, causal, scale, block_q, block_k, window):
    sc = _scale_of(q, scale)
    window = _window_of(window, causal, k.shape[2])
    out, lse = _on_own_rows(
        lambda *qkv: _flash_fwd_call(*qkv, causal, sc, block_q, block_k,
                                     "lse", window), q, k, v)
    return out, (q, k, v, out, lse)


def _fa_vjp_bwd(causal, scale, block_q, block_k, window, res, g):
    q, k, v, out, lse = res
    sc = _scale_of(q, scale)
    window = _window_of(window, causal, k.shape[2])
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    return _on_own_rows(
        lambda *xs: flash_attention_bwd(*xs, causal, sc, block_q, block_k,
                                        q.dtype, window),
        q, k, v, g, lse, delta)


flash_attention.defvjp(_fa_vjp_fwd, _fa_vjp_bwd)
