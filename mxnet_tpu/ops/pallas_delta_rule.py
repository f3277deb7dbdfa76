"""The gated delta rule (``ops/delta_rule.py``) as two Pallas kernels: the
forward and its hand-derived backward, each chunk's whole body inside one
kernel step, each head's state in VMEM.

Layout.  Heads run in pairs: a pair's chunk is ``R = 2 * CHUNK`` = 128
rows (head, position) of ``dk`` (``dv``) channels, so that every pairwise
matrix of a chunk is one [128, 128] tile, block-diagonal over the two heads,
and its transpose (channels on the sublanes, (head, position) on the lanes)
is one too.  The grid is (blocks of ``heads`` heads, chunks); the chunk axis
runs in order (forward) or in reverse (backward, ``index_map`` ``n - 1 -
i``), and the state of each head of the block, transposed (``dv x dk``,
float32), or its gradient, lives in a VMEM scratch between steps.

One chunk, as ``delta_rule._chunk`` computes it, with the same numerics:

* every decay is the exponential of a SUM of log-decays over the positions
  it spans, never of a difference of two cumulative sums and never of
  ``-G``: the cumulative sums ``G``, the suffix sums, the sub-chunks' own
  running sums and the sums back to each sub-chunk's reference point come
  from products of 0/1 matrices with ``g`` split into three bfloat16 parts
  (exact products, float32 accumulation: ``_sums``);
* between sub-chunks of ``SUB`` the pairs go through the position just
  before the later sub-chunk (two factors, each at most 1, one product);
* inside a sub-chunk each pair is decayed by its own suffix sum, summed from
  ``t`` back: ``_within`` walks the 16 offsets ``t - r`` on the transposed
  tile (a lane roll a step), takes each pair's exponential and sums its
  channels down the sublanes; a strided lane roll (``_skew``) sets the 16
  diagonals into the [t, r] tile;
* the unit lower triangular solve is a blocked inverse in float32 products:
  ``(D + E)^-1 = D^-1 - D^-1 E D^-1`` for a block-diagonal ``D`` and its
  off-diagonal half-blocks ``E``, from blocks of one position to the
  chunk's 64 (``_unit_lower_inverse``; no Neumann series);
* the products the XLA form takes at jax's default precision (the pairs
  between sub-chunks, ``W S``, ``q e^G S``, ``P U``, ``k^T U`` and their
  transposes in the backward) take bfloat16 operands under the default
  precision, float32 under ``jax.default_matmul_precision("highest")``
  (``fast``), float32 accumulation either way.

The forward writes ``o``, the (transposed) state each chunk starts from
(the scan's own residual) and the inverse of each chunk's solve, ``T x
CHUNK`` float32 a head, two heads to a [CHUNK, 128] tile (``_packed``): the
inverse's chain of ten float32 products is 45 % of the pair of kernels on
the v5e, and keeping it spares the backward half of that (PERF.md).
The backward recomputes each chunk from its saved state and that inverse
and returns dq, dk, dv, dg and dbeta; nothing of ``T x dk x dv`` or of ``T x
CHUNK x dk`` reaches HBM.  ``gated_delta_rule`` reaches the kernels
through ``delta_rule`` (a ``jax.custom_vjp``) on a TPU where ``dk`` and
``dv`` are multiples of 128 (``available``); tests reach them on the CPU
through ``INTERPRET``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import atlas as _atlas
from .delta_rule import CHUNK, SCOPE, SUB
from .pallas_attention import _on_own_rows

__all__ = ["available", "delta_rule"]

INTERPRET = False

R = 2 * CHUNK                    # rows of a head pair's chunk
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))   # A @ B^T
_TN = (((0,), (0,)), ((), ()))   # A^T @ B
_F32, _BF16 = jnp.float32, jnp.bfloat16
_VMEM_LIMIT = 48 << 20


def available(BH, dk, dv) -> bool:
    """SHAPE eligibility only (the platform is resolved at lowering time by
    the caller): heads in pairs, channels in whole lanes."""
    return BH % 2 == 0 and dk % 128 == 0 and dv % 128 == 0


def heads_per_block(BH):
    """Heads a grid step takes: the most of 4 and 2 that divide ``BH``."""
    return 4 if BH % 4 == 0 else 2


# ------------------------------------------------------------------ helpers
def _iota(dim):
    return jax.lax.broadcasted_iota(jnp.int32, (R, R), dim)


def _dot(a, b, dims, fast):
    """A product the XLA form takes at jax's default precision."""
    if fast:
        a, b = a.astype(_BF16), b.astype(_BF16)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _dot32(a, b, dims=_NN):
    """A float32 product (the solve)."""
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _sums(m01, x):
    """``m01 @ x`` for a 0/1 matrix (bfloat16, exact) and float32 ``x``
    split into three bfloat16 parts: every product exact, float32 sums."""
    hi = x.astype(_BF16)
    rest = x - hi.astype(_F32)
    mid = rest.astype(_BF16)
    lo = (rest - mid.astype(_F32)).astype(_BF16)
    return sum(jax.lax.dot_general(m01, p, _NN, preferred_element_type=_F32)
               for p in (hi, mid, lo))


def sum_matrices():
    """[6R, R] 0/1 rows over a pair's chunk, ``g``'s sums by one product:
    G (cumulative, inclusive), the suffix sums ``sum_{s > r}``, each
    sub-chunk's own running sums, and for sub-chunks 1..3 the sums back
    from the reference point, ``sum_{r < s < lo}`` for ``r < lo``; and
    its transpose, for the gradients."""
    i, j = np.arange(R)[:, None], np.arange(R)[None, :]
    same = i // CHUNK == j // CHUNK
    pi, pj = i % CHUNK, j % CHUNK
    rows = [same & (pj <= pi), same & (pj > pi),
            same & (pi // SUB == pj // SUB) & (pj <= pi)]
    rows += [same & (pi < pj) & (pj < SUB * s) for s in (1, 2, 3)]
    m = np.concatenate(rows).astype(np.float32)
    return jnp.asarray(m, _BF16), jnp.asarray(m.T, _BF16)


def _masks():
    """Pair-tile masks [R, R] over (row t, column r)."""
    r, c = _iota(0), _iota(1)
    same = (r >> 6) == (c >> 6)
    pr, pc = r & (CHUNK - 1), c & (CHUNK - 1)
    sub = (r >> 4) == (c >> 4)
    between = [same & ((pr >> 4) == i) & (pc < SUB * i) for i in (1, 2, 3)]
    return dict(r=r, c=c, eye=r == c, strict=same & (pc < pr),
                incl=same & (pc <= pr), in_strict=sub & (c < r),
                in_incl=sub & (c <= r), between=between)


def _skew(rows, scr):
    """From ``rows[d]`` [1, R] (``M[t, t - d]`` at lane t, d < SUB) the tile
    ``M[t, r]`` (unmasked): row d set at row ``(R - d) % R`` of a zero tile,
    transposed, row t rolled by t along the lanes."""
    scr[...] = jnp.zeros((R, R), _F32)
    for d, row in enumerate(rows):
        scr[pl.ds((R - d) % R, 1), :] = row
    return pltpu.roll(scr[...].T, 0, 1, stride=1, stride_axis=0)


def _diagonals(mT, m):
    """``[M[t, t - d]]`` at lane t for d < SUB, from ``mT`` = M^T [r, t]."""
    return [jnp.sum(jnp.where(m["r"] == m["c"] - d, mT, 0.0), axis=0,
                    keepdims=True) for d in range(SUB)]


def _unit_lower_inverse(A, m):
    """``(I + A)^-1`` for ``A`` strictly lower and block-diagonal in chunks:
    blocks of one position doubled to the chunk's, each step
    ``D^-1 - D^-1 E D^-1`` (``E`` the lower-left halves), float32."""
    r, c = m["r"], m["c"]

    def lower_left(b):
        sh = b.bit_length() - 1
        return (((r >> (sh + 1)) == (c >> (sh + 1))) & (((r >> sh) & 1) == 1)
                & (((c >> sh) & 1) == 0))

    inv = jnp.where(m["eye"], 1.0, 0.0) - jnp.where(lower_left(1), A, 0.0)
    b = 2
    while b < CHUNK:
        inv = inv - _dot32(inv, _dot32(jnp.where(lower_left(b), A, 0.0), inv))
        b *= 2
    return inv


def _packed(tinv):
    """A pair's block-diagonal [R, R] inverse as [CHUNK, R]: each head's
    rows over the lanes of its own block (the other head's are nought)."""
    return tinv[:CHUNK] + tinv[CHUNK:]


def _unpacked(z):
    lanes = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) < CHUNK
    return jnp.concatenate([jnp.where(lanes, z, 0.0),
                            jnp.where(lanes, 0.0, z)], axis=0)


def _within(qT, kT, gT, scr, E_scr=None):
    """The pairs inside each sub-chunk, on the transposed tile [dk, R]:
    offset d = t - r from 0 to SUB - 1, ``diff`` the pair's log-decay summed
    from t back (one more lane-rolled ``g`` a step), its exponential, the
    channels summed down the sublanes.  (A_in, P_in) as [t, r] tiles,
    unmasked; ``E_scr[d]`` keeps the decays for the backward."""
    rows_a, rows_p = [], []
    diff = None
    for d in range(SUB):
        if d == 0:
            krE = kT
        else:
            step = gT if d == 1 else pltpu.roll(gT, d - 1, 1)
            diff = step if d == 1 else diff + step
            E = jnp.exp(diff)
            if E_scr is not None:
                E_scr[d] = E
            krE = pltpu.roll(kT, d, 1) * E
        rows_a.append(jnp.sum(kT * krE, axis=0, keepdims=True))
        rows_p.append(jnp.sum(qT * krE, axis=0, keepdims=True))
    return _skew(rows_a, scr), _skew(rows_p, scr)


def _within_grads(qT, kT, cA, cP, E_scr):
    """The gradients of ``_within``'s pairs, on the transposed tile: ``cA``,
    ``cP`` the diagonals of dA, dP (``_diagonals``), ``E_scr[d]`` the
    decays.  Pair (t, t - d) sends ``cP k_r E`` to q_t, ``cA k_r E`` to k_t
    and both, lane-rolled back by d, to k_r; its whole term ``w`` reaches
    each g_s with r < s <= t: ``acc`` sums the terms from offset d up, and
    lane t of it belongs to s = t - d + 1.  (dqT, dkT, dgT)."""
    dqT, dkT = cP[0] * kT, cP[0] * qT
    acc = dgT = None
    for d in range(SUB - 1, 0, -1):
        E = E_scr[d]
        kr = pltpu.roll(kT, d, 1)
        krE = kr * E
        dqT = dqT + cP[d] * krE
        dkT = dkT + cA[d] * krE
        x = (cA[d] * kT + cP[d] * qT) * E
        dkT = dkT + pltpu.roll(x, R - d, 1)
        w = x * kr
        acc = w if acc is None else acc + w
        back = acc if d == 1 else pltpu.roll(acc, R - d + 1, 1)
        dgT = back if dgT is None else dgT + back
    return dqT, dkT, dgT


def _head(x, h):
    return x[h * CHUNK:(h + 1) * CHUNK]


def _pair(fn, *xs):
    """Concatenate ``fn(h, *heads_of_xs)`` over the pair's two heads."""
    return jnp.concatenate([fn(h, *(_head(x, h) for x in xs))
                            for h in (0, 1)], axis=0)


# ----------------------------------------------------------- one pair's chunk
def _chunk(q, k, v, g, beta, st, sums_m, m, fast, scr, E_scr=None,
           tinv=None):
    """Everything of a pair's chunk up to its corrections: q, k [R, dk], v
    [R, dv], g [R, dk], beta [R, 1] float32; ``st`` the two heads' states
    transposed, [dv, dk] each; ``tinv`` the solve's inverse where the
    forward kept it (``_packed``)."""
    dk = q.shape[1]
    s6 = _sums(sums_m, g)
    G, Sf, Gi, Bk = s6[:R], s6[R:2 * R], s6[2 * R:3 * R], s6[3 * R:]
    eG, eGi = jnp.exp(G), jnp.exp(Gi)
    eBk = jnp.exp(Bk)
    c = dict(G=G, eG=eG, eGi=eGi, eSf=jnp.exp(Sf), eBk=eBk)
    c["Qg"], c["Kg"], c["Ks"] = q * eG, k * eG, k * c["eSf"]
    c["lhs"] = jnp.concatenate([k * eGi, q * eGi], axis=0)        # [2R, dk]
    c["kB"] = jnp.concatenate([k, k, k], axis=0) * eBk              # [3R, dk]
    prod = _dot(c["lhs"], c["kB"], _NT, fast)                       # [2R, 3R]
    A_b = P_b = 0.0
    for i, mask in enumerate(m["between"]):
        A_b = A_b + jnp.where(mask, prod[:R, i * R:(i + 1) * R], 0.0)
        P_b = P_b + jnp.where(mask, prod[R:, i * R:(i + 1) * R], 0.0)
    A_in, P_in = _within(q.T, k.T, g.T, scr, E_scr)
    c["Apair"] = A_b + jnp.where(m["in_strict"], A_in, 0.0)
    c["P"] = P_b + jnp.where(m["in_incl"], P_in, 0.0)
    c["Tinv"] = _unit_lower_inverse(beta * c["Apair"], m) if tinv is None \
        else tinv
    X = _dot32(c["Tinv"], beta * jnp.concatenate([c["Kg"], v], axis=1))
    c["X"], c["W"] = X, X[:, :dk]
    c["U"] = _pair(lambda h, ut, w: ut - _dot(w, st[h], _NT, fast),
                   X[:, dk:], c["W"])
    c["eGc"] = [jnp.exp(G[h * CHUNK + CHUNK - 1:(h + 1) * CHUNK]) for h in (0, 1)]
    return c


def _eye():
    return (jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1))


def _beta(b_ref, p, chunk):
    """The pair's beta at ``chunk`` as an [R, 1] column, from the heads'
    lane-dense rows (``b_ref`` [G, n, CHUNK], every chunk of a head)."""
    eye = _eye()
    return jnp.concatenate([jnp.sum(jnp.where(
        eye, b_ref[2 * p + h, pl.ds(chunk, 1), :], 0.0), axis=1,
        keepdims=True) for h in (0, 1)], axis=0)


def _put_rows(ref, p, chunk, col):
    """An [R, 1] column of the pair back as each head's row at ``chunk``."""
    eye = _eye()
    for h in (0, 1):
        ref[2 * p + h, pl.ds(chunk, 1), :] = jnp.sum(
            jnp.where(eye, _head(col, h), 0.0), axis=0, keepdims=True)


def _load(ref, p):
    x = ref[2 * p:2 * p + 2]
    return x.reshape(R, x.shape[-1]).astype(_F32)


# ------------------------------------------------------------------ forward
def _fwd_kernel(sums_ref, q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s0_ref,
                tinv_ref, st_scr, skew_scr, *, scale, fast):
    @pl.when(pl.program_id(1) == 0)
    def _():
        st_scr[...] = jnp.zeros(st_scr.shape, _F32)

    s0_ref[:, 0] = st_scr[...]
    m = _masks()
    sums_m = sums_ref[...]
    for p in range(q_ref.shape[0] // 2):
        q, k, v, g = (_load(r, p) for r in (q_ref, k_ref, v_ref, g_ref))
        beta = _beta(b_ref, p, pl.program_id(1))
        st = [st_scr[2 * p + h] for h in (0, 1)]
        c = _chunk(q, k, v, g, beta, st, sums_m, m, fast, skew_scr)
        o = _pair(lambda h, qg: _dot(qg, st[h], _NT, fast), c["Qg"]) \
            + _dot(c["P"], c["U"], _NN, fast)
        o_ref[2 * p:2 * p + 2] = (scale * o).reshape(
            2, CHUNK, -1).astype(o_ref.dtype)
        z = _packed(c["Tinv"])
        for h in (0, 1):
            tinv_ref[2 * p + h, 0] = z[h * CHUNK // 2:(h + 1) * CHUNK // 2]
        for h in (0, 1):
            st_scr[2 * p + h] = st[h] * c["eGc"][h] + _dot(
                _head(c["U"], h), _head(c["Ks"], h), _TN, fast)


# ----------------------------------------------------------------- backward
def _bwd_kernel(sums_ref, sumsT_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref,
                tinv_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr,
                skew_scr, E_scr, *, scale, fast):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, _F32)

    m = _masks()
    sums_m, sums_t = sums_ref[...], sumsT_ref[...]
    for p in range(q_ref.shape[0] // 2):
        q, k, v, g, do = (_load(r, p) for r in (q_ref, k_ref, v_ref, g_ref,
                                                do_ref))
        chunk = pl.num_programs(1) - 1 - pl.program_id(1)
        beta = _beta(b_ref, p, chunk)
        dk = q.shape[1]
        st = [s0_ref[2 * p + h, 0] for h in (0, 1)]
        dsn = [ds_scr[2 * p + h] for h in (0, 1)]
        tinv = _unpacked(jnp.concatenate(
            [tinv_ref[2 * p + h, 0] for h in (0, 1)], axis=0))
        c = _chunk(q, k, v, g, beta, st, sums_m, m, fast, skew_scr, E_scr,
                   tinv)
        Qg, Kg, Ks, U, W, X = (c[n] for n in ("Qg", "Kg", "Ks", "U", "W", "X"))

        # o = scale (Qg S + P U);  S' = e^{Gc} S + Ks^T U
        dU = scale * _dot(c["P"], do, _TN, fast) + _pair(
            lambda h, ks: _dot(ks, dsn[h], _NT, fast), Ks)
        dQg = scale * _pair(lambda h, o_: _dot(o_, st[h], _NN, fast), do)
        dP = jnp.where(m["incl"], scale * _dot(do, U, _NT, fast), 0.0)
        dKs = _pair(lambda h, u: _dot(u, dsn[h], _NN, fast), U)
        dGc = []
        for h in (0, 1):
            ds_scr[2 * p + h] = (
                dsn[h] * c["eGc"][h]
                + scale * _dot(_head(do, h), _head(Qg, h), _TN, fast)
                - _dot(_head(dU, h), _head(W, h), _TN, fast))
            dGc.append(c["eGc"][h] * jnp.sum(st[h] * dsn[h], axis=0,
                                              keepdims=True))
        # U = U~ - W S;  [W | U~] = T^-1 beta [Kg | v];  T = I + beta Apair
        dW = -_pair(lambda h, du: _dot(du, st[h], _NN, fast), dU)
        dR = _dot32(c["Tinv"], jnp.concatenate([dW, dU], axis=1), _TN)
        dA = jnp.where(m["strict"], -_dot(dR, X, _NT, fast), 0.0)
        db = (jnp.sum(dR[:, :dk] * Kg, axis=1, keepdims=True)
              + jnp.sum(dR[:, dk:] * v, axis=1, keepdims=True)
              + jnp.sum(dA * c["Apair"], axis=1, keepdims=True))
        dKg, dv = beta * dR[:, :dk], beta * dR[:, dk:]
        dAp = beta * dA

        # the pairs between sub-chunks: lhs = [k e^Gi; q e^Gi], kB_i = k e^Bk_i
        dM = jnp.concatenate([
            jnp.concatenate([jnp.where(mask, d_, 0.0) for mask in m["between"]],
                            axis=1) for d_ in (dAp, dP)], axis=0)   # [2R, 3R]
        dlhs = _dot(dM, c["kB"], _NN, fast)                          # [2R, dk]
        dkB = _dot(dM, c["lhs"], _TN, fast)                          # [3R, dk]
        dq_ = dQg * c["eG"] + dlhs[R:] * c["eGi"]
        dk_ = (dKg * c["eG"] + dKs * c["eSf"] + dlhs[:R] * c["eGi"]
               + sum((dkB * c["eBk"])[i * R:(i + 1) * R] for i in range(3)))
        adj = jnp.concatenate([dQg * Qg + dKg * Kg, dKs * Ks,
                               jnp.sum((dlhs * c["lhs"]).reshape(2, R, dk),
                                       axis=0), dkB * c["kB"]], axis=0)
        dg_ = _sums(sums_t, adj) + jnp.concatenate(
            [jnp.broadcast_to(dGc[h], (CHUNK, dk)) for h in (0, 1)], axis=0)

        # the pairs inside each sub-chunk, on the transposed tile
        dqT, dkT, dgT = _within_grads(
            q.T, k.T, _diagonals(jnp.where(m["in_strict"], dAp, 0.0).T, m),
            _diagonals(jnp.where(m["in_incl"], dP, 0.0).T, m), E_scr)

        rows = slice(2 * p, 2 * p + 2)
        for ref, val in ((dq_ref, dq_ + dqT.T), (dk_ref, dk_ + dkT.T),
                         (dv_ref, dv), (dg_ref, dg_ + dgT.T)):
            ref[rows] = val.reshape(2, CHUNK, -1).astype(ref.dtype)
        _put_rows(db_ref, p, chunk, db)


# ------------------------------------------------------------------- calls
def _specs(G, widths, n=None):
    """Chunk blocks [G, CHUNK, width] of [BH, T, width] arrays, the chunks
    in order, or in reverse where ``n`` (their number) is given."""
    if n is None:
        return [pl.BlockSpec((G, CHUNK, w), lambda b, i: (b, i, 0))
                for w in widths]
    return [pl.BlockSpec((G, CHUNK, w), lambda b, i: (b, n - 1 - i, 0))
            for w in widths]


def _const_spec(shape):
    return pl.BlockSpec(shape, lambda b, i: (0, 0))


def _rows_spec(G, n):
    """Every chunk's beta (or its gradient) of a block's heads, [G, n,
    CHUNK]: one block a head block, kept while its chunks run."""
    return pl.BlockSpec((G, n, CHUNK), lambda b, i: (b, 0, 0))


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _fwd_program(q, k, v, g, beta, scale, fast, interpret):
    BH, T, dk = q.shape
    dv, n = v.shape[-1], T // CHUNK
    G = heads_per_block(BH)
    sums_m, _ = sum_matrices()
    o, s0, tinv = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, fast=fast),
        grid=(BH // G, n),
        in_specs=[_const_spec(sums_m.shape)] + _specs(G, (dk, dk, dv, dk))
        + [_rows_spec(G, n)],
        out_specs=[_specs(G, (dv,))[0],
                   pl.BlockSpec((G, 1, dv, dk), lambda b, i: (b, i, 0, 0)),
                   pl.BlockSpec((G, 1, CHUNK // 2, R),
                                lambda b, i: (b, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, T, dv), v.dtype),
                   jax.ShapeDtypeStruct((BH, n, dv, dk), _F32),
                   jax.ShapeDtypeStruct((BH, n, CHUNK // 2, R), _F32)],
        scratch_shapes=[pltpu.VMEM((G, dv, dk), _F32),
                        pltpu.VMEM((R, R), _F32)],
        compiler_params=_params(),
        name="delta_rule_fwd",
        interpret=interpret,
    )(sums_m, q, k, v, g, beta)
    return o, s0, tinv


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _bwd_program(q, k, v, g, beta, s0, tinv, do, scale, fast, interpret):
    BH, T, dk = q.shape
    dv, n = v.shape[-1], T // CHUNK
    G = heads_per_block(BH)
    sums_m, sums_t = sum_matrices()
    rev = functools.partial(_specs, G, n=n)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, fast=fast),
        grid=(BH // G, n),
        in_specs=[_const_spec(sums_m.shape), _const_spec(sums_t.shape)]
        + rev((dk, dk, dv, dk)) + [_rows_spec(G, n)]
        + [pl.BlockSpec((G, 1, dv, dk), lambda b, i: (b, n - 1 - i, 0, 0)),
           pl.BlockSpec((G, 1, CHUNK // 2, R),
                        lambda b, i: (b, n - 1 - i, 0, 0))]
        + rev((dv,)),
        out_specs=rev((dk, dk, dv, dk)) + [_rows_spec(G, n)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)]
        + [jax.ShapeDtypeStruct(g.shape, _F32),
           jax.ShapeDtypeStruct(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((G, dv, dk), _F32),
                        pltpu.VMEM((R, R), _F32),
                        pltpu.VMEM((SUB, dk, R), _F32)],
        compiler_params=_params(),
        name="delta_rule_bwd",
        interpret=interpret,
    )(sums_m, sums_t, q, k, v, g, beta, s0, tinv, do)


# ------------------------------------------------------------ the custom vjp
def _rows(program, *static):
    """``program`` over [B*H, ...] arrays, as a function of [B, H, ...]."""
    def run(*xs):
        B, H = xs[0].shape[:2]
        outs = program(*(x.reshape((B * H,) + x.shape[2:]) for x in xs),
                       *static, INTERPRET)
        return tuple(o.reshape((B, H) + o.shape[1:]) for o in outs)
    return run


def _forward(q, k, v, g, beta, scale, fast):
    return _on_own_rows(_rows(_fwd_program, scale, fast), q, k, v, g, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def delta_rule(q, k, v, g, beta, scale, fast):
    """The gated delta rule over [B, H, T, .] heads, T a multiple of
    ``CHUNK``: q, k [.., dk] and v [.., dv] in the compute dtype, g [.., dk]
    and beta [B, H, T / CHUNK, CHUNK] float32 (lane-dense: a [.., 1] column
    would be padded to 128 lanes in HBM); o [B, H, T, dv] in v's dtype.  ``fast``:
    bfloat16 operands for the products the XLA form takes at the default
    precision.  Each device of an ambient mesh runs its own batch rows and
    heads (``pallas_attention._on_own_rows``)."""
    return _forward(q, k, v, g, beta, scale, fast)[0]


def _vjp_fwd(q, k, v, g, beta, scale, fast):
    o, s0, tinv = _forward(q, k, v, g, beta, scale, fast)
    return o, (q, k, v, g, beta, s0, tinv)


def _vjp_bwd(scale, fast, res, do):
    with _atlas.part_scope(SCOPE):
        return _on_own_rows(_rows(_bwd_program, scale, fast), *res,
                            do.astype(res[2].dtype))


delta_rule.defvjp(_vjp_fwd, _vjp_bwd)
