"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692): a linear-attention
sequence mixer whose state is updated by a gated delta rule, and the
chunked form of that rule, forward and backward.

The recurrence, per head, with a float32 state ``S`` of ``(dk, dv)``:

    S'_t = Diag(exp(g_t)) S_{t-1}                  g_t <= 0, one a key channel
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = scale * S_t^T q_t

run over the sequence it is T dependent steps, and its autodiff keeps every
state.  ``gated_delta_rule`` runs it in chunks of ``CHUNK`` positions
instead.  Inside a chunk, with ``G`` the in-chunk cumulative log-decay and
``S`` the state the chunk starts from, the rank-one corrections are solved
at once in the WY/UT form:

    A[t, r] = beta_t sum_c k_tc k_rc exp(G_tc - G_rc)          r < t
    (I + A) [W | U~] = [beta k exp(G) | beta v]                one unit lower
                                                               triangular solve
    U   = U~ - W S                                             the corrections
    o_t = scale * (q_t exp(G_t) S + sum_{r<=t} (sum_c q_tc k_rc
                                    exp(G_tc - G_rc)) u_r)
    S_C = Diag(exp(G_C)) S + sum_r (k_r exp(G_C - G_r)) u_r^T

and a ``lax.scan`` over the chunks carries each head's state.  The forward
keeps only the states the chunks start from (``T / CHUNK`` states a head);
the backward runs the chunks in reverse and recomputes each from its saved
state (the scan's body is under ``jax.checkpoint``), so nothing of ``T x dk
x dv`` is held.

Stability: a decay between two positions, ``exp(G_t - G_r)`` with ``r <=
t``, is only ever the exponential of a non-positive number.  The pairs of a
chunk are taken in sub-chunks of ``SUB``: between sub-chunks through the
position just before the later one (``exp(G_t - G_m) exp(G_m - G_r)``, both
factors at most 1), inside a sub-chunk pair by pair; every such difference
is summed from the pair's near end (running and suffix sums), not taken
between two cumulative sums that have decayed far.  Nothing of the form
``exp(-G)`` is formed: at ``A_log = log 16`` one step's log-decay reaches -11
and a chunk's -700.

Precision: gates, decays, the state and the solve are float32; every
product takes float32 operands at jax's default precision, under which the
TPU's compiler may take bfloat16 passes (float32 accumulation); on the CPU
they are float32.

Two forms, one contract.  On a TPU, where a device's heads come in pairs
and ``dk`` and ``dv`` are multiples of 128, the chunks run in the Pallas
kernels of ``ops/pallas_delta_rule.py`` (``_kernel_rule``: each chunk's
whole body in one kernel step, each head's state in VMEM, the same
numerics; ``linear_attention_dispatch_total{path="kernel"}``).  Everywhere
else, the CPU and heads of 8 or 16 among them, the chunks are XLA's own
products in a ``while`` loop (``_chunked_rule``; ``{path="chunked"}``),
which is also the kernels' second reference in the tests.  Either runs
under the op's part scope ``delta_rule``, forward and backward
(``atlas.part_scope``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register, param
from ..base import MXNetError
from .. import atlas as _atlas
from .. import telemetry as _telemetry
from ..parallel.mesh import matmul_wt as _matmul_wt
from .nn import causal_depthwise_conv, _rms_norm_last

CHUNK, SUB = 64, 16
SCOPE = "delta_rule"

_LINEAR_DISPATCH = _telemetry.counter(
    "linear_attention_dispatch_total",
    "Linear-attention cores compiled, by formulation path (trace-time)",
    ("path",))


def _suffix(g):
    """``sum_{s > r} g_s`` along axis 1, summed from the far end: exact
    where it is small, for the positions close to the end."""
    rev = jnp.cumsum(g[:, ::-1], axis=1)[:, ::-1]
    return jnp.concatenate([rev[:, 1:], jnp.zeros_like(rev[:, :1])], axis=1)


def _suffix_pairs(g):
    """[BH, S, S, d]: ``sum_{r < s <= t} g_s`` at [t, r] for ``r <= t``
    (nought above), each summed from ``t`` back: exact for the close pairs,
    whose decays are the ones that do not vanish."""
    S = g.shape[1]
    upto = np.tril(np.ones((S, S), bool))[..., None]     # s <= t at [t, s]
    within = jnp.where(upto, g[:, None, :, :], 0.0)
    return _suffix(within.reshape((-1,) + within.shape[2:])).reshape(
        within.shape)


def _pairwise(a, b, g, strict):
    """``M[t, r] = sum_c a[t,c] b[r,c] exp(G[t,c] - G[r,c])`` for ``r < t``
    (``strict``) or ``r <= t``, nought above: [BH, C, C] from [BH, C, d]
    operands and the chunk's log-decays ``g`` (``G`` their cumulative sum).
    Sub-chunk ``i`` against the positions before it goes through its
    reference point ``m``, the position just before it: ``G_t - G_m`` is
    the sub-chunk's own running sum and ``G_m - G_r`` the sum back from
    ``m``, each at most 0; inside a sub-chunk each pair is decayed on its
    own, by the sum of its own steps taken back from ``t``
    (``_suffix_pairs``), so that a pair a few steps apart keeps its digits
    however far the sub-chunk has decayed (a difference of two running sums
    at 16 x -120 loses them: the A_log gradient read 4e-4 of its largest
    entry off the float64 value on the CPU)."""
    _, C, _ = a.shape
    sub = min(SUB, C)
    keep = np.tril(np.ones((sub, sub), bool), -1 if strict else 0)[..., None]
    rows = []
    for lo in range(0, C, sub):
        hi = lo + sub
        Gi = jnp.cumsum(g[:, lo:hi], axis=1)          # G_t - G_m
        parts = []
        if lo:
            back = _suffix(g[:, :lo])                   # G_m - G_r
            parts.append(jnp.einsum("btc,brc->btr", a[:, lo:hi] * jnp.exp(Gi),
                                    b[:, :lo] * jnp.exp(back)))
        # G_t - G_r = sum_{r < s <= t} g_s, summed from t back to r
        diff = jnp.where(keep, _suffix_pairs(g[:, lo:hi]), 0.0)
        parts.append(jnp.einsum("btc,brc,btrc->btr", a[:, lo:hi],
                                b[:, lo:hi], jnp.where(keep, jnp.exp(diff),
                                                       0.0)))
        if hi < C:
            parts.append(jnp.zeros(a.shape[:1] + (sub, C - hi), a.dtype))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=1)


def _chunk(S, q, k, v, g, beta, scale):
    """One chunk of every head: (the state it ends in, its output), both
    float32.  ``S`` [BH, dk, dv]; q, k [BH, C, dk]; v [BH, C, dv]; g [BH, C,
    dk] float32; beta [BH, C] float32."""
    f32 = jnp.float32
    q, k, v = (x.astype(f32) for x in (q, k, v))
    dk = k.shape[-1]
    G = jnp.cumsum(g, axis=1)
    A = beta[..., None] * _pairwise(k, k, g, strict=True)
    rhs = beta[..., None] * jnp.concatenate([k * jnp.exp(G), v], axis=-1)
    # (I + A) X = rhs; the solve takes A's zero diagonal as ones
    X = jax.lax.linalg.triangular_solve(A, rhs, left_side=True, lower=True,
                                        unit_diagonal=True)
    U = X[..., dk:] - jnp.matmul(X[..., :dk], S)
    o = scale * (jnp.matmul(q * jnp.exp(G), S)
                 + jnp.matmul(_pairwise(q, k, g, strict=False), U))
    S = jnp.exp(G[:, -1])[..., None] * S + jnp.einsum(
        "brc,brv->bcv", k * jnp.exp(_suffix(g)), U)
    return S, o


def _zero_state(k, v):
    return jnp.zeros((k.shape[1], k.shape[-1], v.shape[-1]), jnp.float32)


def _chunked(q, k, v, g, beta, scale):
    """Outputs [N, BH, C, dv] of chunk-major inputs [N, BH, C, .]: a scan
    over the chunks whose body is checkpointed, so that backward keeps the
    state each chunk starts from and recomputes the chunk from it."""
    @jax.checkpoint
    def step(S, xs):
        S, o = _chunk(S, *xs, scale)
        return S, o.astype(v.dtype)
    return jax.lax.scan(step, _zero_state(k, v), (q, k, v, g, beta))[1]


def gated_delta_rule(q, k, v, g, beta, scale=None):
    """The gated delta rule over [B, T, H, .] heads in chunks of ``CHUNK``
    positions: q, k [B, T, H, dk] and v [B, T, H, dv] in the compute dtype,
    g [B, T, H, dk] (log-decays, <= 0) and beta [B, T, H] float32.  Returns
    o [B, T, H, dv] in v's dtype.  ``T`` need not be a multiple of
    ``CHUNK``: the tail is padded with positions that neither decay nor
    write (g, k, v, beta nought), which leaves every output before them as
    it is."""
    from . import pallas_attention as pa, pallas_delta_rule as pdr
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    scale = float(dk ** -0.5 if scale is None else scale)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    BH = int(np.prod(pa.rows_per_device(B, H)))
    kernel = pdr.available(BH, dk, dv)
    if _telemetry.enabled:
        # graftlint: disable=GL002 -- counts compiled variants, not calls
        _LINEAR_DISPATCH.labels(path="kernel" if kernel else "chunked").inc()
    with _atlas.part_scope(SCOPE):
        if not kernel:
            return _chunked_rule(q, k, v, g, beta, scale)
        fast = jax.config.jax_default_matmul_precision in (
            None, "default", "bfloat16", "fastest")
        def on_kernel(*xs):
            return _kernel_rule(*xs, scale, fast)
        if pdr.INTERPRET:
            return on_kernel(q, k, v, g, beta)
        return jax.lax.platform_dependent(
            q, k, v, g, beta, tpu=on_kernel,
            default=lambda *xs: _chunked_rule(*xs, scale))


def _chunked_rule(q, k, v, g, beta, scale):
    """``gated_delta_rule`` as XLA's ``while`` over chunk-major heads."""
    B, T, H, _ = q.shape
    n = -(-T // CHUNK)

    def chunks(x):
        x = jnp.pad(x, ((0, 0), (0, n * CHUNK - T)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((B, n, CHUNK, H) + x.shape[3:])
        x = x.transpose((1, 0, 3, 2) + tuple(range(4, x.ndim)))
        return x.reshape((n, B * H, CHUNK) + x.shape[4:])

    o = _chunked(*(chunks(x) for x in (q, k, v, g, beta)), scale)
    o = o.reshape(n, B, H, CHUNK, -1).transpose(1, 0, 3, 2, 4)
    return o.reshape(B, n * CHUNK, H, -1)[:, :T]


def _kernel_rule(q, k, v, g, beta, scale, fast):
    """``gated_delta_rule`` on the Pallas kernels over head-major heads."""
    from .pallas_delta_rule import delta_rule
    T = q.shape[1]
    pad = -(-T // CHUNK) * CHUNK - T

    def heads(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x, 2, 1)

    B, _, H = beta.shape
    o = delta_rule(*(heads(x) for x in (q, k, v, g)),
                   heads(beta).reshape(B, H, -1, CHUNK), scale, fast)
    return jnp.moveaxis(o, 1, 2)[:, :T]


def delta_rule_reference(q, k, v, g, beta, scale=None):
    """The recurrence itself, position by position (tests' and docs'
    reference): float32, same shapes and contract as ``gated_delta_rule``."""
    dk = q.shape[-1]
    scale = dk ** -0.5 if scale is None else scale
    f32 = jnp.float32

    def step(S, xs):
        qt, kt, vt, gt, bt = xs                      # [B, H, .]
        S = jnp.exp(gt)[..., None] * S
        u = bt[..., None] * (vt - jnp.einsum("bhcv,bhc->bhv", S, kt))
        S = S + kt[..., None] * u[..., None, :]
        return S, scale * jnp.einsum("bhcv,bhc->bhv", S, qt)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    S0 = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], f32)
    with jax.default_matmul_precision("highest"):
        o = jax.lax.scan(step, S0, xs)[1]
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def _l2_heads(x, eps=1e-6):
    """``x / sqrt(sum x^2 + eps)`` over each head's last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


@register("DeltaAttention", nin=16, aliases=("deltaattention",),
          params={"num_heads": param(int, 0, required=True),
                  "head_dim": param(int, 0, required=True),
                  "conv_kernel": param(int, 4),
                  "eps": param(float, 1e-5)})
def _delta_attention(attrs, data, query_weight, key_weight, value_weight,
                     q_conv_weight, k_conv_weight, v_conv_weight, f_a_weight,
                     f_b_weight, A_log, dt_bias, beta_weight, g_a_weight,
                     g_b_weight, o_norm_gamma, out_proj_weight):
    """Kimi Delta Attention (KDA), the Kimi Linear sequence mixer, over
    ``data`` (batch, time, D) with H = ``num_heads`` heads of ``head_dim``
    (keys and values alike; HD = H * head_dim):

        q, k, v = silu(conv(x Wq^T; Cq)), ... (Wk, Ck), (Wv, Cv)
        q, k   <- each head over sqrt(sum^2 + 1e-6)        (L2, float32)
        g      = -exp(A_log[h]) * softplus((x Wfa^T) Wfb^T + dt_bias)
        beta   = sigmoid(x Wb^T)                         one a head
        o      = gated_delta_rule(q, k, v, g, beta), scale head_dim^-1/2
        out    = [RMSNorm_head(o; g_o) * sigmoid((x Wga^T) Wgb^T)] Wo^T

    ``conv`` is the causal depthwise convolution of ``conv_kernel`` taps
    (``nn.causal_depthwise_conv``, as ``ShortConv``'s), no bias, zero
    before position 0.  Weights lie (out, in): ``query_weight``,
    ``key_weight``, ``value_weight`` (HD, D) split by head under
    ``megatron_rules`` (column-parallel) and ``out_proj_weight`` (D, HD)
    by its inputs; the taps ``*_conv_weight`` (HD, conv_kernel); the
    forget gate's low rank ``f_a_weight`` (head_dim, D) and ``f_b_weight``
    (HD, head_dim), ``A_log`` (H,) and ``dt_bias`` (HD,), which stay float32
    under the bf16 policy as the gains do; ``beta_weight`` (H, D); the
    output gate's ``g_a_weight`` (head_dim, D) and ``g_b_weight`` (HD,
    head_dim); ``o_norm_gamma`` (head_dim,), one gain every head shares
    (RMSNorm at ``eps``).  Gates, ``g`` and ``beta`` are float32; the core
    is ``gated_delta_rule`` under the scope ``delta_rule``.  No reference
    analog."""
    if data.ndim != 3:
        raise MXNetError("DeltaAttention: data must be (batch, time, dim), "
                         "got %s" % (data.shape,))
    B, T, _ = data.shape
    H, d, L = attrs["num_heads"], attrs["head_dim"], attrs["conv_kernel"]
    if H <= 0 or d <= 0 or query_weight.shape[0] != H * d \
            or q_conv_weight.shape != (H * d, L):
        raise MXNetError(
            "DeltaAttention: %d heads of %d with %d taps do not fit "
            "query_weight %s and q_conv_weight %s"
            % (H, d, L, query_weight.shape, q_conv_weight.shape))
    def heads(x):
        return x.reshape(B, T, H, d)

    # the products here, their elementwise tails under jax.checkpoint: what
    # backward keeps of a layer is the bfloat16 products and the core's own
    # residuals, not the float32 tensors of T x H*d between them
    proj = [_matmul_wt(data, w) for w in (query_weight, key_weight,
                                          value_weight)]
    f = _matmul_wt(_matmul_wt(data, f_a_weight), f_b_weight)
    b = _matmul_wt(data, beta_weight)
    q, k, v, g, beta = jax.checkpoint(partial(_gates, heads, data.dtype))(
        *proj, q_conv_weight, k_conv_weight, v_conv_weight, f, A_log,
        dt_bias, b)
    o = gated_delta_rule(q, k, v, g, beta, d ** -0.5)
    z = _matmul_wt(_matmul_wt(data, g_a_weight), g_b_weight)
    o = jax.checkpoint(partial(_gated_norm, attrs["eps"]))(o, heads(z),
                                                          o_norm_gamma)
    return _matmul_wt(o.reshape(B, T, H * d), out_proj_weight)


def _gates(heads, dtype, q, k, v, q_taps, k_taps, v_taps, f, A_log, dt_bias,
           b):
    """(q, k, v) in ``dtype`` and (g, beta) in float32 from the products:
    the convolutions with their SiLU, the keys' and queries' L2 norm, the
    forget gate's decay and beta."""
    f32 = jnp.float32

    def conv_silu(y, taps):
        return heads(jax.nn.silu(causal_depthwise_conv(y.astype(f32), taps)))

    q = _l2_heads(conv_silu(q, q_taps)).astype(dtype)
    k = _l2_heads(conv_silu(k, k_taps)).astype(dtype)
    v = conv_silu(v, v_taps).astype(dtype)
    g = -jnp.exp(A_log.astype(f32))[:, None] * heads(
        jax.nn.softplus(f.astype(f32) + dt_bias.astype(f32)))
    return q, k, v, g, jax.nn.sigmoid(b.astype(f32))


def _gated_norm(eps, o, z, gamma):
    """``RMSNorm_head(o; gamma) * sigmoid(z)`` in ``o``'s type."""
    f32 = jnp.float32
    return (_rms_norm_last(o.astype(f32), gamma, eps)
            * jax.nn.sigmoid(z.astype(f32))).astype(o.dtype)
