"""Ring attention: sequence-parallel exact attention over the ICI ring.

Beyond-parity requirement (SURVEY.md §5.7): the reference (2018) has only
bucketing/fused-RNN for long sequences; long-context LM workloads need the
sequence dimension sharded across chips.  Design: K/V blocks rotate around
the mesh ring via ``ppermute`` while each chip holds its Q shard; softmax is
accumulated blockwise with the running-max rescaling trick (flash-attention
style), so attention over sequence length S costs O(S/n) memory per chip and
the K/V transfers ride the ICI ring concurrently with compute.

This module provides:
- ``blockwise_attention``: single-device flash-style blockwise kernel
  building block (jax.lax.scan over K/V blocks; XLA fuses into MXU matmuls).
- ``ring_attention``: shard_map'd ring over a named mesh axis.
- ``ulysses_attention``: all-to-all head-scatter alternative (attention-heavy
  models with many heads: seq-gather/head-scatter costs one all_to_all each
  way instead of (n-1) ring hops).

The per-shard formulation is chosen at trace time by
``ops.pallas_attention.flash_attention_available``, a test of the shard's
shape: the choice is baked into whatever program the caller traces these
entry points into.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["blockwise_attention", "ring_attention", "ulysses_attention"]


def _attn_block(q, k, v, bias, m_prev, l_prev, o_prev, scale):
    """One (Q-block × K-block) update with running softmax rescaling.

    q: [B,H,Tq,D], k/v: [B,H,Tk,D]; m/l/o carry the running max / sum /
    output accumulator.  fp32 accumulation regardless of input dtype.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    o_new = o_prev * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def blockwise_attention(q, k, v, block_size: int = 512, causal: bool = False,
                        scale: Optional[float] = None,
                        use_pallas: bool = True):
    """Flash-style attention via lax.scan over K/V blocks.  [B,H,T,D].

    On TPU, shapes whose K/V fit VMEM dispatch to the Pallas flash
    kernel (ops/pallas_attention.py): same online-softmax math, but the
    whole K-loop runs on-core with scores never touching HBM."""
    B, H, T, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if use_pallas:
        from ..ops import pallas_attention as pa
        if pa.flash_attention_available(B, H, T, Tk, D, q.dtype):
            flash = partial(pa.flash_attention, causal=causal, scale=scale,
                            block_q=block_size, block_k=block_size)
            if pa.INTERPRET:   # test hook: force the interpreter on CPU
                return flash(q, k, v)
            # platform resolved at LOWERING time: CPU-committed arrays on
            # a TPU host get the scan branch, never Mosaic (advisor r03)
            return jax.lax.platform_dependent(
                q, k, v, tpu=flash,
                default=partial(blockwise_attention, block_size=block_size,
                                causal=causal, scale=scale,
                                use_pallas=False))
    bs = min(block_size, Tk)
    nblocks = (Tk + bs - 1) // bs
    pad = nblocks * bs - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(B, H, nblocks, bs, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, nblocks, bs, D).transpose(2, 0, 1, 3, 4)

    q_pos = jnp.arange(T)

    def body(carry, inp):
        m, l, o = carry
        kblk, vblk, blk_idx = inp
        k_pos = blk_idx * bs + jnp.arange(bs)
        bias = None
        mask_pad = k_pos < Tk
        bias = jnp.where(mask_pad, 0.0, -jnp.inf)[None, None, None, :]
        if causal:
            causal_mask = q_pos[:, None] >= k_pos[None, :]
            bias = bias + jnp.where(causal_mask, 0.0,
                                    -jnp.inf)[None, None, :, :]
        m, l, o = _attn_block(q, kblk, vblk, bias, m, l, o, scale)
        return (m, l, o), None

    # derive the carry from q so it inherits q's device-varying axes when
    # this runs inside shard_map (e.g. the Ulysses all-to-all path) — a
    # plain zeros() carry would mismatch the varying scan inputs
    zero = (q[..., 0] * 0).astype(jnp.float32)          # [B,H,T]
    m0 = zero - jnp.inf
    l0 = zero
    o0 = (q * 0).astype(jnp.float32)
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0),
                                (kb, vb, jnp.arange(nblocks)))
    out = o / jnp.maximum(l[..., None], 1e-37)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = False, block_size: int = 512,
                   scale: Optional[float] = None, use_pallas: bool = True):
    """Exact attention with sequence sharded on `axis`.

    Inputs [B,H,T,D] with T = full sequence; returns same sharding.  Each
    of the n ring steps overlaps a K/V ``ppermute`` with attention over
    the already-held shard.  On TPU (lowering-time platform branch) the
    per-shard pass is the Pallas flash kernel emitting online-softmax
    stats (``flash_attention_stats``); the exact cross-shard combine
    (m/l rescaling) runs in XLA between steps, and for causal masks the
    per-step mask kind is resolved with ``lax.switch``: fully-visible
    shards run the kernel unmasked, the diagonal shard runs it causally,
    and fully-masked shards skip the kernel entirely (the classic ring
    load-saving).  The ring decomposition is also what makes the kernel
    APPLICABLE at long T: the VMEM gate sees the per-shard K/V (T/n),
    not the full sequence.  Backward (round 5) runs the Pallas backward
    kernel (one since PR 34, ``flash_dqkv``) per shard against the
    forward's combined full-sequence (out, lse): dq accumulates locally while dk/dv accumulators ride the
    ring with their K/V shard — fused kernels in BOTH directions, like
    the reference's cuDNN ops (src/operator/cudnn_rnn-inl.h:1).
    """
    n = mesh.shape[axis]
    D = q.shape[-1]
    sc = scale if scale is not None else 1.0 / (D ** 0.5)

    def _pvary(*xs):
        # carries become device-varying after the first ppermute, so the
        # initial values must be marked varying over the ring axis too
        return jax.lax.pcast(xs, (axis,), to="varying")

    def per_shard_scan(qs, ks, vs):
        idx = jax.lax.axis_index(axis)
        T_loc = qs.shape[2]
        B, H = qs.shape[0], qs.shape[1]
        q_pos = idx * T_loc + jnp.arange(T_loc)

        def body(carry, step):
            m, l, o, kcur, vcur = carry
            src_block = (idx - step) % n
            k_pos = src_block * T_loc + jnp.arange(T_loc)
            bias = None
            if causal:
                bias = jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0,
                                 -jnp.inf)[None, None, :, :]
            m, l, o = _attn_block(qs, kcur, vcur, bias, m, l, o, sc)
            perm = [(i, (i + 1) % n) for i in range(n)]
            knext = jax.lax.ppermute(kcur, axis, perm)
            vnext = jax.lax.ppermute(vcur, axis, perm)
            return (m, l, o, knext, vnext), None

        m0 = jnp.full((B, H, T_loc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, T_loc), jnp.float32)
        o0 = jnp.zeros((B, H, T_loc, qs.shape[-1]), jnp.float32)
        m0, l0, o0 = _pvary(m0, l0, o0)
        (m, l, o, _, _), _ = jax.lax.scan(body, (m0, l0, o0, ks, vs),
                                          jnp.arange(n))
        out = o / jnp.maximum(l[..., None], 1e-37)
        return out.astype(qs.dtype)

    def per_shard_flash(qs, ks, vs):
        from ..ops import pallas_attention as pa
        idx = jax.lax.axis_index(axis)
        T_loc = qs.shape[2]
        B, H = qs.shape[0], qs.shape[1]
        bs = block_size

        def kernel_full(kc, vc):
            return pa.flash_attention_stats(qs, kc, vc, False, sc, bs, bs)

        def kernel_diag(kc, vc):
            return pa.flash_attention_stats(qs, kc, vc, True, sc, bs, bs)

        def kernel_skip(kc, vc):
            return (jnp.zeros((B, H, T_loc, qs.shape[-1]), jnp.float32),
                    jnp.full((B, H, T_loc), -jnp.inf, jnp.float32),
                    jnp.zeros((B, H, T_loc), jnp.float32))

        def body(carry, step):
            m, l, acc, kcur, vcur = carry
            if causal:
                src = (idx - step) % n
                # 0: src<idx fully visible; 1: diagonal (local causal);
                # 2: src>idx fully masked — kernel skipped
                mode = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
                acci, mi, li = jax.lax.switch(
                    mode, [kernel_full, kernel_diag, kernel_skip],
                    kcur, vcur)
            else:
                acci, mi, li = kernel_full(kcur, vcur)
            # exact online-softmax combine across shards
            m_new = jnp.maximum(m, mi)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            a = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            b = jnp.where(jnp.isfinite(mi), jnp.exp(mi - m_safe), 0.0)
            l_new = l * a + li * b
            acc_new = acc * a[..., None] + acci * b[..., None]
            perm = [(i, (i + 1) % n) for i in range(n)]
            knext = jax.lax.ppermute(kcur, axis, perm)
            vnext = jax.lax.ppermute(vcur, axis, perm)
            return (m_new, l_new, acc_new, knext, vnext), None

        m0 = jnp.full((B, H, T_loc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, T_loc), jnp.float32)
        a0 = jnp.zeros((B, H, T_loc, qs.shape[-1]), jnp.float32)
        m0, l0, a0 = _pvary(m0, l0, a0)
        (m, l, acc, _, _), _ = jax.lax.scan(body, (m0, l0, a0, ks, vs),
                                            jnp.arange(n))
        out = acc / jnp.maximum(l[..., None], 1e-37)
        from ..ops import pallas_attention as pa
        return out.astype(qs.dtype), pa.lse_of(m, l)

    def per_shard_flash_bwd(qs, ks, vs, out, lse, g):
        """Ring backward with the Pallas backward kernel (round 5; one
        kernel returns dq, dk and dv since PR 34).

        The forward's combined (full-sequence) lse and out make each
        per-shard ``flash_attention_bwd`` call an exact partial: summing
        dq locally and carrying dk/dv accumulators around the ring WITH
        their K/V shard yields the exact gradients after n steps (each
        accumulator visits every Q shard once, then arrives home).
        """
        from ..ops import pallas_attention as pa
        idx = jax.lax.axis_index(axis)
        T_loc = qs.shape[2]
        B, H, D = qs.shape[0], qs.shape[1], qs.shape[-1]
        bs = block_size
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)

        def bwd_full(kc, vc):
            return pa.flash_attention_bwd(qs, kc, vc, g, lse, delta,
                                          False, sc, bs, bs)

        def bwd_diag(kc, vc):
            return pa.flash_attention_bwd(qs, kc, vc, g, lse, delta,
                                          True, sc, bs, bs)

        def bwd_skip(kc, vc):
            z = jnp.zeros((B, H, T_loc, D), jnp.float32)
            return z, z, z

        def body(carry, step):
            dq, kcur, vcur, dka, dva = carry
            if causal:
                src = (idx - step) % n
                mode = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
                dqi, dki, dvi = jax.lax.switch(
                    mode, [bwd_full, bwd_diag, bwd_skip], kcur, vcur)
            else:
                dqi, dki, dvi = bwd_full(kcur, vcur)
            dq = dq + dqi
            dka = dka + dki
            dva = dva + dvi
            perm = [(i, (i + 1) % n) for i in range(n)]
            knext = jax.lax.ppermute(kcur, axis, perm)
            vnext = jax.lax.ppermute(vcur, axis, perm)
            dka = jax.lax.ppermute(dka, axis, perm)
            dva = jax.lax.ppermute(dva, axis, perm)
            return (dq, knext, vnext, dka, dva), None

        z = jnp.zeros((B, H, T_loc, D), jnp.float32)
        dq0, dka0, dva0 = _pvary(z, z, z)
        (dq, _, _, dka, dva), _ = jax.lax.scan(
            body, (dq0, ks, vs, dka0, dva0), jnp.arange(n))
        return (dq.astype(qs.dtype), dka.astype(ks.dtype),
                dva.astype(vs.dtype))

    @jax.custom_vjp
    def _ring_flash(qs, ks, vs):
        out, _ = per_shard_flash(qs, ks, vs)
        return out

    def _rf_fwd(qs, ks, vs):
        out, lse = per_shard_flash(qs, ks, vs)
        return out, (qs, ks, vs, out, lse)

    def _rf_bwd(res, g):
        qs, ks, vs, out, lse = res
        return per_shard_flash_bwd(qs, ks, vs, out, lse, g)

    _ring_flash.defvjp(_rf_fwd, _rf_bwd)

    from ..ops import pallas_attention as pa
    B, H, T = q.shape[0], q.shape[1], q.shape[2]
    use_flash = use_pallas and T % n == 0 and \
        pa.flash_attention_available(B, H, T // n, T // n, D, q.dtype)

    def per_shard(qs, ks, vs):
        if pa.INTERPRET:        # test hook: force the interpreter on CPU
            return _ring_flash(qs, ks, vs)
        return jax.lax.platform_dependent(
            qs, ks, vs, tpu=_ring_flash, default=per_shard_scan)

    spec = P(None, None, axis, None)
    # pallas_call inside shard_map is not vma-checkable (the per-shard
    # kernel's internal slices are unvarying); exactness vs the checked
    # scan formulation is pinned by tests
    f = jax.shard_map(per_shard if use_flash else per_shard_scan,
                      mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec, check_vma=not use_flash)
    return f(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                      causal: bool = False, scale: Optional[float] = None):
    """Ulysses/DeepSpeed-style: all-to-all so each chip gets ALL sequence for
    a subset of heads, runs full attention locally, then all-to-alls back."""
    n = mesh.shape[axis]

    def per_shard(qs, ks, vs):
        # [B, H, T/n, D] -> all_to_all over heads -> [B, H/n, T, D]
        def a2a(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)
        qh, kh, vh = a2a(qs), a2a(ks), a2a(vs)
        out = blockwise_attention(qh, kh, vh, causal=causal, scale=scale)
        return jax.lax.all_to_all(out, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    spec = P(None, None, axis, None)
    f = jax.shard_map(per_shard, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec)
    return f(q, k, v)
