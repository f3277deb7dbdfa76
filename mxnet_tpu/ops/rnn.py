"""Fused multi-layer RNN/LSTM/GRU operator.

Reference analog: ``src/operator/rnn-inl.h:149`` (RNNParam), ``rnn_impl.h``
(CPU impl), ``cudnn_rnn-inl.h`` (fused cuDNN path).  Same packed-parameter
convention: ONE flat vector holding, per layer & direction, [i2h_W, h2h_W]
for all layers, then [i2h_bias, h2h_bias] for all layers.

TPU-native design: per layer the input projection ``x @ W_i2h^T + b`` is ONE
large MXU matmul over the whole (T*B, in) sequence, hoisted OUT of the time
loop; only the inherently sequential hidden-to-hidden recurrence runs in a
``lax.scan`` (compiled once, no per-step dispatch).  Bidirectional runs a
second scan over the reversed sequence.  Gate orders match the reference:
LSTM [i, f, g, o], GRU [r, z, n] (cuDNN variant).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register, param

_NGATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def rnn_param_size(num_layers, state_size, input_size, bidirectional, mode):
    """Total packed parameter count (reference: rnn-inl.h GetParamSize)."""
    ng = _NGATES[mode]
    dirs = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * dirs
        size += dirs * ng * state_size * (in_sz + state_size)
    size += num_layers * dirs * 2 * ng * state_size
    return size


def _unpack(params, num_layers, h, input_size, dirs, ng):
    """Split the flat vector into per-(layer,dir) W/R/bW/bR."""
    out = []
    off = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else h * dirs
        for d in range(dirs):
            W = params[off:off + ng * h * in_sz].reshape(ng * h, in_sz)
            off += ng * h * in_sz
            R = params[off:off + ng * h * h].reshape(ng * h, h)
            off += ng * h * h
            out.append([W, R, None, None])
    for layer in range(num_layers):
        for d in range(dirs):
            i = layer * dirs + d
            out[i][2] = params[off:off + ng * h]
            off += ng * h
            out[i][3] = params[off:off + ng * h]
            off += ng * h
    return out


def _lstm_scan_xla(xproj, h0, c0, R, bR):
    """The LSTM recurrence as a ``lax.scan``: the formulation every
    non-TPU target runs, and the reference the Pallas kernel is held to."""
    def step(carry, row):
        hh, cc = carry
        gates = row + hh @ R.T + bR
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c_new = jax.nn.sigmoid(f) * cc + jax.nn.sigmoid(i) * jnp.tanh(g)
        h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
        return (h_new, c_new), h_new

    (hT, cT), ys = jax.lax.scan(step, (h0, c0), xproj)
    return ys, hT, cT


def _cell_scan(mode, xproj, h0, c0, R, bR):
    """Scan the recurrence over time.  xproj: (T, B, ng*h)."""
    h_sz = h0.shape[-1]

    if mode == "lstm":
        from . import pallas_rnn

        if pallas_rnn.lstm_scan_available(xproj.shape[1], h_sz,
                                          xproj.dtype) \
                and h0.dtype == xproj.dtype and c0.dtype == xproj.dtype:
            # mixed-dtype states (e.g. f64 zeros against f32 activations
            # under x64) take the promoting scan; the kernel is monodtype
            if pallas_rnn.INTERPRET:   # test hook: force the interpreter
                return pallas_rnn.lstm_scan(xproj, h0, c0, R, bR)
            # fused Pallas recurrence (cuDNN-RNN role): whole time loop in
            # one kernel, h/c resident in VMEM, custom VJP.  The platform
            # branch is resolved at LOWERING time, so CPU-committed arrays
            # on a TPU host compile the scan, never Mosaic (advisor r03).
            return jax.lax.platform_dependent(
                xproj, h0, c0,
                tpu=lambda xp, h, c: pallas_rnn.lstm_scan(xp, h, c, R, bR),
                default=lambda xp, h, c: _lstm_scan_xla(xp, h, c, R, bR))
        return _lstm_scan_xla(xproj, h0, c0, R, bR)

    if mode == "gru":
        Rr, Rz, Rn = jnp.split(R, 3, axis=0)
        bRr, bRz, bRn = jnp.split(bR, 3)

        def step(h, xp):
            xr, xz, xn = jnp.split(xp, 3, axis=-1)
            r = jax.nn.sigmoid(xr + h @ Rr.T + bRr)
            z = jax.nn.sigmoid(xz + h @ Rz.T + bRz)
            n = jnp.tanh(xn + r * (h @ Rn.T + bRn))
            h_new = (1 - z) * n + z * h
            return h_new, h_new

        hT, ys = jax.lax.scan(step, h0, xproj)
        return ys, hT, None

    act = jnp.tanh if mode == "rnn_tanh" else jax.nn.relu

    def step(h, xp):
        h_new = act(xp + h @ R.T + bR)
        return h_new, h_new

    hT, ys = jax.lax.scan(step, h0, xproj)
    return ys, hT, None


@register("RNN", nin=-1, aliases=("rnn",), nout=3, needs_rng=True,
          train_aware=True,
          env_keys=("MXNET_TPU_PALLAS_RNN",),
          visible=lambda a: (3 if a["mode"] == "lstm" else 2)
          if a["state_outputs"] else 1,
          params={"state_size": param(int, required=True),
                  "num_layers": param(int, required=True),
                  "bidirectional": param(bool, False),
                  "mode": param(["rnn_relu", "rnn_tanh", "lstm", "gru"],
                                required=True),
                  "p": param(float, 0.0),
                  "state_outputs": param(bool, False),
                  "lstm_state_clip_min": param(float, None),
                  "lstm_state_clip_max": param(float, None),
                  "lstm_state_clip_nan": param(bool, False),
                  "__train__": param(bool, False)})
def _rnn(attrs, key, data, params, state, *maybe_cell):
    """Fused RNN forward.  data: (T, B, F) [TNC]; state: (L*dirs, B, h)."""
    mode = attrs["mode"]
    h = attrs["state_size"]
    L = attrs["num_layers"]
    dirs = 2 if attrs["bidirectional"] else 1
    ng = _NGATES[mode]
    T, B, F = data.shape
    wr = _unpack(params, L, h, F, dirs, ng)
    cell = maybe_cell[0] if maybe_cell else None

    x = data
    hTs, cTs = [], []
    dropout = attrs["p"] if attrs.get("__train__") else 0.0
    for layer in range(L):
        outs = []
        for d in range(dirs):
            i = layer * dirs + d
            W, R, bW, bR = wr[i]
            xin = x if d == 0 else jnp.flip(x, axis=0)
            xproj = xin @ W.T + bW          # one MXU pass for all timesteps
            h0 = state[i]
            c0 = cell[i] if cell is not None else None
            ys, hT, cT = _cell_scan(mode, xproj, h0, c0, R, bR)
            if d == 1:
                ys = jnp.flip(ys, axis=0)
            outs.append(ys)
            hTs.append(hT)
            if cT is not None:
                if attrs["lstm_state_clip_min"] is not None and \
                        attrs["lstm_state_clip_max"] is not None:
                    cT = jnp.clip(cT, attrs["lstm_state_clip_min"],
                                  attrs["lstm_state_clip_max"])
                cTs.append(cT)
        x = outs[0] if dirs == 1 else jnp.concatenate(outs, axis=-1)
        if dropout > 0 and layer < L - 1:
            sub = jax.random.fold_in(key, layer)
            keep = jax.random.bernoulli(sub, 1 - dropout, x.shape)
            x = jnp.where(keep, x / (1 - dropout), 0)
    out_h = jnp.stack(hTs)
    out_c = jnp.stack(cTs) if cTs else jnp.zeros_like(out_h)
    return x, out_h, out_c
