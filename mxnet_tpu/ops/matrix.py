"""Matrix / shape-manipulation / indexing operators.

Reference analog: ``src/operator/tensor/matrix_op.cc`` (reshape with MXNet's
0/-1/-2/-3/-4 codes, transpose, slice family, dot, concat/stack/split, tile,
repeat, pad, flip, space/depth), ``indexing_op.cc`` (take, one_hot, pick,
gather_nd, scatter_nd, Embedding), ``cast``.  All are XLA-native
(reshape/transpose are layout ops; dot/batch_dot hit the MXU directly).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register, param
from ..base import MXNetError
from ..parallel.mesh import lookup as _lookup


def infer_reshape(src_shape, target, reverse=False):
    """MXNet reshape target semantics (matrix_op.cc ReshapeShape):
    0=keep, -1=infer, -2=copy rest, -3=merge two, -4=split (next 2 entries)."""
    src = list(src_shape)
    if reverse:
        src = src[::-1]
        target = tuple(target)[::-1]
    out = []
    i = 0  # index into src
    t = list(target)
    j = 0
    while j < len(t):
        d = t[j]
        if d == 0:
            out.append(src[i]); i += 1
        elif d == -1:
            out.append(-1); i += 1
        elif d == -2:
            out.extend(src[i:]); i = len(src)
        elif d == -3:
            out.append(src[i] * src[i + 1]); i += 2
        elif d == -4:
            d1, d2 = t[j + 1], t[j + 2]
            if d1 == -1:
                d1 = src[i] // d2
            if d2 == -1:
                d2 = src[i] // d1
            out.extend([d1, d2]); i += 1; j += 2
        else:
            out.append(d)
            if i < len(src):
                i += 1
        j += 1
    if -1 in out:
        known = int(np.prod([d for d in out if d != -1])) or 1
        total = int(np.prod(src_shape)) if src_shape else 1
        out[out.index(-1)] = total // known
    if reverse:
        out = out[::-1]
    return tuple(int(d) for d in out)


@register("Reshape", nin=1, aliases=("reshape",),
          params={"shape": param("shape", ()), "reverse": param(bool, False),
                  "target_shape": param("shape", ()),
                  "keep_highest": param(bool, False)})
def _reshape(attrs, x):
    tgt = attrs["shape"] or attrs["target_shape"]
    return jnp.reshape(x, infer_reshape(x.shape, tgt, attrs["reverse"]))


@register("Flatten", nin=1, aliases=("flatten",))
def _flatten(attrs, x):
    return jnp.reshape(x, (x.shape[0], -1))


@register("transpose", nin=1, params={"axes": param("shape", ())})
def _transpose(attrs, x):
    axes = attrs["axes"] or None
    return jnp.transpose(x, axes)


@register("expand_dims", nin=1, params={"axis": param(int, 0, required=True)})
def _expand_dims(attrs, x):
    return jnp.expand_dims(x, attrs["axis"])


@register("squeeze", nin=1, params={"axis": param("shape", None)})
def _squeeze(attrs, x):
    ax = attrs["axis"]
    return jnp.squeeze(x, axis=tuple(a % x.ndim for a in ax) if ax else None)


@register("slice", nin=1, aliases=("crop",),
          params={"begin": param("shape", ()), "end": param("shape", ()),
                  "step": param("shape", ())})
def _slice(attrs, x):
    idx = []
    step = attrs["step"] or (None,) * len(attrs["begin"])
    for b, e, s in zip(attrs["begin"], attrs["end"], step):
        idx.append(slice(None if b in (None, "None") else b,
                         None if e in (None, "None") else e,
                         None if s in (None, 0, "None") else s))
    return x[tuple(idx)]


@register("slice_axis", nin=1,
          params={"axis": param(int, 0, required=True),
                  "begin": param(int, 0, required=True),
                  "end": param("shape", None)})
def _slice_axis(attrs, x):
    ax = attrs["axis"] % x.ndim
    end = attrs["end"]
    end = None if end in (None, ()) else int(end[0]) if isinstance(end, tuple) else int(end)
    idx = [slice(None)] * x.ndim
    idx[ax] = slice(attrs["begin"], end)
    return x[tuple(idx)]


@register("slice_like", nin=2, params={"axes": param("shape", ())})
def _slice_like(attrs, x, like):
    axes = attrs["axes"] or tuple(range(x.ndim))
    idx = [slice(None)] * x.ndim
    for a in axes:
        a = a % x.ndim
        idx[a] = slice(0, like.shape[a])
    return x[tuple(idx)]


@register("dot", nin=2, params={"transpose_a": param(bool, False),
                                "transpose_b": param(bool, False)})
def _dot(attrs, a, b):
    """MXU matmul.  Reference dot (matrix_op.cc) contracts the last axis of a
    with the first of b for ndim>2; fp32 accumulation is preserved."""
    if attrs["transpose_a"]:
        a = jnp.transpose(a, tuple(range(1, a.ndim)) + (0,)) if a.ndim > 2 else a.T
    if attrs["transpose_b"]:
        b = jnp.transpose(b, (b.ndim - 1,) + tuple(range(b.ndim - 1))) if b.ndim > 2 else b.T
    return jnp.tensordot(a, b, axes=1)


@register("batch_dot", nin=2, params={"transpose_a": param(bool, False),
                                      "transpose_b": param(bool, False)})
def _batch_dot(attrs, a, b):
    if attrs["transpose_a"]:
        a = jnp.swapaxes(a, -1, -2)
    if attrs["transpose_b"]:
        b = jnp.swapaxes(b, -1, -2)
    return jnp.matmul(a, b)


@register("Concat", nin=-1, aliases=("concat",),
          params={"dim": param(int, 1), "num_args": param(int, 0)})
def _concat(attrs, *xs):
    return jnp.concatenate(xs, axis=attrs["dim"])


@register("stack", nin=-1, params={"axis": param(int, 0),
                                   "num_args": param(int, 0)})
def _stack(attrs, *xs):
    return jnp.stack(xs, axis=attrs["axis"])


def _split_nout(attrs):
    return 1 if attrs.get("squeeze_axis") and attrs["num_outputs"] == 1 \
        else attrs["num_outputs"]


@register("SliceChannel", nin=1, aliases=("split",),
          params={"num_outputs": param(int, 1, required=True),
                  "axis": param(int, 1), "squeeze_axis": param(bool, False)},
          nout=lambda attrs: attrs["num_outputs"])
def _split(attrs, x):
    parts = jnp.split(x, attrs["num_outputs"], axis=attrs["axis"])
    if attrs["squeeze_axis"]:
        parts = [jnp.squeeze(p, axis=attrs["axis"]) for p in parts]
    return tuple(parts) if len(parts) > 1 else parts[0]


@register("tile", nin=1, params={"reps": param("shape", (), required=True)})
def _tile(attrs, x):
    return jnp.tile(x, attrs["reps"])


@register("repeat", nin=1, params={"repeats": param(int, 1, required=True),
                                   "axis": param("shape", None)})
def _repeat(attrs, x):
    ax = attrs["axis"]
    return jnp.repeat(x, attrs["repeats"],
                      axis=None if ax is None else int(ax[0]))


@register("Pad", nin=1, aliases=("pad",),
          params={"mode": param(["constant", "edge", "reflect"], "constant"),
                  "pad_width": param("shape", (), required=True),
                  "constant_value": param(float, 0.0)})
def _pad(attrs, x):
    pw = attrs["pad_width"]
    pairs = [(pw[2 * i], pw[2 * i + 1]) for i in range(len(pw) // 2)]
    if attrs["mode"] == "constant":
        return jnp.pad(x, pairs, constant_values=attrs["constant_value"])
    return jnp.pad(x, pairs, mode=attrs["mode"])


@register("reverse", nin=1, aliases=("flip",),
          params={"axis": param("shape", (), required=True)})
def _reverse(attrs, x):
    out = x
    for a in attrs["axis"]:
        out = jnp.flip(out, axis=a)
    return out


@register("SwapAxis", nin=1, aliases=("swapaxes",),
          params={"dim1": param(int, 0), "dim2": param(int, 0)})
def _swapaxes(attrs, x):
    return jnp.swapaxes(x, attrs["dim1"], attrs["dim2"])


@register("depth_to_space", nin=1, params={"block_size": param(int, 1, required=True)})
def _depth_to_space(attrs, x):
    b = attrs["block_size"]
    n, c, h, w = x.shape
    y = x.reshape(n, b, b, c // (b * b), h, w)
    y = jnp.transpose(y, (0, 3, 4, 1, 5, 2))
    return y.reshape(n, c // (b * b), h * b, w * b)


@register("space_to_depth", nin=1, params={"block_size": param(int, 1, required=True)})
def _space_to_depth(attrs, x):
    b = attrs["block_size"]
    n, c, h, w = x.shape
    y = x.reshape(n, c, h // b, b, w // b, b)
    y = jnp.transpose(y, (0, 3, 5, 1, 2, 4))
    return y.reshape(n, c * b * b, h // b, w // b)


@register("reorg", nin=1, aliases=("newreorg",),
          params={"stride": param(int, 2)})
def _reorg(attrs, x):
    """YOLO-style reorg from the yangyu12 fork (src/operator/nn/reorg.cc):
    space-to-depth with stride s on NCHW."""
    s = attrs["stride"]
    n, c, h, w = x.shape
    y = x.reshape(n, c, h // s, s, w // s, s)
    y = jnp.transpose(y, (0, 1, 3, 5, 2, 4))
    return y.reshape(n, c * s * s, h // s, w // s)


# --------------------------------------------------------------------------
# indexing ops
# --------------------------------------------------------------------------
@register("take", nin=2, params={"axis": param(int, 0),
                                 "mode": param(["clip", "wrap", "raise"], "clip")})
def _take(attrs, a, indices):
    return jnp.take(a, indices.astype(jnp.int32), axis=attrs["axis"],
                    mode="clip" if attrs["mode"] == "raise" else attrs["mode"])


@register("batch_take", nin=2)
def _batch_take(attrs, a, indices):
    return jnp.take_along_axis(
        a, indices.astype(jnp.int32).reshape(-1, 1), axis=1).reshape(indices.shape)


@register("one_hot", nin=1, params={"depth": param(int, 0, required=True),
                                    "on_value": param(float, 1.0),
                                    "off_value": param(float, 0.0),
                                    "dtype": param("dtype", "float32")})
def _one_hot(attrs, indices):
    oh = jax.nn.one_hot(indices.astype(jnp.int32), attrs["depth"],
                        dtype=np.dtype(attrs["dtype"] or "float32"))
    return oh * (attrs["on_value"] - attrs["off_value"]) + attrs["off_value"]


@register("pick", nin=2, params={"axis": param("shape", (-1,)),
                                 "keepdims": param(bool, False),
                                 "mode": param(["clip", "wrap"], "clip")})
def _pick(attrs, x, index):
    ax = attrs["axis"]
    axis = int(ax[0]) % x.ndim if ax else x.ndim - 1
    idx = jnp.expand_dims(index.astype(jnp.int32), axis)
    out = jnp.take_along_axis(x, idx, axis=axis)
    return out if attrs["keepdims"] else jnp.squeeze(out, axis=axis)


@register("where", nin=3)
def _where(attrs, cond, x, y):
    return jnp.where(cond.astype(bool), x, y)


@register("gather_nd", nin=2)
def _gather_nd(attrs, data, indices):
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return data[idx]


@register("scatter_nd", nin=2, params={"shape": param("shape", (), required=True)})
def _scatter_nd(attrs, data, indices):
    out = jnp.zeros(attrs["shape"], dtype=data.dtype)
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return out.at[idx].add(data)


@register("Embedding", nin=2, aliases=("embedding",),
          params={"input_dim": param(int, 0, required=True),
                  "output_dim": param(int, 0, required=True),
                  "dtype": param("dtype", "float32"),
                  "sparse_grad": param(bool, False)})
def _embedding(attrs, data, weight):
    """Embedding lookup = one_hot @ weight on MXU for tiny vocab, or gather.
    XLA picks the gather path; sparse_grad handled by optimizer-side rowwise
    updates (ref: src/operator/tensor/indexing_op.cc Embedding).  In the
    mesh step a table held split along its columns is looked up in each
    chip's own columns (``parallel.mesh.lookup``)."""
    return _lookup(weight, data.astype(jnp.int32))


@register("_contrib_SparseEmbedding", nin=2,
          params={"input_dim": param(int, 0, required=True),
                  "output_dim": param(int, 0, required=True),
                  "dtype": param("dtype", "float32")})
def _sparse_embedding(attrs, data, weight):
    """Embedding whose weight gradient is row-sparse (ref:
    src/operator/tensor/indexing_op.cc _contrib_SparseEmbedding).  Compute
    is the same XLA gather as Embedding; the row-sparse gradient contract
    is honored by the trainer/kvstore layer (row_sparse_pull of touched
    rows), which is where TPU sparsity lives."""
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


@register("sparse_retain", nin=2, aliases=("_sparse_retain",))
def _sparse_retain_op(attrs, data, indices):
    """Dense view of sparse_retain: zero every row of ``data`` whose index
    is not in ``indices`` (ref: src/operator/tensor/sparse_retain.cc:27).
    For RowSparseNDArray inputs the frontend dispatches to
    ndarray.sparse.retain, which keeps the result row_sparse."""
    rows = jnp.arange(data.shape[0])
    mask = jnp.isin(rows, indices.astype(jnp.int32))
    return jnp.where(mask.reshape((-1,) + (1,) * (data.ndim - 1)), data,
                     jnp.zeros_like(data))


@register("Cast", nin=1, aliases=("cast",),
          params={"dtype": param("dtype", "float32", required=True)})
def _cast(attrs, x):
    return x.astype(np.dtype(attrs["dtype"]))


@register("amp_cast", nin=1, params={"dtype": param("dtype", "float32")})
def _amp_cast(attrs, x):
    return x.astype(np.dtype(attrs["dtype"] or "float32"))


register("zeros_like", nin=1)(lambda attrs, x: jnp.zeros_like(x))
register("ones_like", nin=1)(lambda attrs, x: jnp.ones_like(x))
register("shape_array", nin=1)(
    lambda attrs, x: jnp.asarray(x.shape, dtype=jnp.int64))
register("size_array", nin=1)(
    lambda attrs, x: jnp.asarray([x.size], dtype=jnp.int64))
register("reshape_like", nin=2)(
    lambda attrs, x, like: jnp.reshape(x, like.shape))


@register("diag", nin=1, params={"k": param(int, 0)})
def _diag(attrs, x):
    if x.ndim == 1:
        return jnp.diag(x, k=attrs["k"])
    return jnp.diagonal(x, offset=attrs["k"], axis1=-2, axis2=-1)


# --------------------------------------------------------------------------
# sequence ops (ref: src/operator/sequence_*.cc) — used by RNN/bucketing
# --------------------------------------------------------------------------
@register("SequenceMask", nin=-1, aliases=("sequence_mask",),
          params={"use_sequence_length": param(bool, False),
                  "value": param(float, 0.0), "axis": param(int, 0)})
def _sequence_mask(attrs, data, *maybe_len):
    if not attrs["use_sequence_length"] or not maybe_len:
        return data
    seq_len = maybe_len[0]
    ax = attrs["axis"]  # time axis: 0 or 1
    T = data.shape[ax]
    steps = jnp.arange(T)
    shape = [1] * data.ndim
    shape[ax] = T
    steps = steps.reshape(shape)
    lens_shape = [1] * data.ndim
    batch_ax = 1 - ax
    lens_shape[batch_ax] = data.shape[batch_ax]
    mask = steps < seq_len.astype(jnp.int32).reshape(lens_shape)
    return jnp.where(mask, data, attrs["value"])


@register("SequenceLast", nin=-1, aliases=("sequence_last",),
          params={"use_sequence_length": param(bool, False),
                  "axis": param(int, 0)})
def _sequence_last(attrs, data, *maybe_len):
    ax = attrs["axis"]
    if not attrs["use_sequence_length"] or not maybe_len:
        return jnp.take(data, data.shape[ax] - 1, axis=ax)
    seq_len = maybe_len[0].astype(jnp.int32) - 1
    idx = jnp.expand_dims(seq_len, axis=ax)
    while idx.ndim < data.ndim:
        idx = jnp.expand_dims(idx, -1)
    idx = jnp.broadcast_to(idx, data.shape[:ax] + (1,) + data.shape[ax + 1:])
    return jnp.squeeze(jnp.take_along_axis(data, idx, axis=ax), axis=ax)


@register("SequenceReverse", nin=-1, aliases=("sequence_reverse",),
          params={"use_sequence_length": param(bool, False),
                  "axis": param(int, 0)})
def _sequence_reverse(attrs, data, *maybe_len):
    if not attrs["use_sequence_length"] or not maybe_len:
        return jnp.flip(data, axis=0)
    seq_len = maybe_len[0].astype(jnp.int32)
    T = data.shape[0]
    t = jnp.arange(T)[:, None]
    src = jnp.where(t < seq_len[None, :], seq_len[None, :] - 1 - t, t)
    idx = src
    while idx.ndim < data.ndim:
        idx = idx[..., None]
    idx = jnp.broadcast_to(idx, data.shape)
    return jnp.take_along_axis(data, idx, axis=0)
