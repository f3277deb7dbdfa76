"""Systematic finite-difference gradient sweep over core operators.

Parity model: reference tests/python/unittest/test_operator.py — the
largest suite, whose backbone is ``check_numeric_gradient`` applied per
op.  Here one parameterized sweep covers the op families' analytic VJPs
against central differences (test_utils.check_numeric_gradient), plus
symbolic forward golden checks for a few ops with closed forms.
"""
import zlib

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import test_utils as tu


def _u(shape, lo=-1.0, hi=1.0, rng=None):
    rng = rng or np.random
    return rng.uniform(lo, hi, shape).astype(np.float64)


# (name, symbol builder, location builder)
CASES = [
    ("FullyConnected",
     lambda: sym.FullyConnected(sym.var("data"), sym.var("w"),
                                sym.var("b"), num_hidden=3),
     lambda r: {"data": _u((2, 4), rng=r), "w": _u((3, 4), rng=r),
                "b": _u((3,), rng=r)}),
    ("Convolution",
     lambda: sym.Convolution(sym.var("data"), sym.var("w"),
                             kernel=(3, 3), num_filter=2, pad=(1, 1),
                             no_bias=True),
     lambda r: {"data": _u((1, 2, 5, 5), rng=r),
                "w": _u((2, 2, 3, 3), rng=r)}),
    ("Deconvolution",
     lambda: sym.Deconvolution(sym.var("data"), sym.var("w"),
                               kernel=(2, 2), num_filter=2, no_bias=True),
     lambda r: {"data": _u((1, 2, 3, 3), rng=r),
                "w": _u((2, 2, 2, 2), rng=r)}),
    ("Pooling_max",
     lambda: sym.Pooling(sym.var("data"), kernel=(2, 2), stride=(2, 2),
                         pool_type="max"),
     lambda r: {"data": _u((1, 2, 4, 4), rng=r) +
                np.arange(32).reshape(1, 2, 4, 4) * 0.05}),
    ("Pooling_avg",
     lambda: sym.Pooling(sym.var("data"), kernel=(2, 2), stride=(2, 2),
                         pool_type="avg"),
     lambda r: {"data": _u((1, 2, 4, 4), rng=r)}),
    ("Activation_tanh",
     lambda: sym.Activation(sym.var("data"), act_type="tanh"),
     lambda r: {"data": _u((3, 4), rng=r)}),
    ("softmax",
     lambda: sym.softmax(sym.var("data"), axis=-1),
     lambda r: {"data": _u((3, 5), rng=r)}),
    ("LayerNorm",
     lambda: sym.LayerNorm(sym.var("data"), sym.var("g"), sym.var("b")),
     lambda r: {"data": _u((3, 6), rng=r),
                "g": _u((6,), 0.5, 1.5, rng=r), "b": _u((6,), rng=r)}),
    ("dot",
     lambda: sym.dot(sym.var("a"), sym.var("b")),
     lambda r: {"a": _u((3, 4), rng=r), "b": _u((4, 2), rng=r)}),
    ("batch_dot",
     lambda: sym.batch_dot(sym.var("a"), sym.var("b")),
     lambda r: {"a": _u((2, 3, 4), rng=r), "b": _u((2, 4, 2), rng=r)}),
    ("broadcast_mul",
     lambda: sym.broadcast_mul(sym.var("a"), sym.var("b")),
     lambda r: {"a": _u((3, 4), rng=r), "b": _u((1, 4), rng=r)}),
    ("elemwise_div",
     lambda: sym.elemwise_div(sym.var("a"), sym.var("b")),
     lambda r: {"a": _u((3, 4), rng=r),
                "b": _u((3, 4), 0.5, 1.5, rng=r)}),
    ("exp", lambda: sym.exp(sym.var("data")),
     lambda r: {"data": _u((3, 4), rng=r)}),
    ("log", lambda: sym.log(sym.var("data")),
     lambda r: {"data": _u((3, 4), 0.5, 2.0, rng=r)}),
    ("sqrt", lambda: sym.sqrt(sym.var("data")),
     lambda r: {"data": _u((3, 4), 0.5, 2.0, rng=r)}),
    ("sum_axis",
     lambda: sym.sum(sym.var("data"), axis=1),
     lambda r: {"data": _u((3, 4), rng=r)}),
    ("mean_keepdims",
     lambda: sym.mean(sym.var("data"), axis=(1, 2), keepdims=True),
     lambda r: {"data": _u((2, 3, 4), rng=r)}),
    ("transpose",
     lambda: sym.transpose(sym.var("data"), axes=(1, 0, 2)),
     lambda r: {"data": _u((2, 3, 4), rng=r)}),
    ("Reshape",
     lambda: sym.Reshape(sym.var("data"), shape=(4, 6)),
     lambda r: {"data": _u((2, 3, 4), rng=r)}),
    ("Concat",
     lambda: sym.concat(sym.var("a"), sym.var("b"), dim=1),
     lambda r: {"a": _u((2, 3), rng=r), "b": _u((2, 2), rng=r)}),
    ("slice_axis",
     lambda: sym.slice_axis(sym.var("data"), axis=1, begin=1, end=3),
     lambda r: {"data": _u((2, 4), rng=r)}),
    ("clip",
     lambda: sym.clip(sym.var("data"), a_min=-0.4, a_max=0.4),
     lambda r: {"data": _u((3, 4), rng=r) * 2},),
    ("LeakyReLU_leaky",
     lambda: sym.LeakyReLU(sym.var("data"), act_type="leaky", slope=0.3),
     lambda r: {"data": _u((3, 4), rng=r) + 0.1}),
    ("Embedding",
     lambda: sym.Embedding(sym.var("idx"), sym.var("w"), input_dim=7,
                           output_dim=3),
     lambda r: {"idx": np.array([[1, 3], [6, 0]], np.float64),
                "w": _u((7, 3), rng=r)}),
    ("L2Normalization",
     lambda: sym.L2Normalization(sym.var("data")),
     lambda r: {"data": _u((2, 5), 0.3, 1.0, rng=r)}),
    ("smooth_l1",
     lambda: sym.smooth_l1(sym.var("data"), scalar=1.0),
     lambda r: {"data": _u((3, 4), rng=r) * 3}),
]


@pytest.mark.parametrize("name,builder,loc", CASES,
                         ids=[c[0] for c in CASES])
def test_numeric_gradient(name, builder, loc):
    rng = np.random.RandomState(zlib.crc32(name.encode()))
    location = loc(rng)
    grad_nodes = None
    if name == "Embedding":
        grad_nodes = ["w"]        # integer indices have no gradient
    tu.check_numeric_gradient(builder(), location, numeric_eps=1e-3,
                              rtol=1e-2, atol=1e-3,
                              grad_nodes=grad_nodes)


def test_forward_golden_values():
    """Closed-form forward checks (check_symbolic_forward pattern)."""
    x = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    tu.check_symbolic_forward(sym.exp(sym.var("data")), {"data": x},
                              [np.exp(x)])
    tu.check_symbolic_forward(
        sym.softmax(sym.var("data"), axis=-1), {"data": x},
        [np.exp(x) / np.exp(x).sum(-1, keepdims=True)])
    tu.check_symbolic_forward(
        sym.L2Normalization(sym.var("data")), {"data": x},
        [x / np.linalg.norm(x, axis=1, keepdims=True)], rtol=1e-4)


def test_backward_golden_values():
    """check_symbolic_backward pattern: closed-form gradients."""
    x = np.array([[0.5, -0.5], [1.5, -2.0]], np.float32)
    og = np.ones_like(x)
    tu.check_symbolic_backward(sym.exp(sym.var("data")), {"data": x},
                               [og], {"data": np.exp(x)})
    tu.check_symbolic_backward(
        sym.clip(sym.var("data"), a_min=-1.0, a_max=1.0), {"data": x},
        [og], {"data": (np.abs(x) <= 1.0).astype(np.float32)})
