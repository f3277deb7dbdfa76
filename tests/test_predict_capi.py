"""C predict ABI: the c_predict_api surface exercised from real C callers.

Parity model: reference include/mxnet/c_predict_api.h:78-200 consumed by
example/image-classification/predict-cpp and the amalgamation builds.  Two
consumers are tested: a pure-C binary (src/tests/predict_test.c, compiled
here) in a fresh process where the library bootstraps the embedded
interpreter itself, and in-process ctypes where it must piggyback on the
already-running interpreter.
"""
import ctypes
import os
import shutil
import subprocess

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
LIB = os.path.join(REPO, "mxnet_tpu", "_native",
                   "libmxnet_tpu_predict.so")

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("python3-config") is None,
    reason="no C++ toolchain")


def _make(target):
    r = subprocess.run(["make", "-C", SRC, target], capture_output=True,
                       text=True)
    if r.returncode != 0:
        pytest.skip("native build failed: %s" % r.stderr[-500:])


def _model(tmp_path):
    S = mx.symbol
    x = S.var("data")
    c = S.Convolution(x, kernel=(3, 3), num_filter=4, pad=(1, 1),
                      name="c1")
    a = S.Activation(c, act_type="relu")
    p = S.Pooling(a, kernel=(2, 2), stride=(2, 2), pool_type="max")
    fc = S.FullyConnected(S.Flatten(p), num_hidden=5, name="fc")
    out = S.softmax(fc, axis=1, name="prob")
    rng = np.random.RandomState(0)
    shapes, _, _ = out.infer_shape(data=(2, 1, 8, 8))
    params = {n: nd.array(rng.uniform(-0.3, 0.3, s).astype(np.float32))
              for n, s in zip(out.list_arguments(), shapes) if n != "data"}
    sym_file = str(tmp_path / "symbol.json")
    with open(sym_file, "w") as f:
        f.write(out.tojson())
    nd.save(str(tmp_path / "params.bin"), params)
    params_file = str(tmp_path / "params.bin.npz")
    # the C test feeds input[i] = (i % 17) / 8 - 1
    n = 2 * 1 * 8 * 8
    inp = np.array([(i % 17) / 8.0 - 1.0 for i in range(n)],
                   np.float32).reshape(2, 1, 8, 8)
    from mxnet_tpu.predictor import Predictor
    pr = Predictor(out.tojson(), params_file,
                   input_shapes={"data": (2, 1, 8, 8)})
    pr.forward(data=inp)
    expected = pr.get_output(0).asnumpy()
    return sym_file, params_file, expected


def test_c_binary_end_to_end(tmp_path):
    """A pure-C process (no Python of its own) creates, runs, and frees a
    predictor; outputs must match the Python Predictor exactly."""
    _make("predict_test")
    sym_file, params_file, expected = _model(tmp_path)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO     # repo only: the subprocess runs on CPU
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [os.path.join(SRC, "predict_test"), sym_file, params_file,
         "2", "1", "8", "8"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    got = np.array([float(line) for line in r.stdout.split()],
                   np.float32).reshape(expected.shape)
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)
    assert "output shape: 2 5" in r.stderr


def test_ndlist_ctypes_inprocess(tmp_path):
    """MXNDListCreate/Get via ctypes in the live interpreter (the library
    must not try to re-initialize Python)."""
    _make(os.path.relpath(LIB, SRC))
    _, params_file, _ = _model(tmp_path)
    lib = ctypes.CDLL(LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p
    with open(params_file, "rb") as f:
        blob = f.read()
    handle = ctypes.c_void_p()
    length = ctypes.c_uint()
    rc = lib.MXNDListCreate(blob, len(blob), ctypes.byref(handle),
                            ctypes.byref(length))
    assert rc == 0, lib.MXGetLastError()
    assert length.value == 4  # c1 weight/bias, fc weight/bias
    key = ctypes.c_char_p()
    data = ctypes.POINTER(ctypes.c_float)()
    shape = ctypes.POINTER(ctypes.c_uint)()
    ndim = ctypes.c_uint()
    names = set()
    for i in range(length.value):
        rc = lib.MXNDListGet(handle, i, ctypes.byref(key),
                             ctypes.byref(data), ctypes.byref(shape),
                             ctypes.byref(ndim))
        assert rc == 0, lib.MXGetLastError()
        names.add(key.value.decode())
        assert ndim.value >= 1
    assert names == {"c1_weight", "c1_bias", "fc_weight", "fc_bias"}
    # out-of-range index errors cleanly
    assert lib.MXNDListGet(handle, 99, ctypes.byref(key),
                           ctypes.byref(data), ctypes.byref(shape),
                           ctypes.byref(ndim)) != 0
    assert b"out of range" in lib.MXGetLastError()
    assert lib.MXNDListFree(handle) == 0


def test_predictor_ctypes_inprocess(tmp_path):
    """Full create/set/forward/get/reshape cycle via ctypes in-process."""
    _make(os.path.relpath(LIB, SRC))
    sym_file, params_file, expected = _model(tmp_path)
    lib = ctypes.CDLL(LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p
    with open(sym_file) as f:
        sym_json = f.read().encode()
    with open(params_file, "rb") as f:
        blob = f.read()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint * 2)(0, 4)
    shape = (ctypes.c_uint * 4)(2, 1, 8, 8)
    handle = ctypes.c_void_p()
    rc = lib.MXPredCreate(sym_json, blob, len(blob), 1, 0, 1, keys,
                          indptr, shape, ctypes.byref(handle))
    assert rc == 0, lib.MXGetLastError()
    n = 2 * 8 * 8
    inp = np.array([(i % 17) / 8.0 - 1.0 for i in range(n)], np.float32)
    buf = inp.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    assert lib.MXPredSetInput(handle, b"data", buf, n) == 0, \
        lib.MXGetLastError()
    assert lib.MXPredForward(handle) == 0, lib.MXGetLastError()
    oshape = ctypes.POINTER(ctypes.c_uint)()
    ondim = ctypes.c_uint()
    assert lib.MXPredGetOutputShape(handle, 0, ctypes.byref(oshape),
                                    ctypes.byref(ondim)) == 0
    dims = [oshape[i] for i in range(ondim.value)]
    assert dims == [2, 5]
    out = np.zeros(10, np.float32)
    assert lib.MXPredGetOutput(
        handle, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        10) == 0, lib.MXGetLastError()
    np.testing.assert_allclose(out.reshape(2, 5), expected, rtol=1e-5,
                               atol=1e-5)
    # reshape to batch 1 and re-run
    shape1 = (ctypes.c_uint * 4)(1, 1, 8, 8)
    fresh = ctypes.c_void_p()
    assert lib.MXPredReshape(handle, 1, keys, indptr, shape1,
                             ctypes.byref(fresh)) == 0, \
        lib.MXGetLastError()
    assert lib.MXPredSetInput(fresh, b"data", buf, n // 2) == 0
    assert lib.MXPredForward(fresh) == 0
    out1 = np.zeros(5, np.float32)
    assert lib.MXPredGetOutput(
        fresh, 0, out1.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        5) == 0
    assert lib.MXPredFree(fresh) == 0
    assert lib.MXPredFree(handle) == 0


def test_ndlist_list_format_and_pointer_stability(tmp_path):
    """List-format blobs (nd.save of a list) get empty keys; pointers from
    earlier MXNDListGet calls stay valid after later ones (reference
    contract: valid until MXNDListFree)."""
    _make(os.path.relpath(LIB, SRC))
    arrs = [nd.array(np.full((2, 2), 1.0, np.float32)),
            nd.array(np.full((3,), 2.0, np.float32))]
    nd.save(str(tmp_path / "list.bin"), arrs)
    with open(str(tmp_path / "list.bin.npz"), "rb") as f:
        blob = f.read()
    lib = ctypes.CDLL(LIB)
    lib.MXGetLastError.restype = ctypes.c_char_p
    handle = ctypes.c_void_p()
    length = ctypes.c_uint()
    rc = lib.MXNDListCreate(blob, len(blob), ctypes.byref(handle),
                            ctypes.byref(length))
    assert rc == 0, lib.MXGetLastError()
    assert length.value == 2
    held = []
    for i in range(2):
        key = ctypes.c_char_p()
        data = ctypes.POINTER(ctypes.c_float)()
        shape = ctypes.POINTER(ctypes.c_uint)()
        ndim = ctypes.c_uint()
        assert lib.MXNDListGet(handle, i, ctypes.byref(key),
                               ctypes.byref(data), ctypes.byref(shape),
                               ctypes.byref(ndim)) == 0
        held.append((key.value, data, shape, ndim.value))
    # entry 0's pointers must still describe entry 0 after fetching entry 1
    key0, data0, shape0, ndim0 = held[0]
    assert key0 == b""
    assert ndim0 == 2 and shape0[0] == 2 and shape0[1] == 2
    assert [data0[j] for j in range(4)] == [1.0] * 4
    key1, data1, shape1, ndim1 = held[1]
    assert ndim1 == 1 and shape1[0] == 3
    assert [data1[j] for j in range(3)] == [2.0] * 3
    assert lib.MXNDListFree(handle) == 0
