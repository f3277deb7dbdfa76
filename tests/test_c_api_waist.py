"""C API waist (N17): NDArray CRUD + imperative invoke from real C callers.

Parity model: reference include/mxnet/c_api.h Parts 0-2 (src/c_api/c_api.cc,
c_api_ndarray.cc) — the ABI every language binding rides.  Two consumers:
a pure-C binary (src/tests/c_api_test.c) in a fresh process where the
library bootstraps the embedded interpreter, and in-process ctypes where it
piggybacks on the running interpreter.
"""
import ctypes
import os
import shutil
import subprocess

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
LIB = os.path.join(REPO, "mxnet_tpu", "_native", "libmxnet_tpu_c.so")

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("python3-config") is None,
    reason="no C++ toolchain")


def _make(target):
    r = subprocess.run(["make", "-C", SRC, target], capture_output=True,
                       text=True)
    if r.returncode != 0:
        pytest.skip("native build failed: %s" % r.stderr[-500:])


def test_c_binary_full_surface():
    """The C test binary exercises create/copy/invoke/save/load/list/error
    paths in a fresh process."""
    _make("./c_api_test")
    env = dict(os.environ)
    # PYTHONPATH = repo ONLY and JAX_PLATFORMS forced: the embedded
    # interpreter must come up on CPU whatever the inherited environment
    # names, and a test worker may hold the only accelerator
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([os.path.join(SRC, "c_api_test")], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "C API TEST OK" in r.stdout


def test_c_binary_symbolic_surface():
    """The symbolic C consumer: MXSymbol create/compose/list/JSON/infer +
    MXExecutor bind/forward/backward training an MLP to convergence
    (round-5 addition — reference c_api.h Parts 3-4)."""
    _make("./c_api_sym_test")
    env = dict(os.environ)
    # PYTHONPATH = repo ONLY and JAX_PLATFORMS forced: the embedded
    # interpreter must come up on CPU whatever the inherited environment
    # names, and a test worker may hold the only accelerator
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([os.path.join(SRC, "c_api_sym_test")], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all checks passed" in r.stdout


class TestInProcess:
    """ctypes consumer sharing this interpreter (the predict-ABI pattern)."""

    @pytest.fixture(scope="class")
    def lib(self):
        _make("../mxnet_tpu/_native/libmxnet_tpu_c.so")
        lib = ctypes.CDLL(LIB)
        lib.MXGetLastError.restype = ctypes.c_char_p
        # pointer/size_t params must be marshalled 64-bit: ctypes defaults
        # unannotated integer args to 32-bit c_int, which truncates handles
        # read back as plain ints (outs[0]) once the heap is above 4GB
        lib.MXNDArraySyncCopyFromCPU.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
        lib.MXNDArraySyncCopyToCPU.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
        lib.MXNDArrayFree.argtypes = [ctypes.c_void_p]
        lib.MXNDArrayWaitToRead.argtypes = [ctypes.c_void_p]
        return lib

    def test_ndarray_roundtrip(self, lib):
        shape = (ctypes.c_uint32 * 2)(4, 5)
        h = ctypes.c_void_p()
        assert lib.MXNDArrayCreate(shape, 2, 1, 0, 0,
                                   ctypes.byref(h)) == 0
        vals = np.arange(20, dtype=np.float32)
        assert lib.MXNDArraySyncCopyFromCPU(
            h, vals.ctypes.data_as(ctypes.c_void_p), 20) == 0
        out = np.zeros(20, np.float32)
        assert lib.MXNDArraySyncCopyToCPU(
            h, out.ctypes.data_as(ctypes.c_void_p), 20) == 0
        np.testing.assert_array_equal(out, vals)
        dim = ctypes.c_uint32()
        pdata = ctypes.POINTER(ctypes.c_uint32)()
        assert lib.MXNDArrayGetShape(h, ctypes.byref(dim),
                                     ctypes.byref(pdata)) == 0
        assert dim.value == 2 and pdata[0] == 4 and pdata[1] == 5
        lib.MXNDArrayFree(h)

    def test_invoke_matches_python(self, lib):
        """C-side op invoke produces the same numbers as the Python API."""
        rng = np.random.RandomState(0)
        x = rng.randn(3, 6).astype(np.float32)
        shape = (ctypes.c_uint32 * 2)(3, 6)
        h = ctypes.c_void_p()
        lib.MXNDArrayCreate(shape, 2, 1, 0, 0, ctypes.byref(h))
        lib.MXNDArraySyncCopyFromCPU(
            h, x.ctypes.data_as(ctypes.c_void_p), x.size)
        nout = ctypes.c_int()
        outs = ctypes.POINTER(ctypes.c_void_p)()
        keys = (ctypes.c_char_p * 1)(b"act_type")
        vals = (ctypes.c_char_p * 1)(b"sigmoid")
        assert lib.MXImperativeInvokeByName(
            b"Activation", 1, ctypes.byref(h), ctypes.byref(nout),
            ctypes.byref(outs), 1, keys, vals) == 0
        assert nout.value == 1
        got = np.zeros(x.size, np.float32)
        lib.MXNDArraySyncCopyToCPU(
            outs[0], got.ctypes.data_as(ctypes.c_void_p), x.size)
        want = mx.nd.Activation(mx.nd.array(x), act_type="sigmoid").asnumpy()
        np.testing.assert_allclose(got.reshape(3, 6), want, rtol=1e-6)
        lib.MXNDArrayFree(outs[0])
        lib.MXNDArrayFree(h)

    def test_short_buffer_errors_not_overruns(self, lib):
        """SyncCopyToCPU with a wrong element count must return -1
        (reference CHECK), never scale past the buffer."""
        shape = (ctypes.c_uint32 * 1)(8,)
        h = ctypes.c_void_p()
        lib.MXNDArrayCreate(shape, 1, 1, 0, 0, ctypes.byref(h))
        small = np.zeros(4, np.float32)
        r = lib.MXNDArraySyncCopyToCPU(
            h, small.ctypes.data_as(ctypes.c_void_p), 4)
        assert r != 0
        assert b"8" in lib.MXGetLastError()
        r = lib.MXNDArraySyncCopyFromCPU(
            h, small.ctypes.data_as(ctypes.c_void_p), 4)
        assert r != 0
        lib.MXNDArrayFree(h)

    def test_error_contract(self, lib):
        h = ctypes.c_void_p()
        nout = ctypes.c_int()
        outs = ctypes.POINTER(ctypes.c_void_p)()
        shape = (ctypes.c_uint32 * 1)(3,)
        lib.MXNDArrayCreate(shape, 1, 1, 0, 0, ctypes.byref(h))
        r = lib.MXImperativeInvokeByName(
            b"FullyConnected", 1, ctypes.byref(h), ctypes.byref(nout),
            ctypes.byref(outs), 0, None, None)
        assert r != 0
        assert b"num_hidden" in lib.MXGetLastError() or \
            b"required" in lib.MXGetLastError()
        lib.MXNDArrayFree(h)

    def test_autograd_through_abi(self, lib):
        """mark -> record -> invoke -> backward -> grad, all over C."""
        shape = (ctypes.c_uint32 * 2)(2, 3)
        h = ctypes.c_void_p()
        lib.MXNDArrayCreate(shape, 2, 1, 0, 0, ctypes.byref(h))
        x = np.arange(6, dtype=np.float32)
        # mark BEFORE the copy: SyncCopyFromCPU must mutate the handle's
        # array in place, not rebind it, or the marking would be lost
        assert lib.MXAutogradMarkVariables(1, ctypes.byref(h)) == 0
        lib.MXNDArraySyncCopyFromCPU(
            h, x.ctypes.data_as(ctypes.c_void_p), 6)
        prev = ctypes.c_int()
        assert lib.MXAutogradSetIsRecording(1, ctypes.byref(prev)) == 0
        nout = ctypes.c_int(0)
        outs = ctypes.POINTER(ctypes.c_void_p)()
        assert lib.MXImperativeInvokeByName(
            b"square", 1, ctypes.byref(h), ctypes.byref(nout),
            ctypes.byref(outs), 0, None, None) == 0
        sq = ctypes.c_void_p(outs[0])
        nout = ctypes.c_int(0)
        outs = ctypes.POINTER(ctypes.c_void_p)()
        assert lib.MXImperativeInvokeByName(
            b"sum", 1, ctypes.byref(sq), ctypes.byref(nout),
            ctypes.byref(outs), 0, None, None) == 0
        loss = ctypes.c_void_p(outs[0])
        assert lib.MXAutogradSetIsRecording(0, ctypes.byref(prev)) == 0
        assert lib.MXAutogradBackward(1, ctypes.byref(loss), 0) == 0
        g = ctypes.c_void_p()
        assert lib.MXNDArrayGetGrad(h, ctypes.byref(g)) == 0
        got = np.zeros(6, np.float32)
        lib.MXNDArraySyncCopyToCPU(
            g, got.ctypes.data_as(ctypes.c_void_p), 6)
        np.testing.assert_allclose(got, 2 * x)   # d(sum x^2)/dx = 2x
        for hh in (g, loss, sq, h):
            lib.MXNDArrayFree(hh)

    def test_out_supplied_invoke(self, lib):
        """Non-NULL *outputs = caller-supplied out arrays (reference
        contract); the result lands in the existing handle."""
        shape = (ctypes.c_uint32 * 1)(4,)
        h = ctypes.c_void_p()
        t = ctypes.c_void_p()
        lib.MXNDArrayCreate(shape, 1, 1, 0, 0, ctypes.byref(h))
        lib.MXNDArrayCreate(shape, 1, 1, 0, 0, ctypes.byref(t))
        x = np.arange(4, dtype=np.float32)
        lib.MXNDArraySyncCopyFromCPU(
            h, x.ctypes.data_as(ctypes.c_void_p), 4)
        sup = (ctypes.c_void_p * 1)(t)
        psup = ctypes.cast(sup, ctypes.POINTER(ctypes.c_void_p))
        nout = ctypes.c_int(1)
        keys = (ctypes.c_char_p * 1)(b"scalar")
        vals = (ctypes.c_char_p * 1)(b"3.0")
        assert lib.MXImperativeInvokeByName(
            b"_mul_scalar", 1, ctypes.byref(h), ctypes.byref(nout),
            ctypes.byref(psup), 1, keys, vals) == 0
        got = np.zeros(4, np.float32)
        lib.MXNDArraySyncCopyToCPU(
            t, got.ctypes.data_as(ctypes.c_void_p), 4)
        np.testing.assert_allclose(got, 3 * x)
        lib.MXNDArrayFree(h)
        lib.MXNDArrayFree(t)

    def test_op_listing(self, lib):
        n = ctypes.c_uint32()
        arr = ctypes.POINTER(ctypes.c_char_p)()
        assert lib.MXListAllOpNames(ctypes.byref(n), ctypes.byref(arr)) == 0
        names = {arr[i].decode() for i in range(n.value)}
        assert {"Convolution", "FullyConnected", "dot"} <= names
        from mxnet_tpu.ops.registry import list_ops
        assert names == set(list_ops())
