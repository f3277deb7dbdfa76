"""Distributed kvstore tests: process-level fake cluster on one machine.

Parity model: tests/nightly/test_all.sh:55 + dist_sync_kvstore.py — fork N
worker processes with the launcher env and check exact cross-rank sums.
Also unit tests of the 2-bit gradient compressor (reference
tests/nightly/test_kvstore.py compression correctness).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.kvstore_compression import GradientCompression

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestGradientCompression:
    def test_quantize_with_error_feedback(self):
        gc = GradientCompression(threshold=0.5)
        import jax.numpy as jnp
        g = jnp.asarray(np.array([0.9, -0.9, 0.1, 0.0], np.float32))
        q1 = np.asarray(gc.compress("k", g))
        np.testing.assert_allclose(q1, [0.5, -0.5, 0.0, 0.0])
        # residual [0.4, -0.4, 0.1, 0] feeds back
        q2 = np.asarray(gc.compress("k", jnp.asarray(
            np.array([0.2, -0.2, 0.5, 0.0], np.float32))))
        np.testing.assert_allclose(q2, [0.5, -0.5, 0.5, 0.0])
        # cumulative quantized sum tracks the true sum within threshold
        total_true = np.array([1.1, -1.1, 0.6, 0.0])
        np.testing.assert_allclose(np.abs((q1 + q2) - total_true).max(),
                                   0.1, atol=1e-6)

    def test_pack_unpack_wire_format(self):
        vals = np.array([0.5, -0.5, 0.0] * 11, np.float32)  # 33 elems
        words = GradientCompression.pack(vals)
        assert words.dtype == np.uint32
        assert len(words) == 3                      # ceil(33/16)
        back = GradientCompression.unpack(words, len(vals), 0.5)
        np.testing.assert_allclose(back, vals)
        # 16x compression for fp32 payloads
        assert words.nbytes * 16 >= vals.nbytes

    def test_bad_params_rejected(self):
        with pytest.raises(mx.MXNetError):
            GradientCompression(type="1bit")
        with pytest.raises(mx.MXNetError):
            GradientCompression(threshold=0.0)
        kv = mx.kv.create("local")
        with pytest.raises(mx.MXNetError):
            kv.set_gradient_compression({"type": "2bit", "bogus": 1})

    def test_kvstore_api(self):
        kv = mx.kv.create("local")
        assert kv.gradient_compression is None
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        assert kv.gradient_compression.threshold == 0.5


@pytest.mark.skipif(os.environ.get("MXNET_SKIP_DIST_TESTS") == "1",
                    reason="dist tests disabled")
def test_dist_sync_kvstore_two_workers():
    """Fork a 2-worker local cluster through tools/launch.py machinery."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import launch
    finally:
        sys.path.pop(0)
    worker = os.path.join(REPO, "tests", "dist_sync_kvstore_worker.py")
    # PYTHONPATH = repo only: nothing on the inherited path may
    # initialize a JAX backend before jax.distributed.initialize runs
    env = {"JAX_PLATFORMS": "cpu", "MXNET_NO_NATIVE": "0",
           "PYTHONPATH": REPO}
    rc = launch.launch_local(2, [sys.executable, worker], env_extra=env)
    assert rc == 0


def test_launch_cli_single_worker(tmp_path):
    """launch.py CLI end to end with a trivial command."""
    marker = tmp_path / "ran.txt"
    script = tmp_path / "job.py"
    script.write_text(
        "import os\n"
        "with open(%r, 'a') as f:\n"
        "    f.write(os.environ['DMLC_WORKER_ID'] + '\\n')\n" % str(marker))
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", sys.executable, str(script)],
        capture_output=True, text=True).returncode
    assert rc == 0
    ids = sorted(marker.read_text().split())
    assert ids == ["0", "1"]


@pytest.mark.skipif(os.environ.get("MXNET_SKIP_DIST_TESTS") == "1",
                    reason="dist tests disabled")
def test_dist_lenet_training_two_workers():
    """dist_lenet-style e2e (ref tests/nightly/dist_lenet.py): 2 forked
    workers train with dist_sync, assert convergence + cross-rank param
    equality + row_sparse pull."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import launch
    finally:
        sys.path.pop(0)
    worker = os.path.join(REPO, "tests", "dist_lenet_worker.py")
    env = {"JAX_PLATFORMS": "cpu", "MXNET_NO_NATIVE": "0",
           "PYTHONPATH": REPO}
    rc = launch.launch_local(2, [sys.executable, worker], env_extra=env)
    assert rc == 0
