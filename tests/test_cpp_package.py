"""cpp-package: the header-only C++ frontend over the C API waist.

Parity model: reference cpp-package/ (§2.4) — NDArray + Operator builder
classes riding the imperative+autograd C ABI (mlp.cc), plus the round-5
symbolic half: Symbol/Executor classes over the MXSymbol*/MXExecutor* C
sections and the generated per-op wrappers (op.h, the
OpWrapperGenerator.py pattern) trained end-to-end by lenet.cc.
"""
import os
import shutil
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXDIR = os.path.join(REPO, "cpp_package", "example")

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("python3-config") is None,
    reason="no C++ toolchain")


def _build():
    r = subprocess.run(["make", "-C", EXDIR], capture_output=True, text=True)
    if r.returncode != 0:
        pytest.skip("cpp example build failed: %s" % r.stderr[-500:])


def _run(binary):
    env = dict(os.environ)
    # PYTHONPATH = repo ONLY and JAX_PLATFORMS forced: the embedded
    # interpreter must come up on CPU whatever the inherited environment
    # names, and a test worker may hold the only accelerator
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([os.path.join(EXDIR, binary)], env=env,
                          capture_output=True, text=True, timeout=600)


def test_cpp_mlp_trains():
    _build()
    r = _run("mlp")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "MLP TRAIN OK" in r.stdout


def test_cpp_lenet_symbolic_trains():
    """LeNet through Symbol + SimpleBind + Executor + generated op.h —
    the reference cpp-package's symbolic workflow."""
    _build()
    r = _run("lenet")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "LENET SYMBOLIC TRAIN OK" in r.stdout


def test_generated_op_wrappers_current():
    """op.h is generated from the registry; regenerating must reproduce
    the checked-in header byte-for-byte (drift gate), and it must cover
    the whole registry."""
    import sys
    sys.path.insert(0, os.path.join(REPO, "cpp_package", "scripts"))
    try:
        import gen_op_wrappers
    finally:
        sys.path.pop(0)
    text, n = gen_op_wrappers.generate()
    from mxnet_tpu.ops.registry import OPS
    assert n == len(OPS)
    with open(os.path.join(REPO, "cpp_package", "include", "mxnet-cpp",
                           "op.h")) as f:
        assert f.read() == text, \
            "op.h is stale: rerun cpp_package/scripts/gen_op_wrappers.py"
