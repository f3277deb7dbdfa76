"""``chip_smoke.py`` rehearsed on CPU, and the cache-placement function.

The script's phase functions take the device and their sizes as arguments:
here they run at a tiny size on the CPU device, which checks paths,
arguments and control flow (what the run on the chip checks is the chip).
The script itself has no switch around its device check: run as a program
on CPU it must fail before any phase.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _tiny_net():
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential(prefix="tiny_")
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1), nn.BatchNorm(),
                nn.Activation("relu"), nn.GlobalAvgPool2D(), nn.Dense(10))
    return net


def _tiny_symbol(mx):
    out = _tiny_net()(mx.sym.var("data"))
    return mx.sym.SoftmaxOutput(out, mx.sym.var("softmax_label"),
                                name="softmax")


def _phase_lines(capsys):
    return {d["phase"]: d for d in
            (json.loads(l) for l in capsys.readouterr().out.splitlines())}


def test_phases_rehearsed_on_cpu(capsys, monkeypatch):
    import jax
    import chip_smoke
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import pallas_attention, pallas_rnn

    # Mosaic compiles for the TPU only: the rehearsal interprets the kernels
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    monkeypatch.setattr(pallas_rnn, "INTERPRET", True)
    dev = jax.devices()[0]
    watch = chip_smoke.CompileWatch()
    shape = (3, 8, 8)
    try:
        ft, x, y = chip_smoke.phase_fused_trainer(
            dev, watch, 0, net_fn=_tiny_net, batch=8, data_shape=shape,
            classes=10, steps=3, warmup=1, dtype="float32")
        # windows of 32 tiny steps: one preemption of a shared CPU core is
        # then no longer half of a window (2 steps failed 2 of 3 loaded runs)
        chip_smoke.phase_sync(ft, x, y, steps=32)
        chip_smoke.phase_module_step(
            dev, watch, 0, symbol_fn=_tiny_symbol, batch=8,
            data_shape=shape, classes=10, steps=3, warmup=1)
        # f32: XLA:CPU has no bf16 x bf16 -> f32 dot for the interpreter
        chip_smoke.phase_kernels(dev, 0, lstm_tbh=(3, 8, 16),
                                 attn_bhtd=((1, 2, 128, 8), (1, 4, 256, 16)),
                                 dtype="float32")
        chip_smoke.phase_serving(dev, watch, 0, net_fn=_tiny_net,
                                 example_shape=shape, buckets=(2, 4),
                                 request_rows=(1, 4, 3))
    finally:
        telemetry.disable()
        telemetry.reset()
    lines = _phase_lines(capsys)
    assert set(lines) == {"fused_trainer", "sync", "module_step", "kernels",
                          "serving"}
    assert all(d["passed"] and "ok" not in d for d in lines.values())
    assert lines["module_step"]["step_dispatch"] == {"fused": 3, "eager": 0}
    # off the TPU the ops lower without the Mosaic call
    assert lines["kernels"]["rnn_op"]["tpu_custom_call"] is False
    assert [m["tpu_custom_call"] for m in lines["kernels"]["mha_op"]] \
        == [False, False]
    assert lines["serving"]["post_warmup_compile_requests"] == 0


def test_multichip_phases_rehearsed_on_virtual_devices(capsys, monkeypatch):
    import jax
    import chip_smoke
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import pallas_attention

    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    four = jax.devices()[:4]
    try:
        chip_smoke.phase_multichip_module(
            four, chip_smoke.CompileWatch(), 0, symbol_fn=_tiny_symbol,
            batch=8, data_shape=(3, 8, 8), classes=10, steps=2)
        chip_smoke.phase_multichip_dp_tp(four, 0, in_dim=12, hidden=16,
                                         classes=8, batch=8, steps=2)
        chip_smoke.phase_multichip_ring(four, 0, bhtd=(1, 2, 512, 8),
                                        dtype="float32")
    finally:
        telemetry.disable()
        telemetry.reset()
    lines = _phase_lines(capsys)
    assert lines["multichip_module"]["step_dispatch_mesh_fused"] == 2
    assert lines["multichip_module"]["output_rows_per_device"] == [2] * 4
    assert lines["multichip_dp_tp"]["fc1_weight_shard_shapes"] == [[8, 12]]
    assert lines["multichip_ring"]["per_shard_size_gate"] is True


@pytest.mark.parametrize("argv", [[], ["--multichip"]])
def test_script_fails_on_cpu_before_any_phase(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")] + argv,
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, proc.stdout      # no environment line, no phase
    last = json.loads(lines[-1])
    assert last["ok"] is False and "TPU" in last["error"]
    assert '"ok": true' not in proc.stdout


# ---------------------------------------------------------------------------
# cache placement: one function decides (program_cache.resolve_dir)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("jax_dir,own_dir,want,warns", [
    # jax's variable set: used exactly as given, nothing appended
    ("/some/dir", None, "/some/dir", False),
    # neither set: the one fixed path inside the checkout
    (None, None, os.path.join(REPO, ".jax_cache"), False),
    # the repo's own variable alone still places the cache
    (None, "/own/dir", "/own/dir", False),
    # both set: jax's wins and a line says so
    ("/some/dir", "/own/dir", "/some/dir", True),
    # both set to the same place: nothing to say
    ("/same", "/same", "/same", False),
])
def test_cache_placement(monkeypatch, capsys, jax_dir, own_dir, want, warns):
    from mxnet_tpu import program_cache
    for key, val in ((program_cache.JAX_ENV_DIR, jax_dir),
                     (program_cache.ENV_DIR, own_dir)):
        if val is None:
            monkeypatch.delenv(key, raising=False)
        else:
            monkeypatch.setenv(key, val)
    assert program_cache.resolve_dir() == want
    err = capsys.readouterr().err
    assert ("wins" in err) == warns


def test_place_leaves_jax_dir_untouched(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, ``place()`` keeps entries
    directly there and jax's own setting is what it was."""
    import jax
    from mxnet_tpu import program_cache
    program_cache.disable()
    monkeypatch.setenv(program_cache.JAX_ENV_DIR, str(tmp_path))
    monkeypatch.setenv(program_cache.ENV_DIR, str(tmp_path / "loses"))
    # what jax does itself on reading the variable at start-up
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        assert program_cache.place() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        jax.clear_caches()
        jax.jit(lambda v: v * 3.0 + 2.0)(jax.numpy.ones((5,))) \
            .block_until_ready()
        assert any(f.endswith(".mxpc") for f in os.listdir(tmp_path))
        assert not (tmp_path / "loses").exists()
    finally:
        program_cache.disable()
        jax.config.update("jax_compilation_cache_dir", None)
