#!/usr/bin/env python
"""Probe: run ``bench.py --multichip`` and validate the emitted JSON.

``--smoke`` runs ``bench.py --multichip --smoke`` (tiny MLP) on eight
virtual CPU devices so the probe finishes in ~1 min on a dev box; without
it the real resnet50 workload runs over every visible TPU device and fails
with fewer than two.  This process never touches jax: the one child owns
the devices.
Asserts the record carries the multichip contract keys — the driver and
docs/perf_analysis.md both key on ``img_per_sec`` and
``scaling_efficiency`` — and that the mesh-fused path actually dispatched.

Usage:
    python tools/probe_multichip.py --smoke
    python tools/probe_multichip.py            # full resnet50 bench
"""
import json
import os
import subprocess
import sys
import tempfile

REQUIRED_KEYS = ("metric", "img_per_sec", "scaling_efficiency",
                 "n_devices", "mesh_fused_steps", "ok")


def main(argv):
    smoke = "--smoke" in argv
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tempfile.NamedTemporaryFile(
        suffix=".json", prefix="multichip_", delete=False)
    out.close()
    env = dict(os.environ)
    env["MULTICHIP_OUT"] = out.name
    cmd = [sys.executable, os.path.join(repo, "bench.py"), "--multichip"]
    if smoke:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        cmd.append("--smoke")
    proc = subprocess.run(
        cmd, env=env, cwd=repo, capture_output=True, text=True,
        timeout=600 if smoke else 3000)
    if proc.returncode != 0:
        print("bench --multichip failed (rc=%d)\n--- stdout ---\n%s\n"
              "--- stderr ---\n%s" % (proc.returncode,
                                      proc.stdout[-4000:],
                                      proc.stderr[-4000:]))
        return proc.returncode
    with open(out.name) as f:
        rec = json.load(f)
    os.unlink(out.name)

    missing = [k for k in REQUIRED_KEYS if k not in rec]
    assert not missing, "multichip record missing keys %s: %r" \
        % (missing, rec)
    assert rec["img_per_sec"] > 0, rec
    assert 0 < rec["scaling_efficiency"], rec
    assert rec["mesh_fused_steps"] > 0, \
        "mesh-fused path never dispatched: %r" % rec
    assert rec["ok"] is True, rec
    print(json.dumps({"probe": "multichip", "smoke": smoke, "ok": True,
                      "metric": rec["metric"],
                      "img_per_sec": rec["img_per_sec"],
                      "scaling_efficiency": rec["scaling_efficiency"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
