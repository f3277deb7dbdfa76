#!/usr/bin/env python
"""Probe: run ``bench.py`` with the health monitor on and validate the
exported health evidence.

``--smoke`` runs ``bench.py --smoke`` (tiny batch/image, few iters, no
LSTM / phase-breakdown satellites) pinned to CPU so the probe finishes in
a couple of minutes on a dev box; without it the full resnet50 bench runs
and needs the TPU.  This process never touches jax: the one child owns the
device.  Asserts the
acceptance contract of the health PR: the bench JSON carries a nested
``health`` object with live XLA-counted ``program_flops`` /
``program_hbm_bytes``, a ``step_mfu_pct`` gauge value, a verdict cause,
and the measured monitor-overhead A/B.

Usage:
    python tools/probe_health.py --smoke
    python tools/probe_health.py            # full resnet50 bench
"""
import json
import os
import subprocess
import sys

REQUIRED_KEYS = ("step_mfu_pct", "verdict", "step_seconds_ewma",
                 "monitor_overhead_pct", "program_flops",
                 "program_hbm_bytes", "donation_leaks")
HBM_KINDS = ("args", "output", "temp")


def main(argv):
    smoke = "--smoke" in argv
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["BENCH_HEALTH"] = "1"
    cmd = [sys.executable, os.path.join(repo, "bench.py")]
    if smoke:
        env["JAX_PLATFORMS"] = "cpu"
        cmd.append("--smoke")
    proc = subprocess.run(
        cmd, env=env, cwd=repo, capture_output=True, text=True,
        timeout=900 if smoke else 3000)
    if proc.returncode != 0:
        print("bench failed (rc=%d)\n--- stdout ---\n%s\n--- stderr ---\n%s"
              % (proc.returncode, proc.stdout[-4000:], proc.stderr[-4000:]))
        return proc.returncode
    rec = json.loads(proc.stdout.strip().splitlines()[-1])

    health = rec.get("health")
    assert isinstance(health, dict), "bench JSON carries no health block"
    missing = [k for k in REQUIRED_KEYS if k not in health]
    assert not missing, "health block missing keys %s: %r" \
        % (missing, health)
    assert health["step_mfu_pct"] is not None and health["step_mfu_pct"] > 0
    assert health["verdict"] in ("compute_bound", "input_bound",
                                 "sync_bound", "compile_bound")
    assert health["program_flops"], "no program registered its cost"
    for name, flops in health["program_flops"].items():
        assert flops > 0, "program %s reports zero flops" % name
        hbm = health["program_hbm_bytes"][name]
        assert all(k in hbm for k in HBM_KINDS), hbm
        assert hbm["args"] > 0, "program %s reports empty arguments" % name
    assert health["donation_leaks"] == [], \
        "donation chain broke: %s" % health["donation_leaks"]
    print(json.dumps({"probe": "health", "smoke": smoke, "ok": True,
                      "step_mfu_pct": health["step_mfu_pct"],
                      "verdict": health["verdict"],
                      "monitor_overhead_pct":
                          health["monitor_overhead_pct"],
                      "programs": sorted(health["program_flops"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
