"""Run one example script in a fresh interpreter and read its metric.

Shared by ``test_example_scripts_*.py``.  The example families of round 5
(VERDICT r4 item 5) run in a SUBPROCESS: twelve more in-process
convergence runs pushed the single pytest process's accumulated XLA
compile state into a segfault at the tail of the full suite.  Each script
prints its metric and exits by its own threshold; the tests parse the
printed metric and apply their own (sometimes looser, budget-matched) bar.
"""
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_example(relpath, args, pattern, extra_env=None, timeout=1500):
    env = dict(os.environ)
    # PYTHONPATH = repo ONLY and JAX_PLATFORMS=cpu: the example runs on
    # CPU whatever the inherited environment names
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    r = subprocess.run([sys.executable, os.path.join(REPO, relpath)]
                       + list(args), env=env, capture_output=True,
                       text=True, timeout=timeout)
    m = re.search(pattern, r.stdout)
    assert m, ("example produced no metric (rc=%d)\n%s\n%s"
               % (r.returncode, r.stdout[-800:], r.stderr[-800:]))
    return [float(g) for g in m.groups()]
