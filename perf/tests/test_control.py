"""The control at a size a test run can hold: the reference computed in fp8
(the nearest precision below the bfloat16 the configurations state) and put
in the program's place has to come out as not correct, and so has the
reference with half of the batch left out.  The chip's limits belong to the
chip's sizes, so this test holds the control to what the program itself
reads at the rehearsal's size on the same seeds: on at least one of the
numbers compared every control reading is three times the program's largest
or more (the rule the limits are set by).  The chip readings are taken with
the same script, ``perf/tests/readings.py`` (PERF.md section 4 has them).
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
SEEDS = "21,22,4000000023"


@pytest.mark.parametrize("cell", CELLS)
def test_control_and_fault_separate_from_the_program(cell):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "perf/tests/readings.py", "--workload", cell,
         "--seeds", SEEDS, "--control-seeds", SEEDS, "--rehearse"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=1500)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads([l for l in p.stdout.splitlines() if l.strip()][-1])
    for kind in ("control_fp8", "half_batch"):
        apart = [k for k in out["program"]
                 if out[kind][k]["min"] >= 3 * out["program"][k]["max"]]
        assert apart, (kind, out)
