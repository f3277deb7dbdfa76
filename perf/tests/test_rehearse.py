"""The CPU rehearsal of every cell's loop (control flow only, tiny size, no
device metric), and the measuring path's refusals: no TPU, no program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def run(args, cwd=ROOT, env=None):
    full = dict(os.environ, **(env or {}))
    full.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable] + args, cwd=cwd, env=full, text=True,
        capture_output=True, timeout=900)


def last_json(stdout):
    lines = [l for l in stdout.splitlines() if l.strip()]
    assert lines, "no result line"
    return json.loads(lines[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal(cell, trace):
    p = run(["perf/run.py", "--workload", cell, "--seed", "4000000007",
             "--seconds", "1", "--trace", str(trace), "--rehearse"])
    assert p.returncode == 0, p.stderr[-3000:]
    line = last_json(p.stdout)
    assert list(line)[-1] == "check"
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] == line["info"]["steps"] > 0
    assert line["metrics"] == {}                 # no device metric from a CPU
    assert line["device"]["platform"] == "cpu"
    limits = {k for k, v in line["check"].items() if "limit" in v}
    assert any(k.startswith("grad_norm") for k in limits)
    assert any(k.startswith("change_norm") for k in limits)
    # the numbers compared are the last lines of standard error too
    tail = [l for l in p.stderr.splitlines() if l.startswith(("check ", "correct "))]
    assert tail[-1] == "correct True" and len(tail) >= 2
    if trace:
        assert "dispatch_ms.train" in line["info"]["rehearsal"]


def test_measuring_path_needs_a_tpu():
    p = run(["perf/run.py", "--workload", CELLS[0], "--seed", "1",
             "--seconds", "1", "--trace", "0"], env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_nothing_runs_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perf"), tmp_path / "perf",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace_*"))
    p = run(["perf/run.py", "--workload", CELLS[0], "--seed", "1",
             "--seconds", "1", "--trace", "0"], cwd=str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
