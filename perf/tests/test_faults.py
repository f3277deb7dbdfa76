"""The rest of a run with the timed path broken underneath: the harness's
look for a chip skipped (the rehearsal), the program's own step made faulty,
and `correct` has to come out false.  The faults a training cell can have:

state_unchanged  the optimizer cores hand their inputs back: the compiled step
                 returns its state unchanged
half_batch       the second half of every batch is a copy of the first: the
                 step's mean is taken over half of the rows
no_exchange      (cells on four chips) every chip's rows are copies of chip
                 0's: the gradient is what one chip alone would compute
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

DRIVER = r'''
import sys
sys.path.insert(0, %(root)r)
fault = %(fault)r
from perf import harness
import perf.run

_look = harness.devices_or_none


def look_then_break(chips, platform):
    devices = _look(chips, platform)
    if fault == "state_unchanged":
        from mxnet_tpu import optimizer
        for cls in (optimizer.SGD, optimizer.Adam):
            cls.fused_update = \
                lambda self, weight, grad, state, lr, wd, rescale, t: \
                (weight, tuple(state))
    else:
        keep = {"half_batch": 2, "no_exchange": 4}[fault]
        import jax.numpy as jnp
        import mxnet_tpu as mx
        from mxnet_tpu.module import Module
        step = Module.forward_backward

        def rows(a):
            n = a.shape[0] // keep
            return mx.nd.NDArray(jnp.concatenate([a._data[:n]] * keep),
                                 a.context)

        def forward_backward(self, batch):
            return step(self, mx.io.DataBatch(
                data=[rows(a) for a in batch.data],
                label=[rows(a) for a in batch.label]))
        Module.forward_backward = forward_backward
    return devices


harness.devices_or_none = look_then_break
sys.exit(perf.run.main(sys.argv[1:]))
'''


def cases():
    for w in MANIFEST["workloads"]:
        yield w["name"], "state_unchanged"
        yield w["name"], "half_batch"
        if w["chips"] > 1:
            yield w["name"], "no_exchange"


@pytest.mark.parametrize("cell,fault", list(cases()))
def test_fault_comes_out_not_correct(cell, fault):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", DRIVER % {"root": ROOT, "fault": fault},
         "--workload", cell, "--seed", "4000000011", "--seconds", "0.5",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads([l for l in p.stdout.splitlines() if l.strip()][-1])
    assert line["correct"] is False, line["check"]
    over = [k for k, v in line["check"].items()
            if "limit" in v and v["value"] > v["limit"]]
    want = "change_norm" if fault == "state_unchanged" else "grad_norm"
    assert any(k.startswith(want) for k in over), line["check"]
    assert p.stderr.rstrip().splitlines()[-1] == "correct False"
