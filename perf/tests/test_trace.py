"""The trace reducer against the small hand-made trace beside this file
(``make_small_trace.py`` says what is in it).  Sums worked out by hand:

busy      = [0,6) + [8,9) + [10,11) us = 8 us (the nested 0.5 us counts once)
window    = 11 us of program  ->  idle share 3/11
Convolution: = [0,6) = 6 us;  Optimizer:: = 1 us;  GradSync: nothing to read
idle gaps = [6,8) us under perf:dispatch ([6,8.5)), [9,10) us under perf:fetch
"""
import os

import pytest

from perf import trace
from perf.reducers import device_idle, roofline, scope_ms
from perf.tests import make_small_trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(os.path.join(HERE, "small_trace"), 1)


def test_committed_trace_is_the_described_one():
    with open(os.path.join(HERE, "small_trace", "small_trace.xplane.pb"), "rb") as f:
        assert f.read() == make_small_trace.build()


def test_busy_is_the_union(reduced):
    assert reduced.busy_ps == {0: 8_000_000}
    assert reduced.busy_s == pytest.approx(8e-6)


def test_scope_sums(reduced):
    assert reduced.scope_ps("Convolution:") == 6_000_000
    assert reduced.scope_ps("Optimizer::") == 1_000_000
    assert not reduced.matched("GradSync")


def test_readers(reduced):
    ctx = {"trace": reduced, "traced_steps": 2, "traced_window_s": 11e-6}
    assert device_idle.read(ctx, {}) == pytest.approx(100 * 3 / 11)
    assert scope_ms.read(ctx, {"pattern": "Convolution:"}) == pytest.approx(3e-3)
    assert scope_ms.read(ctx, {"pattern": "GradSync"}) is None


def test_roofline_reader_is_silent_without_its_kernel(reduced):
    ctx = {"trace": reduced, "traced_steps": 2, "traced_window_s": 11e-6}
    assert roofline.read(ctx, {"kernel": "flash_attention", "ops": "x",
                               "bytes": "y"}) is None


def test_roofline_reader_reads_a_kernel(reduced):
    class Builder:
        @staticmethod
        def ops(cfg, wl):
            return 197e12 * 1e-6            # one microsecond at the peak

        @staticmethod
        def byts(cfg, wl):
            return 1.0

    ctx = {"trace": reduced, "traced_steps": 2, "traced_window_s": 11e-6,
           "builder": Builder, "config": {}, "workload": {},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    # the Optimizer events: 1 us over 2 steps = 0.5 us a step; least time 1 us
    got = roofline.read(ctx, {"kernel": "Optimizer::", "ops": "ops",
                              "bytes": "byts"})
    assert got == pytest.approx(200.0)


def test_breakdown(reduced):
    b = trace.breakdown(reduced)
    assert b["device_ops"][0] == ["Convolution fwd", pytest.approx(4e-6)]
    assert ["Convolution bwd", pytest.approx(2.5e-6)] in b["device_ops"]
    assert ["Optimizer::SGD", pytest.approx(1e-6)] in b["device_ops"]
    assert ["[copy-done]", pytest.approx(1e-6)] in b["device_ops"]
    assert b["idle_gaps"] == [["dispatch", pytest.approx(2e-6)],
                              ["fetch", pytest.approx(1e-6)]]


def test_spans_are_read_from_the_host_plane(reduced):
    assert [s[0] for s in reduced.spans] == ["perf:dispatch", "perf:fetch"]


def test_subtract():
    # [0,10) minus [2,3) and [5,20)  ->  [0,2) + [3,5) = 4
    assert trace.subtract([(0, 10)], [(2, 3), (5, 20)]) == 4
    assert trace.subtract([(0, 4), (6, 8)], []) == 6
    assert trace.subtract([(0, 4)], [(0, 4)]) == 0


def test_collective_reader_is_silent_on_one_chip(reduced):
    from perf.reducers import collective_exposed_ms
    ctx = {"trace": reduced, "traced_steps": 2}
    assert collective_exposed_ms.read(ctx, {"collective": "all-reduce"}) is None
    # the copy-done stands in for a collective: 1 us alone on the core
    assert collective_exposed_ms.read(ctx, {"collective": "copy-done"}) == \
        pytest.approx(0.5e-3)
