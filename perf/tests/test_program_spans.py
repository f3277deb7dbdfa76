"""The reader of the program's own spans (``perf/reducers/program_span_ms``)
on hand-made ring records, and the seven metrics it reads in the CPU
rehearsal of every cell."""
import json
import os

import pytest

from perf import harness
from perf.reducers import program_span_ms
from perf.tests.test_rehearse import CELLS, ROOT, last_json, run

NEW = ["step_host_ms.train", "step_feed_ms.train", "step_slots_ms.train",
       "step_gather_ms.train", "step_launch_ms.train",
       "step_writeback_ms.train", "donation_copies.train"]


@pytest.fixture
def ring(monkeypatch):
    """A ring of the program's own kind, filled by hand, in the place of
    ``tracing.flight``; times in seconds from the profiler's origin."""
    from mxnet_tpu import profiler, tracing
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_SIZE", "16")
    fake = tracing.FlightRecorder()
    monkeypatch.setattr(tracing, "flight", fake)

    def record(name, begin_s, end_s, args=None):
        fake.record(name, "step", begin_s * 1e6, end_s * 1e6, args)

    return record, profiler._t0


def ctx_of(t0, dispatch_starts, traced_steps):
    spans = harness.Spans()
    for s in dispatch_starts:
        spans.rows.append(("next_batch", t0 + s - 0.001, t0 + s))
        spans.rows.append(("dispatch", t0 + s, t0 + s + 0.5))
    return {"spans": spans, "traced_steps": traced_steps}


def test_sums_over_the_traced_stretch_only(ring):
    record, t0 = ring
    # three steps dispatched at 1, 2 and 3 s; the last two are the traced ones
    for s in (1, 2, 3):
        record("Step::gather", s + 0.10, s + 0.12, {"copies": s, "leaves": 8})
        record("Step::launch", s + 0.20, s + 0.25)
        record("Step::update", s + 0.05, s + 0.40)
    ctx = ctx_of(t0, (1, 2, 3), 2)
    read = program_span_ms.read
    assert read(ctx, {"spans": ["Step::launch"]}) == pytest.approx(50.0)
    assert read(ctx, {"spans": ["Step::gather", "Step::launch"]}) == \
        pytest.approx(70.0)
    assert read(ctx, {"spans": ["Step::update"]}) == pytest.approx(350.0)
    # an args key is summed over the stretch, not divided: 2 + 3
    assert read(ctx, {"spans": ["Step::gather"], "arg": "copies"}) == 5
    assert read(ctx, {"spans": ["Step::gather"], "arg": "absent"}) == 0
    # all three steps traced
    assert read(ctx_of(t0, (1, 2, 3), 3), {"spans": ["Step::launch"]}) == \
        pytest.approx(50.0)


def test_nothing_to_read(ring):
    record, t0 = ring
    record("Step::launch", 1.2, 1.25)
    ctx = ctx_of(t0, (1,), 1)
    assert program_span_ms.read(ctx, {"spans": ["Step::absent"]}) is None
    assert program_span_ms.read(ctx_of(t0, (1,), 2),
                                {"spans": ["Step::launch"]}) is None
    assert program_span_ms.read(ctx_of(t0, (1,), 0),
                                {"spans": ["Step::launch"]}) is None


def test_a_ring_wrapped_past_the_stretch_is_refused(ring):
    record, t0 = ring
    for i in range(20):                      # 16 places: four records lost
        record("Step::launch", 1 + 0.1 * i, 1.05 + 0.1 * i)
    # the oldest kept ended at 1.45 s: a stretch from 1.5 s on is whole
    assert program_span_ms.read(ctx_of(t0, (1.5,), 1),
                                {"spans": ["Step::launch"]}) == \
        pytest.approx(15 * 50.0)
    assert program_span_ms.read(ctx_of(t0, (1.0,), 1),
                                {"spans": ["Step::launch"]}) is None


def test_a_program_without_the_reader(monkeypatch):
    from mxnet_tpu import tracing
    monkeypatch.setattr(tracing, "flight", object())
    assert program_span_ms.read(ctx_of(0.0, (1,), 1),
                                {"spans": ["Step::launch"]}) is None


def test_the_metric_files_name_spans_of_the_program():
    import re
    with open(os.path.join(ROOT, "mxnet_tpu", "fused_step.py")) as f:
        source = f.read()
    with open(os.path.join(ROOT, "mxnet_tpu", "module", "module.py")) as f:
        source += f.read()
    have = set(re.findall(r'"(Step::\w+)"', source))
    for name in NEW:
        with open(os.path.join(ROOT, "perf", "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reducer"] == "program_span_ms"
        assert set(spec["params"]["spans"]) <= have, name


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_reads_the_seven(cell):
    p = run(["perf/run.py", "--workload", cell, "--seed", "4000000011",
             "--seconds", "1", "--trace", "1", "--rehearse"])
    assert p.returncode == 0, p.stderr[-3000:]
    line = last_json(p.stdout)
    assert line["correct"] is True, line["check"]
    read = line["info"]["rehearsal"].split("read: ")[1].split()
    assert set(NEW) <= set(read), read
