"""The ``kimi_linear_48b_a3b`` configuration's own hand-run tests: the file
against the catalog's widths, its shapes->operations functions against
counts made by hand, and every fault of its mechanisms and the control
planted in the reference at the rehearsal's size, each read over the
rehearsal's limits (``rope_mla`` excepted: the workload file's
``limits_note`` says why nothing holds it at this size).  The CPU rehearsal
of the cell is ``tests/test_kimi_linear_ops.py``'s."""
import json
import os

import pytest

from perf import harness
from perf.refs import common, kimi_linear_48b_a3b as ref, train

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "kimilinear_train_4k"


def test_the_entry_and_the_file_agree():
    with open(os.path.join(ROOT, "perf", "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "kimi_linear_48b_a3b"][0]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "linear_attn_config", "num_experts_held",
        "vocab_size"]
    assert cfg["num_hidden_layers"] == 5 and \
        cfg["published"]["num_hidden_layers"] == 27
    lin, pub = cfg["linear_attn_config"], cfg["published"]["linear_attn_config"]
    assert set(lin["kda_layers"]) <= set(pub["kda_layers"])
    assert set(lin["full_attn_layers"]) <= set(pub["full_attn_layers"])
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == \
        list(range(1, 6))


@pytest.fixture(scope="module")
def toy():
    cell = harness.load_cell(ROOT, CELL, rehearse=True)
    return cell.config, cell.workload


def test_faults_and_control_in_the_reference(toy):
    """At the rehearsal's size, on three seeds: every fault of a mechanism
    but ``rope_mla`` reads over one of the rehearsal's limits, a bfloat16
    witness (the precision the configuration states) under both, and the
    fp8 control at least three times the witness by the median leaf's
    gradient."""
    cfg, wl = toy
    limits = wl["limits"]

    def over(numbers):
        return [k for k, lim in limits.items() if numbers[k][0] > lim]

    for seed in (31, 32, 4000000033):
        sound = train.run(ref, cfg, wl, seed)
        witness = train.compare(
            train.run(ref, cfg, wl, seed, precision="bfloat16"), sound)
        control = train.compare(
            train.run(ref, cfg, wl, seed, precision=common.CONTROL), sound)
        assert not over(witness), (seed, witness)
        assert control["grad_norm_median_gap"][0] >= \
            3 * witness["grad_norm_median_gap"][0], (seed, control)
        for fault in ref.FAULTS + ("half_batch",):
            if fault == "rope_mla":
                continue
            kw = {"fault": fault} if fault == "half_batch" else {}
            faulted = cfg if kw else {**cfg, "fault": fault}
            got = train.compare(train.run(ref, faulted, wl, seed, **kw),
                                sound)
            assert over(got), (seed, fault, got)
