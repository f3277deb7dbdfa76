"""The ``mellum2_12b_a2_5b`` configuration's own hand-run tests: the file
against the catalog's widths, its shapes->operations functions against
counts made by hand, the CPU rehearsal of its cell, and every fault of its
mechanisms and the control planted in the reference at the rehearsal's size,
each read over the rehearsal's limits."""
import json
import os
import subprocess
import sys

import pytest

from perf import harness
from perf.refs import common, mellum2_12b_a2_5b as ref, train

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "mellum2moe_train_4k"
WL = {"batch": 1, "seq_len": 4096}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perf", "configs",
                           "mellum2_12b_a2_5b.json")) as f:
        return json.load(f)


def test_widths_are_the_published_ones(cfg):
    published = {"attention_bias": False, "head_dim": 128,
                 "hidden_act": "silu", "hidden_size": 2304,
                 "intermediate_size": 7168, "max_position_embeddings": 131072,
                 "max_window_layers": 0, "model_type": "mellum",
                 "moe_intermediate_size": 896, "norm_topk_prob": True,
                 "num_attention_heads": 32, "num_experts": 64,
                 "num_experts_per_tok": 8, "num_key_value_heads": 4,
                 "rms_norm_eps": 1e-6, "sliding_window": 1024,
                 "tie_word_embeddings": False, "use_sliding_window": True}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 4
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types", "num_experts_held",
                              "vocab_size"]
    assert set(cfg["reduced_note"]) == set(cfg["reduced"])
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 98304
    assert cfg["num_experts_held"] * 8 == cfg["num_experts"]
    assert all(k in cfg for k in ("assumed", "departures", "deployment",
                                  "published"))


def test_parameters_by_hand(cfg):
    d = 2304
    attn = 2 * d * 4096 + 2 * d * 512                    # 21.23 M
    experts = 8 * 3 * d * 896                            # 49.55 M
    want = 4 * (attn + 64 * d + experts + 2 * d) + 2 * 12288 * d + d
    got = 0
    for _, shape, _, _ in ref.param_spec(cfg):
        n = 1
        for s in shape:
            n *= s
        got += n
    assert got == want
    assert 340.2e6 < got < 340.5e6                       # the 340.3 M


def test_operations_by_hand(cfg):
    d, t = 2304, 4096
    inside, causal = 1024 * 1025 // 2 + 3072 * 1024, t * (t + 1) // 2
    assert (inside, causal) == (3670528, 8390656)
    assert ref.seen_pairs(t, 1024) == inside and ref.seen_pairs(t) == causal
    rows = t * 8 * 8 / 64                                # 4096 expected
    assert ref.expert_rows(cfg, WL) == rows
    per_token = 12288 * d + 4 * (2 * d * 4096 + 2 * d * 512 + 64 * d)
    scores = 2 * 2 * (3 * inside + causal) * 32 * 128
    experts = 4 * 2 * rows * 3 * d * 896
    fwd = 2 * per_token * t + scores + experts
    assert ref.fwd_flops(cfg, WL) == fwd
    assert ref.step_flops(cfg, WL) == 3 * fwd
    assert 4.3e12 < 3 * fwd < 4.4e12                     # ISSUE 31: 4.36 TFLOP
    # scores 0.95 TFLOP (0.41 in the full layer, 0.18 a sliding layer; 1.65
    # if the sliding layers' blocks were only masked), experts 0.61
    assert 0.40e12 < 3 * 4 * causal * 4096 < 0.42e12
    assert 0.17e12 < 3 * 4 * inside * 4096 < 0.19e12
    assert 0.60e12 < 3 * experts < 0.62e12
    # the sliding layers' kernels: 6 products of 2 ops over the pairs inside
    assert ref.window_attention_flops(cfg, WL) == 3 * 6 * 2 * inside * 4096
    assert ref.window_attention_bytes(cfg, WL) == \
        3 * (4 * 32 + 4 * 4) * t * 128 * 2
    # compute-bound: 0.54 TFLOP against 0.45 GB a step
    assert ref.window_attention_flops(cfg, WL) / 197e12 > \
        4 * ref.window_attention_bytes(cfg, WL) / 819e9


def test_the_rehearsal_of_the_cell_is_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         CELL, "--seed", "2147483999", "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=600,
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    read = line["info"]["rehearsal"]
    for name in ("window_blocks_skipped_pct.train", "step_mfu_pct.train",
                 "fused_path_pct.train", "donation_copies.train"):
        assert name in read or name.endswith("skipped_pct.train"), name


@pytest.fixture(scope="module")
def toy():
    cell = harness.load_cell(ROOT, CELL, rehearse=True)
    return cell.config, cell.workload


def test_faults_and_control_in_the_reference(toy):
    """At the rehearsal's size, on three seeds: every fault of a mechanism
    reads over the rehearsal's limit on the first gradient's norm (the number
    the chip's limits hold them by too), and the fp8 control reads at least
    three times what a bfloat16 witness (the precision the configuration
    states) reads, by the median leaf's gradient (one routing flip at a tie
    can move a single expert's leaf of a model this small as far)."""
    cfg, wl = toy
    limit = wl["limits"]["grad_norm_gap"]
    for seed in (31, 32, 4000000033):
        sound = train.run(ref, cfg, wl, seed)
        witness = train.compare(
            train.run(ref, cfg, wl, seed, precision="bfloat16"), sound)
        control = train.compare(
            train.run(ref, cfg, wl, seed, precision=common.CONTROL), sound)
        assert witness["grad_norm_gap"][0] < limit, (seed, witness)
        assert control["grad_norm_median_gap"][0] >= \
            3 * witness["grad_norm_median_gap"][0], (seed, control)
        for fault in ref.FAULTS + ("half_batch",):
            kw = {"fault": fault} if fault == "half_batch" else {}
            faulted = cfg if kw else {**cfg, "fault": fault}
            got = train.compare(train.run(ref, faulted, wl, seed, **kw),
                                sound)
            assert got["grad_norm_gap"][0] > limit, (seed, fault, got)


def test_unknown_rope_type_is_refused(toy):
    cfg, _ = toy
    with pytest.raises(ValueError):
        ref.frequencies(16, {"rope_type": "longrope", "rope_theta": 1e4})
