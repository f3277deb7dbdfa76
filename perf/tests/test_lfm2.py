"""The ``lfm2_24b_a2b`` configuration's own hand-run tests: its
shapes->operations functions against counts made by hand, the file against
the catalog's widths, and the two faults of the expert layer and the control
planted in the reference at the rehearsal's size."""
import json
import os

import pytest

from perf import harness
from perf.refs import common, lfm2_24b_a2b as ref, train

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "lfm2moe_train_2k"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perf", "configs", "lfm2_24b_a2b.json")) as f:
        return json.load(f)


WL = {"batch": 1, "seq_len": 2048}


def test_widths_are_the_published_ones(cfg):
    published = {"conv_L_cache": 3, "hidden_size": 2048,
                 "intermediate_size": 11776, "moe_intermediate_size": 1536,
                 "norm_eps": 1e-5, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "num_experts": 64,
                 "num_experts_per_tok": 4, "max_position_embeddings": 128000,
                 "routed_scaling_factor": 1}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    assert cfg["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                  "conv"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 5
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types",
                                   "num_dense_layers", "num_experts_held",
                                   "vocab_size"}
    assert all(k in cfg for k in ("assumed", "departures", "deployment",
                                  "published"))


def test_parameters_by_hand(cfg):
    d, f, fe = 2048, 11776, 1536
    conv = 3 * d * d + d * d + 3 * d                     # 16.8 M
    attn = 2 * d * d + 2 * 512 * d + 2 * 64              # 10.5 M
    experts = 8 * 3 * d * fe + 64 * d + 64               # 75.6 M
    want = (8192 * d) + (conv + 3 * d * f) + (attn + experts) \
        + 3 * (conv + experts) + 11 * d
    got = 0
    for _, shape, _, _ in ref.param_spec(cfg):
        n = 1
        for s in shape:
            n *= s
        got += n
    assert got == want
    assert 468e6 < got < 470e6                           # the 469 M


def test_operations_by_hand(cfg):
    d, f, fe, t = 2048, 11776, 1536, 2048
    rows = t * 4 * 8 / 64                                # 1024 expected
    assert ref.expert_rows(cfg, WL) == rows
    conv = 4 * d * d + 3 * d                             # multiply-adds a token
    attn = 2 * d * d + 2 * d * 512
    per_token = 8192 * d + (conv + 3 * d * f) + (attn + 64 * d) \
        + 3 * (conv + 64 * d)
    scores = 2 * t * t * d                               # 2 products x 2 x half
    experts = 4 * 2 * rows * 3 * d * fe
    fwd = 2 * per_token * t + scores + experts
    assert ref.fwd_flops(cfg, WL) == fwd
    assert ref.step_flops(cfg, WL) == 3 * fwd
    assert 2.2e12 < 3 * fwd < 2.4e12                     # ISSUE 27: 2.3 TFLOP
    assert 1.1e9 < 3 * fwd / t < 1.2e9                   # 1.1 GFLOP a token
    # the dense layer's share of the operations: 47 %, its feed-forward 38 %
    dense = 2 * (conv + 3 * d * f) * t
    assert 0.46 < dense / fwd < 0.48
    assert 0.37 < 2 * 3 * d * f * t / fwd < 0.39
    assert ref.moe_expert_flops(cfg, WL) == 3 * experts
    # 8 x 9.44 M weights, bf16, three times; the rows' five widths, thrice
    weights = 8 * 3 * d * fe * 2
    assert ref.moe_expert_bytes(cfg, WL) == 4 * (
        3 * weights + 3 * rows * (2 * d + 3 * fe) * 2)


@pytest.fixture(scope="module")
def toy():
    cell = harness.load_cell(ROOT, CELL, rehearse=True)
    return cell.config, cell.workload


def test_faults_and_control_in_the_reference(toy):
    """At the rehearsal's size, on three seeds: the fp8 control reads at
    least three times what a bfloat16 witness (the precision the
    configuration states) reads, by the median leaf's gradient (one routing
    flip at a tie can move a single expert's leaf of a model this small as
    far), and each fault of the expert layer moves the worst leaf's
    gradient by more than a tenth."""
    cfg, wl = toy
    for seed in (31, 32, 4000000033):
        sound = train.run(ref, cfg, wl, seed)
        witness = train.compare(
            train.run(ref, cfg, wl, seed, precision="bfloat16"), sound)
        control = train.compare(
            train.run(ref, cfg, wl, seed, precision=common.CONTROL), sound)
        assert control["grad_norm_median_gap"][0] >= \
            3 * witness["grad_norm_median_gap"][0], (seed, control)
        for fault in ref.FAULTS:
            got = train.compare(
                train.run(ref, {**cfg, "fault": fault}, wl, seed), sound)
            assert got["grad_norm_gap"][0] > 0.1, (seed, fault, got)


def test_unknown_precision_is_refused(toy):
    cfg, wl = toy
    with pytest.raises(KeyError):
        train.run(ref, cfg, wl, 1, precision="int4")
