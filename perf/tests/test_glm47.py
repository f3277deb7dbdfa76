"""The ``glm_4_7_flash`` configuration's own hand-run tests: the file against
the catalog's widths, its shapes->operations functions against counts made by
hand, the CPU rehearsal of its cell, and every fault of its mechanisms and
the control planted in the reference at the rehearsal's size, each read over
the rehearsal's limits."""
import json
import os
import subprocess
import sys

import pytest

from perf import harness
from perf.refs import common, glm_4_7_flash as ref, train

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "glm47flash_train_4k"
WL = {"batch": 1, "seq_len": 4096}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perf", "configs",
                           "glm_4_7_flash.json")) as f:
        return json.load(f)


def test_widths_are_the_published_ones(cfg):
    published = {"attention_bias": False, "hidden_act": "silu",
                 "hidden_size": 2048, "intermediate_size": 10240,
                 "max_position_embeddings": 202752,
                 "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
                 "topk_method": "noaux_tc", "norm_topk_prob": True,
                 "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
                 "n_routed_experts": 64, "n_shared_experts": 1,
                 "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
                 "first_k_dense_replace": 1, "num_key_value_heads": 20,
                 "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
                 "rms_norm_eps": 1e-5, "rope_scaling": None,
                 "rope_theta": 1000000, "tie_word_embeddings": False,
                 "q_lora_rank": 768, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
                 "v_head_dim": 256}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert set(cfg["reduced_note"]) == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 5 and \
        cfg["published"]["num_hidden_layers"] == 47
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 154880
    assert cfg["num_experts_held"] * 8 == cfg["n_routed_experts"]
    assert all(k in cfg for k in ("assumed", "departures", "deployment",
                                  "published"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "glm_4_7_flash"][0]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]


def test_parameters_by_hand(cfg):
    d = 2048
    attn = (768 * d + 768 + 768 * 20 * 256 + 576 * d + 512
            + 512 * 20 * 448 + d * 5120)
    assert attn == 21757952 + 768 + 512                  # ISSUE 33's count
    shared = 3 * d * 1536
    sparse = attn + 2 * d + 64 * d + 64 + 8 * shared + shared
    dense = attn + 2 * d + 3 * d * 10240
    module = 2 * d + d * 2 * d + sparse + d
    want = 2 * 19360 * d + d + dense + 4 * sparse + module
    got = 0
    for _, shape, _, _ in ref.param_spec(cfg):
        n = 1
        for s in shape:
            n *= s
        got += n
    assert got == want == 706518848
    # more than half of it is the held experts' weights
    assert 0.53 < 5 * 8 * shared / got < 0.54


def test_operations_by_hand(cfg):
    d, t = 2048, 4096
    causal = t * (t + 1) // 2
    assert causal == 8390656 == ref.causal_pairs(t)
    rows = t * 4 * 8 / 64                                # 2048 expected
    assert ref.expert_rows(cfg, WL) == rows
    attn = 768 * d + 768 * 5120 + 576 * d + 512 * 8960 + 5120 * d
    assert attn == 21757952
    sparse = 64 * d + 3 * d * 1536
    per_token = (6 * attn + 3 * d * 10240 + 5 * sparse + 2 * 19360 * d
                 + 2 * d * d)
    scores = 6 * 2 * causal * 20 * (256 + 256)
    experts = 5 * 2 * rows * 3 * d * 1536
    fwd = 2 * per_token * t + scores + experts
    assert ref.fwd_flops(cfg, WL) == fwd
    assert ref.step_flops(cfg, WL) == 3 * fwd
    assert 11.7e12 < 3 * fwd < 11.8e12
    # by part, forward + backward: the latent layers' projections 3.2 TFLOP
    # and kernels 3.1, the routed experts 0.58 (the op computes 16 times
    # that), the five shared experts 1.16, the two heads 1.95
    assert 3.20e12 < 3 * 2 * 6 * attn * t < 3.22e12
    assert 3.09e12 < 3 * scores < 3.10e12
    assert 0.57e12 < 3 * experts < 0.59e12
    assert 1.15e12 < 3 * 2 * 5 * 3 * d * 1536 * t < 1.17e12
    assert ref.mla_attention_flops(cfg, WL) == 3 * scores
    assert ref.mla_attention_bytes(cfg, WL) == 6 * 8 * 20 * t * 256 * 2
    # compute-bound: 3.09 TFLOP against 2.0 GB a step
    assert ref.mla_attention_flops(cfg, WL) / 197e12 > \
        6 * ref.mla_attention_bytes(cfg, WL) / 819e9


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
    for name in ("attention_flash_path_pct.train", "step_mfu_pct.train",
                 "fused_path_pct.train", "donation_copies.train"):
        assert name in read, name


@pytest.fixture(scope="module")
def toy():
    cell = harness.load_cell(ROOT, CELL, rehearse=True)
    return cell.config, cell.workload


def test_faults_and_control_in_the_reference(toy):
    """At the rehearsal's size, on three seeds: every fault of a mechanism
    reads over one of the rehearsal's limits, a bfloat16 witness (the
    precision the configuration states) under both, and the fp8 control at
    least three times the witness by the median leaf's gradient."""
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
            kw = {"fault": fault} if fault == "half_batch" else {}
            faulted = cfg if kw else {**cfg, "fault": fault}
            got = train.compare(train.run(ref, faulted, wl, seed, **kw),
                                sound)
            assert over(got), (seed, fault, got)
