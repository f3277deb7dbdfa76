"""Smoke tests for the example/ families added in round 4 (verdict item:
examples are a layer of the framework — reference example/rnn/bucketing
and example/module).

Each test imports the example script and runs its main() at toy scale;
convergence thresholds prove the demos actually train, not just execute.
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(relpath, name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lstm_bucketing_example_learns():
    lb = _load("example/rnn/bucketing/lstm_bucketing.py", "lstm_bucketing")
    args = lb.parser.parse_args(
        ["--num-epochs", "8", "--sentences", "600", "--batch-size", "16",
         "--buckets", "8,15", "--num-hidden", "32", "--num-embed", "16",
         "--vocab", "16"])
    ppl = lb.main(args)
    # 90%-deterministic Markov rule: uniform ppl is 16, learned < 6
    assert ppl < 6.0, "bucketed LSTM LM failed to learn: ppl %.2f" % ppl


def test_module_example_trains(tmp_path):
    sm = _load("example/module/sequential_module.py", "sequential_module")
    args = sm.parser.parse_args(
        ["--num-epochs", "8", "--samples", "512",
         "--checkpoint-prefix", str(tmp_path / "mod_demo")])
    acc1, acc2 = sm.main(args)
    assert acc1 > 0.9, acc1
    assert acc2 > 0.8, acc2
    # the checkpoint files exist (epoch 8 symbol+params)
    assert (tmp_path / "mod_demo-symbol.json").exists() or \
        (tmp_path / "mod_demo-0008.params").exists()


def test_quantization_example():
    qz = _load("example/quantization/quantize_resnet.py",
               "quantize_resnet")
    args = qz.parser.parse_args(["--batch-size", "4", "--image-size", "32"])
    agree, corr, n_int8 = qz.main(args)
    assert corr > 0.99, corr
    assert n_int8 >= 20, n_int8      # resnet18: 20 convs quantized


def test_onnx_example(tmp_path):
    ox = _load("example/onnx/onnx_roundtrip.py", "onnx_roundtrip")
    args = ox.parser.parse_args(["--steps", "10",
                                 "--out", str(tmp_path / "m.onnx")])
    err = ox.main(args)
    assert err < 1e-4


def test_dcgan_example_trains():
    gd = _load("example/gluon/dcgan.py", "dcgan")
    args = gd.parser.parse_args(["--num-epochs", "2", "--samples", "128",
                                 "--batch-size", "16"])
    dl, gl, dacc = gd.main(args)
    # adversarial training ran: finite losses, D neither collapsed to
    # random (0.5-ish is fine early) nor to perfect rejection of G
    assert np.isfinite([dl, gl]).all()
    assert 0.2 < dacc <= 1.0, dacc


def test_ctc_example_learns():
    oc = _load("example/ctc/lstm_ocr.py", "lstm_ocr")
    args = oc.parser.parse_args(["--num-epochs", "25", "--samples", "256",
                                 "--batch-size", "32"])
    loss, acc = oc.main(args)
    # CTC cracked the alignment: loss far below the ~10.7 uniform level
    assert loss < 1.5, loss
    assert acc > 0.7, acc


def test_matrix_factorization_example():
    mf = _load("example/recommenders/matrix_fact.py", "matrix_fact")
    args = mf.parser.parse_args(["--num-epochs", "8",
                                 "--ratings", "4000"])
    rmse = mf.main(args)
    # true noise floor is 0.05; random embeddings start near ~0.5
    assert rmse < 0.12, rmse


def test_fgsm_adversary_example():
    fg = _load("example/adversary/fgsm.py", "fgsm")
    args = fg.parser.parse_args(["--num-epochs", "10", "--samples", "512",
                                 "--epsilon", "0.5"])
    clean_acc, adv_acc = fg.main(args)
    assert clean_acc > 0.9, clean_acc
    # the attack must actually hurt (input gradients flowed)
    assert adv_acc < clean_acc - 0.15, (clean_acc, adv_acc)


def test_autoencoder_example_compresses():
    ae = _load("example/autoencoder/autoencoder.py", "autoencoder")
    args = ae.parser.parse_args(["--num-epochs", "15", "--samples", "512"])
    first, last = ae.main(args)
    # rank-4 data through an 8-wide bottleneck: big reconstruction win
    assert last < first * 0.2, (first, last)


def test_bi_lstm_sort_example():
    bs = _load("example/bi-lstm-sort/bi_lstm_sort.py", "bi_lstm_sort")
    args = bs.parser.parse_args(["--num-epochs", "10", "--samples", "1500",
                                 "--seq-len", "5", "--vocab", "8"])
    acc = bs.main(args)
    # chance is 1/8 + sorted-structure prior; learned sorting is far above
    assert acc > 0.75, acc


def test_numpy_ops_custom_softmax_example():
    cs = _load("example/numpy-ops/custom_softmax.py", "custom_softmax")
    args = cs.parser.parse_args(["--num-epochs", "8", "--samples", "512"])
    acc = cs.main(args)
    assert acc > 0.85, acc


def test_multitask_example():
    mt = _load("example/multi-task/multitask.py", "multitask")
    args = mt.parser.parse_args(["--num-epochs", "10", "--samples", "768"])
    acc_cls, acc_par = mt.main(args)
    assert acc_cls > 0.85, acc_cls
    assert acc_par > 0.85, acc_par


def test_vae_example_improves_elbo():
    va = _load("example/vae/vae.py", "vae")
    args = va.parser.parse_args(["--num-epochs", "15", "--samples", "512"])
    init_elbo, last = va.main(args)
    # beats the untrained -ELBO decisively (measured ~0.72x at this scale)
    assert last < init_elbo * 0.8, (init_elbo, last)


def test_nce_example_learns_blocks():
    nc = _load("example/nce-loss/nce.py", "nce")
    args = nc.parser.parse_args(["--num-epochs", "8", "--pairs", "2048"])
    first, last, margin = nc.main(args)
    assert last < first * 0.8, (first, last)
    # same-block words measurably closer than cross-block words
    assert margin > 0.1, margin


def test_profiler_example_dumps_trace(tmp_path):
    pf = _load("example/profiler/profiler_demo.py", "profiler_demo")
    out = str(tmp_path / "trace.json")
    path, n_events, op_names = pf.main(
        pf.parser.parse_args(["--out", out, "--steps", "4"]))
    assert n_events > 10
    assert any("FullyConnected" in (n or "") for n in op_names)
    assert any("train_steps" in (n or "") for n in op_names)


def test_svm_example_trains():
    sv = _load("example/svm_mnist/svm_demo.py", "svm_demo")
    acc_l1 = sv.main(sv.parser.parse_args(
        ["--num-epochs", "8", "--samples", "512"]))
    assert acc_l1 > 0.85, acc_l1
    acc_l2 = sv.main(sv.parser.parse_args(
        ["--num-epochs", "8", "--samples", "512", "--l2"]))
    assert acc_l2 > 0.85, acc_l2


def test_reinforce_example_learns():
    rl = _load("example/reinforcement-learning/reinforce.py", "reinforce")
    early, late = rl.main(rl.parser.parse_args(["--episodes", "300"]))
    # shaped gridworld: learned policy reaches the goal (return > 1 means
    # the +1 goal reward was collected); early policy averages below it
    assert late > 1.0, (early, late)
    assert late > early + 0.1, (early, late)


def test_module_init_params_default_breaks_symmetry():
    """Parity: bare init_params() uses Uniform(0.01) (reference
    base_module.py:629), not zeros — relu nets must break symmetry."""
    import mxnet_tpu as mx
    S = mx.symbol
    net = S.FullyConnected(S.var("data"), num_hidden=4, name="fc1")
    mod = mx.mod.Module(net, data_names=["data"], label_names=[])
    mod.bind(data_shapes=[("data", (2, 8))])
    mod.init_params()
    w = mod.get_params()[0]["fc1_weight"].asnumpy()
    assert abs(w).max() > 0, "bare init_params left weights at zero"
    assert abs(w).max() <= 0.01 + 1e-6   # Uniform(0.01) scale


def test_text_cnn_example():
    tc = _load("example/cnn_text_classification/text_cnn.py", "text_cnn")
    acc = tc.main(tc.parser.parse_args(
        ["--num-epochs", "8", "--samples", "768"]))
    # width-3 filters must find the planted trigram motifs
    assert acc > 0.9, acc


def test_neural_style_example():
    ns = _load("example/neural-style/neural_style.py", "neural_style")
    first, last, img = ns.main(ns.parser.parse_args(
        ["--steps", "120", "--size", "24"]))
    # input optimization converges and produces a finite image
    # (measured ~0.48x at 120 steps; 0.6 leaves seed headroom)
    assert last < first * 0.6, (first, last)
    assert np.isfinite(img).all()


# ---- round-5 families (VERDICT r4 item 5) --------------------------------
#
# These run their example script in a SUBPROCESS (fresh interpreter each):
# twelve more in-process convergence runs pushed the single pytest
# process's accumulated XLA compile state into a segfault at the tail of
# the full suite.  Each script prints its metric and exits by its own
# threshold; the tests parse the printed metric and apply their own
# (sometimes looser, budget-matched) bar.


def _run_example(relpath, args, pattern, extra_env=None, timeout=1500):
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


def test_fcn_xs_example_segments():
    """FCN-16s-style dense prediction: deconv upsampling + crop-aligned
    skip fusion recovers pixel-accurate masks."""
    (acc,) = _run_example("example/fcn-xs/fcn_xs.py",
                          ["--num-epochs", "6", "--samples", "128"],
                          r"FCN pixel accuracy: ([0-9.]+)")
    assert acc > 0.8, acc


def test_module_gan_example():
    """Module-API GAN: G trains purely from D's input gradients
    (get_input_grads -> backward); best-trailing-eval selection."""
    (err,) = _run_example("example/gan/gan_mnist.py", ["--iters", "250"],
                          r"radius - 1\| of generated points: ([0-9.]+)")
    assert err < 0.4, err


def test_capsnet_example_routes():
    """Dynamic routing-by-agreement trains (capsule lengths as class
    scores, margin loss)."""
    (acc,) = _run_example("example/capsnet/capsnet.py",
                          ["--iters", "60"],
                          r"capsnet routing accuracy: ([0-9.]+)")
    assert acc > 0.8, acc


def test_ner_example_tags():
    """BiLSTM sequence labeling: the trigger->next-token rule needs
    cross-timestep context, so beating the O-rate proves the recurrence
    carries it."""
    (acc,) = _run_example("example/named_entity_recognition/ner.py",
                          ["--iters", "80"],
                          r"NER entity-token accuracy: ([0-9.]+)")
    assert acc > 0.9, acc


def test_stochastic_depth_example():
    """Per-layer Bernoulli block dropping at train time, p_l-scaled full
    depth at eval (train/test asymmetry of stochastic depth)."""
    (acc,) = _run_example("example/stochastic-depth/sd_cifar10.py",
                          ["--iters", "120"],
                          r"stochastic-depth eval accuracy: ([0-9.]+)")
    assert acc > 0.85, acc


def test_multivariate_ts_example_beats_naive():
    """LSTNet-style conv+GRU forecasting: at horizon 6 the model must
    exploit the planted cross-channel lags the naive forecast can't."""
    got = _run_example("example/multivariate_time_series/lstnet.py",
                       ["--iters", "150"],
                       r"ratio ([0-9.]+)")
    assert got[0] < 0.6, got


def test_captcha_example_reads_all_slots():
    """Multi-head captcha: summed per-slot CE; whole-sequence accuracy
    requires every head right."""
    (acc,) = _run_example("example/captcha/captcha_train.py",
                          ["--iters", "200"],
                          r"captcha whole-sequence accuracy: ([0-9.]+)")
    assert acc > 0.7, acc


def test_sgld_example_samples_posterior():
    """SGLD: posterior-averaged accuracy high AND the samples actually
    spread (a collapsed chain would have ~zero std)."""
    acc, w_std = _run_example(
        "example/bayesian-methods/sgld.py",
        ["--iters", "500", "--burnin", "250"],
        r"posterior-avg accuracy ([0-9.]+), posterior w-std ([0-9.]+)")
    assert acc > 0.9, acc
    assert w_std > 1e-4, w_std


def test_rnn_time_major_example():
    """NTC and TNC layouts learn the same Markov rule to near-identical
    ppl (seeded init + same data: layout is semantics-free)."""
    p_ntc, p_tnc = _run_example(
        "example/rnn-time-major/rnn_time_major.py", ["--iters", "100"],
        r"final ppl  NTC ([0-9.]+)   TNC ([0-9.]+)")
    assert p_ntc < 6 and p_tnc < 6, (p_ntc, p_tnc)
    assert abs(p_ntc - p_tnc) / p_ntc < 0.02, (p_ntc, p_tnc)


def test_long_context_ring_lm_example():
    """Transformer LM trained end-to-end with ring attention over the
    sp mesh — the SP flagship (fwd + the round-5 ring backward) as a
    user-facing recipe, not just a parallel-layer test."""
    p0, p1 = _run_example(
        "example/long-context-lm/train_ring_lm.py",
        ["--iters", "150", "--sp", "4", "--seq-len", "128"],
        r"ppl ([0-9.]+) -> ([0-9.]+)",
        extra_env={"JAX_PLATFORMS": "cpu",
                   "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert p1 < 8.0 and p1 < 0.5 * p0, (p0, p1)


def test_cnn_visualization_example():
    """Saliency + Grad-CAM concentrate their mass on the evidence patch
    (synthetic ground truth for 'the explanation points at the
    evidence'); box covers only 6% of the image."""
    sal, cam = _run_example(
        "example/cnn_visualization/gradcam.py", ["--iters", "100"],
        r"saliency mass in box: ([0-9.]+)   grad-cam mass in box: "
        r"([0-9.]+)")
    assert sal > 0.15, sal
    assert cam > 0.3, cam


def test_speech_recognition_example():
    """BiLSTM+CTC acoustic model: learns phone identity AND alignment
    from unaligned transcripts (blank=last convention)."""
    (acc,) = _run_example(
        "example/speech_recognition/speech_lstm_ctc.py",
        ["--iters", "200", "--max-frames", "32"],
        r"utterance exact-match rate: ([0-9.]+)")
    assert acc > 0.6, acc
