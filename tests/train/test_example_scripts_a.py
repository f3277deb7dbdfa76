"""Example scripts run as subprocesses, part one of three.

One file held all of these after the in-process examples of
``test_examples.py`` and was, alone on one worker under ``--dist
loadfile``, longer than the rest of tier-1 together.  They are split by
running time (about 450 s, 450 s and 150 s on the CPU) so that the
workers share them; what each test runs and asserts is unchanged.
"""
from .example_runner import run_example as _run_example


def test_fcn_xs_example_segments():
    """FCN-16s-style dense prediction: deconv upsampling + crop-aligned
    skip fusion recovers pixel-accurate masks."""
    (acc,) = _run_example("example/fcn-xs/fcn_xs.py",
                          ["--num-epochs", "6", "--samples", "128"],
                          r"FCN pixel accuracy: ([0-9.]+)")
    assert acc > 0.8, acc


def test_sgld_example_samples_posterior():
    """SGLD: posterior-averaged accuracy high AND the samples actually
    spread (a collapsed chain would have ~zero std)."""
    acc, w_std = _run_example(
        "example/bayesian-methods/sgld.py",
        ["--iters", "500", "--burnin", "250"],
        r"posterior-avg accuracy ([0-9.]+), posterior w-std ([0-9.]+)")
    assert acc > 0.9, acc
    assert w_std > 1e-4, w_std


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
