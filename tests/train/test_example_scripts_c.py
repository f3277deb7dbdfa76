"""Example scripts run as subprocesses, part three of three.

One file held all of these after the in-process examples of
``test_examples.py`` and was, alone on one worker under ``--dist
loadfile``, longer than the rest of tier-1 together.  They are split by
running time (about 450 s, 450 s and 150 s on the CPU) so that the
workers share them; what each test runs and asserts is unchanged.
"""
from .example_runner import run_example as _run_example


def test_capsnet_example_routes():
    """Dynamic routing-by-agreement trains (capsule lengths as class
    scores, margin loss)."""
    (acc,) = _run_example("example/capsnet/capsnet.py",
                          ["--iters", "60"],
                          r"capsnet routing accuracy: ([0-9.]+)")
    assert acc > 0.8, acc


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
