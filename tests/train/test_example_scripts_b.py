"""Example scripts run as subprocesses, part two of three.

One file held all of these after the in-process examples of
``test_examples.py`` and was, alone on one worker under ``--dist
loadfile``, longer than the rest of tier-1 together.  They are split by
running time (about 450 s, 450 s and 150 s on the CPU) so that the
workers share them; what each test runs and asserts is unchanged.
"""
from .example_runner import run_example as _run_example


def test_module_gan_example():
    """Module-API GAN: G trains purely from D's input gradients
    (get_input_grads -> backward); best-trailing-eval selection."""
    (err,) = _run_example("example/gan/gan_mnist.py", ["--iters", "250"],
                          r"radius - 1\| of generated points: ([0-9.]+)")
    assert err < 0.4, err


def test_ner_example_tags():
    """BiLSTM sequence labeling: the trigger->next-token rule needs
    cross-timestep context, so beating the O-rate proves the recurrence
    carries it."""
    (acc,) = _run_example("example/named_entity_recognition/ner.py",
                          ["--iters", "80"],
                          r"NER entity-token accuracy: ([0-9.]+)")
    assert acc > 0.9, acc


def test_rnn_time_major_example():
    """NTC and TNC layouts learn the same Markov rule to near-identical
    ppl (seeded init + same data: layout is semantics-free)."""
    p_ntc, p_tnc = _run_example(
        "example/rnn-time-major/rnn_time_major.py", ["--iters", "100"],
        r"final ppl  NTC ([0-9.]+)   TNC ([0-9.]+)")
    assert p_ntc < 6 and p_tnc < 6, (p_ntc, p_tnc)
    assert abs(p_ntc - p_tnc) / p_ntc < 0.02, (p_ntc, p_tnc)
