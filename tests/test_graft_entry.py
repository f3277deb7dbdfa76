"""``__graft_entry__.dryrun_multichip(n)`` runs on the devices jax reports
and nowhere else: enough devices -> the body runs in-process; too few ->
it raises (it used to re-execute itself on virtual CPU devices and report
success, which hid the missing devices).
"""
import pytest


def test_dryrun_multichip_raises_with_too_few_devices():
    import jax
    import __graft_entry__
    have = len(jax.devices())
    with pytest.raises(RuntimeError, match="jax reports %d" % have):
        __graft_entry__.dryrun_multichip(have + 1)


def test_dryrun_multichip_in_process_when_devices_suffice():
    """With >= n devices already visible (the tests' 8-device virtual mesh),
    the body runs in-process."""
    import jax
    import __graft_entry__
    assert len(jax.devices()) >= 8
    __graft_entry__.dryrun_multichip(8)
