"""Test config: run on an 8-device virtual CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): one op/suite
parameterized by backend; multi-device tests run on virtual host devices
(``--xla_force_host_platform_device_count=8``), the analog of the reference's
process-level fake cluster (tests/nightly/test_all.sh).
"""
import os

_platform = os.environ.get("MXNET_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# jax may already be imported (a plugin or sitecustomize on the path can
# import it at interpreter start, capturing JAX_PLATFORMS from the outer env),
# so update the live config too — this must happen before any backend
# initializes.  The suite never touches an accelerator: it checks results,
# control flow and counts on 8 virtual CPU devices; what only the chip can
# show is chip_smoke.py's job.  No persistent compile cache is switched on
# here either (six workers would write every tiny program into one directory).
import jax

jax.config.update("jax_platforms", _platform)

import numpy as np
import pytest


def pytest_configure(config):
    # tier-1 runs with `-m 'not slow'`; register the marker so the probe
    # smoke tests don't warn as unknown
    config.addinivalue_line(
        "markers", "slow: long-running (excluded from tier-1 via -m 'not slow')")


@pytest.fixture(autouse=True)
def _seed_everything():
    """Analog of the reference @with_seed() fixture (tests/python/unittest/
    common.py:97-130): deterministic per-test seeds."""
    import mxnet_tpu as mx
    np.random.seed(0)
    mx.random.seed(0)
    yield
