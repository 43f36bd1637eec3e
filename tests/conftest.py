"""Test harness: single-host multi-device mesh on CPU.

TPU translation of the reference's ``MultiProcessTestCase``-style single-host
multi-rank testing (apex/transformer/testing/distributed_test_base.py:22-82):
instead of spawning processes, we force 8 virtual CPU devices and build real
``jax.sharding.Mesh``es over them (SURVEY.md §4 "TPU translation").

This file must run before jax initializes its backends, hence env mutation at
import time.
"""

import os

# Force CPU whatever the ambient environment selects: the suite's
# multi-rank tests need 8 virtual devices, and a test run must never
# take the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# also through the config, in case a pytest plugin imported jax (and read
# the environment) before this file ran
jax.config.update("jax_platforms", "cpu")
# NO persistent compilation cache in the suite: an earlier jaxlib
# nondeterministically died with SIGSEGV/SIGABRT while deserializing
# cached CPU executables, which aborted a whole tier-1 run.  Whether the
# installed jaxlib still does has not been re-examined, so the suite
# stays cold; the entry points place a cache themselves
# (apex_tpu.utils.compile_cache).
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs -m 'not slow' (ROADMAP.md); register the marker so
    # slow-marked long benchmarks don't trip UnknownMarkWarning
    config.addinivalue_line(
        "markers",
        "slow: long-running test excluded from tier-1 (-m 'not slow')")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 forced CPU devices, got {len(devs)}"
    return devs


@pytest.fixture
def mesh8(devices):
    """A 1-D 8-device mesh named ('dp',)."""
    from jax.sharding import Mesh

    return Mesh(np.array(devices[:8]), ("dp",))


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


# float32 logits of O(1) that two DIFFERENT programs (cached against
# uncached, one chunking against another, a 2-lane step against a 1-lane
# one) compute from the same dot products: XLA:CPU's gemm rounds a row by
# the rows per batch it sits in, measured <= 3.9e-7 (ROADMAP D1).  What
# these comparisons guard against - a clobbered row, a broken copy-on-write,
# a wrong checkpoint - moves logits by 1e-2 and more.  Runs through the SAME
# program with the same batch are still compared with ``==``.
LOGITS_ATOL = 2e-6


@pytest.fixture(scope="session")
def same_logits():
    """``same_logits(got, want, msg="")``: equal to ``LOGITS_ATOL``."""
    def check(got, want, msg=""):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=LOGITS_ATOL, err_msg=msg)
    return check
