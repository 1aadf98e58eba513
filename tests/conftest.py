"""Test harness: force an 8-virtual-device CPU platform.

The reference test suite needs real GPUs under ``horovodrun -np N``
(``distributed_embeddings/python/layers/dist_model_parallel_test.py:85-89``);
here multi-device tests run anywhere via XLA's host-platform device count —
a capability called out in SURVEY.md §4 as worth having from day 1.

Must run before the first JAX backend initialization: the device count flag
is read when the CPU backend comes up, and ``jax_platforms`` is pinned to cpu
so the suite never takes a chip (jax 0.9.0; the tier-1 command also exports
``JAX_PLATFORMS=cpu``).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# a user-level DETPU_OBS=1 would flip every env-defaulted train step to the
# instrumented 3-tuple return and break the suite's 2-tuple call sites —
# the suite opts in explicitly (with_metrics=True) where it tests metrics.
# DETPU_TELEMETRY likewise changes the step arity (telemetry state in/out).
# Popped here (before any test imports), so subprocess tests inherit the
# sanitized environment too.
os.environ.pop("DETPU_OBS", None)
os.environ.pop("DETPU_TELEMETRY", None)

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running / memory-heavy tests")


import pytest  # noqa: E402 - after the backend-forcing block above


@pytest.fixture
def transfer_guard():
    """Opt-in: fail the test on any IMPLICIT host<->device transfer inside
    it (``jax.transfer_guard("disallow")``). Trainer / dist-embedding step
    tests use this to prove the jitted step never smuggles a hidden
    device->host readback or a per-step host constant upload — the same
    property the step auditor checks statically (analysis/audit.py), here
    enforced at run time. Explicit transfers (``jax.device_put``, committed
    input staging, ``np.asarray`` readbacks the test itself does) stay
    allowed."""
    with jax.transfer_guard("disallow"):
        yield
