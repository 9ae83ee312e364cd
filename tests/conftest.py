"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform so multi-chip sharding tests
run without TPU hardware (the driver separately dry-run-compiles the
multi-chip path via __graft_entry__.dryrun_multichip).

Note: JAX reads JAX_PLATFORMS when it is imported, so the env var alone is
too late if something imported jax before this file ran —
jax.config.update before the first backend use is what sticks. The pin is
also what lets a consensus_backend="tpu" Core start without a chip
(babble_tpu/tpu/runtime.py require_tpu).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _race_certify_session():
    """`make race` / BABBLE_RACE_CERTIFY=1: run the entire tier-1 suite
    inside one certify() scope, and fail the session if any race
    candidate or lock-order cycle surfaced (analysis/lockruntime.py).
    Off by default: instrumentation patches live classes, and tests that
    construct seeded defects manage their own nested scopes."""
    if not os.environ.get("BABBLE_RACE_CERTIFY"):
        yield None
        return
    from babble_tpu.analysis.lockruntime import certify, format_finding

    with certify() as cert:
        yield cert
    assert not cert.findings, (
        "race certification failed across the test session: "
        + "; ".join(format_finding(f) for f in cert.findings)
    )
