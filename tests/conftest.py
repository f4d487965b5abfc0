"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's strategy of testing distributed semantics without a
cluster (test_utils.py:166-205): sharding/resharding tests run on 8 virtual
CPU devices; multi-process semantics are tested with real subprocesses.

The platform is forced twice: through the environment for the
subprocesses tests spawn, and through jax.config for this process (a
plugin may import jax before this file runs, after which the variable
alone is read too late; the config takes effect at first backend
initialization).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # for subprocesses we spawn
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Telemetry is read ONCE at package import: pin it off before any test
# module imports torchsnapshot_tpu so an ambient TORCHSNAPSHOT_TPU_TELEMETRY=1
# can't scatter .snapshot_telemetry/.telemetry artifacts through tests
# that assert exact snapshot directory layouts. Telemetry tests opt back
# in with telemetry.set_enabled(True).
os.environ["TORCHSNAPSHOT_TPU_TELEMETRY"] = "0"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _default_write_batching_off(monkeypatch):
    """Most tests depend on the default per-payload file layout (payload
    names, deterministic dedup locations, corrupt-one-file helpers) —
    slab batching changes all of that by design. Pin it off suite-wide so
    an ambient TORCHSNAPSHOT_TPU_ENABLE_BATCHING=1 can't change test
    semantics; batching tests opt back in with monkeypatch.setenv (their
    in-test setenv runs after this autouse fixture)."""
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_ENABLE_BATCHING", "0")

