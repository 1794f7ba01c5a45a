"""Test configuration: force a virtual 8-device CPU mesh so the entire
distributed stack is testable without TPU hardware (SURVEY.md §4 lesson —
the reference runs its collective tests on CPU/Gloo the same way)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# CPU test processes (and the children they start) stay out of the on-disk
# compile cache the chip runs use.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reseed():
    import paddle_tpu as paddle
    paddle.seed(1234)
    yield
    # excepthook hygiene: any test that constructed a CheckpointManager
    # armed the flight dump-on-exception hook; uninstall it so test order
    # can never flip the excepthook-sensitive flight tests
    from paddle_tpu.observability import flight
    flight.uninstall_excepthook()
