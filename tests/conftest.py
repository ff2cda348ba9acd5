"""Test configuration: a virtual 8-device CPU mesh.

The suite never needs a chip.  ``JAX_PLATFORMS=cpu`` goes into
``os.environ`` before JAX is imported, so it holds for this process
and for every server a test starts as a child; the on-chip tests
(tests/test_tpu_chip.py) undo it for their own children.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
# Shrink the device-kernel event bucket: the semantic kernels' one-hot
# matmuls at the production bucket (8192) are far too slow on the CPU
# backend.  Production size is exercised by the tpu-marked tests.
os.environ.setdefault("TB_DEV_B", "512")

os.environ["JAX_PLATFORMS"] = "cpu"

from tigerbeetle_tpu import device

device.enable_compile_cache()

import pytest


@pytest.fixture(params=["cpu", "tpu"])
def sm(request):
    """Both state-machine implementations, for differential coverage."""
    if request.param == "tpu":
        from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine

        return TpuStateMachine()
    from tigerbeetle_tpu.state_machine import CpuStateMachine

    return CpuStateMachine()
