"""The benchmark's tests run on the CPU with four virtual devices, at
tiny sizes; they steer the platform here, and the runner has no CPU
option. This runs before JAX starts."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()
