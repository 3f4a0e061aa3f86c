"""Test harness setup.

All JAX tests run on a virtual 8-device CPU mesh so multi-chip sharding
(`kepler_tpu.parallel`) is exercised without TPU hardware, and so a test
run never takes a chip away from the process that owns it. Both
variables are set before anything imports jax, which reads them once.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
