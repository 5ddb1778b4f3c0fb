"""Test env: force JAX onto CPU with 8 virtual devices so multi-chip sharding
paths (Mesh over the node axis) are exercised without TPU hardware.

The environment variables cover a JAX that is not imported yet; when a
plugin imported it first, the same two settings go through jax.config,
which raises if a backend has already initialised — the tests never run
on a backend they did not choose.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

if "jax" in sys.modules:
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
