"""Test bootstrap: force the CPU backend with 8 virtual devices.

Every test runs on the CPU; sharding tests use a virtual mesh
(jax.sharding.Mesh over the 8 host-platform devices). The platform is
pinned through both the environment and jax.config before any backend
starts, so nothing in the suite can open an accelerator.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: the suite jits hundreds of device programs
# whose shapes repeat across runs; caching them makes re-runs much faster.
from kueue_tpu.utils.startup import configure_compile_cache  # noqa: E402

configure_compile_cache()
