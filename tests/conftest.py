"""Test env: force a virtual 8-device CPU platform so multi-chip sharding
logic is exercised without TPU hardware (SURVEY.md §4 'Implication')."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# JAX_PLATFORMS above covers the subprocesses tests spawn; the config
# pin covers this process even if jax was imported before conftest (its
# env read happens once, at import) — no backend is initialized yet.
import jax
jax.config.update("jax_platforms", "cpu")
