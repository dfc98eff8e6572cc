"""Test harness config: force an 8-device virtual CPU platform.

Sharding and collective tests then exercise a real multi-device mesh
without TPU hardware (SURVEY.md §4 "Implication for the new framework").
`JAX_PLATFORMS=cpu` (the tier-1 command sets it) keeps this process off
any chip; the `jax.config.update` below does the same for a plain
`pytest` on a machine that has one.
"""

import os
import sys

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )
# Across the process boundary: task subprocesses inherit this, and
# parallel.mesh.select_devices then starts the CPU backend only — asked
# for by name, so a task launched for a chip still fails without one.
os.environ["TPU_YARN_PLATFORM"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)
# Task subprocesses launched by LocalBackend must import tf_yarn_tpu too.
os.environ["PYTHONPATH"] = _REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
