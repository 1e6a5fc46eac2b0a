"""Test fixture environment (SURVEY.md §4 item 2): force an 8-device virtual
CPU platform BEFORE jax initializes, so every SPMD/mesh test runs multi-device
on any machine.  CPU-backend tests don't touch jax and are unaffected.

Real-TPU tier (SURVEY.md §4 item 3): ``MPI_TPU_TEST_TPU=1 pytest -m tpu``
leaves the platform alone so tests/test_tpu_real.py runs on the actual
chip; without the env var those tests see the CPU platform and self-skip."""

import os

if not os.environ.get("MPI_TPU_TEST_TPU"):
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_ENABLE_X64", "0")
