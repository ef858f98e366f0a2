"""Test fixture root.

The reference's distributed-test backbone forks N processes per test and
runs real NCCL on local GPUs (``tests/unit/common.py:67``
``@distributed_test``).  The TPU-native analog (SURVEY.md §4 "lesson"):
ONE process with an 8-device virtual CPU mesh via
``--xla_force_host_platform_device_count`` — collectives execute for real
through XLA's CPU backend, so sharding/collective logic is exercised
without TPU hardware.

This file must set the env vars BEFORE anything imports jax.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Tests run on the CPU mesh, also on a machine that has a chip: force the
# CPU backend through the config API as well, before any device use.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def n_devices():
    import jax

    return jax.device_count()


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def nominal_cpu_physics(monkeypatch):
    """The physics tables (profiling/flops_profiler.py) carry chips only, and
    a device that is not in them is an error.  Tests that drive the
    roofline machinery itself on the CPU mesh add a nominal ``cpu`` row
    here; its numbers mean nothing and never leave the test."""
    from deepspeed_tpu.profiling import flops_profiler

    for table, value in ((flops_profiler.PEAK_FLOPS, 1e12),
                         (flops_profiler.HBM_BYTES_S, 50e9),
                         (flops_profiler.HBM_BYTES, 8e9)):
        monkeypatch.setitem(table, "cpu", value)
