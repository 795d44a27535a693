"""Shared environment bootstrap for subprocess workload drivers.

Must be imported (and ``setup()`` called) BEFORE jax initializes a backend: both settings
are read then (see tests/conftest.py)."""

import os
import sys


def setup():
    """Configure the CPU test platform and repo import path; returns the jax module."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        n = os.environ.get("DS_TEST_CPU_DEVICES", "8")
        os.environ["XLA_FLAGS"] = flags + f" --xla_force_host_platform_device_count={n}"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
    return jax
