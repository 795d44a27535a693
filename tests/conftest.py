"""Test harness: force an 8-device virtual CPU platform BEFORE jax backends initialize.

This mirrors the reference's multi-process-on-one-host distributed testing strategy
(tests/unit/common.py:14-100's @distributed_test decorator): instead of forking N NCCL
processes, we give JAX 8 virtual CPU devices and run real mesh collectives over them.
Both settings are read when the backend initializes, so they are set before jax is
imported. The chip is never used here; ``chip_smoke.py`` is the on-chip check.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# Every engine points the persistent compilation cache at <checkout>/.jax_cache
# (utils/compile_cache.py). A test run must not read what an earlier run left there.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration tests")


# The ROADMAP tier-1 command runs the suite under a hard wall-clock cap. The
# 8-rank interpret-mode ring suites are by far the slowest files (minutes of
# XLA compile + interpret execution each); schedule them last so that if the
# cap truncates the run it cuts into the expensive tail instead of starving
# the hundreds of fast tests collected behind them alphabetically. Stable
# sort: relative order within each group is unchanged.
_HEAVY_FILES = ("test_ring_attention.py", "test_ring_zigzag.py")

# Under xdist (``-n 6 --dist loadfile``, the driver's command) a file is one unit of work,
# handed out in collection order to whichever worker is free: a file of five to ten minutes
# that starts late ends the run alone while five workers idle. There the longest files go
# FIRST, longest first (their seconds in the run of PR 43's tree), the ring suites among
# them, and the hundreds of short files fill the workers' ends evenly.
_LONGEST_FIRST = ("test_qwen3_next.py", "test_tpu_aot_compile.py", "test_nemotron_h.py",
                  "test_rehearsal_hybrid.py", "test_rehearsal_mla_moe.py", "test_glm_moe.py",
                  "test_granite_hybrid.py", "test_ring_attention.py",
                  "test_moe.py", "test_rehearsal_ssm_moe.py", "test_rehearsal_ssm.py",
                  "test_ring_zigzag.py", "test_launcher.py", "test_olmoe.py", "test_ouro.py",
                  "test_flash_attention.py")


def pytest_collection_modifyitems(config, items):
    name = lambda item: os.path.basename(str(item.fspath))      # noqa: E731
    if hasattr(config, "workerinput"):          # an xdist worker: every worker sorts alike
        rank = {f: i for i, f in enumerate(_LONGEST_FIRST)}
        items.sort(key=lambda item: rank.get(name(item), len(rank)))
    else:
        items.sort(key=lambda item: name(item) in _HEAVY_FILES)
