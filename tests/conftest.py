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
    _flags += " --xla_force_host_platform_device_count=8"
# Three fifths of the suite's case-seconds are XLA:CPU compiles of programs that then run once at
# toy sizes, and six workers on eight cores are bound by the CPU's seconds, not the wall's (the
# driver cut PR 52's first runs at 1,470 s, 1,707 of 1,812 tests in). So the CPU's code is
# generated the cheap way: LLVM at -O0 (the optimized HLO text is the same, byte for byte) and
# the elemental emitters in place of the MLIR fusion emitters (which fuse a little differently:
# 304 fusions where 404 were in a toy gradient program). A toy gradient program and an 8-device
# ring compile in 7.4 CPU-seconds where they took 21.3, a sample of three files runs in 178
# CPU-seconds where it took 331, and the whole suite in 796 s where it took 1,671. Nothing a test
# asserts is about the CPU's speed; a compile for a described TPU (``test_tpu_aot_compile.py``)
# gives the same optimized text and temporaries under both, and a flag already in ``XLA_FLAGS``
# is left as given. Subprocess workloads inherit the variable.
for _cheap in ("--xla_backend_optimization_level=0", "--xla_cpu_use_fusion_emitters=false"):
    if _cheap.split("=")[0] not in _flags:
        _flags += " " + _cheap
os.environ["XLA_FLAGS"] = _flags
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

# Under xdist (``-n 6``, the driver's command) the collection order decides which worker runs what,
# and the longest files have to start early and apart or one worker ends the run alone while five
# idle. ``--dist loadfile`` hands a file at a time, in collection order, to whichever worker is
# free. ``--dist load`` (the driver's since PR 51) first sends every worker ONE chunk of consecutive
# tests, a quarter of its even share, and tops a worker up (half of what is left over twice the
# workers) only when it has fewer than two slow tests left: with the longest files simply first,
# the third worker's first chunk was 1,517 s of the run's 1,470. So the order is laid out in those
# chunks: each of the first ``workers`` chunks takes whole files of about equal seconds (the longest
# first, each to the chunk that has least; a file is never split while it fits, its module's
# fixtures and caches are built once) and is filled to its size with short tests; the long files
# that fit no chunk (``test_olmoe.py``: more tests than a chunk) follow, longest first, EVENLY
# SPACED through the first four fifths of the short tests: a later chunk is a hundred consecutive
# tests, and nine files of a minute each side by side were one worker's 680 s near the end. The
# last fifth of the short tests fills the workers' ends. Under ``loadfile`` the same order hands
# the long files out first. The seconds are each file's in the run of PR 51's tree (six workers,
# eight cores; ``tests/perf/suite_seconds.py <junit xml> --table`` prints them and the run's length
# under this order); a new file of a minute or more belongs here. With every entry wrong by up to
# a quarter the run is 25 s longer at the ninth decile (60 draws); without the spacing, 96 s.
# Under the cheap CPU code above every file takes about half its entry, in nearly the same order:
# this table lays PR 52's run out in 775 s, one taken anew from that run in 766.
_SECONDS = {"test_tpu_aot_compile.py": 880, "test_rehearsal_hybrid.py": 395, "test_granite_hybrid.py": 390,
            "test_ring_attention.py": 390, "test_rehearsal_ssm_moe.py": 340, "test_nemotron_h.py": 330,
            "test_qwen3_next.py": 305, "test_rehearsal_ssm.py": 305, "test_olmoe.py": 340,
            "test_launcher.py": 235, "test_moe.py": 210, "test_flash_attention.py": 245,
            "test_ring_zigzag.py": 165, "test_rehearsal_swa_moe.py": 165, "test_rehearsal_mla_moe.py": 160,
            "test_ouro.py": 160, "run_func_test.py": 150, "test_causal_conv_kernel.py": 130,
            "test_rehearsal_hc_moe.py": 140, "test_xing_moe.py": 125,
            "test_glm_moe.py": 125, "test_rehearsal_conv_moe.py": 125, "test_lfm2_moe.py": 110, "test_rehearsal.py": 120, "test_rehearsal_loop.py": 110,
            "test_rehearsal_moe.py": 110, "test_mellum.py": 105, "test_ssd.py": 95, "test_ssd_kernel.py": 95,
            "run_checkpoint_test.py": 90, "test_transformer_layer.py": 75, "test_delta_rule_kernel.py": 75,
            "test_chip_smoke.py": 70, "test_pipeline_spmd.py": 70, "test_generate.py": 70,
            "test_examples.py": 70, "test_resnet.py": 60}


def _in_chunks(items, name, workers):
    """``items`` in the order described above."""
    chunk = max(len(items) // workers // 4, 2)
    by_file = {}
    for item in items:
        by_file.setdefault(name(item), []).append(item)
    short = [item for f, its in by_file.items() if f not in _SECONDS for item in its]
    chunks, later = [[0, []] for _ in range(workers)], []
    for f in sorted((f for f in by_file if f in _SECONDS), key=lambda f: (-_SECONDS[f], f)):
        room = [c for c in chunks if len(c[1]) + len(by_file[f]) <= chunk]
        if room:
            least = min(room, key=lambda c: c[0])
            least[0] += _SECONDS[f]
            least[1].extend(by_file[f])
        else:
            later.append(f)
    order = []
    for _, its in chunks:
        fill = chunk - len(its)
        order.extend(its + short[:fill])
        del short[:fill]
    between = len(short) * 4 // 5 // max(len(later), 1)
    for i, f in enumerate(later):
        order.extend(by_file[f] + short[i * between:(i + 1) * between])
    return order + short[len(later) * between:]


def pytest_collection_modifyitems(config, items):
    name = lambda item: os.path.basename(str(item.fspath))      # noqa: E731
    if hasattr(config, "workerinput"):          # an xdist worker: every worker sorts alike
        items[:] = _in_chunks(items, name, config.workerinput["workercount"])
    else:
        items.sort(key=lambda item: name(item) in _HEAVY_FILES)
