"""``benchmarks/run.py`` end to end on the CPU at a toy size, through the hook that
only tests use (the command itself refuses a CPU); and the traffic generators."""

import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import peaks, run
from benchmarks.manifest import REPO_ROOT, Manifest

import tiny

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SEED = 2 ** 31 + 12345            # more than 32 signed bits hold


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("cellbench"), four_chip=True)
    return Manifest(bench_dir=os.path.join(root, "benchmarks"))


@pytest.fixture
def cpu_peaks(monkeypatch):
    """A row for the CPU so that the readers run; what they return here is never a
    device number and is only checked for being there."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ["tiny_train", "tiny_zero3", "tiny_serve"])
def test_cell_runs_end_to_end(tiny_manifest, cpu_peaks, tmp_path, capsys, cell, trace):
    out_dir = str(tmp_path / "out")
    result = run.run_cell(cell, SEED, 0.5, bool(trace), manifest=tiny_manifest,
                          allow_cpu=True, out_dir=out_dir)
    result = json.loads(json.dumps(result))
    assert set(result) == RESULT_KEYS          # no device planes on the CPU, so no breakdown
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    chips = tiny_manifest.cell(cell)["chips"]
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == chips
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in tiny_manifest.metrics_of(section, cell)}
    assert set(result["metrics"]) <= set(declared)
    for name, entry in result["metrics"].items():
        assert set(entry) == {"value", "unit"} and entry["unit"] == declared[name]
        assert np.isfinite(entry["value"])
    if trace:
        # host-clock and counter metrics are there; device-trace ones find nothing to
        # read on the CPU and are left out
        assert "setup_compile_s" in result["metrics"]
        assert not any(n.startswith(("device_idle", "flash_", "collective_"))
                       for n in result["metrics"])
        assert "busy_s" not in result["device"]
    else:
        assert set(result["metrics"]) == set(declared)
        assert result["metrics"]["setup_s"]["value"] > 0
    # the per-step record, and its summary on an earlier line
    with open(os.path.join(out_dir, f"{cell}.{SEED}.steps.json")) as f:
        record = json.load(f)
    walls = record.get("step_interval_ms") or record["iteration_ms"]
    assert len(walls) >= result["attempted"] or cell == "tiny_serve"
    assert record["reference"]["ok"] is True
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["count"] == len(walls) and summary["window_compiles"] == 0
    if cell != "tiny_serve":
        assert len(summary["first_losses"]) >= 2
        assert record["losses"][-1] < record["warm_losses"][0]
    assert not os.path.exists(os.path.join(out_dir, f"trace.{cell}.{SEED}"))


def test_last_line_is_the_contracts_object(tiny_manifest, tmp_path, monkeypatch, capsys):
    hook = functools.partial(run.run_cell, manifest=tiny_manifest, allow_cpu=True,
                             out_dir=str(tmp_path / "out"))
    monkeypatch.setattr(run, "run_cell", hook)
    run.main(["--workload", "tiny_train", "--seed", "7", "--seconds", "0.3", "--trace", "0"])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    result = json.loads(last)
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(result["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}


def test_the_command_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--workload",
         "xl_d20_train_1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr
    assert not any(line.lstrip().startswith("{") for line in done.stdout.splitlines())


def test_a_cell_that_asks_for_more_chips_than_there_are_is_refused(tiny_manifest, monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:2])
    with pytest.raises(SystemExit, match="needs 4 chips"):
        run.run_cell("tiny_zero3", 1, 0.1, False, manifest=tiny_manifest, allow_cpu=True)


def test_packed_documents_same_shapes_other_tokens():
    manifest = Manifest()
    traffic = manifest.traffic("packed_docs")
    generate = manifest.generator(traffic["generator"])
    a, info_a = generate(traffic, 1, vocab=50304, batch=4, n_batches=6)
    b, info_b = generate(traffic, SEED, vocab=50304, batch=4, n_batches=6)
    again, _ = generate(traffic, 1, vocab=50304, batch=4, n_batches=6)
    assert [(t.shape, l.shape) for t, l in a] == [((4, 1024), (4, 1024))] * 6
    assert all(t.dtype == np.int32 and l.dtype == np.int32 for t, l in a + b)
    assert all(np.array_equal(t[:, 1:], l[:, :-1]) for t, l in a)       # labels: next token
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(a, again))    # same seed, same data
    assert not np.array_equal(a[0][0], b[0][0])
    for batches in (a, b):
        tokens = np.concatenate([t.ravel() for t, _ in batches])
        assert tokens.max() <= traffic["eot_token"] and (tokens == traffic["eot_token"]).any()
        # a Zipf law: the most frequent id is far more frequent than the median one
        counts = np.bincount(tokens[tokens != traffic["eot_token"]])
        assert counts.max() > 50 * np.median(counts[counts > 0])
    spec = traffic["doc_len"]
    for info in (info_a, info_b):
        lens = info["doc_lens"]
        assert lens.min() >= spec["min"] and lens.max() <= spec["max"]


def test_closed_loop_chat_same_multiset_of_lengths_other_order_and_tokens():
    manifest = Manifest()
    traffic = manifest.traffic("chat_closed")
    generate = manifest.generator(traffic["generator"])
    n = traffic["multiset_size"]
    seen = {}
    for seed in (1, SEED):
        requests, info = generate(traffic, seed, vocab=50257)
        cycle = [next(requests) for _ in range(n)]
        seen[seed] = cycle
        assert sorted((len(p), o) for p, o in cycle) == sorted(info["multiset"])
        assert all(len(p) + o <= traffic["max_total_len"] for p, o in cycle)
        assert all(0 <= min(p) and max(p) < 50257 for p, _ in cycle)
        # the next cycle serves the same multiset again
        assert sorted((len(p), o) for p, o in (next(requests) for _ in range(n))) == \
            sorted(info["multiset"])
    order = {seed: [(len(p), o) for p, o in cycle] for seed, cycle in seen.items()}
    assert order[1] != order[SEED]
    assert seen[1][0][0] != seen[SEED][0][0]
    lens = np.array([p for p, _ in info["multiset"]])
    assert traffic["prompt_len"]["min"] <= lens.min() and lens.max() <= traffic["prompt_len"]["max"]
