"""The benchmark's manifest holds to its contract, and grows by files alone."""

import json
import os

import pytest

from benchmarks.manifest import BENCH_DIR, NAME_RE, UNIT_RE, Manifest, check

import tiny


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def test_contract_rules_hold(manifest):
    assert check(manifest) == []
    assert len(json.dumps(manifest.doc)) < 64 * 1024
    assert manifest.doc["command"] == ["python3", "benchmarks/run.py"]
    assert "xl_d20_train_1chip" in [w["name"] for w in manifest.doc["workloads"]]


def test_every_cell_config_and_traffic_file_loads(manifest):
    for w in manifest.doc["workloads"]:
        cell = manifest.cell(w["name"])
        config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
        assert cell["name"] == w["name"] and config["name"] == cell["config"]
        assert callable(manifest.runner(config["runner"]))
        assert callable(manifest.generator(traffic["generator"]))
        entry = next(c for c in manifest.doc["configs"] if c["name"] == cell["config"])
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
        assert config["source"] == entry["source"]


@pytest.mark.parametrize("kind", ["cells", "configs", "traffic"])
def test_every_data_file_is_json_with_its_own_name(kind):
    names = sorted(os.listdir(os.path.join(BENCH_DIR, kind)))
    assert names
    for file_name in names:
        stem, ext = os.path.splitext(file_name)
        assert ext == ".json" and NAME_RE.match(stem)
        with open(os.path.join(BENCH_DIR, kind, file_name)) as f:
            assert json.load(f)["name"] == stem


def test_every_metric_has_a_reader_and_every_reader_returns_nothing_on_nothing(manifest):
    for m in manifest.doc["per_layer"]:
        assert callable(manifest.reader(m["name"]))
    # every reader file, listed or waiting for its cell: nothing to read, nothing returned
    for file_name in sorted(os.listdir(os.path.join(BENCH_DIR, "layer_metrics"))):
        stem, ext = os.path.splitext(file_name)
        if ext == ".py":
            assert NAME_RE.match(stem)
            assert manifest.reader(stem)({"setup": {}, "trace": None}) is None


def test_names_and_units_hold_only_the_allowed_characters(manifest):
    doc = manifest.doc
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME_RE.match(m["name"]) and UNIT_RE.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in doc["workloads"]:
        assert all(NAME_RE.match(w[k]) for k in ("name", "config", "traffic"))
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


def test_moves_is_reported_wherever_the_metric_is(manifest):
    doc = manifest.doc
    cells = {w["name"] for w in doc["workloads"]}
    reported = {m["name"]: set(m.get("workloads", cells)) for m in doc["end_to_end"]}
    for m in doc["per_layer"]:
        assert set(m.get("workloads", cells)) <= reported[m["moves"]], m["name"]
    layers = {m["layer"] for m in doc["per_layer"]}
    assert layers <= {"entry points", "train engine", "ZeRO layouts", "model step", "kernels",
                      "device", "serving engine", "scheduler"}


def test_at_most_a_quarter_of_the_cells_or_one_ask_for_four_chips(manifest):
    cells = manifest.doc["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def test_a_cell_config_traffic_and_metric_are_added_by_files_alone(tmp_path):
    """The fixture copies the benchmark's files untouched, adds files and entries, and
    everything loads by name from the temporary directory."""
    root = tiny.make_root(tmp_path)
    grown = Manifest(bench_dir=os.path.join(root, "benchmarks"))
    assert check(grown) == []
    cell = grown.cell("tiny_train")
    assert grown.config(cell["config"])["model"]["n_embd"] == 32
    assert grown.traffic(cell["traffic"])["generator"] == "train_packed"
    assert grown.reader("steps_in_window")({"steps": 7}) == 7
    names = [m["name"] for m in grown.metrics_of("per_layer", "tiny_train")]
    assert "steps_in_window" in names and "mfu" in names
    assert "steps_in_window" not in [m["name"] for m in grown.metrics_of("per_layer", "tiny_serve")]
    assert [m["name"] for m in grown.metrics_of("end_to_end", "tiny_serve")] == [
        "setup_s", "serve_tokens_per_s", "ttft_ms_p95", "token_gap_ms_p95"]
    # nothing that was there has changed
    for kind in ("cells", "configs", "traffic", "layer_metrics", "generators", "runners"):
        for file_name in os.listdir(os.path.join(BENCH_DIR, kind)):
            if file_name.startswith("__"):
                continue
            with open(os.path.join(BENCH_DIR, kind, file_name), "rb") as a, \
                    open(os.path.join(root, "benchmarks", kind, file_name), "rb") as b:
                assert a.read() == b.read()


def test_a_cell_that_disagrees_with_its_entry_is_refused(tmp_path):
    root = tiny.make_root(tmp_path)
    path = os.path.join(root, "benchmarks", "cells", "tiny_train.json")
    with open(path) as f:
        cell = json.load(f)
    cell["chips"] = 4
    with open(path, "w") as f:
        json.dump(cell, f)
    with pytest.raises(ValueError, match="chips"):
        Manifest(bench_dir=os.path.join(root, "benchmarks")).cell("tiny_train")


@pytest.mark.parametrize("fault, expected", [
    (lambda d: d["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda d: d["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda d: d["workloads"][0].update(why="x" * 201), "why"),
    (lambda d: d["per_layer"][1].update(unit="tokens per s"), "unit"),
    (lambda d: d["workloads"].append(dict(d["workloads"][0], name="twin")), "twice"),
    (lambda d: d.update(run_seconds=90), "run_seconds"),
])
def test_check_names_each_fault(manifest, fault, expected):
    broken = Manifest()
    fault(broken.doc)
    assert any(expected in f for f in check(broken)), check(broken)
