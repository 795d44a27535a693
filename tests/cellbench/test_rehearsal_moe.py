"""``olmoe_d4_train_4chip``'s runner end to end on the CPU at a toy size (four virtual
devices, experts split over them), its record, its new readers on nothing and on a
recorded trace slice, and ``flops_moe.py`` against hand counts."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops_moe, moe_spans, peaks, run
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest, check

import tiny
from test_program_spans import Recorded

CELL = "olmoe_d4_train_4chip"
NEW_READERS = ["moe_time_share", "expert_matmul_roofline", "moe_exchange_share", "mfu.moe",
               "moe_load_max_over_mean"]
TINY_OLMOE = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 64,
              "intermediate_size": 32, "max_position_embeddings": 64, "model_type": "olmoe",
              "norm_topk_prob": False, "num_attention_heads": 4, "num_experts": 8,
              "num_experts_per_tok": 2, "num_hidden_layers": 2, "num_key_value_heads": 4,
              "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
              "tie_word_embeddings": False, "vocab_size": 256}
SEED = 2 ** 31 + 4321


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The tiny root of ``tiny.py`` plus a toy OLMoE configuration and its four-device
    cell, added by files and entries alone."""
    root = tiny.make_root(tmp_path_factory.mktemp("cellbench_moe"))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    tiny._dump(os.path.join(bench, "configs", "tiny-olmoe.json"), dict(
        TINY_OLMOE, name="tiny-olmoe", source="tests/cellbench/test_rehearsal_moe.py",
        runner="train_moe", reduced={}, model=TINY_OLMOE, router_aux_loss_coef=0.01,
        assumed={"initializer_range": [None, 0.02, "toy"]}, compute_dtype="bfloat16",
        engine=dict(tiny.ENGINE, zero_optimization={"stage": 2}),
        reference={"module": "olmoe_reference", "tolerances": "olmoe_tolerances",
                   "last_positions": 16}))
    doc["configs"].append({"name": "tiny-olmoe", "source": "tests/cellbench/test_rehearsal_moe.py",
                           "file": "benchmarks/configs/tiny-olmoe.json", "reduced": [],
                           "why": "toy sizes for the CPU rehearsal"})
    tiny._dump(os.path.join(bench, "cells", "tiny_moe.json"), dict(
        name="tiny_moe", config="tiny-olmoe", traffic="tiny_docs", chips=4,
        micro_batch_per_chip=1, warm_steps=2, trace_seconds=1, why="toy cell"))
    doc["workloads"].append({"name": "tiny_moe", "config": "tiny-olmoe", "traffic": "tiny_docs",
                             "chips": 4, "why": "toy expert cell for the CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_moe")
    tiny._dump(os.path.join(root, "BENCHMARK.json"), doc)
    return Manifest(bench_dir=bench)


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def test_the_cell_and_its_entries_hold_to_the_contract():
    manifest = Manifest()
    assert check(manifest) == []
    cell, config = manifest.cell(CELL), manifest.config("olmoe-1b-7b-d4")
    assert cell["chips"] == 4 and cell["micro_batch_per_chip"] == 2
    assert manifest.traffic(cell["traffic"])["seq_len"] == 4096
    # the published keys stand at the top level, as the catalog has them, and again as
    # the group the runner reads; only the depth differs from the source
    assert {k: config[k] for k in config["model"]} == config["model"]
    assert config["reduced"] == {"num_hidden_layers": [16, 4]}
    assert config["num_hidden_layers"] == 4 and config["hidden_size"] == 2048
    reported = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert set(NEW_READERS) | {"collective_exposed_share", "setup_compile_s"} <= reported
    # every tpu_custom_call counts as flash there: off a cell with a second kernel
    assert not {"flash_time_share", "flash_roofline", "mfu"} & reported


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end(tiny_manifest, cpu_peaks, tmp_path, capsys, trace):
    out_dir = str(tmp_path / "out")
    result = run.run_cell("tiny_moe", SEED, 0.5, bool(trace), manifest=tiny_manifest,
                          allow_cpu=True, out_dir=out_dir)
    result = json.loads(json.dumps(result))
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["count"] == 4
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in tiny_manifest.metrics_of(section, "tiny_moe")}
    assert set(result["metrics"]) <= set(declared)
    if trace:
        # the counter and the host-clock metrics are there; the device-trace ones find
        # no device plane on the CPU
        assert {"mfu.moe", "moe_load_max_over_mean", "setup_compile_s"} <= set(result["metrics"])
        assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
        assert not {"moe_time_share", "expert_matmul_roofline", "moe_exchange_share",
                    "collective_exposed_share"} & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    with open(os.path.join(out_dir, f"tiny_moe.{SEED}.steps.json")) as f:
        record = json.load(f)
    reference = record["reference"]
    assert reference["ok"] is True and set(reference["tolerances"]) <= set(reference)
    assert reference["expert_agreement"] > 0.8 and reference["router_choice_agreement"] > 0.99
    assert record["moe"]["steps_counted"] == result["attempted"]
    assert len(record["memory_peak_bytes_by_chip"]) == 4
    assert len(record["moe"]["load_max_over_mean_by_layer"]) == 2
    assert record["losses"][-1] < record["warm_losses"][0]
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["window_compiles"] == 0 and summary["moe"]["steps_counted"] > 0


def test_every_limit_on_one_expert_layer_fails_the_precision_below(tiny_manifest):
    """``tests/perf/olmoe_precision_probe.py`` at the toy size: the system inside every limit
    of ``olmoe_tolerances.json``; the reference's own router in bfloat16 outside the
    router's, its expert weights through float8 outside the layer's and the gradients'."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("olmoe_precision_probe", os.path.join(
        os.path.dirname(BENCH_DIR), "tests", "perf", "olmoe_precision_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    line, = module.probe(tiny_manifest, "tiny-olmoe", "tiny_docs", [SEED])
    tol = line["system"]["tolerances"]
    assert line["system"]["ok"] is True
    assert line["bf16_router"]["router_logits_rel"] > tol["router_logits_rel"]
    for name in ("expert_layer_rel", "expert_layer_grad_rel"):
        assert line["fp8_expert_weights"][name] > tol[name] > line["bf16_expert_weights"][name]


def test_the_record_has_what_the_readers_that_exist_know(tiny_manifest, cpu_peaks, tmp_path):
    ctx_record = {}

    def keep(metric):
        reader = Manifest.reader(tiny_manifest, metric)

        def read(record):
            ctx_record.update(record)
            return reader(record)
        return read

    grown = Manifest(bench_dir=tiny_manifest.bench_dir)
    grown.reader = keep
    run.run_cell("tiny_moe", 7, 0.3, True, manifest=grown, allow_cpu=True,
                 out_dir=str(tmp_path / "out"))
    assert {"kind", "chips", "batch_per_chip", "seq_len", "tokens_per_s_chip", "model", "vocab",
            "steps", "step_interval_ms", "dispatch_ms", "moe"} <= set(ctx_record)
    assert ctx_record["kind"] == "train" and ctx_record["chips"] == 4
    model = ctx_record["model"]
    assert (model["n_embd"], model["n_layer"], model["n_head"]) == (64, 2, 4)


# ------------------------------------------------------------ the new readers
@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_returns_nothing_on_nothing(name, monkeypatch):
    reader = Manifest().reader(name)
    assert reader({"setup": {}, "trace": None}) is None
    monkeypatch.setattr(ps, "program_recorder", lambda: None)
    assert reader({"setup": {}, "trace": None, "t_window_start": 5.0, "window_s": 2.0,
                   "kind": "train"}) is None
    # GPT-2's record: no expert in the model, no ds_moe scope in the program
    assert reader({"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0,
                   "model": {"n_embd": 32, "n_layer": 2, "n_head": 2}, "vocab": 256,
                   "seq_len": 64, "device_kind": "TPU v5 lite"}) is None


@pytest.fixture
def recorded_moe(monkeypatch):
    """The slice recorded on the chip (GPT-2 XL, PR 24) with its MLP's scope paths renamed
    as an expert layer's would be: the matmuls under ``ds_moe_experts``, every other MLP
    operation under ``ds_moe_combine``."""
    with open(os.path.join(BENCH_DIR, "testdata", "spans_train_slice.json")) as f:
        doc = json.load(f)
    for info in doc["catalog"].values():
        for name, path in info["ops"].items():
            if "ds_mlp" in path:
                scope = "ds_moe_experts" if "dot_general" in path else "ds_moe_combine"
                info["ops"][name] = path.replace("ds_mlp", "ds_mlp/" + scope, 1)
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    model = dict(TINY_OLMOE, hidden_size=1600, intermediate_size=800, num_hidden_layers=20,
                 n_embd=1600, n_layer=20, n_head=25)
    return dict(doc["record"], trace=tr.Reduced(doc["trace"]), setup={}, model=model,
                vocab=256, moe={"load_max_over_mean": 1.5})


def test_every_new_reader_reads_a_recorded_slice(recorded_moe, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, recorded_moe["device_kind"], dict(peaks.PEAKS["TPU v5 lite"]))
    manifest = Manifest()
    values = {name: manifest.reader(name)(recorded_moe) for name in NEW_READERS}
    assert all(v is not None and np.isfinite(v) for v in values.values()), values
    table = moe_spans.analyse(recorded_moe)
    assert set(table["scope_s"]) == {"ds_moe_experts", "ds_moe_combine"}
    # the renamed operations are the MLP's: the same seconds the phase x part table has
    mlp_s = sum(v for phase, part, _, v in ps.analyse(recorded_moe)["trace"]["device_s"]
                if part == "ds_mlp")
    assert sum(table["scope_s"].values()) == pytest.approx(mlp_s, rel=0.02)
    assert 0 < values["moe_time_share"] < 100 and values["expert_matmul_roofline"] > 0
    assert values["moe_exchange_share"] == 0.0          # one chip: no collective in the slice
    assert values["moe_load_max_over_mean"] == 1.5


# ------------------------------------------------------------------ hand counts
def test_flops_moe_against_hand_counts():
    with open(os.path.join(BENCH_DIR, "configs", "olmoe-1b-7b-d4.json")) as f:
        model = json.load(f)["model"]
    # a layer: attention 4 * 2048^2 = 16,777,216; router 2048 * 64 = 131,072; eight experts
    # of 3 * 2048 * 1024 = 6,291,456; the head 50304 * 2048 = 103,022,592
    per_layer = 16_777_216 + 131_072 + 8 * 6_291_456
    assert flops_moe.matmul_params(model, 50304) == 4 * per_layer + 103_022_592
    assert flops_moe.attention_flops_per_token_fwd(model, 4096) == 4 * 2 * 4096 * 2048
    fwd = 2 * flops_moe.matmul_params(model, 50304) + flops_moe.attention_flops_per_token_fwd(model, 4096)
    assert round(fwd / 1e6) == 811                      # the issue's 811 MFLOP a token
    assert round(2 * 4 * 8 * 6_291_456 / 1e6) == 403    # of which the experts
    assert flops_moe.train_flops_per_token(model, 50304, 4096) == \
        6 * flops_moe.matmul_params(model, 50304) + 3 * 4 * 2 * 4096 * 2048
    # every expert of every layer, two embeddings, the norms: 4 x 419.6 M + 206 M
    assert round(flops_moe.param_count(model, 50304) / 1e6) == 1884
    flops, nbytes = flops_moe.expert_matmul_required(model, 8192, training=False)
    assert flops == 4 * 2 * 65536 * 3 * 2048 * 1024
    rows_bytes = 65536 * (2048 + 2048 + 1024 + 2048) * 2
    assert nbytes == 4 * (rows_bytes + 64 * 3 * 2048 * 1024 * 2)
    assert flops_moe.expert_matmul_required(model, 8192) == (3 * flops, 3 * nbytes)
    assert not flops_moe.is_expert_model({"n_embd": 1600}) and flops_moe.is_expert_model(model)
