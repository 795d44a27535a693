"""``lfm2_ep8_d7_train_1chip``'s runner end to end on the CPU at a toy size (whole layers recomputed,
the held experts standing in, the convolution's kernels interpreted), its record, its three new
readers on nothing and on a recorded trace slice, ``flops_conv_moe.py`` against the issue's counts,
and the probe's faults: each read above the system by the limit that has to catch it.

The shape asserts look entries up BY NAME and assert a prefix and a subset, so that the next PR's
appended cell breaks nothing here; nothing asserts on the wall clock, and nothing that a toy's loss
falls within a handful of steps."""

import json
import os

import numpy as np
import pytest

from benchmarks import conv_spans, flops, flops_conv_moe, peaks, run
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest, check

import tiny
from test_program_spans import Recorded

CELL = "lfm2_ep8_d7_train_1chip"
CONFIG = "lfm2-24b-a2b-ep8-d7"
NEW_READERS = ["mfu.conv_moe", "short_conv_time_share", "short_conv_roofline"]
JOINED = ["moe_time_share", "moe_load_max_over_mean", "moe_rows_here_share", "flash_fwd_roofline",
          "flash_bwd_roofline"]
NOT_JOINED = ["recompute_time_share", "expert_matmul_roofline", "held_expert_matmul_roofline", "mfu", "mfu.moe",
              "mfu.hybrid", "mfu.ssm", "mfu.loop", "mfu.ssm_moe", "mfu.swa_moe", "mfu.mla_moe", "flash_time_share",
              "flash_roofline", "flash_band_fwd_roofline", "window_attn_time_share", "latent_attn_time_share",
              "mtp_time_share", "ssm_time_share", "lin_attn_time_share"]
OLDER_CELLS = ["xl_d20_train_1chip", "olmoe_d4_train_4chip", "qwen3next_ep16_train_1chip",
               "granite4h_d10_train_1chip", "ouro_d6_train_1chip", "nemotronh_ep16_d9_train_1chip",
               "mellum2_ep4_d4_train_1chip", "glm47flash_ep8_d5_train_1chip"]
LIMITS = {"train_loss_rel", "last_logits_rel", "expert_agreement", "expert_wrong_choice_share", "short_conv_rel",
          "short_conv_grad_rel", "attention_rel", "attention_grad_rel", "dense_mlp_rel", "dense_mlp_grad_rel",
          "expert_layer_rel", "expert_layer_grad_rel", "router_grad_rel", "router_scores_rel",
          "router_choice_agreement", "router_wrong_choice_share", "router_bias_grad_abs_max"}
STEP_LIMITS = {"step_loss_rel", "step_update_shortfall", "step_bias_abs_err", "step_bias_moment_abs_max"}
KINDS = ["conv", "full_attention", "conv"]
TINY = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 128, "intermediate_size": 96,
        "layer_types": KINDS, "max_position_embeddings": 1024, "model_type": "lfm2_moe", "moe_intermediate_size": 48,
        "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 4, "num_dense_layers": 1, "num_experts": 4,
        "num_experts_per_tok": 2, "num_hidden_layers": 3, "num_key_value_heads": 2,
        "rope_parameters": {"rope_theta": 10000, "rope_type": "default"}, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 256, "router_width": 16, "first_expert": 4, "stand_in": True}
SEED = 2 ** 31 + 5201


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The tiny root of ``tiny.py`` plus a toy LFM2 configuration and its one-device cell, added by
    files and entries alone."""
    root = tiny.make_root(tmp_path_factory.mktemp("cellbench_conv_moe"))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    tiny._dump(os.path.join(bench, "configs", "tiny-conv-moe.json"), dict(
        TINY, name="tiny-conv-moe", source="tests/cellbench/test_rehearsal_conv_moe.py",
        runner="train_conv_moe", reduced={}, model=TINY, remat=True,
        assumed={"initializer_range": [None, 0.1, "toy"], "bias_update_rate": [None, 0.001, "toy"],
                 "router_eps": [None, 1e-6, "toy"]},
        compute_dtype="bfloat16", engine=dict(tiny.ENGINE, zero_optimization={"stage": 2}),
        reference={"module": "lfm2_moe_reference", "tolerances": "tiny_conv_moe_tolerances",
                   "last_positions": 16, "grad_positions": 32, "tie_margin": 1e-4,
                   "tie_margin_whole_model": 0.05}))
    # toy widths in bf16 sit further from the float32 reference than 2048-wide sums do, and a toy
    # expert that few rows reach has gradients near Adam's epsilon
    with open(os.path.join(bench, "reference", "lfm2_moe_tolerances.json")) as f:
        limits = json.load(f)
    loose = {k: dict(v, value=max(v["value"], 0.25 if "grad" in k or "logits" in k else 0.1))
             for k, v in limits.items()}
    for exact in ("router_wrong_choice_share", "router_scores_rel", "router_bias_grad_abs_max",
                  "step_bias_abs_err", "step_bias_moment_abs_max"):
        loose[exact] = limits[exact]
    loose["expert_agreement"]["value"], loose["router_choice_agreement"]["value"] = 0.3, 0.99
    loose["step_update_shortfall"]["value"] = 0.6
    tiny._dump(os.path.join(bench, "reference", "tiny_conv_moe_tolerances.json"), loose)
    doc["configs"].append({"name": "tiny-conv-moe", "source": "tests/cellbench/test_rehearsal_conv_moe.py",
                           "file": "benchmarks/configs/tiny-conv-moe.json", "reduced": [],
                           "why": "toy sizes for the CPU rehearsal"})
    tiny._dump(os.path.join(bench, "cells", "tiny_conv_moe.json"), dict(
        name="tiny_conv_moe", config="tiny-conv-moe", traffic="tiny_docs", chips=1,
        micro_batch_per_chip=2, warm_steps=2, trace_seconds=1, why="toy cell"))
    doc["workloads"].append({"name": "tiny_conv_moe", "config": "tiny-conv-moe", "traffic": "tiny_docs",
                             "chips": 1, "why": "toy short-convolution expert cell for the CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_conv_moe")
    tiny._dump(os.path.join(root, "BENCHMARK.json"), doc)
    return Manifest(bench_dir=bench)


@pytest.fixture(scope="module")
def cell_run(tiny_manifest, tmp_path_factory):
    """``cell_run(trace) -> (result, the run's record file, what the readers were handed)``: the
    toy cell run once without and once with a trace, whichever test asks first."""
    peaks.PEAKS["cpu"] = dict(peaks.PEAKS["TPU v5 lite"])
    runs = {}

    def run_once(trace):
        if trace not in runs:
            handed = {}

            def keep(metric):
                reader = Manifest.reader(tiny_manifest, metric)

                def read(record):
                    handed.update(record)
                    return reader(record)
                return read

            grown = Manifest(bench_dir=tiny_manifest.bench_dir)
            grown.reader = keep
            out_dir = str(tmp_path_factory.mktemp("out"))
            result = run.run_cell("tiny_conv_moe", SEED, 0.5, bool(trace), manifest=grown,
                                  allow_cpu=True, out_dir=out_dir)
            with open(os.path.join(out_dir, f"tiny_conv_moe.{SEED}.steps.json")) as f:
                runs[trace] = json.loads(json.dumps(result)), json.load(f), handed
        return runs[trace]
    yield run_once
    del peaks.PEAKS["cpu"]


# ------------------------------------------------------------------ the contract
def test_the_cell_and_its_entries_hold_to_the_contract():
    manifest = Manifest()
    assert check(manifest) == []
    cell, config = manifest.cell(CELL), manifest.config(CONFIG)
    assert cell["chips"] == 1 and cell["micro_batch_per_chip"] == 1 and cell["warm_steps"] == 8
    assert cell["trace_seconds"] == 12 and len(cell["why"]) <= 200
    traffic = manifest.traffic(cell["traffic"])
    assert traffic["seq_len"] == 8192 and traffic["eot_token"] == config["vocab_size"] - 1 == 8191
    older = manifest.traffic("packed_docs_8k_v19360")
    assert {k: v for k, v in traffic.items() if k not in ("name", "why", "eot_token")} == \
        {k: v for k, v in older.items() if k not in ("name", "why", "eot_token")}
    # the published keys stand at the top level, as the catalog has them, and again as the
    # group the runner reads, which adds the share
    model = config["model"]
    share = ("router_width", "first_expert", "stand_in")
    assert {k: config[k] for k in model if k not in share} == {k: v for k, v in model.items() if k not in share}
    assert (model["router_width"], model["first_expert"], model["num_experts"]) == (64, 0, 8)
    assert model["stand_in"] is True and "STAND IN" in config["deployment"] and "EIGHT" in config["deployment"]
    assert set(config["reduced"]) == {"num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
                                      "vocab_size"}
    assert config["reduced"]["num_hidden_layers"] == [40, 7] and config["reduced"]["num_dense_layers"] == [2, 1]
    assert config["reduced"]["num_experts"] == [64, 8] and config["reduced"]["vocab_size"] == [65536, 8192]
    published = config["published"]["layer_types"]
    assert len(published) == 40 and published.count("conv") == 30 and published[2::4] == ["full_attention"] * 10
    assert config["layer_types"] == published[1:8] == ["conv", "full_attention", "conv", "conv", "conv",
                                                       "full_attention", "conv"]
    # no width, head count, router width, experts a token or tap is cut
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
            config["intermediate_size"], config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["conv_L_cache"]) == (2048, 32, 8, 11776, 1536, 4, 3)
    assert (config["routed_scaling_factor"], config["rope_parameters"], config["norm_eps"], config["conv_bias"],
            config["use_expert_bias"], config["norm_topk_prob"]) == \
        (1, {"rope_theta": 1000000, "rope_type": "default"}, 1e-05, False, True, True)
    assert config["remat"] is True and config["engine"]["optimizer"]["params"] == {"lr": 1e-05}
    assert "scheduler" not in config["engine"] and config["engine"]["zero_optimization"] == {"stage": 2}
    assert {"tie_embedding", "router_eps", "bias_update_rate", "router_bias_init", "projection_order",
            "rotary_pairing", "initializer_range", "eos_token_id", "dropout"} <= set(config["assumed"])
    assert all(len(v) == 3 and len(v[2]) > 10 for v in config["assumed"].values())
    assert config["assumed"]["router_eps"][1] == 1e-6 and config["assumed"]["eos_token_id"][1] == 8191
    assert any("packed documents" in d and "convolution" in d for d in config["departures"])
    assert any("token exchange" in d for d in config["departures"])
    # the builder's own count, stated in the file
    assert flops_conv_moe.param_count(model, config["vocab_size"]) == 647_819_904
    assert "647,819,904" in config["why_reduced"] and "10.37 GB" in config["why_reduced"]
    reported = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert set(NEW_READERS) | set(JOINED) <= reported and not set(NOT_JOINED) & reported
    layer = {"mfu.conv_moe": "model step", "short_conv_time_share": "model step", "short_conv_roofline": "kernels"}
    for m in manifest.doc["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"][0] == CELL and m["moves"] == "train_tokens_per_s_chip"
            assert m["unit"] == "%" and m["layer"] == layer[m["name"]]
    with open(os.path.join(BENCH_DIR, "reference", "lfm2_moe_tolerances.json")) as f:
        limits = json.load(f)
    assert set(limits) == LIMITS | STEP_LIMITS
    assert all(v["value"] >= 0 and len(v["why"]) > 100 for v in limits.values())
    assert limits["router_bias_grad_abs_max"]["value"] == limits["step_bias_moment_abs_max"]["value"] == 0.0


def test_the_benchmark_grew_by_appended_entries_alone():
    """Entries are looked up BY NAME: the older lists are a PREFIX of the new ones and the new
    entries follow them. Nothing here asserts that these entries are the last of all."""
    doc = Manifest().doc
    names = lambda section: [e["name"] for e in doc[section]]       # noqa: E731
    assert names("workloads")[:8] == OLDER_CELLS
    assert names("configs").index(CONFIG) == 8 and names("workloads").index(CELL) == 8
    at = names("per_layer").index("update_program_roofline")
    assert names("per_layer")[at + 1:at + 4] == NEW_READERS
    for m in doc["end_to_end"] + doc["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells and m["name"] not in NEW_READERS:
            before = cells[:cells.index(CELL)]
            assert before == [c for c in OLDER_CELLS if c in before] and before, m["name"]
            assert len(before) == 8 or m["name"] in JOINED, m["name"]
    assert names("end_to_end")[:2] == ["train_tokens_per_s_chip", "setup_s"]
    assert doc["run_seconds"] == 40 and doc["end_to_end"][0]["bound"] == 0.01
    assert doc["paths"] == ["benchmarks", "tests/cellbench"]
    assert sum(w["chips"] == 4 for w in doc["workloads"][:9]) == 1


def test_the_older_flash_readers_are_handed_what_they_know():
    """``flops.flash_required`` under ``flash_sizes`` counts exactly this model's kernel calls: two
    whole triangles at 32 heads of 64 (keys and values priced at the query heads' width)."""
    model = Manifest().config(CONFIG)["model"]
    sizes = flops_conv_moe.flash_sizes(model)
    assert sizes == {"n_embd": 2048, "n_layer": 2, "n_head": 32}
    fwd_flops, fwd_bytes = flops.flash_required(sizes, 1, 8192, training=False)
    assert fwd_flops == 2 * 4 * (8192 * 8192 // 2) * 32 * 64
    assert fwd_bytes == 2 * 4 * 8192 * 32 * 64 * 2
    parts = flops_conv_moe.forward_flops_by_part(model, 8192, 8192, 4)
    assert abs(parts["attention"] / fwd_flops - 1) < 2e-4          # the diagonal's half pairs apart


# ------------------------------------------------------------------ the cell, toy size
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end(tiny_manifest, cell_run, trace):
    result, record, _ = cell_run(trace)
    reference = record["reference"]
    assert reference["ok"] is True, reference
    # ``correct`` holds the step's check, the losses and that nothing compiled in the window too
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in tiny_manifest.metrics_of(section, "tiny_conv_moe")}
    assert set(result["metrics"]) <= set(declared)
    if trace:
        # the host-clock and counter metrics are there; the device-trace ones find no device plane
        assert {"mfu.conv_moe", "moe_load_max_over_mean", "moe_rows_here_share", "setup_compile_s",
                "step_program_variants"} <= set(result["metrics"])
        assert not {"short_conv_time_share", "short_conv_roofline", "moe_time_share", "flash_fwd_roofline",
                    "flash_bwd_roofline"} & set(result["metrics"])
        assert result["metrics"]["moe_rows_here_share"]["value"] == 100.0     # the held experts stand in
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert set(reference["tolerances"]) == LIMITS <= set(reference)
    assert reference["router_bias_grad_abs_max"] == 0.0 and reference["router_wrong_choice_share"] == 0.0
    # the process's first step is the engine's own, layers recomputed, on the reference's sequence;
    # the rule moved the biases of both expert layers by u one way or the other, and no moment
    step = reference["step"]
    assert step["ok"] is True and set(step["tolerances"]) == STEP_LIMITS
    assert step["step_bias_abs_err"] <= 1e-7 and step["step_bias_moment_abs_max"] == 0.0
    assert step["biases_moved"] > 16 and step["biases_sure"] + step["biases_near_the_mean"] == 2 * 16
    assert record["warm_losses"][0] == pytest.approx(step["reference_loss"], rel=step["step_loss_rel"] + 1e-6)
    moe = record["moe"]
    assert moe["steps_counted"] == result["attempted"] and moe["rows_here_by_layer"] == [2 * 64 * 2.0] * 2
    assert 0 < moe["bias_abs_max"] <= 1e-3 * (result["attempted"] + len(record["warm_losses"]) + 1)


def test_the_record_has_what_the_readers_know(cell_run):
    _, _, handed = cell_run(1)
    assert {"kind", "chips", "batch_per_chip", "seq_len", "tokens_per_s_chip", "vocab", "steps", "model",
            "step_interval_ms", "dispatch_ms", "conv_moe_model", "recomputed", "moe"} <= set(handed)
    assert handed["kind"] == "train" and handed["chips"] == 1 and handed["recomputed"] is True
    assert {"rows_here_share", "rows_here_per_token", "rows_here_by_layer", "load_max_over_mean"} <= set(handed["moe"])
    assert flops_conv_moe.is_conv_moe_model(handed["conv_moe_model"])
    assert handed["model"] == {"n_embd": 128, "n_layer": 1, "n_head": 4}


# ------------------------------------------------------------ the new readers
@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_returns_nothing_on_nothing(name, monkeypatch):
    reader = Manifest().reader(name)
    assert reader({"setup": {}, "trace": None}) is None
    monkeypatch.setattr(ps, "program_recorder", lambda: None)
    assert reader({"setup": {}, "trace": None, "t_window_start": 5.0, "window_s": 2.0,
                   "kind": "train"}) is None
    # GLM-4.7-Flash's record: held experts, flash kernels, and no such model
    assert reader({"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0,
                   "mla_moe_model": {"kv_lora_rank": 512, "n_routed_experts": 8},
                   "moe": {"rows_here_per_token": 4.0, "rows_here_by_layer": [10.0]},
                   "vocab": 256, "seq_len": 64, "device_kind": "TPU v5 lite"}) is None
    # this cell's record with no trace, whose counters never came
    model = Manifest().config(CONFIG)["model"]
    no_rows = {"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0, "conv_moe_model": model,
               "moe": {"rows_here_per_token": None, "rows_here_by_layer": None}, "vocab": 8192,
               "seq_len": 8192, "batch_per_chip": 1, "recomputed": True, "device_kind": "TPU v5 lite"}
    assert reader(no_rows) is None


@pytest.fixture
def recorded_conv(monkeypatch):
    """The slice recorded on the chip (GPT-2 XL, PR 24) with its scope paths renamed as this model's
    would be: everything under ``ds_attn`` under ``ds_short_conv`` inside it, and every third of
    those operations under ``ds_short_conv_gate`` besides."""
    with open(os.path.join(BENCH_DIR, "testdata", "spans_train_slice.json")) as f:
        doc = json.load(f)
    for info in doc["catalog"].values():
        for name, path in info["ops"].items():
            if "ds_attn" in path:
                inner = "ds_attn/ds_short_conv/ds_short_conv_gate" if len(name) % 3 == 0 else "ds_attn/ds_short_conv"
                info["ops"][name] = path.replace("ds_attn", inner, 1)
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    monkeypatch.setattr(conv_spans, "OUT_NAME", "conv_spans.test.json")
    model = Manifest().config(CONFIG)["model"]
    return dict(doc["record"], trace=tr.Reduced(doc["trace"]), setup={}, conv_moe_model=model, vocab=8192,
                recomputed=True, moe={"rows_here_per_token": 4.0, "rows_here_by_layer": [32768.0] * 6})


def test_every_new_reader_reads_a_recorded_slice(recorded_conv, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, recorded_conv["device_kind"], dict(peaks.PEAKS["TPU v5 lite"]))
    manifest = Manifest()
    values = {name: manifest.reader(name)(recorded_conv) for name in NEW_READERS}
    assert all(v is not None and np.isfinite(v) and v > 0 for v in values.values()), values
    table = conv_spans.analyse(recorded_conv)
    assert set(table["scope_s"]) == {"ds_short_conv", "ds_short_conv_gate"}
    # the gate's operations lie under the operator's scope too
    assert 0 < table["scope_s"]["ds_short_conv_gate"] < table["scope_s"]["ds_short_conv"]
    assert 0 < values["short_conv_time_share"] < 100
    assert values["short_conv_time_share"] == pytest.approx(
        100 * table["scope_s"]["ds_short_conv"] / table["window_s"])
    # the roofline: the step's least bytes at the chip's bandwidth, over the seconds under the gate
    tokens = recorded_conv["batch_per_chip"] * recorded_conv["seq_len"]
    steps = table["window_s"] * recorded_conv["tokens_per_s_chip"] / tokens
    _, nbytes = flops_conv_moe.short_conv_gate_required(recorded_conv["conv_moe_model"], tokens, True)
    assert values["short_conv_roofline"] == pytest.approx(
        100 * nbytes * steps / 819e9 / table["scope_s"]["ds_short_conv_gate"])
    faster = dict(recorded_conv, tokens_per_s_chip=2 * recorded_conv["tokens_per_s_chip"])
    assert manifest.reader("mfu.conv_moe")(faster) == pytest.approx(2 * values["mfu.conv_moe"])
    os.remove(os.path.join(BENCH_DIR, "out", "conv_spans.test.json"))


# ------------------------------------------------------------------ the issue's counts
def test_the_pricing_functions_against_hand_counts():
    model = Manifest().config(CONFIG)["model"]
    assert flops_conv_moe.layers(model) == (5, 2, 1, 6) and flops_conv_moe.head_dim(model) == 64
    assert flops_conv_moe.short_conv_params(model) == 3 * 2048 * 2048 + 2048 * 2048 + 3 * 2048 == 16_783_360
    assert flops_conv_moe.attention_params(model) == 2 * 2048 * 2048 + 2 * 2048 * 512 + 128 == 10_485_888
    assert flops_conv_moe.dense_mlp_params(model) == 72_351_744 and flops_conv_moe.expert_params(model) == 9_437_184
    assert flops_conv_moe.router_params(model) == 131_072
    assert [flops_conv_moe.layer_params(model, l) for l in range(7)] == [
        89_139_200, 86_118_592, 92_416_064, 92_416_064, 92_416_064, 86_118_592, 92_416_064]
    assert flops_conv_moe.param_count(model, 8192) == 647_819_904
    assert round(flops_conv_moe.param_count(model, 8192) * 16 / 1e7) == 1037       # 10.37 GB of state
    parts = flops_conv_moe.forward_flops_by_part(model, 8192, 8192, 4)
    tera = {k: round(v / 1e10) / 100 for k, v in parts.items()}                    # TF a step of 8,192 tokens
    assert tera == {"short_conv_projections": 1.37, "short_conv_gates": 0.0, "attention_projections": 0.34,
                    "attention": 0.55, "dense_mlp": 1.19, "routers": 0.01, "experts": 3.71, "head": 0.27}
    assert round(sum(parts.values()) / 1e10) == 745                                # the issue's 7.45 TF forward
    assert round(100 * parts["experts"] / sum(parts.values()), 1) == 49.8
    per_token = flops_conv_moe.train_flops_per_token(model, 8192, 8192, 4)
    assert per_token == 3 * sum(parts.values()) / 8192 and round(per_token * 8192 / 1e11) == 224   # 22.4 TF a step
    # fewer rows computed here, fewer operations: never k
    fewer = flops_conv_moe.forward_flops_by_part(model, 8192, 8192, 1)
    assert fewer["experts"] * 4 == parts["experts"] and fewer["dense_mlp"] == parts["dense_mlp"]
    # the gated convolution between the products, by hand: five layers of 8,192 x 2,048 bf16 arrays,
    # 4 a forward (3 read, 1 written), 7 the backward (3 + dy read, 3 written); two forwards recomputed
    array = 8192 * 2048 * 2
    ops, nbytes = flops_conv_moe.short_conv_gate_required(model, 8192, recomputed=True)
    assert nbytes == 5 * (2 * 4 + 7) * array == 2_516_582_400
    assert flops_conv_moe.short_conv_gate_required(model, 8192, recomputed=False)[1] == 5 * (4 + 7) * array
    assert ops == 5 * 4 * 8192 * 2048 * (2 + 2 * 3)
    assert flops.roofline_seconds(ops, nbytes, peaks.PEAKS["TPU v5 lite"]) == (nbytes / 819e9, "memory")


# ------------------------------------------------------------------ the limits' second readings
def test_the_probe_reads_every_fault_above_the_system(tiny_manifest):
    """``tests/perf/lfm2_precision_probe.py`` at the toy size: the system inside every limit, and the
    reference itself at fault in the system's place outside the limit that has to catch it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("lfm2_precision_probe", os.path.join(
        os.path.dirname(BENCH_DIR), "tests", "perf", "lfm2_precision_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    line, = module.probe(tiny_manifest, "tiny-conv-moe", "tiny_docs", [SEED], whole_model=False)
    system = line["system"]
    assert system["ok"] is True
    for fault in ("taps_reversed", "window_a_token_ahead", "parts_in_another_order", "first_gate_left_out",
                  "silu_after_the_taps"):
        assert line[fault]["short_conv_rel"] > 5 * system["short_conv_rel"], (fault, line[fault])
        assert line[fault]["short_conv_grad_rel"] > 5 * system["short_conv_grad_rel"], (fault, line[fault])
    assert line["head_norms_skipped"]["attention_rel"] > 3 * system["attention_rel"], line["head_norms_skipped"]
    # bfloat16 holds the toy's 64 positions exactly: the angles' fault shows from position 257 on (the chip's readings)
    assert np.isfinite(line["bf16_rotary_angles"]["attention_rel"])
    assert line["bf16_router"]["router_scores_rel"] > 1e-4 > 10 * system["router_scores_rel"]
    for name in ("expert_layer_rel", "expert_layer_grad_rel", "router_grad_rel"):
        assert line["weights_not_renormalised"][name] > 3 * system[name], (name, line["weights_not_renormalised"])
    assert line["weights_not_renormalised"]["router_choice_agreement"] == 1.0      # the choice is the same
    for name in ("dense_mlp_rel", "dense_mlp_grad_rel"):
        assert line["activation_on_the_other_half"][name] > 5 * system[name], line["activation_on_the_other_half"]
    assert line["head_untied"]["last_logits_rel"] > 3 * system["last_logits_rel"]
    assert line["head_untied"]["train_loss_rel"] > 10 * system["train_loss_rel"]
