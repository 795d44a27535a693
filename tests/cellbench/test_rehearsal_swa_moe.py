"""``mellum2_ep4_d4_train_1chip``'s runner end to end on the CPU at a toy size (whole layers
recomputed, sixteen-position windows, the held experts standing in), its record, its five new
readers on nothing and on a recorded trace slice, the kernel's edge probe under the windows it
has to tell apart, the probe's faults, and ``flops_swa_moe.py`` against the issue's counts.

The shape asserts look entries up BY NAME and assert a prefix and a subset, so that the next
PR's appended cell breaks nothing here; nothing asserts on the wall clock."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmarks import flops_swa_moe, peaks, run, swa_spans
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest, check
from benchmarks.reference import mellum_reference as ref

import tiny
from test_program_spans import Recorded

CELL = "mellum2_ep4_d4_train_1chip"
CONFIG = "mellum2-12b-a2.5b-ep4-d4"
NEW_READERS = ["mfu.swa_moe", "window_attn_time_share", "flash_band_fwd_roofline",
               "flash_band_bwd_roofline", "flash_band_visited_over_needed"]
JOINED = ["moe_time_share", "moe_load_max_over_mean", "moe_rows_here_share"]
NOT_JOINED = ["flash_fwd_roofline", "flash_bwd_roofline", "flash_time_share", "flash_roofline",
              "recompute_time_share", "mfu", "mfu.moe", "mfu.hybrid", "mfu.ssm", "mfu.loop", "mfu.ssm_moe",
              "expert_matmul_roofline", "held_expert_matmul_roofline"]
OLDER_CELLS = ["xl_d20_train_1chip", "olmoe_d4_train_4chip", "qwen3next_ep16_train_1chip",
               "granite4h_d10_train_1chip", "ouro_d6_train_1chip", "nemotronh_ep16_d9_train_1chip"]
LIMITS = {"train_loss_rel", "last_logits_rel", "expert_agreement", "expert_wrong_choice_share",
          "window_attention_rel", "window_attention_grad_rel", "full_attention_rel", "full_attention_grad_rel",
          "expert_layer_rel", "expert_layer_grad_rel", "router_probs_rel", "router_choice_agreement",
          "router_wrong_choice_share", "edge_probe_abs_err", "rotary_table_rel"}
STEP_LIMITS = {"step_loss_rel", "step_update_shortfall"}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
KINDS = ["sliding_attention", "full_attention"]        # the toy's two layers: one of each kind
TINY = {"attention_bias": False, "head_dim": 16, "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 64,
        "layer_types": KINDS * 4, "mlp_layer_types": ["sparse"] * 8, "max_position_embeddings": 1024,
        "max_window_layers": 0, "model_type": "mellum", "moe_intermediate_size": 24, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 2,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "sliding_window": 16, "tie_word_embeddings": False,
        "vocab_size": 256, "use_sliding_window": True, "router_width": 16, "first_expert": 4, "stand_in": True,
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                               "original_max_position_embeddings": 32, "beta_fast": 2, "beta_slow": 0.5,
                               "attention_factor": 1.1386},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000}}}
SEED = 2 ** 31 + 4321


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The tiny root of ``tiny.py`` plus a toy Mellum configuration and its one-device cell,
    added by files and entries alone."""
    root = tiny.make_root(tmp_path_factory.mktemp("cellbench_swa_moe"))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    tiny._dump(os.path.join(bench, "configs", "tiny-swa-moe.json"), dict(
        TINY, name="tiny-swa-moe", source="tests/cellbench/test_rehearsal_swa_moe.py",
        runner="train_swa_moe", reduced={}, model=TINY, remat=True,
        assumed={"initializer_range": [None, 0.1, "toy"], "router_aux_loss_coef": [None, 0.001, "toy"]},
        compute_dtype="bfloat16", engine=dict(tiny.ENGINE, zero_optimization={"stage": 2}),
        reference={"module": "mellum_reference", "tolerances": "tiny_swa_moe_tolerances",
                   "last_positions": 16, "grad_positions": 32, "tie_margin": 1e-6,
                   "tie_margin_whole_model": 0.02}))
    # toy widths in bf16 sit further from the float32 reference than 2304-wide sums do, and a
    # toy expert that few rows reach has gradients near Adam's epsilon
    with open(os.path.join(bench, "reference", "mellum_tolerances.json")) as f:
        limits = json.load(f)
    loose = {k: dict(v, value=max(v["value"], 0.25 if "grad" in k or "logits" in k else 0.1))
             for k, v in limits.items()}
    for exact in ("router_wrong_choice_share", "router_probs_rel", "edge_probe_abs_err", "rotary_table_rel"):
        loose[exact] = limits[exact]
    loose["expert_agreement"]["value"], loose["router_choice_agreement"]["value"] = 0.3, 0.99
    loose["step_update_shortfall"]["value"] = 0.5
    tiny._dump(os.path.join(bench, "reference", "tiny_swa_moe_tolerances.json"), loose)
    doc["configs"].append({"name": "tiny-swa-moe", "source": "tests/cellbench/test_rehearsal_swa_moe.py",
                           "file": "benchmarks/configs/tiny-swa-moe.json", "reduced": [],
                           "why": "toy sizes for the CPU rehearsal"})
    tiny._dump(os.path.join(bench, "cells", "tiny_swa_moe.json"), dict(
        name="tiny_swa_moe", config="tiny-swa-moe", traffic="tiny_docs", chips=1,
        micro_batch_per_chip=2, warm_steps=2, trace_seconds=1, why="toy cell"))
    doc["workloads"].append({"name": "tiny_swa_moe", "config": "tiny-swa-moe", "traffic": "tiny_docs",
                             "chips": 1, "why": "toy sliding-window expert cell for the CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_swa_moe")
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
            result = run.run_cell("tiny_swa_moe", SEED, 0.5, bool(trace), manifest=grown,
                                  allow_cpu=True, out_dir=out_dir)
            with open(os.path.join(out_dir, f"tiny_swa_moe.{SEED}.steps.json")) as f:
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
    assert traffic["seq_len"] == 8192 and traffic["eot_token"] == config["vocab_size"] - 1 == 24575
    older = manifest.traffic("packed_docs_8k_v16384")
    assert {k: v for k, v in traffic.items() if k not in ("name", "why", "eot_token")} == \
        {k: v for k, v in older.items() if k not in ("name", "why", "eot_token")}
    # the published keys stand at the top level, as the catalog has them, and again as the
    # group the runner reads, which adds the share
    model = config["model"]
    share = ("router_width", "first_expert", "stand_in")
    assert {k: config[k] for k in model if k not in share} == {k: v for k, v in model.items() if k not in share}
    assert (model["router_width"], model["first_expert"], model["num_experts"]) == (64, 0, 16)
    assert model["stand_in"] is True and "STAND IN" in config["deployment"]
    assert config["reduced"] == {"num_hidden_layers": [28, 4], "num_experts": [64, 16],
                                 "vocab_size": [98304, 24576]}
    assert config["published"]["num_experts"] == 64 and config["published"]["vocab_size"] == 98304
    # both lists stay whole, as published; the model runs the first four layers, one period
    assert len(config["layer_types"]) == len(config["mlp_layer_types"]) == 28
    assert config["layer_types"] == PERIOD * 7 and set(config["mlp_layer_types"]) == {"sparse"}
    assert config["layer_types"][:config["num_hidden_layers"]] == PERIOD
    # no width, head count, window, router width or experts a token is cut
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["moe_intermediate_size"], config["intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"]) == (2304, 32, 4, 128, 896, 7168, 8, 1024)
    yarn = config["rope_parameters"]["full_attention"]
    assert (yarn["rope_type"], yarn["factor"], yarn["original_max_position_embeddings"], yarn["beta_fast"],
            yarn["beta_slow"], yarn["attention_factor"]) == ("yarn", 16, 8192, 32, 1, 1.2772588722239782)
    assert config["rope_parameters"]["sliding_attention"] == {"rope_type": "default", "rope_theta": 500000}
    assert config["remat"] is True and config["engine"]["optimizer"]["params"] == {"lr": 1e-05}
    assert "scheduler" not in config["engine"] and config["engine"]["zero_optimization"] == {"stage": 2}
    assert {"qk_norm", "router_aux_loss_coef", "initializer_range", "yarn_truncate", "eos_token_id",
            "dropout"} <= set(config["assumed"])
    assert all(len(v) == 3 and len(v[2]) > 10 for v in config["assumed"].values())
    assert config["assumed"]["router_aux_loss_coef"][1] == 0.001 and config["assumed"]["eos_token_id"][1] == 24575
    assert any("multi-token-prediction" in d for d in config["departures"])
    assert any("packed documents" in d for d in config["departures"])
    # the builder's own count, stated in the file
    assert flops_swa_moe.param_count(model, config["vocab_size"]) == 595_154_176
    assert "595,154,176" in config["why_reduced"] and "9.52 GB" in config["why_reduced"]
    reported = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert set(NEW_READERS) | set(JOINED) <= reported and not set(NOT_JOINED) & reported
    for m in manifest.doc["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s_chip"
    with open(os.path.join(BENCH_DIR, "reference", "mellum_tolerances.json")) as f:
        limits = json.load(f)
    assert set(limits) == LIMITS | STEP_LIMITS
    assert all(v["value"] >= 0 and len(v["why"]) > 100 for v in limits.values())


def test_the_benchmark_grew_by_appended_entries_alone():
    """Entries are looked up BY NAME: the older lists are a PREFIX of the new ones and the new
    entries follow them. Nothing here asserts that these entries are the last of all."""
    doc = Manifest().doc
    names = lambda section: [e["name"] for e in doc[section]]       # noqa: E731
    assert names("workloads")[:6] == OLDER_CELLS
    assert names("configs").index(CONFIG) == 6 and names("workloads").index(CELL) == 6
    at = names("per_layer").index("held_expert_matmul_roofline")
    assert names("per_layer")[at + 1:at + 6] == NEW_READERS
    for m in doc["end_to_end"] + doc["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells and m["name"] not in NEW_READERS:
            before = cells[:cells.index(CELL)]
            assert before == [c for c in OLDER_CELLS if c in before] and before, m["name"]
            assert len(before) == 6 or m["name"] in JOINED, m["name"]
    assert names("end_to_end")[:2] == ["train_tokens_per_s_chip", "setup_s"]
    assert doc["run_seconds"] == 40 and doc["end_to_end"][0]["bound"] == 0.01
    assert doc["paths"] == ["benchmarks", "tests/cellbench"]
    assert sum(w["chips"] == 4 for w in doc["workloads"][:7]) == 1


# ------------------------------------------------------------------ the cell, toy size
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end(tiny_manifest, cell_run, trace):
    result, record, _ = cell_run(trace)
    reference = record["reference"]
    assert reference["ok"] is True, reference
    # ``correct`` holds the step's check, the losses and that nothing compiled in the window too
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in tiny_manifest.metrics_of(section, "tiny_swa_moe")}
    assert set(result["metrics"]) <= set(declared)
    if trace:
        # the host-clock and counter metrics are there; the device-trace ones find no device plane
        assert {"mfu.swa_moe", "flash_band_visited_over_needed", "moe_load_max_over_mean",
                "moe_rows_here_share", "setup_compile_s", "step_program_variants"} <= set(result["metrics"])
        assert not {"window_attn_time_share", "flash_band_fwd_roofline", "flash_band_bwd_roofline",
                    "moe_time_share"} & set(result["metrics"])
        assert result["metrics"]["moe_rows_here_share"]["value"] == 100.0     # the held experts stand in
        assert result["metrics"]["flash_band_visited_over_needed"]["value"] > 1
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert set(reference["tolerances"]) == LIMITS <= set(reference)
    assert reference["edge_probe_abs_err"] == 0.0 and reference["rotary_table_rel"] < 1e-6
    assert reference["router_wrong_choice_share"] == 0.0
    step = reference["step"]
    assert step["ok"] is True and set(step["tolerances"]) == STEP_LIMITS
    assert record["warm_losses"][0] == pytest.approx(reference["reference_loss"], rel=step["step_loss_rel"] + 1e-6)
    assert np.mean(record["losses"][-10:]) < record["warm_losses"][0]
    moe, band = record["moe"], record["band"]
    assert moe["steps_counted"] == result["attempted"] and moe["rows_here_by_layer"] == [2 * 64 * 2.0] * 2
    assert set(band["tiles"]) == set(KINDS) and band["tiles"]["sliding_attention"]["window"] == 16
    assert band["needed"] == flops_swa_moe.band_pairs_required(64, 16) + flops_swa_moe.band_pairs_required(64)


def test_the_record_has_what_the_readers_know(cell_run):
    _, _, handed = cell_run(1)
    assert {"kind", "chips", "batch_per_chip", "seq_len", "tokens_per_s_chip", "vocab", "steps", "band",
            "step_interval_ms", "dispatch_ms", "swa_moe_model", "moe"} <= set(handed)
    assert handed["kind"] == "train" and handed["chips"] == 1
    assert {"rows_here_share", "rows_here_per_token", "rows_here_by_layer", "load_max_over_mean"} <= set(handed["moe"])
    assert flops_swa_moe.is_swa_moe_model(handed["swa_moe_model"])


# ------------------------------------------------------------------ the edge probe
def test_the_edge_probe_tells_the_window_from_one_key_more_or_less_and_from_none():
    """The kernel (interpreted) at 2,048 positions, one head of 128, under the published window
    of 1,024: exactly 1 / 128 on every lane from position 1,023 on; under 1,023, 1,025 or no
    window one lane is off by about a thousandth, ten times the limit."""
    from benchmarks.runners import train_swa_moe
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    with open(os.path.join(BENCH_DIR, "reference", "mellum_tolerances.json")) as f:
        limit = json.load(f)["edge_probe_abs_err"]["value"]
    import jax.numpy as jnp
    T, D = 2048, 128
    zeros = jnp.zeros((1, 1, T, D), jnp.bfloat16)
    values = ref.edge_probe_values(T, D, 1, jnp.bfloat16)
    assert float(values.sum()) == T and float(values[0, 0, 130, 2]) == 1.0
    readings = {}
    for window in (1024, 1023, 1025, None):
        out = fa.flash_attention(zeros, zeros, values, True, window=window, interpret=True)
        readings[window] = ref.edge_probe_error(np.asarray(out, np.float32), 1024, D)
    assert readings[1024] == 0.0 <= limit
    assert all(readings[w] > 5 * limit for w in (1023, 1025, None)), readings
    # the dense oracle says the same
    dense = fa.dense_attention(zeros, zeros, values, True, window=1024)
    assert ref.edge_probe_error(np.asarray(dense, np.float32), 1024, D) == 0.0
    assert train_swa_moe.KINDS == {"sliding_attention": "window_attention", "full_attention": "full_attention"}


# ------------------------------------------------------------ the new readers
@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_returns_nothing_on_nothing(name, monkeypatch):
    reader = Manifest().reader(name)
    assert reader({"setup": {}, "trace": None}) is None
    monkeypatch.setattr(ps, "program_recorder", lambda: None)
    assert reader({"setup": {}, "trace": None, "t_window_start": 5.0, "window_s": 2.0,
                   "kind": "train"}) is None
    # Nemotron-H's record: held experts, flash kernels, and no such model
    assert reader({"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0,
                   "model": {"n_embd": 32, "n_layer": 1, "n_head": 2},
                   "ssm_moe_model": {"hybrid_override_pattern": "ME", "n_routed_experts": 8},
                   "moe": {"rows_here_per_token": 0.6, "rows_here_by_layer": [10.0]},
                   "vocab": 256, "seq_len": 64, "device_kind": "TPU v5 lite"}) is None
    # this cell's record with no trace, whose counters never came
    model = Manifest().config(CONFIG)["model"]
    no_rows = {"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0, "swa_moe_model": model,
               "moe": {"rows_here_per_token": None, "rows_here_by_layer": None}, "band": {}, "vocab": 24576,
               "seq_len": 8192, "batch_per_chip": 1, "device_kind": "TPU v5 lite"}
    assert reader(no_rows) is None


@pytest.fixture
def recorded_swa(monkeypatch):
    """The slice recorded on the chip (GPT-2 XL, PR 24) with its attention's scope paths renamed
    as a sliding-window layer's would be: everything under ``ds_attn`` under ``ds_attn_window``
    inside it."""
    with open(os.path.join(BENCH_DIR, "testdata", "spans_train_slice.json")) as f:
        doc = json.load(f)
    for info in doc["catalog"].values():
        for name, path in info["ops"].items():
            if "ds_attn" in path:
                info["ops"][name] = path.replace("ds_attn", "ds_attn/ds_attn_window", 1)
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    monkeypatch.setattr(swa_spans, "OUT_NAME", "swa_spans.test.json")
    model = Manifest().config(CONFIG)["model"]
    return dict(doc["record"], trace=tr.Reduced(doc["trace"]), setup={}, swa_moe_model=model, vocab=24576,
                moe={"rows_here_per_token": 8.0, "rows_here_by_layer": [65536.0] * 4},
                band={"visited": 3 * 11_796_480 + 37_748_736, "needed": 3 * 7_864_832 + 33_558_528})


def test_every_new_reader_reads_a_recorded_slice(recorded_swa, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, recorded_swa["device_kind"], dict(peaks.PEAKS["TPU v5 lite"]))
    manifest = Manifest()
    values = {name: manifest.reader(name)(recorded_swa) for name in NEW_READERS}
    assert all(v is not None and np.isfinite(v) and v > 0 for v in values.values()), values
    table = swa_spans.analyse(recorded_swa)
    assert set(table["scope_s"]) == {"ds_attn_window"} and 0 < values["window_attn_time_share"] < 100
    assert values["flash_band_visited_over_needed"] == pytest.approx(73_138_176 / 57_153_024)
    # the kernels' time is the trace's own, by their names; the requirement is this model's
    kernel_s = ps.trace_value(recorded_swa, "kernel_s")
    assert kernel_s["ds_flash_fwd"] > 0 and kernel_s["ds_flash_bwd_dkv"] > 0
    ratio = values["flash_band_bwd_roofline"] * kernel_s["ds_flash_bwd_dkv"] / (
        values["flash_band_fwd_roofline"] * kernel_s["ds_flash_fwd"])
    assert ratio == pytest.approx(2.0, rel=1e-6)        # the backward's requirement is twice the forward's
    faster = dict(recorded_swa, tokens_per_s_chip=2 * recorded_swa["tokens_per_s_chip"])
    assert manifest.reader("mfu.swa_moe")(faster) == pytest.approx(2 * values["mfu.swa_moe"])
    os.remove(os.path.join(BENCH_DIR, "out", "swa_spans.test.json"))


# ------------------------------------------------------------------ the issue's counts
def test_flops_swa_moe_against_the_issues_counts():
    model = Manifest().config(CONFIG)["model"]
    assert flops_swa_moe.layer_kinds(model) == PERIOD
    assert flops_swa_moe.attention_matmul_params(model) == 21_233_664
    assert flops_swa_moe.expert_params(model) == 6_193_152 and flops_swa_moe.router_params(model) == 147_456
    assert flops_swa_moe.layer_params(model) == 120_476_416
    assert flops_swa_moe.param_count(model, 24576) == 595_154_176
    assert round(flops_swa_moe.param_count(model, 24576) * 16 / 1e7) == 952       # 9.52 GB of state
    assert flops_swa_moe.band_pairs_required(8192, 1024) == 7_864_832
    assert flops_swa_moe.band_pairs_required(8192) == 33_558_528
    assert flops_swa_moe.band_pairs_required(512, 1024) == flops_swa_moe.band_pairs_required(512)
    parts = flops_swa_moe.forward_flops_by_part(model, 24576, 8192, 8)
    giga = {k: round(v / 1e8) / 10 for k, v in parts.items()}
    assert giga == {"projections": 1391.6, "attention": 936.4, "router": 9.7, "experts": 3247.0, "head": 927.7}
    assert round(parts["projections"] / 4 / 1e8) == 3479 and round(parts["experts"] / 4 / 1e8) == 8117
    assert round((4 * 7_864_832 * 4096) / 1e8) == 1289 and round((4 * 33_558_528 * 4096) / 1e8) == 5498
    assert parts["attention"] == 4 * (3 * 7_864_832 + 33_558_528) * 4096
    assert round(sum(parts.values()) / 1e8) == 65123                              # 6,512.3 GFLOP a step
    per_token = flops_swa_moe.train_flops_per_token(model, 24576, 8192, 8)
    assert per_token == 3 * sum(parts.values()) / 8192 and round(per_token / 1e6) == 2385
    # fewer rows computed here, fewer operations: never k
    fewer = flops_swa_moe.forward_flops_by_part(model, 24576, 8192, 2)
    assert fewer["experts"] * 4 == parts["experts"] and fewer["attention"] == parts["attention"]
    # the kernel's requirement: the pairs inside the band, K and V at four heads
    fwd_flops, fwd_bytes = flops_swa_moe.flash_required(model, 1, 8192, training=False)
    assert fwd_flops == parts["attention"] and fwd_bytes == 4 * 8192 * 128 * 2 * (2 * 32 + 2 * 4) == 603_979_776
    assert flops_swa_moe.flash_required(model, 1, 8192) == (3 * fwd_flops, 1_811_939_328)
    # four full layers would need 2.35 times these operations: what the older flash rooflines count
    assert round(4 * 33_558_528 / (3 * 7_864_832 + 33_558_528), 2) == 2.35


# ------------------------------------------------------------------ the limits' second readings
def test_the_probe_reads_every_fault_above_the_system(tiny_manifest):
    """``tests/perf/mellum_precision_probe.py`` at the toy size: the system inside every limit,
    and the reference itself at fault in the system's place outside the limit that has to
    catch it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("mellum_precision_probe", os.path.join(
        os.path.dirname(BENCH_DIR), "tests", "perf", "mellum_precision_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    line, = module.probe(tiny_manifest, "tiny-swa-moe", "tiny_docs", [SEED], by_leaf=False, whole_model=False)
    system = line["system"]
    assert system["ok"] is True
    for fault in ("no_window_on_a_sliding_layer", "yarn_on_a_sliding_layer", "key_value_head_a_mod_4"):
        assert line[fault]["window_attention_rel"] > 10 * system["window_attention_rel"], fault
    for fault in ("a_window_on_the_full_layer", "the_plain_table_on_the_full_layer", "attention_factor_dropped",
                  "ramp_without_truncation"):
        assert line[fault]["full_attention_rel"] > 10 * system["full_attention_rel"], fault
    for fault in ("attention_factor_dropped", "ramp_without_truncation"):
        assert line[fault]["rotary_table_rel"] > 1e-3 > 100 * system["rotary_table_rel"], fault
    # a bf16 softmax lies inside the bf16 system's own range (its probabilities are bf16 operands
    # of the kernel's second product already): no limit tells it, and the table says so
    for fault in ("bf16_softmax", "bf16_softmax_on_a_sliding_layer"):
        kind = "window" if "sliding" in fault else "full"
        assert line[fault][kind + "_attention_rel"] < 2 * system[kind + "_attention_rel"], fault
    for fault in ("edge_window_1023", "edge_window_1025", "edge_no_window"):
        assert line[fault]["edge_probe_abs_err"] > 1e-3 and system["edge_probe_abs_err"] == 0.0
    assert line["weights_not_renormalised"]["expert_layer_rel"] > 10 * system["expert_layer_rel"]
    assert line["weights_not_renormalised"]["router_choice_agreement"] == 1.0
    assert line["bf16_router"]["router_probs_rel"] > 1e-4 > 10 * system["router_probs_rel"]
    assert 0 <= line["adam_first_step"]["predicted_shortfall"] < 1
