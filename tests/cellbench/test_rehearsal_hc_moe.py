"""``xing4_ep8_d5_train_1chip``'s runner end to end on the CPU at a toy size (four residual streams,
whole blocks recomputed, the held experts standing in), its record, its five new readers on nothing
and on a recorded trace slice, ``flops_hc_moe.py`` against the issue's counts.

The shape asserts look entries up BY NAME and assert a prefix and a subset, so that the next PR's
appended cell breaks nothing here; nothing asserts on the wall clock, and nothing that a toy's loss
falls within a handful of steps."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops_hc_moe, hc_spans, peaks, run
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest, check

import tiny
from test_program_spans import Recorded

CELL = "xing4_ep8_d5_train_1chip"
CONFIG = "xing4.0-29b-a4b-ep8-d5"
NEW_READERS = ["mfu.hc_moe", "hc_time_share", "hc_roofline", "latent_flash_fwd_roofline",
               "latent_flash_bwd_roofline"]
JOINED = ["moe_time_share", "moe_load_max_over_mean", "moe_rows_here_share", "latent_attn_time_share"]
NOT_JOINED = ["flash_fwd_roofline", "flash_bwd_roofline", "mtp_time_share", "recompute_time_share",
              "held_expert_matmul_roofline", "expert_matmul_roofline", "mfu", "mfu.moe", "mfu.mla_moe",
              "mfu.conv_moe", "flash_time_share", "flash_roofline", "short_conv_roofline"]
OLDER_CELLS = ["xl_d20_train_1chip", "olmoe_d4_train_4chip", "qwen3next_ep16_train_1chip",
               "granite4h_d10_train_1chip", "ouro_d6_train_1chip", "nemotronh_ep16_d9_train_1chip",
               "mellum2_ep4_d4_train_1chip", "glm47flash_ep8_d5_train_1chip", "lfm2_ep8_d7_train_1chip"]
LIMITS = {"train_loss_rel", "last_logits_rel", "expert_agreement", "expert_wrong_choice_share", "hc_res_err_rel",
          "hyper_connection_rel", "hyper_connection_grad_rel", "latent_attention_rel", "latent_attention_grad_rel",
          "dense_mlp_rel", "dense_mlp_grad_rel", "expert_layer_rel", "expert_layer_grad_rel", "router_grad_rel",
          "router_scores_rel", "router_choice_agreement", "router_wrong_choice_share", "router_bias_grad_abs_max"}
STEP_LIMITS = {"step_loss_rel", "step_update_shortfall", "step_hc_moved_share", "step_bias_abs_err",
               "step_bias_moment_abs_max"}
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16, "type": "yarn"}
TINY = {"attention_bias": False, "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
        "max_position_embeddings": 1024, "model_type": "xing4_0", "moe_intermediate_size": 24,
        "moe_layer_freq": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid", "norm_topk_prob": True,
        "num_attention_heads": 4, "n_group": 1, "topk_group": 1, "n_routed_experts": 4, "n_shared_experts": 1,
        "routed_scaling_factor": 2, "num_experts_per_tok": 2, "first_k_dense_replace": 1, "num_hidden_layers": 2,
        "num_key_value_heads": 4, "num_nextn_predict_layers": 0, "rms_norm_eps": 1e-6, "rope_scaling": YARN,
        "rope_theta": 10000, "tie_word_embeddings": False, "q_lora_rank": 16, "kv_lora_rank": 12,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8, "vocab_size": 256,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "router_width": 16, "first_expert": 4, "stand_in": True}
SEED = 2 ** 31 + 5801


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The tiny root of ``tiny.py`` plus a toy Xing4.0 configuration and its one-device cell, added by
    files and entries alone."""
    root = tiny.make_root(tmp_path_factory.mktemp("cellbench_hc_moe"))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    tiny._dump(os.path.join(bench, "configs", "tiny-hc-moe.json"), dict(
        TINY, name="tiny-hc-moe", source="tests/cellbench/test_rehearsal_hc_moe.py",
        runner="train_hc_moe", reduced={}, model=TINY, remat=True,
        assumed={"initializer_range": [None, 0.1, "toy"], "bias_update_rate": [None, 0.001, "toy"]},
        compute_dtype="bfloat16", engine=dict(tiny.ENGINE, zero_optimization={"stage": 2}),
        reference={"module": "xing_moe_reference", "tolerances": "tiny_hc_moe_tolerances",
                   "last_positions": 16, "grad_positions": 32, "tie_margin": 1e-4,
                   "tie_margin_whole_model": 0.05}))
    # toy widths in bf16 sit further from the float32 reference than 3584-wide sums do, and a toy
    # expert that few rows reach has gradients near Adam's epsilon
    with open(os.path.join(bench, "reference", "xing_moe_tolerances.json")) as f:
        limits = json.load(f)
    loose = {k: dict(v, value=max(v["value"], 0.25 if "grad" in k or "logits" in k else 0.1))
             for k, v in limits.items()}
    for exact in ("router_wrong_choice_share", "router_scores_rel", "router_bias_grad_abs_max",
                  "step_bias_abs_err", "step_bias_moment_abs_max"):
        loose[exact] = limits[exact]
    loose["expert_agreement"]["value"], loose["router_choice_agreement"]["value"] = 0.3, 0.99
    loose["step_update_shortfall"]["value"], loose["step_hc_moved_share"]["value"] = 0.6, 0.5
    tiny._dump(os.path.join(bench, "reference", "tiny_hc_moe_tolerances.json"), loose)
    doc["configs"].append({"name": "tiny-hc-moe", "source": "tests/cellbench/test_rehearsal_hc_moe.py",
                           "file": "benchmarks/configs/tiny-hc-moe.json", "reduced": [],
                           "why": "toy sizes for the CPU rehearsal"})
    tiny._dump(os.path.join(bench, "cells", "tiny_hc_moe.json"), dict(
        name="tiny_hc_moe", config="tiny-hc-moe", traffic="tiny_docs", chips=1,
        micro_batch_per_chip=2, warm_steps=2, trace_seconds=1, why="toy cell"))
    doc["workloads"].append({"name": "tiny_hc_moe", "config": "tiny-hc-moe", "traffic": "tiny_docs",
                             "chips": 1, "why": "toy hyper-connected expert cell for the CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_hc_moe")
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
            result = run.run_cell("tiny_hc_moe", SEED, 0.5, bool(trace), manifest=grown,
                                  allow_cpu=True, out_dir=out_dir)
            with open(os.path.join(out_dir, f"tiny_hc_moe.{SEED}.steps.json")) as f:
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
    assert traffic["seq_len"] == 4096 and traffic["eot_token"] == config["vocab_size"] - 1 == 16383
    older = manifest.traffic("packed_docs_4k_v49152")
    same = lambda t: {k: v for k, v in t.items() if k not in ("name", "why", "eot_token", "batches_ahead")}       # noqa: E731
    assert same(traffic) == same(older) and traffic["batches_ahead"] == 320
    # the published keys stand at the top level, as the catalog has them, and again as the
    # group the runner reads, which adds the share
    model = config["model"]
    share = ("router_width", "first_expert", "stand_in")
    assert {k: config[k] for k in model if k not in share} == {k: v for k, v in model.items() if k not in share}
    assert (model["router_width"], model["first_expert"], model["n_routed_experts"]) == (64, 0, 8)
    assert model["stand_in"] is True and "STAND IN" in config["deployment"] and "EIGHT" in config["deployment"]
    assert config["reduced"] == {"num_hidden_layers": [40, 5], "first_k_dense_replace": [2, 1],
                                 "n_routed_experts": [64, 8], "vocab_size": [131072, 16384],
                                 "num_nextn_predict_layers": [1, 0]}
    assert config["published"]["n_routed_experts"] == 64 and config["published"]["vocab_size"] == 131072
    # no width, head count, rank, router width, experts a token or stream count is cut
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
            config["q_lora_rank"], config["kv_lora_rank"], config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["n_shared_experts"]) == \
        (3584, 32, 32, 768, 512, 128, 64, 128, 9216, 1024, 4, 1)
    assert (config["hc_mult"], config["hc_sinkhorn_iters"], config["hc_eps"], config["mhc_h_res_clamp_min"],
            config["mhc_h_res_clamp_max"]) == (4, 20, 1e-6, -30, 30)
    assert (config["routed_scaling_factor"], config["rope_theta"], config["rms_norm_eps"],
            config["first_k_dense_replace"], config["num_nextn_predict_layers"], config["norm_topk_prob"]) == \
        (2, 10000, 1e-06, 1, 0, True)
    assert config["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                                      "mscale_all_dim": 1, "original_max_position_embeddings": 4096, "type": "yarn"}
    assert config["remat"] is True and config["engine"]["optimizer"]["params"] == {"lr": 1e-05}
    assert "scheduler" not in config["engine"] and config["engine"]["zero_optimization"] == {"stage": 2}
    assert {"stream_start_and_end", "hc_norm_weight", "hc_eps_placement", "hc_init", "hc_arithmetic",
            "rotary_pairing", "bias_update_rate", "initializer_range", "eos_token_id", "dropout"} <= set(config["assumed"])
    assert all(len(v) == 3 and len(v[2]) > 10 for v in config["assumed"].values())
    assert config["assumed"]["eos_token_id"][1] == 16383
    assert any("packed documents" in d for d in config["departures"])
    assert any("prediction module" in d for d in config["departures"])
    # the builder's own count, stated in the file
    assert flops_hc_moe.param_count(model, config["vocab_size"]) == 759_489_806
    assert "759,489,806" in config["why_reduced"] and "12.15 GB" in config["why_reduced"]
    assert "14.62 GB" in config["why_reduced"]
    reported = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert set(NEW_READERS) | set(JOINED) <= reported and not set(NOT_JOINED) & reported
    for m in manifest.doc["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"][0] == CELL and m["moves"] == "train_tokens_per_s_chip"
            assert m["unit"] == "%" and m["layer"] == ("model step" if "roofline" not in m["name"] else "kernels")
    with open(os.path.join(BENCH_DIR, "reference", "xing_moe_tolerances.json")) as f:
        limits = json.load(f)
    assert set(limits) == LIMITS | STEP_LIMITS
    assert all(v["value"] >= 0 and len(v["why"]) > 100 for v in limits.values())
    assert limits["router_bias_grad_abs_max"]["value"] == limits["step_bias_moment_abs_max"]["value"] == 0.0


def test_the_benchmark_grew_by_appended_entries_alone():
    """Entries are looked up BY NAME: the older lists are a PREFIX of the new ones and the new
    entries follow them. Nothing here asserts that these entries are the last of all."""
    doc = Manifest().doc
    names = lambda section: [e["name"] for e in doc[section]]       # noqa: E731
    assert names("workloads")[:9] == OLDER_CELLS
    assert names("configs").index(CONFIG) == 9 and names("workloads").index(CELL) == 9
    at = names("per_layer").index("short_conv_roofline")
    assert names("per_layer")[at + 1:at + 6] == NEW_READERS
    for m in doc["end_to_end"] + doc["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells and m["name"] not in NEW_READERS:
            before = cells[:cells.index(CELL)]
            assert before == [c for c in OLDER_CELLS if c in before] and before, m["name"]
            assert len(before) == 9 or m["name"] in JOINED, m["name"]
    assert names("end_to_end")[:2] == ["train_tokens_per_s_chip", "setup_s"]
    assert doc["run_seconds"] == 40 and doc["end_to_end"][0]["bound"] == 0.01
    assert doc["paths"] == ["benchmarks", "tests/cellbench"]
    assert sum(w["chips"] == 4 for w in doc["workloads"][:10]) == 1


# ------------------------------------------------------------------ the cell, toy size
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end(tiny_manifest, cell_run, trace):
    result, record, _ = cell_run(trace)
    reference = record["reference"]
    assert reference["ok"] is True, reference
    # ``correct`` holds the step's check, the losses, that nothing compiled in the window and that
    # every sub-layer's H_res was read, too
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in tiny_manifest.metrics_of(section, "tiny_hc_moe")}
    assert set(result["metrics"]) <= set(declared)
    if trace:
        # the host-clock and counter metrics are there; the device-trace ones find no device plane
        assert {"mfu.hc_moe", "moe_load_max_over_mean", "moe_rows_here_share", "setup_compile_s",
                "step_program_variants"} <= set(result["metrics"])
        assert not {"hc_time_share", "hc_roofline", "latent_flash_fwd_roofline", "latent_flash_bwd_roofline",
                    "latent_attn_time_share", "moe_time_share"} & set(result["metrics"])
        assert result["metrics"]["moe_rows_here_share"]["value"] == 100.0     # the held experts stand in
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert set(reference["tolerances"]) == LIMITS <= set(reference)
    assert reference["router_bias_grad_abs_max"] == 0.0 and reference["router_wrong_choice_share"] == 0.0
    # the hyper-connection's gradients: the eight parameter arrays' and the streams', the worst the reading
    by_leaf = reference["hyper_connection_grad_by_leaf"]
    assert {name.split("'")[1] for name in by_leaf if "'" in name} == {
        "norm", "phi_pre", "phi_post", "phi_res", "b_pre", "b_post", "b_res", "gates"} and len(by_leaf) == 9
    assert max(by_leaf.values()) == reference["hyper_connection_grad_rel"]
    # H_res of all four sub-layers, system and reference: doubly stochastic within the rounds' error
    for side in ("system", "reference"):
        assert len(reference["hc"][side]["hc_res_err_max"]) == 4
        assert max(reference["hc"][side]["hc_res_err_max"]) < 1e-2
        assert 0.9 < min(reference["hc"][side]["hc_res_diag_mean"]) <= max(reference["hc"][side]["hc_res_diag_mean"]) < 1
    # the process's first step is the engine's own, blocks recomputed, on the reference's sequence;
    # the rule moved the expert layer's biases by u one way or the other, and no moment with them
    step = reference["step"]
    assert step["ok"] is True and set(step["tolerances"]) == STEP_LIMITS
    assert step["step_bias_abs_err"] <= 1e-7 and step["step_bias_moment_abs_max"] == 0.0
    assert step["biases_moved"] > 8 and step["biases_sure"] + step["biases_near_the_mean"] == 16
    assert "hc_" not in step["worst_leaf"] and step["step_hc_moved_share"] > 0.5
    assert record["warm_losses"][0] == pytest.approx(step["reference_loss"], rel=step["step_loss_rel"] + 1e-6)
    moe, hc = record["moe"], record["hc"]
    assert moe["steps_counted"] == result["attempted"] and moe["rows_here_by_layer"] == [2 * 64 * 2.0]
    assert hc["complete"] is True and len(hc["res_err_max_by_sub_layer"]) == len(hc["res_diag_mean_by_sub_layer"]) == 4
    assert max(hc["res_err_max_by_sub_layer"]) < 1e-2


def test_the_record_has_what_the_readers_know(cell_run):
    _, _, handed = cell_run(1)
    assert {"kind", "chips", "batch_per_chip", "seq_len", "tokens_per_s_chip", "vocab", "steps",
            "step_interval_ms", "dispatch_ms", "hc_moe_model", "recomputed", "moe", "hc"} <= set(handed)
    assert handed["kind"] == "train" and handed["chips"] == 1 and handed["recomputed"] is True
    assert {"rows_here_share", "rows_here_per_token", "rows_here_by_layer", "load_max_over_mean"} <= set(handed["moe"])
    assert flops_hc_moe.is_hc_moe_model(handed["hc_moe_model"]) and "model" not in handed


# ------------------------------------------------------------ the new readers
@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_returns_nothing_on_nothing(name, monkeypatch):
    reader = Manifest().reader(name)
    assert reader({"setup": {}, "trace": None}) is None
    monkeypatch.setattr(ps, "program_recorder", lambda: None)
    assert reader({"setup": {}, "trace": None, "t_window_start": 5.0, "window_s": 2.0,
                   "kind": "train"}) is None
    # GLM-4.7-Flash's record: latent attention, held experts, flash kernels, and no streams
    glm = Manifest().config("glm-4.7-flash-ep8-d5")["model"]
    assert reader({"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0, "mla_moe_model": glm,
                   "moe": {"rows_here_per_token": 4.0, "rows_here_by_layer": [10.0]},
                   "vocab": 256, "seq_len": 64, "device_kind": "TPU v5 lite"}) is None
    # this cell's record with no trace, whose counters never came
    model = Manifest().config(CONFIG)["model"]
    no_rows = {"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0, "hc_moe_model": model,
               "moe": {"rows_here_per_token": None, "rows_here_by_layer": None}, "vocab": 16384,
               "seq_len": 4096, "batch_per_chip": 1, "recomputed": True, "device_kind": "TPU v5 lite"}
    assert reader(no_rows) is None


@pytest.fixture
def recorded_hc(monkeypatch):
    """The slice recorded on the chip (GPT-2 XL, PR 24) with its scope paths renamed as this model's
    would be: of everything under ``ds_attn`` or ``ds_mlp`` a part under ``ds_hc/ds_hc_coef`` and a
    part under ``ds_hc/ds_hc_mix`` inside it."""
    with open(os.path.join(BENCH_DIR, "testdata", "spans_train_slice.json")) as f:
        doc = json.load(f)
    for info in doc["catalog"].values():
        for name, path in info["ops"].items():
            for part in ("ds_attn", "ds_mlp"):
                if part in path and len(name) % 3:
                    inner = "ds_hc_coef" if len(name) % 3 == 1 else "ds_hc_mix"
                    path = path.replace(part, f"{part}/ds_hc/{inner}", 1)
            info["ops"][name] = path
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    monkeypatch.setattr(hc_spans, "OUT_NAME", "hc_spans.test.json")
    model = Manifest().config(CONFIG)["model"]
    return dict(doc["record"], trace=tr.Reduced(doc["trace"]), setup={}, hc_moe_model=model, vocab=16384,
                recomputed=True, moe={"rows_here_per_token": 4.0, "rows_here_by_layer": [16384.0] * 4})


def test_every_new_reader_reads_a_recorded_slice(recorded_hc, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, recorded_hc["device_kind"], dict(peaks.PEAKS["TPU v5 lite"]))
    manifest = Manifest()
    values = {name: manifest.reader(name)(recorded_hc) for name in NEW_READERS}
    assert all(v is not None and np.isfinite(v) and v > 0 for v in values.values()), values
    table = hc_spans.analyse(recorded_hc)
    assert set(table["scope_s"]) == {"ds_hc", "ds_hc_coef", "ds_hc_mix"}
    assert table["scope_s"]["ds_hc"] == pytest.approx(table["scope_s"]["ds_hc_coef"] + table["scope_s"]["ds_hc_mix"])
    assert 0 < values["hc_time_share"] < 100
    assert values["hc_time_share"] == pytest.approx(100 * table["scope_s"]["ds_hc"] / table["window_s"])
    faster = dict(recorded_hc, tokens_per_s_chip=2 * recorded_hc["tokens_per_s_chip"])
    assert manifest.reader("mfu.hc_moe")(faster) == pytest.approx(2 * values["mfu.hc_moe"])
    # not recomputed: a pass fewer to pay for, a lower share of the same time
    plain = dict(recorded_hc, recomputed=False, hc_spans=table)
    assert manifest.reader("hc_roofline")(plain) == pytest.approx(0.75 * values["hc_roofline"])
    os.remove(os.path.join(BENCH_DIR, "out", "hc_spans.test.json"))


# ------------------------------------------------------------------ the issue's counts
def test_flops_hc_moe_against_the_issues_counts():
    model = Manifest().config(CONFIG)["model"]
    assert flops_hc_moe.hc_params(model) == 358_427 and flops_hc_moe.sub_layers(model) == 10
    assert flops_hc_moe.mla.attention_params(model) == 28_411_136
    assert flops_hc_moe.mla.expert_params(model) == 11_010_048
    assert flops_hc_moe.mla.dense_mlp_params(model) == 99_090_432 == 9 * 11_010_048
    assert flops_hc_moe.mla.dense_block_params(model) + 2 * 358_427 == 128_225_590
    assert flops_hc_moe.mla.expert_block_params(model) + 2 * 358_427 == 128_455_030
    assert flops_hc_moe.param_count(model, 16384) == 759_489_806
    assert round(flops_hc_moe.param_count(model, 16384) * 16 / 1e7) == 1215       # 12.15 GB of state
    parts = flops_hc_moe.forward_flops_by_part(model, 16384, 4096, 4)
    mega = {k: round(v / 4096 / 1e5) / 10 for k, v in parts.items()}               # MFLOP a token
    assert mega == {"latent_projections": 284.1, "attention": 209.8, "dense_mlp": 198.2, "routers": 1.8,
                    "experts": 352.3, "shared_experts": 88.1, "heads": 117.4, "hyper_connections": 8.6}
    assert round(sum(parts.values()) / 4096 / 1e7) == 126                          # the issue's 1.25 GF a token
    per_token = flops_hc_moe.train_flops_per_token(model, 16384, 4096, 4)
    assert per_token == 3 * sum(parts.values()) / 4096 and round(per_token * 4096 / 1e11) == 155   # 15.5 TF a step
    # fewer rows computed here, fewer operations: never k
    fewer = flops_hc_moe.forward_flops_by_part(model, 16384, 4096, 1)
    assert fewer["experts"] * 4 == parts["experts"] and fewer["shared_experts"] == parts["shared_experts"]
    # the hyper-connections' least bytes: (3 n + 2) C bf16 elements a token a sub-layer a forward,
    # four passes' worth with the second forward: the issue's 4.1 GB forward and 16 GB a step
    _, step_bytes = flops_hc_moe.hc_required(model, 4096, recomputed=True)
    assert step_bytes == 4 * 14 * 3584 * 2 * 4096 * 10 and round(step_bytes / 1e8) == 164
    assert flops_hc_moe.hc_required(model, 4096, recomputed=False)[1] * 4 == step_bytes * 3
    # the flash kernel's needed work at 192 | 128: two products forward, five backward
    fwd, bwd = (flops_hc_moe.latent_flash_required(model, 1, 4096, forward=f) for f in (True, False))
    pairs = 4096 * 4097 // 2
    assert fwd[0] == 2 * pairs * 5 * 32 * (192 + 128) and bwd[0] == 2 * pairs * 5 * 32 * (3 * 192 + 2 * 128)
    assert fwd[1] == 5 * 4096 * 32 * (2 * 192 + 2 * 128) * 2 and bwd[1] == 2 * fwd[1]
    assert fwd[0] == parts["attention"]


# ------------------------------------------------------------------ the limits' second readings
@pytest.mark.parametrize("draws", [1, 16])
def test_a_small_leafs_gradient_is_read_under_several_cotangents_together(draws):
    """``train_hc_moe.gradients_by_leaf``: a leaf's ``draws`` gradients stacked, the error's norm over
    the reference's; under one draw it is ``Alone.gradients``' reading, and under sixteen a leaf of
    three numbers no longer follows the one draw under which its own sum fell near zero."""
    import jax.numpy as jnp
    from benchmarks.runners import train_hc_moe as runner
    rng = np.random.default_rng(5)
    params = {"gates": jnp.asarray(rng.standard_normal(3), jnp.float32),
              "w": jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)}
    x = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    plain = lambda p, x: jnp.tanh(x @ p["w"]) * p["gates"][0] + x * p["gates"][1] + p["gates"][2]     # noqa: E731
    rounded = lambda p, x: plain(p, x.astype(jnp.bfloat16).astype(jnp.float32)).astype(jnp.bfloat16)  # noqa: E731
    alone = runner.Alone(rounded, plain)
    seeds = [(SEED, d) for d in range(draws)]
    got = runner.gradients_by_leaf(alone, params, x, 32, seeds)
    assert set(got) == {"[0]['gates']", "[0]['w']", "[1]"}
    err, norm = {name: 0.0 for name in got}, {name: 0.0 for name in got}
    alone_each = []
    for seed in seeds:
        cot = jnp.asarray(np.random.default_rng(seed).standard_normal((1, 32, 8)), jnp.float32)
        g, w = (f(params, x[None, -32:], cot) for f in alone.grads)
        pairs = {"[0]['gates']": (g[0]["gates"], w[0]["gates"]), "[0]['w']": (g[0]["w"], w[0]["w"]), "[1]": (g[1], w[1])}
        for name, (a, b) in pairs.items():
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            err[name] += np.sum((a - b) ** 2)
            norm[name] += np.sum(b ** 2)
        alone_each.append(max(np.sqrt(np.sum((np.asarray(a, np.float64) - np.asarray(b, np.float64)) ** 2)
                                      / np.sum(np.asarray(b, np.float64) ** 2)) for a, b in pairs.values()))
    for name in got:
        assert got[name] == pytest.approx(np.sqrt(err[name] / norm[name]), rel=1e-4)
    # a mediant: the stacked reading never passes the worst that one of its draws reads alone
    assert max(got.values()) <= max(alone_each) * (1 + 1e-4)


def test_the_probe_reads_every_fault_above_the_system(tiny_manifest):
    """``tests/perf/xing_precision_probe.py`` at the toy size: the system inside every limit, and the
    reference itself at fault in the system's place outside the reading that has to catch it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("xing_precision_probe", os.path.join(
        os.path.dirname(BENCH_DIR), "tests", "perf", "xing_precision_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    line, = module.probe(tiny_manifest, "tiny-hc-moe", "tiny_docs", [SEED], whole_model=True, adam=False)
    system = line["system"]
    assert system["ok"] is True
    # fewer rounds leave H_res further from doubly stochastic, in a sub-layer alone and in every one
    for fault in ("rounds_19", "rounds_10", "bf16_coefficients"):
        assert line[fault]["hc_res_err_rel"] > 0.05 > 10 * system["hc_res_err_rel"], (fault, line[fault])
    assert line["rounds_10"]["hc_res_err_rel"] > 3 * line["rounds_19"]["hc_res_err_rel"]
    assert line["h_post_plain_sigmoid"]["hyper_connection_rel"] > 10 * system["hyper_connection_rel"]
    assert line["h_post_plain_sigmoid"]["last_logits_rel"] > 3 * system["last_logits_rel"]
    assert line["embedding_in_the_first_stream_alone"]["last_logits_rel"] > 3 * system["last_logits_rel"]
    for fault in ("plain_frequencies", "scale_without_m2", "rotary_key_left_out"):
        assert line[fault]["latent_attention_grad_rel"] > 3 * system["latent_attention_grad_rel"], (fault, line[fault])
    assert line["bf16_router"]["router_scores_rel"] > 1e-4 > 10 * system["router_scores_rel"]
    assert line["factor_1"]["expert_layer_rel"] > 3 * system["expert_layer_rel"]
    assert line["factor_1"]["router_choice_agreement"] == 1.0
