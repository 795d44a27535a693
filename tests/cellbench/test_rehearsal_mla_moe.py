"""``glm47flash_ep8_d5_train_1chip``'s runner end to end on the CPU at a toy size (whole blocks
recomputed, the held experts standing in, both prediction depths trained), its record, its three new
readers on nothing and on a recorded trace slice, ``flops_mla_moe.py`` against the issue's counts, and
the probe's faults: each read above the system by the limit that has to catch it.

The shape asserts look entries up BY NAME and assert a prefix and a subset, so that the next PR's
appended cell breaks nothing here; nothing asserts on the wall clock, and nothing that a toy's loss
falls within a handful of steps."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops, flops_mla_moe, mla_spans, peaks, run
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest, check

import tiny
from test_program_spans import Recorded

CELL = "glm47flash_ep8_d5_train_1chip"
CONFIG = "glm-4.7-flash-ep8-d5"
NEW_READERS = ["mfu.mla_moe", "latent_attn_time_share", "mtp_time_share"]
JOINED = ["moe_time_share", "moe_load_max_over_mean", "moe_rows_here_share", "flash_fwd_roofline",
          "flash_bwd_roofline"]
NOT_JOINED = ["recompute_time_share", "expert_matmul_roofline", "held_expert_matmul_roofline", "mfu", "mfu.moe",
              "mfu.hybrid", "mfu.ssm", "mfu.loop", "mfu.ssm_moe", "mfu.swa_moe", "flash_time_share",
              "flash_roofline", "flash_band_fwd_roofline", "flash_band_bwd_roofline",
              "flash_band_visited_over_needed", "window_attn_time_share"]
OLDER_CELLS = ["xl_d20_train_1chip", "olmoe_d4_train_4chip", "qwen3next_ep16_train_1chip",
               "granite4h_d10_train_1chip", "ouro_d6_train_1chip", "nemotronh_ep16_d9_train_1chip",
               "mellum2_ep4_d4_train_1chip"]
LIMITS = {"train_loss_rel", "loss_main_rel", "loss_mtp_rel", "last_logits_rel", "last_logits_mtp_rel",
          "expert_agreement", "expert_wrong_choice_share", "latent_attention_rel", "latent_attention_grad_rel",
          "dense_mlp_rel", "dense_mlp_grad_rel", "expert_layer_rel", "expert_layer_grad_rel",
          "router_grad_rel", "router_scores_rel", "router_choice_agreement", "router_wrong_choice_share",
          "router_bias_grad_abs_max", "mtp_combine_rel", "mtp_combine_grad_rel"}
STEP_LIMITS = {"step_loss_rel", "step_update_shortfall", "step_bias_abs_err", "step_bias_moment_abs_max"}
TINY = {"attention_bias": False, "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
        "max_position_embeddings": 1024, "model_type": "glm4_moe_lite", "moe_intermediate_size": 24,
        "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 4, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 4, "n_shared_experts": 1, "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
        "first_k_dense_replace": 1, "num_hidden_layers": 2, "num_key_value_heads": 4,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1, "rms_norm_eps": 1e-5, "rope_scaling": None,
        "rope_theta": 10000, "tie_word_embeddings": False, "q_lora_rank": 16, "kv_lora_rank": 12,
        "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 16, "vocab_size": 256,
        "router_width": 16, "first_expert": 4, "stand_in": True}
SEED = 2 ** 31 + 4801


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The tiny root of ``tiny.py`` plus a toy GLM configuration and its one-device cell, added by
    files and entries alone."""
    root = tiny.make_root(tmp_path_factory.mktemp("cellbench_mla_moe"))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    tiny._dump(os.path.join(bench, "configs", "tiny-mla-moe.json"), dict(
        TINY, name="tiny-mla-moe", source="tests/cellbench/test_rehearsal_mla_moe.py",
        runner="train_mla_moe", reduced={}, model=TINY, remat=True,
        assumed={"initializer_range": [None, 0.1, "toy"], "bias_update_rate": [None, 0.001, "toy"],
                 "mtp_loss_weight": [None, 0.3, "toy"]},
        compute_dtype="bfloat16", engine=dict(tiny.ENGINE, zero_optimization={"stage": 2}),
        reference={"module": "glm_moe_reference", "tolerances": "tiny_mla_moe_tolerances",
                   "last_positions": 16, "grad_positions": 32, "tie_margin": 1e-4,
                   "tie_margin_whole_model": 0.05}))
    # toy widths in bf16 sit further from the float32 reference than 2048-wide sums do, and a toy
    # expert that few rows reach has gradients near Adam's epsilon
    with open(os.path.join(bench, "reference", "glm_moe_tolerances.json")) as f:
        limits = json.load(f)
    loose = {k: dict(v, value=max(v["value"], 0.25 if "grad" in k or "logits" in k else 0.1))
             for k, v in limits.items()}
    for exact in ("router_wrong_choice_share", "router_scores_rel", "router_bias_grad_abs_max",
                  "step_bias_abs_err", "step_bias_moment_abs_max"):
        loose[exact] = limits[exact]
    loose["expert_agreement"]["value"], loose["router_choice_agreement"]["value"] = 0.3, 0.99
    loose["step_update_shortfall"]["value"] = 0.6
    tiny._dump(os.path.join(bench, "reference", "tiny_mla_moe_tolerances.json"), loose)
    doc["configs"].append({"name": "tiny-mla-moe", "source": "tests/cellbench/test_rehearsal_mla_moe.py",
                           "file": "benchmarks/configs/tiny-mla-moe.json", "reduced": [],
                           "why": "toy sizes for the CPU rehearsal"})
    tiny._dump(os.path.join(bench, "cells", "tiny_mla_moe.json"), dict(
        name="tiny_mla_moe", config="tiny-mla-moe", traffic="tiny_docs", chips=1,
        micro_batch_per_chip=2, warm_steps=2, trace_seconds=1, why="toy cell"))
    doc["workloads"].append({"name": "tiny_mla_moe", "config": "tiny-mla-moe", "traffic": "tiny_docs",
                             "chips": 1, "why": "toy latent-attention expert cell for the CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_mla_moe")
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
            result = run.run_cell("tiny_mla_moe", SEED, 0.5, bool(trace), manifest=grown,
                                  allow_cpu=True, out_dir=out_dir)
            with open(os.path.join(out_dir, f"tiny_mla_moe.{SEED}.steps.json")) as f:
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
    assert traffic["seq_len"] == 8192 and traffic["eot_token"] == config["vocab_size"] - 1 == 19359
    older = manifest.traffic("packed_docs_8k_v24576")
    assert {k: v for k, v in traffic.items() if k not in ("name", "why", "eot_token")} == \
        {k: v for k, v in older.items() if k not in ("name", "why", "eot_token")}
    # the published keys stand at the top level, as the catalog has them, and again as the
    # group the runner reads, which adds the share
    model = config["model"]
    share = ("router_width", "first_expert", "stand_in")
    assert {k: config[k] for k in model if k not in share} == {k: v for k, v in model.items() if k not in share}
    assert (model["router_width"], model["first_expert"], model["n_routed_experts"]) == (64, 0, 8)
    assert model["stand_in"] is True and "STAND IN" in config["deployment"] and "EIGHT" in config["deployment"]
    assert config["reduced"] == {"num_hidden_layers": [47, 5], "n_routed_experts": [64, 8],
                                 "vocab_size": [154880, 19360]}
    assert config["published"]["n_routed_experts"] == 64 and config["published"]["vocab_size"] == 154880
    # no width, head count, rank, router width or experts a token is cut
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
            config["q_lora_rank"], config["kv_lora_rank"], config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["n_shared_experts"]) == \
        (2048, 20, 20, 768, 512, 192, 64, 256, 10240, 1536, 4, 1)
    assert (config["routed_scaling_factor"], config["rope_theta"], config["rope_scaling"], config["rms_norm_eps"],
            config["first_k_dense_replace"], config["num_nextn_predict_layers"], config["norm_topk_prob"]) == \
        (1.8, 1000000, None, 1e-05, 1, 1, True)
    assert config["remat"] is True and config["engine"]["optimizer"]["params"] == {"lr": 1e-05}
    assert "scheduler" not in config["engine"] and config["engine"]["zero_optimization"] == {"stage": 2}
    assert {"mtp_loss_weight", "mtp_input", "mtp_shared_tables", "rotary_pairing", "bias_update_rate",
            "initializer_range", "eos_token_id", "dropout"} <= set(config["assumed"])
    assert all(len(v) == 3 and len(v[2]) > 10 for v in config["assumed"].values())
    assert config["assumed"]["mtp_loss_weight"][1] == 0.3 and config["assumed"]["eos_token_id"][1] == 19359
    assert any("packed documents" in d for d in config["departures"])
    assert any("block 46" in d for d in config["departures"])
    # the builder's own count, stated in the file
    assert flops_mla_moe.param_count(model, config["vocab_size"]) == 706_518_848
    assert "706,518,848" in config["why_reduced"] and "11.30 GB" in config["why_reduced"]
    reported = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert set(NEW_READERS) | set(JOINED) <= reported and not set(NOT_JOINED) & reported
    for m in manifest.doc["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"][0] == CELL and m["moves"] == "train_tokens_per_s_chip"
            assert m["unit"] == "%" and m["layer"] == "model step"
    with open(os.path.join(BENCH_DIR, "reference", "glm_moe_tolerances.json")) as f:
        limits = json.load(f)
    assert set(limits) == LIMITS | STEP_LIMITS
    assert all(v["value"] >= 0 and len(v["why"]) > 100 for v in limits.values())
    assert limits["router_bias_grad_abs_max"]["value"] == limits["step_bias_moment_abs_max"]["value"] == 0.0


def test_the_benchmark_grew_by_appended_entries_alone():
    """Entries are looked up BY NAME: the older lists are a PREFIX of the new ones and the new
    entries follow them. Nothing here asserts that these entries are the last of all."""
    doc = Manifest().doc
    names = lambda section: [e["name"] for e in doc[section]]       # noqa: E731
    assert names("workloads")[:7] == OLDER_CELLS
    assert names("configs").index(CONFIG) == 7 and names("workloads").index(CELL) == 7
    at = names("per_layer").index("flash_band_visited_over_needed")
    assert names("per_layer")[at + 1:at + 4] == NEW_READERS
    for m in doc["end_to_end"] + doc["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells and m["name"] not in NEW_READERS:
            before = cells[:cells.index(CELL)]
            assert before == [c for c in OLDER_CELLS if c in before] and before, m["name"]
            assert len(before) == 7 or m["name"] in JOINED, m["name"]
    assert names("end_to_end")[:2] == ["train_tokens_per_s_chip", "setup_s"]
    assert doc["run_seconds"] == 40 and doc["end_to_end"][0]["bound"] == 0.01
    assert doc["paths"] == ["benchmarks", "tests/cellbench"]
    assert sum(w["chips"] == 4 for w in doc["workloads"][:8]) == 1


def test_the_older_flash_readers_are_handed_what_they_know():
    """``flops.flash_required`` under ``flash_sizes`` counts exactly this model's kernel calls: six
    whole triangles at 20 heads of 256, keys and values as wide as the queries."""
    model = Manifest().config(CONFIG)["model"]
    sizes = flops_mla_moe.flash_sizes(model)
    assert sizes == {"n_embd": 5120, "n_layer": 6, "n_head": 20}
    fwd_flops, fwd_bytes = flops.flash_required(sizes, 1, 8192, training=False)
    assert fwd_flops == 6 * 4 * (8192 * 8192 // 2) * 20 * 256
    assert fwd_bytes == 6 * 4 * 8192 * 20 * 256 * 2
    parts = flops_mla_moe.forward_flops_by_part(model, 19360, 8192, 4)
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
    declared = {m["name"]: m["unit"] for m in tiny_manifest.metrics_of(section, "tiny_mla_moe")}
    assert set(result["metrics"]) <= set(declared)
    if trace:
        # the host-clock and counter metrics are there; the device-trace ones find no device plane
        assert {"mfu.mla_moe", "moe_load_max_over_mean", "moe_rows_here_share", "setup_compile_s",
                "step_program_variants"} <= set(result["metrics"])
        assert not {"latent_attn_time_share", "mtp_time_share", "moe_time_share", "flash_fwd_roofline",
                    "flash_bwd_roofline"} & set(result["metrics"])
        assert result["metrics"]["moe_rows_here_share"]["value"] == 100.0     # the held experts stand in
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert set(reference["tolerances"]) == LIMITS <= set(reference)
    assert reference["router_bias_grad_abs_max"] == 0.0 and reference["router_wrong_choice_share"] == 0.0
    system, theirs = reference["losses_by_depth"]["system"], reference["losses_by_depth"]["reference"]
    assert reference["reference_loss"] == pytest.approx(theirs[0] + 0.3 * theirs[1], rel=1e-6)
    assert reference["system_loss"] == pytest.approx(system[0] + 0.3 * system[1], rel=1e-3)
    # the process's first step is the engine's own, blocks recomputed, on the reference's sequence;
    # the rule moved the biases of BOTH expert layers (the block's and the module's) by u one way
    # or the other, and no moment with them
    step = reference["step"]
    assert step["ok"] is True and set(step["tolerances"]) == STEP_LIMITS
    assert step["step_bias_abs_err"] <= 1e-7 and step["step_bias_moment_abs_max"] == 0.0
    assert step["biases_moved"] > 16 and step["biases_sure"] + step["biases_near_the_mean"] == 2 * 16
    assert record["warm_losses"][0] == pytest.approx(step["reference_loss"], rel=step["step_loss_rel"] + 1e-6)
    moe, by_depth = record["moe"], record["losses_by_depth"]
    assert moe["steps_counted"] == result["attempted"] and moe["rows_here_by_layer"] == [2 * 64 * 2.0] * 2
    # both depths' losses leave the program as device scalars, and add up to the step's loss
    assert len(by_depth["loss_main"]) == len(by_depth["loss_mtp"]) == result["attempted"]
    total = np.asarray(by_depth["loss_main"]) + 0.3 * np.asarray(by_depth["loss_mtp"])
    np.testing.assert_allclose(total, record["losses"], rtol=2e-2)


def test_the_record_has_what_the_readers_know(cell_run):
    _, _, handed = cell_run(1)
    assert {"kind", "chips", "batch_per_chip", "seq_len", "tokens_per_s_chip", "vocab", "steps", "model",
            "step_interval_ms", "dispatch_ms", "mla_moe_model", "moe"} <= set(handed)
    assert handed["kind"] == "train" and handed["chips"] == 1
    assert {"rows_here_share", "rows_here_per_token", "rows_here_by_layer", "load_max_over_mean"} <= set(handed["moe"])
    assert flops_mla_moe.is_mla_moe_model(handed["mla_moe_model"])
    assert handed["model"] == {"n_embd": 64, "n_layer": 3, "n_head": 4}


# ------------------------------------------------------------ the new readers
@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_returns_nothing_on_nothing(name, monkeypatch):
    reader = Manifest().reader(name)
    assert reader({"setup": {}, "trace": None}) is None
    monkeypatch.setattr(ps, "program_recorder", lambda: None)
    assert reader({"setup": {}, "trace": None, "t_window_start": 5.0, "window_s": 2.0,
                   "kind": "train"}) is None
    # Mellum 2's record: held experts, flash kernels, and no such model
    assert reader({"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0,
                   "swa_moe_model": {"layer_types": ["full_attention"], "moe_intermediate_size": 8},
                   "moe": {"rows_here_per_token": 8.0, "rows_here_by_layer": [10.0]},
                   "vocab": 256, "seq_len": 64, "device_kind": "TPU v5 lite"}) is None
    # this cell's record with no trace, whose counters never came
    model = Manifest().config(CONFIG)["model"]
    no_rows = {"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0, "mla_moe_model": model,
               "moe": {"rows_here_per_token": None, "rows_here_by_layer": None}, "vocab": 19360,
               "seq_len": 8192, "batch_per_chip": 1, "device_kind": "TPU v5 lite"}
    assert reader(no_rows) is None


@pytest.fixture
def recorded_mla(monkeypatch):
    """The slice recorded on the chip (GPT-2 XL, PR 24) with its scope paths renamed as this model's
    would be: everything under ``ds_attn`` under ``ds_attn_latent`` inside it, and the second
    block's operations under ``ds_mtp`` besides."""
    with open(os.path.join(BENCH_DIR, "testdata", "spans_train_slice.json")) as f:
        doc = json.load(f)
    for info in doc["catalog"].values():
        for name, path in info["ops"].items():
            if "ds_attn" in path:
                path = path.replace("ds_attn", "ds_attn/ds_attn_latent", 1)
            if "ds_loss" in path or len(name) % 3 == 0:
                path = "ds_mtp/" + path
            info["ops"][name] = path
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    monkeypatch.setattr(mla_spans, "OUT_NAME", "mla_spans.test.json")
    model = Manifest().config(CONFIG)["model"]
    return dict(doc["record"], trace=tr.Reduced(doc["trace"]), setup={}, mla_moe_model=model, vocab=19360,
                moe={"rows_here_per_token": 4.0, "rows_here_by_layer": [32768.0] * 5})


def test_every_new_reader_reads_a_recorded_slice(recorded_mla, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, recorded_mla["device_kind"], dict(peaks.PEAKS["TPU v5 lite"]))
    manifest = Manifest()
    values = {name: manifest.reader(name)(recorded_mla) for name in NEW_READERS}
    assert all(v is not None and np.isfinite(v) and v > 0 for v in values.values()), values
    table = mla_spans.analyse(recorded_mla)
    assert set(table["scope_s"]) == {"ds_attn_latent", "ds_mtp"}
    assert 0 < values["latent_attn_time_share"] < 100 and 0 < values["mtp_time_share"] < 100
    assert values["latent_attn_time_share"] == pytest.approx(
        100 * table["scope_s"]["ds_attn_latent"] / table["window_s"])
    faster = dict(recorded_mla, tokens_per_s_chip=2 * recorded_mla["tokens_per_s_chip"])
    assert manifest.reader("mfu.mla_moe")(faster) == pytest.approx(2 * values["mfu.mla_moe"])
    os.remove(os.path.join(BENCH_DIR, "out", "mla_spans.test.json"))


# ------------------------------------------------------------------ the issue's counts
def test_flops_mla_moe_against_the_issues_counts():
    model = Manifest().config(CONFIG)["model"]
    assert flops_mla_moe.blocks(model) == (1, 5)
    assert flops_mla_moe.attention_params(model) == 21_759_232
    assert flops_mla_moe.expert_params(model) == 9_437_184 and flops_mla_moe.router_params(model) == 131_072
    assert flops_mla_moe.dense_block_params(model) == 84_677_888
    assert flops_mla_moe.expert_block_params(model) == 106_829_120
    assert flops_mla_moe.module_params(model) == 115_223_872
    assert flops_mla_moe.param_count(model, 19360) == 706_518_848
    assert round(flops_mla_moe.param_count(model, 19360) * 16 / 1e7) == 1130       # 11.30 GB of state
    parts = flops_mla_moe.forward_flops_by_part(model, 19360, 8192, 4)
    mega = {k: round(v / 8192 / 1e5) / 10 for k, v in parts.items()}               # MFLOP a token
    assert mega == {"latent_projections": 261.1, "attention": 503.4, "dense_mlp": 125.8, "routers": 1.3,
                    "experts": 377.5, "shared_experts": 94.4, "mtp_projection": 16.8, "heads": 158.6}
    assert round(sum(parts.values()) / 8192 / 1e6) == 1539                         # the issue's 1,539 MF
    attention = parts["latent_projections"] + parts["attention"]
    assert round(100 * attention / sum(parts.values())) == 50                      # half the forward
    per_token = flops_mla_moe.train_flops_per_token(model, 19360, 8192, 4)
    assert per_token == 3 * sum(parts.values()) / 8192 and round(per_token * 8192 / 1e11) == 378   # 37.8 TF a step
    # fewer rows computed here, fewer operations: never k
    fewer = flops_mla_moe.forward_flops_by_part(model, 19360, 8192, 1)
    assert fewer["experts"] * 4 == parts["experts"] and fewer["shared_experts"] == parts["shared_experts"]


# ------------------------------------------------------------------ the limits' second readings
def test_the_probe_reads_every_fault_above_the_system(tiny_manifest):
    """``tests/perf/glm_precision_probe.py`` at the toy size: the system inside every limit, and the
    reference itself at fault in the system's place outside the limit that has to catch it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("glm_precision_probe", os.path.join(
        os.path.dirname(BENCH_DIR), "tests", "perf", "glm_precision_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    line, = module.probe(tiny_manifest, "tiny-mla-moe", "tiny_docs", [SEED], whole_model=False, adam=False)
    system = line["system"]
    assert system["ok"] is True
    # the mixer's faults show in its gradients first (on the chip the scale's reads BELOW the system
    # in the output and 23 times above it in the gradients: glm_moe_tolerances.json)
    for fault in ("rotary_key_left_out", "rotary_key_a_head_its_own", "scale_of_the_nope_width",
                  "latent_norm_skipped"):
        assert line[fault]["latent_attention_grad_rel"] > 5 * system["latent_attention_grad_rel"], (fault, line[fault])
    for fault in ("rotary_key_left_out", "rotary_key_a_head_its_own", "latent_norm_skipped"):
        assert line[fault]["latent_attention_rel"] > 2 * system["latent_attention_rel"], (fault, line[fault])
    # a bf16 softmax lies inside the bf16 system's own range: no limit tells it, and the table says so
    assert line["bf16_softmax"]["latent_attention_rel"] < 2 * system["latent_attention_rel"]
    assert line["bf16_router"]["router_scores_rel"] > 1e-4 > 10 * system["router_scores_rel"]
    assert line["factor_1"]["expert_layer_rel"] > 3 * system["expert_layer_rel"]
    assert line["factor_1"]["router_choice_agreement"] == 1.0
    assert line["module_fed_t_i"]["last_logits_mtp_rel"] > 3 * system["last_logits_mtp_rel"]
    assert line["module_fed_t_i"]["loss_main_rel"] == 0.0          # the first depth never sees the module
    for fault in ("l2_dropped", "l2_weighted_1"):
        assert line[fault]["train_loss_rel"] > 0.1 > 10 * system["train_loss_rel"], fault
        assert line[fault]["loss_mtp_rel"] == 0.0
    assert line["block_0_as_experts"]["last_logits_rel"] > 3 * system["last_logits_rel"]
