"""``nemotronh_ep16_d9_train_1chip``'s runner end to end on the CPU at a toy size (whole layers
recomputed, the selection bias moved by the engine's rule-updated-leaf path), its record, its
new readers on nothing and on a recorded trace slice, the step check's reading of the rule,
and ``flops_ssm_moe.py`` against the issue's counts.

The shape asserts look entries up BY NAME and assert a prefix and a subset, so that the next
PR's appended cell breaks nothing here; nothing asserts on the wall clock."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops, flops_ssm, flops_ssm_moe, moe_spans, peaks, run
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest, check
from benchmarks.runners import train_ssm_moe

import tiny
from test_program_spans import Recorded

CELL = "nemotronh_ep16_d9_train_1chip"
CONFIG = "nemotron-twotower-30b-a3b-ep16-d9"
NEW_READERS = ["mfu.ssm_moe", "held_expert_matmul_roofline"]
JOINED = ["ssm_time_share", "ssd_scan_roofline", "recompute_time_share", "moe_time_share",
          "moe_load_max_over_mean", "moe_rows_here_share"]
OLDER_CELLS = ["xl_d20_train_1chip", "olmoe_d4_train_4chip", "qwen3next_ep16_train_1chip",
               "granite4h_d10_train_1chip", "ouro_d6_train_1chip"]
LIMITS = {"train_loss_rel", "last_logits_rel", "expert_agreement", "expert_wrong_choice_share",
          "mixer_rel", "mixer_grad_rel", "scan_rel", "scan_bf16_rel", "scan_grad_rel", "attention_rel",
          "attention_grad_rel", "expert_layer_rel", "expert_layer_grad_rel", "router_scores_rel",
          "router_choice_agreement", "router_wrong_choice_share", "router_bias_grad_abs_max"}
STEP_LIMITS = {"step_loss_rel", "step_update_shortfall", "step_bias_abs_err", "step_bias_moment_abs_max"}
TINY = {"attention_bias": False, "chunk_size": 16, "conv_kernel": 4, "head_dim": 16, "hidden_size": 32,
        "hybrid_override_pattern": "MEM*EMEM", "layer_norm_epsilon": 1e-05, "mamba_head_dim": 8,
        "mamba_hidden_act": "silu", "mamba_num_heads": 8, "mamba_proj_bias": False, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 24,
        "moe_shared_expert_intermediate_size": 48, "n_group": 1, "n_groups": 2, "n_routed_experts": 4,
        "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4, "num_experts_per_tok": 3,
        "num_hidden_layers": 5, "num_key_value_heads": 2, "routed_scaling_factor": 2.5,
        "ssm_state_size": 16, "tie_word_embeddings": False, "time_step_limit": [0, None], "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "vocab_size": 256, "router_width": 16, "first_expert": 4,
        "stand_in": True}
SEED = 2 ** 31 + 4321


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The tiny root of ``tiny.py`` plus a toy Nemotron-H configuration and its one-device
    cell, added by files and entries alone."""
    root = tiny.make_root(tmp_path_factory.mktemp("cellbench_ssm_moe"))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    tiny._dump(os.path.join(bench, "configs", "tiny-ssm-moe.json"), dict(
        TINY, name="tiny-ssm-moe", source="tests/cellbench/test_rehearsal_ssm_moe.py",
        runner="train_ssm_moe", reduced={}, model=TINY, remat=True,
        assumed={"initializer_range": [None, 0.1, "toy"], "bias_update_rate": [None, 0.001, "toy"]},
        compute_dtype="bfloat16", engine=dict(tiny.ENGINE, zero_optimization={"stage": 2}),
        reference={"module": "nemotron_h_reference", "tolerances": "tiny_ssm_moe_tolerances",
                   "last_positions": 16, "grad_positions": 32, "tie_margin": 1e-4,
                   "tie_margin_whole_model": 0.1}))
    # toy widths in bf16 sit further from the float32 reference than 2688-wide sums do, and
    # sixteen experts' scores lie closer together than 128's
    with open(os.path.join(bench, "reference", "nemotron_h_tolerances.json")) as f:
        limits = json.load(f)
    loose = {k: dict(v, value=max(v["value"], 0.25 if "grad" in k or "logits" in k else 0.1))
             for k, v in limits.items()}
    for exact in ("router_bias_grad_abs_max", "router_wrong_choice_share", "step_bias_abs_err",
                  "step_bias_moment_abs_max", "router_scores_rel"):
        loose[exact] = limits[exact]
    loose["scan_rel"]["value"], loose["scan_grad_rel"]["value"] = 1e-5, 1e-4
    loose["expert_agreement"]["value"], loose["router_choice_agreement"]["value"] = 0.3, 0.99
    tiny._dump(os.path.join(bench, "reference", "tiny_ssm_moe_tolerances.json"), loose)
    doc["configs"].append({"name": "tiny-ssm-moe", "source": "tests/cellbench/test_rehearsal_ssm_moe.py",
                           "file": "benchmarks/configs/tiny-ssm-moe.json", "reduced": [],
                           "why": "toy sizes for the CPU rehearsal"})
    tiny._dump(os.path.join(bench, "cells", "tiny_ssm_moe.json"), dict(
        name="tiny_ssm_moe", config="tiny-ssm-moe", traffic="tiny_docs", chips=1,
        micro_batch_per_chip=2, warm_steps=2, trace_seconds=1, why="toy cell"))
    doc["workloads"].append({"name": "tiny_ssm_moe", "config": "tiny-ssm-moe", "traffic": "tiny_docs",
                             "chips": 1, "why": "toy state-space expert cell for the CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_ssm_moe")
    tiny._dump(os.path.join(root, "BENCHMARK.json"), doc)
    return Manifest(bench_dir=bench)


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


# ------------------------------------------------------------------ the contract
def test_the_cell_and_its_entries_hold_to_the_contract():
    manifest = Manifest()
    assert check(manifest) == []
    cell, config = manifest.cell(CELL), manifest.config(CONFIG)
    assert cell["chips"] == 1 and cell["micro_batch_per_chip"] == 1 and cell["warm_steps"] == 8
    traffic = manifest.traffic(cell["traffic"])
    assert traffic["seq_len"] == 8192 and traffic["eot_token"] == config["vocab_size"] - 1 == 16383
    older = manifest.traffic("packed_docs_8k_v12544")
    assert {k: v for k, v in traffic.items() if k not in ("name", "why", "eot_token")} == \
        {k: v for k, v in older.items() if k not in ("name", "why", "eot_token")}
    # the published keys stand at the top level, as the catalog has them, and again as the
    # group the runner reads, which adds the share
    model = config["model"]
    share = ("router_width", "first_expert", "stand_in")
    assert {k: config[k] for k in model if k not in share} == {k: v for k, v in model.items() if k not in share}
    assert (model["router_width"], model["first_expert"], model["n_routed_experts"]) == (128, 0, 8)
    # the eight held experts stand in for the 120 absent ones: every assignment is computed here
    assert model["stand_in"] is True and "stand in" in config["deployment"]
    assert config["reduced"] == {"num_hidden_layers": [52, 9], "n_routed_experts": [128, 8],
                                 "vocab_size": [131072, 16384]}
    # the pattern stays whole, as published; the model runs its first nine characters
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == 52 and (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (23, 23, 6)
    assert pattern[:config["num_hidden_layers"]] == "MEMEM*EME"
    # no width is cut
    assert (config["hidden_size"], config["mamba_num_heads"], config["mamba_head_dim"],
            config["ssm_state_size"], config["n_groups"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"], config["moe_intermediate_size"],
            config["moe_shared_expert_intermediate_size"], config["num_experts_per_tok"],
            config["chunk_size"]) == (2688, 64, 64, 128, 8, 32, 2, 128, 1856, 3712, 6, 128)
    assert (config["routed_scaling_factor"], config["norm_topk_prob"], config["mlp_hidden_act"]) == \
        (2.5, True, "relu2")
    assert config["remat"] is True and config["engine"]["optimizer"]["params"] == {"lr": 1e-05}
    # the traffic as ISSUE 40 fixes it: Adam at a constant 1e-5 (no scheduler), the selection
    # biases from zero
    assert "scheduler" not in config["engine"] and config["assumed"]["router_bias_init"][1] == 0.0
    assert {"bias_update_rate", "router_aux_loss_coef", "positional_embedding", "gated_norm_groups",
            "d_inner", "group_limited_choice", "initializer_range", "A_log", "dt_bias",
            "router_bias_init"} <= set(config["assumed"])
    assert all(len(v) == 3 and len(v[2]) > 10 for v in config["assumed"].values())
    assert "second tower" in config["deployment"] and "NOT modelled" in config["deployment"]
    # the builder's own count, stated in the file
    assert flops_ssm_moe.param_count(model, config["vocab_size"]) == 666_963_456
    assert "666,963,456" in config["why_reduced"] and "10.67 GB" in config["why_reduced"]
    reported = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert set(NEW_READERS) | set(JOINED) <= reported
    assert not {"mfu.ssm", "mfu.moe", "mfu.hybrid", "expert_matmul_roofline"} & reported
    for m in manifest.doc["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"][0] == CELL and m["moves"] == "train_tokens_per_s_chip"
            assert m["unit"] == "%" and m["better"] == "higher"
    with open(os.path.join(BENCH_DIR, "reference", "nemotron_h_tolerances.json")) as f:
        limits = json.load(f)
    assert set(limits) == LIMITS | STEP_LIMITS
    assert all(v["value"] >= 0 and len(v["why"]) > 100 for v in limits.values())
    assert limits["step_bias_abs_err"]["value"] == 1e-7
    assert limits["router_bias_grad_abs_max"]["value"] == limits["step_bias_moment_abs_max"]["value"] == 0.0


def test_the_benchmark_grew_by_appended_entries_alone():
    """Entries are looked up BY NAME: the older lists are a PREFIX of the new ones and the new
    entries follow them. Nothing here asserts that these entries are the last of all."""
    doc = Manifest().doc
    names = lambda section: [e["name"] for e in doc[section]]       # noqa: E731
    assert names("workloads")[:5] == OLDER_CELLS
    assert names("configs").index(CONFIG) == 5 and names("workloads").index(CELL) == 5
    at = names("per_layer").index("exit_time_share")
    assert names("per_layer")[at + 1:at + 3] == NEW_READERS
    for m in doc["end_to_end"] + doc["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells and m["name"] not in NEW_READERS:
            before = cells[:cells.index(CELL)]
            assert before == [c for c in OLDER_CELLS if c in before] and before, m["name"]
            assert len(before) == 5 or m["name"] in JOINED, m["name"]
    assert names("end_to_end")[:2] == ["train_tokens_per_s_chip", "setup_s"]
    assert doc["run_seconds"] == 40 and doc["end_to_end"][0]["bound"] == 0.01
    assert doc["paths"] == ["benchmarks", "tests/cellbench"]
    assert sum(w["chips"] == 4 for w in doc["workloads"][:6]) == 1


def test_the_older_readers_are_handed_what_they_know():
    model = Manifest().config(CONFIG)["model"]
    sizes = train_ssm_moe.flash_sizes(model)
    assert sizes == {"n_embd": 4096, "n_layer": 1, "n_head": 32}
    fwd_flops, _ = flops.flash_required(sizes, 1, 8192, training=False)
    assert fwd_flops == 8192 * 2 * 8192 * 32 * 128
    # the scan's roofline reads Granite's key names: four mamba layers, eight groups
    ssm = train_ssm_moe.ssm_keys(model)
    assert flops_ssm.is_ssm_model(ssm) and flops_ssm.layer_kinds(ssm) == (4, 0)
    need_flops, need_bytes = flops_ssm.ssd_scan_required(ssm, 8192, training=False)
    assert need_flops == 8192 * 4 * 64 * 5 * 64 * 128
    assert need_bytes == 4 * 8192 * ((4096 + 2 * 8 * 128) * 2 + 64 * 4 + 4096 * 2)


# ------------------------------------------------------------------ the cell, toy size
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end(tiny_manifest, cpu_peaks, tmp_path, capsys, trace):
    out_dir = str(tmp_path / "out")
    result = run.run_cell("tiny_ssm_moe", SEED, 0.5, bool(trace), manifest=tiny_manifest,
                          allow_cpu=True, out_dir=out_dir)
    result = json.loads(json.dumps(result))
    with open(os.path.join(out_dir, f"tiny_ssm_moe.{SEED}.steps.json")) as f:
        record = json.load(f)
    reference = record["reference"]
    assert reference["ok"] is True, reference
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in tiny_manifest.metrics_of(section, "tiny_ssm_moe")}
    assert set(result["metrics"]) <= set(declared)
    if trace:
        # the host-clock and counter metrics are there; the device-trace ones find no device plane
        assert {"mfu.ssm_moe", "moe_load_max_over_mean", "moe_rows_here_share", "setup_compile_s",
                "step_program_variants"} <= set(result["metrics"])
        assert not {"ssm_time_share", "ssd_scan_roofline", "recompute_time_share", "moe_time_share",
                    "held_expert_matmul_roofline"} & set(result["metrics"])
        assert 0 < result["metrics"]["moe_rows_here_share"]["value"] <= 100
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert set(reference["tolerances"]) == LIMITS <= set(reference)
    assert reference["scan_rel"] < 1e-5 and reference["scan_grad_rel"] < 1e-4
    assert reference["router_bias_grad_abs_max"] == 0.0 and reference["router_wrong_choice_share"] == 0.0
    # the process's first step is the engine's own, layers recomputed, on the reference's
    # sequence; the rule moved every bias by u one way or the other, and no moment with it
    step = reference["step"]
    assert step["ok"] is True and set(step["tolerances"]) == STEP_LIMITS
    assert step["step_bias_abs_err"] <= 1e-7 and step["step_bias_moment_abs_max"] == 0.0
    assert step["biases_moved"] > 0 and step["biases_sure"] + step["biases_near_the_mean"] == 2 * 16
    # the engine started from the biases' initial zero
    assert step["bias_abs_max_at_start"] == 0.0 and max(step["load_max_over_mean_at_start"]) <= 16 / 3
    assert record["warm_losses"][0] == pytest.approx(step["reference_loss"], rel=step["step_loss_rel"] + 1e-6)
    assert np.mean(record["losses"][-10:]) < record["warm_losses"][0]
    moe = record["moe"]
    assert moe["steps_counted"] == result["attempted"] and len(moe["rows_here_by_layer"]) == 2
    assert moe["bias_abs_max"] >= moe["at_start"]["bias_abs_max"] > 0          # the rule keeps moving it
    assert set(moe["at_start"]) == set(moe["at_end"]) == {"rows_here_share", "load_max_over_mean", "bias_abs_max"}
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["window_compiles"] == 0


def test_the_record_has_what_the_readers_know(tiny_manifest, cpu_peaks, tmp_path):
    ctx_record = {}

    def keep(metric):
        reader = Manifest.reader(tiny_manifest, metric)

        def read(record):
            ctx_record.update(record)
            return reader(record)
        return read

    grown = Manifest(bench_dir=tiny_manifest.bench_dir)
    grown.reader = keep
    run.run_cell("tiny_ssm_moe", 7, 0.3, True, manifest=grown, allow_cpu=True, out_dir=str(tmp_path / "out"))
    assert {"kind", "chips", "batch_per_chip", "seq_len", "tokens_per_s_chip", "model", "vocab", "steps",
            "step_interval_ms", "dispatch_ms", "ssm_model", "ssm_moe_model", "moe"} <= set(ctx_record)
    assert ctx_record["kind"] == "train" and ctx_record["chips"] == 1
    assert ctx_record["model"] == {"n_embd": 64, "n_layer": 1, "n_head": 4}
    assert ctx_record["ssm_model"]["mamba_n_groups"] == 2


# ------------------------------------------------------------ the step check and the rule
class SteppedOnce:
    """What ``check_step`` takes of an engine, with an update of the test's choosing."""

    def __init__(self, master, update, rate=1e-5):
        self.master_params, self.update, self.rate = master, update, rate
        self.opt_state = (tree_of(np.zeros((2, 16), np.float32)), tree_of(np.zeros((2, 16), np.float32)))

    def get_lr(self):
        return [self.rate]

    def __call__(self, tokens, labels):
        return np.float32(5.0)

    def backward(self, loss):
        pass

    def step(self):
        self.master_params = self.update(self.master_params, self.rate)


def tree_of(biases, norm_f=None):
    """The toy pattern's five layers (MEM*E) with the two expert layers' biases, and one Adam leaf."""
    moe = [{"moe": {"router_bias": b}} for b in biases]
    return {"layers": [{}, moe[0], {}, {}, moe[1]], "norm_f": np.ones((32,), np.float32) if norm_f is None else norm_f}


@pytest.mark.parametrize("fault, reads", [
    (None, 0.0), ("the rule not applied", 1e-3), ("the rule applied twice", 1e-3),
    ("the bias moved by Adam", 1e-3 - 1e-5), ("the sign the other way", 2e-3)])
def test_the_step_check_reads_the_rule_on_the_references_counts(tiny_manifest, fault, reads):
    rng = np.random.default_rng(0)
    counts = np.stack([rng.permutation(16), rng.permutation(16)]).astype(np.float32) * 10   # the mean is 75
    want = 1e-3 * np.sign(counts.mean(axis=1, keepdims=True) - counts).astype(np.float32)

    def update(tree, rate):
        moved = {None: want, "the rule not applied": 0 * want, "the rule applied twice": 2 * want,
                 "the bias moved by Adam": rate * np.sign(want), "the sign the other way": -want}[fault]
        return tree_of(moved, tree["norm_f"] - rate * np.sign(rng.standard_normal(32)).astype(np.float32))

    ctx = {"config": tiny_manifest.config("tiny-ssm-moe"), "manifest": tiny_manifest}
    tokens = np.arange(64, dtype=np.int32)
    master = tree_of(np.zeros((2, 16), np.float32))
    step, _ = train_ssm_moe.check_step(ctx, SteppedOnce(master, update), tokens, tokens, 2,
                                       {"reference": counts, "apart": 4.0, "loss": 5.0, "load_max_over_mean": [2.0, 2.0]})
    assert step["step_bias_abs_err"] == pytest.approx(reads, abs=2e-8)
    assert step["biases_sure"] == 32 and step["step_update_shortfall"] < 3e-3
    assert step["ok"] is (fault is None)


def test_near_ties_are_told_apart_from_wrong_choices():
    scores = np.array([[0.9, 0.8, 0.7, 0.6995, 0.1], [0.9, 0.8, 0.7, 0.5, 0.1]])
    wide = train_ssm_moe.wide_gaps(scores, np.zeros(5), 3, 0.002)
    assert wide.tolist() == [False, True]
    # the bias takes part in the gap: it closes the second token's
    assert train_ssm_moe.wide_gaps(scores, np.array([0, 0, 0, 0.1999, 0]), 3, 0.002).tolist() == [True, False]
    got, want = np.array([[0, 1, 3], [0, 1, 3]]), np.array([[0, 1, 2], [0, 1, 2]])
    assert train_ssm_moe.choice_readings(got, want, wide) == (0.0, 0.5)
    assert train_ssm_moe.choice_readings(want, want, wide) == (1.0, 0.0)


# ------------------------------------------------------------ the new readers
@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_returns_nothing_on_nothing(name, monkeypatch):
    reader = Manifest().reader(name)
    assert reader({"setup": {}, "trace": None}) is None
    monkeypatch.setattr(ps, "program_recorder", lambda: None)
    assert reader({"setup": {}, "trace": None, "t_window_start": 5.0, "window_s": 2.0,
                   "kind": "train"}) is None
    # Qwen3-Next's record: held experts, but of three matrices, and no such model
    assert reader({"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0,
                   "model": {"n_embd": 32, "n_layer": 1, "n_head": 2},
                   "hybrid_model": {"linear_num_value_heads": 4, "full_attention_interval": 4},
                   "moe": {"rows_here_per_token": 0.6, "rows_here_by_layer": [10.0]},
                   "vocab": 256, "seq_len": 64, "device_kind": "TPU v5 lite"}) is None
    # this cell's record with no trace, or whose counters never came
    model = Manifest().config(CONFIG)["model"]
    no_rows = {"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0, "ssm_moe_model": model,
               "moe": {"rows_here_per_token": None, "rows_here_by_layer": None}, "vocab": 16384,
               "seq_len": 8192, "device_kind": "TPU v5 lite"}
    assert reader(no_rows) is None


@pytest.fixture
def recorded_moe(monkeypatch):
    """The slice recorded on the chip (GPT-2 XL, PR 24) with its MLP's scope paths renamed as
    an expert layer's would be: the products under ``ds_moe_experts``, the rest of the block's
    MLP under ``ds_moe_shared``."""
    with open(os.path.join(BENCH_DIR, "testdata", "spans_train_slice.json")) as f:
        doc = json.load(f)
    for info in doc["catalog"].values():
        for name, path in info["ops"].items():
            if "ds_mlp" in path:
                inner = "ds_moe_experts" if "dot_general" in path else "ds_moe_shared"
                info["ops"][name] = path.replace("ds_mlp", "ds_mlp/" + inner, 1)
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    monkeypatch.setattr(moe_spans, "OUT_NAME", "moe_spans.test.json")
    model = Manifest().config(CONFIG)["model"]
    return dict(doc["record"], trace=tr.Reduced(doc["trace"]), setup={}, ssm_moe_model=model, vocab=16384,
                moe={"rows_here_per_token": 0.375, "rows_here_by_layer": [3072.0] * 4})


def test_every_new_reader_reads_a_recorded_slice(recorded_moe, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, recorded_moe["device_kind"], dict(peaks.PEAKS["TPU v5 lite"]))
    manifest = Manifest()
    values = {name: manifest.reader(name)(recorded_moe) for name in NEW_READERS}
    assert all(v is not None and np.isfinite(v) and v > 0 for v in values.values()), values
    table = moe_spans.analyse(recorded_moe)
    assert table["scope_s"]["ds_moe_experts"] > 0
    # the share is the least time over the time under the scope: twice the rows, twice the share
    # (the products bound it, not the weights' bytes, at 3,072 rows)
    doubled = dict(recorded_moe, moe={"rows_here_per_token": 0.75, "rows_here_by_layer": [6144.0] * 4})
    doubled.pop("moe_spans", None)
    again = manifest.reader("held_expert_matmul_roofline")(doubled)
    assert again == pytest.approx(2 * values["held_expert_matmul_roofline"], rel=1e-6)
    assert manifest.reader("mfu.ssm_moe")(doubled) > values["mfu.ssm_moe"]
    os.remove(os.path.join(BENCH_DIR, "out", "moe_spans.test.json"))


# ------------------------------------------------------------------ the issue's counts
def test_flops_ssm_moe_against_the_issues_counts():
    model = Manifest().config(CONFIG)["model"]
    assert flops_ssm_moe.layer_kinds(model) == (4, 4, 1)
    # in_proj 2688 x 10,304 (z 4096, xBC 6144, dt 64), out_proj 4096 x 2688
    assert flops_ssm_moe.mamba_matmul_params(model) == 27_697_152 + 11_010_048
    assert flops_ssm_moe.mamba_layer_params(model) == 38_744_896
    assert flops_ssm_moe.attention_matmul_params(model) + 2688 == 23_399_040
    assert flops_ssm_moe.expert_params(model) == 9_977_856
    assert flops_ssm_moe.dense_expert_layer_params(model) == 344_064 + 19_955_712
    assert flops_ssm_moe.expert_layer_params(model) == 100_125_440
    assert flops_ssm_moe.param_count(model, 16384) == 666_963_456
    assert round(flops_ssm_moe.param_count(model, 16384) * 16 / 1e7) == 1067      # 10.67 GB of state
    even = 6 * 8 / 128                      # a token's assignments on held experts at an even router
    parts = flops_ssm_moe.forward_flops_by_part(model, 16384, 8192, even)
    rounded = {k: round(v / 1e6) for k, v in parts.items()}
    assert rounded == {"mixers": 320, "expert_layers_dense": 162, "held_experts": 30, "attention": 114, "head": 88}
    assert rounded["expert_layers_dense"] + rounded["held_experts"] == 192
    fwd = flops_ssm_moe.forward_flops_per_token(model, 16384, 8192, even)
    assert round(fwd / 1e6) == 715
    # one recomputed forward on top of forward and backward: the issue's 23.4 TFLOP a step
    assert round(4 * fwd * 8192 / 1e11) == 234
    assert flops_ssm_moe.train_flops_per_token(model, 16384, 8192, even) == 3 * fwd
    # more rows on held experts, more operations: never k
    assert flops_ssm_moe.forward_flops_per_token(model, 16384, 8192, 2 * even) - fwd == \
        pytest.approx(parts["held_experts"])
    # 384 rows an expert, 3,072 a layer: two products over them, the 16 matrices read thrice
    need_flops, need_bytes = flops_ssm_moe.held_experts_required(model, 3072, training=False)
    assert need_flops == 4 * 3072 * 2 * 2 * 2688 * 1856
    assert need_bytes == 4 * (8 * 2 * 2688 * 1856 * 2 + 2 * 3072 * 2688 * 2)
    train_flops, train_bytes = flops_ssm_moe.held_experts_required(model, 3072)
    assert train_flops == 3 * need_flops and train_bytes == 3 * need_bytes
    # the cell as it ships: the held experts stand in for the absent ones, so all six of a
    # token's assignments are computed here, 6,144 rows an expert as the exchange would bring
    parts = flops_ssm_moe.forward_flops_by_part(model, 16384, 8192, 6)
    assert round(parts["held_experts"] / 1e6) == 479
    assert round(flops_ssm_moe.forward_flops_per_token(model, 16384, 8192, 6) / 1e6) == 1164
    assert flops_ssm_moe.held_experts_required(model, 6 * 8192, training=False)[0] == 16 * need_flops


# ------------------------------------------------------------------ the limits' second readings
def test_the_probe_reads_every_fault_above_the_system(tiny_manifest):
    """``tests/perf/nemotron_h_precision_probe.py`` at the toy size: the system inside every
    limit, and the reference itself at fault in the system's place outside the limit that has
    to catch it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("nemotron_h_precision_probe", os.path.join(
        os.path.dirname(BENCH_DIR), "tests", "perf", "nemotron_h_precision_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    line, = module.probe(tiny_manifest, "tiny-ssm-moe", "tiny_docs", [SEED])
    system = line["system"]
    assert system["ok"] is True
    # the scan: a lower precision, and another group's B for a head
    for fault in ("bf16_state", "bf16_dt", "next_groups_B"):
        assert line[fault]["scan_rel"] > 10 * system["scan_rel"], fault
        assert line[fault]["scan_grad_rel"] > 10 * system["scan_grad_rel"], fault
    assert line["next_groups_B"]["scan_rel"] > 1e-3
    # (at the toy's widths D x weighs most of a mixer's output: the scan's own limit is the one
    # that tells another group's B)
    for fault, times in (("norm_over_all_channels", 3), ("next_groups_B_mixer", 2)):
        assert line[fault]["mixer_rel"] > times * system["mixer_rel"], fault
    assert line["other_key_value_head"]["attention_rel"] > 10 * system["attention_rel"]
    # the expert layer: its form, the factor, and the bias's two places
    for fault in ("relu_for_relu2", "factor_dropped", "bias_left_out_of_the_choice", "bias_let_into_the_weights"):
        assert line[fault]["expert_layer_rel"] > 3 * system["expert_layer_rel"], fault
    assert line["bias_left_out_of_the_choice"]["router_wrong_choice_share"] > 0.01
    assert line["bias_let_into_the_weights"]["router_choice_agreement"] == 1.0
    assert line["bf16_router"]["router_scores_rel"] > 100 * system["router_scores_rel"]
    assert line["relu_for_relu2"]["router_scores_rel"] == 0.0
    assert 0 <= line["adam_first_step"]["predicted_shortfall"] < 1
