"""``qwen3next_ep16_train_1chip``'s runner end to end on the CPU at a toy size (one device,
4 of 16 experts held), its record, its new readers on nothing and on a recorded trace
slice, and ``flops_hybrid.py`` against hand counts."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops, flops_hybrid, hybrid_spans, peaks, run
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest, check
from benchmarks.runners import train_hybrid

import tiny
from test_program_spans import Recorded

CELL = "qwen3next_ep16_train_1chip"
CONFIG = "qwen3-next-80b-a3b-ep16-d4"
NEW_READERS = ["lin_attn_time_share", "delta_rule_roofline", "mfu.hybrid", "moe_rows_here_share"]
LISTED = ["host_dispatch_ms_p50.train", "step_ms_max_over_p50.train", "device_idle_share.train",
          "engine_self_ms_p50.train", "engine_stall_ms_per_step.train", "forward_time_share",
          "backward_time_share", "optimizer_time_share", "step_program_variants",
          "step_program_load_s", "loss_time_share", "moe_time_share", "moe_load_max_over_mean",
          "flash_fwd_roofline", "flash_bwd_roofline"]
TINY = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 16, "hidden_act": "silu",
        "hidden_size": 32, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 8,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_value_head_dim": 8,
        "mlp_only_layers": [], "model_type": "qwen3_next", "moe_intermediate_size": 16,
        "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4, "router_width": 16,
        "first_expert": 4, "num_experts_per_tok": 4, "num_hidden_layers": 4,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 16,
        "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 256}
SEED = 2 ** 31 + 4321


def toy_schedule():
    """The configuration's warm-up of the rate, at rates that move a toy model's loss past
    its batches' noise within the rehearsal's few steps."""
    schedule = Manifest().config(CONFIG)["engine"]["scheduler"]
    return dict(schedule, params=dict(schedule["params"], cycle_min_lr=1e-3, cycle_max_lr=5e-3))


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The tiny root of ``tiny.py`` plus a toy Qwen3-Next configuration and its one-device
    cell, added by files and entries alone."""
    root = tiny.make_root(tmp_path_factory.mktemp("cellbench_hybrid"))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    tiny._dump(os.path.join(bench, "configs", "tiny-hybrid.json"), dict(
        TINY, name="tiny-hybrid", source="tests/cellbench/test_rehearsal_hybrid.py",
        runner="train_hybrid", reduced={}, model=TINY, router_aux_loss_coef=0.001,
        assumed={"initializer_range": [None, 0.1, "toy"]}, compute_dtype="bfloat16",
        engine=dict(tiny.ENGINE, zero_optimization={"stage": 2}, scheduler=toy_schedule()),
        reference={"module": "qwen3_next_reference", "tolerances": "tiny_hybrid_tolerances",
                   "last_positions": 16, "grad_positions": 32}))
    # toy widths in bf16 sit further from the float32 reference than 2048-wide sums do
    with open(os.path.join(bench, "reference", "qwen3_next_tolerances.json")) as f:
        loose = {k: dict(v, value={"expert_agreement": 0.5, "router_choice_agreement": 0.9}.get(
            k, max(v["value"], 0.2 if "grad" in k or "logits_rel" == k[-10:] else 0.1)))
            for k, v in json.load(f).items()}
    loose["router_logits_rel"]["value"] = 1e-5
    loose["delta_rule_rel"]["value"], loose["delta_rule_grad_rel"]["value"] = 1e-5, 1e-4
    tiny._dump(os.path.join(bench, "reference", "tiny_hybrid_tolerances.json"), loose)
    doc["configs"].append({"name": "tiny-hybrid", "source": "tests/cellbench/test_rehearsal_hybrid.py",
                           "file": "benchmarks/configs/tiny-hybrid.json", "reduced": [],
                           "why": "toy sizes for the CPU rehearsal"})
    tiny._dump(os.path.join(bench, "cells", "tiny_hybrid.json"), dict(
        name="tiny_hybrid", config="tiny-hybrid", traffic="tiny_docs", chips=1,
        micro_batch_per_chip=2, warm_steps=2, trace_seconds=1, why="toy cell"))
    doc["workloads"].append({"name": "tiny_hybrid", "config": "tiny-hybrid", "traffic": "tiny_docs",
                             "chips": 1, "why": "toy hybrid cell for the CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_hybrid")
    tiny._dump(os.path.join(root, "BENCHMARK.json"), doc)
    return Manifest(bench_dir=bench)


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def test_the_cell_and_its_entries_hold_to_the_contract():
    manifest = Manifest()
    assert check(manifest) == []
    cell, config = manifest.cell(CELL), manifest.config(CONFIG)
    assert cell["chips"] == 1 and cell["micro_batch_per_chip"] == 1
    traffic = manifest.traffic(cell["traffic"])
    assert traffic["seq_len"] == 8192 and traffic["eot_token"] == config["vocab_size"] - 1
    # the published keys stand at the top level, as the catalog has them, and again (with
    # the share's two keys) as the group the runner reads
    assert {k: config[k] for k in config["model"]} == config["model"]
    assert config["reduced"] == {"num_hidden_layers": [48, 4], "num_experts": [512, 32],
                                 "vocab_size": [151936, 18992]}
    assert (config["router_width"], config["first_expert"]) == (512, 0)
    # no width is cut
    assert (config["hidden_size"], config["head_dim"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["linear_key_head_dim"]) == (2048, 256, 512, 10, 128)
    reported = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert set(NEW_READERS) | set(LISTED) | {"setup_compile_s"} == reported
    # those count k experts a token, every expert's matrix, or n_layer x n_embd of attention
    assert not {"mfu", "mfu.moe", "expert_matmul_roofline", "flash_time_share", "flash_roofline",
                "collective_exposed_share", "moe_exchange_share"} & reported
    for m in manifest.doc["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s_chip"


def test_the_benchmark_grew_by_appended_entries_alone():
    """The configuration, the cell and the four metrics are the last of their lists, in the
    issue's order, and the cell is the last of every ``workloads`` list it joined: nothing that
    was there moved. (``test_loss_time_share.py``'s check that ITS entry is the last one with
    exactly two cells fails from the first PR that appends a metric or a cell; a ``model_config``
    PR may not edit that file, PERF.md section 7.)"""
    doc = Manifest().doc
    assert doc["configs"][-1]["name"] == CONFIG and doc["workloads"][-1]["name"] == CELL
    assert [c["name"] for c in doc["configs"][:-1]] == ["gpt2-xl-d20", "olmoe-1b-7b-d4"]
    assert [w["name"] for w in doc["workloads"][:-1]] == ["xl_d20_train_1chip", "olmoe_d4_train_4chip"]
    assert [m["name"] for m in doc["per_layer"][-4:]] == NEW_READERS
    assert doc["per_layer"][-5] == {
        "name": "loss_time_share", "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "model step", "moves": "train_tokens_per_s_chip",
        "workloads": ["xl_d20_train_1chip", "olmoe_d4_train_4chip", CELL]}
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
    assert [m["name"] for m in doc["end_to_end"]] == ["train_tokens_per_s_chip", "setup_s"]
    assert doc["run_seconds"] == 40 and doc["end_to_end"][0]["bound"] == 0.01


def test_the_flash_readers_are_handed_exactly_the_one_full_attention_layer():
    """``flops.flash_required`` counts ``n_layer x n_embd``: the record's ``model`` says one
    layer of 16 heads x 256, and the count is this model's causal QK^T and PV."""
    runner = Manifest()._module("runners", "train_hybrid")
    model = Manifest().config(CONFIG)["model"]
    sizes = runner.flash_sizes(model)
    assert sizes == {"n_embd": 4096, "n_layer": 1, "n_head": 16}
    fwd_flops, _ = flops.flash_required(sizes, 1, 8192, training=False)
    assert fwd_flops == 8192 * 2 * 8192 * 16 * 256
    assert fwd_flops == 8192 * flops_hybrid.attention_flops_per_token_fwd(model, 8192)
    assert flops.flash_required(sizes, 1, 8192)[0] == 3 * fwd_flops


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end(tiny_manifest, cpu_peaks, tmp_path, capsys, trace):
    out_dir = str(tmp_path / "out")
    result = run.run_cell("tiny_hybrid", SEED, 0.5, bool(trace), manifest=tiny_manifest,
                          allow_cpu=True, out_dir=out_dir)
    result = json.loads(json.dumps(result))
    with open(os.path.join(out_dir, f"tiny_hybrid.{SEED}.steps.json")) as f:
        record = json.load(f)
    reference = record["reference"]
    assert reference["ok"] is True, reference
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in tiny_manifest.metrics_of(section, "tiny_hybrid")}
    assert set(result["metrics"]) <= set(declared)
    if trace:
        # the counters and the host-clock metrics are there; the device-trace ones find
        # no device plane on the CPU
        assert {"mfu.hybrid", "moe_rows_here_share", "moe_load_max_over_mean",
                "setup_compile_s"} <= set(result["metrics"])
        assert 0 < result["metrics"]["moe_rows_here_share"]["value"] <= 100
        assert not {"lin_attn_time_share", "delta_rule_roofline", "moe_time_share"} & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert set(reference["tolerances"]) <= set(reference)
    # the process's first step is the engine's own, on the reference's sequence
    step = reference["step"]
    assert step["ok"] is True and set(step["tolerances"]) == {"step_loss_rel", "step_update_shortfall"}
    assert record["warm_losses"][0] == pytest.approx(reference["reference_loss"], rel=step["step_loss_rel"] + 1e-6)
    assert "step_loss_rel" not in reference["tolerances"]
    assert reference["delta_rule_rel"] < 1e-5 and reference["router_logits_rel"] < 1e-5
    moe = record["moe"]
    assert moe["steps_counted"] == result["attempted"]
    assert len(moe["rows_here_by_layer"]) == len(moe["load_max_over_mean_by_layer"]) == 4
    # 2 x 64 tokens x 4 choices a layer, a quarter of the experts held
    assert moe["rows_here_share"] == pytest.approx(np.mean(moe["rows_here_by_layer"]) / 512)
    assert moe["rows_here_per_token"] == pytest.approx(4 * moe["rows_here_share"])
    assert record["losses"][-1] < record["warm_losses"][0]
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["window_compiles"] == 0 and summary["moe"]["steps_counted"] > 0


class SteppedOnce:
    """What ``check_step`` takes of an engine, with an update of the test's choosing."""

    def __init__(self, master, update, rate=2e-6):
        self.master_params, self.update, self.rate = master, update, rate

    def get_lr(self):
        return [self.rate]

    def __call__(self, tokens, labels):
        assert tokens.shape == labels.shape == (2, 64)
        return np.float32(5.0)

    def backward(self, loss):
        pass

    def step(self):
        self.master_params = {k: self.update(k, v, self.rate) for k, v in self.master_params.items()}


@pytest.mark.parametrize("fault, reads", [
    (None, 0.0), ("a leaf's gradient lost", 1.0), ("the rate applied twice", 1.0),
    ("no bias correction", 0.1 / np.sqrt(0.001) - 1), ("the whole embedding moved", np.sqrt(256 / 40) - 1)])
def test_the_step_check_reads_adams_first_step(tiny_manifest, fault, reads):
    rng = np.random.default_rng(0)
    master = {"embed": rng.standard_normal((256, 32)).astype(np.float32) * 0.1,
              "head": rng.standard_normal((256, 32)).astype(np.float32) * 0.1}
    tokens = np.resize(np.arange(40, dtype=np.int32), 64)

    def update(name, p, rate):
        sign = np.sign(rng.standard_normal(p.shape)).astype(np.float32)
        if name == "embed" and fault != "the whole embedding moved":
            sign[~np.isin(np.arange(256), tokens)] = 0.0
        if name == "head":
            sign *= {"a leaf's gradient lost": 0.0, "the rate applied twice": 2.0,
                     "no bias correction": 0.1 / np.sqrt(0.001)}.get(fault, 1.0)
        return p - rate * sign

    ctx = {"config": tiny_manifest.config("tiny-hybrid"), "manifest": tiny_manifest}
    step, loss = train_hybrid.check_step(ctx, SteppedOnce(master, update), tokens, tokens, 2, 5.001)
    assert loss == 5.0 and step["step_loss_rel"] == pytest.approx(0.001 / 5.001, rel=1e-3)
    assert step["tokens_seen"] == 40
    assert step["step_update_shortfall"] == pytest.approx(reads, rel=3e-3, abs=2e-3)
    assert step["ok"] is (fault is None)


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
    run.run_cell("tiny_hybrid", 7, 0.3, True, manifest=grown, allow_cpu=True,
                 out_dir=str(tmp_path / "out"))
    assert {"kind", "chips", "batch_per_chip", "seq_len", "tokens_per_s_chip", "model", "vocab",
            "steps", "step_interval_ms", "dispatch_ms", "moe", "hybrid_model"} <= set(ctx_record)
    assert ctx_record["kind"] == "train" and ctx_record["chips"] == 1
    assert ctx_record["model"] == {"n_embd": 64, "n_layer": 1, "n_head": 4}


# ------------------------------------------------------------ the new readers
@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_returns_nothing_on_nothing(name, monkeypatch):
    reader = Manifest().reader(name)
    assert reader({"setup": {}, "trace": None}) is None
    monkeypatch.setattr(ps, "program_recorder", lambda: None)
    assert reader({"setup": {}, "trace": None, "t_window_start": 5.0, "window_s": 2.0,
                   "kind": "train"}) is None
    # OLMoE's record: experts, but no linear layer in the model, no ds_lin_attn scope
    assert reader({"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0,
                   "model": {"n_embd": 32, "n_layer": 2, "n_head": 2, "num_experts": 8},
                   "vocab": 256, "seq_len": 64, "device_kind": "TPU v5 lite",
                   "moe": {"load_max_over_mean": 1.5}}) is None


@pytest.fixture
def recorded_hybrid(monkeypatch):
    """The slice recorded on the chip (GPT-2 XL, PR 24) with its attention's scope paths
    renamed as a delta-rule mixer's would be: everything under ``ds_lin_attn``, the flash
    kernels' operations also under ``ds_delta_rule``."""
    with open(os.path.join(BENCH_DIR, "testdata", "spans_train_slice.json")) as f:
        doc = json.load(f)
    for info in doc["catalog"].values():
        for name, path in info["ops"].items():
            if "ds_attn" in path:
                inner = "ds_lin_attn/ds_delta_rule" if "ds_flash" in path else "ds_lin_attn"
                info["ops"][name] = path.replace("ds_attn", "ds_attn/" + inner, 1)
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    model = Manifest().config(CONFIG)["model"]
    return dict(doc["record"], trace=tr.Reduced(doc["trace"]), setup={}, hybrid_model=model,
                vocab=18992, moe={"rows_here_share": 0.0625, "rows_here_per_token": 0.625})


def test_every_new_reader_reads_a_recorded_slice(recorded_hybrid, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, recorded_hybrid["device_kind"], dict(peaks.PEAKS["TPU v5 lite"]))
    manifest = Manifest()
    values = {name: manifest.reader(name)(recorded_hybrid) for name in NEW_READERS}
    assert all(v is not None and np.isfinite(v) for v in values.values()), values
    table = hybrid_spans.analyse(recorded_hybrid)
    assert set(table["scope_s"]) == {"ds_lin_attn", "ds_delta_rule"}
    # the renamed operations are the attention's: the same seconds the phase x part table has
    attn_s = sum(v for phase, part, _, v in ps.analyse(recorded_hybrid)["trace"]["device_s"]
                 if part == "ds_attn")
    assert table["scope_s"]["ds_lin_attn"] == pytest.approx(attn_s, rel=0.02)
    assert 0 < table["scope_s"]["ds_delta_rule"] < table["scope_s"]["ds_lin_attn"]
    assert 0 < values["lin_attn_time_share"] < 100 and values["delta_rule_roofline"] > 0
    assert values["moe_rows_here_share"] == 6.25


# ------------------------------------------------------------------ hand counts
def test_flops_hybrid_against_hand_counts():
    model = Manifest().config(CONFIG)["model"]
    assert flops_hybrid.layer_kinds(model) == (3, 1)
    # a delta-rule mixer: Wqkvz 2048 x 12,288, Wba 2048 x 64, Wout 4096 x 2048
    assert flops_hybrid.linear_mixer_params(model) == 25_165_824 + 131_072 + 8_388_608
    # the gated attention: Wq 2048 x 8192, Wk and Wv 2048 x 512 each, Wo 4096 x 2048
    assert flops_hybrid.full_attention_params(model) == 16_777_216 + 2_097_152 + 8_388_608
    # router over all 512, the shared expert and its gate; one routed expert
    assert flops_hybrid.dense_params_per_layer(model) == 1_048_576 + 3_145_728 + 2048
    assert flops_hybrid.expert_params(model) == 3_145_728
    # the issue's reckoning: 625.7 M parameters here
    assert round(flops_hybrid.param_count(model, 18992) / 1e5) == 6257
    head = 18992 * 2048
    dense = 3 * 33_685_504 + 27_262_976 + 4 * 4_196_352 + head
    assert flops_hybrid.matmul_params(model, 18992, 0.0) == dense
    assert flops_hybrid.matmul_params(model, 18992, 0.625) == dense + 4 * 0.625 * 3_145_728
    # a token and value head: 7 x 128 x 128 forward; 32 heads, 3 layers
    assert flops_hybrid.delta_rule_flops_per_token_fwd(model) == 3 * 32 * 7 * 128 * 128
    assert flops_hybrid.attention_flops_per_token_fwd(model, 8192) == 2 * 8192 * 4096
    assert flops_hybrid.conv_flops_per_token_fwd(model) == 3 * 2 * 4 * 8192
    fwd = flops_hybrid.forward_flops_per_token(model, 18992, 8192, 0.625)
    assert fwd == 2 * (dense + 7_864_320) + 67_108_864 + 11_010_048 + 196_608
    assert flops_hybrid.train_flops_per_token(model, 18992, 8192, 0.625) == 3 * fwd
    assert 11.0e12 < 3 * fwd * 8192 < 12.0e12            # the issue's 11.5 TFLOP a step
    need_flops, need_bytes = flops_hybrid.delta_rule_required(model, 8192, training=False)
    assert need_flops == 8192 * 11_010_048
    per_token = (2 * 2048 + 4096) * 2 + 2 * 32 * 4 + 4096 * 2      # q, k, v; g, beta; o
    assert need_bytes == 3 * 8192 * per_token
    train_flops, train_bytes = flops_hybrid.delta_rule_required(model, 8192)
    assert train_flops == 3 * need_flops
    assert train_bytes == need_bytes + 3 * 8192 * (per_token + per_token - 4096 * 2)
    assert flops_hybrid.is_hybrid_model(model) and not flops_hybrid.is_hybrid_model({"n_embd": 1600})


def test_the_limits_on_one_layer_fail_the_precision_below(tiny_manifest):
    """``tests/perf/qwen3_next_precision_probe.py`` at the toy size: the system inside every
    limit; the reference's own delta rule with a bfloat16 state outside the rule's limits,
    its router in bfloat16 outside the router's."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("qwen3_next_precision_probe", os.path.join(
        os.path.dirname(BENCH_DIR), "tests", "perf", "qwen3_next_precision_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    line, = module.probe(tiny_manifest, "tiny-hybrid", "tiny_docs", [SEED])
    tol = line["system"]["tolerances"]
    assert line["system"]["ok"] is True
    for name in ("delta_rule_rel", "delta_rule_grad_rel"):
        assert line["bf16_state"][name] > tol[name] > line["system"][name]
    assert line["bf16_router"]["router_logits_rel"] > tol["router_logits_rel"]
