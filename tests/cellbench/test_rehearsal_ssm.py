"""``granite4h_d10_train_1chip``'s runner end to end on the CPU at a toy size (whole blocks
recomputed), its record, its new readers on nothing and on a recorded trace slice, the step
check's reading of Adam's first step on a tied table, and ``flops_ssm.py`` against the
issue's counts."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops, flops_ssm, peaks, run, ssm_spans
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest, check
from benchmarks.runners import train_ssm

import tiny
from test_program_spans import Recorded

CELL = "granite4h_d10_train_1chip"
CONFIG = "granite-4.0-h-micro-d10"
NEW_READERS = ["ssm_time_share", "ssd_scan_roofline", "mfu.ssm", "recompute_time_share"]
LISTED = ["host_dispatch_ms_p50.train", "step_ms_max_over_p50.train", "device_idle_share.train",
          "engine_self_ms_p50.train", "engine_stall_ms_per_step.train", "forward_time_share",
          "backward_time_share", "optimizer_time_share", "step_program_variants",
          "step_program_load_s", "loss_time_share", "flash_fwd_roofline", "flash_bwd_roofline"]
TINY = {"attention_bias": False, "attention_multiplier": 0.0625, "embedding_multiplier": 6,
        "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
        "layer_types": ["mamba", "mamba", "attention", "mamba"], "logits_scaling": 4,
        "mamba_chunk_size": 16, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 8,
        "mamba_d_state": 16, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 8,
        "mamba_proj_bias": False, "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
        "num_attention_heads": 4, "num_experts_per_tok": 0, "num_hidden_layers": 4,
        "num_key_value_heads": 2, "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.5, "rms_norm_eps": 1e-05, "shared_intermediate_size": 48,
        "tie_word_embeddings": True, "vocab_size": 256}
SEED = 2 ** 31 + 4321


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The tiny root of ``tiny.py`` plus a toy Granite 4.0-H configuration and its one-device
    cell, added by files and entries alone."""
    root = tiny.make_root(tmp_path_factory.mktemp("cellbench_ssm"))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    tiny._dump(os.path.join(bench, "configs", "tiny-ssm.json"), dict(
        TINY, name="tiny-ssm", source="tests/cellbench/test_rehearsal_ssm.py", runner="train_ssm",
        reduced={}, model=TINY, remat=True, assumed={"initializer_range": [None, 0.1, "toy"]},
        compute_dtype="bfloat16", engine=dict(tiny.ENGINE, zero_optimization={"stage": 2}),
        reference={"module": "granite_hybrid_reference", "tolerances": "tiny_ssm_tolerances",
                   "last_positions": 16, "grad_positions": 32}))
    # toy widths in bf16 sit further from the float32 reference than 2048-wide sums do
    with open(os.path.join(bench, "reference", "granite_hybrid_tolerances.json")) as f:
        loose = {k: dict(v, value=max(v["value"], 0.2 if "grad" in k or "logits" in k else 0.1))
                 for k, v in json.load(f).items()}
    loose["scan_rel"]["value"], loose["scan_grad_rel"]["value"] = 1e-5, 1e-4
    tiny._dump(os.path.join(bench, "reference", "tiny_ssm_tolerances.json"), loose)
    doc["configs"].append({"name": "tiny-ssm", "source": "tests/cellbench/test_rehearsal_ssm.py",
                           "file": "benchmarks/configs/tiny-ssm.json", "reduced": [],
                           "why": "toy sizes for the CPU rehearsal"})
    tiny._dump(os.path.join(bench, "cells", "tiny_ssm.json"), dict(
        name="tiny_ssm", config="tiny-ssm", traffic="tiny_docs", chips=1,
        micro_batch_per_chip=2, warm_steps=2, trace_seconds=1, why="toy cell"))
    doc["workloads"].append({"name": "tiny_ssm", "config": "tiny-ssm", "traffic": "tiny_docs",
                             "chips": 1, "why": "toy state-space cell for the CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_ssm")
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
    assert traffic["seq_len"] == 8192 and traffic["eot_token"] == config["vocab_size"] - 1 == 12543
    older = manifest.traffic("packed_docs_8k")
    assert {k: v for k, v in traffic.items() if k not in ("name", "why", "eot_token")} == \
        {k: v for k, v in older.items() if k not in ("name", "why", "eot_token")}
    # the published keys stand at the top level, as the catalog has them, and again as the
    # group the runner reads
    assert {k: config[k] for k in config["model"]} == config["model"]
    assert config["reduced"] == {"num_hidden_layers": [40, 10], "vocab_size": [100352, 12544]}
    # layer_types stays whole, as published; the model runs its first num_hidden_layers entries
    assert config["layer_types"] == (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    assert config["num_hidden_layers"] == 10
    # no width is cut
    assert (config["hidden_size"], config["shared_intermediate_size"], config["mamba_n_heads"],
            config["mamba_d_head"], config["mamba_d_state"], config["num_attention_heads"],
            config["num_key_value_heads"], config["mamba_chunk_size"]) == (2048, 8192, 64, 64, 128, 32, 8, 256)
    assert (config["embedding_multiplier"], config["residual_multiplier"], config["attention_multiplier"],
            config["logits_scaling"]) == (12, 0.22, 0.015625, 8)
    assert config["remat"] is True and "activation_checkpointing" not in config["engine"]
    assert config["engine"]["optimizer"]["params"] == {"lr": 1e-05} and "scheduler" not in config["engine"]
    reported = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert set(NEW_READERS) | set(LISTED) | {"setup_compile_s"} == reported
    for m in manifest.doc["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s_chip"


def test_the_benchmark_grew_by_appended_entries_alone():
    """Entries are looked up BY NAME: the older lists are a prefix of the new ones, the new
    entries follow them in the issue's order, and the cell is the last of every ``workloads``
    list it joined. Nothing here asserts that these entries are the last of all."""
    doc = Manifest().doc
    older_cells = ["xl_d20_train_1chip", "olmoe_d4_train_4chip", "qwen3next_ep16_train_1chip"]
    names = lambda section: [e["name"] for e in doc[section]]       # noqa: E731
    assert names("configs")[:3] == ["gpt2-xl-d20", "olmoe-1b-7b-d4", "qwen3-next-80b-a3b-ep16-d4"]
    assert names("workloads")[:3] == older_cells
    assert names("configs").index(CONFIG) == 3 and names("workloads").index(CELL) == 3
    at = names("per_layer").index("moe_rows_here_share")
    assert names("per_layer")[at + 1:at + 5] == NEW_READERS
    for m in doc["end_to_end"] + doc["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells and m["name"] not in NEW_READERS:
            assert cells[:-1] == [c for c in older_cells if c in cells] and cells[-1] == CELL, m["name"]
            assert cells[:3] == older_cells or m["name"] in ("flash_fwd_roofline", "flash_bwd_roofline") \
                or len(cells) < 4, m["name"]
    assert names("end_to_end") == ["train_tokens_per_s_chip", "setup_s"]
    assert doc["run_seconds"] == 40 and doc["end_to_end"][0]["bound"] == 0.01
    assert doc["paths"] == ["benchmarks", "tests/cellbench"]
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1


def test_the_flash_readers_are_handed_exactly_the_one_attention_layer():
    model = Manifest().config(CONFIG)["model"]
    sizes = train_ssm.flash_sizes(model)
    assert sizes == {"n_embd": 2048, "n_layer": 1, "n_head": 32}
    fwd_flops, _ = flops.flash_required(sizes, 1, 8192, training=False)
    assert fwd_flops == 8192 * flops_ssm.forward_flops_by_part(model, 12544, 8192)["attention"]
    assert fwd_flops == 8192 * 2 * 8192 * 32 * 64


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end(tiny_manifest, cpu_peaks, tmp_path, capsys, trace):
    out_dir = str(tmp_path / "out")
    result = run.run_cell("tiny_ssm", SEED, 0.5, bool(trace), manifest=tiny_manifest,
                          allow_cpu=True, out_dir=out_dir)
    result = json.loads(json.dumps(result))
    with open(os.path.join(out_dir, f"tiny_ssm.{SEED}.steps.json")) as f:
        record = json.load(f)
    reference = record["reference"]
    assert reference["ok"] is True, reference
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in tiny_manifest.metrics_of(section, "tiny_ssm")}
    assert set(result["metrics"]) <= set(declared)
    if trace:
        # the host-clock metrics are there; the device-trace ones find no device plane on the CPU
        assert {"mfu.ssm", "setup_compile_s", "step_program_variants"} <= set(result["metrics"])
        assert not {"ssm_time_share", "ssd_scan_roofline", "recompute_time_share"} & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert set(reference["tolerances"]) <= set(reference)
    assert set(reference["tolerances"]) == {"train_loss_rel", "last_logits_rel", "mixer_rel", "mixer_grad_rel",
                                            "scan_rel", "scan_grad_rel", "attention_rel", "attention_grad_rel"}
    assert reference["scan_rel"] < 1e-5 and reference["scan_grad_rel"] < 1e-4
    # the process's first step is the engine's own, blocks recomputed, on the reference's sequence
    step = reference["step"]
    assert step["ok"] is True and set(step["tolerances"]) == {"step_loss_rel", "step_update_shortfall"}
    assert record["warm_losses"][0] == pytest.approx(reference["reference_loss"], rel=step["step_loss_rel"] + 1e-6)
    assert np.mean(record["losses"][-10:]) < record["warm_losses"][0]
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["window_compiles"] == 0


class SteppedOnce:
    """What ``check_step`` takes of an engine, with an update of the test's choosing."""

    def __init__(self, master, update, rate=1e-5):
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
    ("no bias correction", 0.1 / np.sqrt(0.001) - 1),
    ("the table moved as an embedding alone", 1 - np.sqrt(40 / 256))])
def test_the_step_check_reads_adams_first_step_on_a_tied_table(tiny_manifest, fault, reads):
    rng = np.random.default_rng(0)
    master = {"embed": rng.standard_normal((256, 32)).astype(np.float32) * 0.1,
              "norm_f": np.ones((32,), np.float32)}
    tokens = np.resize(np.arange(40, dtype=np.int32), 64)

    def update(name, p, rate):
        sign = np.sign(rng.standard_normal(p.shape)).astype(np.float32)
        if name == "embed" and fault == "the table moved as an embedding alone":
            sign[~np.isin(np.arange(256), tokens)] = 0.0          # the head's use lost
        if name == "norm_f":
            sign *= {"a leaf's gradient lost": 0.0, "the rate applied twice": 2.0,
                     "no bias correction": 0.1 / np.sqrt(0.001)}.get(fault, 1.0)
        return p - rate * sign

    ctx = {"config": tiny_manifest.config("tiny-ssm"), "manifest": tiny_manifest}
    step, loss = train_ssm.check_step(ctx, SteppedOnce(master, update), tokens, tokens, 2, 5.001)
    assert loss == 5.0 and step["step_loss_rel"] == pytest.approx(0.001 / 5.001, rel=1e-3)
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
    run.run_cell("tiny_ssm", 7, 0.3, True, manifest=grown, allow_cpu=True, out_dir=str(tmp_path / "out"))
    assert {"kind", "chips", "batch_per_chip", "seq_len", "tokens_per_s_chip", "model", "vocab",
            "steps", "step_interval_ms", "dispatch_ms", "ssm_model"} <= set(ctx_record)
    assert ctx_record["kind"] == "train" and ctx_record["chips"] == 1
    assert ctx_record["model"] == {"n_embd": 32, "n_layer": 1, "n_head": 4}


# ------------------------------------------------------------ the new readers
@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_returns_nothing_on_nothing(name, monkeypatch):
    reader = Manifest().reader(name)
    assert reader({"setup": {}, "trace": None}) is None
    monkeypatch.setattr(ps, "program_recorder", lambda: None)
    assert reader({"setup": {}, "trace": None, "t_window_start": 5.0, "window_s": 2.0,
                   "kind": "train"}) is None
    # Qwen3-Next's record: linear attention, but no state-space layer and no ds_ssm scope
    assert reader({"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0,
                   "model": {"n_embd": 32, "n_layer": 1, "n_head": 2},
                   "hybrid_model": {"linear_num_value_heads": 4, "full_attention_interval": 4},
                   "vocab": 256, "seq_len": 64, "device_kind": "TPU v5 lite"}) is None


@pytest.fixture
def recorded_ssm(monkeypatch):
    """The slice recorded on the chip (GPT-2 XL, PR 24) with its attention's scope paths
    renamed as a Mamba-2 mixer's would be: everything under ``ds_ssm``, the flash kernels'
    operations also under ``ds_ssd_scan``, and the forward's attention named as made again."""
    with open(os.path.join(BENCH_DIR, "testdata", "spans_train_slice.json")) as f:
        doc = json.load(f)
    for info in doc["catalog"].values():
        for name, path in info["ops"].items():
            if "ds_attn" in path:
                inner = "ds_ssm/ds_ssd_scan" if "ds_flash" in path else "ds_ssm"
                again = "" if "transpose(" in path else "checkpoint/rematted_computation/"
                info["ops"][name] = path.replace("ds_attn", again + "ds_attn/" + inner, 1)
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    model = Manifest().config(CONFIG)["model"]
    return dict(doc["record"], trace=tr.Reduced(doc["trace"]), setup={}, ssm_model=model, vocab=12544)


def test_every_new_reader_reads_a_recorded_slice(recorded_ssm, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, recorded_ssm["device_kind"], dict(peaks.PEAKS["TPU v5 lite"]))
    manifest = Manifest()
    values = {name: manifest.reader(name)(recorded_ssm) for name in NEW_READERS}
    assert all(v is not None and np.isfinite(v) for v in values.values()), values
    table = ssm_spans.analyse(recorded_ssm)
    assert set(table["scope_s"]) == {"ds_ssm", "ds_ssd_scan", "rematted_computation"}
    rows = ps.analyse(recorded_ssm)["trace"]["device_s"]
    attn_s = sum(v for phase, part, _, v in rows if part == "ds_attn")
    forward_attn_s = sum(v for phase, part, _, v in rows if part == "ds_attn" and phase == "forward")
    assert table["scope_s"]["ds_ssm"] == pytest.approx(attn_s, rel=0.02)
    assert table["scope_s"]["rematted_computation"] == pytest.approx(forward_attn_s, rel=0.02)
    assert 0 < table["scope_s"]["ds_ssd_scan"] < table["scope_s"]["ds_ssm"]
    assert 0 < values["recompute_time_share"] < values["ssm_time_share"] < 100
    assert values["ssd_scan_roofline"] > 0 and values["mfu.ssm"] > 0


# ------------------------------------------------------------------ the issue's counts
def test_flops_ssm_against_the_issues_counts():
    model = Manifest().config(CONFIG)["model"]
    assert flops_ssm.layer_kinds(model) == (9, 1)
    # in_proj 2048 x 8512 (z 4096, xBC 4352, dt 64), out_proj 4096 x 2048
    assert flops_ssm.mamba_matmul_params(model) == 17_432_576 + 8_388_608
    # + convolution 4352 x 4 + 4352, dt_bias, A_log, D 3 x 64, the gated norm 4096
    assert flops_ssm.mamba_mixer_params(model) == 25_847_232
    assert flops_ssm.attention_params(model) == 10_485_760
    assert flops_ssm.mlp_params(model) == 50_331_648
    assert flops_ssm.layer_params(model, "mamba") == 76_182_976
    assert flops_ssm.layer_params(model, "attention") == 60_821_504
    assert flops_ssm.param_count(model, 12544) == 772_160_448
    assert round(flops_ssm.param_count(model, 12544) * 16 / 1e7) == 1235        # 12.35 GB of state
    parts = flops_ssm.forward_flops_by_part(model, 12544, 8192)
    assert parts["scan"] == 9 * 64 * 5 * 64 * 128
    assert parts["convolution"] == 9 * 2 * 4 * 4352
    assert parts["attention"] == 2 * 8192 * 2048
    assert parts["projections"] == 2 * (9 * 25_821_184 + 10_485_760)
    assert parts["mlp"] == 2 * 10 * 50_331_648 and parts["head"] == 2 * 12544 * 2048
    fwd = flops_ssm.forward_flops_per_token(model, 12544, 8192)
    assert fwd == sum(parts.values()) == 1_601_226_752
    # the issue's 1.653 GFLOP a token counts the scan at the CHUNKED form's 8.4 MFLOP a token
    # and layer where this file, as the issue asks, counts the recurrence's 2.62
    assert round((fwd - parts["scan"] + 9 * 8.4e6) / 1e6) == 1653
    assert flops_ssm.train_flops_per_token(model, 12544, 8192) == 3 * fwd
    # the nine mixers are a third of the forward, the one attention layer's flash 2 %
    mixers = 2 * 9 * 25_821_184 + parts["scan"] + parts["convolution"]
    assert 0.29 < mixers / fwd < 0.34 and 0.02 < parts["attention"] / fwd < 0.022
    need_flops, need_bytes = flops_ssm.ssd_scan_required(model, 8192, training=False)
    assert need_flops == 8192 * parts["scan"]
    per_token = 4352 * 2 + 64 * 4 + 4096 * 2                   # x, B, C in bf16; dt; y
    assert need_bytes == 9 * 8192 * per_token
    train_flops, train_bytes = flops_ssm.ssd_scan_required(model, 8192)
    assert train_flops == 3 * need_flops
    assert train_bytes == need_bytes + 9 * 8192 * (per_token + per_token - 4096 * 2)
    assert flops_ssm.is_ssm_model(model) and not flops_ssm.is_ssm_model({"n_embd": 1600})


def test_the_probe_reads_every_fault_above_the_system(tiny_manifest):
    """``tests/perf/granite_hybrid_precision_probe.py`` at the toy size: the system inside every
    limit; the reference's own scan with a bfloat16 state or a bfloat16 step outside the
    scan's limits; every other fault (norm before gate, no ``D`` skip, the head-width scale, a
    multiplier left at 1) further from the reference than the system is."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("granite_hybrid_precision_probe", os.path.join(
        os.path.dirname(BENCH_DIR), "tests", "perf", "granite_hybrid_precision_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    line, = module.probe(tiny_manifest, "tiny-ssm", "tiny_docs", [SEED])
    system, tol = line["system"], line["system"]["tolerances"]
    assert system["ok"] is True
    for name in ("scan_rel", "scan_grad_rel"):
        assert line["bf16_state"][name] > tol[name] > system[name]
    assert line["bf16_dt"]["scan_rel"] > tol["scan_rel"]
    for fault in ("norm_before_gate", "no_D_skip"):
        assert line[fault]["mixer_rel"] > 3 * system["mixer_rel"], fault
    assert line["head_width_scale"]["attention_rel"] > 3 * system["attention_rel"]
    for name in module.MULTIPLIERS:
        assert line["no_" + name]["last_logits_rel"] > 3 * system["last_logits_rel"], name
    first = line["adam_first_step"]
    assert 0 < first["moved_over_rate"] <= 1 and first["predicted_shortfall"] == 1 - first["moved_over_rate"]
