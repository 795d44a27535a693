"""``ouro_d6_train_1chip``'s runner end to end on the CPU at a toy size (whole blocks recomputed,
four passes on one set of weights), its record, its new readers on nothing and on a recorded
trace slice, ``flops_loop.py`` against the issue's counts, and the precision probe's faults."""

import importlib.util
import json
import os

import numpy as np
import pytest

from benchmarks import flops, flops_loop, loop_spans, peaks, run
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest, check
from benchmarks.runners import train_loop

import tiny
from test_program_spans import Recorded

CELL = "ouro_d6_train_1chip"
CONFIG = "ouro-2.6b-d6"
NEW_READERS = ["mfu.loop", "loop_time_share", "loop_recompute_time_share", "exit_time_share"]
LISTED = ["flash_time_share", "host_dispatch_ms_p50.train", "step_ms_max_over_p50.train", "device_idle_share.train",
          "engine_self_ms_p50.train", "engine_stall_ms_per_step.train", "forward_time_share",
          "backward_time_share", "optimizer_time_share", "flash_fwd_roofline", "flash_bwd_roofline",
          "step_program_variants", "step_program_load_s", "loss_time_share", "engine_cpu_ms_p50.train",
          "steps_in_flight_p50.train", "launch_lead_ms_p10.train", "hbm_in_use_share_max.train"]
LIMITS = {"train_loss_rel", "exit_ce_rel", "exit_p_abs", "last_logits_rel", "pass_rel", "pass_grad_rel",
          "shared_grad_rel", "shared_pass_weight_abs", "head_ce_rel", "head_grad_rel", "exit_alone_abs"}
TINY = {"head_dim": 8, "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
        "layer_types": ["full_attention"] * 4, "max_position_embeddings": 64, "model_type": "ouro",
        "num_attention_heads": 4, "num_hidden_layers": 1, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False, "vocab_size": 250}
SEED = 2 ** 31 + 4321


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The tiny root of ``tiny.py`` plus a toy Ouro configuration and its one-device cell,
    added by files and entries alone."""
    root = tiny.make_root(tmp_path_factory.mktemp("cellbench_loop"))
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    tiny._dump(os.path.join(bench, "configs", "tiny-loop.json"), dict(
        TINY, name="tiny-loop", source="tests/cellbench/test_rehearsal_loop.py", runner="train_loop",
        reduced={}, model=TINY, remat=True, exit_entropy_coef=0.05,
        assumed={"initializer_range": [None, 0.1, "toy"]}, compute_dtype="bfloat16",
        engine=dict(tiny.ENGINE, zero_optimization={"stage": 2}),
        reference={"module": "ouro_reference", "tolerances": "tiny_loop_tolerances",
                   "last_positions": 16, "grad_positions": 32}))
    # toy widths in bf16 sit further from the float32 reference than 2048-wide sums do
    with open(os.path.join(bench, "reference", "ouro_tolerances.json")) as f:
        loose = {k: dict(v, value=max(v["value"], 0.25 if "grad" in k or "logits" in k else 0.1))
                 for k, v in json.load(f).items()}
    tiny._dump(os.path.join(bench, "reference", "tiny_loop_tolerances.json"), loose)
    doc["configs"].append({"name": "tiny-loop", "source": "tests/cellbench/test_rehearsal_loop.py",
                           "file": "benchmarks/configs/tiny-loop.json", "reduced": [],
                           "why": "toy sizes for the CPU rehearsal"})
    tiny._dump(os.path.join(bench, "cells", "tiny_loop.json"), dict(
        name="tiny_loop", config="tiny-loop", traffic="tiny_docs", chips=1,
        micro_batch_per_chip=2, warm_steps=2, trace_seconds=1, why="toy cell"))
    doc["workloads"].append({"name": "tiny_loop", "config": "tiny-loop", "traffic": "tiny_docs",
                             "chips": 1, "why": "toy looped cell for the CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_loop")
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
    assert cell["chips"] == 1 and cell["micro_batch_per_chip"] == 2
    assert (cell["warm_steps"], cell["trace_seconds"]) == (8, 12)
    traffic = manifest.traffic(cell["traffic"])
    assert traffic["seq_len"] == 4096 and traffic["eot_token"] == config["vocab_size"] - 1 == 49151
    older = manifest.traffic("packed_docs_4k")
    assert {k: v for k, v in traffic.items() if k not in ("name", "why", "eot_token")} == \
        {k: v for k, v in older.items() if k not in ("name", "why", "eot_token")}
    # the published keys stand at the top level, as the catalog has them, and again as the
    # group the runner reads
    assert {k: config[k] for k in config["model"]} == config["model"]
    assert config["reduced"] == {"num_hidden_layers": [48, 6]}
    # layer_types stays whole, as published; the model runs its first num_hidden_layers entries
    assert config["layer_types"] == ["full_attention"] * 48 and config["num_hidden_layers"] == 6
    # no width is cut, and the vocabulary is whole
    assert (config["hidden_size"], config["intermediate_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"], config["vocab_size"]) == (2048, 5632, 16, 16, 128, 49152)
    assert (config["total_ut_steps"], config["early_exit_threshold"], config["rope_theta"],
            config["rms_norm_eps"], config["tie_word_embeddings"]) == (4, 1, 1000000, 1e-06, False)
    assert config["remat"] is True and "activation_checkpointing" not in config["engine"]
    assert config["engine"]["optimizer"]["params"] == {"lr": 1e-05} and "scheduler" not in config["engine"]
    assert config["params"] == 509_661_185 == flops_loop.param_count(config["model"], 49152)
    assert config["training_state_gb"] == round(config["params"] * 16 / 1e9, 2) == 8.15
    for name, entry in config["assumed"].items():
        assert len(entry) == 3 and entry[0] is None and entry[2], name        # each with its reason
    assert {"sandwich_norms", "norm_f_carried", "exit_gate", "training_loss", "exit_entropy_coef",
            "initializer_range", "gate_init", "eos_token_id"} <= set(config["assumed"])
    assert config["exit_entropy_coef"] == config["assumed"]["exit_entropy_coef"][1] == 0.05
    entry = next(c for c in manifest.doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] and len(entry["why"]) <= 200
    assert len(manifest.workload(CELL)["why"]) <= 200
    reported = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert set(NEW_READERS) | set(LISTED) | {"setup_compile_s"} <= reported
    assert "recompute_time_share" not in reported        # its reader needs an operation under ds_ssm
    for m in manifest.doc["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"][0] == CELL and m["moves"] == "train_tokens_per_s_chip"
    with open(os.path.join(BENCH_DIR, "reference", "ouro_tolerances.json")) as f:
        limits = json.load(f)
    assert set(limits) == LIMITS | {"step_loss_rel", "step_update_shortfall"}
    assert all(v["value"] > 0 and len(v["why"]) > 100 for v in limits.values())


def test_the_benchmark_grew_by_appended_entries_alone():
    """Entries are looked up BY NAME: the older lists are a PREFIX of the new ones and the new
    entries follow them in the issue's order. Nothing here asserts that these entries are the
    last of all, so that the next PR's appended entries do not break it."""
    doc = Manifest().doc
    older_cells = ["xl_d20_train_1chip", "olmoe_d4_train_4chip", "qwen3next_ep16_train_1chip",
                   "granite4h_d10_train_1chip"]
    names = lambda section: [e["name"] for e in doc[section]]       # noqa: E731
    assert names("configs")[:4] == ["gpt2-xl-d20", "olmoe-1b-7b-d4", "qwen3-next-80b-a3b-ep16-d4",
                                    "granite-4.0-h-micro-d10"]
    assert names("workloads")[:4] == older_cells
    assert names("configs").index(CONFIG) == 4 and names("workloads").index(CELL) == 4
    at = names("per_layer").index("hbm_in_use_share_max.train")
    assert names("per_layer")[at + 1:at + 5] == NEW_READERS
    for m in doc["end_to_end"] + doc["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells and m["name"] not in NEW_READERS:
            before = cells[:cells.index(CELL)]
            assert before == [c for c in older_cells if c in before], m["name"]
            # only the whole-step flash share has so far been listed for one older cell alone
            assert len(before) >= (1 if m["name"] == "flash_time_share" else 4), m["name"]
    assert names("end_to_end")[:2] == ["train_tokens_per_s_chip", "setup_s"]
    assert doc["run_seconds"] == 40 and doc["end_to_end"][0]["bound"] == 0.01
    assert doc["paths"] == ["benchmarks", "tests/cellbench"]
    assert sum(w["chips"] == 4 for w in doc["workloads"][:5]) == 1


def test_the_flash_readers_are_handed_a_call_a_layer_and_pass():
    model = Manifest().config(CONFIG)["model"]
    sizes = train_loop.flash_sizes(model)
    assert sizes == {"n_embd": 2048, "n_layer": 24, "n_head": 16}
    fwd_flops, _ = flops.flash_required(sizes, 2, 4096, training=False)
    assert fwd_flops == 8192 * flops_loop.forward_flops_by_part(model, 49152, 4096)["attention"]
    assert fwd_flops == 24 * 8192 * 2 * 4096 * 16 * 128


# ------------------------------------------------------------------ the toy cell
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end(tiny_manifest, cpu_peaks, tmp_path, capsys, trace):
    """Counting STEPS: ``--seconds 0`` ends the window after its first step's return, and the
    warm-up's steps are the rest; nothing here waits for a clock."""
    out_dir = str(tmp_path / "out")
    handed = {}        # the record as the readers are handed it

    def keep(metric):
        reader = Manifest.reader(tiny_manifest, metric)

        def read(record):
            handed.update(record)
            return reader(record)
        return read

    grown = Manifest(bench_dir=tiny_manifest.bench_dir)
    grown.reader = keep
    result = run.run_cell("tiny_loop", SEED, 0.0, bool(trace), manifest=grown, allow_cpu=True, out_dir=out_dir)
    result = json.loads(json.dumps(result))
    with open(os.path.join(out_dir, f"tiny_loop.{SEED}.steps.json")) as f:
        record = json.load(f)
    reference = record["reference"]
    assert reference["ok"] is True, reference
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] == 1
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in tiny_manifest.metrics_of(section, "tiny_loop")}
    assert set(result["metrics"]) <= set(declared)
    if trace:
        # the record has what the readers that exist know a training cell by
        assert {"kind", "chips", "batch_per_chip", "seq_len", "tokens_per_s_chip", "model", "vocab",
                "steps", "step_interval_ms", "dispatch_ms", "loop_model", "exits"} <= set(handed)
        assert handed["kind"] == "train" and handed["model"] == {"n_embd": 32, "n_layer": 4, "n_head": 4}
        # the host-clock metrics are there; the device-trace ones find no device plane on the CPU
        assert {"mfu.loop", "setup_compile_s", "step_program_variants"} <= set(result["metrics"])
        assert not {"loop_time_share", "loop_recompute_time_share", "exit_time_share"} & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert set(reference["tolerances"]) == LIMITS <= set(reference)
    # float32 parts: the exit distribution and the head's losses sit close to the reference's
    assert reference["exit_p_abs"] < 0.02 and reference["head_ce_rel"] < 0.01 and reference["exit_alone_abs"] < 1e-5
    # the process's first step is the engine's own, blocks recomputed, on the reference's sequence
    step = reference["step"]
    assert step["ok"] is True and set(step["tolerances"]) == {"step_loss_rel", "step_update_shortfall"}
    assert record["warm_losses"][0] == pytest.approx(reference["reference_loss"], rel=step["step_loss_rel"] + 1e-6)
    assert len(record["warm_losses"]) >= 3 and record["losses"][-1] < record["warm_losses"][0]
    # the exit distribution's device scalars, every step of the window
    exits = record["exits"]
    assert exits["steps_counted"] == len(record["losses"]) and exits["mass_sum_error_max"] <= 1e-5
    assert len(exits["mass_by_pass"]) == len(exits["ce_by_pass"]) == 4 and exits["entropy"] > 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["window_compiles"] == 0


def test_a_window_whose_exits_do_not_sum_to_one_is_not_correct():
    kept = [{"exit_mass": np.array([0.5, 0.25, 0.125, 0.125]), "exit_ce": np.full(4, 5.0), "exit_entropy": 1.2},
            {"exit_mass": np.array([0.5, 0.25, 0.125, 0.1251]), "exit_ce": np.full(4, 5.0), "exit_entropy": 1.2}]
    assert train_loop.exits_of(kept[:1])["mass_sum_error_max"] == 0.0
    both = train_loop.exits_of(kept)
    assert both["steps_counted"] == 2 and both["mass_sum_error_max"] == pytest.approx(1e-4)
    assert both["mass_sum_error_max"] > train_loop.EXIT_SUM_TOLERANCE
    assert train_loop.exits_of([]) == {"steps_counted": 0, "mass_by_pass": None, "ce_by_pass": None,
                                       "entropy": None, "mass_sum_error_max": None}


@pytest.mark.parametrize("lost", range(4))
def test_a_pass_lost_from_a_shared_gradient_reads_one_however_small_it_is(lost):
    """``read_shared`` on made-up contributions of which the later ones are a hundredth of the
    first: the sum's own reading hides a later pass under an error of 3 %, the passes' weights
    read it as one; the error alone moves no weight."""
    rng = np.random.default_rng(lost)
    sizes = [1.0, 0.06, 0.02, 0.01]
    by_pass = [{"wq": size * rng.standard_normal((64, 64)), "w_down": size * rng.standard_normal((96, 64))}
               for size in sizes]
    total = {k: sum(one[k] for one in by_pass) for k in by_pass[0]}
    noisy = {k: v + 0.03 * np.linalg.norm(v) / np.sqrt(v.size) * rng.standard_normal(v.shape) for k, v in total.items()}
    clean = train_loop.read_shared(noisy, by_pass)
    assert clean["shared_grad_rel"] == pytest.approx(0.03, rel=0.1) and clean["shared_pass_weight_abs"] < 0.3
    assert train_loop.read_shared(total, by_pass) == pytest.approx({"shared_grad_rel": 0.0, "shared_pass_weight_abs": 0.0}, abs=1e-9)
    faulty = train_loop.read_shared({k: noisy[k] - by_pass[lost][k] for k in noisy}, by_pass)
    assert faulty["shared_pass_weight_abs"] > 0.7
    if lost:       # the sum cannot tell: a later pass is under the error
        assert faulty["shared_grad_rel"] < 0.1
    twice = train_loop.read_shared({k: total[k] + by_pass[lost][k] for k in total}, by_pass)
    assert twice["shared_pass_weight_abs"] == pytest.approx(1.0, abs=1e-6)


# ------------------------------------------------------------ the new readers
@pytest.mark.parametrize("name", NEW_READERS)
def test_every_new_reader_returns_nothing_on_nothing(name, monkeypatch):
    reader = Manifest().reader(name)
    assert reader({"setup": {}, "trace": None}) is None
    monkeypatch.setattr(ps, "program_recorder", lambda: None)
    assert reader({"setup": {}, "trace": None, "t_window_start": 5.0, "window_s": 2.0,
                   "kind": "train"}) is None
    # Granite's record: blocks recomputed, but no pass scope and no looped model
    assert reader({"setup": {}, "trace": None, "kind": "train", "tokens_per_s_chip": 1.0,
                   "model": {"n_embd": 32, "n_layer": 1, "n_head": 2},
                   "ssm_model": {"mamba_n_heads": 8, "layer_types": ["mamba"]},
                   "vocab": 256, "seq_len": 64, "device_kind": "TPU v5 lite"}) is None


@pytest.fixture
def recorded_loop(monkeypatch):
    """The slice recorded on the chip (GPT-2 XL, PR 24) with its blocks' scope paths renamed
    as a looped model's would be: the blocks under ``ds_loop``, the forward's blocks named as
    made again, and the head under ``ds_exit``."""
    with open(os.path.join(BENCH_DIR, "testdata", "spans_train_slice.json")) as f:
        doc = json.load(f)
    for info in doc["catalog"].values():
        for name, path in info["ops"].items():
            again = "" if "transpose(" in path else "checkpoint/rematted_computation/"
            for part in ("ds_attn", "ds_mlp"):
                if part in path:
                    info["ops"][name] = path.replace(part, again + "ds_loop/" + part, 1)
            if "ds_loss" in path:
                info["ops"][name] = path.replace("ds_loss", "ds_loss/ds_exit", 1)
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    model = dict(Manifest().config(CONFIG)["model"], total_ut_steps=1)
    return dict(doc["record"], trace=tr.Reduced(doc["trace"]), setup={}, loop_model=model, vocab=49152, steps=1)


def test_every_new_reader_reads_a_recorded_slice(recorded_loop, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, recorded_loop["device_kind"], dict(peaks.PEAKS["TPU v5 lite"]))
    manifest = Manifest()
    values = {name: manifest.reader(name)(recorded_loop) for name in NEW_READERS}
    assert all(v is not None and np.isfinite(v) for v in values.values()), values
    table = loop_spans.analyse(recorded_loop)
    assert set(table) == {"loop_s", "exit_s", "window_s"} and set(table["loop_s"]) == {"recomputed", "backward"}
    rows = ps.analyse(recorded_loop)["trace"]["device_s"]
    part_s = lambda phase, *parts: sum(v for ph, p, _, v in rows if p in parts and phase in (None, ph))   # noqa: E731
    assert sum(table["loop_s"].values()) == pytest.approx(part_s(None, "ds_attn", "ds_mlp"), rel=0.02)
    assert table["loop_s"]["recomputed"] == pytest.approx(part_s("forward", "ds_attn", "ds_mlp"), rel=0.02)
    assert table["exit_s"] == pytest.approx(part_s(None, "ds_loss"), rel=0.02)
    assert 0 < values["loop_recompute_time_share"] < values["loop_time_share"] < 100
    assert 0 < values["exit_time_share"] < values["loop_time_share"]
    assert values["mfu.loop"] > 0


class Catalogued:
    def __init__(self, catalog):
        self.catalog = catalog

    def programs(self, engine):
        return self.catalog


def looped_trace(turn_ms, steps=2, cut=None):
    """A gradient program whose loop body holds one instruction a phase, run ``steps`` steps
    of four turns; turn ``k`` of the forward loop takes ``turn_ms[k]``, of the backward loop
    (which runs from the last pass to the first) ``turn_ms[3 - k]`` twice over."""
    ops = {"fusion.1": "jit(f)/jvp(while)/body/checkpoint/ds_loop/ds_attn/dot_general",
           "fusion.2": "jit(f)/transpose(jvp(while))/body/checkpoint/rematted_computation/ds_loop/ds_mlp/dot_general",
           "fusion.3": "jit(f)/transpose(jvp(while))/body/checkpoint/ds_loop/ds_mlp/transpose/dot_general",
           "fusion.4": "jit(f)/jvp(ds_loss)/ds_exit/mul", "fusion.5": "jit(f)/jvp(ds_loss)/dot_general"}
    events, now = [], 1.0
    for _ in range(steps):
        for k in range(4):
            events.append(["fusion.1 bf16[8]", now, turn_ms[k] * 1e-3]); now += turn_ms[k] * 1e-3
        for name in ("fusion.5", "fusion.4"):
            events.append([name + " f32[8]", now, 1e-3]); now += 1e-3
        for k in range(4):
            for name in ("fusion.2", "fusion.3"):
                events.append([name + " bf16[8]", now, turn_ms[3 - k] * 1e-3]); now += turn_ms[3 - k] * 1e-3
    events = events[:cut]
    trace = tr.Reduced({"devices": {"/device:TPU:0": events}, "window": [1.0, now], "host": []})
    return {"loss_and_grad": {"ops": ops}}, trace


@pytest.mark.parametrize("cut", [None, -3], ids=["whole-steps", "cut-inside-a-step"])
def test_a_loops_seconds_are_summed_by_phase_over_all_its_turns(cut, monkeypatch):
    """The passes are turns of one loop body under one scope: every turn's run of an
    instruction counts under its phase, whatever the turn took, and nothing tells turns apart."""
    turn_ms = [4.0, 2.0, 2.0, 1.0]
    catalog, trace = looped_trace(turn_ms, cut=cut)
    monkeypatch.setattr(ps, "analyse", lambda record: {"engine": 0})
    monkeypatch.setattr(ps, "program_recorder", lambda: Catalogued(catalog))
    monkeypatch.setattr(loop_spans.os, "makedirs", lambda *a, **k: (_ for _ in ()).throw(AssertionError))
    record = {"trace": trace, "loop_model": {"total_ut_steps": 4}, "steps": 2}
    table = loop_spans._analyse(record)
    record["loop_spans"] = table
    assert set(table) == {"loop_s", "exit_s", "window_s"} and table["exit_s"] == pytest.approx(2e-3)
    assert table["loop_s"]["forward"] == pytest.approx(18e-3)
    # the cut takes the last step's last three runs away: a backward of 2 ms, then the first
    # pass's recomputed forward and backward of 4 ms each
    lost = {"recomputed": 4e-3, "backward": 2e-3 + 4e-3} if cut else {"recomputed": 0.0, "backward": 0.0}
    assert table["loop_s"]["recomputed"] == pytest.approx(18e-3 - lost["recomputed"])
    assert table["loop_s"]["backward"] == pytest.approx(18e-3 - lost["backward"])
    manifest = Manifest()
    assert manifest.reader("loop_recompute_time_share")(record) == pytest.approx(
        100 * table["loop_s"]["recomputed"] / trace.window_s)
    assert manifest.reader("loop_time_share")(record) == pytest.approx(
        100 * sum(table["loop_s"].values()) / trace.window_s)
    assert manifest.reader("exit_time_share")(record) == pytest.approx(100 * 2e-3 / trace.window_s)
    # another model's record (no ``loop_model``) and a program without the scope read nothing
    assert loop_spans._analyse({"trace": trace, "steps": 2}) is None
    bare = {"loss_and_grad": {"ops": {k: v.replace("ds_loop/", "") for k, v in catalog["loss_and_grad"]["ops"].items()}}}
    monkeypatch.setattr(ps, "program_recorder", lambda: Catalogued(bare))
    assert loop_spans._analyse({"trace": trace, "loop_model": {"total_ut_steps": 4}, "steps": 2}) is None


# ------------------------------------------------------------------ the issue's counts
def test_flops_loop_against_the_issues_counts():
    model = Manifest().config(CONFIG)["model"]
    assert flops_loop.is_loop_model(model) and not flops_loop.is_loop_model({"n_embd": 1600})
    assert flops_loop.passes(model) == 24
    assert flops_loop.block_matmul_params(model) == 4 * 2048 ** 2 + 3 * 2048 * 5632 == 51_380_224
    assert flops_loop.layer_params(model) == 51_388_416
    assert flops_loop.param_count(model, 49152) == 6 * 51_388_416 + 201_326_592 + 2048 + 2049 == 509_661_185
    parts = flops_loop.forward_flops_by_part(model, 49152, 4096)
    # 102.76 M + 16.78 M a block pass and token, forward, at T = 4096
    assert parts["blocks"] == 24 * 102_760_448 and parts["attention"] == 24 * 16_777_216
    assert parts["heads"] == 4 * 2 * 100_663_296 == 805_306_368 and parts["gate"] == 3 * 4096
    fwd = flops_loop.forward_flops_per_token(model, 49152, 4096)
    assert fwd == sum(parts.values()) and round(fwd / 1e6) == 3674
    assert 0.218 < parts["heads"] / fwd < 0.220                         # the four heads: 21.9 %
    train = flops_loop.train_flops_per_token(model, 49152, 4096)
    assert train == 3 * fwd and round(train / 1e7) == 1102              # 11.02 G a trained token
    assert round(train * 8192 / 1e11) == 903                            # 90.3 TFLOP a step
    # a share of the peak cannot pass 100 %: at the chip's peak the step takes 0.458 s
    peak = peaks.PEAKS["TPU v5 lite"]["flops_per_s"]
    record = {"kind": "train", "loop_model": model, "vocab": 49152, "seq_len": 4096,
              "device_kind": "TPU v5 lite", "tokens_per_s_chip": 8192 / (train * 8192 / peak)}
    assert Manifest().reader("mfu.loop")(record) == pytest.approx(100.0)
    assert Manifest().reader("mfu.loop")(dict(record, tokens_per_s_chip=9000.0)) == pytest.approx(50.35, abs=0.05)


def test_the_probe_reads_every_fault_above_the_system(tiny_manifest):
    """``tests/perf/ouro_precision_probe.py`` at the toy size: the system inside every limit;
    the reference's own gate and exit distribution in bfloat16, its cross-entropy in bfloat16
    and its passes' contributions added in bfloat16 each further from the float32 reference
    than exact arithmetic is; the structural faults (no norm after a branch, the un-normed
    stream carried into the next pass) further than the system is."""
    spec = importlib.util.spec_from_file_location("ouro_precision_probe", os.path.join(
        os.path.dirname(BENCH_DIR), "tests", "perf", "ouro_precision_probe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    line, = module.probe(tiny_manifest, "tiny-loop", "tiny_docs", [SEED])
    system = line["system"]
    assert system["ok"] is True
    assert line["bf16_exit"]["exit_p_abs"] > 1e-4 and line["bf16_exit"]["train_loss_rel"] > 0
    assert line["bf16_exit"]["exit_alone_abs"] > 1e-3 > 100 * system["exit_alone_abs"]
    assert line["bf16_cross_entropy"]["head_ce_rel"] > max(1e-3, 3 * system["head_ce_rel"])
    assert line["bf16_cross_entropy"]["exit_ce_rel"] > 1e-4
    assert line["pass0_lost"]["shared_grad_rel"] > 3 * system["shared_grad_rel"] > 0
    assert line["pass0_lost"]["shared_grad_rel"] > 10 * line["bf16_pass_sum"]["shared_grad_rel"] > 1e-3
    # a pass's contribution lost reads one on the passes' weights whatever its share of the sum
    for t in range(4):
        assert line[f"pass{t}_lost"]["shared_pass_weight_abs"] == pytest.approx(1.0, abs=1e-6)
        assert line[f"pass{t}_lost"]["shared_grad_rel"] > 0
    assert line["last_pass_halved"]["shared_pass_weight_abs"] == pytest.approx(0.5, abs=1e-6)
    assert system["shared_pass_weight_abs"] < 0.5 * line["last_pass_halved"]["shared_pass_weight_abs"]
    assert line["bf16_pass_sum"]["shared_pass_weight_abs"] < 0.05
    assert all(len(v) == 4 and max(v) <= 1.5 for v in line["pass_share_of_sum"].values())
    assert line["float8_kept_head_gradient"]["head_grad_rel"] > 5 * line["bf16_kept_head_gradient"]["head_grad_rel"] > 1e-3
    assert line["no_entropy_term"]["train_loss_rel"] > 10 * system["train_loss_rel"]
    for fault in ("no_norm_after", "no_norm_carried"):
        assert line[fault]["last_logits_rel"] > 3 * system["last_logits_rel"], fault
    assert line["no_norm_after"]["pass_rel"] > 3 * system["pass_rel"]
    first = line["adam_first_step"]
    assert 0 < first["moved_over_rate"] <= 1 and first["predicted_shortfall"] == 1 - first["moved_over_rate"]
