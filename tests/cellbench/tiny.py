"""A tiny benchmark root for the CPU tests: the real plug-in directories, and data
files of a toy size written beside them. It doubles as the proof that a cell, a
configuration, a traffic mix and a per-layer metric are each added by new files and
one ``BENCHMARK.json`` entry, with no edit to a file that exists."""

import json
import os
import shutil

from benchmarks.manifest import BENCH_DIR, REPO_ROOT

TINY_MODEL = {"vocab_size": 250, "n_positions": 64, "n_ctx": 64, "n_embd": 32, "n_head": 2,
              "n_layer": 2, "layer_norm_epsilon": 1e-05, "initializer_range": 0.02,
              "activation_function": "gelu_new"}
ENGINE = {"bf16": {"enabled": True}, "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "steps_per_print": 10 ** 9}


def _dump(path, doc):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)


def make_root(tmp_path, four_chip=False):
    """``tmp_path/BENCHMARK.json`` and ``tmp_path/benchmarks/``: the accepted
    benchmark as it stands, plus tiny cells (``four_chip`` adds a ZeRO-3 cell
    over four devices) and one new per-layer metric."""
    root = str(tmp_path)
    bench = os.path.join(root, "benchmarks")
    for kind in ("generators", "runners", "layer_metrics", "reference", "cells", "configs",
                 "traffic"):
        shutil.copytree(os.path.join(BENCH_DIR, kind), os.path.join(bench, kind))
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)

    def config(name, **more):
        _dump(os.path.join(bench, "configs", name + ".json"), dict(
            name=name, source="tests/cellbench/tiny.py", reduced={}, model=TINY_MODEL,
            padded_vocab_size=256, compute_dtype="bfloat16", use_flash_attention=True,
            assumed={"loss_chunk": 16}, **more))
        doc["configs"].append({"name": name, "source": "tests/cellbench/tiny.py",
                               "file": f"benchmarks/configs/{name}.json", "reduced": [],
                               "why": "toy sizes for the CPU rehearsal"})

    config("tiny-train", runner="train",
           engine=dict(ENGINE, zero_optimization={"stage": 2}),
           reference={"module": "gpt2_reference", "tolerance": "train_loss_rel"})
    if four_chip:
        config("tiny-zero3", runner="train",
               engine=dict(ENGINE, zero_optimization={"stage": 3}),
               reference={"module": "gpt2_reference", "tolerance": "train_loss_rel"})
    config("tiny-serve", runner="serve", weights_dtype="bfloat16",
           serving={"max_seqs": 4, "block_size": 8, "num_blocks": 33, "max_model_len": 64,
                    "prefill_chunk": 16, "use_pallas_decode": False},
           reference={"module": "gpt2_reference", "tolerance": "serve_logits_rel"})
    _dump(os.path.join(bench, "traffic", "tiny_docs.json"), {
        "name": "tiny_docs", "generator": "train_packed", "seq_len": 64,
        "doc_len": {"dist": "lognormal", "median": 20, "sigma": 1.0, "min": 4, "max": 200},
        "token_dist": {"dist": "zipf", "exponent": 1.1}, "eot_token": 249,
        "batches_ahead": 16})
    _dump(os.path.join(bench, "traffic", "tiny_chat.json"), {
        "name": "tiny_chat", "generator": "serve_closed", "clients": "slots",
        "multiset_size": 16, "lengths_seed": 3,
        "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 3, "max": 40},
        "output_len": {"dist": "lognormal", "median": 5, "sigma": 0.5, "min": 2, "max": 12},
        "max_total_len": 64, "token_dist": {"dist": "uniform"}, "temperature": 0.0,
        "shared_prefix": 0, "correctness_sample": [5, 30]})
    cells = {"tiny_train": ("tiny-train", "tiny_docs", 1, {"micro_batch_per_chip": 2, "warm_steps": 2}),
             "tiny_zero3": ("tiny-zero3", "tiny_docs", 4, {"micro_batch_per_chip": 1, "warm_steps": 2}),
             "tiny_serve": ("tiny-serve", "tiny_chat", 1, {})}
    if not four_chip:
        del cells["tiny_zero3"]
    for name, (cfg, traffic, chips, more) in cells.items():
        _dump(os.path.join(bench, "cells", name + ".json"), dict(
            name=name, config=cfg, traffic=traffic, chips=chips, trace_seconds=1,
            why="toy cell", **more))
        doc["workloads"].append({"name": name, "config": cfg, "traffic": traffic,
                                 "chips": chips, "why": "toy cell for the CPU rehearsal"})
    train, serve = [c for c in cells if c != "tiny_serve"], ["tiny_serve"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + train
    if four_chip:
        doc["per_layer"].append({"name": "collective_exposed_share", "unit": "%",
                                 "better": "lower", "source": "device_trace",
                                 "layer": "ZeRO layouts", "moves": "train_tokens_per_s_chip",
                                 "workloads": ["tiny_zero3"]})
    # the serving metrics come with their cell: end-to-end ones and their layers' readers
    for name, unit, better in (("serve_tokens_per_s", "tokens/s", "higher"),
                               ("ttft_ms_p95", "ms", "lower"),
                               ("token_gap_ms_p95", "ms", "lower")):
        doc["end_to_end"].append({"name": name, "unit": unit, "better": better, "bound": 0.1,
                                  "source": "host_clock", "workloads": serve})
    for name, unit, better, source, layer, moves in (
            ("iteration_ms_p50.serve", "ms", "lower", "host_clock", "serving engine",
             "token_gap_ms_p95"),
            ("batch_occupancy.serve", "%", "higher", "program_counter", "scheduler",
             "serve_tokens_per_s"),
            ("prefill_wait_ms_p50.serve", "ms", "lower", "host_clock", "scheduler", "ttft_ms_p95"),
            ("device_idle_share.serve", "%", "lower", "device_trace", "device",
             "serve_tokens_per_s")):
        doc["per_layer"].append({"name": name, "unit": unit, "better": better, "source": source,
                                 "layer": layer, "moves": moves, "workloads": serve})
    # a per-layer metric of the test's own, added by its file and its entry
    with open(os.path.join(bench, "layer_metrics", "steps_in_window.py"), "w") as f:
        f.write("def read(record):\n    return record.get('steps')\n")
    doc["per_layer"].append({"name": "steps_in_window", "unit": "steps", "better": "higher",
                             "source": "program_counter", "layer": "train engine",
                             "moves": "train_tokens_per_s_chip", "workloads": train})
    _dump(os.path.join(root, "BENCHMARK.json"), doc)
    return root
