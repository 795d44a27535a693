"""``olmoe_d4_train_4chip``'s step by what its expert layers keep of the experts they fetched
(``parallel/moe.fetches_kept``; PERF.md, PR 54): the tree's own rule, nothing or everything,
each in a process of its own, one after the other (``w_down``'s pieces alone, PERF.md's third
column, were read under a rule by name that PR 54 did not ship).

    chiprun --chips 4 --timeout 2400 -- python tests/perf/kept_fetches_probe.py \
        --keep model,none,all --out /root/repo/chiprun_out/<dir>

A process builds the cell's engine as ``benchmarks/runners/train_moe.py`` does (no comparison with
the reference), makes 20 fenced steps from the seed (their losses to the last bit), reads every
chip's ``memory_stats()``, times ``--steps`` steps with one fence at the end, and traces six more:
the ledger's table of device operations, the exposed collectives, and every
``collective-permute-done`` of the first chip's last whole step in the order it ran (forward
layers 0..3, backward 3..0). ``<out>/<keep>.json`` holds it all, with the step programs' need of
memory as the compiler states it. ``--keep model`` is the tree as it is; the others replace the
engine's reading of its room in that process alone: the program has no option for it.
``--keep compare`` runs the gradient program both ways on one batch and says which parameters'
gradients differ in any bit (``compare``; ``<out>/compare.json``). From the
root of a parent unpacked under ``_parent/`` only ``model`` means anything (give ``--out`` an
absolute path).
"""

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, ".")

CELL = "olmoe_d4_train_4chip"
ROOMS = {"none": 0, "all": 10 ** 13}


def manifest_of(bench_dir):
    from benchmarks.manifest import Manifest
    return Manifest(bench_dir=bench_dir) if bench_dir else Manifest()


def build(manifest, cell_name, seed):
    """The cell's engine as ``benchmarks/runners/train_moe.py`` builds it, and its batches."""
    from benchmarks import run
    from benchmarks.compile_log import CompileLog
    from benchmarks.manifest import BENCH_DIR
    from benchmarks.runners import train_moe
    from benchmarks.runners.train import _build_engine
    cell = manifest.cell(cell_name)
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    devices = run.pick_devices(cell["chips"], allow_cpu=manifest.bench_dir != BENCH_DIR)    # a toy's: a rehearsal
    run.configure_compile_cache()
    batch_size = cell["micro_batch_per_chip"] * cell["chips"]
    model = train_moe.build_model(config)
    batches, _ = manifest.generator(traffic["generator"])(
        traffic, seed, vocab=config["model"]["vocab_size"], batch=batch_size, n_batches=traffic["batches_ahead"])
    params = train_moe.init_params(model, seed, train_moe._mesh(devices))
    return _build_engine({"config": config, "devices": devices, "log": CompileLog()}, model, params, batch_size), batches


def compare(manifest, cell_name, seed, out):
    """``--keep compare``: the engine's gradient program traced with everything kept and with
    nothing, both on the first batch and the initial weights, each run twice: per parameter
    the elements whose bits differ between the two programs (and between two runs of one), and the
    largest difference over the largest gradient. Where a loss differs, which leaves carry it."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    ROOMS["compare"] = 10 ** 13
    engine, batches = build(manifest, cell_name, seed)
    batch = tuple(map(engine.shard_batch, batches[0]))
    results = {}
    for name, room in (("kept", 10 ** 13), ("not_kept", 0)):
        ROOMS["compare"] = room
        # a new jit a room (the room is read when a program is traced), laid out as the engine's own
        program = jax.jit(engine._loss_and_grad_fn, out_shardings=(NamedSharding(engine.mesh, P()), engine._grad_shardings))
        runs = [jax.device_get(program(engine.params, engine.scaler_state.cur_scale, *batch)) for _ in range(2)]
        results[name] = runs
    def loss_of(r):
        return np.float32(engine._loss_scalars_sums(r[0])[0])
    def leaves(r):
        return {jax.tree_util.keystr(k): np.asarray(v, np.float32) for k, v in jax.tree_util.tree_leaves_with_path(r[1])}
    report = {"loss": {n: [loss_of(r).tobytes().hex() for r in rs] for n, rs in results.items()}, "leaves": {}}
    kept, kept_again, not_kept = leaves(results["kept"][0]), leaves(results["kept"][1]), leaves(results["not_kept"][0])
    for key, want in not_kept.items():
        got = kept[key]
        differ = int(np.sum(got != want))
        if differ or np.any(kept_again[key] != got):
            report["leaves"][key] = {"elements": int(want.size), "differ": differ, "differ_between_runs": int(np.sum(kept_again[key] != got)),
                                     "max_abs_over_max": float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))}
    report["leaves_in_all"] = len(not_kept)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "compare.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({"loss": report["loss"], "leaves_that_differ": len(report["leaves"]), "of": len(not_kept),
                      "worst": sorted(((v["max_abs_over_max"], k, v["differ"], v["elements"]) for k, v in report["leaves"].items()), reverse=True)[:12]}), flush=True)


def one(keep, seed, steps, out, cell_name, bench_dir):
    import jax
    import numpy as np
    from benchmarks import harness, run, trace_reduce
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.utils import spans

    if keep != "model":
        DeepSpeedEngine._room_beside_state = lambda self: ROOMS[keep]
    if keep == "compare":
        return compare(manifest_of(bench_dir), cell_name, seed, out)
    manifest = manifest_of(bench_dir)
    cell = manifest.cell(cell_name)
    traffic = manifest.traffic(cell["traffic"])
    devices = run.pick_devices(cell["chips"], allow_cpu=bool(bench_dir))
    batch_size = cell["micro_batch_per_chip"] * cell["chips"]
    engine, batches = build(manifest, cell_name, seed)

    def step(i):
        loss = engine(*batches[i % len(batches)])
        engine.backward(loss)
        engine.step()
        return loss

    t = harness.clock()
    first = []
    for i in range(20):
        first.append(step(i))
        jax.block_until_ready(engine.params)
    first = jax.device_get(first)
    result = {"keep": keep, "seed": seed, "warm_s": harness.clock() - t,
              "first_losses": [float(x) for x in first],
              "first_losses_hex": [np.float32(x).tobytes().hex() for x in first],
              "fenced": [d.memory_stats() for d in devices]}
    if hasattr(engine, "_room_beside_state"):
        result["room"] = engine._room_beside_state()
    t0 = harness.clock()
    losses = [step(20 + i) for i in range(steps)]
    jax.block_until_ready((engine.params, losses[-1]))
    result["step_ms"] = (harness.clock() - t0) / steps * 1e3
    result["tokens_per_s_chip"] = batch_size * traffic["seq_len"] / cell["chips"] / result["step_ms"] * 1e3
    result["after_window"] = [d.memory_stats() for d in devices]
    steps_spans = [s for s in spans.recorder().spans(engine._span_engine) if s["name"] == "train.step"][-steps:]
    result["in_flight"] = [s["attrs"].get("in_flight") for s in steps_spans]
    result["bytes_in_use_max"] = max((s["attrs"].get("bytes_in_use") or 0) for s in steps_spans)
    result["skipped_steps"] = int(engine.skipped_steps)

    tracing = harness.Tracing(True, os.path.join(out, f"trace.{keep}"))
    with tracing.window():
        traced = [step(20 + steps + i) for i in range(6)]
        jax.block_until_ready((engine.params, traced[-1]))
    reduced = tracing.reduced
    if reduced is not None and reduced.devices:
        result["traced"] = {
            "window_s": reduced.window_s, "step_ms": reduced.window_s / 6 * 1e3, "idle_share": reduced.idle_share(),
            "collective_exposed_share": reduced.collective_exposed_s() / reduced.window_s,
            "breakdown": reduced.breakdown(top=70)["device_ops"]}
        # the first chip's transfers in the order they ended: those of the last of the six steps
        dones = sorted((start, trace_reduce.op_group(name), dur) for name, start, dur in next(iter(reduced.devices.values()))
                       if "collective-permute-done" in name)
        mine = dones[-(len(dones) // 6):]
        result["traced"]["permute_done"] = [[group, round((start - mine[0][0]) * 1e3, 3), round(dur * 1e3, 3)]
                                            for start, group, dur in mine]
        result["traced"]["permute_done_ms_per_step"] = sum(dur for *_, dur in dones) / 6 * 1e3
    catalog = spans.recorder().programs(engine._span_engine)
    result["program_memory"] = {name: entry["memory"] for name, entry in catalog.items()}
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{keep}.json"), "w") as f:
        json.dump(result, f, indent=1)
    brief = {k: result[k] for k in ("keep", "step_ms", "tokens_per_s_chip", "bytes_in_use_max", "room") if k in result}
    stats = [s or {} for s in result["after_window"]]          # the CPU of a rehearsal reports none
    brief["peak"] = max(s.get("peak_bytes_in_use", 0) for s in stats)
    brief["limit"] = stats[0].get("bytes_limit")
    brief["temp"] = {k: v and v["temp"] for k, v in result["program_memory"].items()}
    brief["in_flight_p50"] = float(np.median([x for x in result["in_flight"] if x is not None] or [0]))
    if "traced" in result:
        brief.update({k: result["traced"][k] for k in ("idle_share", "collective_exposed_share", "permute_done_ms_per_step")},
                     step_ms_traced=result["traced"]["step_ms"])
    print(json.dumps(brief), flush=True)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--keep", default="model", help="comma-separated: model, none, all, compare")
    parser.add_argument("--seed", type=int, default=5400000001)
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--out", required=True)
    parser.add_argument("--cell", default=CELL)
    parser.add_argument("--bench-dir", default=None, help="a toy benchmark's, for a rehearsal on the CPU")
    parser.add_argument("--child", default=None)
    args = parser.parse_args()
    if args.child is not None:
        return one(args.child, args.seed, args.steps, args.out, args.cell, args.bench_dir)
    os.makedirs(args.out, exist_ok=True)
    for keep in args.keep.split(","):       # this process never touches JAX: a chip belongs to one at a time
        with open(os.path.join(args.out, f"{keep}.err"), "w") as err:
            done = subprocess.run([sys.executable, __file__, "--child", keep, "--seed", str(args.seed),
                                   "--steps", str(args.steps), "--out", args.out, "--cell", args.cell]
                                  + (["--bench-dir", args.bench_dir] if args.bench_dir else []), stderr=err)
        print(f"{keep}: rc {done.returncode}", flush=True)
        if done.returncode:
            with open(os.path.join(args.out, f"{keep}.err")) as err:
                print(err.read()[-3000:], flush=True)


if __name__ == "__main__":
    main()
