"""The cell benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It refuses anything but a TPU with at least the cell's chips, finds the
cell, its configuration, its traffic and every metric's reader by file name
(``manifest.py``), hands them to the configuration's runner, and prints as the last
line of standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, when traced, ``breakdown``. With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
"""

import time

T_PROCESS = time.perf_counter()          # set-up is counted from here

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

def configure_compile_cache():
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at the fixed path ``<checkout>/.jax_cache`` (the path is part of the key; the
    program's own helper, ``deepspeed_tpu/utils/compile_cache.py``, picks the same one).
    Every program is kept, however quickly it compiled and however large it is: the
    step programs here serialize to hundreds of megabytes (PERF.md, PR 21)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(REPO_ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def pick_devices(chips, allow_cpu):
    """The cell's devices, or exit: no result is printed without an accelerator, or
    with fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        sys.exit(f"the benchmark needs a TPU; JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        sys.exit(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def run_cell(workload, seed, seconds, trace, *, manifest=None, allow_cpu=False,
             keep_trace=False, out_dir=None):
    """Run one cell and return the result object. ``allow_cpu`` and ``manifest`` are
    for the rehearsal tests; the command never sets them."""
    from benchmarks import harness
    from benchmarks.compile_log import CompileLog
    from benchmarks.manifest import Manifest

    manifest = manifest or Manifest()
    cell = manifest.cell(workload)
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    devices = pick_devices(cell["chips"], allow_cpu)
    if not allow_cpu:
        configure_compile_cache()
    out_dir = out_dir or os.path.join(manifest.bench_dir, "out")
    tracing = harness.Tracing(trace, os.path.join(out_dir, f"trace.{workload}.{seed}"),
                              keep=keep_trace)
    ctx = {"manifest": manifest, "cell": cell, "config": config, "traffic": traffic,
           "seed": int(seed), "seconds": float(seconds),
           "devices": devices, "log": CompileLog(), "tracing": tracing, "out_dir": out_dir}
    record = manifest.runner(config["runner"])(ctx)
    record["setup_s"] = record["t_window_start"] - T_PROCESS
    record["device_kind"] = devices[0].device_kind
    record["trace"] = reduced = tracing.reduced

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": record["memory_peak_bytes"]}
    if trace:
        wanted = manifest.metrics_of("per_layer", workload)
        values = {m["name"]: manifest.reader(m["name"])(record) for m in wanted}
        if reduced is not None and reduced.busy_s() is not None:
            device["busy_s"], device["window_s"] = reduced.busy_s(), reduced.window_s
    else:
        wanted = manifest.metrics_of("end_to_end", workload)
        values = dict(record["end_to_end"], setup_s=record["setup_s"])
        values = {m["name"]: values.get(m["name"]) for m in wanted}
    units = {m["name"]: m["unit"] for m in wanted}
    result = {"correct": record["correct"], "attempted": record["attempted"],
              "failed": record["failed"],
              "metrics": {k: {"value": float(v), "unit": units[k]}
                          for k, v in values.items() if v is not None},
              "device": device}
    breakdown = reduced.breakdown() if reduced is not None else None
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", action="store_true",
                        help="leave the profiler's files under benchmarks/out/")
    args = parser.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
