"""The router's scalars alone on the chip: device milliseconds a call of the three ways ``n k``
single floats moved by index in ``parallel/moe.py`` until PR 51, beside what replaced them.

    python tests/perf/router_scalars_probe.py [--out chiprun_out/router_scalars.jsonl]

At 8,192 tokens and each expert cell's ``(k, E)``: ``read`` (``take_along_axis(values, experts)``
against ``_chosen``), ``pull`` (their cotangents: a scatter into zeros ``[n, E]`` against the
select the other way) and ``back`` (the sorted weights' cotangent ``g[inverse]`` against a sort by
``order`` with ``g`` as its operand, stable and not). Each is a program of its own with a profiler trace
of its own: the device's busy time (the union of its ``XLA Ops``) a call, and ``same_bits`` says
whether a kind's ways agree bit for bit on the chip.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from benchmarks import trace_reduce as tr  # noqa: E402
from deepspeed_tpu.parallel import moe  # noqa: E402

N = 8192
CELLS = {"nemotronh": (6, 128), "glm47flash": (4, 64), "mellum2": (8, 64), "olmoe": (8, 64), "qwen3next": (10, 512)}


def programs(k, E, rng):
    """``{way: (function, operands)}`` for one cell's ``(k, E)``."""
    values = jnp.asarray(rng.random(size=(N, E)), jnp.float32)
    experts = jax.lax.top_k(values, k)[1]
    cot = jnp.asarray(rng.normal(size=(N, k)), jnp.float32)
    order = jnp.asarray(rng.permutation(N * k), jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32).reshape(N, k)
    g = jnp.asarray(rng.normal(size=N * k), jnp.float32)
    by_index = lambda v, e: jnp.take_along_axis(v, e, axis=-1)      # noqa: E731
    return {"read.take_along_axis": (by_index, (values, experts)),
            "read.chosen": (moe._chosen, (values, experts)),
            "pull.scatter": (lambda v, e, c: jax.vjp(lambda v: by_index(v, e), v)[1](c)[0], (values, experts, cot)),
            "pull.chosen": (lambda v, e, c: jax.vjp(lambda v: moe._chosen(v, e), v)[1](c)[0], (values, experts, cot)),
            "back.gather": (lambda g, inverse: g[inverse].reshape(-1), (g, inverse)),
            "back.sort_stable": (lambda g, order: jax.lax.sort((order, g), num_keys=1)[1], (g, order)),
            "back.sort": (lambda g, order: jax.lax.sort((order, g), num_keys=1, is_stable=False)[1], (g, order))}


def busy_ms(trace_dir):
    """The device's busy milliseconds in the trace (the union of its ``XLA Ops``), and their count."""
    devices = tr.load_xplane(tr.find_xplane(trace_dir))["devices"]
    events = [[start, start + seconds] for ops in devices.values() for _, start, seconds in ops]
    return tr.measure(tr.union(events)) * 1e3, len(events)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/router_scalars.jsonl")
    ap.add_argument("--calls", type=int, default=8)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("a device time comes from the chip alone")
    rng = np.random.default_rng(0)
    ready, first = [], {}
    for cell, (k, E) in CELLS.items():
        for way, (fn, operands) in programs(k, E, rng).items():
            jitted = jax.jit(fn)
            got = np.asarray(jitted(*operands))
            same = bool(np.array_equal(first.setdefault((cell, way.split(".")[0]), got), got))
            ready.append((dict(cell=cell, n=N, k=k, E=E, way=way, same_bits=same), jitted, operands))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        for line, jitted, operands in ready:          # a trace a program
            trace_dir = tempfile.mkdtemp(prefix="router_scalars_")
            try:
                jax.profiler.start_trace(trace_dir)
                for _ in range(args.calls):
                    out = jitted(*operands)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                ms, events = busy_ms(trace_dir)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            if not events:
                raise SystemExit(f"{line}: no device operation in its trace")
            line.update(events_a_call=events / args.calls, ms=ms / args.calls)
            f.write(json.dumps(line) + "\n")
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
