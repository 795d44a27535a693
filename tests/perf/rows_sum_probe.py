"""The combine alone on the chip: device milliseconds a call of ``parallel/moe._sum_rows``' two
forms at the benchmark's five ``(k, G, H)`` and 8,192 tokens, under a balanced and a leaning router.

    python tests/perf/rows_sum_probe.py [--out chiprun_out/rows_sum.jsonl] [--tiles 256,512]

``gather`` is the compiled form (``sum_j ys[inverse[:, j]]``: a gather of ``n k`` rows FROM the
``n k`` sorted rows and a sum over ``k``), ``take`` the dispatch's gather from the ``n`` tokens' rows
(``x[tok]``, for the price of a row by index), ``kernel.<tokens>`` the run bounds and
``ops/pallas/rows_sum.py`` at that many tokens a tile. Each is a program of its own with a profiler trace of its
own: the device's busy time (the union of its ``XLA Ops``) a call, ``ops`` its three longest
operations, ``max_diff`` | ``same_bits`` the kernel's result beside the compiled form's on the chip,
``visited`` the chunks the kernel copied over the chunks the rows fill.
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
from deepspeed_tpu.ops.pallas import rows_sum as rs  # noqa: E402
from deepspeed_tpu.parallel import moe  # noqa: E402

N = 8192
# (k, experts, groups held, H): the rows a layer sorts by the held expert they are folded onto
CELLS = {"nemotronh": (6, 128, 8, 2688), "mellum2": (8, 64, 16, 2304), "glm47flash": (4, 64, 8, 2048),
         "lfm2": (4, 64, 8, 2048), "olmoe": (8, 64, 64, 2048)}
LEANS = {"balanced": 0.0, "leaning": 6.0}


def sorted_rows(k, E, G, lean, rng):
    """``(group, tok, inverse, load max over mean)`` of a router whose logits lean by ``lean`` times
    a ramp over the experts beside Gumbel noise, each row folded onto group ``e % G``."""
    logits = rng.gumbel(size=(N, E)) + lean * rng.permutation(E) / E * 4
    experts = np.argsort(-logits, axis=1)[:, :k]
    load = np.bincount(experts.reshape(-1), minlength=E)
    group = experts.reshape(-1) % G
    order = np.argsort(group, kind="stable")
    inverse = np.argsort(order).reshape(N, k)
    return group[order], order // k, inverse, float(load.max() / load.mean())


def programs(k, G, H, sort, tiles, rng):
    """``{way: (function, operands)}`` for one cell's shapes and one router."""
    group, tok, inverse = (jnp.asarray(a, jnp.int32) for a in sort[:3])
    ys = jnp.asarray(rng.normal(size=(N * k, H)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.bfloat16)
    gather = lambda ys, inverse: jnp.sum(moe._rows_of_the_tokens(ys, inverse).astype(jnp.float32),  # noqa: E731
                                         axis=0).astype(ys.dtype)
    ways = {"gather": (gather, (ys, inverse)), "take": (lambda x, tok: x[tok], (x, tok))}
    for T in tiles:
        def kernel(ys, group, tok, T=T):
            return rs.rows_sum(ys, tok, rs.visits(rs.run_bounds(group, tok, N, G, T), N * k), N)
        ways[f"kernel.{T}"] = (kernel, (ys, group, tok))
    return ways


def visited(sort, G, T):
    """Chunks the kernel copies under this router: every non-empty run's, by the bounds' own rule."""
    group, tok = np.asarray(sort[0], np.int64), np.asarray(sort[1], np.int64)
    at = np.arange(N // T + 1)[:, None] * T + np.arange(G)[None, :] * N
    return rs.chunks_visited(np.searchsorted(group * N + tok, at))


def traced(jitted, operands, calls):
    """``(busy ms a call, events a call, the three longest operations' ms a call)`` from a trace."""
    trace_dir = tempfile.mkdtemp(prefix="rows_sum_")
    try:
        jax.profiler.start_trace(trace_dir)
        for _ in range(calls):
            out = jitted(*operands)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        devices = tr.load_xplane(tr.find_xplane(trace_dir))["devices"]
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    events = [event for ops in devices.values() for event in ops]
    by_name = {}
    for name, _, seconds in events:
        by_name[name] = by_name.get(name, 0.0) + seconds * 1e3 / calls
    busy = tr.measure(tr.union([[start, start + seconds] for _, start, seconds in events])) * 1e3 / calls
    longest = dict(sorted(by_name.items(), key=lambda item: -item[1])[:3])
    return busy, len(events) / calls, {name: round(ms, 4) for name, ms in longest.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/rows_sum.jsonl")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--tiles", default="256,512,128", help="tokens a tile")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("a device time comes from the chip alone")
    tiles = [int(tile) for tile in args.tiles.split(",")]
    rng = np.random.default_rng(0)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        for cell in args.cells.split(","):
            k, E, G, H = CELLS[cell]
            for router, lean in LEANS.items():
                sort = sorted_rows(k, E, G, lean, rng)
                want = None
                for way, (fn, operands) in programs(k, G, H, sort, tiles, rng).items():
                    line = dict(cell=cell, n=N, k=k, G=G, H=H, router=router, load_max_over_mean=round(sort[3], 2), way=way)
                    try:
                        jitted = jax.jit(fn)
                        got = np.asarray(jitted(*operands).astype(jnp.float32))
                        if way == "gather":
                            want = got
                        elif way.startswith("kernel"):
                            line.update(max_diff=float(np.abs(got - want).max()), same_bits=float((got == want).mean()),
                                        visited=visited(sort, G, int(way.split(".")[1])), filled=N * k // rs.CHUNK)
                        ms, events, longest = traced(jitted, operands, args.calls)
                        if not events:
                            raise SystemExit(f"{line}: no device operation in its trace")
                        line.update(ms=round(ms, 4), events_a_call=events, ops=longest)
                    except Exception as e:      # a tile the chip's compiler refuses is a line
                        line.update(error=str(e)[:300])
                    f.write(json.dumps(line) + "\n")
                    f.flush()
                    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
