"""The grouped products alone on the chip, tile by tile: device milliseconds a call of
``ds_gmm`` and ``ds_tgmm`` (``ops/pallas/grouped_matmul.py``) at the benchmark's call shapes,
read from a profiler trace, beside what the products need and what the tiles issue.

    python tests/perf/gmm_sweep.py [--cells mellum2,nemotronh,olmoe,qwen3next,glm47flash,lfm2] [--shapes 2304x1792]
                                   [--grid near|full|picked] [--tm 128,256,512,tgmm:1024] [--vmem 64] [--seed 0] [--check]
                                   [--out chiprun_out/gmm_sweep.jsonl]

Run it from the root of a checkout; from the root of another checkout (a parent unpacked
under ``_parent/``) it measures THAT tree's kernels (megablox's, in a tree from before PR 55) and
marks what its ``parallel/moe._tiles`` picks (give ``--out`` an absolute path there):

    (cd _parent && python ../tests/perf/gmm_sweep.py --out /root/repo/chiprun_out/parent.jsonl)

The shapes are ``expert_calls``: every grouped product an expert layer of the six expert
cells makes in a step, read from the cell's files under ``benchmarks/`` (rows, widths, the
groups of a call, the pieces the experts come in and whether a call writes into an existing
buffer), forward (``gmm``), the cotangent of the rows (``gmm_t``: ``transpose_rhs``) and of
the weights (``tgmm``), bfloat16. The kernels are called as ``grouped_matmul`` and
``grouped_matmul_weight_grad`` call them, with the candidate's tiles in place of ``_tiles``'.

Group sizes come from ``--seed``, two ways: ``even`` (every expert of the router alike: a
multinomial draw, so that a group's boundary falls inside a row tile) and ``lean`` (a
router at initialisation: shares from a Dirichlet draw, no expert past the 1 / k of the
assignments that top-k allows; ``load_max_over_mean`` 8 to 15 in the ledger), folded onto the
held experts where they stand in. A held range that follows the router (Qwen3-Next's) gets a
pass of 8,192 rows of which ``even`` fills what the ledger's ``moe_rows_here_share`` does.

A candidate is ``(tm, tk, tn)``. Tiles for K and for N are the multiples of 128 from 512 to
1,152 under the width, the halves and thirds of the width that are multiples of 128, and the
whole width, where the blocks fit ``--vmem`` MiB (``block_bytes``; 64 by default, well past what
``_tiles`` picks from, so that the rule is read from both sides; 16 in a tree before PR 55, all
megablox's call has). ``--grid near`` (the default) takes a
whole K and what ``(512, 1024, 1024)`` bounded it to until PR 55, beside the N tiles that issue
at most 1.05 times the work the widths need, ``(512, 1024, 1024)`` clipped and what this tree's
``_tiles`` picks; ``--grid full`` every pair; ``--grid picked`` this tree's pick alone (a tree's
kernels read again in a few minutes). ``--tm`` adds this tree's pick at other row tiles, BY KIND:
an entry is a row tile for every kind (``128``) or for one (``tgmm:1024``); 128, 256 and 512 by
default, since PR 57's ``_tiles`` picks among them by the kind, the rows and the groups.
A line holds ``ms`` (the kernels' device time a call, all pieces),
``tflops`` of the NEEDED operations (2 x rows in the call's groups x K x N),
``issued_over_needed`` by the width tiles, ``row_tiles_visited`` / ``row_tiles_needed`` (the
(group, row tile) pairs the walk takes at these group sizes over the row tiles that hold the
rows; ``visits_bound`` is the most any sizes can make it, ``(M / tm + G - 1) / (M / tm)``),
``vmem_bytes``, ``first_call_s`` (the candidate's first call: its compile),
``picked`` (this tree's ``_tiles``) and ``clipped``. ``--check`` adds the relative error against
a per-expert float32 loop on the same values.
"""

import argparse
import collections
import functools
import glob
import inspect
import json
import os
import re
import shutil
import sys
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from benchmarks.manifest import Manifest  # noqa: E402
from deepspeed_tpu.parallel import moe  # noqa: E402
try:
    from deepspeed_tpu.ops.pallas import grouped_matmul as grouped  # noqa: E402
except ImportError:          # a tree from before PR 55: megablox's kernels under 16 MiB
    grouped = None

CELLS = {"mellum2": "mellum2_ep4_d4_train_1chip", "nemotronh": "nemotronh_ep16_d9_train_1chip",
         "olmoe": "olmoe_d4_train_4chip", "qwen3next": "qwen3next_ep16_train_1chip",
         "glm47flash": "glm47flash_ep8_d5_train_1chip", "lfm2": "lfm2_ep8_d7_train_1chip"}
CLIPPED = (512, 1024, 1024)          # what ``_tiles`` clipped with ``min`` until PR 47
VMEM = (64 if grouped else 16) * 2 ** 20    # what a candidate's blocks may take (megablox's calls: a kernel's 16 MiB)
NEAR = 1.05                          # ``--grid near``: the width tiles that issue at most this over the need
ROWS_HERE = {"qwen3next": 0.0615}    # ledger, PR 46: ``moe_rows_here_share`` of a held range alone
ROW_TILES = "128,256,512"            # ``--tm``: what ``_tiles`` picks among since PR 57

Call = collections.namedtuple("Call", "cell kind rows K N groups pieces")


def expert_calls(manifest, key):
    """The grouped products an expert layer of ``CELLS[key]`` makes in a step, from the
    cell's files: ``Call(cell, kind, rows, K, N, groups, pieces)``. ``groups`` is the length
    of ``group_sizes``, ``pieces`` how many calls share them (the experts of four chips arrive
    in four pieces, and each piece's call writes into the buffer of the one before: an
    existing output is one more block)."""
    cell = manifest.cell(CELLS[key])
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    model = config["model"]
    tokens = cell["micro_batch_per_chip"] * traffic["seq_len"]
    H, k = model["hidden_size"], model["num_experts_per_tok"]
    F = model.get("moe_intermediate_size", model["intermediate_size"])
    wide = F if model.get("mlp_hidden_act") == "relu2" else 2 * F         # gate and up side by side
    held = model.get("n_routed_experts", model.get("num_experts"))
    every_row_here = model.get("stand_in", False) or "router_width" not in model
    rows = tokens * k if every_row_here else tokens                        # a held range: a pass of n rows
    shape = dict(cell=key, rows=rows, groups=held, pieces=cell["chips"])
    return [Call(kind=kind, K=K, N=N, **shape)
            for kind in ("gmm", "gmm_t", "tgmm")
            # the first product and the second; a cotangent of the rows contracts the other width
            for K, N in (((H, wide), (F, H)) if kind != "gmm_t" else ((wide, H), (H, F)))]


def block_bytes(call, tiles, itemsize=2):
    """The VMEM a call's blocks take, as the kernels reckon it (an existing output, where the
    experts come in pieces, is one more block). In a tree before PR 55, megablox's: it keeps
    its float32 accumulator ``[tm, tn]`` at a whole K too."""
    if grouped is not None:
        return (grouped.tgmm_block_bytes(tiles, itemsize) if call.kind == "tgmm"
                else grouped.gmm_block_bytes(tiles, call.K, itemsize, call.pieces > 1))
    tm, tk, tn = tiles
    if call.kind == "tgmm":
        return 2 * itemsize * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn * (2 if call.pieces > 1 else 1)) + 4 * tm * tn


def issued_over_needed(tiles, K, N):
    """The products the width tiles issue over those the widths need: megablox rounds K and
    N up to whole tiles and computes every tile in full."""
    _, tk, tn = tiles
    return (-(-K // tk) * tk) * (-(-N // tn) * tn) / (K * N)


def clipped(call):
    return tuple(min(t, d) for t, d in zip(CLIPPED, (call.rows, call.K, call.N)))


def clipped_rule(call):
    """What ``_tiles`` picked under ``(512, 1024, 1024)`` from PR 47 to PR 54: the whole width
    up to 1,024, else the multiple of 128 from 512 to 1,024 that pads it least, the larger of two."""
    width = lambda w: w if w <= 1024 else min(range(1024, 511, -128), key=lambda t: -(-w // t) * t)     # noqa: E731
    return min(512, call.rows), width(call.K), width(call.N)


def width_tiles(width):
    parts = [width // d for d in (2, 3) if width % (128 * d) == 0 and width // d > 1152]
    return [t for t in range(512, 1152 + 1, 128) if t < width] + sorted(parts) + [width]


def picked(call):
    """This tree's ``parallel/moe._tiles`` for the call: by the kind, the rows, the groups and the
    widths since PR 57, by the rows and the widths alone in a tree before it."""
    if len(inspect.signature(moe._tiles).parameters) == 3:
        return moe._tiles(call.rows, call.K, call.N)
    return moe._tiles(call.kind, call.rows, call.groups, call.K, call.N)


def row_tiles_by_kind(text):
    """``--tm``: ``{kind: [tm, ...]}`` from entries ``tm`` (every kind) and ``kind:tm``."""
    by_kind = {kind: [] for kind in ("gmm", "gmm_t", "tgmm")}
    for entry in filter(None, text.split(",")):
        kind, _, tm = entry.rpartition(":")
        for each in [kind] if kind else by_kind:
            by_kind[each].append(int(tm))
    return by_kind


def candidates(call, grid, row_tiles, vmem=VMEM):
    fits = lambda t: block_bytes(call, t) <= vmem      # noqa: E731
    pick = picked(call)
    more = list(dict.fromkeys([pick, *((tm,) + pick[1:] for tm in row_tiles[call.kind] if call.rows % tm == 0)]))
    if grid == "picked":
        return more
    out = [(512, tk, tn) for tk in width_tiles(call.K) for tn in width_tiles(call.N) if fits((512, tk, tn))]
    if grid == "near":
        out = [t for t in out if t[1] in (call.K, clipped_rule(call)[1]) and issued_over_needed(t, call.K, call.N) <= NEAR]
    return out + [tiles for tiles in dict.fromkeys((clipped(call), *more)) if tiles not in out and fits(tiles)]


def group_sizes(call, model, how, rng):
    """``[groups]`` int32 summing to at most ``call.rows``: the router's experts' loads
    (``even`` or ``lean``), folded onto the held experts where they stand in, cut to the held
    range where they do not."""
    router, k = model.get("router_width", call.groups), model["num_experts_per_tok"]
    shares = np.full(router, 1.0 / router)
    if how == "lean":
        shares = rng.dirichlet(np.full(router, 0.3))
        for _ in range(8):                          # top-k: no expert takes more than 1 / k
            shares = np.minimum(shares, 1.0 / k)
            shares /= shares.sum()
    if model.get("stand_in"):
        return rng.multinomial(call.rows, shares.reshape(-1, call.groups).sum(0)).astype(np.int32)
    if router == call.groups:
        return rng.multinomial(call.rows, shares).astype(np.int32)
    # a held range alone: its share of all n k assignments, a pass holds at most n of them
    here = ROWS_HERE[call.cell] if how == "even" else 1.0
    landed = rng.multinomial(int(call.rows * k * here), shares[:call.groups] / shares[:call.groups].sum())
    return np.diff(np.minimum(np.cumsum(landed), call.rows), prepend=0).astype(np.int32)


def row_tiles_visited(sizes, tm):
    ends = np.cumsum(sizes)
    starts = ends - sizes
    return int(np.sum(np.where(sizes > 0, -(-ends // tm) - starts // tm, 0)))


def kernel(call, tiles):
    """``fn(lhs, other, sizes) -> out``: the call as ``parallel/moe.py`` makes it on the TPU,
    every piece in turn, with ``tiles`` in place of ``_tiles``'."""
    if grouped is None:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm as rows_last
        tgmm = lambda lhs, *rest: rows_last(lhs.swapaxes(0, 1), *rest)       # noqa: E731
    else:
        gmm, tgmm = grouped.gmm, grouped.tgmm
    per = call.groups // call.pieces
    firsts = [None] if call.pieces == 1 else [jnp.int32(i * per) for i in range(call.pieces)]

    def fn(lhs, other, sizes):
        if call.kind == "tgmm":
            return tuple(tgmm(lhs, other, sizes, lhs.dtype, tiles, first, per) for first in firsts)
        out = None
        for i, first in enumerate(firsts):
            if first is not None and out is None:
                out = jax.lax.empty((call.rows, call.N), lhs.dtype)
            out = gmm(lhs, other[i * per:(i + 1) * per], sizes, lhs.dtype, tiles, first, out,
                      transpose_rhs=call.kind == "gmm_t")
        return out
    return fn


@functools.partial(jax.jit, static_argnums=0)
def reference(call, lhs, other, sizes):
    """The same product expert by expert in float32, every expert's over all the rows with the
    other experts' rows zero (one shape, one compile); the rows of no group come out zero."""
    lhs, other = lhs.astype(jnp.float32), other.astype(jnp.float32)
    group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(call.rows), side="right")
    mine = lambda g: jnp.where((group == g)[:, None], lhs, 0.0)      # noqa: E731
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    per = call.groups // call.pieces
    if call.kind == "tgmm":
        whole = jax.lax.map(lambda g: dot(mine(g).T, other), jnp.arange(call.groups))
        return tuple(whole[i * per:(i + 1) * per] for i in range(call.pieces))
    if call.kind == "gmm_t":
        other = other.swapaxes(1, 2)
    add = lambda out, g: (out + dot(mine(g), other[g]), None)        # noqa: E731
    return jax.lax.scan(add, jnp.zeros((call.rows, call.N), jnp.float32), jnp.arange(call.groups))[0]


def rel(got, want, rows):
    leaves = zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))
    cut = (lambda a: a[:rows]) if rows is not None else (lambda a: a)      # past the groups: unspecified
    d = sum(float(jnp.sum((cut(g).astype(jnp.float32) - cut(w)) ** 2)) for g, w in leaves)
    n = sum(float(jnp.sum(cut(w) ** 2)) for w in jax.tree_util.tree_leaves(want))
    return (d / n) ** 0.5


def kernel_events(trace_dir):
    """The device durations (ms) of the grouped products' kernels in the trace, in time order."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    found, others = [], collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                if re.match(r"%?(?:ds_)?t?gmm\b", e.name.lstrip()):
                    found.append((e.start_ns, e.duration_ns * 1e-6))
                else:
                    others[re.sub(r"[.\d]+$", "", e.name.split(" ")[0])] += 1
    return [ms for _, ms in sorted(found)], others


def measure(call, model, tiles_of, seed, check, calls=3):
    """One trace for the shape: every candidate ``calls`` times on each way of the group
    sizes; a kernel's event a piece, in the order the host made the calls."""
    rng = np.random.default_rng(seed)
    bf = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.5, jnp.bfloat16)       # noqa: E731
    lhs = bf(call.rows, call.K)
    other = bf(call.rows, call.N) if call.kind == "tgmm" else \
        bf(call.groups, *((call.N, call.K) if call.kind == "gmm_t" else (call.K, call.N)))
    ways = {how: group_sizes(call, model, how, rng) for how in ("even", "lean")}
    on_chip = {how: jnp.asarray(sizes) for how, sizes in ways.items()}
    want = {how: reference(call, lhs, other, sizes) for how, sizes in on_chip.items()} if check else {}
    lines, compiled = [], []
    for tiles in tiles_of:
        line = dict(call._asdict(), tiles=list(tiles), vmem_bytes=block_bytes(call, tiles),
                    issued_over_needed=issued_over_needed(tiles, call.K, call.N),
                    visits_bound=(call.rows // tiles[0] + call.groups - 1) / (call.rows // tiles[0]),
                    picked=tuple(tiles) == picked(call),
                    clipped=tuple(tiles) == clipped(call))
        lines.append(line)
        try:
            fn = jax.jit(kernel(call, tiles))
            for how, sizes in ways.items():
                start = time.perf_counter()
                got = jax.block_until_ready(fn(lhs, other, on_chip[how]))
                line.setdefault("first_call_s", time.perf_counter() - start)
                if check:
                    line.setdefault("rel", {})[how] = rel(
                        got, want[how], None if call.kind == "tgmm" else int(sizes.sum()))
            compiled.append((line, fn))
        except Exception as e:  # a tile the compiler refuses is a line of the table too
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    trace_dir = tempfile.mkdtemp(prefix="gmm_sweep_")
    try:
        jax.profiler.start_trace(trace_dir)
        for _, fn in compiled:
            for sizes in on_chip.values():
                for _ in range(calls):
                    out = fn(lhs, other, sizes)
                jax.block_until_ready(out)
        jax.profiler.stop_trace()
        events, others = kernel_events(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if len(events) != len(compiled) * len(ways) * calls * call.pieces:
        raise RuntimeError(f"{len(events)} grouped-product kernels in the trace where {len(compiled)} candidates x "
                           f"{len(ways)} x {calls} calls x {call.pieces} pieces ran; it holds {others.most_common(8)}")
    each = iter(events)
    for line, _ in compiled:
        for how, sizes in ways.items():
            ms = sum(next(each) for _ in range(calls * call.pieces)) / calls
            needed = 2.0 * int(sizes.sum()) * call.K * call.N
            line[how] = dict(ms=ms, tflops=needed / (ms * 1e-3) / 1e12, rows=int(sizes.sum()),
                             load_max_over_mean=float(sizes.max() / sizes.mean()),
                             row_tiles_visited=row_tiles_visited(sizes, line["tiles"][0]),
                             row_tiles_needed=-(-int(sizes.sum()) // line["tiles"][0]))
    return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--shapes", default="", help="only the calls whose kind:KxN holds this, e.g. tgmm:2304x1792")
    ap.add_argument("--grid", default="near", choices=("near", "full", "picked"))
    ap.add_argument("--tm", default=ROW_TILES, help="further row tiles for this tree's pick, for every kind (256) or one (tgmm:1024)")
    ap.add_argument("--vmem", type=float, default=VMEM / 2 ** 20, help="MiB of blocks a candidate may take")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--out", default="chiprun_out/gmm_sweep.jsonl")
    opts = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("gmm_sweep.py measures the compiled kernels: it needs a TPU")
    os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
    manifest = Manifest()
    row_tiles = row_tiles_by_kind(opts.tm)
    with open(opts.out, "w") as f:
        for key in opts.cells.split(","):
            model = manifest.config(manifest.cell(CELLS[key])["config"])["model"]
            for call in expert_calls(manifest, key):
                if opts.shapes not in f"{call.kind}:{call.K}x{call.N}":
                    continue
                try:
                    lines = measure(call, model, candidates(call, opts.grid, row_tiles, opts.vmem * 2 ** 20), opts.seed, opts.check)
                except RuntimeError as e:       # a trace that does not hold the calls: say so, go on
                    print(f"{key} {call.kind} {call.K} -> {call.N}: {e}", flush=True)
                    continue
                for line in lines:
                    line["device"] = jax.devices()[0].device_kind
                    f.write(json.dumps(line) + "\n")
                    f.flush()
                    mark = ("*" if line["picked"] else " ") + ("c" if line["clipped"] else " ")
                    head = (f"{key:9s} {call.kind:5s} {call.rows:6d} x {call.K:4d} -> {call.N:4d} "
                            f"{str(tuple(line['tiles'])):18s}{mark} issued {line['issued_over_needed']:.3f} "
                            f"vmem {line['vmem_bytes'] / 2 ** 20:5.1f} MiB first {line.get('first_call_s', 0):4.1f} s")
                    if "error" in line:
                        print(head, line["error"][:160], flush=True)
                        continue
                    print(head, " | ".join(
                        f"{how} {line[how]['ms']:7.3f} ms {line[how]['tflops']:6.1f} TF/s "
                        f"row tiles {line[how]['row_tiles_visited']}/{line[how]['row_tiles_needed']}"
                        + (f" rel {line['rel'][how]:.1e}" if "rel" in line else "")
                        for how in ("even", "lean")), flush=True)


if __name__ == "__main__":
    main()
