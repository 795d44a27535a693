"""Pallas TPU kernel of the expert layer's combine (``parallel/moe._sum_rows`` has the entry
point and the rule that picks it): every token's rows of the sorted ``[n k, H]`` added up,

    y[t] = sum of ys[m] over the sorted rows m with tok[m] == t,      in float32, rounded once

The rows are sorted by group (the expert whose weights they went through), stably, so inside
a group they lie in TOKEN order: the rows that the tokens ``[t0, t0 + T)`` have in group ``g``
are one contiguous RUN of ``ys``, ``[runs[i, g], runs[i + 1, g])`` (``run_bounds``). A gather
of ``n k`` rows by index pays 29-36 ns a row whatever the row's width (PERF.md, PR 53: a row
of a tiled array is ``H / 128`` pieces fetched one descriptor at a time); a run is whole tiles
streamed at HBM's rate, and what is left of the permutation happens in fast memory.

The grid walks the token tiles; a tile's sum ``[T, H]`` stays in a float32 scratch and is
written once. ``ys`` stays in HBM. A tile's runs, group after group, are cut into VISITS of one
chunk of ``CHUNK`` rows each, on a grid of whole chunks (``visits``: the chunk, and the rows of
it that are the run's, for every visit of the call in order). The kernel is one loop over its
tile's visits: the copy of the visit ``AHEAD`` places on, be it another tile's, is started
before the present chunk is added, so ``AHEAD`` copies are in flight at any time and no visit
waits for a whole copy but the call's first. A chunk is added through the MXU: a ``[T, chunk]``
one-hot of the rows' tokens, zero in the columns of the rows outside the run, times the chunk
``[chunk, H]``, accumulated in float32. A product with one or zero is exact, so a token's sum
is the float32 sum of its rows in their sorted order. The trip count is the runs' own chunks:
the work follows ``n k``, and a chunk in which a run ends is visited once more, by the tile of
the run that begins there; an empty run costs nothing. A row outside its run meets a zero:
``ys`` is finite there or the tile is not (every row of the whole range's form is written by
its product).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
TOKENS = 256       # tokens a tile: the one-hot's rows. PERF.md, PR 53, has the probe's table
CHUNK = 128        # sorted rows a copy: the one-hot's columns, one pass of the MXU's depth
COLUMNS = 512      # columns of the hidden width a product: what its float32 result holds at once
AHEAD = 3          # copies in flight: one alone left every chunk its copy's latency (0.92 ms a call for 0.70)


def run_bounds(group, tok, n, G, tokens=TOKENS):
    """``runs [n / tokens + 1, G]`` int32: ``runs[i, g]`` is the first sorted row of group ``g``
    whose token is ``i * tokens`` or later, so tile ``i``'s run in the group ends at
    ``runs[i + 1, g]``. ``group [n k]`` (``0 .. G - 1``, ascending) and ``tok [n k]``
    (ascending inside a group) make one ascending key; its ``G (n / tokens + 1)`` queries are
    answered by a compare and a sum the compiler fuses, not by a search's loop of gathers."""
    key = group.astype(jnp.int32) * n + tok.astype(jnp.int32)
    at = (jnp.arange(n // tokens + 1, dtype=jnp.int32)[:, None] * tokens
          + jnp.arange(G, dtype=jnp.int32)[None, :] * n)
    return jnp.sum(key[None, :] < at.reshape(-1, 1), axis=1, dtype=jnp.int32).reshape(at.shape)


def visits(runs, rows):
    """``(first [tiles + 1], chunks, lo, hi)``: tile ``i``'s visits are ``first[i] .. first[i + 1] - 1``
    of the call's ``first[-1]``; visit ``v`` copies chunk ``chunks[v]`` and adds its rows
    ``[lo[v], hi[v])``. The three are ``rows / CHUNK + tiles G`` long, the most a call can make
    (every chunk once, and once more for every run that begins inside one); the rest is zeros.
    Dense compares and sums over ``tiles G`` runs: nothing is searched or gathered."""
    tiles, G = runs.shape[0] - 1, runs.shape[1]
    lo, hi = runs[:-1].reshape(-1), runs[1:].reshape(-1)
    begins = lo // CHUNK
    count = jnp.where(hi > lo, (hi - 1) // CHUNK - begins + 1, 0)
    ends = jnp.cumsum(count)
    starts = ends - count
    v = jnp.arange(rows // CHUNK + tiles * G, dtype=jnp.int32)
    mine = (starts[None, :] <= v[:, None]) & (v[:, None] < ends[None, :])       # [visits, runs]: one run a visit
    pick = lambda of_run: jnp.sum(jnp.where(mine, of_run[None, :], 0), axis=1, dtype=jnp.int32)   # noqa: E731
    first = jnp.concatenate([jnp.zeros(1, jnp.int32), ends.reshape(tiles, G)[:, -1]])
    return first, pick(begins - starts) + jnp.where(v < ends[-1], v, 0), pick(lo), pick(hi)


def chunks_visited(runs):
    """The visits a call makes for these bounds (``runs [tiles + 1][G]``, plain integers): every
    non-empty run's chunks, on the grid of whole chunks."""
    return sum(int(hi - 1) // CHUNK - int(lo) // CHUNK + 1
               for above, below in zip(runs[:-1], runs[1:]) for lo, hi in zip(above, below) if hi > lo)


def rows_sum_chunks(n, k, G, T=TOKENS):
    """``(chunks a balanced router's runs visit, chunks the rows fill)`` for ``n`` tokens of ``k``
    rows over ``G`` groups in tiles of ``T`` tokens: every run ``n k / G / (n / T)`` rows long.
    Their ratio is what the kernel copies and multiplies over what it needs; a router that leans
    has fewer runs that end inside a chunk, never more than ``(n / T) G`` of them."""
    rows = n * k
    runs = [[g * rows // G + i * rows // G * T // n for g in range(G)] for i in range(n // T + 1)]
    return chunks_visited(runs), rows // CHUNK


def _kernel(first_ref, chunks_ref, lo_ref, hi_ref, tok_ref, ys_ref, y_ref, acc_ref, buf_ref, sem_ref, *, precision):
    T, H = acc_ref.shape
    slots, C = buf_ref.shape[:2]
    i = pl.program_id(0)
    total = first_ref[pl.num_programs(0)]
    tile = jax.lax.broadcasted_iota(jnp.int32, (T, C), 0) + i * T
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)

    def copy(v):
        slot = jax.lax.rem(v, slots)
        return pltpu.make_async_copy(ys_ref.at[pl.ds(pl.multiple_of(chunks_ref[v] * C, C), C), :],
                                     buf_ref.at[slot], sem_ref.at[slot])

    @pl.when(i == 0)
    def _():
        for v in range(AHEAD):
            @pl.when(v < total)
            def _():
                copy(v).start()

    acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def add(v, carry):
        copy(v).wait()

        # into the buffer of the visit before this one, which is added
        @pl.when(v + AHEAD < total)
        def _():
            copy(v + AHEAD).start()

        j, slot = chunks_ref[v], jax.lax.rem(v, slots)
        row = j * C + lane
        mine = jnp.where((row >= lo_ref[v]) & (row < hi_ref[v]), tok_ref[pl.ds(j, 1), :], -1)      # [1, C]
        onehot = (tile == mine).astype(buf_ref.dtype)                                                # [T, C]
        for c0 in range(0, H, COLUMNS):
            c1 = min(c0 + COLUMNS, H)
            acc_ref[:, c0:c1] += jnp.dot(onehot, buf_ref[slot, :, c0:c1],
                                         preferred_element_type=_F32, precision=precision)
        return carry

    jax.lax.fori_loop(first_ref[i], first_ref[i + 1], add, 0)
    y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _vmem_bytes(M, H, tokens, itemsize):
    """What the kernel holds in fast memory: the sum, the chunks' buffers, two blocks of the
    result, the rows' tokens twice, a product's float32 result; and as much again for what the
    compiler adds."""
    return 2 * (tokens * H * 4 + (AHEAD + 1) * CHUNK * H * itemsize + 2 * tokens * H * itemsize + 2 * M * 4
                + tokens * min(COLUMNS, H) * 4)


@functools.partial(jax.jit, static_argnames=("n", "interpret"), inline=True)
def rows_sum(ys, tok, runs, n, interpret=False):
    """``y [n, H]`` from the sorted rows ``ys [n k, H]``, their tokens ``tok [n k]`` and
    ``runs = visits(run_bounds(group, tok, n, G, tokens), n k)``, whose first array's length
    says the tile (made once a layer: the forward's call and the backward's read the same).
    ``n`` is whole tiles, the rows whole chunks, ``H`` whole registers of 128 lanes (``fits``)."""
    M, H = ys.shape
    tiles = runs[0].shape[0] - 1
    tokens = n // tiles
    assert fits(n, M, H, tokens) and runs[1].shape[0] >= M // CHUNK, (ys.shape, n)
    # a float32 row goes through the MXU whole: three bfloat16 pieces, six passes
    precision = jax.lax.Precision.HIGHEST if ys.dtype == _F32 else None
    return pl.pallas_call(
        functools.partial(_kernel, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles,),
            in_specs=[pl.BlockSpec((M // CHUNK, CHUNK), lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tokens, H), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tokens, H), _F32), pltpu.VMEM((AHEAD + 1, CHUNK, H), ys.dtype),
                            pltpu.SemaphoreType.DMA((AHEAD + 1,))]),
        out_shape=jax.ShapeDtypeStruct((n, H), ys.dtype),
        # a tile takes up the copies the tile before it started: in order, on one core. The limit is
        # the default's 16 MiB at two bytes an element (18 at 2,688 columns): what the kernel is
        # given, the compiler's own values beside it (the dispatch's source in ``S(1)``) lose
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(16 * 2 ** 20, _vmem_bytes(M, H, tokens, ys.dtype.itemsize))),
        cost_estimate=pl.CostEstimate(flops=2 * tokens * M * H, transcendentals=0,
                                      bytes_accessed=(M + n) * H * ys.dtype.itemsize + 4 * M),
        interpret=interpret,
        name="ds_moe_rows_sum",
    )(*runs, tok.astype(jnp.int32).reshape(M // CHUNK, CHUNK), ys)


def fits(n, rows, H, tokens=TOKENS):
    """Whether the kernel takes these shapes: whole tiles, whole chunks, whole registers."""
    return n % tokens == 0 and rows % CHUNK == 0 and H % 128 == 0
