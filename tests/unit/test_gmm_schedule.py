"""The walk the grouped products' kernels take over (group, row tile) pairs.

``ops/pallas/grouped_matmul.py`` imports it: ``make_group_metadata``, a PRIVATE function of
JAX's megablox kernels, no API of JAX's. Here it is held to a plain reckoning of the same walk
(a loop over the groups, each visiting the row tiles its rows touch), at the six expert cells'
own rows, groups, pieces and row tile and at the edges a router produces: an empty group, a
group that ends on a tile's edge and one that does not, rows that belong to no group. An
upgrade of JAX that changes the function is red here on the CPU, where on the chip it would be
a wrong product or a slower one. Integers in, integers out: nothing of a model is compiled."""

import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.manifest import Manifest
from deepspeed_tpu.ops.pallas import grouped_matmul as grouped
from deepspeed_tpu.parallel.moe import _tiles

_spec = importlib.util.spec_from_file_location("gmm_sweep", os.path.join(
    os.path.dirname(__file__), "..", "perf", "gmm_sweep.py"))
sweep = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sweep)

MANIFEST = Manifest()


def plain_walk(sizes, m, tm, first, held, visit_empty_groups):
    """``(offsets [G + 1], [(group, row tile), ...])`` for the ``held`` groups from ``first``
    on: a group visits every row tile one of its rows lies in, in order; an empty group visits
    the tile its offset lies in (the last tile, where that is past the rows) if empty groups
    are visited at all (``ds_tgmm`` has their output to zero), and none otherwise."""
    ends = np.cumsum(sizes)
    starts = ends - sizes
    pairs = []
    for g in range(first, first + held):
        if sizes[g]:
            pairs += [(g, t) for t in range(starts[g] // tm, (ends[g] - 1) // tm + 1)]
        elif visit_empty_groups:
            pairs.append((g, min(starts[g] // tm, m // tm - 1)))
    return np.concatenate([[0], ends]), pairs


def check(sizes, m, tm, first=0, held=None):
    """Both kernels' walks against the plain one; returns ``ds_tgmm``'s (empty groups visited)."""
    sizes = np.asarray(sizes, np.int32)
    held = len(sizes) if held is None else held
    for visit_empty_groups in (False, True):            # ``ds_gmm``'s walk and ``ds_tgmm``'s
        (offsets, groups, tiles, start), steps = grouped._schedule(
            jnp.asarray(sizes), m, tm, None if first == 0 else first, held, visit_empty_groups)
        want_offsets, want = plain_walk(sizes, m, tm, first, held, visit_empty_groups)
        steps = int(steps)
        assert int(start[0]) == first
        assert np.array_equal(np.asarray(offsets), want_offsets)
        assert steps == len(want)
        # the grid is as long as the walk can get; only the first ``steps`` entries are read
        assert groups.shape == tiles.shape == (m // tm + len(sizes) - 1,)
        assert list(zip(np.asarray(groups)[:steps].tolist(), np.asarray(tiles)[:steps].tolist())) == want
    return want


KINDS = ("gmm", "gmm_t", "tgmm")
# a cell's first product, by kind (the walk goes by rows, groups, pieces and the row tile the rule picks the kind)
CELL_SHAPES = {(key, kind): next(c for c in sweep.expert_calls(MANIFEST, key) if c.kind == kind)
               for key in sweep.CELLS for kind in KINDS}


def test_the_six_expert_cells_walk_five_shapes():
    assert {key: (c.rows, c.groups, c.pieces) for (key, _), c in CELL_SHAPES.items()} == {
        "mellum2": (65536, 16, 1), "nemotronh": (49152, 8, 1), "olmoe": (65536, 64, 4),
        "qwen3next": (8192, 32, 1), "glm47flash": (32768, 8, 1), "lfm2": (32768, 8, 1)}


@pytest.mark.parametrize("how", ["even", "lean"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("key", sorted(sweep.CELLS))
def test_the_walk_at_a_cell_s_rows_groups_and_row_tile(key, kind, how):
    """At the row tile ``_tiles`` picks the kind since PR 57 (128, 256 or 512 by the rows a group holds)."""
    call = CELL_SHAPES[key, kind]
    model = MANIFEST.config(MANIFEST.cell(sweep.CELLS[key])["config"])["model"]
    sizes = sweep.group_sizes(call, model, how, np.random.default_rng(56))
    tm = _tiles(call.kind, call.rows, call.groups, call.K, call.N)[0]
    assert tm in (128, 256, 512) and call.rows % tm == 0 and len(sizes) == call.groups and sizes.sum() <= call.rows
    a_piece = call.groups // call.pieces
    walked = 0
    for piece in range(call.pieces):        # the experts of four chips arrive in four pieces
        walked += len(check(sizes, call.rows, tm, piece * a_piece, a_piece))
    # every row tile with a row in it once, and once more for every boundary inside a tile
    assert walked == sweep.row_tiles_visited(sizes, tm) + int((sizes == 0).sum())
    assert walked <= call.rows // tm + call.groups - 1 + int((sizes == 0).sum())


EDGES = {
    # [sizes], m, tm, first group, groups held
    "a-group-ends-on-a-tile-s-edge": ([8, 8, 16], 32, 8, 0, None),
    "a-group-ends-inside-a-tile": ([5, 11, 16], 32, 8, 0, None),
    "every-boundary-inside-a-tile": ([3, 7, 9, 13], 32, 8, 0, None),
    "an-empty-group-inside-a-tile": ([5, 0, 11, 16], 32, 8, 0, None),
    "an-empty-group-on-an-edge": ([8, 0, 8, 16], 32, 8, 0, None),
    "an-empty-group-first": ([0, 12, 20], 32, 8, 0, None),
    "an-empty-group-last": ([12, 20, 0], 32, 8, 0, None),
    "two-empty-groups-side-by-side": ([12, 0, 0, 20], 32, 8, 0, None),
    "a-group-over-many-tiles": ([1, 30, 1], 32, 8, 0, None),
    "rows-that-belong-to-no-group": ([5, 6, 2], 32, 8, 0, None),
    "one-tile": ([2, 0, 3, 3], 8, 8, 0, None),
    "a-piece-that-starts-inside-a-tile": ([5, 6, 10, 11], 32, 8, 2, 2),
    "a-piece-whose-first-group-is-empty": ([5, 6, 0, 21], 32, 8, 2, 2),
    "a-piece-that-is-all-empty": ([16, 16, 0, 0], 32, 8, 2, 2),
}


@pytest.mark.parametrize("case", sorted(EDGES))
def test_the_walk_at_an_edge(case):
    sizes, m, tm, first, held = EDGES[case]
    check(sizes, m, tm, first, held)
