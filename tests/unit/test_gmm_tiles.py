"""The tiles ``parallel/moe._tiles`` hands the grouped products' kernels
(``ops/pallas/grouped_matmul.py``) at every grouped product the expert cells make: the shapes
are read from the cells' files under ``benchmarks/`` by ``tests/perf/gmm_sweep.py:
expert_calls`` (the sweep measures the same list on the chip). Since PR 57 the ROW tile goes by
the kind of product and the rows a group holds: ``ROW_TILE`` is what the rule picks at the six
cells' shapes, by cell and kind, as the chip's sweep read them (``chiprun_out/pr57*/``).
Arithmetic on shapes: nothing is traced."""

import importlib.util
import inspect
import os

import pytest

from benchmarks.manifest import Manifest
from deepspeed_tpu.ops.pallas import grouped_matmul as grouped
from deepspeed_tpu.parallel import moe
from deepspeed_tpu.parallel.moe import _tiles

_spec = importlib.util.spec_from_file_location("gmm_sweep", os.path.join(
    os.path.dirname(__file__), "..", "perf", "gmm_sweep.py"))
sweep = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sweep)

MANIFEST = Manifest()
FOUR = ("mellum2", "nemotronh", "olmoe", "qwen3next")      # the cells PR 47 chose the rule at
CALLS = [call for key in FOUR for call in sweep.expert_calls(MANIFEST, key)]
# the cells since: the rule is held to their shapes too (GLM-4.7-Flash's: 32,768 rows, 2,048 | 3,072 | 1,536)
LATER = [call for key in sweep.CELLS if key not in FOUR for call in sweep.expert_calls(MANIFEST, key)]
# what ``_tiles`` picked for OLMoE's and Qwen3-Next's widths until PR 55, under megablox's 16 MiB
BEFORE = {(2048, 2048): (512, 1024, 1024), (1024, 2048): (512, 1024, 1024), (2048, 1024): (512, 1024, 1024),
          (512, 2048): (512, 512, 1024), (2048, 512): (512, 1024, 512)}
# what the chip's sweep chose for them (PERF.md, PR 55): the whole contraction and the whole width
# beside it (OLMoE's ``[2048, 2048]`` is the bound itself, ``moe.GMM_ACC``), 0-4 % faster a call than
# the tiles before at OLMoE's, level at Qwen3-Next's
CHOSEN = {(2048, 2048): (2048, 2048), (1024, 2048): (1024, 2048), (2048, 1024): (2048, 1024),
          (512, 2048): (512, 2048), (2048, 512): (2048, 512)}
KINDS = ("gmm", "gmm_t", "tgmm")
# the row tile the rule picks (PR 57), by cell, for ``KINDS`` in turn (a kind's two products take the same):
# 512 at every one of them until then
ROW_TILE = {"olmoe": (256, 256, 256), "qwen3next": (128, 128, 128), "mellum2": (256, 256, 256),
            "glm47flash": (256, 256, 256), "lfm2": (256, 256, 256), "nemotronh": (256, 256, 512)}


def tiles_of(call):
    return _tiles(call.kind, call.rows, call.groups, call.K, call.N)


def ids(calls):
    return [f"{c.cell}-{c.kind}-{c.K}x{c.N}" for c in calls]


def test_the_four_expert_cells_make_twenty_four_grouped_products():
    assert len(CALLS) == 24 and len({c[:5] for c in CALLS}) == 24
    assert {(c.rows, c.groups, c.pieces) for c in CALLS} == {(65536, 16, 1), (49152, 8, 1), (65536, 64, 4), (8192, 32, 1)}


@pytest.mark.parametrize("call", CALLS + LATER, ids=ids(CALLS + LATER))
def test_the_tiles_divide_the_widths_they_are_given(call):
    tiles = tiles_of(call)
    clipped, before = sweep.clipped(call), sweep.clipped_rule(call)
    assert call.rows % tiles[0] == 0 and tiles[0] in moe.GMM_ROW_TILES and before[0] == 512
    assert tiles[1] == call.K           # a whole contraction at every width of the six cells
    assert tiles[2] == call.N or (tiles[2] % 128 == 0 and 512 <= tiles[2] < call.N)
    issued = sweep.issued_over_needed(tiles, call.K, call.N)
    assert issued <= 1.04 and issued <= sweep.issued_over_needed(before, call.K, call.N) <= sweep.issued_over_needed(clipped, call.K, call.N)
    # ``ds_tgmm``'s float32 accumulator on the near side of what the chip read, and the blocks among the sweep's candidates
    assert tiles[1] * tiles[2] <= moe.GMM_ACC and sweep.block_bytes(call, tiles) <= 40 * 2 ** 20 < sweep.VMEM
    # and the limit the kernel asks the compiler for holds them, under the cap
    assert sweep.block_bytes(call, tiles) < grouped.vmem_limit(sweep.block_bytes(call, tiles), tiles[1] * tiles[2]) <= grouped.VMEM_CAP
    if call.cell in ("olmoe", "qwen3next"):
        assert before == clipped == BEFORE[call.K, call.N] and tiles[1:] == CHOSEN[call.K, call.N]
    elif sweep.issued_over_needed(clipped, call.K, call.N) == 1.0:
        assert issued == 1.0            # widths the clipped tiles divided already (2,048 and 3,072)
    else:
        assert issued < sweep.issued_over_needed(clipped, call.K, call.N)


@pytest.mark.parametrize("K, N, tiles", [(16384, 1856, (512, 1024, 640)), (9216, 8192, (512, 1024, 1024)),
                                         (4096, 2688, (512, 4096, 896)), (8192, 2688, (512, 8192, 512)),
                                         (2688, 8192, (512, 2688, 1024)), (256, 16384, (512, 256, 8192))],
                         ids=["deep", "deep-and-wide", "deep-whole", "the-deepest-whole", "wide", "shallow-and-wide"])
def test_a_contraction_the_bound_cannot_hold_is_cut_as_before(K, N, tiles):
    """Past the cells' widths: K stays whole while a column tile of 512 beside it keeps ``ds_tgmm``'s
    accumulator under the bound (8,192 does) and N takes the widest tile under it, of those that pad
    it least; else both are cut at the tiles that pad them least up to 1,024, as under megablox's
    16 MiB, and the row tile beside a CUT contraction stays 512 whatever the kind (a smaller one would
    fetch the weights' block again a row tile). Whatever the widths, the kernels' limit holds the
    blocks under its cap."""
    got = {_tiles(kind, 65536, 16, K, N) for kind in KINDS}
    assert {t[1:] for t in got} == {tiles[1:]} and (got == {tiles} if tiles[1] < K else max(got) <= tiles)
    blocks = max(grouped.gmm_block_bytes(tiles, K, 2, True), grouped.tgmm_block_bytes(tiles, 2))
    assert blocks < grouped.vmem_limit(blocks, max(tiles[0], tiles[1]) * tiles[2]) < grouped.VMEM_CAP


@pytest.mark.parametrize("call", CALLS + LATER, ids=ids(CALLS + LATER))
def test_the_row_tile_at_a_cell_s_call_shape_is_what_the_chip_chose(call):
    """By kind and by the rows a group holds (1,024 OLMoE, 256 Qwen3-Next, 4,096 Mellum 2, GLM and
    LFM2, 6,144 Nemotron-H): the fastest row tile the chip's sweep read, or within 0.8 % of it
    (``chiprun_out/pr57a/``), and whole row tiles at every call shape."""
    tm = tiles_of(call)[0]
    assert tm == ROW_TILE[call.cell][KINDS.index(call.kind)]
    assert call.rows % tm == 0 and tm <= 512


@pytest.mark.parametrize("K, N", [(2688, 1856), (1856, 2688)], ids=["the-first-product", "the-second"])
def test_nemotron_h_s_weight_gradients_keep_the_row_tile_the_chip_chose(K, N):
    """Eight groups of 6,144 rows, three column tiles a visit: 512, where 256 read 2 % slower a call
    (PERF.md, PR 55 and PR 57); its rows' products take the smaller tile."""
    assert _tiles("tgmm", 49152, 8, K, N)[0] == 512
    assert _tiles("gmm", 49152, 8, K, N)[0] <= 256 and _tiles("gmm_t", 49152, 8, N, K)[0] <= 256


@pytest.mark.parametrize("kind", KINDS)
def test_the_tiles_are_a_function_of_the_kind_and_the_shapes_alone(kind, monkeypatch):
    """No knob: five positional arguments, no default among them, nothing read from the environment
    or from a model's or a cell's name; the same arguments give the same tiles."""
    assert list(inspect.signature(_tiles).parameters) == ["kind", "rows", "groups", "contraction", "columns"]
    assert all(p.default is p.empty for p in inspect.signature(_tiles).parameters.values())
    source = inspect.getsource(_tiles) + inspect.getsource(moe._row_tile)
    assert not [word for word in ("environ", "getenv", "config", *sweep.CELLS) if word in source]
    before = {call: tiles_of(call) for call in CALLS + LATER if call.kind == kind}
    monkeypatch.setenv("GMM_ROW_TILE", "512")
    monkeypatch.setenv("DS_TPU_GMM_TM", "512")
    assert before == {call: tiles_of(call) for call in before} and len(before) == 12
    with pytest.raises(KeyError):
        _tiles("another kind", 65536, 64, 2048, 2048)


@pytest.mark.parametrize("K, N", [(2048, 2048), (1024, 2048), (2688, 1856), (2304, 1792), (512, 2048), (2048, 3072)],
                         ids=lambda width: str(width))
@pytest.mark.parametrize("kind", KINDS)
def test_fewer_rows_a_group_never_pick_a_larger_row_tile(kind, K, N):
    """At one product's widths and rows, from one group to a group every sixteen rows: the row tile
    only falls, from 512 at one group (every visit needed) to the smallest candidate."""
    for rows in (8192, 49152, 65536):
        picks = [_tiles(kind, rows, groups, K, N)[0] for groups in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)]
        assert picks == sorted(picks, reverse=True), (rows, picks)
        assert picks[0] == 512 and picks[-1] == min(moe.GMM_ROW_TILES)
        assert {_tiles(kind, rows, groups, K, N)[1:] for groups in (1, 64, 512)} == {_tiles(kind, rows, 8, K, N)[1:]}


@pytest.mark.parametrize("rows, among", [(1024, (128, 256, 512)), (640, (128,)), (768, (128, 256)), (1000, (512,)), (96, (96,))])
def test_rows_the_candidates_do_not_divide(rows, among):
    """The kernels take whole row tiles: the rule picks among the candidates that divide the rows,
    and where none does the tile is what it was before PR 57, 512 or all the rows."""
    assert {_tiles(kind, rows, 8, 2048, 2048)[0] for kind in KINDS} <= set(among)
