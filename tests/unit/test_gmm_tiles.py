"""The tiles ``parallel/moe._tiles`` hands the grouped products' kernels
(``ops/pallas/grouped_matmul.py``) at every grouped product the expert cells make: the shapes
are read from the cells' files under ``benchmarks/`` by ``tests/perf/gmm_sweep.py:
expert_calls`` (the sweep measures the same list on the chip).
Arithmetic on shapes: nothing is traced."""

import importlib.util
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
CHOSEN = {(2048, 2048): (512, 2048, 2048), (1024, 2048): (512, 1024, 2048), (2048, 1024): (512, 2048, 1024),
          (512, 2048): (512, 512, 2048), (2048, 512): (512, 2048, 512)}


def test_the_four_expert_cells_make_twenty_four_grouped_products():
    assert len(CALLS) == 24 and len({c[:5] for c in CALLS}) == 24
    assert {(c.rows, c.groups, c.pieces) for c in CALLS} == {(65536, 16, 1), (49152, 8, 1), (65536, 64, 4), (8192, 32, 1)}


@pytest.mark.parametrize("call", CALLS + LATER, ids=[f"{c.cell}-{c.kind}-{c.K}x{c.N}" for c in CALLS + LATER])
def test_the_tiles_divide_the_widths_they_are_given(call):
    tiles = _tiles(call.rows, call.K, call.N)
    clipped, before = sweep.clipped(call), sweep.clipped_rule(call)
    assert call.rows % tiles[0] == 0 and tiles[0] == before[0] == 512
    assert tiles[1] == call.K           # a whole contraction at every width of the six cells
    assert tiles[2] == call.N or (tiles[2] % 128 == 0 and 512 <= tiles[2] < call.N)
    issued = sweep.issued_over_needed(tiles, call.K, call.N)
    assert issued <= 1.04 and issued <= sweep.issued_over_needed(before, call.K, call.N) <= sweep.issued_over_needed(clipped, call.K, call.N)
    # ``ds_tgmm``'s float32 accumulator on the near side of what the chip read, and the blocks among the sweep's candidates
    assert tiles[1] * tiles[2] <= moe.GMM_ACC and sweep.block_bytes(call, tiles) <= 40 * 2 ** 20 < sweep.VMEM
    # and the limit the kernel asks the compiler for holds them, under the cap
    assert sweep.block_bytes(call, tiles) < grouped.vmem_limit(sweep.block_bytes(call, tiles), tiles[1] * tiles[2]) <= grouped.VMEM_CAP
    if call.cell in ("olmoe", "qwen3next"):
        assert before == clipped == BEFORE[call.K, call.N] and tiles == CHOSEN[call.K, call.N]
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
    16 MiB. Whatever the widths, the kernels' limit holds the blocks under its cap."""
    assert _tiles(65536, K, N) == tiles
    blocks = max(grouped.gmm_block_bytes(tiles, K, 2, True), grouped.tgmm_block_bytes(tiles, 2))
    assert blocks < grouped.vmem_limit(blocks, max(tiles[0], tiles[1]) * tiles[2]) < grouped.VMEM_CAP
