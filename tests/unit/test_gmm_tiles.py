"""The tiles ``parallel/moe._tiles`` hands megablox at every grouped product the expert
cells make: the shapes are read from the cells' files under ``benchmarks/`` by
``tests/perf/gmm_sweep.py: expert_calls`` (the sweep measures the same list on the chip).
Arithmetic on shapes: nothing is traced."""

import importlib.util
import os

import pytest

from benchmarks.manifest import Manifest
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
# the widths that (512, 1024, 1024) already divided: their programs are the parent's
AS_BEFORE = {(2048, 2048): (512, 1024, 1024), (1024, 2048): (512, 1024, 1024), (2048, 1024): (512, 1024, 1024),
             (512, 2048): (512, 512, 1024), (2048, 512): (512, 1024, 512)}


def test_the_four_expert_cells_make_twenty_four_grouped_products():
    assert len(CALLS) == 24 and len({c[:5] for c in CALLS}) == 24
    assert {(c.rows, c.groups, c.pieces) for c in CALLS} == {(65536, 16, 1), (49152, 8, 1), (65536, 64, 4), (8192, 32, 1)}


@pytest.mark.parametrize("call", CALLS + LATER, ids=[f"{c.cell}-{c.kind}-{c.K}x{c.N}" for c in CALLS + LATER])
def test_the_tiles_divide_the_widths_they_are_given(call):
    tiles = _tiles(call.rows, call.K, call.N)
    clipped = sweep.clipped(call)
    assert call.rows % tiles[0] == 0
    for tile, width in zip(tiles[1:], (call.K, call.N)):
        assert tile == width or (tile % 128 == 0 and tile < width)
    issued = sweep.issued_over_needed(tiles, call.K, call.N)
    assert issued <= 1.04 and issued <= sweep.issued_over_needed(clipped, call.K, call.N)
    assert sweep.block_bytes(call, tiles) < sweep.VMEM
    if call.cell in ("olmoe", "qwen3next"):
        assert tiles == clipped == AS_BEFORE[call.K, call.N]
    elif sweep.issued_over_needed(clipped, call.K, call.N) == 1.0:
        assert issued == 1.0            # widths the clipped tiles divided already (2,048 and 3,072)
    else:
        assert issued < sweep.issued_over_needed(clipped, call.K, call.N)
