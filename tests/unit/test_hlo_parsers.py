"""Direct unit tests for the utils/hlo.py text parsers on hand-written HLO.

The lint program passes stand on these parsers; each fixture below is a
minimal HLO fragment exercising one syntactic wrinkle the real optimizer
emits — async ``-start`` tuple conventions, nested-brace module headers,
bracketed layout types inside entry layouts — so a parser regression fails
here with a two-line diff instead of inside an engine-scale lint run.
"""

from deepspeed_tpu.utils import hlo

# async all-gather-start: (operands..., results..., u32 context scalars).
# Only the produced bf16[64] halves are wire transfers.
ASYNC_GATHER = """
HloModule m

ENTRY main {
  p0 = bf16[8]{0} parameter(0)
  p1 = bf16[8]{0} parameter(1)
  ags = (bf16[8]{0}, bf16[8]{0}, bf16[64]{0}, bf16[64]{0}, u32[], u32[]) all-gather-start(p0, p1), dimensions={0}
  agd = (bf16[64]{0}, bf16[64]{0}) all-gather-done(ags)
  ROOT out = bf16[64]{0} get-tuple-element(agd), index=0
}
"""

# all-reduce-start returns its results directly (no operand echo)
ASYNC_REDUCE = """
HloModule m

ENTRY main {
  p0 = f32[1024]{0} parameter(0)
  ars = f32[1024]{0} all-reduce-start(p0), to_apply=add
  ROOT ard = f32[1024]{0} all-reduce-done(ars)
}
"""

PERMUTE_START = """
HloModule m

ENTRY main {
  p0 = f16[32,32]{1,0} parameter(0)
  cps = (f16[32,32]{1,0}, f16[32,32]{1,0}, u32[], u32[]) collective-permute-start(p0), source_target_pairs={{0,1},{1,0}}
  ROOT cpd = f16[32,32]{1,0} collective-permute-done(cps)
}
"""

ALIAS_HEADER = """
HloModule m, input_output_alias={ {0}: (0, {}, may-alias), {2}: (1, {0}, must-alias) }, entry_computation_layout={(f32[8,8]{1,0}, bf16[64]{0}, f32[4]{0})->(f32[8,8]{1,0}, pred[], bf16[64]{0})}

ENTRY main {
  ROOT t = (f32[8,8]{1,0}, pred[], bf16[64]{0}) parameter(0)
}
"""


def test_async_all_gather_start_reports_produced_halves_only():
    types = hlo.collective_result_types(ASYNC_GATHER, "all-gather")
    assert types == ["bf16", "bf16"]
    results = hlo.collective_results(ASYNC_GATHER, "all-gather")
    assert [(dt, dims) for _op, dt, dims in results] == \
        [("bf16", (64,)), ("bf16", (64,))]
    # the -done is bookkeeping, never a second transfer
    assert hlo.collective_counts(ASYNC_GATHER) == {"all-gather": 1}


def test_async_all_reduce_start_counts_results_directly():
    assert hlo.collective_result_types(ASYNC_REDUCE, "all-reduce") == ["f32"]
    assert hlo.collective_counts(ASYNC_REDUCE) == {"all-reduce": 1}


def test_collective_permute_start_drops_context_scalars():
    results = hlo.collective_results(PERMUTE_START, "collective-permute")
    assert [(dt, dims) for _op, dt, dims in results] == [("f16", (32, 32))]


def test_collective_bytes_covers_bf16_tuples_from_start_variants():
    # 2 produced bf16[64] buffers * 2 bytes = 256
    assert hlo.collective_bytes(ASYNC_GATHER) == 2 * 64 * 2
    assert hlo.collective_bytes(ASYNC_REDUCE) == 1024 * 4


def test_dtype_bytes_table_covers_lint_element_types():
    for dt, nbytes in (("bf16", 2), ("f16", 2), ("f32", 4), ("f64", 8),
                       ("s4", 1), ("u4", 1), ("f8e4m3fn", 1), ("f8e5m2", 1),
                       ("pred", 1), ("c64", 8), ("c128", 16)):
        assert hlo.dtype_bytes(dt) == nbytes, dt
    assert hlo.dtype_bytes("token") is None


def test_input_output_aliases_parses_nested_brace_header():
    aliases = hlo.input_output_aliases(ALIAS_HEADER)
    assert aliases == {0: [((0,), (), "may-alias")],
                       1: [((2,), (0,), "must-alias")]}


def test_entry_layout_types_split_past_bracketed_layouts():
    assert hlo.entry_parameter_types(ALIAS_HEADER) == \
        [("f32", (8, 8)), ("bf16", (64,)), ("f32", (4,))]
    assert hlo.entry_result_types(ALIAS_HEADER) == \
        [("f32", (8, 8)), ("pred", ()), ("bf16", (64,))]


def test_result_bytes_reads_the_definition_type():
    assert hlo.result_bytes("  p = f32[128]{0} parameter(0)") == 512
    assert hlo.result_bytes(
        "  t = (bf16[64]{0}, bf16[64]{0}) all-gather-done(x)") == 256
    assert hlo.result_bytes("ENTRY main {") == 0


def test_f32_dot_probe_reads_unannotated_operands():
    # pre-backend HLO writes bare operand names with no inline types
    text = """
ENTRY main {
  a = bf16[8,16]{1,0} parameter(0)
  b = bf16[16,4]{1,0} parameter(1)
  ca = f32[8,16]{1,0} convert(a)
  cb = f32[16,4]{1,0} convert(b)
  ROOT d = f32[8,4]{1,0} dot(ca, cb), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    assert hlo.f32_dots_with_lowp_operands(text) == [("d", ["ca", "cb"])]


def test_lossy_roundtrip_detected_through_unannotated_converts():
    text = """
ENTRY main {
  a = f32[128]{0} parameter(0)
  down = bf16[128]{0} convert(a)
  up = f32[128]{0} convert(down)
  ROOT r = f32[128]{0} add(up, up)
}
"""
    assert hlo.lossy_convert_roundtrips(text) == [("down", ("f32", "bf16", "f32"))]
    # a widening detour (f32 -> f64 -> f32) is NOT lossy
    widen = text.replace("bf16[128]{0} convert(a)", "f64[128]{0} convert(a)")
    assert hlo.lossy_convert_roundtrips(widen) == []
