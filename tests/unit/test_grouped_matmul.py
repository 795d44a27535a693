"""``ops/pallas/grouped_matmul.py`` interpreted on the CPU: ``ds_gmm``, its ``transpose_rhs`` form
and ``ds_tgmm`` against ``lax.ragged_dot`` and its transpose on the same values, float32 so that
only the order of the sums differs. The groups are uneven, one is EMPTY and every boundary falls
inside a row tile; the widths are toys with the cells' remainders (232 = 1,856 / 8 is no multiple
of 128, as 1,856 is none)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import grouped_matmul as grouped
from deepspeed_tpu.parallel import moe

M, TM = 256, 64
SIZES = (100, 0, 90, 66)                        # uneven, an empty group, no boundary on a row tile
# (K, N, tk, tn): one K tile and several, a width that is no multiple of 128 whole and cut
# (a K remainder is masked, an N remainder clipped), a column tile under the width
WIDTHS = {"whole-k": (256, 256, 256, 128), "whole-k-whole-n": (232, 256, 232, 256), "cut-k": (256, 128, 128, 128),
          "cut-k-with-a-remainder": (232, 256, 128, 256), "three-k-tiles-n-clipped": (384, 232, 128, 128)}


def operands(K, N, groups=len(SIZES), seed=0):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)      # noqa: E731
    return f32(M, K), f32(groups, K, N), f32(M, N)


def close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=2e-4)


@pytest.mark.parametrize("transpose_rhs", [False, True], ids=["gmm", "gmm_t"])
@pytest.mark.parametrize("widths", WIDTHS.values(), ids=WIDTHS.keys())
def test_the_rows_products_are_ragged_dots(widths, transpose_rhs):
    K, N, tk, tn = widths
    lhs, rhs, _ = operands(K, N)
    sizes = jnp.asarray(SIZES, jnp.int32)
    got = grouped.gmm(lhs, rhs.swapaxes(1, 2) if transpose_rhs else rhs, sizes, jnp.float32, (TM, tk, tn),
                      transpose_rhs=transpose_rhs, interpret=True)
    close(got, jax.lax.ragged_dot(lhs, rhs, sizes))


@pytest.mark.parametrize("widths", WIDTHS.values(), ids=WIDTHS.keys())
def test_the_weights_cotangent_is_the_ragged_dots_transpose(widths):
    K, N, tk, tn = widths
    lhs, rhs, grad = operands(K, N)
    sizes = jnp.asarray(SIZES, jnp.int32)
    want, = jax.linear_transpose(lambda r: jax.lax.ragged_dot(lhs, r, sizes), rhs)(grad)
    got = grouped.tgmm(lhs, grad, sizes, jnp.float32, (TM, tk, tn), interpret=True)
    close(got, want)
    assert not np.any(np.asarray(got[1]))            # the empty group's is written, as zeros


@pytest.mark.parametrize("tk", [256, 128], ids=["whole-k", "cut-k"])
@pytest.mark.parametrize("transpose_rhs", [False, True], ids=["gmm", "gmm_t"])
def test_a_chain_of_four_pieces_fills_one_buffer(transpose_rhs, tk):
    """OLMoE's four: eight groups whose weights arrive two at a time; each piece's call writes its
    groups' rows into the buffer of the one before, which starts as nothing has written it."""
    K, N, pieces = 256, 128, 4
    lhs, rhs, _ = operands(K, N, groups=8, seed=1)
    sizes = jnp.asarray([40, 30, 0, 50, 36, 20, 44, 36], jnp.int32)
    out = jnp.full((M, N), jnp.nan, jnp.float32)
    for i in (2, 0, 3, 1):                         # in the order they arrive, not the groups'
        piece = rhs[2 * i:2 * i + 2]
        out = grouped.gmm(lhs, piece.swapaxes(1, 2) if transpose_rhs else piece, sizes, jnp.float32, (TM, tk, 128),
                          jnp.int32(2 * i), out, transpose_rhs=transpose_rhs, interpret=True)
    close(out, jax.lax.ragged_dot(lhs, rhs, sizes))


# twelve groups that arrive three at a time, 1,024 rows: one group EMPTY, and a boundary inside every
# row tile of 128 (so of 256 too): 70 | 160 | 260 370 | 450 | 545 | 650 710 | 830 | 920
PIECES_SIZES = (70, 90, 0, 100, 110, 80, 95, 105, 60, 120, 90, 104)


@pytest.mark.parametrize("tm", [128, 256])
@pytest.mark.parametrize("kind", ["gmm", "gmm_t", "tgmm"])
def test_the_row_tiles_the_rule_picks_from_walk_a_chain_of_four_pieces(kind, tm):
    """OLMoE's chain at the row tiles ``moe._tiles`` picks for it since PR 57 (``moe.GMM_ROW_TILES``
    under 512): the rows' products written piece by piece, in arrival order, into a buffer of NaNs
    (a row no call wrote would stay one), the weights' cotangent a piece at a time."""
    assert {128, 256} < set(moe.GMM_ROW_TILES)
    rows, K, N, a_piece = 1024, 256, 128, 3
    rng = np.random.default_rng(4)
    lhs, rhs, grad = (jnp.asarray(rng.normal(size=shape), jnp.float32) for shape in ((rows, K), (12, K, N), (rows, N)))
    sizes = jnp.asarray(PIECES_SIZES, jnp.int32)
    ends = np.cumsum(PIECES_SIZES)
    assert ends[-1] == rows and all(np.any((ends > t) & (ends < t + 128)) for t in range(0, rows, 128))
    if kind == "tgmm":
        want, = jax.linear_transpose(lambda r: jax.lax.ragged_dot(lhs, r, sizes), rhs)(grad)
        for i in (2, 0, 3, 1):
            got = grouped.tgmm(lhs, grad, sizes, jnp.float32, (tm, K, N), jnp.int32(a_piece * i), a_piece, interpret=True)
            close(got, want[a_piece * i:a_piece * (i + 1)])
        assert not np.any(np.asarray(grouped.tgmm(lhs, grad, sizes, jnp.float32, (tm, K, N), interpret=True)[2]))
        return
    out = jnp.full((rows, N), jnp.nan, jnp.float32)
    for i in (2, 0, 3, 1):                         # in the order they arrive, not the groups'
        piece = rhs[a_piece * i:a_piece * (i + 1)]
        out = grouped.gmm(lhs, piece.swapaxes(1, 2) if kind == "gmm_t" else piece, sizes, jnp.float32, (tm, K, N),
                          jnp.int32(a_piece * i), out, transpose_rhs=kind == "gmm_t", interpret=True)
    close(out, jax.lax.ragged_dot(lhs, rhs, sizes))


@pytest.mark.parametrize("tk", [256, 128], ids=["whole-k", "cut-k"])
def test_a_held_range_leaves_the_rows_outside_every_group_untouched(tk):
    """Two of five groups held, the rows before and after them the existing output's, the rows
    past the last group (the sizes sum to less than ``M``) too; the weights' cotangent of the two."""
    K, N = 256, 128
    lhs, rhs, grad = operands(K, N, groups=5, seed=2)
    sizes = jnp.asarray([50, 70, 30, 40, 20], jnp.int32)              # 210 of 256 rows
    before = jnp.asarray(np.random.default_rng(3).normal(size=(M, N)), jnp.float32)
    got = grouped.gmm(lhs, rhs[1:3], sizes, jnp.float32, (TM, tk, 128), jnp.int32(1), before, interpret=True)
    whole = jax.lax.ragged_dot(lhs, rhs, sizes)
    rows = np.arange(M)
    mine = (rows >= 50) & (rows < 150)
    close(got[mine], whole[mine])
    np.testing.assert_array_equal(np.asarray(got[~mine]), np.asarray(before[~mine]))
    want, = jax.linear_transpose(lambda r: jax.lax.ragged_dot(lhs, r, sizes), rhs)(grad)
    close(grouped.tgmm(lhs, grad, sizes, jnp.float32, (TM, tk, 128), jnp.int32(1), 2, interpret=True), want[1:3])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_bfloat16_operands_round_once_at_the_store(dtype):
    """A whole contraction and a cut one add the same float32 products in another order: bfloat16
    results agree to a rounding of the output."""
    lhs, rhs, _ = (x.astype(dtype) for x in operands(256, 128))
    sizes = jnp.asarray(SIZES, jnp.int32)
    whole = grouped.gmm(lhs, rhs, sizes, dtype, (TM, 256, 128), interpret=True)
    cut = grouped.gmm(lhs, rhs, sizes, dtype, (TM, 128, 128), interpret=True)
    assert whole.dtype == cut.dtype == dtype
    want = jax.lax.ragged_dot(lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes)
    for got in (whole, cut):
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=2 ** -7, atol=0.05)


def test_the_limit_a_call_asks_for_holds_its_blocks_and_stays_under_the_cap():
    for tiles, k in (((512, 2688, 1856), 2688), ((512, 1024, 1024), 2048), ((512, 3072, 2048), 3072)):
        for blocks in (grouped.gmm_block_bytes(tiles, k, 2, True), grouped.tgmm_block_bytes(tiles, 2)):
            assert blocks < grouped.vmem_limit(blocks, tiles[1] * tiles[2]) <= grouped.VMEM_CAP < 128 * 2 ** 20
    assert grouped.gmm_block_bytes((512, 1024, 1024), 2048, 2) - grouped.gmm_block_bytes((512, 1024, 1024), 1024, 2) \
        == 4 * 512 * 1024                            # the accumulator, only where K is cut
    assert grouped.vmem_limit(2 ** 20, 128 * 128) == 16 * 2 ** 20


@pytest.mark.parametrize("K, how", [(2688, "whole_k"), (16384, "cut_k")], ids=["whole-k", "cut-k"])
def test_a_traced_program_leaves_how_each_grouped_product_runs(monkeypatch, K, how):
    """``recorder().counters(engine)`` answers how many of a program's grouped products keep their
    contraction whole: one counter a product, its widths and tiles in the name, left while the
    program's call is open (the engine's ``_call_program``); nothing outside such a call."""
    from deepspeed_tpu.utils import spans
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")         # the kernels' path; only traced here
    rec = spans.recorder()
    engine = rec.new_engine()
    rows, wide = jax.ShapeDtypeStruct((1024, K), jnp.bfloat16), jax.ShapeDtypeStruct((1024, 1856), jnp.bfloat16)
    weights, sizes = jax.ShapeDtypeStruct((8, K, 1856), jnp.bfloat16), jax.ShapeDtypeStruct((8,), jnp.int32)

    def products(lhs, rhs, grad, sizes):
        return (moe.grouped_matmul(lhs, rhs, sizes), moe.grouped_matmul(grad, rhs, sizes, transpose_rhs=True),
                moe.grouped_matmul_weight_grad(lhs, grad, sizes, None, rhs))

    jax.make_jaxpr(products)(rows, weights, wide, sizes)               # no program's call is open: nothing is left
    assert rec.counters(engine) == {}
    with rec.span("train.grad_program", engine=engine, program="loss_and_grad"):
        jax.make_jaxpr(lambda *args: products(*args))(rows, weights, wide, sizes)      # a trace of its own
    def said(kind, *widths):
        """What the rule saw and what it bought: 128 rows a group here, and the most (group, row tile)
        pairs the walk can take over the row tiles the 1,024 rows need."""
        tm, tk, tn = moe._tiles(kind, 1024, 8, *widths)
        return f"{widths[0]}x{widths[1]} in {tm}x{tk}x{tn}, 128 rows a group, visits <= {(1024 // tm + 7) / (1024 // tm):.2f}"

    assert (moe._tiles("gmm", 1024, 8, K, 1856)[1] == K) == (how == "whole_k")
    # the rows' cotangent contracts the OTHER width, 1,856, which stays whole either way
    assert rec.counters(engine) == {f"moe.gmm.{how}[loss_and_grad] " + said("gmm", K, 1856): 1,
                                    "moe.gmm_t.whole_k[loss_and_grad] " + said("gmm_t", 1856, K): 1,
                                    f"moe.tgmm.{how}[loss_and_grad] " + said("tgmm", K, 1856): 1}
    # a whole contraction beside eight short groups takes the smallest row tile, a cut one keeps 512
    assert f" in {128 if how == 'whole_k' else 512}x" in next(name for name in rec.counters(engine) if ".tgmm." in name)
    whole = sum(n for name, n in rec.counters(engine).items() if ".whole_k[loss_and_grad]" in name)
    assert whole == (3 if how == "whole_k" else 1)


def test_moe_imports_no_kernel_from_megablox():
    import inspect
    assert not [line for line in inspect.getsource(moe).splitlines() if "import" in line and "megablox" in line]
    assert moe.grouped is grouped
