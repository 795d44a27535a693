"""The flash kernel's sliding window (``ops/pallas/flash_attention.py``): the band's tile
schedule against a brute-force count with Python ints (forward and backward views), the
counter ``band_pairs``, the kernel in interpret mode against the banded dense oracle (values
and all three gradients, eight query heads a key/value head), the paths that refuse a window,
and that a call without one binds the kernels it bound before."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")


def allowed(q_tile, k_tile, bq, bk, window):
    """The pairs of one tile that a causal mask with ``window`` allows, by brute force."""
    return sum(1 for r in range(q_tile * bq, (q_tile + 1) * bq)
               for c in range(k_tile * bk, (k_tile + 1) * bk) if c <= r and r - c < window)


def visits(loops):
    seen = {}
    for (lo, hi), masked in loops:
        for tile in range(lo, hi):
            assert tile not in seen, "a tile visited twice"
            seen[tile] = masked
    return seen


# windows smaller than, equal to and larger than a tile; unequal block_q / block_k; a window
# past the sequence; a window of one key
SCHEDULES = [(64, 8, 8, 8), (64, 8, 8, 3), (64, 8, 8, 20), (64, 16, 8, 8), (64, 8, 16, 8),
             (64, 8, 16, 5), (64, 16, 8, 40), (64, 8, 8, 64), (64, 8, 8, 100), (48, 8, 4, 1)]


@pytest.mark.parametrize("T, bq, bk, window", SCHEDULES)
def test_the_bands_schedule_against_a_brute_force_count(T, bq, bk, window):
    """Every allowed pair lies in exactly one visited tile, no visited tile is empty, the
    masked body runs on exactly the tiles an edge crosses, and the forward's and the backward's
    views are the same set of tiles."""
    forward, backward = set(), set()
    for i in range(T // bq):
        seen = visits(fa.band_k_loops(i, bq, bk, window))
        for j in range(T // bk):
            n = allowed(i, j, bq, bk, window)
            assert (j in seen) == (n > 0), (i, j, n)
            if n:
                assert seen[j] == (n < bq * bk), (i, j, n)
        forward |= {(i, j) for j in seen}
    for j in range(T // bk):
        seen = visits(fa.band_q_loops(j, bq, bk, window, T // bq))
        for i in seen:
            assert seen[i] == (allowed(i, j, bq, bk, window) < bq * bk), (i, j)
        backward |= {(i, j) for i in seen}
    assert forward == backward
    visited, needed = fa.band_pairs(T, bq, bk, window)
    assert visited == len(forward) * bq * bk
    assert needed == sum(allowed(i, j, bq, bk, window) for i, j in forward)


def test_without_a_window_the_schedule_is_the_triangles():
    for i in range(8):
        assert fa.causal_k_tiles(i, 16, 8) == fa.causal_k_tiles(i, 16, 8, None)
        assert len(fa.causal_k_tiles(i, 16, 8)) == len(fa.causal_q_tiles(i, 8, 16)) == 2
    visited, needed = fa.band_pairs(64, 8, 8, None)
    assert needed == 64 * 65 // 2 and visited == 36 * 64


def test_the_counter_at_the_cells_shape():
    """8,192 positions under a window of 1,024: 7,864,832 of the triangle's 33,558,528 pairs,
    and a band of 1024-, 512- and 256-tiles visits 2.00, 1.50 and 1.25 times that."""
    assert fa.band_pairs(8192, 1024, 1024, None)[1] == 33_558_528
    ratios = []
    for side in (1024, 512, 256):
        visited, needed = fa.band_pairs(8192, side, side, 1024)
        assert needed == 7_864_832
        ratios.append(round(visited / needed, 2))
    assert ratios == [2.0, 1.5, 1.25]


def census(T, bq, bk, window):
    """``(touched, full, allowed)`` by brute force over every (query, key) pair of ``T`` positions,
    a row of q-tiles at a time: which (q tile, k tile) hold an allowed pair, which hold nothing
    else, and how many pairs the mask allows."""
    touched = np.zeros((T // bq, T // bk), bool)
    full = np.zeros_like(touched)
    keys = np.arange(T, dtype=np.int32)[None, :]
    allowed_pairs = 0
    for i in range(T // bq):
        queries = np.arange(i * bq, (i + 1) * bq, dtype=np.int32)[:, None]
        mask = keys <= queries
        if window is not None:
            mask &= queries - keys < window
        a_tile = mask.reshape(bq, T // bk, bk).sum(axis=(0, 2))
        touched[i], full[i] = a_tile > 0, a_tile == bq * bk
        allowed_pairs += int(a_tile.sum())
    return touched, full, allowed_pairs


# the cells' calls (1,024 positions under 512-tiles, 4,096 under 512, 8,192 under 1,024, and under
# 512 where a window of 1,024 makes a band) and the tiles ``tests/perf/flash_sweep.py`` reads
# beside them on the chip, square and not
AT_THE_CELLS = [(1024, 128, 128, None), (1024, 256, 256, None), (1024, 512, 512, None), (1024, 1024, 1024, None),
                (1024, 512, 512, 1024), (4096, 512, 512, None), (4096, 1024, 1024, None), (4096, 512, 512, 1024),
                (8192, 512, 512, None), (8192, 1024, 1024, None), (8192, 256, 256, 1024), (8192, 512, 512, 1024),
                (8192, 1024, 1024, 1024), (8192, 512, 256, 1024), (8192, 256, 512, 1024), (8192, 1024, 512, 1024)]


@pytest.mark.parametrize("T, bq, bk, window", AT_THE_CELLS,
                         ids=[f"{T}-{bq}x{bk}-{'band' if w else 'triangle'}" for T, bq, bk, w in AT_THE_CELLS])
def test_the_schedule_s_integers_at_the_cells_sizes(T, bq, bk, window):
    """What the forward walks a q-tile, what the backward walks a k-tile and what ``band_pairs``
    counts, against the census: every touched tile visited once and no other, the plain body on
    exactly the tiles the mask fills, the same tiles both ways round."""
    touched, full, allowed_pairs = census(T, bq, bk, window)
    for i in range(T // bq):
        if window is None:
            n_full, last = fa.causal_k_tiles(i, bq, bk)
            seen = {j: j >= n_full for j in range(last)}
        else:
            seen = visits(fa.band_k_loops(i, bq, bk, window))
        assert sorted(seen) == np.flatnonzero(touched[i]).tolist(), i
        assert [j for j in seen if not seen[j]] == np.flatnonzero(full[i]).tolist(), i
    for j in range(T // bk):
        if window is None:
            first, full_from = fa.causal_q_tiles(j, bq, bk)
            seen = {i: i < full_from for i in range(first, T // bq)}
        else:
            seen = visits(fa.band_q_loops(j, bq, bk, window, T // bq))
        assert sorted(seen) == np.flatnonzero(touched[:, j]).tolist(), j
        assert sorted(i for i in seen if not seen[i]) == np.flatnonzero(full[:, j]).tolist(), j
    assert fa.band_pairs(T, bq, bk, window) == (int(touched.sum()) * bq * bk, allowed_pairs)


# ------------------------------------------------------------------ the kernel, interpreted
B, H, G, T, D = 1, 8, 1, 128, 32


@pytest.fixture(scope="module")
def operands():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (B, H, T, D), jnp.float32)
    k, v = (jax.random.normal(key, (B, G, T, D), jnp.float32) for key in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], (B, H, T, D), jnp.float32)


def oracle(q, k, v, window):
    return fa.dense_attention(q, jnp.repeat(k, H // G, 1), jnp.repeat(v, H // G, 1), True, window=window)


# a window smaller than, equal to and larger than a tile, unequal tiles, a window past T
@pytest.mark.parametrize("window, bq, bk", [(32, 32, 32), (20, 32, 32), (50, 16, 32), (32, 32, 16),
                                            (8, 32, 32), (200, 32, 32)])
def test_the_banded_kernel_against_the_dense_oracle(operands, window, bq, bk):
    q, k, v, cot = operands

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, True, block_q=bq, block_k=bk, interpret=True, window=window)

    np.testing.assert_allclose(kernel(q, k, v), oracle(q, k, v, window), atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * cot), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(oracle(*a, window) * cot), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()) + 1e-5)


def test_a_window_is_refused_where_there_is_no_band(operands):
    q, k, v, _ = operands
    with pytest.raises(AssertionError, match="causal"):
        fa.flash_attention(q, k, v, False, window=8, interpret=True)
    long = jnp.zeros((1, 1, 16384, 8), jnp.float32)
    with pytest.raises(ValueError, match="chunked"):
        fa.flash_attention(long, long, long, True, window=1024, interpret=True)
    with pytest.raises(ValueError, match="ring"):
        fa.flash_attention_with_lse(q, k, v, True, window=8, interpret=True)
    from deepspeed_tpu.parallel.ring_attention import ring_attention
    with pytest.raises(ValueError, match="ring_attention"):
        ring_attention(q, k, v, "data", causal=True, window=8)
    with pytest.raises(AssertionError):
        fa.dense_attention(q, q, q, False, window=8)


def test_a_call_without_a_window_walks_the_triangles_two_loops(operands):
    """The forward kernel of a call without a window walks the triangle's two loops as
    before windows, and a windowed one the band's three."""
    q, k, v, _ = operands
    plain = str(jax.make_jaxpr(lambda *a: fa.flash_attention(*a, True, interpret=True))(q, k, v))
    banded = str(jax.make_jaxpr(lambda *a: fa.flash_attention(*a, True, interpret=True, window=8))(q, k, v))
    assert (plain.count("while["), banded.count("while[")) == (2, 3)
    assert fa._resolve(q, None, None, None, True, True)[1:3] == (128, 128)


def test_the_tiles_of_a_windowed_call_come_from_the_window():
    q = jnp.zeros((1, 1, 8192, 128), jnp.bfloat16)
    assert fa._resolve(q, None, None, None, True, False)[1:3] == (1024, 1024)
    assert fa._resolve(q, None, None, None, True, False, 1024)[1:3] == (512, 512)
    short = jnp.zeros((1, 1, 256, 128), jnp.bfloat16)
    assert fa._resolve(short, None, None, None, True, False, 64)[1:3] == (256, 256)
