"""Block-sparse attention tests (parity with reference tests/unit/test_sparse_attention.py
strategy: kernel vs dense equivalents, layout properties, utils)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig, BSLongformerSparsityConfig,
                                                DenseSparsityConfig, FixedSparsityConfig,
                                                SparseAttentionUtils, SparseSelfAttention,
                                                BertSparseSelfAttention, VariableSparsityConfig)
from deepspeed_tpu.ops.pallas.block_sparse_attention import (block_sparse_attention, build_luts,
                                                             dense_blocksparse_attention)

B, H, T, D, BLOCK = 2, 4, 256, 32, 32


def qkv(seed=0, shape=(B, H, T, D)):
    return tuple(jax.random.normal(k, shape, jnp.float32)
                 for k in jax.random.split(jax.random.PRNGKey(seed), 3))


# ---------------- layout properties ----------------

def test_dense_layout_all_ones():
    layout = DenseSparsityConfig(num_heads=H, block=BLOCK).make_layout(T)
    assert layout.shape == (H, T // BLOCK, T // BLOCK)
    assert layout.all()


def test_fixed_layout_properties():
    cfg = FixedSparsityConfig(num_heads=H, block=BLOCK, num_local_blocks=4, num_global_blocks=1)
    layout = cfg.make_layout(T)
    nb = T // BLOCK
    # local windows are dense within themselves
    for w in range(0, nb, 4):
        assert layout[0, w:w + 4, w:w + 4].all()
    # single layout propagated to all heads
    assert (layout == layout[0]).all()
    # global column (last block of each window) attended by everyone
    assert layout[0, :, 3].all()


def test_fixed_unidirectional_upper_triangle_empty():
    cfg = FixedSparsityConfig(num_heads=H, block=BLOCK, num_local_blocks=4,
                              attention="unidirectional")
    layout = cfg.make_layout(T)
    nb = T // BLOCK
    for r in range(nb):
        assert not layout[0, r, r + 1:].any()


def test_fixed_different_layout_per_head():
    cfg = FixedSparsityConfig(num_heads=H, block=BLOCK, different_layout_per_head=True,
                              num_local_blocks=4, num_global_blocks=1,
                              num_different_global_patterns=4)
    layout = cfg.make_layout(T)
    assert not (layout[0] == layout[1]).all()


def test_bigbird_layout_properties():
    cfg = BigBirdSparsityConfig(num_heads=H, block=BLOCK, num_random_blocks=1,
                                num_sliding_window_blocks=3, num_global_blocks=1)
    layout = cfg.make_layout(T)
    nb = T // BLOCK
    assert layout[0, 0, :].all() and layout[0, :, 0].all()  # global first block
    for r in range(1, nb - 1):
        assert layout[0, r, r - 1:r + 2].all()  # sliding window


def test_bslongformer_layout_properties():
    cfg = BSLongformerSparsityConfig(num_heads=H, block=BLOCK, num_sliding_window_blocks=3,
                                     global_block_indices=[0, 2])
    layout = cfg.make_layout(T)
    assert layout[0, 2, :].all() and layout[0, :, 2].all()


def test_variable_layout_global_ranges():
    cfg = VariableSparsityConfig(num_heads=H, block=BLOCK, num_random_blocks=0,
                                 local_window_blocks=[2, 4],
                                 global_block_indices=[0], global_block_end_indices=[2])
    layout = cfg.make_layout(T)
    assert layout[0, :, 0].all() and layout[0, :, 1].all()


def test_layout_seq_not_divisible_raises():
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=H, block=BLOCK).make_layout(T + 7)


def test_invalid_configs_raise():
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=H, num_local_blocks=4, num_global_blocks=3)
    with pytest.raises(NotImplementedError):
        FixedSparsityConfig(num_heads=H, attention="sideways")
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=H, attention="unidirectional",
                            horizontal_global_attention=True)
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=H, num_different_global_patterns=2)


def test_build_luts_roundtrip():
    cfg = FixedSparsityConfig(num_heads=H, block=BLOCK, num_local_blocks=4)
    layout = cfg.make_layout(T)
    counts, cols, counts_t, rows_t = build_luts(layout)
    nb = T // BLOCK
    for h in range(H):
        for i in range(nb):
            active = set(np.nonzero(layout[h, i])[0])
            assert set(cols[h * nb + i, :counts[h * nb + i]]) == active


# ---------------- kernel parity ----------------

@pytest.mark.parametrize("pattern", ["fixed", "fixed_uni", "bigbird", "bslongformer", "variable"])
def test_kernel_parity(pattern):
    causal = False
    if pattern == "fixed":
        cfg = FixedSparsityConfig(num_heads=H, block=BLOCK, num_local_blocks=4)
    elif pattern == "fixed_uni":
        cfg = FixedSparsityConfig(num_heads=H, block=BLOCK, num_local_blocks=4,
                                  attention="unidirectional")
        causal = True
    elif pattern == "bigbird":
        cfg = BigBirdSparsityConfig(num_heads=H, block=BLOCK)
    elif pattern == "bslongformer":
        cfg = BSLongformerSparsityConfig(num_heads=H, block=BLOCK)
    else:
        cfg = VariableSparsityConfig(num_heads=H, block=BLOCK, num_random_blocks=1)
    layout = cfg.make_layout(T)
    q, k, v = qkv()
    out_s = block_sparse_attention(q, k, v, layout, BLOCK, causal=causal)
    out_d = dense_blocksparse_attention(q, k, v, layout, BLOCK, causal=causal)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d), rtol=3e-5, atol=3e-5)


def test_kernel_backward_parity():
    cfg = FixedSparsityConfig(num_heads=H, block=BLOCK, num_local_blocks=4)
    layout = cfg.make_layout(T)
    q, k, v = qkv()
    g = jax.random.normal(jax.random.PRNGKey(5), q.shape)
    gs = jax.grad(lambda q, k, v: jnp.sum(block_sparse_attention(q, k, v, layout, BLOCK) * g),
                  argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(dense_blocksparse_attention(q, k, v, layout, BLOCK) * g),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gs, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4,
                                   err_msg=f"d{n}")


def test_dense_config_matches_full_attention():
    from deepspeed_tpu.ops.pallas.flash_attention import dense_attention
    layout = DenseSparsityConfig(num_heads=H, block=BLOCK).make_layout(T)
    q, k, v = qkv()
    out_s = block_sparse_attention(q, k, v, layout, BLOCK)
    out_full = dense_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_full), rtol=3e-5, atol=3e-5)


# ---------------- modules + utils ----------------

def test_sparse_self_attention_module():
    attn = SparseSelfAttention(FixedSparsityConfig(num_heads=H, block=BLOCK))
    q, k, v = qkv()
    out = attn(q, k, v)
    assert out.shape == q.shape
    # with a key padding mask the dense path is used; zero mask = no-op vs sparse path
    out_masked = attn(q, k, v, key_padding_mask=jnp.zeros((B, T)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_masked), rtol=3e-5, atol=3e-5)


def test_bert_sparse_self_attention():
    layer = BertSparseSelfAttention(hidden_size=H * D, num_attention_heads=H,
                                    sparsity_config=FixedSparsityConfig(num_heads=H, block=BLOCK))
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, H * D), jnp.float32)
    out = layer.apply(params, x)
    assert out.shape == (B, T, H * D)


def test_pad_unpad_roundtrip():
    ids = jnp.ones((2, 100), jnp.int32)
    mask = jnp.ones((2, 100), jnp.int32)
    pad_len, ids_p, mask_p, _, _, _ = SparseAttentionUtils.pad_to_block_size(
        block_size=64, input_ids=ids, attention_mask=mask, pad_token_id=9)
    assert pad_len == 28
    assert ids_p.shape == (2, 128)
    assert int(ids_p[0, -1]) == 9 and int(mask_p[0, -1]) == 0
    out = SparseAttentionUtils.unpad_sequence_output(pad_len, ids_p)
    assert out.shape == (2, 100)


def test_extend_position_embedding():
    pe = jnp.arange(16 * 4, dtype=jnp.float32).reshape(16, 4)
    ext = SparseAttentionUtils.extend_position_embedding(pe, 40)
    assert ext.shape == (40, 4)
    np.testing.assert_array_equal(np.asarray(ext[16:32]), np.asarray(pe))


@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("causal", [False, True])
def test_grouped_kernel_parity(group, causal):
    """Row-group union LUT + membership masks must be
    numerically identical to the ungrouped kernel and the dense oracle — fwd AND
    grads, causal included."""
    cfg = BigBirdSparsityConfig(num_heads=H, block=BLOCK)
    layout = cfg.make_layout(T)
    assert (T // BLOCK) % group == 0
    q, k, v = qkv()
    out_g = block_sparse_attention(q, k, v, layout, BLOCK, causal=causal, group=group)
    out_d = dense_blocksparse_attention(q, k, v, layout, BLOCK, causal=causal)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d), rtol=3e-5, atol=3e-5)

    g = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    gs = jax.grad(lambda q, k, v: jnp.sum(
        block_sparse_attention(q, k, v, layout, BLOCK, causal=causal, group=group) * g),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(
        dense_blocksparse_attention(q, k, v, layout, BLOCK, causal=causal) * g),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gs, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4,
                                   err_msg=f"d{n} (group={group})")


@pytest.mark.parametrize("group", [1, 2])
def test_empty_row_inside_nonempty_group(group):
    """An all-masked q-row packed into a group whose union is non-empty must yield
    ZERO output and finite grads (the l-clamp guards the 0/0; regression pin for a
    review-flagged NaN scenario that the clamp in fact prevents)."""
    lay = np.ones((H, T // BLOCK, T // BLOCK), np.int64)
    lay[:, 1, :] = 0   # empty q-row inside group {0,1}
    lay[:, :, 2] = 0   # empty k-column inside a group too (dkv side)
    q, k, v = qkv()
    out = block_sparse_attention(q, k, v, lay, BLOCK, group=group)
    ref = dense_blocksparse_attention(q, k, v, lay, BLOCK)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)
    row1 = np.asarray(out)[:, :, BLOCK:2 * BLOCK, :]
    np.testing.assert_array_equal(row1, np.zeros_like(row1))
    g = jax.grad(lambda q: jnp.sum(block_sparse_attention(q, k, v, lay, BLOCK,
                                                          group=group)))(q)
    assert bool(jnp.isfinite(g).all())


@pytest.mark.parametrize("group", [1, 2])
def test_dma_path_parity(monkeypatch, group):
    """The manual-DMA kernels remain the production path past the VMEM residency
    budget; force them (the resident fast path otherwise shadows them in every
    test) and re-check fwd + grad parity vs the dense oracle."""
    import deepspeed_tpu.ops.pallas.block_sparse_attention as bsa
    monkeypatch.setattr(bsa, "_resident_fits", lambda *a, **k: False)
    cfg = BigBirdSparsityConfig(num_heads=H, block=BLOCK)
    layout = cfg.make_layout(T)
    q, k, v = qkv()
    out = block_sparse_attention(q, k, v, layout, BLOCK, group=group)
    ref = dense_blocksparse_attention(q, k, v, layout, BLOCK)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)
    g = jax.random.normal(jax.random.PRNGKey(8), q.shape)
    gs = jax.grad(lambda q, k, v: jnp.sum(block_sparse_attention(
        q, k, v, layout, BLOCK, group=group) * g), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(dense_blocksparse_attention(
        q, k, v, layout, BLOCK) * g), argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gs, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4,
                                   err_msg=f"d{n} (dma, group={group})")
