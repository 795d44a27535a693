"""Flash-attention kernel parity tests (interpret mode on CPU; compiled path covered by
bench/TPU runs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.flash_attention import flash_attention, dense_attention


# bf16 exercises the kernels' MXU-native cast paths (bf16 operands, fp32 accumulate);
# fp32 pins exact numerics. Tolerances scale with the dtype's epsilon.
_DTYPES = [(jnp.float32, 2e-5, 2e-4), (jnp.bfloat16, 3e-2, 5e-2)]


@pytest.mark.parametrize("dtype,fwd_tol,bwd_tol", _DTYPES)
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 3, 256, 64), (1, 2, 128, 32)])
def test_forward_parity(causal, shape, dtype, fwd_tol, bwd_tol):
    B, H, T, D = shape
    q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    out_f = flash_attention(q, k, v, causal, None, 128, 128, True).astype(jnp.float32)
    # reference in fp32 regardless of input dtype
    out_d = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), causal=causal)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=fwd_tol, atol=fwd_tol)


# the one-pass backward at 1, 2 and 4 tiles a side (dQ accumulates across the k-tiles of
# the grid, dK/dV across the q-tiles of the loop), and at the benchmark cell's T = 1024,
# D = 64 with the tiles _resolve picks
_BWD_CASES = [((2, 3, 256, 64), 256), ((2, 3, 256, 64), 128), ((2, 3, 256, 64), 64),
              ((1, 2, 1024, 64), None)]


@pytest.mark.parametrize("dtype,fwd_tol,bwd_tol", _DTYPES)
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape,block", _BWD_CASES,
                         ids=[f"T{s[2]}-block{b}" for s, b in _BWD_CASES])
def test_backward_parity(causal, shape, block, dtype, fwd_tol, bwd_tol):
    q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    g = jax.random.normal(jax.random.PRNGKey(9), shape, jnp.float32).astype(dtype)
    gf = jax.grad(lambda q, k, v: jnp.sum((flash_attention(q, k, v, causal, None, block, block,
                                                           True) * g).astype(jnp.float32)),
                  argnums=(0, 1, 2))(q, k, v)
    f32 = (q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    gd = jax.grad(lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=causal)
                                          * g.astype(jnp.float32)),
                  argnums=(0, 1, 2))(*f32)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=bwd_tol, atol=bwd_tol, err_msg=f"d{name}")


def test_block_size_autofit():
    # T=192 is not divisible by the default blocks; the kernel must fit them down
    shape = (1, 2, 192, 32)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    out_f = flash_attention(q, k, v, True, None, 256, 512, True)
    out_d = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d), rtol=2e-5, atol=2e-5)


def test_sm_scale_override():
    shape = (1, 2, 128, 32)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    out_f = flash_attention(q, k, v, False, 0.5, 128, 128, True)
    out_d = dense_attention(q, k, v, causal=False, sm_scale=0.5)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block", [128, 64])
@pytest.mark.parametrize("causal", [False, True])
def test_bias_mask_parity(causal, block):
    """Additive key bias (the BERT padding mask) fused in-kernel must match the dense
    oracle in forward and all three gradients, at one tile and at two tiles a side (the
    backward takes the bias a k-tile at a time)."""
    B, H, T, D = 2, 3, 128, 32
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(1), 3))
    bias = np.zeros((B, 1, 1, T), np.float32)
    # padding sits at the END of the sequence (BERT convention) so no causal row is
    # fully masked — a fully-masked row's softmax is degenerate/undefined
    bias[0, ..., -17:] = -1e9
    bias[1, ..., -5:] = -1e9
    bias = jnp.asarray(bias)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, None, block, block, True,
                                       bias=bias) ** 2)

    def f_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal,
                                       bias=bias.reshape(B, 1, T)) ** 2)

    np.testing.assert_allclose(float(f_flash(q, k, v)), float(f_dense(q, k, v)),
                               rtol=2e-5)
    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_dropout_parity_vs_oracle(causal):
    """In-kernel dropout must equal dense attention with the exact oracle keep-mask
    (dropout_keep_reference reproduces the kernel's coordinate-hash bit stream), in
    forward AND gradients — this pins fwd/bwd mask agreement across all three kernels."""
    from deepspeed_tpu.ops.pallas.flash_attention import dropout_keep_reference
    B, H, T, D = 2, 2, 128, 32
    rate, seed = 0.15, 4242
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(2), 3))
    keep = dropout_keep_reference(seed, B, H, T, T, rate)
    # the mask really drops ~rate of entries and scales the rest
    frac = float((keep == 0).mean())
    assert abs(frac - rate) < 0.02, frac

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, None, 64, 64, True,
                                       dropout_rate=rate, dropout_seed=seed) ** 2)

    def f_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal, dropout_keep=keep) ** 2)

    np.testing.assert_allclose(float(f_flash(q, k, v)), float(f_dense(q, k, v)),
                               rtol=2e-5)
    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_dropout_block_shape_invariance(what):
    """The coordinate-hash mask must not depend on block configuration (this is what
    guarantees fwd/bwd agreement when block_q != block_k): the forward's output, and the
    gradients of the backward that regenerates the bits on its transposed tiles."""
    B, H, T, D = 1, 2, 256, 32
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(3), 3))

    def run(block_q, block_k, seed):
        attn = lambda q, k, v: flash_attention(q, k, v, False, None, block_q, block_k, True,
                                               dropout_rate=0.1, dropout_seed=seed)
        if what == "forward":
            return [attn(q, k, v)]
        return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)

    r1, r2, r3 = run(64, 128, 7), run(256, 64, 7), run(64, 128, 8)
    for a, b in zip(r1, r2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(r1[0]) - np.asarray(r3[0])).max() > 1e-3  # seed actually matters


@pytest.mark.parametrize("segmented", [False, True], ids=["contiguous", "segmented"])
def test_lse_cotangent_and_segments_backward_parity(segmented):
    """``flash_attention_with_lse`` differentiates through BOTH outputs (the lse cotangent
    folds into delta), causal with dropout, at two tiles a side; segmented, the local
    sequence is two global chunks (the zigzag ring's (7,) operand) and the dropout bits
    are those of the global coordinates."""
    from deepspeed_tpu.ops.pallas.flash_attention import (DEFAULT_MASK_VALUE,
                                                          dropout_keep_reference,
                                                          flash_attention_with_lse)
    B, H, T, D = 1, 2, 128, 32
    rate, seed = 0.15, 31
    q, k, v, g = (jax.random.normal(kk, (B, H, T, D), jnp.float32)
                  for kk in jax.random.split(jax.random.PRNGKey(5), 4))
    g_lse = jax.random.normal(jax.random.PRNGKey(6), (B, H, T), jnp.float32)
    if segmented:   # chunks 0 and 3 of a global sequence of 4 x 64
        segments = (0, 192)
        where = np.concatenate([np.arange(0, 64), np.arange(192, 256)])
    else:
        segments, where = None, np.arange(T)
    keep = dropout_keep_reference(seed, B, H, 256, 256, rate)[:, :, where][:, :, :, where]

    def f_flash(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v, True, None, 64, 64, True,
                                            dropout_rate=rate, dropout_seed=seed,
                                            q_segments=segments, k_segments=segments)
        return jnp.sum(out * g) + jnp.sum(lse * g_lse)

    def f_dense(q, k, v):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, DEFAULT_MASK_VALUE)
        out = dense_attention(q, k, v, causal=True, dropout_keep=keep)
        return jnp.sum(out * g) + jnp.sum(jax.nn.logsumexp(scores, axis=-1) * g_lse)

    np.testing.assert_allclose(float(f_flash(q, k, v)), float(f_dense(q, k, v)), rtol=2e-5)
    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("blocks", [None, (256, 512), (128, 64)],
                         ids=["picked", "256x512", "128x64"])
@pytest.mark.parametrize("T", [128, 192, 1024, 4096, 8192])
def test_causal_tile_schedule(T, blocks):
    """The schedule itself, no kernel run: the forward's bounds (k-tiles of a q-tile) and
    the backward's (q-tiles of a k-tile) visit every tile that holds an unmasked element
    exactly once, none that holds none, and run the masked body on exactly the tiles the
    diagonal crosses — at the tiles ``_resolve`` picks and at two uneven pairs."""
    from deepspeed_tpu.ops.pallas.flash_attention import (_resolve, causal_k_tiles,
                                                          causal_q_tiles)
    _, bq, bk, _ = _resolve(jax.ShapeDtypeStruct((1, 1, T, 64), jnp.bfloat16), None,
                            *(blocks or (None, None)), True, True)
    assert T % bq == 0 and T % bk == 0
    nq, nk = T // bq, T // bk
    q_lo, k_lo = np.arange(nq)[:, None] * bq, np.arange(nk)[None, :] * bk
    some = q_lo + bq - 1 >= k_lo                 # largest query sees smallest key
    every = q_lo >= k_lo + bk - 1                # smallest query sees largest key
    want = {(i, j): not every[i, j] for i in range(nq) for j in range(nk) if some[i, j]}

    forward = {}
    for i in range(nq):
        n_full, last = causal_k_tiles(i, bq, bk)
        assert 0 <= n_full <= last <= nk
        for j in range(last):
            assert (i, j) not in forward
            forward[i, j] = j >= n_full
    backward = {}
    for j in range(nk):
        first, full_from = causal_q_tiles(j, bq, bk)
        assert 0 <= first <= full_from <= nq
        for i in range(first, nq):
            assert (i, j) not in backward
            backward[i, j] = i < full_from
    assert forward == want
    assert backward == want
    if blocks is None:      # the picked tiles are square: only the diagonal is masked
        assert bq == bk and sum(want.values()) == nq


def test_transformer_layer_masked_dropout_uses_flash(monkeypatch):
    """DeepSpeedTransformerLayer with an attention_mask AND train-mode attn dropout must
    dispatch to the flash kernel (VERDICT: the BERT pretraining path stayed dense)."""
    from deepspeed_tpu.ops.transformer.transformer import (DeepSpeedTransformerConfig,
                                                           DeepSpeedTransformerLayer)
    import importlib
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    calls = {"n": 0}
    real = fa.flash_attention

    def spy(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(fa, "flash_attention", spy)
    cfg = DeepSpeedTransformerConfig(batch_size=2, max_seq_length=64, hidden_size=64,
                                     heads=4, attn_dropout_ratio=0.1,
                                     hidden_dropout_ratio=0.0, num_hidden_layers=2,
                                     initializer_range=0.02, bf16=False)
    layer = DeepSpeedTransformerLayer(cfg)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64), jnp.float32)
    mask = np.zeros((2, 1, 1, 64), np.float32)
    mask[:, ..., -8:] = -1e9
    out = layer.apply(params, x, attention_mask=jnp.asarray(mask),
                      rng=jax.random.PRNGKey(2), deterministic=False)
    assert calls["n"] == 1, "masked+dropout attention did not dispatch to flash"
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_long_context_matches_dense(causal):
    """The k-chunked long-context path (used past the resident kernel's VMEM cap)
    must match dense attention exactly — fwd and grads, causal decomposition
    included (diagonal square + trailing rectangles)."""
    from deepspeed_tpu.ops.pallas.flash_attention import _flash_attention_chunked

    B, H, T, D = 1, 2, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32) for kk in ks)
    out = _flash_attention_chunked(q, k, v, causal, None, True, chunk=64)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    g = jax.random.normal(jax.random.PRNGKey(12), (B, H, T, D), jnp.float32)
    gc = jax.grad(lambda q, k, v: jnp.sum(_flash_attention_chunked(
        q, k, v, causal, None, True, chunk=64) * g), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(dense_attention(
        q, k, v, causal=causal) * g), argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gc, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{n} (causal={causal})")


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_dropout_matches_global_oracle(causal):
    """Chunked tiles hash GLOBAL coordinates: dropout through the chunked path must
    equal dense attention with the whole-sequence oracle mask (the
    long-context path previously ran without attention dropout)."""
    from deepspeed_tpu.ops.pallas.flash_attention import (_flash_attention_chunked,
                                                          dropout_keep_reference)
    B, H, T, D = 1, 2, 256, 32
    rate, seed = 0.15, 99
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    q, k, v = (jax.random.normal(kk, (B, H, T, D), jnp.float32) for kk in ks)
    keep = dropout_keep_reference(seed, B, H, T, T, rate)

    def f_chunk(q, k, v):
        return jnp.sum(_flash_attention_chunked(q, k, v, causal, None, True,
                                                chunk=64, rate=rate, seed=seed) ** 2)

    def f_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal, dropout_keep=keep) ** 2)

    np.testing.assert_allclose(float(f_chunk(q, k, v)), float(f_dense(q, k, v)),
                               rtol=2e-5)
    gc = jax.grad(f_chunk, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gc, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{n} (causal={causal})")


def test_long_context_dispatch_raises_when_chunk_ineligible(monkeypatch):
    """Past the resident VMEM ceiling, an ineligible chunked path must raise a
    descriptive error instead of compiling the resident kernel into a Mosaic
    failure."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    D = 32
    k = jnp.zeros((1, 1, 16384, D), jnp.bfloat16)
    v = jnp.zeros((1, 1, 16384, D), jnp.bfloat16)
    # non-square cross attention
    with pytest.raises(ValueError, match="square self-attention"):
        flash_attention(jnp.zeros((1, 1, 128, D), jnp.bfloat16), k, v)
    # additive bias not supported on the chunked path
    q = jnp.zeros((1, 1, 16384, D), jnp.bfloat16)
    with pytest.raises(ValueError, match="additive bias"):
        flash_attention(q, k, v, bias=jnp.zeros((1, 1, 1, 16384)))
    # no divisor chunk >= 1024 (8704 = 512 * 17)
    t = jnp.zeros((1, 1, 8704, D), jnp.bfloat16)
    with pytest.raises(ValueError, match="divisor"):
        flash_attention(t, t, t)


def test_kernel_splits_itself_over_the_context_mesh(eight_devices):
    """Under a jit whose mesh is in context (the engine's), the kernel runs per
    batch-and-head shard (ops/pallas/partition.py): same values and gradients as
    one device, bias and all, and no collective — XLA cannot partition a compiled
    Pallas kernel, so nothing may be left for it to partition."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.utils.hlo import collective_counts

    mesh = build_mesh(data=4, model=2, pipe=1)
    shape = (4, 2, 128, 32)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    bias = jnp.zeros((4, 1, 128), jnp.float32).at[1, :, -40:].set(-1e9)

    def loss(q, k, v, bias):
        return jnp.sum(flash_attention(q, k, v, True, bias=bias, interpret=True) ** 2)

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    want = step(q, k, v, bias)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    args = (*(put(x, P("data", "model")) for x in (q, k, v)), put(bias, P("data")))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        got = step(*args)
        text = step.lower(*args).compile().as_text()
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=2e-5, atol=2e-4), got, want)
    # the scalar loss is summed across shards; the kernel's tensors never move
    assert set(collective_counts(text)) <= {"all-reduce"}
    assert got[1][0].sharding.is_equivalent_to(args[0].sharding, 4)


# the row-major entry at the cells' head shapes (query heads, key/value heads, D | Dv) and a toy
# T of two tiles a side: lanes (GLM-4.7-Flash, Qwen3-Next, Nemotron-H, Ouro), and what the entry
# turns head-major itself: Mellum 2's band, GPT-2 XL's 25 heads of 64, LFM2's and Granite's 32
# over 8 of 64, Xing's 192 | 128. ``given`` says which of q, k and v arrive head-major
# [B, heads, T, width] (a model's rotary pass writes them so) beside the others as projected
_ROW_CASES = [(20, 20, 256, 256, None, "lanes", ""), (16, 2, 256, 256, None, "lanes", ""),
              (32, 4, 128, 128, 96, "heads_major", ""), (16, 16, 128, 128, None, "lanes", ""),
              (25, 25, 64, 64, None, "heads_major", ""), (32, 8, 64, 64, None, "heads_major", ""),
              (32, 32, 192, 128, None, "heads_major", ""),
              (20, 20, 256, 256, None, "lanes", "qkv"), (32, 4, 128, 128, 96, "heads_major", "qk"),
              (16, 2, 256, 256, None, "lanes", "qk"), (32, 8, 64, 64, None, "heads_major", "qk"),
              (32, 4, 128, 128, None, "lanes", "")]


@pytest.mark.parametrize("rate", [0.0, 0.25], ids=["plain", "dropout"])
@pytest.mark.parametrize("H,Hkv,D,Dv,window,layout,given", _ROW_CASES,
                         ids=[f"{c[0]}over{c[1]}x{c[2]}-{c[3]}{'-band' if c[4] else ''}{'-' + c[6] + '-head-major' if c[6] else ''}"
                              for c in _ROW_CASES])
def test_row_major_entry_matches_head_major(H, Hkv, D, Dv, window, layout, given, rate):
    """Forward and all three gradients of ``flash_attention_rows`` against ``flash_attention`` on
    the same values turned head-major; under dropout the same mask for a seed."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_rows, layout_of
    assert layout_of(D, Dv, window) == layout
    B, T = (2, 256) if H <= 20 else (1, 256)
    keys = jax.random.split(jax.random.PRNGKey(H + D), 4)
    q = jax.random.normal(keys[0], (B, T, H * D), jnp.float32)
    k = jax.random.normal(keys[1], (B, T, Hkv * D), jnp.float32)
    v = jax.random.normal(keys[2], (B, T, Hkv * Dv), jnp.float32)
    g = jax.random.normal(keys[3], (B, T, H * Dv), jnp.float32)
    kw = dict(block_q=128, block_k=128, interpret=True, dropout_rate=rate,
              dropout_seed=jnp.int32(11) if rate else None, window=window)
    heads = lambda a, n: a.reshape(B, T, n, -1).transpose(0, 2, 1, 3)      # noqa: E731

    def rows(q, k, v):
        q, k, v = (heads(a, n) if name in given else a for a, n, name in ((q, H, "q"), (k, Hkv, "k"), (v, Hkv, "v")))
        y = flash_attention_rows(q, k, v, H, Hkv, True, **kw)
        return jnp.sum(y * g), y

    def head_major(q, k, v):
        y = flash_attention(heads(q, H), heads(k, Hkv), heads(v, Hkv), True, **kw)
        y = y.transpose(0, 2, 1, 3).reshape(B, T, H * Dv)
        return jnp.sum(y * g), y

    (_, y_r), grads_r = jax.jit(jax.value_and_grad(rows, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, y_h), grads_h = jax.jit(jax.value_and_grad(head_major, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    assert y_r.shape == (B, T, H * Dv) and np.isfinite(np.asarray(y_r)).all()
    np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_h), rtol=2e-5, atol=2e-5)
    for a, b, name in zip(grads_r, grads_h, "qkv"):
        assert a.shape == b.shape and np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("H,Hkv,D,given", [(4, 2, 128, "qk"), (4, 4, 128, "")], ids=["q-and-k-head-major", "all-row-major"])
def test_the_row_major_kernels_split_themselves_over_the_context_mesh(eight_devices, H, Hkv, D, given):
    """``flash_attention_rows`` under the engine's mesh (batch over ``data``, the heads over
    ``model``: the lane blocks of a row-major operand's last axis): one device's values and gradients, each in its operand's layout and
    sharding, and nothing left for XLA to partition."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_rows
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.utils.hlo import collective_counts

    mesh = build_mesh(data=4, model=2, pipe=1)
    B, T = 4, 128
    shape = lambda n, name: (B, n, T, D) if name in given else (B, T, n * D)      # noqa: E731
    spec = lambda name: P("data", "model") if name in given else P("data", None, "model")      # noqa: E731
    q, k, v = (jax.random.normal(key, shape(n, name), jnp.float32)
               for key, n, name in zip(jax.random.split(jax.random.PRNGKey(0), 3), (H, Hkv, Hkv), "qkv"))

    def loss(q, k, v):
        return jnp.sum(flash_attention_rows(q, k, v, H, Hkv, True, interpret=True) ** 2)

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    want = step(q, k, v)
    args = tuple(jax.device_put(x, NamedSharding(mesh, spec(name))) for x, name in zip((q, k, v), "qkv"))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        got = step(*args)
        text = step.lower(*args).compile().as_text()
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-4), got, want)
    assert set(collective_counts(text)) <= {"all-reduce"}
    for grad, arg in zip(got[1], args):
        assert grad.sharding.is_equivalent_to(arg.sharding, grad.ndim)
