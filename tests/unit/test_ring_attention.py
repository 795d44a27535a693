"""Ring attention (sequence parallelism) vs dense attention on the 8-device mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops.pallas.flash_attention import (dense_attention,
                                                      flash_attention_with_lse)
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.parallel.ring_attention import ring_attention_sharded

B, H, T, D = 2, 4, 256, 32


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(data=8, model=1, pipe=1)


def qkv(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, H, T, D), jnp.float32) for k in ks)


def test_flash_lse_matches_dense_logsumexp():
    q, k, v = qkv()
    out, lse = flash_attention_with_lse(q, k, v, interpret=True)
    import math
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(D)
    ref_lse = jax.scipy.special.logsumexp(scores, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense_attention(q, k, v)),
                               rtol=1e-5, atol=1e-5)


def test_flash_lse_cotangent_matches_autodiff():
    """grad through BOTH outputs (out and lse) must match dense autodiff — the lse
    cotangent is what makes the pure-JAX ring backward correct."""
    q, k, v = qkv(1)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, H, T), jnp.float32)

    def loss_flash(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v, interpret=True)
        return jnp.sum(out ** 2) + jnp.sum(w * lse)

    def loss_dense(q, k, v):
        import math
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(D)
        lse = jax.scipy.special.logsumexp(scores, axis=-1)
        out = dense_attention(q, k, v)
        return jnp.sum(out ** 2) + jnp.sum(w * lse)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(mesh, causal):
    q, k, v = qkv(2)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal, interpret=True)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    # really sequence-sharded over the ring axis
    assert not out.sharding.is_fully_replicated


# The causal-grads, dropout, and GPT-2 sequence-parallel integration tests below
# are the slow tail of this file (15-45s each on the 8-rank interpret mesh,
# compile-bound).
@pytest.mark.parametrize("causal", [False, True])
def test_ring_grads_match_dense(mesh, causal):
    q, k, v = qkv(3)
    g = jax.random.normal(jax.random.PRNGKey(7), (B, H, T, D), jnp.float32)
    spec = NamedSharding(mesh, P(None, None, "data", None))
    g = jax.device_put(g, spec)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=causal,
                                              interpret=True) * g)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) * g)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name} (causal={causal})")


def test_ring_memory_is_chunked(mesh):
    """The per-chunk flash only ever sees [T/n]-sized operands: a sequence whose
    FULL [T, T] score matrix would be enormous still runs (no O(T^2) anywhere)."""
    T_big = 1024  # scores would be [1024, 1024] per (b, h) — chunk kernel sees 128
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, T_big, D), jnp.float32) for kk in ks)
    # ONE compiled program, as a model's step runs it: called eagerly, a ``shard_map`` compiles every
    # primitive of the ring's body as an 8-device program of its own
    out = jax.jit(lambda q, k, v: ring_attention_sharded(q, k, v, mesh, interpret=True))(q, k, v)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_gpt2_sequence_parallel_matches_dense(mesh):
    """GPT-2 with with_sequence_parallel over 8 ranks: loss AND grads equal the
    plain dense model on the full sequence (positions offset per rank, ring
    attention, pmean'd token loss)."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=128, n_positions=128, n_embd=32, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 128)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)  # global shift BEFORE sharding

    sp_loss = model.sequence_parallel_loss_fn(mesh, "data")
    l_sp = jax.jit(sp_loss)(params, jnp.asarray(toks), jnp.asarray(labels))
    l_ref = jax.jit(model.apply)(params, jnp.asarray(toks), jnp.asarray(labels))
    np.testing.assert_allclose(float(l_sp), float(l_ref), rtol=2e-5)

    g_sp = jax.jit(jax.grad(sp_loss))(params, jnp.asarray(toks), jnp.asarray(labels))
    g_ref = jax.jit(jax.grad(model.apply))(params, jnp.asarray(toks), jnp.asarray(labels))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-3, atol=1e-5),
        g_sp, g_ref)


def test_gpt2_sequence_parallel_trains_through_engine(mesh):
    """The packaged model_fn drives DeepSpeedEngine end to end (seq sharded over
    the data axis; params replicated; loss decreases)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32)
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    model_fn = model.sequence_parallel_loss_fn(mesh, "data")
    engine = DeepSpeedEngine(
        model=model_fn, model_parameters=params, mesh=mesh,
        config_params={"train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
                       "gradient_accumulation_steps": 1, "steps_per_print": 100,
                       "optimizer": {"type": "Adam", "params": {"lr": 3e-3}}})
    rng = np.random.default_rng(2)
    losses = []
    toks = rng.integers(0, 64, size=(2, 64)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    # the 'data' axis carries the SEQUENCE here: pre-shard inputs on dim 1 (the
    # engine's shard_batch default of dim-0-over-data doesn't apply)
    spec = NamedSharding(mesh, P(None, "data"))
    toks_d = jax.device_put(jnp.asarray(toks), spec)
    labels_d = jax.device_put(jnp.asarray(labels), spec)
    for _ in range(30):
        loss = engine(toks_d, labels_d)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])


@pytest.mark.parametrize("causal", [False, True])
def test_ring_dropout_matches_global_oracle(mesh, causal):
    """Attention dropout under the ring: every rank hashes GLOBAL coordinates, so
    the 8-shard ring must equal dense attention with the whole-sequence oracle
    mask — fwd and grads."""
    from deepspeed_tpu.ops.pallas.flash_attention import dropout_keep_reference
    rate, seed = 0.2, 1234
    q, k, v = qkv(5)
    keep = dropout_keep_reference(seed, B, H, T, T, rate)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=causal,
                                              interpret=True, dropout_rate=rate,
                                              dropout_seed=seed) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal,
                                       dropout_keep=keep) ** 2)

    np.testing.assert_allclose(float(jax.jit(loss_ring)(q, k, v)),
                               float(loss_dense(q, k, v)), rtol=2e-5)
    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name} (causal={causal})")


def test_gpt2_sequence_parallel_dropout_trains(mesh):
    """Dropout under sequence parallelism (round 4): the ring threads a shared seed
    (global-coordinate attention masks) and hidden dropout folds the rank into its
    key. Same rng -> identical loss; different rng -> different loss; grads finite;
    no-rng path stays the deterministic one."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32, dropout=0.2)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(0, 64, size=(2, 64)).astype(np.int32))
    labels = jnp.roll(toks, -1, axis=1)
    loss_fn = model.sequence_parallel_loss_fn(mesh, "data")

    l1 = float(jax.jit(loss_fn)(params, toks, labels, jax.random.PRNGKey(5)))
    l1b = float(jax.jit(loss_fn)(params, toks, labels, jax.random.PRNGKey(5)))
    l2 = float(jax.jit(loss_fn)(params, toks, labels, jax.random.PRNGKey(6)))
    assert l1 == l1b, "same rng must reproduce the same masks"
    assert l1 != l2, "different rng must sample different masks"
    l_det = float(jax.jit(loss_fn)(params, toks, labels))
    ref = float(jax.jit(model.apply)(params, toks, labels))
    np.testing.assert_allclose(l_det, ref, rtol=2e-5)

    g = jax.jit(jax.grad(lambda p: loss_fn(p, toks, labels, jax.random.PRNGKey(7))))(params)
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree_util.tree_leaves(g))
