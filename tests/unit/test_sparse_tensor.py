"""Row-sparse gradient tests (reference had no csr_tensor unit tests; the engine CSR
allreduce at engine.py:1091-1147 is covered here by numeric parity vs dense psum)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.parallel.mesh import DATA_AXIS, build_mesh
from deepspeed_tpu.runtime.sparse_tensor import (SparseTensor, match_sparse_paths,
                                                 row_sparse_allreduce)


def _row_sparse(rows=32, cols=8, nnz=5, seed=0):
    rng = np.random.default_rng(seed)
    dense = np.zeros((rows, cols), np.float32)
    idx = rng.choice(rows, nnz, replace=False)
    dense[idx] = rng.normal(size=(nnz, cols)).astype(np.float32)
    return jnp.asarray(dense)


def test_from_dense_to_dense_roundtrip():
    dense = _row_sparse()
    st = SparseTensor.from_dense(dense, capacity=8)
    np.testing.assert_allclose(np.asarray(st.to_dense()), np.asarray(dense))


def test_from_dense_exact_capacity():
    dense = _row_sparse(nnz=6)
    st = SparseTensor.from_dense(dense, capacity=6)
    np.testing.assert_allclose(np.asarray(st.to_dense()), np.asarray(dense))


def test_from_dense_full_capacity_default():
    dense = _row_sparse()
    st = SparseTensor.from_dense(dense)
    np.testing.assert_allclose(np.asarray(st.to_dense()), np.asarray(dense))


def test_row_zero_nonzero_kept():
    """row 0 nonzero + fill_value=0 slots must not double-count row 0."""
    dense = jnp.zeros((8, 4)).at[0].set(1.0).at[3].set(2.0)
    st = SparseTensor.from_dense(dense, capacity=6)
    np.testing.assert_allclose(np.asarray(st.to_dense()), np.asarray(dense))


def test_add_concatenates_and_accumulates():
    a = SparseTensor.from_dense(_row_sparse(seed=1), capacity=8)
    b = SparseTensor.from_dense(_row_sparse(seed=2), capacity=8)
    merged = a.add(b)
    expected = np.asarray(a.to_dense()) + np.asarray(b.to_dense())
    np.testing.assert_allclose(np.asarray(merged.to_dense()), expected)


def test_sparse_size():
    st = SparseTensor.from_dense(_row_sparse(rows=64, cols=16), capacity=4)
    sparse, dense = st.sparse_size()
    assert sparse == 4 + 4 * 16
    assert dense == 64 * 16


def test_jit_friendly():
    """from_dense/to_dense must trace with static shapes."""
    f = jax.jit(lambda d: SparseTensor.from_dense(d, capacity=8).to_dense())
    dense = _row_sparse()
    np.testing.assert_allclose(np.asarray(f(dense)), np.asarray(dense))


def test_match_sparse_paths():
    assert match_sparse_paths("embeddings/word", ("embeddings/word",))
    assert not match_sparse_paths("h/0/attn/w", ("embeddings",))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs multi-device mesh")
def test_row_sparse_allreduce_matches_pmean():
    mesh = build_mesh(model=1, pipe=1)
    world = mesh.shape[DATA_AXIS]
    rows, cols, k = 64, 8, 6
    per_shard = [np.asarray(_row_sparse(rows, cols, nnz=k, seed=s)) for s in range(world)]
    stacked = jnp.asarray(np.stack(per_shard))  # [world, rows, cols]

    def local(x):
        return row_sparse_allreduce(x[0], DATA_AXIS, capacity=k)

    with jax.set_mesh(mesh):
        out = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(DATA_AXIS),
                                    out_specs=P(), check_vma=False))(stacked)
    expected = np.mean(np.stack(per_shard), axis=0)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6)


class _UntiedEmbedModel:
    """Tiny classifier with an UNTIED embedding table: its grad is row-sparse
    (the tied GPT-2/BERT tables get dense LM-head grads, so they don't qualify)."""

    def __init__(self, vocab=64, width=16):
        self.vocab, self.width = vocab, width

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"embed": {"table": jax.random.normal(k1, (self.vocab, self.width)) * 0.02},
                "head": {"w": jax.random.normal(k2, (self.width, 4)) * 0.02}}

    def apply(self, params, tokens, labels):
        x = params["embed"]["table"][tokens].mean(axis=1)  # [B, width]
        logits = x @ params["head"]["w"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))

    def sparse_grad_paths(self):
        return ("embed/table",)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs multi-device mesh")
@pytest.mark.parametrize("zero_stage", [0, 2])
def test_engine_sparse_gradients_parity(zero_stage):
    """Training with sparse_gradients=true must match dense reduction step-for-step."""
    model = _UntiedEmbedModel()
    rng = np.random.default_rng(0)
    batch = (jnp.asarray(rng.integers(0, 64, (8, 12))), jnp.asarray(rng.integers(0, 4, (8,))))

    results = {}
    for sparse in (False, True):
        cfg = {"train_batch_size": 8, "train_micro_batch_size_per_gpu": 8 // len(jax.devices()),
               "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
               "sparse_gradients": sparse,
               "zero_optimization": {"stage": zero_stage}}
        # the engine takes ownership of (and may donate) the param buffers → fresh init
        params = model.init(jax.random.PRNGKey(0))
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                                   config_params=cfg)
        if sparse:
            assert engine._sparse_grad_flags is not None
            assert sum(jax.tree_util.tree_leaves(engine._sparse_grad_flags)) == 1
        for _ in range(3):
            loss = engine.forward(*batch)
            engine.backward(loss)
            engine.step()
        results[sparse] = jax.device_get(engine.master_params)

    # dense path differentiates over the global batch, sparse path over local shards
    # + pmean — same math, different fp32 reduction order, so allow ~1e-4 drift.
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4),
        results[False], results[True])


class _LabelsFirstModel(_UntiedEmbedModel):
    """Token ids are the SECOND positional input: without the sparse_grad_tokens()
    hint the engine would size the sparse row capacity from the labels tensor."""

    def __init__(self):
        super().__init__(vocab=512)  # big table so the sparse gather path is taken

    def apply(self, params, labels, tokens):
        return super().apply(params, tokens, labels)

    def sparse_grad_tokens(self, labels, tokens):
        return int(np.prod(tokens.shape))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs multi-device mesh")
def test_engine_sparse_gradients_tokens_hint():
    """Capacity comes from the model's sparse_grad_tokens() hint, not batch arg 0."""
    model = _LabelsFirstModel()
    rng = np.random.default_rng(0)
    # labels-first batch: arg 0 has 8 elements, the token tensor has 8*12
    batch = (jnp.asarray(rng.integers(0, 4, (8,))), jnp.asarray(rng.integers(0, 512, (8, 12))))

    results = {}
    for sparse in (False, True):
        cfg = {"train_batch_size": 8, "train_micro_batch_size_per_gpu": 8 // len(jax.devices()),
               "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
               "sparse_gradients": sparse,
               "zero_optimization": {"stage": 0}}
        params = model.init(jax.random.PRNGKey(0))
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                                   config_params=cfg)
        for _ in range(3):
            loss = engine.forward(*batch)
            engine.backward(loss)
            engine.step()
        if sparse:  # the hint sizes capacity below the table height -> sparse gather
            assert engine._sparse_tokens_fn is not None
        results[sparse] = jax.device_get(engine.master_params)

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-3, atol=5e-4),
        results[False], results[True])
