"""First-class tensor parallelism (SURVEY §2.3: the reference delegated TP to Megatron's
external mpu; here Megatron-style layouts are built in).

Covers both TP flavors on the 8-device virtual CPU platform:
- GSPMD: GPT2Model.param_shardings over a data×model mesh through the full engine —
  losses must match the model=1 run bit-for-bit-ish (same math, different partitioning).
- Manual (shard_map): GPT2Pipe(tp=2) on a pipe×data×model 3D mesh — the Megatron
  psum forward with rank-grouped qkv shards must match the dense model's loss.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model, qkv_tp_permutation
from deepspeed_tpu.models.gpt2_pipe import GPT2Pipe
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.runtime.engine import DeepSpeedEngine

CFG = dict(vocab_size=96, n_positions=32, n_embd=32, n_layer=4, n_head=4,
           compute_dtype=jnp.float32)


def _data(batch=8, seq=16, vocab=96, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    labels = np.roll(toks, -1, 1)
    return toks, labels


def _run_engine(mesh, param_shardings, steps=3):
    model = GPT2Model(GPT2Config(**CFG))
    params = model.init(jax.random.PRNGKey(7))
    engine = DeepSpeedEngine(
        model=model, model_parameters=params, mesh=mesh, param_shardings=param_shardings,
        config_params={"train_batch_size": 8, "steps_per_print": 100,
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                       "zero_optimization": {"stage": 2}})
    toks, labels = _data()
    losses = []
    for _ in range(steps):
        loss = engine(toks, labels)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    return losses


def test_gspmd_tp_matches_replicated(eight_devices):
    base = _run_engine(build_mesh(data=8, model=1, pipe=1), None)

    mesh = build_mesh(data=4, model=2, pipe=1)
    model = GPT2Model(GPT2Config(**CFG))
    tp = _run_engine(mesh, model.param_shardings(mesh))

    assert tp == pytest.approx(base, rel=2e-5, abs=2e-5), f"base={base} tp={tp}"


def test_gspmd_tp_weights_actually_sharded(eight_devices):
    mesh = build_mesh(data=4, model=2, pipe=1)
    model = GPT2Model(GPT2Config(**CFG))
    params = model.init(jax.random.PRNGKey(0))
    sh = model.param_shardings(mesh)
    placed = jax.device_put(params, sh)
    w = placed["blocks"][0]["attn"]["c_attn_w"]
    # column-parallel: each model rank holds half the output columns
    shard_shapes = {s.data.shape for s in w.addressable_shards}
    assert shard_shapes == {(32, 3 * 32 // 2)}, shard_shapes


def test_qkv_tp_permutation_is_rank_grouped_qkv():
    H, tp = 8, 2
    perm = qkv_tp_permutation(H, tp)
    assert sorted(perm.tolist()) == list(range(3 * H))
    # rank 0's contiguous shard = [q_0, k_0, v_0]
    r0 = perm[:3 * H // tp]
    np.testing.assert_array_equal(r0[:4], np.arange(0, 4))          # q first half
    np.testing.assert_array_equal(r0[4:8], np.arange(H, H + 4))     # k first half
    np.testing.assert_array_equal(r0[8:12], np.arange(2 * H, 2 * H + 4))  # v first half


def test_pipe_3d_tp_loss_matches_dense(eight_devices):
    """pipe=2 × data=2 × model=2: the full 3D path vs the plain dense model."""
    mesh = build_mesh(pipe=2, data=2, model=2)
    cfg = GPT2Config(**CFG)
    dense = GPT2Model(cfg)
    dense_params = dense.init(jax.random.PRNGKey(3))

    pipe = GPT2Pipe(cfg, num_stages=2, tp=2)
    pipe_params = pipe.from_dense(jax.tree_util.tree_map(lambda x: x, dense_params))
    shardings = pipe.param_shardings(mesh, pipe_params)
    pipe_params = jax.device_put(pipe_params, shardings)

    M = 2
    toks, labels = _data(batch=2 * M * 2, seq=16)
    toks_mb = jnp.asarray(toks).reshape(M, 4, 16)
    labels_mb = jnp.asarray(labels).reshape(M, 4, 16)

    got = float(jax.jit(lambda p, t, l: pipe.loss(p, t, l, mesh=mesh))(
        pipe_params, toks_mb, labels_mb))

    want = float(np.mean([float(dense.apply(dense_params, np.asarray(toks_mb[m]),
                                            np.asarray(labels_mb[m]))) for m in range(M)]))
    assert got == pytest.approx(want, rel=2e-5, abs=2e-5), f"pipe3d={got} dense={want}"


def test_pipe_3d_weights_sharded_over_pipe_and_model(eight_devices):
    mesh = build_mesh(pipe=2, data=2, model=2)
    cfg = GPT2Config(**CFG)
    pipe = GPT2Pipe(cfg, num_stages=2, tp=2)
    params = pipe.init(jax.random.PRNGKey(0))
    placed = jax.device_put(params, pipe.param_shardings(mesh, params))
    w = placed["stages"]["attn"]["c_attn_w"]          # [S, L/S, H, 3H]
    shard_shapes = {s.data.shape for s in w.addressable_shards}
    assert shard_shapes == {(1, 2, 32, 3 * 32 // 2)}, shard_shapes
