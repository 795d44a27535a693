"""Activation checkpointing tests (reference had no dedicated unit tests for
checkpointing.py — its coverage came from Megatron model tests; here we test grad
parity, offload policy, partitioned saveables, and the RNG parity API directly)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.parallel.mesh import MODEL_AXIS, build_mesh
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ckpt


@pytest.fixture(autouse=True)
def _reset():
    ckpt.reset()
    yield
    ckpt.reset()


def _block(x, w):
    return jnp.tanh(x @ w) @ w.T


def _loss(fn, x, w):
    return jnp.sum(fn(x, w) ** 2)


def _grads(fn, x, w):
    return jax.jit(jax.grad(lambda xx, ww: _loss(fn, xx, ww), argnums=(0, 1)))(x, w)


@pytest.fixture
def xw():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return jax.random.normal(k1, (8, 16)), jax.random.normal(k2, (16, 16)) * 0.1


def test_checkpoint_grad_parity(xw):
    x, w = xw
    ckpt.configure()
    ref = _grads(_block, x, w)
    got = _grads(ckpt.checkpoint_wrapper(_block), x, w)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g), rtol=1e-6)


def test_checkpoint_call_style(xw):
    """reference call style: checkpoint(function, *args) (checkpointing.py:739)."""
    x, w = xw
    out = ckpt.checkpoint(_block, x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_block(x, w)), rtol=1e-6)


def test_cpu_checkpointing_grad_parity(xw):
    x, w = xw
    ckpt.configure(checkpoint_in_cpu=True)
    assert ckpt.is_configured()
    ref = _grads(_block, x, w)
    got = _grads(ckpt.checkpoint_wrapper(_block), x, w)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g), rtol=1e-6)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs multi-device mesh")
def test_partition_activations_grad_parity(xw):
    x, w = xw
    mesh = build_mesh(data=2, model=4, pipe=1) if len(jax.devices()) == 8 else \
        build_mesh(data=1, model=len(jax.devices()), pipe=1)
    ckpt.configure(partition_activations=True, mesh=mesh)
    ref = _grads(_block, x, w)
    with jax.set_mesh(mesh):
        got = _grads(ckpt.checkpoint_wrapper(_block), x, w)
    for r, g in zip(ref, got):
        # sharded matmul reduction order shifts the last few ulps
        np.testing.assert_allclose(np.asarray(r), np.asarray(g), rtol=1e-4, atol=1e-6)


def test_configure_from_deepspeed_config():
    cfg = deepspeed_tpu.DeepSpeedConfig(
        {"train_batch_size": 8,
         "activation_checkpointing": {"partition_activations": True,
                                      "cpu_checkpointing": True,
                                      "number_checkpoints": 4,
                                      "profile": True}},
        world_size=1)
    ckpt.configure(deepspeed_config=cfg)
    assert ckpt._config["partition_activations"] is True
    assert ckpt._config["cpu_checkpointing"] is True
    assert ckpt._config["number_checkpoints"] == 4
    assert ckpt._config["profile"] is True


def test_profile_mode_runs(xw):
    x, w = xw
    ckpt.configure(profile=True)
    got = _grads(ckpt.checkpoint_wrapper(_block), x, w)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in got)


def test_rng_tracker_streams():
    tracker = ckpt.get_rng_tracker()
    tracker.reset()
    tracker.add("model-parallel-rng", 42)
    a = tracker.fork()
    b = tracker.fork()
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        tracker.add("model-parallel-rng", 1)
    with pytest.raises(KeyError):
        tracker.fork("nope")
    # replay determinism: same seed → same stream
    tracker.reset()
    tracker.add("model-parallel-rng", 42)
    a2 = tracker.fork()
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a2))


def test_model_parallel_manual_seed_parity_api():
    ckpt.model_parallel_cuda_manual_seed(1234)
    t = ckpt.get_cuda_rng_tracker()
    assert "model-parallel-rng" in t.get_states() and "data-parallel-rng" in t.get_states()


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs multi-device mesh")
def test_model_parallel_seed_differs_per_rank():
    from jax.sharding import PartitionSpec as P
    mesh = build_mesh(data=1, model=len(jax.devices()), pipe=1)

    def f():
        key = ckpt.model_parallel_seed(7, axis=MODEL_AXIS)
        return jax.random.uniform(key, (1,))

    with jax.set_mesh(mesh):
        out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(), out_specs=P(MODEL_AXIS),
                                    check_vma=False))()
    vals = np.asarray(out)
    assert len(np.unique(vals)) == len(vals), "per-rank dropout keys must differ"


def test_gpt2_remat_uses_config(xw):
    """GPT-2 remat path goes through checkpoint_wrapper and trains identically."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=4, remat=True)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 16)))
    loss_remat = jax.jit(lambda p: model.apply(p, tok[:, :-1], tok[:, 1:]))(params)

    cfg2 = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=4, remat=False)
    loss_plain = jax.jit(lambda p: GPT2Model(cfg2).apply(p, tok[:, :-1], tok[:, 1:]))(params)
    np.testing.assert_allclose(float(loss_remat), float(loss_plain), rtol=1e-5)


def test_engine_composes_with_cpu_checkpointing():
    """regression: offload-remat custom-calls must not collide with the engine's
    out_shardings (XLA SPMD 'side-effect ops cannot be replicated')."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=4, remat=True)
    model = GPT2Model(cfg)
    ds_cfg = {"train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 2},
              "activation_checkpointing": {"cpu_checkpointing": True,
                                           "partition_activations": True}}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)), config_params=ds_cfg)
    tok = jnp.asarray(np.random.default_rng(0).integers(0, 64, (8, 17)))
    losses = []
    for _ in range(4):
        loss = engine.forward(tok[:, :-1], tok[:, 1:])
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    assert losses[-1] < losses[0]
