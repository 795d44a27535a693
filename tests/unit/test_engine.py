"""End-to-end engine tests on the 8-device virtual CPU mesh (reference test_fp16.py style)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from simple_model import SimpleModel, random_dataset, simple_config

HIDDEN = 16


def run_training(config, steps=10, hidden=HIDDEN, seed=0):
    model = SimpleModel(hidden)
    params = model.init(jax.random.PRNGKey(seed))
    data = random_dataset(256, hidden, seed=seed)
    engine, optimizer, loader, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, training_data=data, config_params=config)
    losses = []
    it = iter(loader)
    for _ in range(steps * engine.gradient_accumulation_steps()):
        x, y = next(it)
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    return engine, losses


@pytest.mark.parametrize("zero_stage", [0, 1, 2])
def test_zero_stage_training_loss_decreases(zero_stage):
    cfg = simple_config(zero_optimization={"stage": zero_stage})
    engine, losses = run_training(cfg, steps=20)
    assert losses[-1] < losses[0] * 0.9, f"loss did not decrease: {losses[0]} -> {losses[-1]}"
    assert engine.global_steps == 20


def test_zero_stages_agree():
    """Stages 0/1/2 are different layouts of the same math: losses must match closely."""
    results = {}
    for stage in [0, 1, 2]:
        cfg = simple_config(zero_optimization={"stage": stage})
        _, losses = run_training(cfg, steps=5, seed=3)
        results[stage] = losses
    for stage in [1, 2]:
        np.testing.assert_allclose(results[0], results[stage], rtol=2e-2)


def test_gradient_accumulation():
    cfg = simple_config(batch=16, gradient_accumulation_steps=2)
    engine, losses = run_training(cfg, steps=5)
    assert engine.gradient_accumulation_steps() == 2
    assert engine.global_steps == 5
    assert engine.micro_steps == 10


def test_grad_accum_equivalence():
    """grad_acc=2 at micro-batch 8 must match grad_acc=1 at batch 16 (same total batch)."""
    model = SimpleModel(HIDDEN)
    params = model.init(jax.random.PRNGKey(0))
    data = random_dataset(64, HIDDEN, seed=1)

    def run(cfg):
        p = jax.tree_util.tree_map(jnp.array, params)
        engine, _, loader, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=p, training_data=data, config_params=cfg)
        xs = np.stack([data[i][0] for i in range(16)])
        ys = np.stack([data[i][1] for i in range(16)])
        if engine.gradient_accumulation_steps() == 1:
            loss = engine(xs, ys)
            engine.backward(loss)
            engine.step()
        else:
            for half in range(2):
                x = xs[half * 8:(half + 1) * 8]
                y = ys[half * 8:(half + 1) * 8]
                loss = engine(x, y)
                engine.backward(loss)
                engine.step()
        return jax.device_get(engine.master_params)

    p_full = run(simple_config(batch=16))
    p_acc = run(simple_config(batch=16, gradient_accumulation_steps=2))
    jax.tree_util.tree_map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
                           p_full, p_acc)


def test_fp16_dynamic_loss_scale_init():
    cfg = simple_config(fp16={"enabled": True, "initial_scale_power": 8})
    engine, losses = run_training(cfg, steps=25)
    assert engine.fp16_enabled()
    assert engine.dynamic_loss_scale()
    assert losses[-1] < losses[0]


def test_fp16_static_loss_scale():
    cfg = simple_config(fp16={"enabled": True, "loss_scale": 128.0})
    engine, losses = run_training(cfg, steps=25)
    assert engine.loss_scale() == 128.0
    assert losses[-1] < losses[0]


def test_lamb_optimizer():
    """LAMB's trust ratio shrinks small-model updates; like the reference's lamb tests we
    check stable execution + that parameters actually move, not convergence speed."""
    cfg = simple_config(optimizer={"type": "Lamb", "params": {"lr": 2e-3}})
    model = SimpleModel(HIDDEN)
    params = model.init(jax.random.PRNGKey(0))
    data = random_dataset(256, HIDDEN)
    engine, _, loader, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                                    training_data=data, config_params=cfg)
    before = jax.device_get(engine.master_params)
    it = iter(loader)
    losses = []
    for _ in range(10):
        x, y = next(it)
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    after = jax.device_get(engine.master_params)
    assert all(np.isfinite(l) for l in losses)
    assert engine.optimizer.name == "lamb"
    moved = any(not np.allclose(a, b) for a, b in zip(jax.tree_util.tree_leaves(before),
                                                      jax.tree_util.tree_leaves(after)))
    assert moved, "LAMB step did not change parameters"


def test_scheduler_integration():
    cfg = simple_config(scheduler={"type": "WarmupLR",
                                   "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 0.01,
                                              "warmup_num_steps": 10}})
    engine, _ = run_training(cfg, steps=5)
    lr_now = engine.get_lr()[0]
    assert 0 < lr_now <= 0.01


def test_gradient_clipping_runs():
    cfg = simple_config(gradient_clipping=0.1)
    engine, losses = run_training(cfg, steps=5)
    assert losses[-1] <= losses[0] * 1.5  # just needs to run stably


def test_eval_mode_no_grads():
    model = SimpleModel(HIDDEN)
    params = model.init(jax.random.PRNGKey(0))
    cfg = simple_config()
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                               training_data=random_dataset(16, HIDDEN),
                                               config_params=cfg)
    engine.eval()
    x, y = np.zeros((8, HIDDEN), np.float32), np.zeros((8, HIDDEN), np.float32)
    loss = engine(x, y)
    assert np.isfinite(float(jax.device_get(loss)))
    with pytest.raises(AssertionError):
        engine.backward(loss)


def test_zero_sharded_state_layout(eight_devices):
    """Stage >=1 must actually shard the optimizer state over the data axis."""
    hidden = 64  # 64x64 weights are above the min-shard size and divisible by dp=8
    cfg = simple_config(zero_optimization={"stage": 2})
    model = SimpleModel(hidden)
    params = model.init(jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                               training_data=random_dataset(16, hidden),
                                               config_params=cfg)
    sharded = engine.master_params["w1"].sharding
    assert not sharded.is_fully_replicated, "ZeRO>=1 master weights should be dp-sharded"
    opt_sharded = engine.opt_state.exp_avg["w1"].sharding
    assert not opt_sharded.is_fully_replicated, "ZeRO>=1 optimizer state should be dp-sharded"


def test_zero_sharded_fraction_reported(eight_devices):
    """The engine must account what fraction of master/optimizer bytes
    actually sharded, and flagship-shaped configs must exceed 90% (GPT-2-like dims
    divisible by dp; a user should never silently run 'ZeRO-2' mostly replicated)."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    import jax.numpy as jnp

    cfg = GPT2Config(vocab_size=512, n_layer=2, n_head=4, n_embd=128, n_positions=128,
                     compute_dtype=jnp.float32)
    model = GPT2Model(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=simple_config(zero_optimization={"stage": 2}))
    assert engine._zero_sharded_fraction is not None
    assert engine._zero_sharded_fraction > 0.9, engine._zero_sharded_fraction

    # tiny awkward shapes (all leaves under min_size): fraction reported, clearly low
    small = SimpleModel(8)
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=small, model_parameters=small.init(jax.random.PRNGKey(1)),
        config_params=simple_config(zero_optimization={"stage": 2}))
    assert engine2._zero_sharded_fraction is not None
    assert engine2._zero_sharded_fraction < 0.5


def test_eval_forward_is_jitted_and_compiles_once():
    """eval() forwards must go through one cached jit: op-by-op
    dispatch of a large model would make eval pathologically slow."""
    model = SimpleModel(HIDDEN)
    params = model.init(jax.random.PRNGKey(0))
    traces = []

    def model_fn(p, x, y):
        traces.append(1)
        return model.apply(p, x, y)

    engine, _, _, _ = deepspeed_tpu.initialize(model=model_fn, model_parameters=params,
                                               config_params=simple_config())
    engine.eval()
    x = np.random.default_rng(0).normal(size=(8, HIDDEN)).astype(np.float32)
    y = np.zeros((8, HIDDEN), np.float32)
    l1 = float(jax.device_get(engine(x, y)))
    l2 = float(jax.device_get(engine(x, y)))
    assert len(traces) == 1, f"eval forward retraced: {len(traces)} traces for 2 calls"
    assert abs(l1 - l2) < 1e-12
    # numerics match the un-jitted model
    ref = float(model.apply(params, jnp.asarray(x), jnp.asarray(y)))
    assert abs(l1 - ref) < 1e-5


def _client_pair():
    """A client (init, apply) pair on the engine's contract: apply(grads, state, master,
    step, hyper) -> (new master, new state). State that does not mirror the param tree."""
    import jax.numpy as jnp

    def init(master):
        n = sum(l.size for l in jax.tree_util.tree_leaves(master))
        return {"shard": jnp.zeros((n // 4,), jnp.float32)}

    def apply(grads, state, master, step, hyper):
        g = jnp.concatenate([x.reshape(-1) for x in jax.tree_util.tree_leaves(grads)])
        new_master = jax.tree_util.tree_map(lambda m, x: m - hyper["lr"] * x, master, grads)
        return new_master, {"shard": state["shard"] - hyper["lr"] * g[: state["shard"].size]}

    return init, apply


def test_external_master_optimizer(tmp_path):
    """A client (init, apply) pair (the one that used to carry the external_master
    mark, now without it) trains through the standard two-program step: the engine
    holds the fp32 master the pair updates, re-derives the compute params from it,
    and round-trips the pair's own state through a checkpoint."""
    model = SimpleModel(HIDDEN)
    params = model.init(jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, optimizer=_client_pair(),
        config_params=simple_config(zero_optimization={"stage": 2},
                                    zero_allow_untested_optimizer=True))
    def params_are_the_master_recast():
        jax.tree_util.tree_map(
            lambda m, p: np.testing.assert_array_equal(np.asarray(m.astype(p.dtype)),
                                                       np.asarray(p)),
            engine.master_params, engine.params)

    params_are_the_master_recast()
    before_master = jax.device_get(engine.master_params)
    shard0 = np.asarray(jax.device_get(engine.opt_state["shard"]))

    x = np.random.default_rng(0).normal(size=(8, HIDDEN)).astype(np.float32)
    for _ in range(2):
        loss = engine(x, np.tanh(x))
        engine.backward(loss)
        engine.step()
    assert engine.global_steps == 2
    # the pair's state moved, and so did the master it was handed; the compute
    # params are the updated master, re-cast
    assert np.abs(np.asarray(jax.device_get(engine.opt_state["shard"])) - shard0).max() > 0
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        jax.device_get(engine.master_params), before_master)
    assert min(jax.tree_util.tree_leaves(moved)) > 0
    params_are_the_master_recast()

    # checkpoint roundtrip: the pair's shard and the engine's master both survive
    shard_now = np.asarray(jax.device_get(engine.opt_state["shard"]))
    master_now = jax.device_get(engine.master_params)
    engine.save_checkpoint(str(tmp_path))
    for _ in range(2):
        engine.backward(engine(x, np.tanh(x)))
        engine.step()
    engine.load_checkpoint(str(tmp_path))
    np.testing.assert_allclose(np.asarray(jax.device_get(engine.opt_state["shard"])),
                               shard_now, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6),
        jax.device_get(engine.master_params), master_now)


def test_external_master_mark_is_refused():
    """An apply marked external_master asked for a step path that is gone: the engine
    says so at construction instead of handing it a master it never asked for."""
    init, apply = _client_pair()
    apply.external_master = True
    model = SimpleModel(HIDDEN)
    with pytest.raises(ValueError, match="external-master step path was removed"):
        deepspeed_tpu.initialize(
            model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
            optimizer=(init, apply), config_params=simple_config())


def test_fused_step_config_matches_two_jit_path():
    """{"fused_step": true}: the standard engine's single-jit step must produce the
    SAME losses and master weights as the two-jit step — including fp16 overflow
    skip behavior — and enforce the rotation contract."""
    model = SimpleModel(HIDDEN)
    data = random_dataset(64, HIDDEN, seed=5)
    results = {}
    for fused in (False, True):
        params = model.init(jax.random.PRNGKey(2))
        cfg = simple_config(fused_step=fused)
        engine, _, loader, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, training_data=data,
            config_params=cfg)
        assert (engine._run_fused_step is not None) == fused
        it = iter(loader)
        losses = []
        for _ in range(6):
            x, y = next(it)
            loss = engine(x, y)
            engine.backward(loss)
            engine.step()
            losses.append(float(jax.device_get(loss)))
        results[fused] = (losses, jax.device_get(engine.master_params))
    # the last engine is the fused one: a second forward before step() fails loudly
    engine(x, y)
    with pytest.raises(RuntimeError, match="rotation"):
        engine(x, y)
    np.testing.assert_allclose(results[True][0], results[False][0], rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
        results[True][1], results[False][1])


def test_fused_step_fp16_overflow_parity():
    """Overflow under the fused step must skip the master update, halve the scale,
    and count a skipped step — exactly like the two-jit path."""
    model = SimpleModel(HIDDEN)
    params = model.init(jax.random.PRNGKey(0))
    cfg = simple_config(fused_step=True,
                        fp16={"enabled": True, "loss_scale": 0,
                              "initial_scale_power": 4, "hysteresis": 1})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                               config_params=cfg)
    assert engine._run_fused_step is not None
    s0 = float(engine.loss_scale())
    before = jax.device_get(engine.master_params)
    x = np.ones((8, HIDDEN), np.float32)
    y = np.full((8, HIDDEN), 1e30, np.float32)  # cotangents overflow fp16
    loss = engine(x, y)
    engine.backward(loss)
    engine.step()
    assert engine.skipped_steps == 1
    assert float(engine.loss_scale()) == s0 / 2
    jax.tree_util.tree_map(lambda a, b: np.testing.assert_array_equal(a, b),
                           jax.device_get(engine.master_params), before)
