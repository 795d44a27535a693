"""Pipeline engine end-to-end tests: LinearStack pipe vs sequential parity, tied weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.parallel.pipe import LayerSpec, TiedLayerSpec, PipelineModule
from deepspeed_tpu.runtime.pipe.engine import PipelineEngine, PipelineError

HIDDEN = 8


class Linear:
    """Minimal pure-function layer module: init(rng, x) -> params; apply(params, x)."""

    def __init__(self, dim, activation=True):
        self.dim = dim
        self.activation = activation

    def init(self, rng, x):
        k1, _ = jax.random.split(rng)
        return {"w": jax.random.normal(k1, (x.shape[-1], self.dim), jnp.float32) * 0.3,
                "b": jnp.zeros((self.dim,), jnp.float32)}

    def apply(self, params, x):
        y = x @ params["w"].astype(x.dtype) + params["b"].astype(x.dtype)
        return jnp.tanh(y) if self.activation else y

    def param_shapes(self):
        return [(HIDDEN, self.dim), (self.dim,)]


def mse_loss(out, target):
    return jnp.mean(jnp.square(out.astype(jnp.float32) - target.astype(jnp.float32)))


def make_pipe(num_layers=4, num_stages=2, seed=0, tied=False):
    if tied:
        layers = [TiedLayerSpec("emb", Linear, HIDDEN)] + \
                 [LayerSpec(Linear, HIDDEN) for _ in range(num_layers - 2)] + \
                 [TiedLayerSpec("emb", Linear, HIDDEN)]
    else:
        layers = [LayerSpec(Linear, HIDDEN) for _ in range(num_layers)]
    module = PipelineModule(layers=layers, num_stages=num_stages, loss_fn=mse_loss)
    sample = jnp.zeros((4, HIDDEN), jnp.float32)
    params = module.init_params(jax.random.PRNGKey(seed), sample)
    return module, params


def pipe_config(batch=32, micro=2):
    # dp world is 8 virtual devices: batch 32 / (micro-batches 2 * dp 8) = micro size 2
    return {
        "train_batch_size": batch,
        "gradient_accumulation_steps": micro,
        "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    }


def data_iter(hidden=HIDDEN, batch=4, seed=0):
    rng = np.random.default_rng(seed)
    w_true = np.random.default_rng(77).normal(size=(hidden, hidden)).astype(np.float32) * 0.4
    while True:
        x = rng.normal(size=(batch, hidden)).astype(np.float32)
        yield x, np.tanh(x @ w_true)


@pytest.mark.parametrize("num_stages", [1, 2, 4])
def test_pipe_training_loss_decreases(num_stages):
    module, params = make_pipe(num_layers=4, num_stages=num_stages)
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                               config_params=pipe_config())
    assert isinstance(engine, PipelineEngine)
    it = data_iter(batch=16)
    losses = [float(jax.device_get(engine.train_batch(it))) for _ in range(20)]
    assert losses[-1] < losses[0] * 0.8, f"{losses[0]} -> {losses[-1]}"


def test_pipe_matches_sequential():
    """The same layers trained with 2 pipeline stages (SPMD executor) vs 1 stage give
    identical weights at fp32 — compared in the canonical layer-keyed representation.
    (fp32 pinned: cross-executor comparisons at bf16 drift through Adam's sqrt(v)
    normalization within a few steps.)"""
    results = []
    for stages in [1, 2]:
        module, params = make_pipe(num_layers=4, num_stages=stages, seed=5)
        cfg = pipe_config()
        cfg["bf16"] = {"enabled": False}
        engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                                   config_params=cfg)
        assert engine._spmd == (stages == 2), "2-stage homogeneous stack must route SPMD"
        it = data_iter(batch=16, seed=11)
        for _ in range(3):
            engine.train_batch(it)
        results.append({k: np.asarray(jax.device_get(v), np.float32)
                        for k, v in jax.tree_util.tree_flatten_with_path(
                            engine.canonical_master_params())[0]
                        for k, v in [("/".join(str(p) for p in k), v)]})
    for k in results[0]:
        np.testing.assert_allclose(results[0][k], results[1][k], rtol=1e-4, atol=1e-6,
                                   err_msg=f"mismatch in {k}")


def test_spmd_loss_matches_instruction_executor_fp32():
    """Under the SAME public API and config, the SPMD
    executor's per-step losses equal the instruction executor's at fp32."""
    losses = {}
    for mode in ["spmd", "instruction"]:
        module, params = make_pipe(num_layers=4, num_stages=2, seed=7)
        cfg = pipe_config()
        cfg["bf16"] = {"enabled": False}
        cfg["pipeline"] = {"spmd": mode == "spmd"}
        engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                                   config_params=cfg)
        assert engine._spmd == (mode == "spmd")
        it = data_iter(batch=16, seed=23)
        losses[mode] = [float(jax.device_get(engine.train_batch(it)))
                        for _ in range(4)]
    np.testing.assert_allclose(losses["spmd"], losses["instruction"], rtol=1e-6,
                               err_msg=f"{losses}")


def test_pipe_tied_weights():
    module, params = make_pipe(num_layers=4, num_stages=2, tied=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                               config_params=pipe_config())
    assert "tied::emb" in engine.master_params
    it = data_iter(batch=16)
    for _ in range(5):
        loss = engine.train_batch(it)
    assert np.isfinite(float(jax.device_get(loss)))
    # only one copy of the tied params exists
    n_tied = sum(1 for k in engine.master_params if k.startswith("tied::"))
    assert n_tied == 1


def test_pipe_blocks_base_api():
    module, params = make_pipe()
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                               config_params=pipe_config())
    with pytest.raises(PipelineError):
        engine.forward(np.zeros((4, HIDDEN)))
    with pytest.raises(PipelineError):
        engine.backward(None)
    with pytest.raises(PipelineError):
        engine.step()


def test_pipe_eval_batch():
    module, params = make_pipe()
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                               config_params=pipe_config())
    loss = engine.eval_batch(data_iter(batch=16))
    assert np.isfinite(float(jax.device_get(loss)))


def test_partition_balanced_by_parameters():
    module, _ = make_pipe(num_layers=4, num_stages=2)
    # 4 equal layers over 2 stages -> 2+2 split
    assert module.parts == [0, 2, 4]


def test_pipe_deep_schedule_many_microbatches():
    """4 stages x 8 micro-batches: stages have UNEQUAL buffer ring sizes, exercising the
    micro-batch-keyed channels (regression: receiver-local buffer ids don't align)."""
    module, params = make_pipe(num_layers=8, num_stages=4)
    cfg = {
        "train_batch_size": 64,  # 8 micro-batches x micro size 1 x dp 8
        "gradient_accumulation_steps": 8,
        "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                               config_params=cfg)
    it = data_iter(batch=8)
    losses = [float(jax.device_get(engine.train_batch(it))) for _ in range(5)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_pipe_activation_checkpoint_interval():
    """activation_checkpoint_interval remats chunks of stage layers and must be a
    pure memory/compute tradeoff — identical training results."""
    results = []
    for interval in [0, 1, 2]:
        layers = [LayerSpec(Linear, HIDDEN) for _ in range(4)]
        module = PipelineModule(layers=layers, num_stages=2, loss_fn=mse_loss,
                                activation_checkpoint_interval=interval)
        sample = jnp.zeros((4, HIDDEN), jnp.float32)
        params = module.init_params(jax.random.PRNGKey(3), sample)
        engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                                   config_params=pipe_config())
        it = data_iter(batch=16, seed=13)
        for _ in range(3):
            engine.train_batch(it)
        results.append(jax.device_get(engine.master_params))
    for other in results[1:]:
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                    rtol=1e-5, atol=1e-6),
            results[0], other)


def test_pipe_eval_batch_inference_schedule_parity():
    """eval_batch executes the InferenceSchedule stream; its aggregate loss must equal
    the sequential whole-model loss over the same micro-batches."""
    module, params = make_pipe(num_layers=4, num_stages=2)
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                               config_params=pipe_config())
    it = data_iter(batch=16, seed=21)   # distinct micro-batches so mb routing matters
    batches = [next(it) for _ in range(engine.micro_batches)]
    got = float(jax.device_get(engine.eval_batch(iter(batches))))
    want = np.mean([float(jax.device_get(
        engine._whole_model_fn(engine.params, jnp.asarray(x), jnp.asarray(y))))
        for x, y in batches])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_pipe_fp16_loss_scale_parity():
    """fp16 pipeline grads are loss-scaled in the stage backward and unscaled in the
    update: the first-step weights must match an fp32 run to fp16 resolution."""
    results = {}
    for prec in ["fp32", "fp16"]:
        module, params = make_pipe(num_layers=4, num_stages=2, seed=9)
        cfg = pipe_config()
        if prec == "fp16":
            cfg["fp16"] = {"enabled": True, "loss_scale": 1024.0}
        engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                                   config_params=cfg)
        it = data_iter(batch=16, seed=13)
        for _ in range(2):
            loss = engine.train_batch(it)
        results[prec] = (float(jax.device_get(loss)),
                         jax.device_get(engine.master_params))
    np.testing.assert_allclose(results["fp16"][0], results["fp32"][0], rtol=2e-2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=3e-2, atol=2e-3),
        results["fp16"][1], results["fp32"][1])


def test_pipe_fp16_overflow_skips_step():
    module, params = make_pipe(num_layers=4, num_stages=2)
    cfg = pipe_config()
    cfg["fp16"] = {"enabled": True, "loss_scale": 0, "initial_scale_power": 4,
                   "hysteresis": 1}
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                               config_params=cfg)
    s0 = float(engine.loss_scale())
    before = jax.device_get(engine.master_params)

    def bad_iter():
        while True:
            yield (np.ones((16, HIDDEN), np.float32),
                   np.full((16, HIDDEN), 1e30, np.float32))  # cotangents overflow fp16

    engine.train_batch(bad_iter())
    assert engine.skipped_steps == 1
    assert float(engine.loss_scale()) == s0 / 2
    jax.tree_util.tree_map(lambda a, b: np.testing.assert_array_equal(a, b),
                           jax.device_get(engine.master_params), before)


def test_pipe_wall_clock_breakdown_timers():
    module, params = make_pipe(num_layers=4, num_stages=2)
    cfg = pipe_config()
    cfg["wall_clock_breakdown"] = True
    cfg["pipeline"] = {"spmd": False}  # per-instruction timers are instruction-mode
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                               config_params=cfg)
    engine.train_batch(data_iter(batch=16))
    for name in ["batch_input", "forward_microstep", "backward_microstep",
                 "pipe_send_output", "pipe_recv_input", "pipe_send_grad",
                 "pipe_recv_grad", "step_microstep", "train_batch"]:
        assert name in engine.timers.timers, f"missing timer {name}"
        assert engine.timers.timers[name].elapsed_ > 0 or name in (
            "pipe_send_output", "pipe_recv_input", "pipe_send_grad", "pipe_recv_grad")


def test_instruction_path_buffer_bound_m_much_greater_than_s():
    """The reference's num_pipe_buffers memory contract as a tested invariant:
    with M >> S the channel dicts must never hold more
    in-flight payloads than the receiver's ring size — the engine asserts this on
    every Send, so a clean train_batch at M = 8S IS the proof."""
    S, M = 2, 16
    module, params = make_pipe(num_layers=4, num_stages=S)
    cfg = pipe_config(batch=M * 8, micro=M)  # micro size 1 x dp 8
    cfg["pipeline"] = {"spmd": False}  # the buffer-ring contract is instruction-mode
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=module, model_parameters=params, config_params=cfg)
    assert not engine._spmd
    assert engine.micro_batches == M
    it = data_iter(batch=8)
    losses = [float(jax.device_get(engine.train_batch(it))) for _ in range(2)]
    assert np.isfinite(losses).all()


def test_spmd_pipe_composes_with_zero2():
    """Public-API pipeline + ZeRO-2: merge_zero_into claims a free data-divisible
    axis on the pipe-stacked master/optimizer state, so 2-D (pipe x data) state
    sharding happens under deepspeed.initialize with a JSON config."""
    hidden = 64  # [2, 64, 64] stacked weights: above min_size, 64 % dp(4) == 0
    layers = [LayerSpec(Linear, hidden) for _ in range(4)]
    module = PipelineModule(layers=layers, num_stages=2, loss_fn=mse_loss)
    params = module.init_params(jax.random.PRNGKey(3),
                                jnp.zeros((4, hidden), jnp.float32))
    cfg = pipe_config()
    cfg["zero_optimization"] = {"stage": 2}
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                               config_params=cfg)
    assert engine._spmd
    from deepspeed_tpu.runtime.pipe.engine import STACKED_KEY
    # stacked core master WEIGHTS are sharded on BOTH pipe (leading) and data axes
    w = engine.master_params[STACKED_KEY][0]["w"]
    spec = w.sharding.spec
    flat = [ax for e in spec if e for ax in ((e,) if isinstance(e, str) else e)]
    assert "pipe" in flat, spec
    assert "data" in flat, spec

    def it():
        rng = np.random.default_rng(19)
        w_true = np.random.default_rng(7).normal(size=(hidden, hidden)).astype(np.float32) * 0.3
        while True:
            x = rng.normal(size=(16, hidden)).astype(np.float32)
            yield x, np.tanh(x @ w_true)

    gen = it()
    losses = [float(jax.device_get(engine.train_batch(gen))) for _ in range(10)]
    assert losses[-1] < losses[0] * 0.9, losses
