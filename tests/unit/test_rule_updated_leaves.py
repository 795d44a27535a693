"""Leaves that a model updates by a rule of its own (``rule_updated_leaves``, ``rule_sums``,
``apply_rule``: ``runtime/engine.py``): such a leaf moves by the rule alone, once a step, from
sums taken over the step's micro-batches, whatever the optimizer, its weight decay, the
clipping and the scheduler do to the other leaves; on the default two-program step and on the
fused step; and every other path refuses it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from simple_model import SimpleModel, random_dataset, simple_config

HIDDEN, RATE = 16, 0.25


class RuledModel(SimpleModel):
    """``SimpleModel`` plus a leaf ``tally [HIDDEN]`` that the loss reads WITH a gradient (so
    that an optimizer, left to it, would move it) and that the model's own rule moves by
    ``RATE * sign(sum over the step of x's column sums)``."""
    device_scalars = ("tally_abs_max",)
    rule_updated_leaves = (r"^tally$",)
    rule_sums = ("column_sums",)

    def init(self, rng):
        return dict(super().init(rng), tally=jnp.full((self.hidden_dim,), 0.5, jnp.float32))

    def apply(self, params, x, y):
        loss = super().apply(params, x + params["tally"].astype(x.dtype), y)
        return loss, {"tally_abs_max": jnp.max(jnp.abs(params["tally"])).astype(jnp.float32),
                      "column_sums": jnp.sum(x.astype(jnp.float32), axis=0)}

    def apply_rule(self, leaves, sums):
        assert set(leaves) >= {"tally"} and all(
            v is None for k, v in leaves.items() if k != "tally"), leaves
        return dict(leaves, tally=leaves["tally"] + RATE * jnp.sign(sums["column_sums"]))


def config(gas, fused=False, **more):
    cfg = simple_config(batch=8 * gas, gradient_accumulation_steps=gas, gradient_clipping=0.05,
                        scheduler={"type": "WarmupLR", "params": {"warmup_min_lr": 1e-3,
                                                                  "warmup_max_lr": 1e-2,
                                                                  "warmup_num_steps": 10}})
    cfg["optimizer"] = {"type": "AdamW", "params": {"lr": 1e-2, "weight_decay": 0.1}}
    if fused:
        cfg["fused_step"] = True
    cfg.update(more)
    return cfg


def batches(n, rows=8, seed=0):
    data = random_dataset(n * rows, HIDDEN, seed=seed)
    xs, ys = np.stack([d[0] for d in data]), np.stack([d[1] for d in data])
    return [(xs[i * rows:(i + 1) * rows], ys[i * rows:(i + 1) * rows]) for i in range(n)]


@pytest.mark.parametrize("gas, fused", [(1, False), (2, False), (1, True)],
                         ids=["two-programs", "two-programs-accumulating", "fused-step"])
def test_a_rule_updated_leaf_moves_by_the_rule_alone(gas, fused):
    model = RuledModel(HIDDEN)
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
                                          config_params=config(gas, fused))
    assert (engine._run_fused_step is not None) == fused
    want = np.full((HIDDEN,), 0.5, np.float64)
    stream = batches(3 * gas)
    for step in range(3):
        before = jax.device_get(engine.master_params)
        window = stream[step * gas:(step + 1) * gas]
        for x, y in window:
            loss = engine(x, y)
            engine.backward(loss)
            engine.step()
        # the rule ONCE a step, on the sums over the step's micro-batches
        want = want + RATE * np.sign(sum(x.sum(axis=0) for x, _ in window))
        after = jax.device_get(engine.master_params)
        np.testing.assert_allclose(after["tally"], want, rtol=0, atol=1e-7)
        np.testing.assert_array_equal(np.asarray(engine.params["tally"], np.float32),
                                      after["tally"].astype(engine.params["tally"].dtype))
        for name in ("w1", "w2", "b1", "b2"):       # the optimizer still moves the others
            assert np.abs(after[name] - before[name]).max() > 0, name
    assert engine.global_steps == 3
    # no optimizer state came to the leaf: Adam's moments of it are what they started as
    for field in engine.opt_state:
        if isinstance(field, dict) and "tally" in field:
            assert not np.any(np.asarray(field["tally"])), "a moment of the rule-updated leaf moved"
            assert np.any(np.asarray(field["w1"]))


def test_the_leaf_has_no_share_of_the_clipped_norm():
    """With the rule-updated leaf's gradient in the norm, clipping at 0.05 would scale the
    other leaves' gradients differently: the norm the engine reports is the others' alone."""
    model = RuledModel(HIDDEN)
    params = model.init(jax.random.PRNGKey(0))
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params=config(1))
    (x, y), = batches(1)
    loss = engine(x, y)
    grads = jax.device_get(engine._pending_grads)
    assert np.linalg.norm(grads["tally"]) > 0.01
    others = np.sqrt(sum(float(np.sum(g * g)) for k, g in grads.items() if k != "tally"))
    engine.backward(loss)
    engine.step()
    assert float(engine._last_grad_norm) == pytest.approx(others, rel=1e-5)


def test_the_compute_copy_holds_the_leaf_as_the_master_does():
    """Under bf16 the weights' compute copy is bf16; the rule-updated leaf is no weight and
    stays float32 there, equal to the master bit for bit after every step (0.5 + 3 x 0.25 / 1024
    is no bf16 value)."""
    model = RuledModel(HIDDEN)
    model.apply_rule = lambda leaves, sums: dict(
        leaves, tally=leaves["tally"] + RATE / 1024 * jnp.sign(sums["column_sums"]))
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
                                          config_params=config(1, bf16={"enabled": True}))
    for x, y in batches(3):
        assert engine.params["tally"].dtype == jnp.float32 and engine.params["w1"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(engine.params["tally"]),
                                      np.asarray(engine.master_params["tally"]))
        loss = engine(x.astype(jnp.bfloat16), y)
        engine.backward(loss)
        engine.step()
    moved = np.asarray(engine.params["tally"], np.float64) - 0.5
    assert np.all(np.abs(moved) <= 3 * RATE / 1024 + 1e-7) and np.any(moved != 0)
    assert np.any(np.asarray(engine.params["tally"]) != np.asarray(engine.params["tally"].astype(jnp.bfloat16), np.float32))


@pytest.mark.parametrize("more, match", [
    ({"zero_optimization": {"stage": 2, "cpu_offload": True}}, "rule_updated_leaves"),
    ({"optimizer": {"type": "OneBitAdam", "params": {"lr": 1e-2, "freeze_step": 2}}}, "rule_updated_leaves"),
], ids=["offload", "one-bit-adam"])
def test_the_other_step_paths_refuse_such_a_model(more, match):
    model = RuledModel(HIDDEN)
    cfg = simple_config(batch=8, **more)
    if "zero_optimization" in more:
        cfg["bf16"] = {"enabled": True}
    with pytest.raises(AssertionError, match=match):
        deepspeed_tpu.initialize(model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
                                 config_params=cfg)


def test_the_pipeline_engine_refuses_such_a_model():
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
    from deepspeed_tpu.parallel.pipe.module import PipelineModule

    class Ruled(PipelineModule):
        rule_updated_leaves = ("tally",)

        def __init__(self):       # the refusal comes before anything of the module is read
            self.num_stages = 1

    with pytest.raises(AssertionError, match="rule of its own"):
        PipelineEngine(model=Ruled(), config_params=simple_config(batch=8))


def test_a_model_without_such_leaves_compiles_the_programs_it_did():
    """No rule, no extra operand: the update program's signature is the old one."""
    model = SimpleModel(HIDDEN)
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
                                          config_params=simple_config(batch=8))
    assert engine._rule_sum_names == () and engine._rule_fn is None
    (x, y), = batches(1)
    loss = engine(x, y)
    engine.backward(loss)
    engine.step()
    assert engine._rule_sums is None and np.isfinite(float(loss))
