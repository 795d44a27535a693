"""Checkpoint round-trip tests (parity with reference tests/unit/test_checkpointing.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from simple_model import SimpleModel, random_dataset, simple_config

HIDDEN = 16


def make_engine(cfg, seed=0, hidden=HIDDEN):
    model = SimpleModel(hidden)
    params = model.init(jax.random.PRNGKey(seed))
    data = random_dataset(128, hidden, seed=seed)
    engine, _, loader, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                                    training_data=data, config_params=cfg)
    return engine, loader


def train_steps(engine, loader, n):
    it = iter(loader)
    for _ in range(n):
        x, y = next(it)
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    return it


def trees_equal(a, b, rtol=0.0, atol=0.0):
    la = jax.tree_util.tree_leaves(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float32), np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("zero_stage", [0, 1, 2])
def test_checkpoint_roundtrip(tmp_path, zero_stage):
    cfg = simple_config(zero_optimization={"stage": zero_stage})
    engine, loader = make_engine(cfg)
    train_steps(engine, loader, 3)
    engine.save_checkpoint(str(tmp_path), client_state={"note": "hello"})

    engine2, _ = make_engine(cfg, seed=99)  # different init
    path, client_state = engine2.load_checkpoint(str(tmp_path))
    assert path is not None
    assert client_state == {"note": "hello"}
    assert engine2.global_steps == engine.global_steps
    trees_equal(engine.master_params, engine2.master_params)
    trees_equal(engine.opt_state, engine2.opt_state)
    trees_equal(engine.params, engine2.params)


def test_checkpoint_continue_training_matches(tmp_path):
    """Save at step 3, keep training to 6; reload at 3 and retrain — same weights."""
    cfg = simple_config()
    engine, loader = make_engine(cfg)
    it = iter(loader)
    batches = []
    for _ in range(6):
        batches.append(next(it))
    for x, y in batches[:3]:
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    engine.save_checkpoint(str(tmp_path))
    for x, y in batches[3:]:
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    final_a = jax.device_get(engine.master_params)

    engine2, _ = make_engine(cfg, seed=7)
    engine2.load_checkpoint(str(tmp_path))
    for x, y in batches[3:]:
        loss = engine2(x, y)
        engine2.backward(loss)
        engine2.step()
    final_b = jax.device_get(engine2.master_params)
    jax.tree_util.tree_map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
                           final_a, final_b)


def test_checkpoint_lr_scheduler_state(tmp_path):
    cfg = simple_config(scheduler={"type": "WarmupLR",
                                   "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 0.01,
                                              "warmup_num_steps": 20}})
    engine, loader = make_engine(cfg)
    train_steps(engine, loader, 5)
    saved_iter = engine.lr_scheduler.last_batch_iteration
    engine.save_checkpoint(str(tmp_path))

    engine2, _ = make_engine(cfg)
    engine2.load_checkpoint(str(tmp_path))
    assert engine2.lr_scheduler.last_batch_iteration == saved_iter


def test_checkpoint_no_optim_states(tmp_path):
    cfg = simple_config()
    engine, loader = make_engine(cfg)
    train_steps(engine, loader, 3)
    engine.save_checkpoint(str(tmp_path))
    engine2, _ = make_engine(cfg, seed=42)
    engine2.load_checkpoint(str(tmp_path), load_optimizer_states=False)
    # params restored; master derived from (possibly lower-precision) params
    trees_equal(engine.params, engine2.params)


def test_checkpoint_latest_tag(tmp_path):
    cfg = simple_config()
    engine, loader = make_engine(cfg)
    train_steps(engine, loader, 1)
    engine.save_checkpoint(str(tmp_path), tag="step1")
    train_steps(engine, loader, 1)
    engine.save_checkpoint(str(tmp_path), tag="step2")
    assert (tmp_path / "latest").read_text() == "step2"
    engine2, _ = make_engine(cfg, seed=5)
    path, _ = engine2.load_checkpoint(str(tmp_path))
    assert path.endswith("step2")


def test_checkpoint_missing_dir():
    cfg = simple_config()
    engine, _ = make_engine(cfg)
    path, client_state = engine.load_checkpoint("/tmp/definitely_missing_dir_xyz")
    assert path is None
    assert client_state == {}


def test_checkpoint_elastic_world_size_change(tmp_path, eight_devices):
    """Save under dp=8, reload under dp=4 (elastic resharding; reference stage2.py:1713-1779)."""
    import jax
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    cfg = simple_config(zero_optimization={"stage": 2})
    engine, loader = make_engine(cfg)
    assert engine.dp_size == 8
    train_steps(engine, loader, 3)
    engine.save_checkpoint(str(tmp_path))

    model = SimpleModel(HIDDEN)
    params = model.init(jax.random.PRNGKey(42))
    mesh4 = build_mesh(data=4, model=1, pipe=1, devices=eight_devices[:4])
    engine2 = DeepSpeedEngine(model=model, model_parameters=params,
                              config_params=simple_config(batch=4, zero_optimization={"stage": 2}),
                              mesh=mesh4)
    assert engine2.dp_size == 4
    path, _ = engine2.load_checkpoint(str(tmp_path))
    assert path is not None
    trees_equal(engine.master_params, engine2.master_params)
    trees_equal(engine.opt_state, engine2.opt_state)
    assert engine2.global_steps == engine.global_steps


def test_checkpoint_elastic_grow(tmp_path, eight_devices):
    """Save under dp=4, reload under dp=8 (elastic regrow; reference stage1.py:836-947
    supports arbitrary saved→current dp)."""
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    model = SimpleModel(HIDDEN)
    mesh4 = build_mesh(data=4, model=1, pipe=1, devices=eight_devices[:4])
    engine = DeepSpeedEngine(model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
                             config_params=simple_config(batch=4, zero_optimization={"stage": 2}),
                             mesh=mesh4)
    data = random_dataset(64, HIDDEN, seed=0)
    it = iter(engine.deepspeed_io(data))
    for _ in range(3):
        x, y = next(it)
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    engine.save_checkpoint(str(tmp_path))

    engine2, _ = make_engine(simple_config(zero_optimization={"stage": 2}), seed=9)
    assert engine2.dp_size == 8
    path, _ = engine2.load_checkpoint(str(tmp_path))
    assert path is not None
    trees_equal(engine.master_params, engine2.master_params)
    trees_equal(engine.opt_state, engine2.opt_state)


def test_checkpoint_elastic_zero3(tmp_path, eight_devices):
    """Stage-3 checkpoints resize too: save under dp=8, resume under dp=4 — the
    restored compute params re-adopt the NEW mesh's stage-3 sharded layout and
    training numerics carry over (params/master/opt all agree with the source)."""
    import jax
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    engine, loader = make_engine(simple_config(zero_optimization={"stage": 3},
                                               bf16={"enabled": True}),
                                 hidden=64)  # > min_size so the leaves shard
    train_steps(engine, loader, 3)
    engine.save_checkpoint(str(tmp_path))

    model = SimpleModel(64)
    mesh4 = build_mesh(data=4, model=1, pipe=1, devices=eight_devices[:4])
    engine2 = DeepSpeedEngine(model=model, model_parameters=model.init(jax.random.PRNGKey(42)),
                              config_params=simple_config(batch=4,
                                                          zero_optimization={"stage": 3},
                                                          bf16={"enabled": True}),
                              mesh=mesh4)
    path, _ = engine2.load_checkpoint(str(tmp_path))
    assert path is not None
    trees_equal(engine.master_params, engine2.master_params)
    trees_equal(engine.opt_state, engine2.opt_state)
    trees_equal(engine.params, engine2.params)
    # and the restored params are sharded over the NEW (dp=4) data axis
    for leaf in jax.tree_util.tree_leaves(engine2.params):
        if leaf.ndim == 2:
            assert not leaf.sharding.is_fully_replicated
            assert leaf.addressable_shards[0].data.size * 4 == leaf.size


def test_checkpoint_pipe_topology_change(tmp_path):
    """Pipeline checkpoints are layer-keyed, so stage boundaries can move between
    save and load (reference pipe/module.py:536-567, test_checkpointing.py:617+)."""
    from deepspeed_tpu.parallel.pipe import LayerSpec, PipelineModule

    class Linear:
        def __init__(self, dim):
            self.dim = dim
        def init(self, rng, x):
            k1, _ = jax.random.split(rng)
            return {"w": jax.random.normal(k1, (x.shape[-1], self.dim), jnp.float32) * 0.3}
        def apply(self, p, x):
            return jnp.tanh(x @ p["w"].astype(x.dtype))

    def mse(out, tgt):
        return jnp.mean(jnp.square(out.astype(jnp.float32) - tgt.astype(jnp.float32)))

    def build(num_stages):
        module = PipelineModule(layers=[LayerSpec(Linear, HIDDEN) for _ in range(4)],
                                num_stages=num_stages, loss_fn=mse)
        params = module.init_params(jax.random.PRNGKey(1), jnp.zeros((4, HIDDEN), jnp.float32))
        cfg = {"train_batch_size": 32, "gradient_accumulation_steps": 2,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}}
        engine, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                                   config_params=cfg)
        return engine

    def data_iter():
        rng = np.random.default_rng(3)
        while True:
            x = rng.normal(size=(16, HIDDEN)).astype(np.float32)
            yield x, np.tanh(x @ np.ones((HIDDEN, HIDDEN), np.float32) * 0.1)

    engine = build(num_stages=2)
    it = data_iter()
    for _ in range(3):
        engine.train_batch(it)
    engine.save_checkpoint(str(tmp_path))

    for new_stages in (1, 4):
        engine2 = build(num_stages=new_stages)
        path, _ = engine2.load_checkpoint(str(tmp_path))
        assert path is not None, f"reload at {new_stages} stages failed"
        # compare in the canonical layer-keyed representation: the SPMD executor
        # stores core stages pipe-stacked, and stage counts differ across engines
        trees_equal(engine.canonical_master_params(),
                    engine2.canonical_master_params())
        # training continues identically after the re-partition
        e1_it, e2_it = data_iter(), data_iter()
        l1 = float(jax.device_get(engine.eval_batch(e1_it)))
        l2 = float(jax.device_get(engine2.eval_batch(e2_it)))
        np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_external_master_checkpoint_is_refused(tmp_path):
    """A checkpoint whose metadata says external_master: true was written by the
    removed step path: it holds no master and a client's flat shard as optimizer
    state. This engine cannot hold that, and says so instead of loading part of it."""
    import glob
    import json
    import os
    from deepspeed_tpu.checkpoint.checkpointing import MANIFEST_NAME, model_states_name

    engine, loader = make_engine(simple_config())
    train_steps(engine, loader, 1)
    engine.save_checkpoint(str(tmp_path), tag="ext")
    (meta_path,) = glob.glob(os.path.join(str(tmp_path), "ext", model_states_name() + ".json"))
    meta = json.load(open(meta_path))
    assert "external_master" not in meta          # this engine no longer writes the key
    meta["external_master"] = True
    json.dump(meta, open(meta_path, "w"))
    os.remove(os.path.join(str(tmp_path), "ext", MANIFEST_NAME))   # or the edit reads as a torn checkpoint
    with pytest.raises(ValueError, match="external-master"):
        engine.load_checkpoint(str(tmp_path), tag="ext")
