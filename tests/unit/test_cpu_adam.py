"""CPU Adam + ZeRO-Offload tests (analog of reference tests/unit/test_cpu_adam.py and
the zero_stage x cpu_offload sweeps in tests/unit/test_fp16.py:236-301)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.ops import adam as jadam
from deepspeed_tpu.ops.cpu_adam import DeepSpeedCPUAdam

from simple_model import SimpleModel, random_dataset, simple_config


def _params(rng):
    return {"w": rng.normal(size=(33, 17)).astype(np.float32),
            "b": rng.normal(size=(129,)).astype(np.float32)}


def test_cpu_adam_matches_fused_adam():
    """Trajectory parity vs the jitted fused Adam (mirrors test_cpu_adam.py's check
    against torch.optim.Adam)."""
    rng = np.random.default_rng(0)
    params = _params(rng)
    opt = DeepSpeedCPUAdam(params)
    jp = jax.tree_util.tree_map(jnp.asarray, params)
    jstate = jadam.init(jp)
    hyper = dict(lr=jnp.float32(1e-3), beta1=jnp.float32(0.9), beta2=jnp.float32(0.999),
                 eps=jnp.float32(1e-8), weight_decay=jnp.float32(0.01))
    for step in range(1, 8):
        g = _params(rng)
        opt.step(opt.flatten_grads(g), step=step, lr=1e-3, weight_decay=0.01)
        jp, jstate = jadam.apply(jax.tree_util.tree_map(jnp.asarray, g), jstate, jp,
                                 jnp.int32(step), hyper)
    got = opt.params_tree()
    for k in params:
        np.testing.assert_allclose(got[k], np.asarray(jp[k]), rtol=3e-5, atol=3e-6)


def _reference_adam_step(p, g, m, v, step, lr, b1, b2, eps, wd, adamw):
    """Hand-rolled fp64 oracle with torch semantics: torch.optim.Adam folds wd*p into
    the gradient BEFORE the moments (classic L2); torch.optim.AdamW decays p directly."""
    p, g, m, v = (np.asarray(a, np.float64) for a in (p, g, m, v))
    if not adamw:
        g = g + wd * p
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    update = (m / (1 - b1 ** step)) / (np.sqrt(v / (1 - b2 ** step)) + eps)
    p = p - lr * update - (lr * wd * p if adamw else 0.0)
    return p, m, v


@pytest.mark.parametrize("adamw", [False, True])
def test_adam_decay_semantics(adamw):
    """'type': 'Adam' must be classic L2 Adam (wd folded into the gradient before the
    moments, torch.optim.Adam semantics); 'AdamW' decoupled decay. Parity for the
    jitted fused path (ops/adam.py) and the host-tier DeepSpeedCPUAdam (native + numpy)
    vs a hand-rolled fp64 oracle — and vs torch itself when available.
    Reference update: csrc/adam/cpu_adam.cpp."""
    rng = np.random.default_rng(7)
    params = _params(rng)
    wd, lr = 0.1, 1e-2

    try:
        import torch
    except ImportError:
        torch = None
    if torch is not None:
        tparams = [torch.nn.Parameter(torch.from_numpy(params[k].copy()))
                   for k in sorted(params)]
        topt = (torch.optim.AdamW if adamw else torch.optim.Adam)(
            tparams, lr=lr, betas=(0.9, 0.999), eps=1e-8, weight_decay=wd)

    ref_p = {k: params[k].astype(np.float64) for k in params}
    ref_m = {k: np.zeros_like(ref_p[k]) for k in params}
    ref_v = {k: np.zeros_like(ref_p[k]) for k in params}

    jp = jax.tree_util.tree_map(jnp.asarray, params)
    jstate = jadam.init(jp)
    hyper = dict(lr=jnp.float32(lr), beta1=jnp.float32(0.9), beta2=jnp.float32(0.999),
                 eps=jnp.float32(1e-8), weight_decay=jnp.float32(wd))
    copt = DeepSpeedCPUAdam(params, adamw=adamw)
    nopt = DeepSpeedCPUAdam(params, adamw=adamw)
    nopt._lib = None  # numpy fallback path

    for step in range(1, 6):
        g = _params(rng)
        for k in params:
            ref_p[k], ref_m[k], ref_v[k] = _reference_adam_step(
                ref_p[k], g[k], ref_m[k], ref_v[k], step, lr, 0.9, 0.999, 1e-8, wd, adamw)
        if torch is not None:
            for tp, k in zip(tparams, sorted(params)):
                tp.grad = torch.from_numpy(g[k].copy())
            topt.step()
        jp, jstate = jadam.apply(jax.tree_util.tree_map(jnp.asarray, g), jstate, jp,
                                 jnp.int32(step), hyper, adamw=adamw)
        copt.step(copt.flatten_grads(g), step=step, lr=lr, weight_decay=wd)
        nopt.step(nopt.flatten_grads(g), step=step, lr=lr, weight_decay=wd)

    got_c, got_n = copt.params_tree(), nopt.params_tree()
    for k in params:
        np.testing.assert_allclose(np.asarray(jp[k]), ref_p[k], rtol=3e-5, atol=3e-6)
        np.testing.assert_allclose(got_c[k], ref_p[k], rtol=3e-5, atol=3e-6)
        np.testing.assert_allclose(got_n[k], ref_p[k], rtol=3e-5, atol=3e-6)
    if torch is not None:  # the oracle itself agrees with torch
        for tp, k in zip(tparams, sorted(params)):
            np.testing.assert_allclose(ref_p[k], tp.detach().numpy(), rtol=3e-5, atol=3e-6)


def test_cpu_adam_native_matches_numpy_fallback():
    rng = np.random.default_rng(1)
    params = _params(rng)
    a = DeepSpeedCPUAdam(params)
    b = DeepSpeedCPUAdam(params)
    b._lib = None  # force numpy path
    if a._lib is None:
        pytest.skip("native toolchain unavailable; fallback is the only path")
    for step in range(1, 5):
        g_flat = rng.normal(size=a.numel).astype(np.float32)
        a.step(g_flat, step=step, lr=1e-3, weight_decay=0.01)
        b.step(g_flat, step=step, lr=1e-3, weight_decay=0.01)
    np.testing.assert_allclose(a.fp32, b.fp32, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(a.exp_avg, b.exp_avg, rtol=1e-6, atol=1e-7)


def test_cpu_adam_fused_bf16_cast():
    rng = np.random.default_rng(2)
    opt = DeepSpeedCPUAdam(_params(rng))
    g = rng.normal(size=opt.numel).astype(np.float32)
    bf = opt.step_and_cast_bf16(g, step=1, lr=1e-2)
    assert bf.shape == (opt.numel,)
    np.testing.assert_allclose(np.asarray(bf, np.float32), opt.fp32, rtol=1e-2, atol=1e-2)


def _train(engine, steps=10, batch=8, hidden=16):
    data = random_dataset(batch * steps, hidden)
    losses = []
    for i in range(steps):
        xs = np.stack([data[i * batch + j][0] for j in range(batch)])
        ys = np.stack([data[i * batch + j][1] for j in range(batch)])
        loss = engine(xs, ys)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    return losses


@pytest.mark.parametrize("precision", ["bf16", "fp16"])
def test_engine_zero_offload_trains(precision):
    model = SimpleModel(hidden_dim=16)
    params = model.init(jax.random.PRNGKey(0))
    cfg = simple_config(batch=8)
    cfg["zero_optimization"] = {"stage": 2, "cpu_offload": True}
    if precision == "bf16":
        cfg["bf16"] = {"enabled": True}
    else:
        cfg["fp16"] = {"enabled": True, "loss_scale": 128.0}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                               config_params=cfg)
    assert engine._offload is not None
    losses = _train(engine, steps=30)
    assert losses[-1] < losses[0] * 0.7, f"loss did not drop: {losses[0]} -> {losses[-1]}"
    # master weights really live on host as numpy views of the flat buffer
    leaf = jax.tree_util.tree_leaves(engine.master_params)[0]
    assert isinstance(leaf, np.ndarray)
    assert leaf.base is engine._offload.fp32 or leaf.base.base is engine._offload.fp32


def test_engine_zero_offload_checkpoint_roundtrip(tmp_path):
    model = SimpleModel(hidden_dim=16)

    def make():
        params = model.init(jax.random.PRNGKey(0))
        cfg = simple_config(batch=8)
        cfg["zero_optimization"] = {"stage": 2, "cpu_offload": True}
        cfg["bf16"] = {"enabled": True}
        eng, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                                config_params=cfg)
        return eng

    e1 = make()
    _train(e1, steps=5)
    e1.save_checkpoint(str(tmp_path))
    e2 = make()
    e2.load_checkpoint(str(tmp_path))
    np.testing.assert_allclose(e2._offload.fp32, e1._offload.fp32, rtol=1e-6)
    np.testing.assert_allclose(e2._offload.exp_avg, e1._offload.exp_avg, rtol=1e-6)
    assert e2.global_steps == e1.global_steps
    # resumed training continues from identical state: next-step loss matches
    l1 = _train(e1, steps=1)[0]
    l2 = _train(e2, steps=1)[0]
    assert abs(l1 - l2) < 1e-5


def test_engine_zero_offload_fp16_overflow_skips_step():
    """Inf/NaN grads on the host tier must skip the master update and back off the loss
    scale (reference: CheckOverflow before DeepSpeedCPUAdam.step), not poison fp32."""
    model = SimpleModel(hidden_dim=16)
    params = model.init(jax.random.PRNGKey(0))
    cfg = simple_config(batch=8)
    cfg["zero_optimization"] = {"stage": 2, "cpu_offload": True}
    cfg["fp16"] = {"enabled": True, "loss_scale": 0, "initial_scale_power": 4,
                   "hysteresis": 1}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                               config_params=cfg)
    master_before = np.array(engine._offload.fp32, copy=True)
    s0 = float(engine.loss_scale())

    # SimpleModel computes in the input dtype, so fp32 math stays finite; the overflow
    # comes from the fp16 PARAM leaves: the huge target makes cotangents ~1e19, which
    # overflow when the grads are produced for the engine's fp16-stored params.
    x = np.ones((8, 16), np.float32)
    y = np.full((8, 16), 1e20, np.float32)
    loss = engine(x, y)
    engine.backward(loss)
    engine.step()

    assert engine.skipped_steps == 1
    np.testing.assert_array_equal(engine._offload.fp32, master_before)
    assert np.all(np.isfinite(engine._offload.fp32))
    assert float(engine.loss_scale()) == s0 / 2, (s0, float(engine.loss_scale()))

    # and a sane batch afterwards still trains
    losses = _train(engine, steps=3)
    assert np.isfinite(losses).all()


def test_offload_partitioned_matches_device_engine():
    """Partitioned offload (real ZeRO regions over the 8-device mesh) must track the
    fully on-device ZeRO-2 engine: hidden_dim=64 makes the weight leaves big enough for
    zero_spec to shard them, so the host tier steps 8 distinct regions per leaf."""
    model = SimpleModel(hidden_dim=64)

    def make(offload):
        params = model.init(jax.random.PRNGKey(0))
        cfg = simple_config(batch=8)
        cfg["optimizer"] = {"type": "AdamW", "params": {"lr": 1e-2, "weight_decay": 0.01}}
        cfg["zero_optimization"] = {"stage": 2, "cpu_offload": offload}
        eng, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                                config_params=cfg)
        return eng

    e_host, e_dev = make(True), make(False)
    if jax.device_count() > 1:
        # the host tier really is partitioned: >1 region for the sharded weight leaves
        assert any(len(r) > 1 for r in e_host._offload._leaf_regions)
    data = random_dataset(8 * 10, 64)
    for i in range(10):
        xs = np.stack([data[i * 8 + j][0] for j in range(8)])
        ys = np.stack([data[i * 8 + j][1] for j in range(8)])
        for eng in (e_host, e_dev):
            loss = eng(xs, ys)
            eng.backward(loss)
            eng.step()
    host_params = jax.device_get(e_host.params)
    dev_params = jax.device_get(e_dev.params)
    for k in host_params:
        # host (fma-ordered SIMD) vs XLA fused Adam drift compounds over 10 steps
        np.testing.assert_allclose(np.asarray(host_params[k], np.float32),
                                   np.asarray(dev_params[k], np.float32),
                                   rtol=1e-2, atol=1e-4)
    # master assembly agrees with the device master too
    host_master = e_host.master_params
    dev_master = jax.device_get(e_dev.master_params)
    for k in host_master:
        np.testing.assert_allclose(host_master[k], np.asarray(dev_master[k]),
                                   rtol=1e-2, atol=1e-4)
    t = e_host._offload.last_step_timing
    assert t is not None and t["total"] > 0


def test_region_layout_non_contiguous_assembly():
    """A leaf sharded on a non-leading axis stores non-contiguous regions; assembly and
    load_trees must still round-trip exactly."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 devices")
    mesh = Mesh(np.array(devs).reshape(len(devs), 1), ("data", "model"))
    rng = np.random.default_rng(3)
    params = {"w": rng.normal(size=(24, 8 * len(devs))).astype(np.float32)}
    shard = {"w": NamedSharding(mesh, P(None, "data"))}  # axis-1: non-contiguous regions
    opt = DeepSpeedCPUAdam(params, shardings=shard)
    assert not opt._leaf_viewable[0]
    got = opt.params_tree()
    np.testing.assert_array_equal(got["w"], params["w"])
    # round-trip through load_trees
    new = {"w": rng.normal(size=params["w"].shape).astype(np.float32)}
    opt.load_trees(master_tree=new)
    np.testing.assert_array_equal(opt.params_tree()["w"], new["w"])
    # a flat-buffer step over regions equals a whole-tree step
    ref = DeepSpeedCPUAdam(params)
    g = {"w": rng.normal(size=params["w"].shape).astype(np.float32)}
    opt.load_trees(master_tree=params)
    opt.step(opt.flatten_grads(g), step=1, lr=1e-2, weight_decay=0.01)
    ref.step(ref.flatten_grads(g), step=1, lr=1e-2, weight_decay=0.01)
    np.testing.assert_allclose(opt.params_tree()["w"], ref.params_tree()["w"],
                               rtol=1e-6, atol=1e-7)


def _make_engine(model, offload, lr=1e-2):
    params = model.init(jax.random.PRNGKey(0))
    cfg = simple_config(batch=8)
    cfg["optimizer"] = {"type": "AdamW", "params": {"lr": lr, "weight_decay": 0.01}}
    cfg["zero_optimization"] = {"stage": 2, "cpu_offload": offload}
    eng, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                            config_params=cfg)
    return eng


def test_offload_region_checkpoint_partitioned_roundtrip(tmp_path):
    """Region-wise offload checkpoint (per-process files) with REAL ZeRO partitions
    (hidden 64 -> sharded leaves): save -> fresh engine load -> identical buffers and
    identical next-step loss."""
    model = SimpleModel(hidden_dim=64)
    e1 = _make_engine(model, offload=True)
    assert any(len(r) > 1 for r in e1._offload._leaf_regions)
    _train(e1, steps=5, hidden=64)
    e1.save_checkpoint(str(tmp_path))
    import os
    assert os.path.isfile(tmp_path / f"global_step{e1.global_steps}" /
                          "offload_manifest_0.json")
    e2 = _make_engine(model, offload=True)
    e2.load_checkpoint(str(tmp_path))
    np.testing.assert_allclose(e2._offload.fp32, e1._offload.fp32, rtol=1e-6)
    np.testing.assert_allclose(e2._offload.exp_avg, e1._offload.exp_avg, rtol=1e-6)
    l1 = _train(e1, steps=1, hidden=64)[0]
    l2 = _train(e2, steps=1, hidden=64)[0]
    assert abs(l1 - l2) < 1e-5


def test_offload_checkpoint_cross_layout(tmp_path):
    """An offload (region-layout) checkpoint must restore into a NON-offload engine and
    vice versa — the loader detects the on-disk layout, not the engine mode."""
    model = SimpleModel(hidden_dim=64)
    # offload save -> device-engine load
    e1 = _make_engine(model, offload=True)
    _train(e1, steps=4, hidden=64)
    e1.save_checkpoint(str(tmp_path / "a"))
    e2 = _make_engine(model, offload=False)
    e2.load_checkpoint(str(tmp_path / "a"))
    m1 = e1.master_params
    m2 = jax.device_get(e2.master_params)
    for k in m1:
        np.testing.assert_allclose(np.asarray(m2[k]), m1[k], rtol=1e-6, atol=1e-7)
    l1 = _train(e1, steps=1, hidden=64)[0]
    l2 = _train(e2, steps=1, hidden=64)[0]
    assert abs(l1 - l2) < 1e-4

    # device-engine save -> offload load
    e3 = _make_engine(model, offload=False)
    _train(e3, steps=4, hidden=64)
    e3.save_checkpoint(str(tmp_path / "b"))
    e4 = _make_engine(model, offload=True)
    e4.load_checkpoint(str(tmp_path / "b"))
    m3 = jax.device_get(e3.master_params)
    m4 = e4.master_params
    for k in m4:
        np.testing.assert_allclose(m4[k], np.asarray(m3[k]), rtol=1e-6, atol=1e-7)
    l3 = _train(e3, steps=1, hidden=64)[0]
    l4 = _train(e4, steps=1, hidden=64)[0]
    assert abs(l3 - l4) < 1e-4


def test_offload_push_bytes_proportional_to_partition():
    """H2D pushes after the host step must total the local PARTITION size, not
    x n_devices: replicated leaves ride one PCIe push + an
    on-device broadcast."""
    model = SimpleModel(hidden_dim=16)  # leaves too small to shard -> replicated on 8 devs
    eng = _make_engine(model, offload=True)
    _train(eng, steps=1)
    off = eng._offload
    assert off.last_push_elements == off.numel, \
        (off.last_push_elements, off.numel, jax.device_count())
    if jax.device_count() > 1:
        # every region in this config is replicated across all devices
        assert all(len(r.devices or []) > 1 for rs in off._leaf_regions for r in rs)
    # the broadcast arrays still carry the construction shardings
    for leaf, sh in zip(jax.tree_util.tree_leaves(eng.params),
                        jax.tree_util.tree_leaves(eng._param_shardings)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)


def test_offload_grad_fetch_fallback_uses_addressable_shards():
    """A grad layout that doesn't tile the master regions must be assembled from
    addressable shards (never whole-leaf device_get, which breaks multi-host), and the
    stepped result must match the matched-layout path."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 devices")
    mesh = Mesh(np.array(devs).reshape(len(devs), 1), ("data", "model"))
    rng = np.random.default_rng(11)
    params = {"w": rng.normal(size=(8 * len(devs), 16)).astype(np.float32)}
    master_sh = {"w": NamedSharding(mesh, P("data", None))}
    opt = DeepSpeedCPUAdam(params, shardings=master_sh)
    assert len(opt._leaf_regions[0]) == len(devs)

    g_np = {"w": rng.normal(size=params["w"].shape).astype(np.float32)}
    # grads sharded on the WRONG axis: per-device shard shape != region shape
    g_dev = {"w": jax.device_put(g_np["w"], NamedSharding(mesh, P(None, "data")))}
    handles = opt.begin_grad_fetch(g_dev)
    assert any(kind == "region_shards" for kind, *_ in handles)
    assert opt._warned_fallback
    opt.step_regions(handles, step=1, lr=1e-2, weight_decay=0.01)

    ref = DeepSpeedCPUAdam(params, shardings=master_sh)
    ref.step_regions(ref.begin_grad_fetch(
        {"w": jax.device_put(g_np["w"], master_sh["w"])}), step=1, lr=1e-2,
        weight_decay=0.01)
    np.testing.assert_allclose(opt.fp32, ref.fp32, rtol=1e-6, atol=1e-7)


def test_offload_grad_accumulation_fp32_accumulator():
    """With accumulation > 1 under offload, the accumulate buffer must be fp32 even
    though per-microbatch grads stay in the compute dtype."""
    model = SimpleModel(hidden_dim=16)
    params = model.init(jax.random.PRNGKey(0))
    cfg = simple_config(batch=16, gradient_accumulation_steps=2)
    cfg["zero_optimization"] = {"stage": 2, "cpu_offload": True}
    cfg["bf16"] = {"enabled": True}
    eng, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                            config_params=cfg)
    assert eng._acc_dtype == jnp.float32
    x = np.random.default_rng(0).normal(size=(8, 16)).astype(np.float32)
    y = np.zeros((8, 16), np.float32)
    loss = eng(x, y)
    eng.backward(loss)
    for leaf in jax.tree_util.tree_leaves(eng._grad_acc):
        assert leaf.dtype == jnp.float32
    loss = eng(x, y)
    eng.backward(loss)
    eng.step()
    assert eng.global_steps == 1
    assert np.all(np.isfinite(eng._offload.fp32))
