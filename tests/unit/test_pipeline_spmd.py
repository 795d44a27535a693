"""SPMD pipe-axis pipeline tests: numerics vs sequential, grads, GPT2Pipe end-to-end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.parallel.pipeline_spmd import (pipeline_apply, stack_stage_params,
                                                  stacked_param_sharding)

S, M, B, H = 2, 4, 8, 16


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(data=4, model=1, pipe=2)


@pytest.fixture(scope="module")
def toy(mesh):
    key = jax.random.PRNGKey(0)
    per_stage = []
    for _ in range(S):
        k1, key = jax.random.split(key)
        per_stage.append({"w": jax.random.normal(k1, (H, H)) * 0.3, "b": jnp.zeros((H,))})
    stacked = stack_stage_params(per_stage)
    stacked = jax.device_put(stacked, stacked_param_sharding(mesh, stacked))
    x_mb = jax.random.normal(key, (M, B, H))
    labels_mb = jnp.tanh(x_mb @ (jax.random.normal(jax.random.PRNGKey(9), (H, H)) * 0.5))
    return stacked, x_mb, labels_mb


def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def seq_loss(stacked, x_mb, labels_mb):
    losses = []
    for m in range(M):
        x = x_mb[m]
        for s in range(S):
            x = stage_fn(jax.tree_util.tree_map(lambda a: a[s], stacked), x)
        losses.append(jnp.mean((x - labels_mb[m])**2))
    return jnp.mean(jnp.stack(losses))


def test_pipeline_forward_matches_sequential(mesh, toy):
    stacked, x_mb, _ = toy
    outs = jax.jit(lambda s, x: pipeline_apply(stage_fn, s, x, mesh=mesh))(stacked, x_mb)
    ref = jnp.stack([
        stage_fn(jax.tree_util.tree_map(lambda a: a[1], stacked),
                 stage_fn(jax.tree_util.tree_map(lambda a: a[0], stacked), x_mb[m]))
        for m in range(M)
    ])
    np.testing.assert_allclose(np.asarray(outs), np.asarray(ref), rtol=1e-5, atol=1e-6)


def test_pipeline_loss_and_grads_match_sequential(mesh, toy):
    stacked, x_mb, labels_mb = toy

    def last_fn(y, labels_all, mb):
        return jnp.mean((y - labels_all[mb])**2)

    def pipe_loss(stacked, x_mb):
        from jax.sharding import PartitionSpec as P
        return pipeline_apply(stage_fn, stacked, x_mb, mesh=mesh,
                              last_stage_fn=last_fn, last_stage_args=(labels_mb,),
                              last_stage_args_specs=(P(None, "data"),))

    l_seq = jax.jit(lambda s, x: seq_loss(s, x, labels_mb))(stacked, x_mb)
    l_pipe = jax.jit(pipe_loss)(stacked, x_mb)
    np.testing.assert_allclose(float(l_seq), float(l_pipe), rtol=1e-6)

    g_seq = jax.jit(jax.grad(lambda s, x: seq_loss(s, x, labels_mb)))(stacked, x_mb)
    g_pipe = jax.jit(jax.grad(pipe_loss))(stacked, x_mb)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g_seq[k]), np.asarray(g_pipe[k]),
                                   rtol=1e-5, atol=1e-6)


def test_ambiguous_last_stage_args_refused_without_specs(mesh, toy):
    """A last_stage_args leaf whose leading dim == M is ambiguous (micro-batched
    labels vs a weight that coincidentally matches); the default streamed path must
    refuse and name the leaf — same contract as the drain-per-flush schedule —
    instead of silently guessing data-sharded."""
    stacked, x_mb, labels_mb = toy

    def last_fn(y, labels_all, mb):
        return jnp.mean((y - labels_all[mb])**2)

    with pytest.raises(ValueError, match=r"last_stage_args leaf .* leading dim == M"):
        jax.jit(lambda s, x: pipeline_apply(
            stage_fn, s, x, mesh=mesh, last_stage_fn=last_fn,
            last_stage_args=(labels_mb,)))(stacked, x_mb)

    # an unambiguous extra arg (no M-leading dim) still infers P() without specs
    scale = jnp.float32(2.0)

    def last_fn2(y, s, mb):
        return s * jnp.mean(y**2)

    l_ok = jax.jit(lambda s, x: pipeline_apply(
        stage_fn, s, x, mesh=mesh, last_stage_fn=last_fn2,
        last_stage_args=(scale,)))(stacked, x_mb)
    assert np.isfinite(float(l_ok))


def test_stacked_params_actually_pipe_sharded(mesh, toy):
    stacked, _, _ = toy
    sh = stacked["w"].sharding
    assert not sh.is_fully_replicated


def test_gpt2_pipe_trains(mesh):
    """Full 3D slice: GPT2Pipe (pipe=2 stages x data=4 DP x ZeRO-2) through the engine."""
    from deepspeed_tpu.models.gpt2 import GPT2Config
    from deepspeed_tpu.models.gpt2_pipe import GPT2Pipe
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = GPT2Config(vocab_size=128, n_positions=32, n_embd=32, n_layer=4, n_head=2,
                     compute_dtype=jnp.float32)
    pipe = GPT2Pipe(cfg, num_stages=2)
    params = pipe.init(jax.random.PRNGKey(0))
    shardings = pipe.param_shardings(mesh, params)

    def model_fn(p, tokens_mb, labels_mb):
        return pipe.loss(p, tokens_mb, labels_mb, mesh=mesh)

    # all M micro-batches run inside one engine call (the pipeline IS the accumulation)
    ds_cfg = {"train_batch_size": 8 * M, "train_micro_batch_size_per_gpu": 2 * M,
              "gradient_accumulation_steps": 1,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 2}, "steps_per_print": 100}
    engine = DeepSpeedEngine(model=model_fn, model_parameters=params, config_params=ds_cfg,
                             mesh=mesh, param_shardings=shardings)

    rng = np.random.default_rng(0)
    data_spec = NamedSharding(mesh, P(None, "data"))
    # overfit one fixed batch: loss must drop (random fresh tokens would be irreducible)
    toks = rng.integers(0, cfg.vocab_size, size=(M, 8, 16)).astype(np.int32)
    labels = np.roll(toks, -1, axis=2)
    toks = jax.device_put(jnp.asarray(toks), data_spec)
    labels = jax.device_put(jnp.asarray(labels), data_spec)
    losses = []
    for step in range(8):
        loss = engine(toks, labels)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    assert engine.global_steps == 8, "every call must fire an optimizer update"
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], f"{losses}"
    # stacked block weights keep pipe sharding through the update
    assert not engine.master_params["stages"]["attn"]["c_attn_w"].sharding.is_fully_replicated


def test_per_rank_param_bytes_scale_with_stages():
    """VERDICT #6: the tied vocab table shards over pipe (vocab-parallel embed/head),
    so per-pipe-rank parameter bytes ∝ 1/S INCLUDING the embedding — no leaf may be
    replicated over pipe except the small ln_f/wpe extras."""
    from deepspeed_tpu.models.gpt2 import GPT2Config
    from deepspeed_tpu.models.gpt2_pipe import GPT2Pipe
    from deepspeed_tpu.parallel.mesh import build_mesh

    cfg = GPT2Config(vocab_size=512, n_layer=4, n_head=2, n_embd=64, n_positions=64)
    S = 4
    mesh = build_mesh(data=2, model=1, pipe=S)
    pipe = GPT2Pipe(cfg, num_stages=S)
    params = pipe.init(jax.random.PRNGKey(0))
    sh = pipe.param_shardings(mesh, params)
    placed = jax.device_put(params, sh)

    total = sum(l.nbytes for l in jax.tree_util.tree_leaves(placed))
    dev0 = mesh.devices.ravel()[0]
    per_dev = 0
    for leaf in jax.tree_util.tree_leaves(placed):
        for s in leaf.addressable_shards:
            if s.device == dev0:
                per_dev += s.data.nbytes
    # replicated-over-pipe extras: wpe [T, E] + ln_f scale/bias
    extras = placed["io"]["wpe"].nbytes + sum(
        l.nbytes for l in jax.tree_util.tree_leaves(placed["io"]["ln_f"]))
    assert per_dev <= total / S + extras + 1024, (per_dev, total / S, extras)
    # and specifically the vocab table is split over pipe
    wte = placed["io"]["wte"]
    shard_rows = {s.data.shape[0] for s in wte.addressable_shards}
    assert shard_rows == {cfg.vocab_size // S}, shard_rows


def test_gpt2_pipe_odd_vocab_matches_dense():
    """A GPT-2-style odd vocab (not divisible by num_stages) must pad the pipe-sharded
    table internally and still produce the DENSE model's exact loss (padded logit
    columns masked out of the vocab-parallel softmax)."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.models.gpt2_pipe import GPT2Pipe
    from deepspeed_tpu.parallel.mesh import build_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = GPT2Config(vocab_size=131, n_positions=32, n_embd=32, n_layer=4, n_head=2,
                     compute_dtype=jnp.float32)
    mesh = build_mesh(data=2, model=1, pipe=4)
    dense = GPT2Model(cfg)
    dense_params = dense.init(jax.random.PRNGKey(3))
    pipe = GPT2Pipe(cfg, num_stages=4)
    params = pipe.from_dense(dense_params)
    assert params["io"]["wte"].shape[0] == 132  # padded to a stage multiple
    placed = jax.device_put(params, pipe.param_shardings(mesh, params))

    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 4, 16)).astype(np.int32)
    labels = np.roll(toks, -1, axis=2)
    spec = NamedSharding(mesh, P(None, "data"))
    toks_d = jax.device_put(jnp.asarray(toks), spec)
    labels_d = jax.device_put(jnp.asarray(labels), spec)
    pipe_loss = float(jax.device_get(pipe.loss(placed, toks_d, labels_d, mesh=mesh)))

    dense_losses = [float(jax.device_get(dense.apply(dense_params, jnp.asarray(toks[m]),
                                                     jnp.asarray(labels[m]))))
                    for m in range(2)]
    np.testing.assert_allclose(pipe_loss, np.mean(dense_losses), rtol=1e-5)


@pytest.mark.parametrize("tp", [1, 2])
def test_gpt2_pipe_to_dense_roundtrip(tp):
    """to_dense must invert _stack exactly — vocab padding stripped, qkv permutation
    undone — so checkpoints can move across (num_stages, tp) topologies."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.models.gpt2_pipe import GPT2Pipe

    cfg = GPT2Config(vocab_size=131, n_positions=32, n_embd=32, n_layer=4, n_head=2,
                     compute_dtype=jnp.float32)
    dense_params = GPT2Model(cfg).init(jax.random.PRNGKey(5))
    pipe = GPT2Pipe(cfg, num_stages=2, tp=tp)
    stacked = pipe.from_dense(dense_params)
    assert stacked["io"]["wte"].shape[0] == 132  # stage-padded inside the stacked tree
    back = pipe.to_dense(stacked)
    assert back["wte"].shape[0] == cfg.vocab_size  # padding stripped on export
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0),
        dense_params, back)
    # and the dense tree reloads onto a DIFFERENT topology
    pipe4 = GPT2Pipe(cfg, num_stages=4)
    restacked = pipe4.from_dense(back)
    assert restacked["io"]["wte"].shape[0] == 132


@pytest.mark.parametrize("streamed", [True, False])
def test_auto_flush_split_matches_single_flush(mesh, streamed):
    """M = 8S must auto-split into rematerialized segments with
    bit-comparable loss AND grads vs the unsplit pipeline — in BOTH the streamed
    (single-fill, default) and the legacy drain-per-flush schedule. The grad check
    covers every segment-boundary micro-batch (the streamed carry's hard case)."""
    from jax.sharding import PartitionSpec as P
    S2, M8 = 2, 16
    key = jax.random.PRNGKey(2)
    per_stage = []
    for _ in range(S2):
        k1, key = jax.random.split(key)
        per_stage.append({"w": jax.random.normal(k1, (H, H)) * 0.3, "b": jnp.zeros((H,))})
    stacked = stack_stage_params(per_stage)
    stacked = jax.device_put(stacked, stacked_param_sharding(mesh, stacked))
    x_mb = jax.random.normal(key, (M8, B, H))
    labels_mb = jnp.tanh(x_mb @ (jax.random.normal(jax.random.PRNGKey(3), (H, H)) * 0.5))

    def last_fn(y, labels_all, mb):
        return jnp.mean((y - labels_all[mb])**2)

    def loss(cap):
        def f(s, x):
            return pipeline_apply(stage_fn, s, x, mesh=mesh, last_stage_fn=last_fn,
                                  last_stage_args=(labels_mb,),
                                  last_stage_args_specs=(P(None, "data"),),
                                  max_microbatches_per_flush=cap,
                                  stream_segments=streamed)
        return f

    l_split = jax.jit(loss(None))(stacked, x_mb)       # default cap 4*S=8 < M: splits
    l_whole = jax.jit(loss(0))(stacked, x_mb)          # splitting disabled
    np.testing.assert_allclose(float(l_split), float(l_whole), rtol=1e-6)

    g_split = jax.jit(jax.grad(loss(None)))(stacked, x_mb)
    g_whole = jax.jit(jax.grad(loss(0)))(stacked, x_mb)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g_split[k]), np.asarray(g_whole[k]),
                                   rtol=1e-5, atol=1e-6)


def test_flush_schedule_accounting():
    """Step accounting: the streamed schedule pays the single (S-1)-step fill once
    (the reference 1F1B discipline, schedule.py:182-289); the legacy schedule pays
    it per flush."""
    from deepspeed_tpu.parallel.pipeline_spmd import flush_schedule

    acc = flush_schedule(M=128, S=8, cap=32, streamed=True)
    assert acc == {"steps": 135, "ideal_steps": 135, "n_segments": 4,
                   "bubble_fraction": acc["bubble_fraction"]}
    assert abs(acc["bubble_fraction"] - (1 - 128 / 135)) < 1e-12
    legacy = flush_schedule(M=128, S=8, cap=32, streamed=False)
    assert legacy["steps"] == 4 * (32 + 7) == 156
    assert legacy["bubble_fraction"] > 0.17 > acc["bubble_fraction"]

    with pytest.raises(AssertionError):
        flush_schedule(M=10, S=2, cap=4)


def _scan_lengths(jaxpr):
    """All (length, has_stage_marker) for scan eqns anywhere in a jaxpr, where the
    marker is whether the scan body applies the stage function (detected via a
    sentinel primitive-free probe: we instead return raw lengths and let the
    caller reason about them)."""
    def as_jaxpr(v):
        # ClosedJaxpr wraps .jaxpr; raw Jaxpr (shard_map/remat bodies) has .eqns
        if hasattr(v, "eqns"):
            return v
        inner = getattr(v, "jaxpr", None)
        return inner if hasattr(inner, "eqns") else None

    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for v in eqn.params.values():
            for w in (v if isinstance(v, (list, tuple)) else [v]):
                j = as_jaxpr(w)
                if j is not None:
                    out.extend(_scan_lengths(j))
    return out


def test_streamed_executes_single_fill_step_count(mesh):
    """The TRACED streamed program's scan trip counts prove the single-fill
    schedule: an n-segment outer scan whose body runs `cap` pipeline steps, plus
    one (S-1)-step drain — total executed steps == flush_schedule(streamed)
    == M + S - 1, NOT the legacy n*(cap+S-1). A regression that drains per
    segment would show an inner length of cap+S-1 (or an extra S-1 scan per
    segment) and fail the exact-multiset assertion."""
    from deepspeed_tpu.parallel.pipeline_spmd import flush_schedule
    S2, M8, cap = 2, 16, 8

    key = jax.random.PRNGKey(2)
    per_stage = []
    for _ in range(S2):
        k1, key = jax.random.split(key)
        per_stage.append({"w": jax.random.normal(k1, (H, H)) * 0.3, "b": jnp.zeros((H,))})
    stacked = stack_stage_params(per_stage)
    stacked = jax.device_put(stacked, stacked_param_sharding(mesh, stacked))
    x_mb = jax.random.normal(key, (M8, B, H))

    def last_fn(y, mb):
        return jnp.mean(y)

    def f(s, x):
        return pipeline_apply(stage_fn, s, x, mesh=mesh, last_stage_fn=last_fn,
                              max_microbatches_per_flush=cap)

    lengths = sorted(_scan_lengths(jax.make_jaxpr(f)(stacked, x_mb).jaxpr))
    n = M8 // cap
    # exactly three scans: drain (S-1), segment body (cap), outer segments (n)
    assert lengths == sorted([S2 - 1, cap, n]), lengths
    # executed pipeline steps = n * cap + (S - 1) = the single-fill optimum
    acc = flush_schedule(M=M8, S=S2, cap=cap, streamed=True)
    assert n * cap + (S2 - 1) == acc["steps"] == M8 + S2 - 1

    # the legacy schedule shows its drain in the trip counts: inner flush scans
    # run cap + S - 1 steps each
    def f_legacy(s, x):
        return pipeline_apply(stage_fn, s, x, mesh=mesh, last_stage_fn=last_fn,
                              max_microbatches_per_flush=cap, stream_segments=False)

    legacy_lengths = sorted(_scan_lengths(jax.make_jaxpr(f_legacy)(stacked, x_mb).jaxpr))
    assert cap + S2 - 1 in legacy_lengths, legacy_lengths
    assert n * (cap + S2 - 1) == flush_schedule(M8, S2, cap, streamed=False)["steps"]


def test_auto_flush_split_through_gpt2_pipe(mesh):
    """GPT2Pipe at M = 8S (vocab-parallel embedding/head + collective last stage)
    still matches the dense model under the flush splitter."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.models.gpt2_pipe import GPT2Pipe
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = GPT2Config(vocab_size=64, n_positions=16, n_embd=16, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32)
    dense = GPT2Model(cfg)
    dense_params = dense.init(jax.random.PRNGKey(4))
    pipe = GPT2Pipe(cfg, num_stages=2)
    params = pipe.from_dense(dense_params)
    placed = jax.device_put(params, pipe.param_shardings(mesh, params))

    M8 = 16  # 8 * num_stages -> two flushes of 8
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg.vocab_size, size=(M8, 4, 8)).astype(np.int32)
    labels = np.roll(toks, -1, axis=2)
    spec = NamedSharding(mesh, P(None, "data"))
    toks_d = jax.device_put(jnp.asarray(toks), spec)
    labels_d = jax.device_put(jnp.asarray(labels), spec)
    pipe_loss = float(jax.device_get(pipe.loss(placed, toks_d, labels_d, mesh=mesh)))
    dense_losses = [float(jax.device_get(dense.apply(dense_params, jnp.asarray(toks[m]),
                                                     jnp.asarray(labels[m]))))
                    for m in range(M8)]
    np.testing.assert_allclose(pipe_loss, np.mean(dense_losses), rtol=1e-5)
