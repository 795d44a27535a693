"""Resilience-layer tests (docs/resilience.md; PR 13 acceptance).

Covers the commit protocol (manifest checksums refuse torn checkpoints, the
``latest`` pointer is written atomically), async snapshot consistency (a save
issued mid-run restores the state AT the save point, not whatever the engine
mutated afterwards), topology-changing restore (ZeRO-2 dp=4 -> dp=2/dp=8
loss-trajectory parity, bucketed-overlap EF bit-equal continuation + elastic
remap + geometry refusal), flight-recorder-driven auto-resume selection, the
serving warm-restart state round-trip, and HLO-instruction-identity of the
step programs with the resilience block enabled (everything is host-side).
"""

import json
import os

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.checkpoint.checkpointing import (MANIFEST_NAME,
                                                    verify_checkpoint,
                                                    write_latest)
from deepspeed_tpu.resilience import (AsyncCheckpointer, auto_resume,
                                      find_resume_point, restore_server,
                                      save_server)
from deepspeed_tpu.utils.hlo import instructions, optimized_hlo
from simple_model import SimpleModel, random_dataset, simple_config

HIDDEN = 16


def make_engine(cfg, seed=0, hidden=HIDDEN):
    model = SimpleModel(hidden)
    params = model.init(jax.random.PRNGKey(seed))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=cfg)
    return engine


def batches(n, hidden=HIDDEN, seed=0, batch=8):
    """Explicit global batches so engines of DIFFERENT dp sizes consume the
    identical sample stream (each shards the same (batch, hidden) arrays)."""
    rng = np.random.default_rng(seed)
    w = np.random.default_rng(99).normal(size=(hidden, hidden)).astype(
        np.float32) * 0.3
    out = []
    for _ in range(n):
        x = rng.normal(size=(batch, hidden)).astype(np.float32)
        out.append((x, np.tanh(x @ w)))
    return out


def train(engine, bs):
    losses = []
    for x, y in bs:
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    return losses


def trees_equal(a, b, rtol=0.0, atol=0.0):
    la = jax.tree_util.tree_leaves(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


# ------------------------------------------------------- commit protocol
def test_manifest_verifier_detects_torn_checkpoint(tmp_path):
    """Every committed file is checksummed; truncation, bit-rot, and missing
    files are all detected — and load_checkpoint REFUSES the tag."""
    engine = make_engine(simple_config())
    train(engine, batches(2))
    engine.save_checkpoint(str(tmp_path), tag="t")
    ckpt = tmp_path / "t"
    assert verify_checkpoint(str(ckpt)) == (True, "ok")

    shard = ckpt / "zero_pp_rank_0_mp_rank_00_optim_states.npz"
    orig = shard.read_bytes()
    shard.write_bytes(orig[: len(orig) // 2])  # torn write
    ok, reason = verify_checkpoint(str(ckpt))
    assert not ok and "size mismatch" in reason
    engine2 = make_engine(simple_config(), seed=5)
    path, cs = engine2.load_checkpoint(str(tmp_path), tag="t")
    assert path is None and cs == {}  # refused, never loaded

    flipped = bytearray(orig)
    flipped[len(orig) // 2] ^= 0xFF  # bit rot at the original size
    shard.write_bytes(bytes(flipped))
    ok, reason = verify_checkpoint(str(ckpt))
    assert not ok and "checksum mismatch" in reason

    shard.write_bytes(orig)
    assert verify_checkpoint(str(ckpt))[0]
    shard.unlink()
    ok, reason = verify_checkpoint(str(ckpt))
    assert not ok and "missing" in reason

    shard.write_bytes(orig)
    (ckpt / MANIFEST_NAME).unlink()  # pre-resilience checkpoints still load
    ok, reason = verify_checkpoint(str(ckpt))
    assert ok and "legacy" in reason


def test_latest_pointer_write_is_atomic(tmp_path):
    write_latest(str(tmp_path), "step1")
    assert (tmp_path / "latest").read_text() == "step1"
    write_latest(str(tmp_path), "step2")
    assert (tmp_path / "latest").read_text() == "step2"
    # the tmp file used for the atomic replace never survives
    assert [p.name for p in tmp_path.iterdir()] == ["latest"]


def test_tmp_carcass_is_invisible_to_restore(tmp_path):
    """A fully-written but never-renamed <tag>.tmp (death mid-commit) is
    skipped by tag enumeration and auto-resume."""
    engine = make_engine(simple_config())
    train(engine, batches(2))
    engine.save_checkpoint(str(tmp_path), tag="good")
    (tmp_path / "bad.tmp").mkdir()
    (tmp_path / "bad.tmp" / "junk.npz").write_bytes(b"x")
    info = find_resume_point(str(tmp_path))
    assert info is not None and info["tag"] == "good"


# ---------------------------------------------------- async checkpointing
def test_async_save_snapshot_consistency(tmp_path):
    """The snapshot is taken on the caller thread at save(); training three
    MORE steps while the commit thread writes must not leak into the file —
    restore lands bit-equal on the save-point state."""
    bs = batches(6)
    engine = make_engine(simple_config())
    train(engine, bs[:3])
    at_save = jax.device_get(engine.master_params)
    ck = AsyncCheckpointer(engine, str(tmp_path))
    ck.save(tag="step3")
    train(engine, bs[3:])  # overlaps the background commit
    ck.wait()
    assert ck.saves_committed == 1
    assert ck.last_stall_ms >= 0.0

    engine2 = make_engine(simple_config(), seed=7)
    path, _ = engine2.load_checkpoint(str(tmp_path))
    assert path is not None and engine2.global_steps == 3
    trees_equal(at_save, engine2.master_params)


# ------------------------------------------------- topology-changing restore
@pytest.mark.parametrize("dp_new", [2, 8])
def test_zero2_elastic_loss_trajectory_parity(tmp_path, eight_devices, dp_new):
    """Save ZeRO-2 at dp=4, restore at dp=2 / dp=8: the remaining loss
    trajectory matches the uninterrupted dp=4 oracle at pinned rtol."""
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    bs = batches(6)

    def build(dp, seed):
        model = SimpleModel(HIDDEN)
        mesh = build_mesh(data=dp, model=1, pipe=1,
                          devices=eight_devices[:dp])
        return DeepSpeedEngine(
            model=model, model_parameters=model.init(jax.random.PRNGKey(seed)),
            config_params=simple_config(zero_optimization={"stage": 2}),
            mesh=mesh)

    oracle = build(4, seed=0)
    oracle_losses = train(oracle, bs)

    saver = build(4, seed=0)
    train(saver, bs[:3])
    saver.save_checkpoint(str(tmp_path))

    resumed = build(dp_new, seed=31)  # different init: restore must win
    path, _ = resumed.load_checkpoint(str(tmp_path))
    assert path is not None and resumed.dp_size == dp_new
    resumed_losses = train(resumed, bs[3:])
    np.testing.assert_allclose(resumed_losses, oracle_losses[3:],
                               rtol=1e-5, atol=1e-7)
    trees_equal(oracle.master_params, resumed.master_params,
                rtol=1e-5, atol=1e-7)


COMPRESSED = dict(zero_optimization={"stage": 2},
                  comm={"mode": "hierarchical_compressed", "dcn_slices": 2,
                        "compress_start_step": 2,
                        "overlap": {"mode": "bucketed", "bucket_mb": 0.01}})


def _compressed_engine(seed=0, hidden=64, **cfg_overrides):
    cfg = {k: v for k, v in COMPRESSED.items()}
    cfg.update(cfg_overrides)
    return make_engine(simple_config(**cfg), seed=seed, hidden=hidden)


def test_comm_ef_roundtrip_bit_equal_continuation(tmp_path):
    """Bucketed-overlap EF buffers ride the checkpoint: after the compression
    warmup, save -> restore into a fresh engine -> compressed training
    continues BIT-EQUAL to the uninterrupted run (ISSUE satellite: the EF
    residual is part of the optimizer state, losing it is a regression)."""
    bs = batches(9, hidden=64)
    engine = _compressed_engine()
    train(engine, bs[:6])  # past compress_start_step: EF nonzero
    assert np.asarray(engine._comm_we).any()
    engine.save_checkpoint(str(tmp_path))
    uninterrupted = train(engine, bs[6:])

    engine2 = _compressed_engine(seed=13)
    path, _ = engine2.load_checkpoint(str(tmp_path))
    assert path is not None
    resumed = train(engine2, bs[6:])
    assert resumed == uninterrupted  # bit-equal float-for-float


def test_comm_ef_elastic_remap_dp8_to_dp4(tmp_path, eight_devices):
    """EF buffers saved at dp=8 restore into a dp=4 engine: server residual
    carries over by exact permutation and compressed training continues."""
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    bs = batches(8, hidden=64)
    engine = _compressed_engine()
    assert engine.dp_size == 8
    train(engine, bs[:6])
    se_saved = np.asarray(engine._comm_se)
    assert se_saved.any()
    engine.save_checkpoint(str(tmp_path))

    model = SimpleModel(64)
    mesh4 = build_mesh(data=4, model=1, pipe=1, devices=eight_devices[:4])
    engine4 = DeepSpeedEngine(
        model=model, model_parameters=model.init(jax.random.PRNGKey(21)),
        config_params=simple_config(**COMPRESSED), mesh=mesh4)
    assert engine4.dp_size == 4
    path, _ = engine4.load_checkpoint(str(tmp_path))
    assert path is not None
    assert engine4._comm_se.shape[0] == 4
    # the global server residual is preserved exactly: reconstruct it from
    # both layouts bucket by bucket (server remap is a pure permutation)
    from deepspeed_tpu.ops.onebit_adam import OneBitAdam
    se_new = np.asarray(engine4._comm_se)
    L_o = engine._comm_topo.slice_size
    L_n = engine4._comm_topo.slice_size
    o_off = n_off = 0
    for b_old, b_new in zip(engine._overlap_plan, engine4._overlap_plan):
        npad_o, npad_n = b_old["n_pad"], b_new["n_pad"]
        cs_o, cs_n = npad_o // 8, npad_n // 4
        g_o = np.zeros(npad_o, np.float32)
        for d, off in enumerate(OneBitAdam._server_offsets(8, L_o, npad_o)):
            g_o[off:off + cs_o] = se_saved[d, o_off:o_off + cs_o]
        g_n = np.zeros(npad_n, np.float32)
        for d, off in enumerate(OneBitAdam._server_offsets(4, L_n, npad_n)):
            g_n[off:off + cs_n] = se_new[d, n_off:n_off + cs_n]
        keep = min(npad_o, npad_n)
        np.testing.assert_array_equal(g_n[:keep], g_o[:keep])
        o_off += cs_o
        n_off += cs_n
    # and the resized engine keeps training under compression
    resumed = train(engine4, bs[6:])
    assert all(np.isfinite(resumed))


def test_comm_ef_geometry_refusal(tmp_path):
    """A saved EF layout that does not replay under the live bucket plan is
    refused with ValueError — never silently sliced into the wrong chunks."""
    bs = batches(7, hidden=64)
    engine = _compressed_engine()
    train(engine, bs[:6])
    engine.save_checkpoint(str(tmp_path))

    mono = _compressed_engine(
        seed=3, comm={"mode": "hierarchical_compressed", "dcn_slices": 2,
                      "compress_start_step": 2,
                      "overlap": {"mode": "bucketed", "bucket_mb": 64.0}})
    with pytest.raises(ValueError, match="refusing"):
        mono.load_checkpoint(str(tmp_path))


# ------------------------------------------------------------- auto-resume
def test_auto_resume_selection_and_scale_clamp(tmp_path):
    """Newest-before-first-bad-step selection, torn-tag skip, and the
    journaled loss-scale clamp (no overflow-spiral replay)."""
    save_dir = tmp_path / "ckpts"
    dump_dir = tmp_path / "dumps"
    dump_dir.mkdir()
    bs = batches(5)
    engine = make_engine(simple_config(fp16={"enabled": True,
                                             "initial_scale_power": 10}))
    train(engine, bs[:2])
    engine.save_checkpoint(str(save_dir), tag="step2")
    train(engine, bs[2:4])
    engine.save_checkpoint(str(save_dir), tag="step4")

    # no dump: plain warm restart takes the newest commit
    assert find_resume_point(str(save_dir))["tag"] == "step4"

    (dump_dir / "numerics_dump_host0_0.json").write_text(json.dumps(
        {"first_bad_step": 3,
         "loss_scale_trajectory": [[2, 1024.0], [3, 256.0]]}))
    info = find_resume_point(str(save_dir), str(dump_dir))
    assert info["tag"] == "step2" and info["journal_scale"] == 256.0

    engine2 = make_engine(simple_config(fp16={"enabled": True,
                                              "initial_scale_power": 10}),
                          seed=9)
    path, _, info = auto_resume(engine2, str(save_dir), str(dump_dir))
    assert path is not None and engine2.global_steps == 2
    # the checkpoint recorded 1024; the journal had backed off to 256
    assert float(engine2.scaler_state.cur_scale) == 256.0


def test_scan_dump_dir_ignores_torn_dump(tmp_path):
    from deepspeed_tpu.utils.numerics import scan_dump_dir
    assert scan_dump_dir(None) is None
    assert scan_dump_dir(str(tmp_path / "missing")) is None
    (tmp_path / "numerics_dump_host0_0.json").write_text('{"first_bad')
    assert scan_dump_dir(str(tmp_path)) is None  # torn dump never blocks resume
    (tmp_path / "numerics_dump_host0_1.json").write_text(
        '{"first_bad_step": 7}')
    assert scan_dump_dir(str(tmp_path))["first_bad_step"] == 7


# ------------------------------------------------------- serving warm restart
def _server(seed=0, num_blocks=65):
    from deepspeed_tpu.resilience.crash_sim import _make_server
    return _make_server(seed, num_blocks)


def test_serve_state_roundtrip_token_identical(tmp_path):
    """Kill a serving replica mid-schedule, snapshot, restore into a fresh
    engine: the drained outputs are token-identical to the uninterrupted
    oracle and the ledger (allocator order, prefix index) round-trips."""
    from deepspeed_tpu.resilience.crash_sim import _drain, _serve_trace
    from deepspeed_tpu.serve.scheduler import pack_request, unpack_request

    trace = _serve_trace(0)
    oracle = _server(0)
    out, _ = oracle.run([unpack_request(pack_request(r)) for r in trace])
    want = {o.req_id: list(o.tokens) for o in out if o.status == "finished"}

    victim = _server(0)
    for r in trace:
        victim.submit(unpack_request(pack_request(r)))
    for _ in range(4):
        victim.step()
    snap_dir = save_server(victim, str(tmp_path))
    assert verify_checkpoint(snap_dir)[0]

    warm = _server(0)
    assert restore_server(warm, snap_dir)
    # allocator ledger round-trips ORDER-exactly (allocation determinism)
    assert (list(warm.scheduler.allocator._free)
            == list(victim.scheduler.allocator._free))
    assert (list(warm.scheduler.allocator._cached)
            == list(victim.scheduler.allocator._cached))
    _drain(warm)
    got = {rid: list(o.tokens) for rid, o in warm.outputs.items()
           if o.status == "finished"}
    assert got == want


def test_serve_restart_geometry_refusal(tmp_path):
    victim = _server(0)
    for _ in range(2):
        victim.step()
    snap_dir = save_server(victim, str(tmp_path))
    other = _server(0, num_blocks=33)  # different pool: indices meaningless
    with pytest.raises(ValueError, match="geometry"):
        restore_server(other, snap_dir)


def test_serve_torn_snapshot_refused(tmp_path):
    victim = _server(0)
    snap_dir = save_server(victim, str(tmp_path))
    pool = os.path.join(snap_dir, "serve_pool.npz")
    data = open(pool, "rb").read()
    with open(pool, "wb") as f:
        f.write(data[: len(data) // 2])
    fresh = _server(0)
    assert restore_server(fresh, snap_dir) is False  # cold start, not a crash


def test_prefix_chain_key_roundtrip():
    from deepspeed_tpu.serve.prefix_cache import chain_to_key, key_to_chain
    key = (((None, (1, 2, 3)), (4, 5, 6)), (7, 8))
    chain = key_to_chain(key)
    assert chain == [[1, 2, 3], [4, 5, 6], [7, 8]]
    back = chain_to_key(chain)
    assert back == key and hash(back) == hash(key)


# ------------------------------------------------------------ off-switch
def test_resilience_enabled_is_hlo_instruction_identical(tmp_path):
    """The resilience hooks are all host-side: enabling the block leaves the
    compiled step program HLO-instruction-identical (acceptance: the async
    save never enters the graph)."""
    base = make_engine(simple_config(zero_optimization={"stage": 2}))
    res = make_engine(simple_config(
        zero_optimization={"stage": 2},
        resilience={"enabled": True, "save_dir": str(tmp_path),
                    "save_interval": 2}))
    assert res._resilience is not None
    xs, ys = batches(1)[0]
    h1 = optimized_hlo(base._jit_loss_and_grad, base.params,
                       base.scaler_state.cur_scale, xs, ys)
    h2 = optimized_hlo(res._jit_loss_and_grad, res.params,
                       res.scaler_state.cur_scale, xs, ys)
    assert instructions(h1) and instructions(h1) == instructions(h2)
