"""Overlap-centric grad exchange tests (docs/overlap.md).

Covers the ISSUE-11 acceptance contract: deterministic bucket partition at a
given ``comm.overlap.bucket_mb``, bit-equality of the bucketed exchange
against the monolithic exchange across the engine's step paths (two-jit
standard, fused flat and hierarchical, two-jit compressed), the
bucketed error-feedback state layout, and HLO-instruction-identical steps
when ``comm.overlap`` is off.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import CommTopology
from deepspeed_tpu.comm.hierarchical import (bucket_partition, bucket_plan,
                                             bucketed_error_state_shapes,
                                             error_state_shapes)
from deepspeed_tpu.utils.hlo import instructions, optimized_hlo
from simple_model import SimpleModel, random_dataset, simple_config

HIDDEN = 64

# tiny buckets: SimpleModel(64) splits into (b1, b2) / (w1) / (w2); a huge
# bound collapses the whole tree into ONE bucket — the monolithic exchange
# inside the identical bucketed scaffold (the flat GSPMD psum differs by
# reassociation, so monolithic-vs-bucketed comparisons hold the scaffold fixed)
TINY = {"overlap": {"mode": "bucketed", "bucket_mb": 0.01}}
ONE = {"overlap": {"mode": "bucketed", "bucket_mb": 64.0}}


def _build(**overrides):
    model = SimpleModel(HIDDEN)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=simple_config(**overrides))
    return eng


def _batch(seed=0):
    data = random_dataset(8, HIDDEN, seed=seed)
    return np.stack([d[0] for d in data]), np.stack([d[1] for d in data])


def _train(eng, steps, seed=0):
    xs, ys = _batch(seed)
    losses = []
    for _ in range(steps):
        loss = eng(xs, ys)
        eng.backward(loss)
        eng.step()
        losses.append(float(jax.device_get(loss)))
    return losses


# ----------------------------------------------------------- bucket planning
def test_bucket_partition_deterministic_and_covering():
    params = SimpleModel(HIDDEN).init(jax.random.PRNGKey(0))
    # 0.01 MB = 10485 bytes: b1+b2 (512 B) fit one bucket, each 64x64 weight
    # (16384 B) overflows into its own — partition depends on shapes only
    got = bucket_partition(params, int(0.01 * 1024 * 1024))
    leaves = jax.tree_util.tree_leaves(params)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    assert sizes == [64, 64, 4096, 4096]  # b1, b2, w1, w2 (dict order)
    assert got == [[0, 1], [2], [3]]
    assert got == bucket_partition(params, int(0.01 * 1024 * 1024))  # stable
    # every leaf exactly once, in tree order
    assert sorted(sum(got, [])) == list(range(len(leaves)))
    # a bound below the largest leaf still gives it its own (oversized) bucket
    tiny = bucket_partition(params, 16)
    assert tiny == [[0], [1], [2], [3]]
    # a huge bound collapses to one bucket
    assert bucket_partition(params, 1 << 30) == [[0, 1, 2, 3]]


def test_bucket_plan_geometry():
    params = SimpleModel(HIDDEN).init(jax.random.PRNGKey(0))
    plan = bucket_plan(params, int(0.01 * 1024 * 1024), dp=8)
    # n_pad rounds each bucket up to the dp x lane quantum (8 x 128 = 1024):
    # every one of the dp scatter chunks is a whole multiple of the lane width
    assert [(b["leaf_indices"], b["n"], b["n_pad"]) for b in plan] == \
        [((0, 1), 128, 1024), ((2,), 4096, 4096), ((3,), 4096, 4096)]
    for b in plan:
        assert b["n_pad"] % (8 * 128) == 0 and sum(b["sizes"]) == b["n"]
    ragged = bucket_plan({"a": jnp.zeros((5,))}, 1 << 20, dp=8)
    assert ragged[0]["n_pad"] == 1024


def test_bucketed_error_state_shapes_layout():
    params = SimpleModel(HIDDEN).init(jax.random.PRNGKey(0))
    topo = CommTopology(8, 2)
    plan = bucket_plan(params, int(0.01 * 1024 * 1024), dp=8)
    (dp_w, we_cols), (dp_s, se_cols) = bucketed_error_state_shapes(plan, topo)
    assert dp_w == dp_s == 8
    assert we_cols == sum(b["n_pad"] // topo.slice_size for b in plan)
    assert se_cols == sum(b["n_pad"] // 8 for b in plan)
    # a single all-covering bucket reproduces the monolithic layout
    one = bucket_plan(params, 1 << 30, dp=8)
    assert bucketed_error_state_shapes(one, topo) == \
        error_state_shapes(one[0]["n_pad"], topo)


# ------------------------------------------- bit-equality across step paths
def test_bucketed_bit_equal_monolithic_hierarchical_two_jit():
    """Two-jit path, hierarchical topology: the bucketed exchange reassociates
    NOTHING per element (same reduce-scatter/psum/all-gather tree per bucket),
    so grads must be BIT-equal to the monolithic two-level exchange."""
    mono = _build(zero_optimization={"stage": 2},
                  comm={"mode": "hierarchical", "dcn_slices": 2})
    bkt = _build(zero_optimization={"stage": 2},
                 comm=dict({"mode": "hierarchical", "dcn_slices": 2}, **TINY))
    assert len(bkt._overlap_plan) == 3
    xs, ys = _batch()
    bx = mono.shard_batch((xs, ys))
    l1, g1 = mono._jit_loss_and_grad(mono.params, mono.scaler_state.cur_scale,
                                     *bx)
    l2, g2 = bkt._jit_loss_and_grad(bkt.params, bkt.scaler_state.cur_scale,
                                    *bx)
    assert float(l1) == float(l2)
    for k in g1:
        np.testing.assert_array_equal(np.asarray(g1[k]), np.asarray(g2[k]),
                                      err_msg=k)


def test_bucketed_bit_equal_single_bucket_flat_two_jit():
    """Two-jit path, flat topology: tiny buckets vs one all-covering bucket
    (the monolithic exchange in the same shard_map scaffold) are bit-equal."""
    one = _build(zero_optimization={"stage": 2},
                 comm=dict({"mode": "flat"}, **ONE))
    bkt = _build(zero_optimization={"stage": 2},
                 comm=dict({"mode": "flat"}, **TINY))
    assert len(one._overlap_plan) == 1 and len(bkt._overlap_plan) == 3
    xs, ys = _batch()
    bx = one.shard_batch((xs, ys))
    l1, g1 = one._jit_loss_and_grad(one.params, one.scaler_state.cur_scale,
                                    *bx)
    l2, g2 = bkt._jit_loss_and_grad(bkt.params, bkt.scaler_state.cur_scale,
                                    *bx)
    assert float(l1) == float(l2)
    for k in g1:
        np.testing.assert_array_equal(np.asarray(g1[k]), np.asarray(g2[k]),
                                      err_msg=k)


def test_bucketed_bit_equal_fused_standard_path():
    """Fused standard step ({"fused_step": true}): per-step losses bit-equal
    between tiny buckets and the single-bucket monolithic exchange."""
    one = _build(fused_step=True, comm=dict({"mode": "flat"}, **ONE))
    bkt = _build(fused_step=True, comm=dict({"mode": "flat"}, **TINY))
    assert one._run_fused_step is not None
    assert bkt._run_fused_step is not None
    np.testing.assert_array_equal(_train(one, 3), _train(bkt, 3))


def test_bucketed_bit_equal_fused_hierarchical_path():
    """Fused step over the two-level exchange (the one shard_mapped reduction the
    fused program inlines): per-step losses bit-equal between tiny buckets and
    the single-bucket exchange."""
    hier = {"mode": "hierarchical", "dcn_slices": 2}
    one = _build(fused_step=True, zero_optimization={"stage": 2}, comm=dict(hier, **ONE))
    bkt = _build(fused_step=True, zero_optimization={"stage": 2}, comm=dict(hier, **TINY))
    assert one._run_fused_step is not None
    assert bkt._run_fused_step is not None
    np.testing.assert_array_equal(_train(one, 3), _train(bkt, 3))


# ------------------------------------------------ compressed overlap / EF
def test_compressed_overlap_ef_state_layout_and_training():
    """Two-jit compressed path: the engine's persistent EF buffers take the
    bucketed per-bucket layout, stay zero through the uncompressed warmup,
    accumulate once compression starts, and the run keeps training within
    the documented tolerance of the monolithic compressed exchange."""
    mono = _build(zero_optimization={"stage": 2},
                  comm={"mode": "hierarchical_compressed", "dcn_slices": 2,
                        "compress_start_step": 2})
    bkt = _build(zero_optimization={"stage": 2},
                 comm=dict({"mode": "hierarchical_compressed",
                            "dcn_slices": 2, "compress_start_step": 2},
                           **TINY))
    topo = bkt._comm_topo
    plan = bkt._overlap_plan
    assert len(plan) == 3
    (_, we_cols), (_, se_cols) = bucketed_error_state_shapes(plan, topo)
    assert bkt._comm_we.shape == (8, we_cols)
    assert bkt._comm_se.shape == (8, se_cols)
    assert not np.asarray(bkt._comm_we).any()
    l_mono = _train(mono, 12)
    l_bkt = _train(bkt, 12)
    # warmup steps run the UNCOMPRESSED bucketed exchange -> bit-equal to the
    # monolithic hierarchical warmup
    np.testing.assert_array_equal(l_bkt[:2], l_mono[:2])
    # compressed steps: per-bucket RMS scale segments reassociate, so parity
    # is the documented tolerance, and training still converges
    assert max(abs(a - b) for a, b in zip(l_bkt[2:], l_mono[2:])) < 0.1
    assert l_bkt[-1] < l_bkt[0]
    assert np.asarray(bkt._comm_we).any()  # EF residual accumulated
    assert np.asarray(bkt._comm_se).any()


# ---------------------------------------------------- off-switch invariance
def test_overlap_off_is_hlo_instruction_identical():
    """With comm.overlap absent (or mode "off") the compiled two-jit step is
    HLO-instruction-identical to the pre-overlap engine's."""
    base = _build(zero_optimization={"stage": 2},
                  comm={"mode": "hierarchical", "dcn_slices": 2})
    off = _build(zero_optimization={"stage": 2},
                 comm={"mode": "hierarchical", "dcn_slices": 2,
                       "overlap": {"mode": "off"}})
    assert base._overlap_plan is None and off._overlap_plan is None
    xs, ys = _batch()
    h1 = optimized_hlo(base._jit_loss_and_grad, base.params,
                       base.scaler_state.cur_scale, xs, ys)
    h2 = optimized_hlo(off._jit_loss_and_grad, off.params,
                       off.scaler_state.cur_scale, xs, ys)
    assert instructions(h1) and instructions(h1) == instructions(h2)


def test_flat_overlap_falls_back_when_dp_is_one():
    """overlap requires a data-parallel exchange: a dp==1-equivalent setup
    (model too small / no sharded grads) must not crash — the plan is built
    only when the exchange exists (dp > 1 on the 8-device mesh, so here we
    just pin that the engine records a plan exactly when overlap is active)."""
    eng = _build(zero_optimization={"stage": 2},
                 comm=dict({"mode": "flat"}, **TINY))
    assert eng._overlap_plan is not None
    assert all(b["n_pad"] % eng.dp_size == 0 for b in eng._overlap_plan)
