"""Hierarchical comm subsystem tests (docs/multislice.md).

Covers the topology factorization, the two-level schedules' numerics contract
(bit-equality on integer-valued data where every partial sum is exact,
tolerance parity on real training — the reduction is reassociated, not
changed), the ISSUE-8 acceptance gates (>= 20-step loss parity, >= 8x
cross-slice byte reduction HLO-pinned via the per-level wire-byte ledger,
clean per-level desync audit on the 2x4-factorized mesh), and the replica-
group parser / ICI-DCN classifier the ledger is built on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.comm import CommTopology, derive_num_slices, derive_topology
from deepspeed_tpu.comm.hierarchical import (error_state_shapes,
                                             two_level_allreduce,
                                             two_level_compressed_allreduce)
from deepspeed_tpu.parallel.mesh import DATA_AXIS, build_mesh
from deepspeed_tpu.utils.hlo import (collective_axis_breakdown,
                                     collective_axis_bytes,
                                     collective_bytes, optimized_hlo,
                                     parse_replica_groups)
from deepspeed_tpu.utils.numerics import compare_audit_rows
from simple_model import SimpleModel, random_dataset, simple_config

HIDDEN = 16


# ------------------------------------------------------------------- topology
def test_derive_num_slices_rules():
    # explicit request wins and must divide dp
    assert derive_num_slices(8, 4) == 4
    with pytest.raises(ValueError, match="does not divide"):
        derive_num_slices(8, 3)
    # auto: one slice per process when the processes tile the axis
    assert derive_num_slices(8, 0, process_count=2) == 2
    assert derive_num_slices(6, 0, process_count=3) == 3
    assert derive_num_slices(6, 0, process_count=4) == 1  # 4 does not tile 6
    # auto single-process: the canonical 8-device test mesh is virtually 2x4
    assert derive_num_slices(8, 0, process_count=1) == 2
    assert derive_num_slices(4, 0, process_count=1) == 1
    assert derive_topology(8, 0, process_count=1) == CommTopology(8, 2)


def test_topology_groups_and_positions():
    t = CommTopology(8, 2)
    assert (t.dp, t.num_slices, t.slice_size) == (8, 2, 4)
    assert t.is_hierarchical
    assert t.ici_groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert t.dcn_groups == [[0, 4], [1, 5], [2, 6], [3, 7]]
    assert t.slice_rows == t.ici_groups
    assert [t.slice_of(r) for r in range(8)] == [0, 0, 0, 0, 1, 1, 1, 1]
    # every device appears exactly once per level
    assert sorted(sum(t.ici_groups, [])) == list(range(8))
    assert sorted(sum(t.dcn_groups, [])) == list(range(8))
    # degenerate single slice: flat
    flat = CommTopology(8, 1)
    assert not flat.is_hierarchical and flat.ici_groups == [list(range(8))]
    with pytest.raises(ValueError, match="not divisible"):
        CommTopology(8, 3)


def test_slice_device_sets_include_model_fiber(eight_devices):
    # pure-dp mesh: slices are contiguous device halves
    mesh = build_mesh(data=8)
    t = CommTopology(8, 2)
    sets = t.slice_device_sets(mesh)
    assert sets == [frozenset(range(4)), frozenset(range(4, 8))]
    # dp=4 x model=2: each data rank's whole model fiber joins its slice, so
    # model-axis collectives inside one data shard classify as ICI
    mesh2 = build_mesh(data=4, model=2)
    t2 = CommTopology(4, 2)
    sets2 = t2.slice_device_sets(mesh2)
    assert len(sets2) == 2 and sets2[0] | sets2[1] == set(range(8))
    assert sets2[0].isdisjoint(sets2[1])
    flat_dev = [d.id for d in np.asarray(mesh2.devices).reshape(4, 2)[:2].ravel()]
    assert sets2[0] == frozenset(flat_dev)


def test_error_state_shapes():
    assert error_state_shapes(1024, CommTopology(8, 2)) == ((8, 256), (8, 128))
    # flat slice_size == 1 keeps the historical (dp, n) worker layout
    assert error_state_shapes(1024, CommTopology(8, 8)) == ((8, 1024), (8, 128))


# ------------------------------------------------------------------ numerics
def test_two_level_mean_bit_equal_flat_on_integer_data(eight_devices):
    """On integer-valued data every partial sum is exact, so the reassociated
    two-level mean must be BIT-equal to the flat mean (the generic-fp32 case
    is tolerance-only by design — reassociation changes rounding)."""
    mesh = build_mesh(data=8)
    topo = CommTopology(8, 2)
    rng = np.random.default_rng(0)
    rows = rng.integers(-512, 512, size=(8, 4096)).astype(np.float32)
    x = jax.device_put(rows, NamedSharding(mesh, P(DATA_AXIS, None)))
    hier = np.asarray(jax.jit(
        lambda v: two_level_allreduce(mesh, v, topo))(x))
    flat = rows.mean(axis=0, dtype=np.float32)
    np.testing.assert_array_equal(hier, flat)


def test_compressed_allreduce_flat_topology_matches_historical(eight_devices):
    """slice_size == 1 (every device its own slice) must reproduce the flat
    compressed_allreduce's math and EF layout exactly — same inputs, same
    output, same residuals."""
    from deepspeed_tpu.runtime.custom_collectives import compressed_allreduce
    mesh = build_mesh(data=8)
    topo = CommTopology(8, 8)
    n = 1024
    rng = np.random.default_rng(1)
    rows = rng.normal(size=(8, n)).astype(np.float32)
    sh = NamedSharding(mesh, P(DATA_AXIS, None))
    x = jax.device_put(rows, sh)
    we = jax.device_put(np.zeros((8, n), np.float32), sh)
    se = jax.device_put(np.zeros((8, n // 8), np.float32), sh)
    out_h, we_h, se_h = two_level_compressed_allreduce(mesh, x, we, se, topo)
    out_f, we_f, se_f = compressed_allreduce(mesh, x, we, se)
    np.testing.assert_array_equal(np.asarray(out_h), np.asarray(out_f))
    np.testing.assert_array_equal(np.asarray(we_h), np.asarray(we_f))
    np.testing.assert_array_equal(np.asarray(se_h), np.asarray(se_f))


# ------------------------------------------------------- engine loss parity
def _build(**overrides):
    model = SimpleModel(HIDDEN)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=simple_config(**overrides))
    return eng


def _train(eng, steps, seed=0):
    data = random_dataset(8, HIDDEN, seed=seed)
    xs = np.stack([d[0] for d in data])
    ys = np.stack([d[1] for d in data])
    losses = []
    for _ in range(steps):
        loss = eng(xs, ys)
        eng.backward(loss)
        eng.step()
        losses.append(float(jax.device_get(loss)))
    return losses


def test_hierarchical_loss_parity_20_steps():
    """ISSUE-8 acceptance: training loss parity flat vs hierarchical over
    >= 20 steps on the 2x4-factorized mesh (same mean, reassociated — the
    documented tolerance, not bits)."""
    flat = _train(_build(zero_optimization={"stage": 2}), 21)
    hier = _train(_build(zero_optimization={"stage": 2},
                         comm={"mode": "hierarchical"}), 21)
    np.testing.assert_allclose(hier, flat, rtol=2e-3, atol=2e-4)
    assert flat[-1] < flat[0]  # both actually trained
    assert hier[-1] < hier[0]


def test_compressed_warmup_bit_equal_then_documented_tolerance():
    """hierarchical_compressed: steps before comm.compress_start_step run the
    UNCOMPRESSED hierarchical program (bit-equal losses); compressed steps
    stay within the documented 1-bit tolerance and keep training. The
    engine-held EF residuals become nonzero exactly at the phase switch."""
    hier = _build(zero_optimization={"stage": 2}, comm={"mode": "hierarchical"})
    comp = _build(zero_optimization={"stage": 2},
                  comm={"mode": "hierarchical_compressed",
                        "compress_start_step": 3})
    assert np.asarray(comp._comm_we).any() == False  # noqa: E712 — zero-init
    l_hier = _train(hier, 21)
    l_comp = _train(comp, 21)
    np.testing.assert_array_equal(l_comp[:3], l_hier[:3])  # warmup: same program
    assert max(abs(a - b) for a, b in zip(l_comp[3:], l_hier[3:])) < 0.1
    assert l_comp[-1] < l_comp[0]
    assert np.asarray(comp._comm_we).any()  # EF residual accumulated
    assert np.asarray(comp._comm_se).any()


# -------------------------------------------------------- per-level desync
def test_compare_audit_rows_classifies_levels():
    names = ["w1", "w2"]
    rows = CommTopology(4, 2).slice_rows
    clean = [[7, 9]] * 4
    assert compare_audit_rows(clean, names, slice_rows=rows) is None
    # slices internally consistent but disagreeing -> the DCN hop is the culprit
    cross = [[7, 9], [7, 9], [8, 9], [8, 9]]
    div = compare_audit_rows(cross, names, slice_rows=rows)
    assert div["subtree"] == "w1" and div["level"] == "cross_slice"
    assert div["diverging_slices"] == [1]
    assert div["diverging_replicas"] == [2, 3]
    # a slice disagreeing with itself -> ICI exchange / local compute
    intra = [[7, 9], [6, 9], [7, 9], [7, 9]]
    div = compare_audit_rows(intra, names, slice_rows=rows)
    assert div["level"] == "intra_slice"
    # without a topology there is no level classification
    div = compare_audit_rows(cross, names)
    assert "level" not in div and div["diverging_replicas"] == [2, 3]


def test_desync_audit_clean_on_factorized_mesh():
    """ISSUE-8 acceptance: the per-level audit runs against the hierarchical
    engine's replicated state and flags nothing on a healthy run."""
    eng = _build(zero_optimization={"stage": 2},
                 comm={"mode": "hierarchical_compressed"},
                 numerics={"enabled": True, "audit_interval": 2})
    _train(eng, 4)
    assert eng._comm_topo.is_hierarchical
    assert eng._numerics.audit_runs == 2
    assert eng._numerics.desync is None


# ------------------------------------------------- HLO wire-byte acceptance
def test_dcn_byte_reduction_hlo_pinned():
    """ISSUE-8 acceptance: compiled hierarchical_compressed step shows >= 8x
    fewer cross-slice bytes than the flat fp32 exchange, measured on the
    per-axis wire-byte ledger over the engines' own grad programs. hidden=64
    (not the parity tests' 16): the toy-16 model sits entirely under ZeRO's
    min-size sharding floor and its step compiles with no collectives at all —
    there would be nothing to measure."""
    hidden = 64

    def build(**overrides):
        model = SimpleModel(hidden)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
            config_params=simple_config(**overrides))
        return eng

    flat_eng = build(zero_optimization={"stage": 2})
    comp_eng = build(zero_optimization={"stage": 2},
                     comm={"mode": "hierarchical_compressed"})
    topo = comp_eng._comm_topo
    slice_sets = topo.slice_device_sets(comp_eng.mesh)
    data = random_dataset(8, hidden, seed=0)
    xs = np.stack([d[0] for d in data])
    ys = np.stack([d[1] for d in data])

    flat_txt = optimized_hlo(flat_eng._jit_loss_and_grad, flat_eng.params,
                             flat_eng.scaler_state.cur_scale, xs, ys)
    comp_txt = optimized_hlo(comp_eng._jit_loss_and_grad_comm, comp_eng.params,
                             comp_eng.scaler_state.cur_scale,
                             comp_eng._comm_we, comp_eng._comm_se, xs, ys)
    flat_ax = collective_axis_bytes(flat_txt, slice_sets)
    comp_ax = collective_axis_bytes(comp_txt, slice_sets)
    assert flat_ax["dcn"] > 0
    assert comp_ax["dcn"] > 0
    reduction = flat_ax["dcn"] / comp_ax["dcn"]
    assert reduction >= 8.0, (
        f"cross-slice bytes reduced only {reduction:.1f}x "
        f"(flat {flat_ax}, compressed {comp_ax})")
    # the two buckets always sum exactly to the unclassified total
    assert flat_ax["ici"] + flat_ax["dcn"] == collective_bytes(flat_txt)
    assert comp_ax["ici"] + comp_ax["dcn"] == collective_bytes(comp_txt)


def test_axis_breakdown_sums_match_axis_bytes(eight_devices):
    mesh = build_mesh(data=8)
    topo = CommTopology(8, 2)
    x = jax.device_put(np.ones((8, 4096), np.float32),
                       NamedSharding(mesh, P(DATA_AXIS, None)))
    txt = optimized_hlo(jax.jit(lambda v: two_level_allreduce(mesh, v, topo)), x)
    sets = topo.slice_device_sets(mesh)
    ax = collective_axis_bytes(txt, sets)
    br = collective_axis_breakdown(txt, sets)
    for lvl in ("ici", "dcn"):
        assert sum(ops[lvl]["bytes"] for ops in br.values()) == ax[lvl]
    assert sum(ops["ici"]["count"] + ops["dcn"]["count"]
               for ops in br.values()) >= 2


# ----------------------------------------------------- replica-group parser
def test_parse_replica_groups_forms():
    # explicit groups
    assert parse_replica_groups(
        "x = f32[4] all-reduce(y), replica_groups={{0,1},{2,3}}") \
        == [(0, 1), (2, 3)]
    # iota form with transpose: [2,4]<=[4,2]T(1,0) -> columns become rows
    got = parse_replica_groups(
        "x = f32[4] all-gather(y), replica_groups=[2,4]<=[4,2]T(1,0)")
    assert got == [(0, 2, 4, 6), (1, 3, 5, 7)]
    # iota without transpose
    assert parse_replica_groups(
        "x = f32[4] all-gather(y), replica_groups=[2,2]<=[4]") \
        == [(0, 1), (2, 3)]
    # empty grouping and no grouping both mean "all devices, one group"
    assert parse_replica_groups(
        "x = f32[4] all-reduce(y), replica_groups={}") is None
    assert parse_replica_groups("x = f32[4] all-reduce(y)") is None
    # collective-permute names pairs instead
    assert parse_replica_groups(
        "x = f32[4] collective-permute(y), source_target_pairs={{0,1},{1,0}}") \
        == [(0, 1), (1, 0)]


# --------------------------------------------------------------- comm-sim
def test_comm_sim_report_passes_manifest():
    """The comm-sim gate (scripts/lint.sh) holds on the shipped schedule and
    its JSON rendering is deterministic and parseable."""
    import json as _json
    from deepspeed_tpu.comm.sim import MIN_DCN_REDUCTION, build_report, render
    report = build_report(num_slices=2)
    assert report["ok"], report["violations"]
    assert report["dcn_reduction_vs_flat"] >= MIN_DCN_REDUCTION
    assert report["mesh"]["num_slices"] == 2
    text = render(report)
    assert text.endswith("\n") and _json.loads(text) == _json.loads(text)
    assert render(report) == text
