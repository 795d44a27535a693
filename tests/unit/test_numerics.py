"""Numerics observatory tests (docs/numerics.md).

Covers the four pieces and their core guarantees:
  - numerics DISABLED (the default) leaves the compiled step program
    HLO-instruction-identical — the sentinel is a trace-time branch, not a
    runtime one;
  - numerics ENABLED adds no collectives to the step (the per-subtree
    segment-sum replaces the scalar global-norm reduction 1:1) and no host
    sync beyond the loss fetch (enforced statically by test_no_sync_guard.py);
  - overflow is localized to a named parameter subtree;
  - the loss-scale journal replays the device scaler exactly;
  - the cross-rank desync audit runs only on audit steps and flags nothing on
    a healthy replicated run;
  - the flight recorder dumps a parseable post-mortem bundle.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.fp16.fused_optimizer import FP16_Optimizer
from deepspeed_tpu.utils.hlo import (collective_counts, instruction_count,
                                     optimized_hlo)
from deepspeed_tpu.utils.numerics import (FlightRecorder, build_subtree_index,
                                          compare_audit_rows, subtree_name)
from simple_model import SimpleModel, random_dataset, simple_config

HIDDEN = 16


def _build(**overrides):
    model = SimpleModel(HIDDEN)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=simple_config(**overrides))
    return eng


def _batch(n=8, seed=0):
    data = random_dataset(n, HIDDEN, seed=seed)
    return (np.stack([d[0] for d in data]), np.stack([d[1] for d in data]))


def _run_steps(eng, steps, n=8):
    xs, ys = _batch(n)
    for _ in range(steps):
        loss = eng(xs, ys)
        eng.backward(loss)
        eng.step()


def _poison(eng, key="w2"):
    """Overwrite one accumulated-gradient subtree with NaN between backward
    and step — a localized overflow the sentinel must attribute to ``key``."""
    g = dict(eng._grad_acc)
    leaf = g[key]
    g[key] = jax.device_put(jnp.full(leaf.shape, jnp.nan, leaf.dtype), leaf.sharding)
    eng._grad_acc = g


def _apply_update_hlo(eng):
    grads = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, eng._acc_dtype), eng.params)
    step = jnp.asarray(1, jnp.int32)
    hyper = eng.optimizer.current_hyper()
    return optimized_hlo(eng._jit_apply_update, eng.master_params, eng.opt_state,
                         eng.scaler_state, grads, eng.params, step, hyper)


# --------------------------------------------------------------- HLO identity
def test_disabled_step_program_hlo_identical():
    """The numerics block absent and {"enabled": false} must compile the very
    same step program: the sentinel is gated at trace time (a captured Python
    None), so disabled mode cannot perturb what XLA sees."""
    base = _build()
    off = _build(numerics={"enabled": False})
    h_base, h_off = _apply_update_hlo(base), _apply_update_hlo(off)
    assert instruction_count(h_base) == instruction_count(h_off)
    assert collective_counts(h_base) == collective_counts(h_off)


def test_enabled_adds_no_collectives():
    """The per-subtree segment-sum replaces the scalar global-norm reduction
    1:1: turning the sentinel on must not change the step's collective set,
    and must leave the forward/backward program untouched entirely."""
    off = _build()
    on = _build(numerics={"enabled": True})
    assert collective_counts(_apply_update_hlo(off)) == \
        collective_counts(_apply_update_hlo(on))
    xs, ys = _batch()
    fwd_off = optimized_hlo(off._jit_loss_and_grad, off.params,
                            off.scaler_state.cur_scale, xs, ys)
    fwd_on = optimized_hlo(on._jit_loss_and_grad, on.params,
                           on.scaler_state.cur_scale, xs, ys)
    assert instruction_count(fwd_off) == instruction_count(fwd_on)


# --------------------------------------------------------------- sentinel
def test_sentinel_reports_per_subtree_stats():
    eng = _build(numerics={"enabled": True})
    _run_steps(eng, 2)
    rec = eng._numerics.last_record
    assert rec["step"] == 2
    assert sorted(rec["subtrees"]) == ["b1", "b2", "w1", "w2"]
    assert all(v >= 0 for v in rec["grad_norm_per_subtree"])
    assert all(v > 0 for v in rec["weight_norm_per_subtree"])
    assert rec["nonfinite_total"] == 0 and rec["anomaly"] is None
    # derived global norm agrees with the engine's own scalar
    assert np.isclose(rec["grad_norm"],
                      float(jax.device_get(eng._last_grad_norm)), rtol=1e-5)


def test_sentinel_localizes_overflow_to_subtree():
    eng = _build(fp16={"enabled": True, "initial_scale_power": 4},
                 numerics={"enabled": True})
    xs, ys = _batch()
    loss = eng(xs, ys)
    eng.backward(loss)
    _poison(eng, "w2")
    eng.step()
    assert eng.skipped_steps == 1
    rec = eng._numerics.last_record
    assert rec["overflow"] is True
    assert rec["anomaly"]["kind"] == "nonfinite_grad"
    assert rec["anomaly"]["subtree"] == "w2"
    per = dict(zip(rec["subtrees"], rec["nonfinite_per_subtree"]))
    assert per["w2"] > 0
    assert per["w1"] == per["b1"] == per["b2"] == 0


def test_sentinel_works_on_fused_step_path():
    eng = _build(fused_step=True, numerics={"enabled": True})
    _run_steps(eng, 2)
    rec = eng._numerics.last_record
    assert rec["step"] == 2 and sorted(rec["subtrees"]) == ["b1", "b2", "w1", "w2"]
    assert rec["grad_norm"] is not None and rec["grad_norm"] > 0


def test_sentinel_works_on_offload_path():
    cfg = {"zero_optimization": {"stage": 2, "cpu_offload": True},
           "fp16": {"enabled": True, "initial_scale_power": 4},
           "numerics": {"enabled": True}}
    eng = _build(**cfg)
    assert eng._offload is not None
    xs, ys = _batch()
    loss = eng(xs, ys)
    eng.backward(loss)
    _poison(eng, "w2")
    eng.step()
    assert eng.skipped_steps == 1
    rec = eng._numerics.last_record
    assert rec["anomaly"]["subtree"] == "w2"


def test_overflow_dedup_standard_and_offload_agree():
    """Satellite: the three historical overflow checks now share ONE helper
    (runtime/utils.detect_overflow); both engine branches must reach the same
    verdict and the same offending subtree on the same crafted overflow."""
    std = _build(fp16={"enabled": True, "initial_scale_power": 4},
                 numerics={"enabled": True})
    off = _build(zero_optimization={"stage": 2, "cpu_offload": True},
                 fp16={"enabled": True, "initial_scale_power": 4},
                 numerics={"enabled": True})
    xs, ys = _batch()
    for eng in (std, off):
        loss = eng(xs, ys)
        eng.backward(loss)
        _poison(eng, "w1")
        eng.step()
    assert std.skipped_steps == off.skipped_steps == 1
    assert std._numerics.last_record["anomaly"]["subtree"] == "w1"
    assert off._numerics.last_record["anomaly"]["subtree"] == "w1"


def test_fp16_optimizer_overflow_and_journal():
    """Satellite: the standalone FP16_Optimizer shares detect_overflow and
    carries its own loss-scale journal."""
    model = SimpleModel(HIDDEN)
    params = model.init(jax.random.PRNGKey(0))
    opt = FP16_Optimizer(params, optimizer="adam", initial_scale_power=4,
                         hysteresis=1)
    nan_grads = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, jnp.nan, jnp.float32), params)
    opt.step(nan_grads)
    assert opt.overflow is True
    assert opt.journal.cur_scale == opt.cur_scale
    assert [e["kind"] for e in opt.journal.events] == ["backoff", "skip"]


# --------------------------------------------------------------- journal
def test_journal_replays_device_scaler_exactly():
    eng = _build(fp16={"enabled": True, "initial_scale_power": 4,
                       "loss_scale_window": 2, "hysteresis": 1},
                 numerics={"enabled": True})
    xs, ys = _batch()
    for i in range(6):
        loss = eng(xs, ys)
        eng.backward(loss)
        if i in (2, 3):
            _poison(eng, "w1")
        eng.step()
        assert eng._numerics.journal.cur_scale == float(eng.loss_scale()), \
            f"journal desynced from device scaler at step {i}"
    kinds = [e["kind"] for e in eng._numerics.journal.events]
    assert "ramp" in kinds and "backoff" in kinds and "skip" in kinds
    assert "recovered" in kinds  # the clean step after the poisoned streak


def test_journal_min_scale_floor_and_streak():
    from deepspeed_tpu.runtime.fp16.loss_scaler import LossScaleJournal
    j = LossScaleJournal(dynamic=True, init_scale=4.0, scale_window=1000,
                        min_scale=1.0, hysteresis=1)
    for s in range(1, 4):
        j.record(s, True)
    kinds = [e["kind"] for e in j.events]
    assert j.cur_scale == 1.0
    assert "min_scale_floor" in kinds
    assert j.skip_streak == 3
    assert [e["streak"] for e in j.events if e["kind"] == "skip"] == [1, 2, 3]


# --------------------------------------------------------------- desync audit
def test_audit_runs_on_schedule_and_is_clean(tmp_path):
    eng = _build(numerics={"enabled": True, "audit_interval": 2},
                 tensorboard={"enabled": True, "output_path": str(tmp_path),
                              "job_name": "aud"})
    _run_steps(eng, 4)
    num = eng._numerics
    assert num.audit_runs == 2          # steps 2 and 4 only
    assert num.desync is None
    assert num.audit_seconds > 0
    eng.monitor.close()
    events = [json.loads(l) for l in
              open(os.path.join(str(tmp_path), "aud", "events.jsonl"))]
    audits = [e for e in events if e["event"] == "desync_audit"]
    assert len(audits) == 2
    assert all(e["payload"]["divergence"] is None for e in audits)
    assert all(e["payload"]["replicas"] == eng.dp_size for e in audits)


def test_audit_covers_params_and_optimizer_state():
    eng = _build(numerics={"enabled": True, "audit_interval": 1})
    _run_steps(eng, 1)
    assert eng._audit_fn_cached not in (None, False)
    _, names = eng._audit_fn_cached
    assert any(n.startswith("params/") for n in names)
    assert any(n.startswith("opt/") for n in names)


def test_no_audit_collectives_off_schedule():
    """Extra collectives appear ONLY on audit steps: the audit is a separate
    jitted program, never fused into the step."""
    eng = _build(numerics={"enabled": True, "audit_interval": 3})
    _run_steps(eng, 2)
    assert eng._numerics.audit_runs == 0        # not due yet
    assert eng._audit_fn_cached is None         # never even compiled
    _run_steps(eng, 1)
    assert eng._numerics.audit_runs == 1


def test_compare_audit_rows():
    names = ["a", "b", "c"]
    clean = np.asarray([[1, 2, 3], [1, 2, 3]], np.uint32)
    assert compare_audit_rows(clean, names) is None
    bad = np.asarray([[1, 2, 3], [1, 9, 3], [1, 2, 3]], np.uint32)
    d = compare_audit_rows(bad, names)
    assert d["subtree"] == "b" and d["index"] == 1
    assert d["diverging_replicas"] == [1]
    assert compare_audit_rows(np.asarray([[1, 2]], np.uint32), ["a", "b"]) is None


# --------------------------------------------------------------- flight recorder
def test_flight_recorder_ring_is_bounded_and_dumps(tmp_path):
    rec = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
    for s in range(10):
        rec.record_step({"step": s, "overflow": False, "loss_scale": 2.0 ** s,
                         "anomaly": None})
    rec.record_event("loss_scale", {"kind": "ramp"}, step=9)
    assert len(rec.steps) == 4                      # ring stayed bounded
    assert rec.steps[0]["step"] == 6
    rec.note_anomaly()
    path = rec.trigger("test_reason", {"why": "unit test"})
    assert path and os.path.exists(path)
    bundle = json.load(open(path))
    assert bundle["reason"] == "test_reason"
    assert bundle["loss_scale_trajectory"][-1] == [9, 2.0 ** 9]
    assert [s["step"] for s in bundle["steps"]] == [6, 7, 8, 9]
    assert bundle["events"][0]["event"] == "loss_scale"


def test_a_dump_holds_no_alerts_block(tmp_path):
    """The alert plane is gone: a session that still answers ``alerts_snapshot`` (an older
    caller's object) adds nothing to a dump, and the dump's keys are the ones listed here."""
    class Session:
        def memory_snapshot(self):
            return None

        def alerts_snapshot(self):
            return {"rules": [], "fired": [], "ring": {}}

    rec = FlightRecorder(capacity=4, dump_dir=str(tmp_path), telemetry=Session())
    rec.record_step({"step": 1, "overflow": False, "anomaly": None})
    bundle = rec.bundle("r", None)
    assert bundle["reason"] == "r" and [s["step"] for s in bundle["steps"]] == [1]
    assert set(bundle) - {"run", "span"} == {
        "version", "reason", "detail", "host", "time", "first_bad_step", "offending_subtree",
        "loss_scale_trajectory", "steps", "events", "compile_records"}


def test_flight_recorder_first_bad_step(tmp_path):
    rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path))
    rec.record_step({"step": 1, "overflow": False, "anomaly": None})
    rec.record_step({"step": 2, "overflow": True,
                     "anomaly": {"kind": "nonfinite_grad", "subtree": "w2"}})
    rec.record_step({"step": 3, "overflow": True,
                     "anomaly": {"kind": "nonfinite_grad", "subtree": "w2"}})
    bad = rec.first_bad_step()
    assert bad["step"] == 2
    bundle = rec.bundle("r", None)
    assert bundle["first_bad_step"] == 2
    assert bundle["offending_subtree"] == "w2"


def test_consecutive_skip_streak_triggers_dump(tmp_path):
    eng = _build(fp16={"enabled": True, "initial_scale_power": 4},
                 numerics={"enabled": True, "consecutive_skip_trigger": 2,
                           "dump_dir": str(tmp_path)})
    xs, ys = _batch()
    for _ in range(2):
        loss = eng(xs, ys)
        eng.backward(loss)
        _poison(eng, "b1")
        eng.step()
    rec = eng._numerics.recorder
    assert rec.dump_count == 1
    bundle = json.load(open(rec.last_dump_path))
    assert bundle["reason"] == "consecutive_overflow_skips"
    assert bundle["offending_subtree"] == "b1"


# --------------------------------------------------------------- helpers
def test_build_subtree_index_and_names():
    tree = {"w1": jnp.ones((2, 2)), "blk": {"a": jnp.ones((3,)), "b": jnp.ones((3,))}}
    idx = build_subtree_index(tree, depth=1)
    assert sorted(idx.names) == ["blk", "w1"]
    assert idx.n == 2
    assert len(idx.leaf_buckets) == 3   # one entry per leaf


def test_subtree_name_depths():
    tree = {"blk": {"a": jnp.ones((3,))}}
    (path, _), = jax.tree_util.tree_flatten_with_path(tree)[0]
    assert subtree_name(path, 1) == "blk"
    assert subtree_name(path, 2) == "blk/a"
    assert subtree_name((), 1) == "<root>"
