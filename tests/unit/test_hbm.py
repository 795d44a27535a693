"""HBM memory observatory tests (docs/hbm.md).

Four layers, mirroring the subsystem's own structure:

* **utils/hlo.py parsers** — ``entry_buffer_table`` (per-leaf entry layout,
  dtype/shape/bytes, donation via aliases + buffer_donor) and
  ``temp_allocation_estimate`` (def-to-last-use liveness over the ENTRY
  computation) on real compiled programs and hand-written fixtures.
* **Attribution + model** — manifest signature classification, the per-class
  MAX across a program set, the closed-form ZeRO predictor, and the
  reconciliation verdicts — including the seeded-misattribution fixture
  proving reconciliation FAILS when the model is wrong.
* **Registry scale** — the full lint-registry sweep reconciles on every
  entry within the pinned tolerance, and its stable projection is
  byte-compared against the committed golden (the same file
  scripts/lint.sh regenerates and diffs in CI).
* **Engine + forecast** — telemetry.hbm emits Memory/* scalars without
  changing one HLO instruction; the round-5 OOM frontier (PERF.md) is
  re-derived offline; the flight recorder's dump carries OOM forensics.

Regenerate the golden with:
    ds-tpu hbm --golden-out tests/unit/golden/hbm_registry_sweep.json
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.utils import hbm
from deepspeed_tpu.utils.hlo import (entry_buffer_table, instruction_count,
                                     optimized_hlo, temp_allocation_estimate)
from simple_model import SimpleModel, random_dataset, simple_config

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "hbm_registry_sweep.json")
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


# ------------------------------------------------------------ device stats
def test_device_memory_stats_none_on_cpu():
    """The single memory_stats read of the package: a dict where the backend
    reports watermarks, None where it doesn't (the CPU CI contract) — never
    an exception, never a half-empty dict."""
    stats = hbm.device_memory_stats()
    if jax.default_backend() == "cpu":
        assert stats is None
    else:
        assert isinstance(stats, dict) and stats


def test_device_memory_stats_swallows_device_errors():
    class _Boom:
        def memory_stats(self):
            raise RuntimeError("no stats here")

    assert hbm.device_memory_stats(_Boom()) is None


# ------------------------------------------------------------- hlo parsers
@pytest.fixture(scope="module")
def donated_program_text():
    """Optimized HLO of a jit with one donated argument — exercises the
    entry-layout split, per-leaf byte accounting, and donation detection."""
    def step(state, batch):
        return state + jnp.dot(batch, batch.T).sum(), jnp.tanh(batch)

    jitted = jax.jit(step, donate_argnums=(0,))
    state = jnp.zeros((), jnp.float32)
    batch = jnp.ones((8, 16), jnp.float32)
    return optimized_hlo(jitted, state, batch)


def test_entry_buffer_table_bytes_and_donation(donated_program_text):
    table = entry_buffer_table(donated_program_text)
    params = table["parameters"]
    assert len(params) == 2
    by_bytes = sorted(p["bytes"] for p in params)
    assert by_bytes == [4, 8 * 16 * 4]
    assert table["parameter_bytes"] == 4 + 8 * 16 * 4
    # the donated f32[] scalar aliases an output; the batch does not
    donated = [p for p in params if p["donated"]]
    assert len(donated) == 1 and donated[0]["bytes"] == 4
    assert table["result_bytes"] >= 4 + 8 * 16 * 4
    assert (table["aliased_result_bytes"]
            + table["unaliased_result_bytes"]) == table["result_bytes"]
    assert table["aliased_result_bytes"] >= 4


def test_entry_buffer_table_fixture_layout():
    text = """
HloModule m, input_output_alias={ {0}: (0, {}, may-alias) }, entry_computation_layout={(f32[4,4]{1,0}, bf16[8]{0})->(f32[4,4]{1,0}, bf16[8]{0})}

ENTRY main {
  p0 = f32[4,4]{1,0} parameter(0)
  p1 = bf16[8]{0} parameter(1)
  t = f32[4,4]{1,0} add(p0, p0)
  ROOT out = (f32[4,4]{1,0}, bf16[8]{0}) tuple(t, p1)
}
"""
    table = entry_buffer_table(text)
    assert table["parameter_bytes"] == 4 * 4 * 4 + 8 * 2
    assert [p["donated"] for p in table["parameters"]] == [True, False]
    assert table["aliased_result_bytes"] == 64
    assert table["unaliased_result_bytes"] == 16


def test_temp_allocation_estimate_liveness():
    """Hand-written ENTRY with a known liveness peak: a and b overlap (128 B)
    before c replaces them — parameters and ROOT are excluded."""
    text = """
HloModule m

ENTRY main {
  p0 = f32[4,4]{1,0} parameter(0)
  a = f32[4,4]{1,0} add(p0, p0)
  b = f32[4,4]{1,0} multiply(%a, %a)
  ROOT c = f32[4,4]{1,0} subtract(%a, %b)
}
"""
    assert temp_allocation_estimate(text) == 128


def test_temp_allocation_estimate_on_compiled(donated_program_text):
    est = temp_allocation_estimate(donated_program_text)
    assert isinstance(est, int) and est >= 0


# ------------------------------------------------- classification + model
def test_manifest_signatures_and_classification(donated_program_text):
    """Classifying a program against a manifest whose class matches the batch
    leaf by (dtype, shape): the 512-byte batch lands in the class, the
    scalar falls through to other."""
    manifest = {"classes": {"params": [jnp.ones((8, 16), jnp.float32)]},
                "geometry": {}}
    sigs, class_bytes = hbm.manifest_signatures(manifest)
    assert class_bytes == {"params": 8 * 16 * 4}
    rep = hbm.classify_program(donated_program_text, sigs)
    assert rep["by_class"].get("params") == 8 * 16 * 4
    assert rep["parameter_bytes"] == 4 + 8 * 16 * 4


def test_attribute_programs_takes_per_class_max():
    reports = [{"by_class": {"params": 100, "grads": 10}},
               {"by_class": {"params": 80, "optimizer": 50}}]
    assert hbm.attribute_programs(reports) == {
        "params": 100, "grads": 10, "optimizer": 50}


def test_modeled_classes_zero2_sharding_fraction():
    """ZeRO-2 over dp=8 with 97% coverage: grads/master/optimizer shard to
    frac = 1 - zsf + zsf/dp per device, params stay replicated (stage < 3)."""
    psi, zsf, dp = 1000, 0.97, 8
    geo = {"kind": "training", "psi": psi, "param_itemsize": 4,
           "grad_itemsize": 4, "dp": dp, "zero_stage": 2,
           "zero_sharded_fraction": zsf,
           "offload": False, "fused": False, "comm_ef_bytes": 0}
    classes = hbm.modeled_classes(geo)
    frac = 1.0 - zsf + zsf / dp
    assert classes["params"] == 4 * psi
    assert classes["grads"] == int(4 * psi * frac)
    assert classes["master"] == int(4 * psi * frac)
    assert classes["optimizer"] == int(8 * psi * frac)
    # stage 1 keeps grads replicated
    geo1 = dict(geo, zero_stage=1)
    assert hbm.modeled_classes(geo1)["grads"] == 4 * psi


def test_reconcile_verdicts():
    classes, ok = hbm.reconcile({"params": 1000, "grads": 0},
                                {"params": 1010, "grads": 500},
                                rel_tol=0.02, abs_tol=16)
    assert ok
    assert classes["params"]["status"] == "ok"
    assert classes["grads"]["status"] == "unobserved"
    classes, ok = hbm.reconcile({"params": 1000}, {"params": 2000},
                                rel_tol=0.02, abs_tol=16)
    assert not ok and classes["params"]["status"] == "drift"


# --------------------------------------------------------- registry sweep
@pytest.fixture(scope="module")
def registry_sweep():
    """The full lint-registry sweep, captured once per module (13 engine
    builds — the same surface scripts/lint.sh gates in CI)."""
    return hbm.sweep_registry()


def test_registry_sweep_reconciles_every_entry(registry_sweep):
    """THE model-accuracy gate: parsed-vs-modeled agree within the pinned
    tolerance on every lint-registry entry, no errors, no drift."""
    assert registry_sweep["errors"] == []
    assert registry_sweep["drift_entries"] == []
    assert registry_sweep["ok"]
    for entry, rep in registry_sweep["entries"].items():
        assert rep["reconciled"], entry
        # every entry attributes SOMETHING: params at minimum
        assert rep["classes"].get("params", {}).get("parsed_bytes", 0) > 0, \
            entry


def test_registry_sweep_matches_golden_bytes(registry_sweep):
    """The stable projection (parsed/modeled bytes + verdicts, no
    XLA-scheduler-dependent watermarks), byte-for-byte against the pinned
    golden scripts/lint.sh regenerates and diffs in CI."""
    text = json.dumps(hbm.stable_projection(registry_sweep), indent=2,
                      sort_keys=True) + "\n"
    with open(GOLDEN) as f:
        golden = f.read()
    assert text == golden, ("hbm sweep drifted from golden (regen via "
                            "ds-tpu hbm --golden-out, see module doc)")


def test_seeded_misattribution_fails_reconciliation(registry_sweep):
    """The negative control: feed the reconciler a WRONG model (psi doubled,
    as if the predictor missed half the parameter tree) and it must flag
    drift — proving the all-ok sweep is a real check, not a tautology."""
    rep = registry_sweep["entries"]["standard"]
    parsed = {c: row["parsed_bytes"] for c, row in rep["classes"].items()}
    wrong_geometry = dict(rep["geometry"])
    wrong_geometry["psi"] = int(wrong_geometry["psi"]) * 2
    wrong_modeled = hbm.modeled_classes(wrong_geometry)
    _, ok = hbm.reconcile(parsed, wrong_modeled)
    assert not ok
    # and the diff gate catches parsed growth the same way
    grown = json.loads(json.dumps(registry_sweep))
    row = grown["entries"]["standard"]["classes"]["params"]
    row["parsed_bytes"] = row["parsed_bytes"] * 10
    diff = hbm.diff_reports(registry_sweep, grown)
    assert not diff["ok"] and any("standard/params" in r
                                  for r in diff["regressions"])


# ----------------------------------------------------------------- forecast
def test_forecast_round5_rederives_oom_frontier():
    """The acceptance headline: every config that OOMed in the round-5 sweep
    (PERF.md) is predicted infeasible, every config that ran is predicted
    feasible, and the winner fits — all offline, no compile, no device."""
    report = hbm.forecast_round5()
    assert report["ok"], report["mismatches"]
    assert report["mismatches"] == []
    cells = {(c["remat"], c["batch"], c["ce_chunk"]): c
             for c in report["cells"]}
    assert len(cells) == len(hbm.ROUND5_SWEEP)
    for remat, batch, chunk, oomed in hbm.ROUND5_SWEEP:
        cell = cells[(remat, batch, chunk)]
        assert cell["predicted_fits"] == (not oomed), cell
    assert cells[hbm.ROUND5_WINNER]["predicted_fits"]


def test_forecast_headroom_and_fitting_deltas():
    cfg = {"model": dict(hbm.ROUND5_MODEL), "remat": "dots+attn",
           "batch_per_device": 8, "seq_len": 1024, "ce_chunk": 128,
           "external_master_shards": hbm.ROUND5_SHARDS, "dp": 1,
           "budget_gib": hbm.ROUND5_BUDGET_GIB}
    f = hbm.forecast(cfg)
    assert not f["fits"] and f["headroom_bytes"] < 0
    deltas = hbm.smallest_fitting_delta(cfg)
    assert deltas, "no single-knob fix found for a near-miss config"
    for d in deltas:
        fixed = json.loads(json.dumps(cfg))
        fixed[d["change"]] = d["value"]
        assert hbm.forecast(fixed)["fits"], d


def test_gpt2_param_count_1p5b():
    assert hbm.gpt2_param_count(**hbm.ROUND5_MODEL) == 1_557_686_400


# ------------------------------------------------------------ engine scale
def test_engine_memory_manifest_classes():
    eng = _build(zero_optimization={"stage": 2})
    manifest = eng.memory_manifest()
    classes = manifest["classes"]
    assert {"params", "grads", "master", "optimizer"} <= set(classes)
    geo = manifest["geometry"]
    assert geo["kind"] == "training" and geo["psi"] > 0
    _, class_bytes = hbm.manifest_signatures(manifest)
    assert all(v > 0 for v in class_bytes.values())


def test_hbm_scalars_ride_end_step(tmp_path):
    eng = _build(telemetry={"enabled": True, "output_path": str(tmp_path),
                            "job_name": "tel", "hbm": {"enabled": True}})
    assert eng.telemetry._memory_class_bytes is not None
    xs, ys = _batch()
    for _ in range(2):
        loss = eng(xs, ys)
        eng.backward(loss)
        eng.step()
    eng.telemetry.close()
    path = os.path.join(str(tmp_path), "tel", "scalars.jsonl")
    scalars = [json.loads(l) for l in open(path)]
    by_tag = {}
    for s in scalars:
        by_tag.setdefault(s["tag"], []).append(s["value"])
    mem_tags = sorted(t for t in by_tag if t.startswith("Memory/"))
    assert "Memory/params_bytes" in mem_tags
    assert "Memory/compiled_temp_peak_bytes" in mem_tags
    assert all(v > 0 for v in by_tag["Memory/params_bytes"])
    # the scalar is the manifest constant: identical every step
    assert len(set(by_tag["Memory/params_bytes"])) == 1


def test_hbm_keeps_step_path_hlo_identical(tmp_path):
    """THE non-perturbation gate: telemetry.hbm only installs host dicts —
    with it on, every program compiles to instruction-identical HLO."""
    model = SimpleModel(HIDDEN)
    engines = []
    for tel in (None, {"enabled": True, "output_path": str(tmp_path),
                       "hbm": {"enabled": True}}):
        over = dict(zero_optimization={"stage": 2})
        if tel:
            over["telemetry"] = tel
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
            config_params=simple_config(**over))
        engines.append(eng)
    eng_off, eng_on = engines
    batch = _batch()
    progs_off = {n: (j, a) for n, j, a, _m in eng_off.lint_programs(batch)}
    progs_on = {n: (j, a) for n, j, a, _m in eng_on.lint_programs(batch)}
    assert sorted(progs_off) == sorted(progs_on)
    for name in sorted(progs_off):
        h_off = optimized_hlo(*progs_off[name][0:1],
                              *progs_off[name][1])
        h_on = optimized_hlo(*progs_on[name][0:1], *progs_on[name][1])
        assert instruction_count(h_off) > 0, name
        assert instruction_count(h_off) == instruction_count(h_on), name


def test_hbm_requires_telemetry():
    with pytest.raises(ValueError, match="telemetry.hbm.enabled requires"):
        _build(telemetry={"hbm": {"enabled": True}})


# ------------------------------------------------------------ OOM forensics
def test_memory_snapshot_and_oom_forensics():
    from deepspeed_tpu.utils.monitor import SummaryMonitor
    from deepspeed_tpu.utils.telemetry import TelemetrySession
    session = TelemetrySession(monitor=SummaryMonitor(enabled=False))
    assert session.memory_snapshot() is None
    cfg = {"model": dict(hbm.ROUND5_MODEL), "remat": "dots+attn",
           "batch_per_device": 8, "seq_len": 1024, "ce_chunk": 128,
           "external_master_shards": hbm.ROUND5_SHARDS, "dp": 1,
           "budget_gib": hbm.ROUND5_BUDGET_GIB}
    session.set_memory_manifest({"params": 400, "optimizer": 1200},
                                geometry={"kind": "training"},
                                forecast_config=cfg)
    snap = session.memory_snapshot()
    assert snap["classes"] == {"params": 400, "optimizer": 1200}
    forensics = hbm.oom_forensics(snap)
    assert [r["class"] for r in forensics["largest_classes"]] == [
        "optimizer", "params"]
    # the registered config OOMs, so forensics names the smallest fixes
    assert forensics["forecast"]["fits"] is False
    assert forensics["fitting_deltas"]
    session.close()


def test_flight_recorder_dump_carries_hbm_block(tmp_path):
    eng = _build(telemetry={"enabled": True, "output_path": str(tmp_path),
                            "hbm": {"enabled": True}},
                 numerics={"enabled": True,
                           "dump_dir": str(tmp_path / "dumps")})
    xs, ys = _batch()
    loss = eng(xs, ys)
    eng.backward(loss)
    eng.step()
    bundle = eng._numerics.recorder.bundle("test")
    assert "hbm" in bundle
    assert bundle["hbm"]["classes"].get("params", 0) > 0
    assert bundle["hbm"]["largest_classes"]
    eng.telemetry.close()


# ------------------------------------------------- mem_unavailable satellite
def test_compile_mem_unavailable_warns_once_per_backend(tmp_path,
                                                        monkeypatch):
    """The fixed silent-except: when compiled.memory_analysis raises, the
    compile record carries mem_unavailable=True and ONE warning names the
    backend — not a silent pass, not a warning storm."""
    import logging

    from deepspeed_tpu.utils import telemetry as tel_mod
    from deepspeed_tpu.utils.logging import logger

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    monkeypatch.setattr(tel_mod, "_mem_unavailable_warned", set())
    real = tel_mod._analyze_compiled

    class _NoMem:
        def __init__(self, compiled):
            self._c = compiled

        def cost_analysis(self):
            return self._c.cost_analysis()

        def memory_analysis(self):
            raise RuntimeError("synthetic backend without memory_analysis")

        def as_text(self):
            return self._c.as_text()

    monkeypatch.setattr(
        tel_mod, "_analyze_compiled",
        lambda compiled, *a, **kw: real(_NoMem(compiled), *a, **kw))
    handler = _Capture()
    logger.addHandler(handler)
    try:
        eng = _build(telemetry={"enabled": True,
                                "output_path": str(tmp_path)})
        xs, ys = _batch()
        loss = eng(xs, ys)
        eng.backward(loss)
        eng.step()
    finally:
        logger.removeHandler(handler)
    recs = [r for prog in eng.telemetry.watchdog.records.values()
            for r in prog.values()]
    assert recs and all(r.mem_unavailable for r in recs)
    assert all(r.argument_bytes == 0 and r.temp_bytes == 0 for r in recs)
    warned = [m for m in records if "memory_analysis is unavailable" in m]
    assert len(warned) == 1 and "'cpu'" in warned[0]
    eng.telemetry.close()


def test_compile_mem_available_on_cpu(tmp_path):
    """The flip side: jax's CPU backend DOES report memory_analysis, so the
    default path records real byte counts with mem_unavailable False."""
    eng = _build(telemetry={"enabled": True, "output_path": str(tmp_path)})
    xs, ys = _batch()
    loss = eng(xs, ys)
    eng.backward(loss)
    eng.step()
    recs = [r for prog in eng.telemetry.watchdog.records.values()
            for r in prog.values()]
    assert recs and all(not r.mem_unavailable for r in recs)
    assert any(r.argument_bytes > 0 for r in recs)
    eng.telemetry.close()
