"""Step-time anatomy tests (docs/anatomy.md).

Three layers, mirroring the subsystem's own structure:

* **roofline.py** — the chip-spec table and floor arithmetic, pure math.
* **anatomy.analyze_program** — overlap windows, exposure, level split, and
  the named zero-overlap opportunities on hand-written HLO fixtures (the CPU
  backend emits only synchronous collectives, so the async forms are
  exercised on fixtures exactly like test_hlo_parsers.py).
* **Engine scale** — the anatomy rides the telemetry watchdog without
  changing a single HLO instruction; the flat-vs-hierarchical comparison
  shows strictly less exposed DCN for both two-level modes (golden-pinned,
  the byte-stable file scripts/lint.sh diffs); ZeRO grad collectives are
  flagged zero-overlap; and the roofline invariant holds against measured
  step time (floor <= measured, ceiling >= measured MFU).

Regenerate the golden with:
    ds-tpu anatomy --entry standard --entry comm_hierarchical \
        --entry comm_compressed --entry comm_overlap \
        --entry comm_overlap_compressed \
        --comm-compare-out tests/unit/golden/anatomy_comm_compare.json
"""

import json
import os

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.utils import anatomy
from deepspeed_tpu.utils.hlo import instruction_count, optimized_hlo
from deepspeed_tpu.utils.roofline import (CHIP_SPECS, ChipSpec, resolve_spec,
                                          roofline)
from simple_model import SimpleModel, random_dataset, simple_config

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "anatomy_comm_compare.json")

SLICE_SETS = [frozenset(range(0, 4)), frozenset(range(4, 8))]
SPEC = resolve_spec("cpu-test")


# ----------------------------------------------------------------- roofline
def test_resolve_spec_table_and_overrides():
    spec = resolve_spec("tpu-v5e")
    assert spec.peak_tflops == CHIP_SPECS["tpu-v5e"].peak_tflops
    over = resolve_spec("tpu-v5e", hbm_gbps=1000.0)
    assert over.hbm_gbps == 1000.0
    assert over.peak_tflops == spec.peak_tflops  # 0 keeps the table value
    with pytest.raises(ValueError, match="unknown chip"):
        resolve_spec("tpu-v9000")


@pytest.mark.parametrize("platform,kind,expect", [
    ("cpu", "cpu", "cpu-test"),
    ("tpu", "TPU v5 lite", "tpu-v5e"),       # the string a v5e chip reports (PR 21)
    ("tpu", "TPU v6 lite", "tpu-v6e"),
    ("tpu", "TPU v9000", None),
    ("gpu", "NVIDIA H100", None),
])
def test_detect_chip_knows_the_device_or_raises(monkeypatch, platform, kind, expect):
    """``cpu-test`` is for the CPU alone: an accelerator the table does not know
    is an error, not a default that prices a roofline off made-up rates."""
    from types import SimpleNamespace
    from deepspeed_tpu.utils.roofline import detect_chip
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [SimpleNamespace(platform=platform, device_kind=kind)])
    if expect is None:
        with pytest.raises(ValueError, match="no chip spec"):
            detect_chip()
    else:
        assert detect_chip() == expect


def test_roofline_floor_and_ceiling_arithmetic():
    spec = ChipSpec("t", peak_tflops=1.0, hbm_gbps=1.0, ici_gbps=1.0,
                    dcn_gbps=1.0)
    # 1e12 flops at 1 TFLOP/s = 1 s compute; 5e8 bytes at 1 GB/s = 0.5 s HBM
    rf = roofline(1e12, 5e8, exposed_ici_s=0.25, exposed_dcn_s=0.25, spec=spec)
    assert rf["compute_floor_s"] == pytest.approx(1.0)
    assert rf["hbm_floor_s"] == pytest.approx(0.5)
    # floor = binding bound (compute) + exposed comm
    assert rf["predicted_floor_s"] == pytest.approx(1.5)
    assert rf["mfu_ceiling"] == pytest.approx(1.0 / 1.5)
    # attribution against a measured time
    rf = roofline(1e12, 5e8, 0.25, 0.25, spec, measured_seconds=2.0)
    assert rf["hbm_bound_s"] == pytest.approx(0.0)   # compute binds, not HBM
    assert rf["host_gap_s"] == pytest.approx(0.5)


def test_roofline_hbm_bound_program():
    spec = ChipSpec("t", peak_tflops=1.0, hbm_gbps=1.0, ici_gbps=1.0,
                    dcn_gbps=1.0)
    rf = roofline(1e10, 2e9, 0.0, 0.0, spec, measured_seconds=3.0)
    assert rf["hbm_floor_s"] == pytest.approx(2.0)
    assert rf["compute_s"] == pytest.approx(0.01)
    assert rf["hbm_bound_s"] == pytest.approx(2.0 - 0.01)
    assert rf["host_gap_s"] == pytest.approx(1.0)


# ---------------------------------------------------------- analyze_program
# async all-reduce with a fat annotated dot inside the window: the window
# hides part (not all) of the wire time
PARTIAL_OVERLAP = """
HloModule m

ENTRY main {
  p0 = f32[262144]{0} parameter(0)
  a = f32[64,64]{1,0} parameter(1)
  b = f32[64,64]{1,0} parameter(2)
  ars = f32[262144]{0} all-reduce-start(p0), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=add
  d = f32[64,64]{1,0} dot(f32[64,64]{1,0} a, f32[64,64]{1,0} b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ard = f32[262144]{0} all-reduce-done(f32[262144]{0} ars)
  ROOT out = f32[64,64]{1,0} add(d, d)
}
"""

# same collective, nothing scheduled in the window: async but zero overlap
EMPTY_WINDOW = """
HloModule m

ENTRY main {
  p0 = f32[262144]{0} parameter(0)
  ars = f32[262144]{0} all-reduce-start(p0), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=add
  ROOT ard = f32[262144]{0} all-reduce-done(f32[262144]{0} ars)
}
"""

SYNC_ONLY = """
HloModule m

ENTRY main {
  p0 = f32[1024]{0} parameter(0)
  ar = f32[1024]{0} all-reduce(p0), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=add
  ROOT out = f32[1024]{0} add(ar, ar)
}
"""


def test_async_window_partially_hides_the_wire():
    r = anatomy.analyze_program(PARTIAL_OVERLAP, 1e6, 1e5, SPEC,
                                slice_sets=SLICE_SETS, name="p")
    (row,) = r["collectives"]
    assert row["async"] and not row["zero_overlap"]
    assert row["level"] == "ici"  # both groups stay inside one slice
    assert 0 < row["overlap_s"] < row["comm_s"]
    assert row["exposed_s"] == pytest.approx(row["comm_s"] - row["overlap_s"])
    assert r["exposed_s"]["ici"] == pytest.approx(row["exposed_s"])
    assert r["exposed_s"]["dcn"] == 0.0


def test_empty_async_window_is_zero_overlap_and_cross_slice():
    r = anatomy.analyze_program(EMPTY_WINDOW, 0, 0, SPEC,
                                slice_sets=SLICE_SETS, name="e")
    (row,) = r["collectives"]
    assert row["async"] and row["zero_overlap"]
    assert row["level"] == "dcn"  # the one group spans both slices
    assert row["overlap_s"] == 0.0
    assert row["exposed_s"] == pytest.approx(row["comm_s"])


def test_sync_collective_is_fully_exposed():
    r = anatomy.analyze_program(SYNC_ONLY, 0, 0, SPEC,
                                slice_sets=SLICE_SETS, name="s")
    (row,) = r["collectives"]
    assert not row["async"] and row["zero_overlap"]
    assert row["exposed_s"] == pytest.approx(row["comm_s"]) and row["comm_s"] > 0


def test_no_slice_factorization_means_no_dcn():
    r = anatomy.analyze_program(SYNC_ONLY, 0, 0, SPEC, slice_sets=None,
                                name="s")
    assert r["exposed_s"]["dcn"] == 0.0
    assert r["exposed_s"]["ici"] > 0.0


# two-bucket grad exchange in the scheduled (synchronous) form the CPU
# backend emits: each bucket's producer -> reduce-scatter (ici) -> all-reduce
# (dcn) -> all-gather (ici) chain carries the ds_grad_bucket{k} scope, with a
# compute instruction inside each bucket's issue window and an untagged loss
# all-reduce that must keep the fully-exposed sync pricing
BUCKETED_SYNC = """
HloModule m

ENTRY main {
  p0 = f32[1024]{0} parameter(0)
  prod0 = f32[1024]{0} negate(p0), metadata={op_name="jit(f)/ds_grad_bucket0/pad"}
  rs0 = f32[256]{0} reduce-scatter(prod0), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, to_apply=add, metadata={op_name="jit(f)/ds_grad_bucket0/reduce_scatter"}
  c0 = f32[1024]{0} add(p0, p0)
  ar0 = f32[256]{0} all-reduce(rs0), replica_groups={{0,4},{1,5},{2,6},{3,7}}, to_apply=add, metadata={op_name="jit(f)/ds_grad_bucket0/psum"}
  ag0 = f32[1024]{0} all-gather(ar0), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, metadata={op_name="jit(f)/ds_grad_bucket0/all_gather"}
  prod1 = f32[1024]{0} negate(p0), metadata={op_name="jit(f)/ds_grad_bucket1/reshape"}
  rs1 = f32[256]{0} reduce-scatter(prod1), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, to_apply=add, metadata={op_name="jit(f)/ds_grad_bucket1/reduce_scatter"}
  c1 = f32[1024]{0} add(p0, p0)
  ar1 = f32[256]{0} all-reduce(rs1), replica_groups={{0,4},{1,5},{2,6},{3,7}}, to_apply=add, metadata={op_name="jit(f)/ds_grad_bucket1/psum"}
  ag1 = f32[1024]{0} all-gather(ar1), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, metadata={op_name="jit(f)/ds_grad_bucket1/all_gather"}
  loss = f32[] all-reduce(p0), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=add
  ROOT t = (f32[1024]{0}, f32[1024]{0}, f32[]) tuple(ag0, ag1, loss)
}
"""


def test_bucket_scope_regex_matches_comm_constant():
    """anatomy parses HLO text without importing jax, so it carries its own
    copy of the bucket scope — pin it to the comm subsystem's constant."""
    from deepspeed_tpu.comm.hierarchical import GRAD_BUCKET_SCOPE
    m = anatomy._BUCKET_RE.search(f"op_name=\"x/{GRAD_BUCKET_SCOPE}7/psum\"")
    assert m is not None and m.group(1) == "7"


def test_bucketed_sync_collectives_get_overlap_credit():
    """The eager-issue pricing of the bucketed exchange: every tagged ICI
    phase hides fully under the other bucket's in-flight DCN wire (equal
    buckets: all-gather wire time == the peer DCN psum wire time at the
    cpu-test 4x ICI:DCN ratio), the DCN phases hide only behind the compute
    in their own issue window (partial), and the untagged loss all-reduce
    keeps the fully-exposed synchronous pricing."""
    r = anatomy.analyze_program(BUCKETED_SYNC, 0, 0, SPEC,
                                slice_sets=SLICE_SETS, name="b")
    rows = r["collectives"]
    assert [row["bucket"] for row in rows] == [0, 0, 0, 1, 1, 1, None]
    for row in rows:
        if row["bucket"] is None:
            continue
        assert not row["async"] and not row["zero_overlap"]
        if row["level"] == "ici":
            assert row["exposed_s"] == pytest.approx(0.0)
            assert row["overlap_s"] == pytest.approx(row["comm_s"])
        else:
            # window compute (one 4 KB add) hides part of the DCN psum
            assert 0 < row["overlap_s"] < row["comm_s"]
            assert row["exposed_s"] == pytest.approx(
                row["comm_s"] - row["overlap_s"])
    loss = rows[-1]
    assert loss["bucket"] is None and loss["zero_overlap"]
    assert loss["exposed_s"] == pytest.approx(loss["comm_s"])
    assert r["exposed_s"]["ici"] == pytest.approx(0.0)
    # both DCN psums partially exposed — strictly between 0 and full wire
    dcn_wire = sum(row["comm_s"] for row in rows
                   if row["level"] == "dcn" and row["bucket"] is not None)
    assert 0 < r["exposed_s"]["dcn"] < dcn_wire + loss["comm_s"]


def test_opportunities_threshold_and_order():
    big = anatomy.analyze_program(EMPTY_WINDOW, 0, 0, SPEC, SLICE_SETS, "big")
    small = anatomy.analyze_program(SYNC_ONLY, 0, 0, SPEC, SLICE_SETS, "small")
    opps = anatomy.opportunities([small, big], min_bytes=1024)
    assert [o["program"] for o in opps] == ["big", "small"]  # bytes-descending
    assert "start" in opps[0]["hint"]          # async phrasing
    assert "synchronous" in opps[1]["hint"]    # sync phrasing
    # threshold drops the 4 KB sync all-reduce
    assert anatomy.opportunities([small], min_bytes=1 << 20) == []


def test_trace_events_lay_exposed_comm_after_the_floor():
    r = anatomy.analyze_program(PARTIAL_OVERLAP, 1e6, 1e5, SPEC,
                                SLICE_SETS, "p")
    trace = anatomy.to_anatomy_trace_events([r])
    slices = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    floor = [e for e in slices if e["cat"] == "roofline"]
    comm = [e for e in slices if e["cat"] == "exposed-comm"]
    assert len(floor) == 1 and len(comm) == 1
    assert floor[0]["tid"] == 0 and comm[0]["tid"] == 1
    # comm track starts where the binding floor ends (dur itself carries the
    # 1 us Perfetto visibility clamp, so compare against the floor args)
    bound_us = max(floor[0]["args"]["compute_floor_us"],
                   floor[0]["args"]["hbm_floor_us"])
    assert comm[0]["ts"] == pytest.approx(bound_us)
    assert trace["otherData"]["generator"] == "ds-tpu anatomy"


# ------------------------------------------------------------- engine scale
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


# the engine step-path matrix: the two-program step, the opt-in fused step, the
# accumulation window, and ZeRO-Offload's host-tier split
STEP_PATHS = {
    "standard": dict(zero_optimization={"stage": 2}),
    "fused_step": dict(zero_optimization={"stage": 2}, fused_step=True),
    "accumulation": dict(train_batch_size=16, gradient_accumulation_steps=2,
                         zero_optimization={"stage": 2}),
    "zero_offload": dict(zero_optimization={"stage": 2, "cpu_offload": True}),
}


@pytest.mark.parametrize("path", sorted(STEP_PATHS))
def test_anatomy_keeps_every_step_path_hlo_identical(path, tmp_path):
    """THE non-perturbation gate: telemetry.anatomy prices artifacts the
    watchdog already holds — with it on, every program on all four engine
    step paths compiles to the instruction-identical HLO."""
    overrides = STEP_PATHS[path]
    model = SimpleModel(HIDDEN)
    engines = []
    for tel in (None, {"enabled": True, "output_path": str(tmp_path),
                       "anatomy": {"enabled": True}}):
        over = dict(overrides)
        if tel:
            over["telemetry"] = tel
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
            config_params=simple_config(**over))
        engines.append(eng)
    eng_off, eng_on = engines
    assert eng_on.telemetry.anatomy_spec is not None
    batch = _batch()
    progs_off = {n: (j, a) for n, j, a, _m in eng_off.lint_programs(batch)}
    progs_on = {n: (j, a) for n, j, a, _m in eng_on.lint_programs(batch)}
    assert sorted(progs_off) == sorted(progs_on)
    for name in sorted(progs_off):
        j_off, a_off = progs_off[name]
        j_on, a_on = progs_on[name]
        h_off = optimized_hlo(j_off, *a_off)
        h_on = optimized_hlo(j_on, *a_on)
        assert instruction_count(h_off) > 0, name
        assert instruction_count(h_off) == instruction_count(h_on), name


@pytest.fixture(scope="module")
def comm_entry_reports():
    """Anatomy reports for the flat/hierarchical/compressed/overlap registry
    entries, captured once per module (five engine builds)."""
    from deepspeed_tpu.lint import registry
    out = {}
    for entry in ("standard", "comm_hierarchical", "comm_compressed",
                  "comm_overlap", "comm_overlap_compressed"):
        artifacts = registry.capture_entry(entry)
        out[entry] = [anatomy.analyze_artifact(a, SPEC, slice_sets=SLICE_SETS)
                      for a in artifacts]
    return out


def test_hierarchical_and_compressed_expose_less_dcn(comm_entry_reports):
    """The headline claim of the two-level exchange, stated in anatomy terms:
    both hierarchical modes strictly reduce estimated exposed-DCN time."""
    def dcn(entry):
        return sum(r["exposed_s"]["dcn"] for r in comm_entry_reports[entry])
    flat = dcn("standard")
    assert flat > 0
    assert dcn("comm_hierarchical") < flat
    assert dcn("comm_compressed") < flat


def test_overlap_entry_grad_collectives_are_bucketed_and_hidden(
        comm_entry_reports):
    """The overlap acceptance shape on the real registry programs: every bucket
    keeps its own ICI reduce-scatter and all-gather under its bucket id, the whole
    cross-slice payload rides in bucket-tagged DCN hops, and an ICI phase is
    fully hidden (exposed == 0, absent from the opportunity list) wherever
    another bucket's DCN hop is there to ride under. Stated per bucket because
    the CPU compiler decides how many DCN hops there are: the installed XLA
    merges the three buckets' 1 KB cross-slice all-reduces into one tuple
    all-reduce carrying the first bucket's tag, which leaves that bucket's ICI
    phases nothing to hide behind; the seed's XLA kept three."""
    reports = {r["name"]: r for r in comm_entry_reports["comm_overlap"]}
    rows = reports["comm_overlap:loss_and_grad"]["collectives"]
    tagged = [r for r in rows if r["bucket"] is not None]
    ici = [r for r in tagged if r["level"] == "ici"]
    dcn = [r for r in tagged if r["level"] == "dcn"]
    assert sorted((r["bucket"], r["op"]) for r in ici) == sorted(
        (k, op) for k in (0, 1, 2) for op in ("reduce-scatter", "all-gather"))
    assert {r["op"] for r in dcn} == {"all-reduce"}
    assert sum(r["bytes"] for r in dcn) == 3 * 1024
    covered = [r for r in ici if {d["bucket"] for d in dcn} - {r["bucket"]}]
    assert len({r["bucket"] for r in ici} - {r["bucket"] for r in covered}) <= 1
    assert all(r["exposed_s"] == 0.0 and not r["zero_overlap"] for r in covered)
    listed = {o["instruction"]
              for o in anatomy.opportunities(comm_entry_reports["comm_overlap"])
              if "loss_and_grad" in o["program"]}
    assert not listed & {r["instruction"] for r in covered}


def test_zero_grad_collective_is_flagged_zero_overlap(comm_entry_reports):
    """>= 1 ZeRO gradient collective surfaces as a named opportunity: the CPU
    backend schedules collectives synchronously, so the grad exchange in
    loss_and_grad is fully exposed and crosses the opportunity threshold."""
    reports = comm_entry_reports["standard"]
    opps = anatomy.opportunities(reports)
    grad = [o for o in opps if "loss_and_grad" in o["program"]
            and o["op"] in ("all-reduce", "reduce-scatter")]
    assert grad, f"no zero-overlap grad collective in {opps}"
    assert all(o["exposed_us"] > 0 for o in grad)


def test_comm_compare_matches_golden_bytes(comm_entry_reports):
    """The flat-vs-hierarchical comparison, byte-for-byte against the pinned
    golden (the same file scripts/lint.sh regenerates and diffs in CI). What
    the comparison exists for is asserted clause by clause: both two-level modes
    expose less DCN than flat, and bucketing exposes less ICI than the monolithic
    two-level exchange and no more DCN. The report's own ``ok`` wants strictly
    less DCN and zero exposed ICI of the overlap entry; the installed XLA merges
    the buckets' cross-slice hops into one (see the test above), so its DCN phase
    IS the monolithic one and ``ok`` reads false: the golden pins that too."""
    compare = anatomy.comm_compare(comm_entry_reports)
    assert compare is not None
    dcn = {mode: compare[mode]["exposed_dcn_us"]
           for mode in ("flat", "hierarchical", "compressed", "overlap")}
    assert dcn["flat"] > dcn["hierarchical"] >= dcn["overlap"]
    assert dcn["flat"] > dcn["compressed"]
    assert (compare["hierarchical"]["exposed_ici_us"]
            > compare["overlap"]["exposed_ici_us"])
    text = json.dumps(compare, indent=2, sort_keys=True) + "\n"
    with open(GOLDEN) as f:
        golden = f.read()
    assert text == golden, "comm compare drifted from golden (regen via " \
                           "ds-tpu anatomy --comm-compare-out, see module doc)"


def test_roofline_sanity_against_measured_step(tmp_path):
    """floor <= measured and ceiling >= measured MFU: the cpu-test spec is an
    upper bound on any CI machine, so the prediction brackets reality."""
    eng = _build(zero_optimization={"stage": 2},
                 telemetry={"enabled": True, "output_path": str(tmp_path),
                            "anatomy": {"enabled": True}})
    xs, ys = _batch()
    for _ in range(4):
        loss = eng(xs, ys)
        eng.backward(loss)
        eng.step()
    summary = eng.telemetry.summary()
    rf = summary["anatomy"]
    assert rf is not None
    assert rf["predicted_floor_ms"] <= summary["step_time_ms"]
    assert rf["mfu_ceiling"] >= (summary["mfu"] or 0.0)
    assert rf["host_gap_ms"] >= 0.0
    # the Anatomy/* scalars landed in the ledger
    eng.telemetry.close()
    path = os.path.join(str(tmp_path), "DeepSpeedTelemetry", "scalars.jsonl")
    tags = {json.loads(l)["tag"] for l in open(path)}
    assert {"Anatomy/predicted_floor_ms", "Anatomy/mfu_ceiling",
            "Anatomy/host_gap_ms", "Anatomy/compute_ms",
            "Anatomy/hbm_bound_ms", "Anatomy/exposed_ici_ms",
            "Anatomy/exposed_dcn_ms"} <= tags


def test_anatomy_off_emits_no_anatomy_scalars(tmp_path):
    eng = _build(telemetry={"enabled": True, "output_path": str(tmp_path)})
    assert eng.telemetry.anatomy_spec is None
    xs, ys = _batch()
    for _ in range(2):
        loss = eng(xs, ys)
        eng.backward(loss)
        eng.step()
    assert eng.telemetry.summary()["anatomy"] is None
    eng.telemetry.close()
    path = os.path.join(str(tmp_path), "DeepSpeedTelemetry", "scalars.jsonl")
    tags = {json.loads(l)["tag"] for l in open(path)}
    assert not any(t.startswith("Anatomy/") for t in tags)
