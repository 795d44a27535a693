"""Pipeline schedule observatory tests: span recording through the instruction
executor, goodput decomposition + telemetry scalars, measured-vs-analytic bubble
agreement on a 4-stage CPU mesh, straggler naming under an injected delay, the
HLO-identity guarantee when disabled, flight-recorder embedding, and the
Perfetto exporter (golden-file byte stability + CLI round trips).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.utils.hlo import instruction_count, optimized_hlo
from deepspeed_tpu.utils.pipeline_trace import (SPAN_BUF, SPAN_DUR, SPAN_MB,
                                                SPAN_NAME, SPAN_STAGE, SPAN_STEP,
                                                measured_costs, simulate_schedule,
                                                simulated_bundle, serialize_trace,
                                                timeline_main, to_trace_events)
from test_pipe_engine import HIDDEN, make_pipe, pipe_config, data_iter

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden",
                      "pipeline_timeline_2x4.trace.json")


def _build(stages=2, micro=2, layers=4, batch=32, **cfg_over):
    module, params = make_pipe(num_layers=layers, num_stages=stages)
    cfg = pipe_config(batch=batch, micro=micro)
    cfg["pipeline"] = {"spmd": False}  # span recording is instruction-executor-mode
    cfg.update(cfg_over)
    eng, _, _, _ = deepspeed_tpu.initialize(model=module, model_parameters=params,
                                            config_params=cfg)
    return eng


def _trace_cfg(**pt_over):
    pt = {"enabled": True}
    pt.update(pt_over)
    return {"telemetry": {"pipeline_trace": pt}}


# ------------------------------------------------------------- span recording


def test_tracer_disabled_by_default():
    eng = _build()
    assert eng.pipe_trace is None
    eng.train_batch(data_iter(batch=16))  # untraced path still executes


def test_spans_cover_the_schedule():
    eng = _build(**_trace_cfg())
    it = data_iter(batch=16)
    eng.train_batch(it)
    eng.train_batch(it)
    assert len(eng.pipe_trace.steps) == 2
    rec = eng.pipe_trace.steps[-1]
    assert rec["kind"] == "train" and rec["schedule"] == "TrainSchedule"
    spans = rec["spans"]
    # every compute slot of the analytic replay appears as a measured span
    sim = simulate_schedule(rec["micro_batches"], eng.num_stages, "train")
    measured_slots = sorted({(sp[0], sp[1]) for sp in spans
                             if sp[2] in ("ForwardPass", "BackwardPass")})
    assert measured_slots == sim["busy_slots"]
    # micro-batch and buffer attribution
    for s in range(eng.num_stages):
        fwd_mbs = sorted(sp[3] for sp in spans if sp[0] == s and sp[2] == "ForwardPass")
        assert fwd_mbs == list(range(rec["micro_batches"])), f"stage {s}"
    assert all(sp[6] >= 0 and sp[5] >= 0 for sp in spans)


def test_eval_batch_records_inference_spans():
    eng = _build(**_trace_cfg())
    it = data_iter(batch=16)
    eng.eval_batch(it)
    rec = eng.pipe_trace.steps[-1]
    assert rec["kind"] == "eval" and rec["schedule"] == "InferenceSchedule"
    assert any(sp[2] == "ForwardPass" for sp in rec["spans"])
    assert not any(sp[2] == "BackwardPass" for sp in rec["spans"])


def test_capacity_bounds_the_ring():
    eng = _build(**_trace_cfg(capacity=2))
    it = data_iter(batch=16)
    for _ in range(4):
        eng.train_batch(it)
    assert len(eng.pipe_trace.steps) == 2
    assert eng.pipe_trace.steps[-1]["step"] == 3  # most recent kept


# ------------------------------------------------------- goodput + telemetry


def test_goodput_scalars_flow_through_telemetry(tmp_path):
    eng = _build(telemetry={"enabled": True, "output_path": str(tmp_path),
                            "pipeline_trace": {"enabled": True}})
    it = data_iter(batch=16)
    eng.train_batch(it)
    eng.telemetry.monitor.flush()
    scalars = open(os.path.join(str(tmp_path), "DeepSpeedTelemetry",
                                "scalars.jsonl")).read()
    for name in ("Pipeline/Goodput/bubble_fraction", "Pipeline/Goodput/fwd_seconds",
                 "Pipeline/Goodput/bwd_seconds", "Pipeline/Goodput/opt_seconds"):
        assert name in scalars, name
    g = eng.pipe_trace.last_schedule_goodput
    assert g["fwd_seconds"] > 0 and g["bwd_seconds"] > 0
    assert 0.0 <= g["bubble_fraction"] < 1.0
    assert len(g["per_stage_busy_seconds"]) == eng.num_stages
    # the one-release "goodput" alias is gone: the bare name means the
    # run-level goodput ledger (docs/goodput.md), not this decomposition
    assert not hasattr(eng.pipe_trace, "last_goodput")
    assert "goodput" not in eng.pipe_trace.steps[-1]


def _four_stage_step(micro, batch):
    """A four-stage engine and its tracer's record of one step after a warm-up.

    Every stage program is fenced: each holds an all-reduce over the eight
    virtual devices, and with a 4 x 8 schedule's programs all in flight
    XLA:CPU's in-process rendezvous now and then waits for participants that
    never get a thread, and aborts the process after 40 s (``rendezvous.cc``:
    "only 6 of them arrived on time")."""
    def fenced(fn):
        return lambda *args: jax.block_until_ready(fn(*args))

    eng = _build(stages=4, micro=micro, batch=batch, **_trace_cfg())
    eng._stage_fwd = [fenced(f) for f in eng._stage_fwd]
    eng._stage_bwd = [fenced(f) for f in eng._stage_bwd]
    eng._stage_last_bwd = fenced(eng._stage_last_bwd)
    it = data_iter(batch=8)
    eng.train_batch(it)  # warmup: stage-fn compiles land inside these spans
    eng.train_batch(it)
    return eng, eng.pipe_trace.steps[-1]


def _replay(tracer, rec, duration_us):
    """Hand ``tracer`` the recorded step ``rec`` again through its own
    ``begin_step`` / ``record`` / ``end_step``, every span lasting the whole
    microseconds ``duration_us(span)`` says: what the clock read is then not
    under test, what the tracer makes of its spans is, and the comparison is
    arithmetic. (The half a microsecond keeps the tracer's truncation on the
    number that was asked for.)"""
    tracer.begin_step(rec["step"], rec["schedule"], rec["micro_batches"])
    for sp in rec["spans"]:
        tracer.record(sp[SPAN_STAGE], sp[SPAN_STEP], sp[SPAN_NAME], sp[SPAN_MB],
                      sp[SPAN_BUF], 0.0, (duration_us(sp) + 0.5) / 1e6)
    tracer.end_step()
    return tracer.steps[-1]


def test_four_stage_measured_bubble_matches_simulator():
    """Acceptance: on the 4-stage CPU-mesh pipeline, the bubble fraction the
    tracer reconstructs from a step's spans agrees with the analytic simulator
    run at the measured mean fwd/bwd costs: a recorded step's own spans, every
    forward lasting 10 ms and every backward 20."""
    eng, rec = _four_stage_step(micro=8, batch=64)
    cost_us = {"ForwardPass": 10_000, "BackwardPass": 20_000}
    timed = _replay(eng.pipe_trace, rec,
                    lambda sp: cost_us.get(sp[SPAN_NAME], sp[SPAN_DUR]))
    measured = timed["schedule_goodput"]["bubble_fraction"]
    t_fwd, t_bwd = measured_costs(timed)
    assert (t_fwd, t_bwd) == pytest.approx((0.01, 0.02), rel=1e-12)
    expected = simulate_schedule(8, 4, "train", t_fwd=t_fwd, t_bwd=t_bwd)["bubble_fraction"]
    assert 0.0 < expected < 1.0
    assert measured == pytest.approx(expected, abs=1e-9), (measured, expected)
    # and the slot structure is EXACTLY the schedule's
    sim = simulate_schedule(8, 4, "train")
    slots = sorted({(sp[0], sp[1]) for sp in rec["spans"]
                    if sp[2] in ("ForwardPass", "BackwardPass")})
    assert slots == sim["busy_slots"]


def test_injected_delay_names_the_straggler():
    """20 ms added to every forward of stage 2, in the spans: the tracer names
    the stage, by how much, and names nobody where every stage costs the same."""
    eng, rec = _four_stage_step(micro=4, batch=32)
    tracer = eng.pipe_trace

    def even(sp):
        return 1_000 if sp[SPAN_NAME] in ("ForwardPass", "BackwardPass") else sp[SPAN_DUR]

    _replay(tracer, rec, even)
    assert tracer.divergence(threshold=3.0) is None
    assert tracer.last_schedule_goodput["straggler"] is None
    _replay(tracer, rec, lambda sp: even(sp) + 20_000 * (
        sp[SPAN_NAME] == "ForwardPass" and sp[SPAN_STAGE] == 2))
    straggler = tracer.divergence(threshold=3.0)
    # stage 2: four forwards of 21 ms and four backwards of 1; its peers 8 ms
    assert straggler == {"stage": 2, "ratio": pytest.approx(88 / 8)}, straggler
    assert tracer.last_schedule_goodput["straggler"]["stage"] == 2


# --------------------------------------------------------------- HLO identity


def test_pipeline_hlo_identical_when_disabled():
    """Tracing is host-side only: the compiled stage programs of a traced build
    match an untraced build instruction for instruction, so the disabled
    default is trivially identical to pre-subsystem builds."""
    eng_off = _build()
    eng_on = _build(**_trace_cfg())
    x = jnp.zeros((4, HIDDEN), jnp.float32)
    scale = jnp.asarray(1.0, jnp.float32)
    for s in range(eng_off.num_stages - 1):
        h_off = optimized_hlo(eng_off._stage_fwd[s], eng_off._select_params(s), x)
        h_on = optimized_hlo(eng_on._stage_fwd[s], eng_on._select_params(s), x)
        assert instruction_count(h_off) > 0
        assert instruction_count(h_off) == instruction_count(h_on), f"stage {s} fwd"
    last = eng_off.num_stages - 1
    h_off = optimized_hlo(eng_off._stage_last_bwd, eng_off._select_params(last), x, x, scale)
    h_on = optimized_hlo(eng_on._stage_last_bwd, eng_on._select_params(last), x, x, scale)
    assert instruction_count(h_off) == instruction_count(h_on), "last-stage bwd"


# ------------------------------------------------- flight recorder embedding


def test_flight_recorder_embeds_span_bundle(tmp_path):
    eng = _build(numerics={"enabled": True, "dump_dir": str(tmp_path)},
                 **_trace_cfg())
    it = data_iter(batch=16)
    eng.train_batch(it)
    rec = eng._numerics.recorder
    assert rec.pipeline_trace is eng.pipe_trace
    path = rec.trigger("manual_test")
    bundle = json.load(open(path))
    embedded = bundle["pipeline_trace"]
    assert embedded["kind"] == "pipeline_trace"
    assert embedded["stages"] == eng.num_stages
    assert len(embedded["steps"]) == 1
    # the timeline CLI resolves the flight-recorder dump directly
    out = os.path.join(str(tmp_path), "dump.trace.json")
    assert timeline_main([path, "-o", out]) == 0
    trace = json.load(open(out))
    assert any(e["ph"] == "X" for e in trace["traceEvents"])


# ------------------------------------------------------------ Perfetto export


def test_perfetto_export_matches_golden():
    """2-stage x 4-microbatch deterministic bundle serializes byte-identically
    to the committed golden file and round-trips with the required fields."""
    bundle = simulated_bundle(4, 2)
    data = serialize_trace(to_trace_events(bundle))
    assert data == serialize_trace(to_trace_events(simulated_bundle(4, 2)))  # stable
    golden = open(GOLDEN).read()
    assert data == golden
    trace = json.loads(data)
    slices = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert slices, "no complete events"
    for ev in slices:
        for field in ("ph", "ts", "dur", "pid", "tid", "name"):
            assert field in ev, field
        assert ev["tid"] in (0, 1)
    # one thread-name metadata track per stage + counter tracks present
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names == {"stage 0", "stage 1"}
    counters = {e["name"] for e in trace["traceEvents"] if e["ph"] == "C"}
    assert "bubble_fraction" in counters
    assert any(n.endswith("buffers") for n in counters)


def test_timeline_cli_on_live_bundle(tmp_path, capsys):
    eng = _build(**_trace_cfg(dump_dir=str(tmp_path)))
    eng.train_batch(data_iter(batch=16))
    path = eng.pipe_trace.dump()
    assert timeline_main([path]) == 0
    out = capsys.readouterr().out
    assert "trace events" in out
    produced = path[:-5] + ".trace.json"
    trace = json.load(open(produced))
    assert trace["otherData"]["stages"] == 2
    assert any(e.get("cat") == "fwd" for e in trace["traceEvents"])


def test_timeline_cli_rejects_traceless_input(tmp_path, capsys):
    path = os.path.join(str(tmp_path), "not_a_bundle.json")
    json.dump({"reason": "whatever", "steps": []}, open(path, "w"))
    assert timeline_main([path]) == 2
    assert "no pipeline_trace bundle" in capsys.readouterr().out


def test_ds_tpu_timeline_subprocess(tmp_path):
    """The shipped CLI entry point converts a bundle end to end."""
    bundle_path = os.path.join(str(tmp_path), "bundle.json")
    json.dump(simulated_bundle(4, 2), open(bundle_path, "w"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds-tpu"), "timeline", bundle_path],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "trace events" in proc.stdout
    trace = json.load(open(bundle_path[:-5] + ".trace.json"))
    assert trace["traceEvents"]
