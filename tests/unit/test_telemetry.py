"""Telemetry subsystem tests (docs/telemetry.md).

Covers the four pillars and their core guarantee: default-mode telemetry is
NON-PERTURBING — the compiled step program is instruction-identical with
telemetry on and off (named_scope is metadata; the watchdog's AOT cache runs
the same executable jit would), and the only per-step block rides the loss
fetch the engine already performs.
"""

import json
import glob
import logging
import os

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.utils import logger
from deepspeed_tpu.utils.hlo import (collective_counts, instruction_count,
                                     optimized_hlo)
from deepspeed_tpu.utils.telemetry import CompileWatchdog, TelemetrySession
from simple_model import SimpleModel, random_dataset, simple_config

HIDDEN = 16


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.records = []

    def emit(self, record):
        self.records.append(record)

    @property
    def text(self):
        return "\n".join(r.getMessage() for r in self.records)


@pytest.fixture
def capture():
    h = _Capture()
    logger.addHandler(h)
    try:
        yield h
    finally:
        logger.removeHandler(h)


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


# --------------------------------------------------------------- pillar 1+4:
# non-perturbing step metrics + resource ledger through scalars.jsonl
def test_per_step_scalars_and_summary(tmp_path):
    eng = _build(telemetry={"enabled": True, "peak_tflops": 1e-6, "mfu_window": 4,
                            "output_path": str(tmp_path), "job_name": "tel"})
    _run_steps(eng, 4)
    eng.telemetry.close()
    path = os.path.join(str(tmp_path), "tel", "scalars.jsonl")
    scalars = [json.loads(l) for l in open(path)]
    tags = {s["tag"] for s in scalars}
    assert "Telemetry/Samples/step_time_ms" in tags
    assert "Telemetry/Samples/samples_per_sec" in tags
    assert "Telemetry/Samples/wire_bytes" in tags
    # rolling MFU needs >= 1 compile-free step; 4 steps with stable shapes give 3
    assert "Telemetry/Samples/mfu" in tags
    # HBM watermarks are emitted only where the backend reports memory_stats
    # (None on CPU CI) — when present they must be positive
    for s in scalars:
        if s["tag"].startswith("Telemetry/Samples/hbm_"):
            assert s["value"] > 0
    times = [s["value"] for s in scalars if s["tag"] == "Telemetry/Samples/step_time_ms"]
    assert len(times) == 4 and all(t > 0 for t in times)

    summary = eng.telemetry.summary()
    assert summary["steps_recorded"] == 4
    assert summary["compile_count"] >= 2  # loss_and_grad + apply_update at minimum
    assert summary["mfu"] is not None and summary["mfu"] > 0
    assert summary["compile_seconds"] > 0


def test_default_telemetry_blocks_are_only_the_loss_fetch(tmp_path):
    """wall_clock_breakdown=true is suppressed under telemetry (its section
    barriers perturb the run); perturbing_breakdown=true forces it with a loud
    one-time warning."""
    h = _Capture()
    logger.addHandler(h)
    try:
        eng = _build(wall_clock_breakdown=True,
                     telemetry={"enabled": True, "output_path": str(tmp_path)})
        assert eng.wall_clock_breakdown() is False
        assert "suppressed" in h.text
        h.records.clear()
        eng2 = _build(telemetry={"enabled": True, "perturbing_breakdown": True,
                                 "output_path": str(tmp_path)})
        assert eng2.wall_clock_breakdown() is True
        assert eng2.wall_clock_breakdown() is True
        warns = [r for r in h.records if "perturbing_breakdown" in r.getMessage()]
        assert len(warns) == 1, "loud warning must fire exactly once"
        # telemetry off: the plain config flag is untouched
        eng3 = _build(wall_clock_breakdown=True)
        assert eng3.wall_clock_breakdown() is True
    finally:
        logger.removeHandler(h)


# --------------------------------------------------------------- pillar 2:
# trace windows around the configured step range. One window serves both tests:
# trace start/stop late in a long pytest process is expensive.
@pytest.fixture(scope="module")
def trace_window(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("trace_window"))
    trace_dir = os.path.join(tmp, "trace")
    eng = _build(telemetry={"enabled": True, "trace_steps": [1, 2],
                            "trace_dir": trace_dir,
                            "output_path": tmp, "job_name": "prof"})
    xs, ys = _batch()
    seen = []   # after each step: (the trace dir exists, the window is open, it has closed)
    for _ in range(3):   # before the window, inside it, past it
        loss = eng(xs, ys); eng.backward(loss); eng.step()
        seen.append((os.path.exists(trace_dir), eng.telemetry._trace_active,
                     eng.telemetry._trace_done))
    yield eng, trace_dir, seen
    eng.telemetry.close()


def test_trace_window_artifacts(trace_window):
    eng, trace_dir, seen = trace_window
    if eng.telemetry._trace_failed:
        pytest.skip("profiler backend unavailable on this platform")
    # step 0 is before the window (the trace dir must not even exist yet), the
    # window closes with step 1, and step 2 finds it stopped and written
    assert seen == [(False, False, False), (True, False, True), (True, False, True)]
    # the profiler session lands in the run/host-namespaced subdir, and
    # nowhere else under trace_dir
    out_dir = eng.telemetry.trace_output_dir
    assert os.listdir(trace_dir) == [os.path.basename(out_dir)]
    assert os.path.basename(out_dir) == \
        f"trace_{eng.telemetry.run_id}_host{eng.telemetry.host_id}"
    summary = eng.telemetry.summary()
    assert summary["trace"] == {"trace_dir": out_dir, "steps": [1, 2],
                                "active": False, "done": True, "failed": False}


def test_trace_window_is_read_by_the_benchmarks_reduction(trace_window):
    """The package keeps no reader of a trace: the window's ``.xplane.pb`` is what
    ``benchmarks/trace_reduce.py`` finds and loads, and the recorder's spans are in
    it as annotations under their own names (docs/telemetry.md)."""
    from benchmarks import trace_reduce
    eng, _, _ = trace_window
    if eng.telemetry._trace_failed:
        pytest.skip("profiler backend unavailable on this platform")
    path = trace_reduce.find_xplane(eng.telemetry.trace_output_dir)
    assert path is not None and path.endswith(".xplane.pb")
    trace = trace_reduce.load_xplane(path)
    assert trace["devices"] == {}      # the CPU has no device plane; a chip's has "XLA Ops"
    held = trace_reduce.describe_xplane(path, per_line=10 ** 6)
    # (train.step opened before the window did: a trace holds what begins inside it)
    for name in ("train.grad_program", "train.update_program"):
        assert f"    {name} start=" in held, name


def test_trace_dir_namespacing_and_legacy_layout(tmp_path):
    """Two sessions sharing one trace_dir get distinct trace_<run>_host<h>/
    subdirs (the PR-14 flight-recorder naming); run_id=\"\" opts back into the
    legacy layout where the profiler writes into trace_dir itself."""
    shared = str(tmp_path / "shared")
    s1 = TelemetrySession(trace_dir=shared, trace_steps=[0, 1],
                          run_id="run-a", host_id=0, output_path=str(tmp_path))
    s2 = TelemetrySession(trace_dir=shared, trace_steps=[0, 1],
                          run_id="run-b", host_id=1, output_path=str(tmp_path))
    assert s1.trace_output_dir == os.path.join(shared, "trace_run-a_host0")
    assert s2.trace_output_dir == os.path.join(shared, "trace_run-b_host1")
    assert s1.trace_output_dir != s2.trace_output_dir
    legacy = TelemetrySession(trace_dir=shared, trace_steps=[0, 1],
                              run_id="", output_path=str(tmp_path))
    assert legacy.trace_output_dir == shared
    for s in (s1, s2, legacy):
        s.close()


def test_trace_failure_latched_into_summary(tmp_path, capture):
    """A profiler that cannot start warns ONCE, latches _trace_failed, stops
    all window bookkeeping, and surfaces the flag in summary()['trace'] so a
    bench run can't silently lose its measurement."""
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("a file where the trace dir must go")
    session = TelemetrySession(trace_dir=str(blocker), trace_steps=[0, 2],
                               run_id="", output_path=str(tmp_path))
    session.on_step_begin(0)
    assert session._trace_failed and not session._trace_active
    assert capture.text.count("profiler trace unavailable") == 1
    # subsequent steps must not retry or warn again
    session.on_step_begin(1)
    session.end_step(1, 8)
    assert capture.text.count("profiler trace unavailable") == 1
    summary = session.summary()
    assert summary["trace"]["failed"] is True
    assert summary["trace"]["done"] is False
    session.close()


def test_trace_steps_validation():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    for bad in ([3], [5, 2], [2, 2], [-1, 4], "0:2", [0, 2, 4]):
        with pytest.raises(ValueError):
            DeepSpeedConfig({"train_batch_size": 8,
                             "telemetry": {"enabled": True, "trace_steps": bad}},
                            world_size=1)
    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "telemetry": {"enabled": True, "trace_steps": [2, 5]}},
                          world_size=1)
    assert cfg.telemetry_trace_steps == (2, 5)


# --------------------------------------------------------------- pillar 3:
# compile watchdog — observed compiles, shape-driven recompiles, storm warning
def test_watchdog_counts_shape_driven_recompile(capture, tmp_path):
    eng = _build(telemetry={"enabled": True, "recompile_warn": 2,
                            "output_path": str(tmp_path)})
    _run_steps(eng, 2, n=8)
    base = eng.telemetry.watchdog.compiles("loss_and_grad")
    assert base >= 1
    # a different leading batch dim reaches the jitted step: the classic silent
    # recompile. 16 stays divisible by the 8-device data axis.
    _run_steps(eng, 1, n=16)
    wd = eng.telemetry.watchdog
    assert wd.compiles("loss_and_grad") == base + 1
    assert wd.recompiles("loss_and_grad") >= 1
    assert len(wd.records["loss_and_grad"]) >= 2  # distinct signatures
    assert "recompile storm" in capture.text
    assert "loss_and_grad" in capture.text
    # compile records carry the cost/memory analysis of each compile
    rec = next(iter(wd.records["loss_and_grad"].values()))
    assert rec.compile_seconds > 0
    assert eng.telemetry.summary()["recompile_count"] >= 1


def test_watchdog_storm_warning_threshold():
    wd = CompileWatchdog(recompile_warn=3)
    h = _Capture()
    logger.addHandler(h)
    try:
        wd.record("prog", ("sig_a",), 0.1)
        wd.record("prog", ("sig_b",), 0.1)
        assert "recompile storm" not in h.text
        wd.record("prog", ("sig_c",), 0.1)
        assert "recompile storm" in h.text
        n_warn = h.text.count("recompile storm")
        wd.record("prog", ("sig_d",), 0.1)  # storm warns once per program
        assert h.text.count("recompile storm") == n_warn
    finally:
        logger.removeHandler(h)
    assert wd.compiles("prog") == 4
    assert wd.recompiles("prog") == 3
    assert wd.compile_seconds("prog") == pytest.approx(0.4)


# --------------------------------------------------------------- the core
# guarantee: default telemetry adds ZERO HLO instructions to the step program
def test_default_telemetry_is_hlo_identical(tmp_path):
    eng_off = _build()
    eng_on = _build(telemetry={"enabled": True, "output_path": str(tmp_path)})
    xs, ys = _batch()
    hlos = []
    for eng in (eng_off, eng_on):
        jitted = eng._jit_loss_and_grad  # raw jit vs _WatchedJit proxy
        hlos.append(optimized_hlo(jitted, eng.params,
                                  eng.scaler_state.cur_scale, xs, ys))
    assert instruction_count(hlos[0]) > 0
    assert instruction_count(hlos[0]) == instruction_count(hlos[1])
    assert collective_counts(hlos[0]) == collective_counts(hlos[1])


def test_instruction_count_parses_hlo():
    hlo = """HloModule m

%fused_add (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  ROOT %add.1 = f32[8]{0} add(%p0, %p1)
}

ENTRY %main (a: f32[8], b: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %b = f32[8]{0} parameter(1)
  ROOT %fusion = f32[8]{0} fusion(%a, %b), kind=kLoop, calls=%fused_add
}
"""
    assert instruction_count(hlo) == 6


# --------------------------------------------------------------- results parity:
# the watchdog's AOT execution path must be bit-identical to the raw jit path
def test_watched_step_matches_unwatched(tmp_path):
    eng_off = _build()
    eng_on = _build(telemetry={"enabled": True, "output_path": str(tmp_path)})
    xs, ys = _batch()
    for step in range(3):
        l_off = eng_off(xs, ys); eng_off.backward(l_off); eng_off.step()
        l_on = eng_on(xs, ys); eng_on.backward(l_on); eng_on.step()
        assert float(jax.device_get(l_off)) == float(jax.device_get(l_on)), step
    p_off = jax.device_get(eng_off.params)
    p_on = jax.device_get(eng_on.params)
    for a, b in zip(jax.tree_util.tree_leaves(p_off), jax.tree_util.tree_leaves(p_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_session_uses_engine_monitor_when_tensorboard_enabled(tmp_path):
    eng = _build(tensorboard={"enabled": True, "output_path": str(tmp_path),
                              "job_name": "tb"},
                 telemetry={"enabled": True})
    assert eng.telemetry.monitor is eng.monitor
    _run_steps(eng, 2)
    eng.monitor.close()
    scalars = [json.loads(l) for l in
               open(os.path.join(str(tmp_path), "tb", "scalars.jsonl"))]
    tags = {s["tag"] for s in scalars}
    # engine training scalars and telemetry scalars share the sink
    assert "Train/Samples/train_loss" in tags
    assert "Telemetry/Samples/step_time_ms" in tags
