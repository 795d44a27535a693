"""``benchmarks/host_lead.py``: the host's lead over the device from the program's spans (CPU
beside wall, steps in flight, bytes in use) and the device trace, on hand-made cases and on a
slice recorded on the chip. No test reads a clock."""

import json
import os

import pytest

from benchmarks import host_lead as hl
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest, check

NEW_METRICS = ["engine_cpu_ms_p50.train", "steps_in_flight_p50.train",
               "launch_lead_ms_p10.train", "hbm_in_use_share_max.train"]
CELLS = ["xl_d20_train_1chip", "olmoe_d4_train_4chip", "qwen3next_ep16_train_1chip",
         "granite4h_d10_train_1chip"]
CATALOG = {"loss_and_grad": {"module": "jit_g", "ops": {"g.1": "a", "g.2": "b", "copy.9": ""}},
           "apply_update": {"module": "jit_u", "ops": {"u.1": "c", "copy.9": ""}}}


def span(id, parent, name, start, end, cpu_s=None, engine=1, step=0, **attrs):
    return {"id": id, "parent": parent, "engine": engine, "name": name, "start": start,
            "end": end, "cpu_s": cpu_s, "step": step, "attrs": attrs}


def call(program, start, end):
    name = "train.update_program" if program == "apply_update" else "train.grad_program"
    return span(0, None, name, start, end, program=program)


def ev(name, start, dur):
    return [f"{name} bf16[8] fusion", start, dur]


def grad(start, dur):
    """A gradient program's execution: three operations, the last a name both programs have."""
    return [ev("g.1", start, dur / 2), ev("g.2", start + dur / 2, dur / 4),
            ev("copy.9", start + 3 * dur / 4, dur / 4)]


def update(start, dur):
    return [ev("u.1", start, dur)]


# ---------------------------------------------------------------- the device side
def test_an_execution_is_a_run_of_one_programs_operations():
    events = grad(0.0, 4.0) + update(4.0, 1.0) + grad(5.5, 4.0) + [ev("nobody.1", 9.5, 0.1)] \
        + update(10.0, 1.0)
    runs = hl.executions(events, CATALOG)
    # the shared copy.9 lies between the two programs and goes with neither side
    assert runs == [["loss_and_grad", 0.0, 3.0], ["apply_update", 4.0, 5.0],
                    ["loss_and_grad", 5.5, 8.5], ["apply_update", 10.0, 11.0]]
    # two gradient programs back to back (an accumulation window) read as one execution
    assert len(hl.executions(grad(0.0, 2.0) + grad(2.0, 2.0), CATALOG)) == 1


def test_pairing_runs_from_the_windows_end_with_two_executions_in_flight_at_its_start():
    # the window opens at 10: the executions at 10 and 14 were launched before it
    runs = [["loss_and_grad", 6.0, 10.0],                                  # began before it
            ["apply_update", 10.0, 11.0], ["loss_and_grad", 11.0, 15.0],   # launched before it
            ["apply_update", 15.0, 16.0], ["loss_and_grad", 16.0, 20.0],
            ["apply_update", 20.0, 21.0]]
    calls = [call("apply_update", 10.5, 10.6), call("loss_and_grad", 10.7, 10.9),
             call("apply_update", 10.9, 15.2)]          # held until the device let it go
    pairs = hl.pair_calls(calls, runs, 10.0, 25.0)
    got = sorted((c["attrs"]["program"], c["end"], free, start) for c, free, start in pairs)
    assert got == [("apply_update", 10.6, 15.0, 15.0), ("apply_update", 15.2, 20.0, 20.0),
                   ("loss_and_grad", 10.9, 16.0, 16.0)]
    table = hl.lead_table(pairs)
    assert sorted(table["by_program"]["apply_update"]["leads_ms"]) == [4400.0, 4800.0]
    assert table["by_program"]["loss_and_grad"]["leads_ms"] == [5100.0]
    assert table["negative_lead_s"] == 0
    assert table["launch_lead_ms_p10"] == pytest.approx(4400.0 + 0.2 * 400.0)


def test_a_synthetic_gap_gives_a_negative_lead_of_its_length():
    # the device finishes at 5.0; the host's launch returns at 5.3 and the program starts then
    runs = [["loss_and_grad", 1.0, 5.0], ["apply_update", 5.3, 6.0]]
    calls = [call("loss_and_grad", 0.8, 0.9), call("apply_update", 4.9, 5.3)]
    table = hl.lead_table(hl.pair_calls(calls, runs, 0.0, 10.0))
    assert table["by_program"]["apply_update"]["leads_ms"] == [pytest.approx(-300.0)]
    assert table["by_program"]["apply_update"]["idle_before_ms"]["max"] == pytest.approx(300.0)
    assert table["by_program"]["apply_update"]["start_less_return_ms"]["max"] == pytest.approx(0.0)
    # nothing ran before the trace's first execution: the device was free since the window opened
    assert table["by_program"]["loss_and_grad"]["leads_ms"] == [pytest.approx(-900.0)]
    assert table["negative_lead_s"] == pytest.approx(0.3 + 0.9)
    assert table["launch_lead_ms_p10"] == pytest.approx(-900.0 + 0.1 * 600.0)


@pytest.mark.parametrize("fault", ["an execution short", "nine executions ahead",
                                   "ran before it was called", "no call at all"])
def test_counts_that_cannot_be_paired_give_none(fault):
    runs = [["apply_update", float(t), t + 0.5] for t in range(12)]
    calls = [call("apply_update", t - 0.2, t - 0.1) for t in range(2, 12)]
    assert len(hl.pair_calls(calls, runs, 0.0, 20.0)) == 10
    if fault == "an execution short":
        assert hl.pair_calls(calls, runs[:9], 0.0, 20.0) is None
    elif fault == "nine executions ahead":
        assert hl.pair_calls(calls[7:], runs, 0.0, 20.0) is None
    elif fault == "ran before it was called":
        late = calls[:-1] + [call("apply_update", 11.1, 11.2)]
        assert hl.pair_calls(late, runs, 0.0, 20.0) is None
    else:
        assert hl.pair_calls([], runs, 0.0, 20.0) is None


# ------------------------------------------------------------------ the host side
def steps_of(n, period=1.0):
    """``n`` steps of 0.6 s a ``period`` apart: 0.05 s put_batch (all CPU), 0.4 s in the
    gradient program's call (0.01 CPU), 0.1 s in the update's (0.01 CPU), 0.05 s its own."""
    spans = []
    for i in range(n):
        t, k = i * period, 10 * i
        spans += [span(k + 1, None, "train.step", t, t + 0.6, 0.09, step=i, in_flight=1),
                  span(k + 2, k + 1, "train.put_batch", t, t + 0.05, 0.05, step=i),
                  span(k + 3, k + 1, "train.grad_program", t + 0.05, t + 0.45, 0.01, step=i,
                       program="loss_and_grad"),
                  span(k + 4, k + 1, "train.update_program", t + 0.5, t + 0.6, 0.01, step=i,
                       program="apply_update")]
    return spans


def stall(spans, at, seconds, cpu_in=()):
    """``seconds`` put in at the moment ``at``: whatever is open then ends later, whatever
    begins after it begins later; the open spans named in ``cpu_in`` worked through it."""
    for s in spans:
        if s["start"] < at < s["end"] and s["name"] in cpu_in and s.get("cpu_s") is not None:
            s["cpu_s"] += seconds
        if s["start"] >= at:
            s["start"] += seconds
        if s["end"] > at:
            s["end"] += seconds
    return spans


def test_wall_cpu_and_held_by_span_and_the_steps_own_share():
    spans = steps_of(5)
    steps = [s for s in spans if s["name"] == "train.step"]
    got = hl.host_side(spans, steps)
    table = got["spans"]
    def row(name):
        return [table[name][k] for k in ("wall_ms", "cpu_ms", "held_ms")]

    assert row("train.grad_program") == pytest.approx([400.0, 10.0, 390.0])
    assert row("train.put_batch") == pytest.approx([50.0, 50.0, 0.0])
    # the step's own: 0.6 s less 0.55 s of children, 0.09 s of CPU less 0.07 s of theirs
    assert row(hl.SELF) == pytest.approx([50.0, 20.0, 30.0])
    assert table[hl.SELF]["held_ms_mean"] == pytest.approx(30.0)
    assert table[ps.CALLER]["wall_ms"] == pytest.approx(400.0) and table[ps.CALLER]["cpu_ms"] is None
    assert got["engine_cpu_ms_p50"] == pytest.approx(90.0)
    assert got["in_flight"] == {"min": 1, "median": 1, "max": 1}
    assert got["engine_cpu_ms_mean"] == pytest.approx(90.0)
    assert table["train.grad_program"]["cpu_ms_mean"] == pytest.approx(10.0)
    assert got["step_ms"] == pytest.approx(1000.0) and got["stalled_steps"] == []


def test_a_host_two_steps_ahead_is_held_two_steps_in_one_call_and_that_is_no_stall():
    # the steps begin 1.9, 0.1, 1.9, 0.1 ... apart: two steps' time in one call, none in the next
    spans = steps_of(8)
    for s in spans:                                     # steps of 0.06 s, 0.1 s apart
        s.update(start=s["start"] / 10, end=s["end"] / 10, cpu_s=s["cpu_s"] / 10)
    for i in range(0, 8, 2):
        stall(spans, i * 1.0 + 0.02, 1.8)              # held inside the gradient program's call
    got = hl.host_side(spans, [s for s in spans if s["name"] == "train.step"])
    assert got["step_ms"] == pytest.approx(1000.0) and got["stalled_steps"] == []
    assert got["step_wall_ms_min"] == pytest.approx(60.0)      # a step that nothing held
    stall(spans, 4.02, 0.6)                             # and one real stall on top of a long step
    (row,) = hl.host_side(spans, [s for s in spans if s["name"] == "train.step"])["stalled_steps"]
    assert (row["step"], row["span"], row["whose"]) == (4, "train.grad_program", "runtime")
    assert row["ms"] == pytest.approx(2500.0) and row["with_the_next_ms"] == pytest.approx(2600.0)


STALLS = {
    # a collection in the engine's own Python: wall and CPU in the step, outside its children
    "python": (2.47, ("train.step",), hl.SELF),
    # the runtime keeps the host in the gradient program's call: wall there, no CPU
    "runtime": (2.2, (), "train.grad_program"),
    # the process was stopped between two calls: wall in the step's own share, no CPU under it
    "machine": (2.47, (), hl.SELF),
    # the caller took its time between two steps: no span of the engine's was open
    ps.CALLER: (2.8, (), ps.CALLER),
}


@pytest.mark.parametrize("whose", sorted(STALLS))
def test_a_stalled_step_is_put_down_to_python_the_runtime_the_machine_or_the_caller(whose):
    at, cpu_in, where = STALLS[whose]
    spans = stall(steps_of(6), at, 0.8, cpu_in)
    (row,) = hl.host_side(spans, [s for s in spans if s["name"] == "train.step"])["stalled_steps"]
    assert (row["step"], row["span"], row["whose"]) == (2, where, whose)
    assert row["ms"] == pytest.approx(1800.0) and row["in_flight"] == 1
    assert row["span_ms"] - row["span_median_ms"] == pytest.approx(800.0)
    if whose == "python":
        assert row["span_cpu_ms"] == pytest.approx(820.0) and row["span_held_ms"] == pytest.approx(30.0)
    elif whose == "runtime":
        assert row["span_cpu_ms"] == pytest.approx(10.0) and row["span_held_ms"] == pytest.approx(1190.0)


def test_a_program_without_cpu_seconds_gives_the_walls_and_no_verdict():
    spans = stall(steps_of(6), 2.47, 0.8)
    for s in spans:                       # the parent's spans: no ``cpu_s`` key, no ``in_flight``
        del s["cpu_s"]
        s["attrs"].pop("in_flight", None)
    got = hl.host_side(spans, [s for s in spans if s["name"] == "train.step"])
    assert got["engine_cpu_ms_p50"] is None and got["in_flight"] is None
    grad_program = got["spans"]["train.grad_program"]
    assert grad_program["wall_ms"] == pytest.approx(400.0)
    assert grad_program["cpu_ms"] is None and grad_program["held_ms_mean"] is None
    (row,) = got["stalled_steps"]
    assert row["span"] == hl.SELF and row["whose"] is None and row["span_cpu_ms"] is None


def test_memory_is_the_windows_most_against_the_limit_noted_once():
    spans = steps_of(3)
    steps = [s for s in spans if s["name"] == "train.step"]
    assert hl.memory(spans, steps) is None                    # the CPU: nothing noted
    for s, used in zip(steps, (300, 500, 400)):
        s["attrs"]["bytes_in_use"] = used
    assert hl.memory(spans, steps)["share_max"] is None       # the first step left the ring
    first = span(99, None, "train.step", -50.0, -49.0, 0.1, bytes_in_use=100, bytes_limit=1000)
    got = hl.memory([first] + spans, steps)
    assert got == {"bytes_in_use_min": 300, "bytes_in_use_max": 500, "bytes_limit": 1000,
                   "share_max": pytest.approx(50.0)}


# ---------------------------------------------------------------- the manifest
def test_the_manifest_takes_the_four_appended_entries():
    manifest = Manifest()
    assert check(manifest) == []
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    names = [m["name"] for m in manifest.doc["per_layer"]]
    at = names.index("recompute_time_share")
    assert names[at + 1:at + 5] == NEW_METRICS                # appended after it, in this order
    for name in NEW_METRICS:
        entry = by_name[name]
        assert entry["workloads"] == CELLS and entry["moves"] == "train_tokens_per_s_chip"
        assert os.path.isfile(os.path.join(BENCH_DIR, "layer_metrics", name + ".py"))
    assert [by_name[n]["better"] for n in NEW_METRICS] == ["lower", "higher", "higher", "lower"]
    assert [by_name[n]["source"] for n in NEW_METRICS] == [
        "program_span", "program_span", "device_trace", "program_span"]
    assert [by_name[n]["layer"] for n in NEW_METRICS] == ["train engine"] * 3 + ["device"]
    # the metrics that time the same layer from outside stay
    assert {"host_dispatch_ms_p50.train", "step_ms_max_over_p50.train",
            "engine_self_ms_p50.train"} <= set(names[:at])


# ------------------------------------------------------------ readers on nothing
class Recorded:
    """The program's recorder as a slice's file holds it."""

    def __init__(self, doc):
        self.doc = doc

    def spans(self):
        return self.doc["spans"]

    def counters(self, engine):
        return self.doc.get("counters", {})

    def programs(self, engine):
        return self.doc["catalog"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_reader_returns_nothing_on_nothing(name, monkeypatch):
    reader = Manifest().reader(name)
    assert reader({"setup": {}, "trace": None}) is None                   # no window
    window = {"setup": {}, "trace": None, "t_window_start": 1.9, "window_s": 4.0, "kind": "train"}
    monkeypatch.setattr(ps, "program_recorder", lambda: None)             # no recorder
    assert reader(dict(window)) is None
    # a recorder with no step in the window
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded({"spans": steps_of(5), "catalog": {}}))
    assert reader(dict(window, t_window_start=50.0)) is None
    # the parent's recorder (no CPU seconds, nothing in flight, no bytes), and no trace
    bare = steps_of(5)
    for s in bare:
        del s["cpu_s"]
        s["attrs"].pop("in_flight", None)
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded({"spans": bare, "catalog": {}}))
    assert reader(dict(window)) is None
    # this PR's recorder, untraced, on a backend that reports no memory: the two host readings
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded({"spans": steps_of(5), "catalog": {}}))
    value = reader(dict(window))
    assert value == {"engine_cpu_ms_p50.train": pytest.approx(90.0),
                     "steps_in_flight_p50.train": 1}.get(name)


# ------------------------------------------------------------ the recorded slice
@pytest.fixture(scope="module")
def slice_doc():
    with open(os.path.join(BENCH_DIR, "testdata", "host_lead_slice.json")) as f:
        return json.load(f)


@pytest.fixture
def recorded(slice_doc, monkeypatch):
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(slice_doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    monkeypatch.setattr(hl, "_leave_table", lambda result: None)
    return dict(slice_doc["record"], trace=tr.Reduced(slice_doc["trace"]), setup={})


def test_recorded_slice_pairs_from_the_windows_end_with_three_programs_in_flight(recorded, slice_doc):
    trace, expect = recorded["trace"], slice_doc["expect"]
    first = next(iter(trace.devices))
    runs = [r for r in hl.executions(trace.devices[first], slice_doc["catalog"])
            if trace.lo <= r[1] <= trace.hi]
    ran = {p: sum(r[0] == p for r in runs) for p in slice_doc["catalog"]}
    result = hl.analyse(recorded)
    called = {p: row["lead_ms"]["count"] for p, row in result["trace"]["by_program"].items()}
    # one gradient and two update programs were launched before the window opened
    assert (ran, called) == ({"loss_and_grad": 10, "apply_update": 11},
                             {"loss_and_grad": 9, "apply_update": 9})
    for program, row in result["trace"]["by_program"].items():
        assert row["leads_ms"] == pytest.approx(expect["leads_ms"][program], abs=2e-3)
        assert min(row["leads_ms"]) > 0          # every program waited in the device's queue
        # the device turns from one program to the next in well under a millisecond
        assert row["idle_before_ms"]["max"] < 0.1
        assert row["start_less_return_ms"]["p50"] == pytest.approx(row["lead_ms"]["p50"], abs=0.1)
    # the host is let go when an update program starts: a gradient program's lead is that
    # of the update launched before it less the update's own 30 ms, every other step short
    assert sorted(result["trace"]["by_program"]["loss_and_grad"]["leads_ms"])[:4] == pytest.approx(
        [25.3, 25.4, 25.6, 26.1], abs=0.1)
    assert result["trace"]["negative_lead_s"] == 0
    assert result["trace"]["clock_offset_s"] == pytest.approx(expect["clock_offset_s"], abs=1e-6)
    assert abs(result["trace"]["clock_offset_spread"]["second_half_median_us"]) < 1.0
    assert result["steps"] == expect["steps"] == 9 and result["stalled_steps"] == []


def test_recorded_slice_an_execution_short_gives_no_lead_and_the_rest_stays(recorded, slice_doc):
    first = next(iter(slice_doc["trace"]["devices"]))
    last = max(e[1] for e in slice_doc["trace"]["devices"][first] if e[0].startswith("run."))
    short = dict(slice_doc["trace"], devices={first: [
        e for e in slice_doc["trace"]["devices"][first] if e[1] < last]})
    recorded["trace"] = tr.Reduced(short)       # the window's last update program never ran
    result = hl.analyse(recorded)
    assert "launch_lead_ms_p10" not in result["trace"] and "by_program" not in result["trace"]
    assert Manifest().reader("launch_lead_ms_p10.train")(recorded) is None
    assert result["in_flight"] == slice_doc["expect"]["in_flight"]


def test_every_new_reader_reads_the_recorded_slice(recorded, slice_doc):
    manifest, expect = Manifest(), slice_doc["expect"]
    values = {name: manifest.reader(name)(recorded) for name in NEW_METRICS}
    assert values == {
        "engine_cpu_ms_p50.train": pytest.approx(expect["engine_cpu_ms_p50"]),
        "steps_in_flight_p50.train": expect["in_flight"]["median"],
        "launch_lead_ms_p10.train": pytest.approx(expect["launch_lead_ms_p10"], abs=1e-3),
        "hbm_in_use_share_max.train": pytest.approx(expect["share_max"])}
    assert values["steps_in_flight_p50.train"] == 2 and 70 < values["hbm_in_use_share_max.train"] < 100
    result = hl.analyse(recorded)
    # a step's CPU seconds lie inside its wall seconds; this host's thread clock ticks at 10 ms
    steps, _ = ps.window_steps(slice_doc["spans"], recorded["t_window_start"],
                               recorded["t_window_start"] + recorded["window_s"])
    assert all(0 <= s["cpu_s"] <= s["end"] - s["start"] for s in steps)
    assert {round(1e3 * s["cpu_s"]) % 10 for s in steps} == {0}
    assert result["engine_cpu_ms_mean"] > result["spans"]["train.grad_program"]["cpu_ms_mean"] > 0
    # the programs' own need, as the compiler states it: the update writes over its state
    memory = result["program_memory"]
    assert memory["apply_update"]["alias"] > 0.99 * memory["apply_update"]["argument"]
    assert memory["loss_and_grad"]["temp"] + memory["apply_update"]["argument"] < result["memory"]["bytes_limit"]
