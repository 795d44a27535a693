"""``benchmarks/program_spans.py``: from the program's spans, counters and scope names
to the per-layer numbers, on hand-made cases and on a slice recorded on the chip."""

import json
import os

import pytest

from benchmarks import kernels, program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest

NEW_METRICS = ["engine_self_ms_p50.train", "engine_stall_ms_per_step.train",
               "forward_time_share", "backward_time_share", "optimizer_time_share",
               "flash_fwd_roofline", "flash_bwd_roofline", "step_program_variants",
               "step_program_load_s"]


def span(id, parent, name, start, end, engine=1, step=0, **attrs):
    return {"id": id, "parent": parent, "engine": engine, "name": name, "start": start,
            "end": end, "step": step, "attrs": attrs}


# one engine's step with a build in it, then two plain steps; a second engine's step
SPANS = [
    span(1, None, "train.step", 0.0, 10.0),
    span(2, 1, "train.put_batch", 0.1, 0.5),
    span(3, 1, "train.grad_program", 1.0, 6.0, program="loss_and_grad", builds=1),
    span(4, 3, "compile.backend", 2.0, 3.0),
    span(5, 3, "compile.cache_load", 2.1, 2.9),          # inside its compile.backend
    span(6, 1, "compile.backend", 6.1, 6.2),             # a one-operation program
    span(7, 1, "train.update_program", 6.5, 9.0, program="apply_update"),
    span(8, None, "train.step", 20.0, 21.0, step=1),
    span(9, 8, "train.grad_program", 20.1, 20.7, step=1, program="loss_and_grad"),
    span(10, 8, "train.update_program", 20.7, 20.9, step=1, program="apply_update"),
    span(11, None, "train.step", 21.5, 22.7, step=2),
    span(12, 11, "train.grad_program", 21.6, 22.5, step=2, program="loss_and_grad"),
    span(13, None, "train.step", 20.2, 20.4, engine=2),
]


def test_the_windows_steps_are_those_of_the_engine_that_ran_in_it():
    steps, engine = ps.window_steps(SPANS, 19.0, 23.0)
    assert engine == 1 and [s["id"] for s in steps] == [8, 11]
    assert ps.window_steps(SPANS, 11.0, 19.0) == ([], None)
    assert ps.window_steps(SPANS, 20.5, 23.0)[0] == [SPANS[10]]   # step 8 began before it


def test_self_time_and_the_host_table():
    mine = [s for s in SPANS if s["engine"] == 1]
    table, step_self = ps.host_table(mine, [mine[0]])
    # the step's children cover 0.1-0.5, 1-6, 6.1-6.2, 6.5-9: 8.0 of its 10 s
    assert step_self == [pytest.approx(2.0)]
    assert table["train.grad_program"]["self_median_ms"] == pytest.approx(4000.0)  # 5 - (2..3)
    assert table["compile.backend"]["count"] == 2
    table, step_self = ps.host_table(mine, [mine[7], mine[10]])
    assert sorted(step_self) == [pytest.approx(0.2), pytest.approx(0.3)]
    assert table["train.step"]["median_ms"] == pytest.approx(1100.0)


def test_self_time_on_a_hand_made_tree():
    step = {"id": 1, "parent": None, "start": 0.0, "end": 10.0}
    kids = [{"id": 2, "parent": 1, "start": 1.0, "end": 4.0},
            {"id": 3, "parent": 1, "start": 3.0, "end": 5.0},      # overlaps the first
            {"id": 4, "parent": 1, "start": 3.5, "end": 4.5},      # lies inside both
            {"id": 5, "parent": 1, "start": 9.0, "end": 12.0}]     # runs past its parent
    grandchild = {"id": 6, "parent": 2, "start": 1.5, "end": 2.0}
    # covered: 1-5 and 9-10, so 5 of 10 s are the step's own
    assert ps.self_seconds(step, kids) == pytest.approx(5.0)
    assert ps.self_seconds(kids[0], [grandchild]) == pytest.approx(2.5)
    assert ps.self_seconds(grandchild, []) == pytest.approx(0.5)


def test_build_seconds_count_program_calls_whole_and_stop_at_the_window():
    # the grad program call that built (5 s) and the one-operation compile (0.1 s)
    assert ps.build_seconds(SPANS, 1, before=19.0) == pytest.approx(5.1)
    assert ps.build_seconds(SPANS, 1, before=2.95) == pytest.approx(5.0)   # cache load only
    assert ps.build_seconds(SPANS, 1, before=1.5) == 0
    assert ps.build_seconds(SPANS, 2, before=30.0) == 0


def test_clock_offset_is_the_median_difference_of_the_dispatch_ends():
    returns = [1.0, 2.0, 3.0, 4.0, 5.0]
    host = [["dispatch", r + 99.5, r + 100.0 + late] for r, late in
            zip(returns, (1e-5, 2e-5, 3e-2, 1e-5, 2e-5))] + [["fence", 105.0, 105.2]]
    assert ps.clock_offset(host, returns) == pytest.approx(100.0 + 2e-5)
    assert ps.clock_offset(host[:-2], returns) is None          # one dispatch span short
    assert ps.clock_offset([], []) is None


def test_idle_goes_to_the_innermost_span_that_covers_it_or_to_the_caller():
    mine = [s for s in SPANS if s["engine"] == 1]
    gaps = [[100.6, 101.4],      # 0.5-1.0 is the step's own, 1.0-1.4 the grad program's
            [102.0, 102.5],      # all inside compile.backend, 0.4 of it inside the cache load
            [109.5, 110.5],      # the step's last half second, then the caller's
            [121.2, 121.6]]      # 0.3 the caller's between two steps, then 0.1 of step 2
    got = ps.idle_by_span(gaps, mine, offset=100.0)
    assert "train.put_batch" not in got and "train.update_program" not in got
    assert got["train.step"] == pytest.approx(0.4 + 0.5 + 0.1)
    assert got["train.grad_program"] == pytest.approx(0.4)
    assert got["compile.backend"] == pytest.approx(0.1)
    assert got["compile.cache_load"] == pytest.approx(0.4)
    assert got[ps.CALLER] == pytest.approx(0.5 + 0.3)
    assert sum(got.values()) == pytest.approx(sum(e - s for s, e in gaps))


CATALOG = {
    "loss_and_grad": {"module": "jit_loss_and_grad", "ops": {
        "fusion.1": "jit(f)/ds_fwd_bwd/jvp(ds_mlp)/dot_general",
        "fusion.2": "jit(f)/ds_fwd_bwd/transpose(jvp(ds_mlp))/dot_general",
        "fusion.7": "jit(f)/ds_fwd_bwd/transpose(jvp(ds_loss))/mul",
        "ds_flash_fwd.1": "jit(f)/ds_fwd_bwd/jvp(ds_attn)/ds_flash_fwd/pallas_call",
        "shard_map.3": "jit(f)/ds_fwd_bwd/transpose(jvp(ds_attn))/ds_flash_bwd_dq/pallas_call",
        "copy-done.4": "", "while.9": "jit(f)/ds_fwd_bwd/jvp(ds_loss)/while"}},
    "apply_update": {"module": "jit_apply_update", "ops": {
        "fusion.1": "jit(g)/ds_apply_update/mul", "fusion.7": "jit(g)/ds_apply_update/add",
        "fusion.30": "jit(g)/ds_apply_update/sqrt", "copy-done.4": ""}},
}


def ev(name, start, dur, kind="fusion"):
    return [f"{name} bf16[8,8] {kind}", start, dur]


def test_a_shared_instruction_name_goes_with_its_neighbours():
    events = [ev("fusion.1", 0, 1), ev("fusion.2", 1, 1), ev("fusion.7", 2, 1),
              ev("copy-done.4", 3, 1),                 # shared, between the two programs
              ev("fusion.7", 4, 1), ev("fusion.30", 5, 1), ev("fusion.1", 6, 1),
              ev("fusion.99", 7, 1),                   # nobody's
              ev("fusion.1", 8, 1), ev("ds_flash_fwd.1", 9, 1)]
    assert ps.assign_programs(events, CATALOG) == [
        "loss_and_grad",               # nothing certain before it, fusion.2 after it
        "loss_and_grad",
        None, None, None,              # between fusion.2 and fusion.30: either program's
        "apply_update",
        None,                          # fusion.30 before it, the flash call after it
        None,                          # nobody's
        None,
        "loss_and_grad"]
    events[2:5] = [ev("fusion.7", 2, 1), ev("fusion.2", 3, 1), ev("copy-done.4", 4, 1)]
    assert ps.assign_programs(events, CATALOG)[:6] == [
        "loss_and_grad"] * 4 + [None, "apply_update"]
    assert ps.assign_programs([ev("fusion.1", 0, 1)], CATALOG) == [None]


def test_phases_parts_kernels_and_the_time_only_an_enclosing_operation_ran():
    events = [
        ev("fusion.1", 0.0, 1.0),
        ev("ds_flash_fwd.1", 1.0, 2.0, "custom-call tpu_custom_call"),
        ev("copy-done.4", 3.0, 0.5, "copy-done"),     # no scope path: goes with the forward
        # 3.5-4.0: only the enclosing while ran, between two forward operations
        ev("fusion.1", 4.0, 1.0),
        ev("fusion.2", 5.0, 1.0),
        ev("shard_map.3", 6.0, 2.0, "custom-call tpu_custom_call"),
        # 8.0-8.25 busy, between a backward and an optimizer operation: nobody's
        ev("fusion.30", 8.25, 0.75),
        ev("fusion.30", 8.5, 1.0),                    # starts under the one before: cut to 9.0-9.5
        ev("fusion.99", 9.5, 0.25),                   # in no program
        # 9.75-10: idle
    ]
    # fusion.1 is in both programs: the first two go with the flash call after them
    busy = [[0.0, 9.75]]
    table = ps.device_table(events, busy, 0.0, 10.0, CATALOG, {"apply_update"})
    assert table == {
        ("forward", "ds_mlp", ""): pytest.approx(2.0),
        ("forward", "ds_attn", "ds_flash_fwd"): pytest.approx(2.0),
        ("forward", "", ""): pytest.approx(0.5),
        ("forward", "enclosing", ""): pytest.approx(0.5),
        ("backward", "ds_mlp", ""): pytest.approx(1.0),
        ("backward", "ds_attn", "ds_flash_bwd_dq"): pytest.approx(2.0),
        ("optimizer", "", ""): pytest.approx(1.25),
        (ps.UNASSIGNED, "", ""): pytest.approx(0.25 + 0.25),
    }
    assert sum(table.values()) == pytest.approx(tr.measure(busy))
    # a fused step: the grad program holds the update, told by its scope
    assert ps.phase_of("fused_step", "jit(s)/ds_apply_update/mul", set()) == "optimizer"
    assert ps.phase_of("fused_step", "jit(s)/ds_fwd_bwd/transpose(jvp(ds_mlp))/dot", set()) == "backward"
    assert ps.kernel_of("jvp__.3 bf16[8] custom-call tpu_custom_call", "") == "custom_call"
    assert ps.kernel_of("fusion.3 bf16[8] fusion", "x/ds_flash_fwd/y") == ""


# ------------------------------------------------------------ the recorded slice
class Recorded:
    """The program's recorder as the slice's file holds it."""

    def __init__(self, doc):
        self.doc = doc

    def spans(self):
        return self.doc["spans"]

    def counters(self, engine):
        return self.doc["counters"]

    def programs(self, engine):
        return self.doc["catalog"]


@pytest.fixture(scope="module")
def slice_doc():
    with open(os.path.join(BENCH_DIR, "testdata", "spans_train_slice.json")) as f:
        return json.load(f)


@pytest.fixture
def recorded(slice_doc, monkeypatch):
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(slice_doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    record = dict(slice_doc["record"], trace=tr.Reduced(slice_doc["trace"]), setup={})
    return record


def test_recorded_slice_clock_offset_and_idle_by_span(recorded, slice_doc):
    result = ps.analyse(recorded)
    trace = result["trace"]
    # the dispatch spans end a few microseconds after the returns the record holds
    assert trace["clock_offset_s"] == pytest.approx(slice_doc["expect"]["clock_offset_s"], abs=2e-4)
    reduced = recorded["trace"]
    idle_s = reduced.idle_share() * reduced.window_s
    assert sum(trace["idle_s"].values()) == pytest.approx(idle_s, rel=1e-6)
    assert set(trace["idle_s"]) <= {"train.step", "train.put_batch", "train.grad_program",
                                    "train.accumulate", "train.update_program", ps.CALLER}
    assert trace["stall_ms_per_step"] >= 0
    assert result["steps"] == slice_doc["expect"]["steps"]
    assert result["program_builds"] == 4
    assert 0 < result["engine_self_ms_p50"] < 20


def test_recorded_slice_phases_sum_to_the_window(recorded, slice_doc):
    trace = ps.analyse(recorded)["trace"]
    reduced = recorded["trace"]
    phases = trace["phase_s"]
    idle_s = reduced.idle_share() * reduced.window_s
    assert sum(phases.values()) + trace["unassigned_s"] + idle_s == pytest.approx(
        reduced.window_s, rel=1e-9)
    assert 0 <= trace["unassigned_s"] < ps.MAX_UNASSIGNED * reduced.window_s
    # the slice runs from the end of one backward pass through an update into a forward pass
    assert all(phases[p] > 0 for p in ps.PHASES)
    shares = {p: ps.phase_share(recorded, p) for p in ps.PHASES}
    assert sum(shares.values()) == pytest.approx(100.0 * sum(phases.values()) / reduced.window_s)
    # the kernels found by scope are the custom calls the older reader finds by target
    flash_s, count = reduced.op_seconds(kernels.is_flash)
    assert count > 0 and sum(trace["kernel_s"].values()) == pytest.approx(flash_s, rel=1e-9)
    assert set(trace["kernel_s"]) <= {"ds_flash_fwd", "ds_flash_bwd_dq", "ds_flash_bwd_dkv"}
    # slow and independent: every operation by its own name alone, where one program has it
    owners = {}
    for program, info in slice_doc["catalog"].items():
        for name in info["ops"]:
            owners.setdefault(name, []).append(program)
    first = next(iter(reduced.devices))
    sure = sum(min(s + d, reduced.hi) - max(s, reduced.lo) for n, s, d in reduced.devices[first]
               if owners.get(n.split(" ")[0]) == ["apply_update"]
               and min(s + d, reduced.hi) > max(s, reduced.lo))
    assert sure <= phases["optimizer"] <= sure * 1.25


def test_shares_are_withheld_when_too_much_is_unassigned(recorded, slice_doc, monkeypatch):
    holed = dict(slice_doc["catalog"])
    holed["loss_and_grad"] = {"module": "m", "ops": {}}
    monkeypatch.setattr(ps, "program_recorder",
                        lambda: Recorded(dict(slice_doc, catalog=holed)))
    trace = ps.analyse(recorded)["trace"]
    assert trace["unassigned_s"] > ps.MAX_UNASSIGNED * trace["window_s"]
    manifest = Manifest()
    for name in ("forward_time_share", "backward_time_share", "optimizer_time_share"):
        assert manifest.reader(name)(recorded) is None
    assert manifest.reader("engine_stall_ms_per_step.train")(recorded) is not None


def test_every_new_reader_reads_the_recorded_slice(recorded, monkeypatch):
    from benchmarks import peaks
    monkeypatch.setitem(peaks.PEAKS, recorded["device_kind"], dict(peaks.PEAKS["TPU v5 lite"]))
    manifest = Manifest()
    values = {name: manifest.reader(name)(recorded) for name in NEW_METRICS}
    assert all(v is not None and v == v for v in values.values()), values
    assert values["step_program_variants"] == 4
    assert 0 < values["flash_fwd_roofline"] < 100 and 0 < values["flash_bwd_roofline"] < 100
    total = sum(values[f"{p}_time_share"] for p in ps.PHASES)
    assert 97.0 < total + 100.0 * recorded["trace"].idle_share() <= 100.0 + 1e-9


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_reader_returns_nothing_on_nothing(name, monkeypatch):
    reader = Manifest().reader(name)
    assert reader({"setup": {}, "trace": None}) is None
    # a window, and a program without a recorder (the parent of the PR that added it)
    monkeypatch.setattr(ps, "program_recorder", lambda: None)
    assert reader({"setup": {}, "trace": None, "t_window_start": 5.0, "window_s": 2.0,
                   "kind": "train"}) is None
    # a recorder with no step in the window
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(
        {"spans": SPANS, "counters": {}, "catalog": {}}))
    assert reader({"setup": {}, "trace": None, "t_window_start": 50.0, "window_s": 2.0,
                   "kind": "train"}) is None
