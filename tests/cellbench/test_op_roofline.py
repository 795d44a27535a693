"""``benchmarks/op_roofline.py``: every compiled operation of a traced window against the
program's own ``cost``, on a hand-made reduced trace and a hand-made catalog. Arithmetic
only: nothing is compiled and no clock is read. The manifest's entries are looked up BY
NAME; nothing asserts a position or another metric's list."""

import json

import pytest

from benchmarks import op_roofline as oroof
from benchmarks import program_spans as ps
from benchmarks import trace_reduce as tr
from benchmarks.manifest import Manifest, check

from test_program_spans import Recorded

METRICS = {"xla_product_roofline": ("model step", "higher"),
           "xla_memory_bound_roofline": ("model step", "higher"),
           "xla_memory_bound_time_share": ("model step", "lower"),
           "update_program_roofline": ("train engine", "higher")}
CELLS = {"xl_d20_train_1chip", "olmoe_d4_train_4chip", "qwen3next_ep16_train_1chip",
         "granite4h_d10_train_1chip", "ouro_d6_train_1chip", "nemotronh_ep16_d9_train_1chip",
         "mellum2_ep4_d4_train_1chip", "glm47flash_ep8_d5_train_1chip"}
PEAK = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}      # a floor is then read off by eye
FWD = "jit(step)/ds_fwd_bwd/jvp(ds_mlp)/ds_moe_experts/dot_general"
BWD = "jit(step)/ds_fwd_bwd/transpose(jvp(ds_attn))/ds_attn_window/dot_general"
AGAIN = "jit(step)/ds_fwd_bwd/transpose(jvp(ds_fwd_bwd))/checkpoint/rematted_computation/ds_mlp/mul"

# one step on one device, twenty seconds of window. fusion.1: 200 flops -> 2 s at peak, 10
# bytes -> 1 s: compute binds, it ran 4 s. fusion.2 (backward): 100 flops -> 1 s, 30 bytes
# -> 3 s: memory binds, it ran 3.75 s. fusion.3, a tuple a trace names by its reduction:
# 50 flops, ran 1 s. multiply.4 (recomputed): 20 bytes -> 2 s, ran 4 s. copy-done.5: a wait
# of 0.5 s at a floor of nothing. The update's fusion.6: 40 bytes -> 4 s, ran 5 s.
CATALOG = {
    "loss_and_grad": {
        "module": "jit_step",
        "ops": {"fusion.1": FWD, "fusion.2": BWD, "fusion.3": FWD, "multiply.4": AGAIN,
                "copy-done.5": "", "ds_flash_fwd.7": FWD, "all-reduce.8": BWD, "made-up.9": ""},
        "memory": None,
        "cost": {"fusion.1": [200, 10], "fusion.2": [100, 30], "fusion.3": [50, 1],
                 "multiply.4": [0, 20], "copy-done.5": [0, 0]},
        "products": {"fusion.1": {"mkn": [[10, 5, 2, "bf16xbf16->f32"]], "as": None},
                     "fusion.2": {"mkn": [[5, 5, 2, "bf16xbf16->f32"]], "as": None},
                     "fusion.3": {"mkn": [[5, 1, 5, "bf16xbf16->f32"], [1, 1, 1, "bf16xbf16->f32"]],
                                  "as": "bf16[5,5]"}}},
    "apply_update": {"module": "jit_update", "ops": {"fusion.6": "jit(update)/ds_apply_update/add"},
                     "memory": None, "cost": {"fusion.6": [0, 40]}, "products": {}},
}
EVENTS = [["fusion.1 bf16[10,2] fusion", 0.0, 4.0],
          ["ds_flash_fwd.7 bf16[8] custom-call tpu_custom_call", 4.0, 1.0],
          ["fusion.3 f32[5] fusion", 5.0, 1.0],
          ["multiply.4 f32[20] multiply", 6.0, 4.0],
          ["copy-done.5 bf16[8] copy-done", 10.0, 0.5],
          ["all-reduce.8 f32[8] all-reduce", 10.5, 0.25],
          ["fusion.2 bf16[5,2] fusion", 10.75, 3.75],
          ["made-up.9 f32[8] fusion", 14.5, 0.25],
          ["fusion.6 f32[40] fusion", 14.75, 5.0]]


def reduced(events=EVENTS, devices=1, window=(0.0, 20.0)):
    return tr.Reduced({"window": list(window), "host": [],
                       "devices": {f"/device:TPU:{d}": [list(e) for e in events]
                                   for d in range(devices)}})


def table(**kw):
    return oroof.table(reduced(**kw), CATALOG, {"apply_update"}, PEAK, steps=2)


def test_every_operation_falls_into_one_class_on_its_side():
    sides = table()["sides"]
    seconds = {side: {c: v["device_s"] for c, v in classes.items() if v["device_s"]}
               for side, classes in sides.items()}
    assert seconds == {
        "gradient": {"product": 4.0 + 3.75 + 1.0, "memory": 4.0 + 0.5, "kernel": 1.0,
                     "collective": 0.25, "unpriced": 0.25},
        "update": {"memory": 5.0}}
    assert sides["gradient"]["product"]["events"] == 3 and sides["update"]["memory"]["events"] == 1
    # a kernel, a collective and an instruction without a price have no floor here
    assert all(sides["gradient"][c]["floor_s"] == 0 for c in ("kernel", "collective", "unpriced"))


def test_a_fusion_that_only_wraps_a_collective_is_the_links_work():
    """A reduce-scatter the compiler wrote as a fusion has a fusion's name in the trace; its
    program's ``collectives`` says what it is, and it is not counted as unpriced."""
    told = {p: dict(info) for p, info in CATALOG.items()}
    told["loss_and_grad"]["collectives"] = ["made-up.9"]
    sides = oroof.table(reduced(), told, {"apply_update"}, PEAK, steps=2)["sides"]
    assert sides["gradient"]["collective"]["device_s"] == 0.25 + 0.25
    assert sides["gradient"]["unpriced"]["device_s"] == 0.0
    assert oroof.class_of("fusion.132 f32[12768,2048] fusion", None, collective=True) == "collective"
    assert oroof.class_of("fusion.132 f32[12768,2048] fusion", None) == "unpriced"
    assert oroof.class_of("gmm.1 bf16[8] custom-call tpu_custom_call", None, collective=True) == "kernel"


def test_a_floor_is_the_larger_of_the_two_and_says_which_binds():
    rows = {r["name"]: r for r in table()["rows"]}
    first, second = rows["fusion bf16[10,2]"], rows["fusion bf16[5,2]"]
    # ms a step: the window held two steps
    assert (first["floor_ms"], first["measured_ms"], first["bound"]) == (1000.0, 2000.0, "compute")
    assert (second["floor_ms"], second["measured_ms"], second["bound"]) == (1500.0, 1875.0, "memory")
    assert first["share"] == 50.0 and second["share"] == 80.0
    assert (first["flops_a_step"], first["bytes_a_step"], first["events_a_step"]) == (100.0, 5.0, 0.5)
    assert first["mkn"] == [10, 5, 2] and first["types"] == "bf16xbf16->f32"
    wait = rows["copy-done bf16[8]"]
    assert (wait["class"], wait["floor_ms"], wait["share"], wait["bound"]) == ("memory", 0.0, 0.0, "")
    assert wait["over_floor_ms"] == 250.0
    sides = table()["sides"]
    assert sides["gradient"]["product"]["floor_s"] == 2.0 + 3.0 + 0.5
    assert sides["gradient"]["memory"]["floor_s"] == 2.0 and sides["update"]["memory"]["floor_s"] == 4.0


def test_the_rows_are_sorted_by_what_they_take_over_their_floor_and_none_is_over_100():
    result = table()
    over = [r["over_floor_ms"] for r in result["rows"]]
    assert over == sorted(over, reverse=True) and over[0] == 1000.0
    assert [r["name"] for r in result["rows"][:2]] == ["fusion bf16[10,2]", "multiply f32[20]"]
    assert all(0 <= r["share"] <= 100 for r in result["rows"])
    assert result["worst_share"] == 80.0 and result["steps"] == 2 and result["unseen_s"] == 0.0
    assert result["product_flops_a_step"] == (200 + 100 + 50) / 2
    assert {r["class"] for r in result["rows"]} == {"product", "memory"}     # kernels have their own


def test_a_row_has_its_phase_its_innermost_scope_and_a_tuples_product_for_a_name():
    rows = {r["name"]: r for r in table()["rows"]}
    assert (rows["fusion bf16[10,2]"]["phase"], rows["fusion bf16[10,2]"]["part"]) == \
        ("forward", "ds_moe_experts")
    assert (rows["fusion bf16[5,2]"]["phase"], rows["fusion bf16[5,2]"]["part"]) == \
        ("backward", "ds_attn_window")
    assert (rows["multiply f32[20]"]["phase"], rows["multiply f32[20]"]["part"]) == ("recompute", "ds_mlp")
    # the trace calls it ``fusion f32[5]``, the reduction that rides along
    assert "fusion f32[5]" not in rows and rows["fusion bf16[5,5]"]["mkn"] == [5, 1, 5]
    # an operation the compiler made up goes with the scoped one before it
    assert (rows["copy-done bf16[8]"]["phase"], rows["copy-done bf16[8]"]["part"]) == ("recompute", "ds_mlp")
    assert (rows["fusion f32[40]"]["phase"], rows["fusion f32[40]"]["part"]) == ("optimizer", "ds_apply_update")


def test_devices_are_averaged_and_the_windows_edge_cuts_time_and_floor_alike():
    one, two = table(), table(devices=2)
    assert two["sides"] == one["sides"] and two["rows"] == one["rows"]
    cut = table(window=(2.0, 20.0))                   # half of fusion.1 lies before the window
    row = {r["name"]: r for r in cut["rows"]}["fusion bf16[10,2]"]
    assert (row["measured_ms"], row["floor_ms"], row["share"]) == (1000.0, 500.0, 50.0)
    # an operation the reduction took for an enclosing one (a zero-length copy-start fell inside
    # it) is in no row and no class: its time is told apart as busy and unseen
    hidden = EVENTS + [["fusion.2 bf16[5,2] fusion", 19.75, 0.25], ["copy-start.3 bf16[8] copy-start", 19.8, 0.0]]
    lost = oroof.table(reduced(hidden), CATALOG, {"apply_update"}, PEAK, steps=2)
    assert lost["unseen_s"] == pytest.approx(0.25) and lost["sides"] == one["sides"]


def record_with(result):
    return {"op_roofline": result, "kind": "train"}


def test_the_four_readers_read_their_side_and_classes():
    record = record_with(table())
    read = {name: Manifest().reader(name)(record) for name in METRICS}
    assert read["xla_product_roofline"] == pytest.approx(100 * 5.5 / 8.75)
    assert read["xla_memory_bound_roofline"] == pytest.approx(100 * 2.0 / 4.5)
    assert read["xla_memory_bound_time_share"] == pytest.approx(100 * 4.5 / 20.0)
    assert read["update_program_roofline"] == pytest.approx(100 * 4.0 / 5.0)
    assert all(0 < v <= 100 for v in read.values())


def test_a_share_over_a_part_of_the_work_is_not_given_out():
    # the unpriced quarter second is 1.25 % of twenty seconds; of ten it is 2.5 %
    events = [e for e in EVENTS if not e[0].startswith("fusion.6")]
    short = oroof.table(reduced(events, window=(0.0, 10.0)), CATALOG, {"apply_update"}, PEAK, 1)
    assert short["sides"]["gradient"]["unpriced"]["device_s"] == 0.0     # it lies after the window
    late = [[n, s - 6.0, d] for n, s, d in events if s >= 6.0]
    short = oroof.table(reduced(late, window=(0.0, 10.0)), CATALOG, {"apply_update"}, PEAK, 1)
    assert short["sides"]["gradient"]["unpriced"]["device_s"] == 0.25
    record = record_with(short)
    assert oroof.side_share(record, oroof.GRADIENT, (oroof.PRODUCT,)) is None
    assert oroof.side_share(record, oroof.GRADIENT, (oroof.MEMORY,), of_window=True) is None
    # the update program ran nothing in that window: nothing to read is None too
    assert oroof.side_share(record, oroof.UPDATE, oroof.PRICED) is None


def test_a_program_without_cost_gives_none_and_raises_nothing(monkeypatch, tmp_path):
    bare = {p: {k: v for k, v in info.items() if k not in ("cost", "products")}
            for p, info in CATALOG.items()}
    assert oroof.table(reduced(), bare, {"apply_update"}, PEAK, 2) is None
    assert oroof.table(reduced(), {}, set(), PEAK, 2) is None
    # no trace, no window, no recorder: every reader returns None
    for record in ({}, {"kind": "train", "trace": None}, {"kind": "train", "trace": reduced()}):
        monkeypatch.setattr(ps, "program_recorder", lambda: None)
        assert all(Manifest().reader(name)(dict(record)) is None for name in METRICS)


def test_the_parents_catalog_on_a_recorded_slice_reads_nothing(monkeypatch):
    """The slice recorded on the chip at PR 24 holds a catalog as the parent of this PR
    makes it (``module``, ``ops``): the readers return None on it."""
    import os
    from benchmarks.manifest import BENCH_DIR
    with open(os.path.join(BENCH_DIR, "testdata", "spans_train_slice.json")) as f:
        doc = json.load(f)
    monkeypatch.setattr(ps, "program_recorder", lambda: Recorded(doc))
    monkeypatch.setattr(ps, "_leave_table", lambda result: None)
    record = dict(doc["record"], trace=tr.Reduced(doc["trace"]), setup={}, device_kind="TPU v5 lite")
    assert ps.analyse(record)["trace"] is not None
    assert oroof.analyse(record) is None
    assert all(Manifest().reader(name)(record) is None for name in METRICS)


def test_the_tables_head_prints_from_the_file_it_leaves(tmp_path, capsys):
    path = tmp_path / "op_roofline.last.json"
    path.write_text(json.dumps(table()))
    oroof.main([str(path), "--top", "2"])
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 3 and "fusion bf16[10,2] [10, 5, 2] bf16xbf16->f32" in lines[1]
    assert lines[1].split()[:4] == ["1000.000", "2000.000", "1000.000", "50.0%"]


def test_the_manifest_holds_the_four_entries_in_all_eight_cells():
    manifest = Manifest()
    assert check(manifest) == []
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    for name, (layer, better) in METRICS.items():
        entry = by_name[name]
        assert set(entry["workloads"]) == CELLS, name
        assert (entry["layer"], entry["better"], entry["unit"]) == (layer, better, "%")
        assert (entry["source"], entry["moves"]) == ("device_trace", "train_tokens_per_s_chip")
        assert callable(manifest.reader(name))
    for cell in CELLS:
        assert set(METRICS) <= {m["name"] for m in manifest.metrics_of("per_layer", cell)}
