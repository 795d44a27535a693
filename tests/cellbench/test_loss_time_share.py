"""``loss_time_share`` (PR 29): the reader on the slice recorded on the chip, as each
training runner's record holds it, on nothing, and its entry in the manifest."""

import pytest

from benchmarks import program_spans as ps
from benchmarks.manifest import Manifest, check

from test_program_spans import recorded, slice_doc            # noqa: F401  (fixtures)
from test_rehearsal_moe import recorded_moe                   # noqa: F401

NAME = "loss_time_share"
CELLS = ["xl_d20_train_1chip", "olmoe_d4_train_4chip"]


@pytest.mark.parametrize("runner", ["train", "train_moe"])
def test_a_share_of_the_window_on_each_runners_traced_record(runner, request):
    record = request.getfixturevalue({"train": "recorded", "train_moe": "recorded_moe"}[runner])
    share = Manifest().reader(NAME)(record)
    assert share is not None and 0.0 < share < 100.0
    # the rows it sums are the phase x part table's, and no more than the forward and
    # backward shares together
    trace = ps.analyse(record)["trace"]
    loss_s = sum(s for _, part, _, s in trace["device_s"] if part == "ds_loss")
    assert share == pytest.approx(100.0 * loss_s / trace["window_s"])
    assert loss_s <= trace["phase_s"]["forward"] + trace["phase_s"]["backward"]


def test_nothing_without_a_trace_a_recorder_or_a_catalog(recorded, monkeypatch):      # noqa: F811
    reader = Manifest().reader(NAME)
    assert reader({"setup": {}, "trace": None}) is None
    assert reader(dict(recorded, trace=None, program_spans=None)) is None
    analysed = ps.analyse(recorded)
    without = dict(analysed["trace"])
    without.pop("device_s")                 # no catalog: the trace has no phase x part table
    assert reader(dict(recorded, program_spans=dict(analysed, trace=without))) is None
    monkeypatch.setattr(ps, "program_recorder", lambda: None)       # the program has no recorder
    assert reader({"setup": {}, "trace": None, "t_window_start": 5.0, "window_s": 2.0,
                   "kind": "train"}) is None


def test_the_manifest_takes_the_grown_benchmark():
    manifest = Manifest()
    assert check(manifest) == []
    entry = manifest.doc["per_layer"][-1]             # appended, nothing before it moved
    assert entry == {"name": NAME, "unit": "%", "better": "lower", "source": "device_trace",
                     "layer": "model step", "moves": "train_tokens_per_s_chip",
                     "workloads": CELLS}
    for cell in CELLS:
        assert NAME in [m["name"] for m in manifest.metrics_of("per_layer", cell)]
