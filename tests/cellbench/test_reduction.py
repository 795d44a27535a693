"""The reduction from a trace to numbers, and the operation counts, against values
worked by hand and against slow independent arithmetic on recorded traces."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops, kernels, peaks
from benchmarks import trace_reduce as tr
from benchmarks.manifest import BENCH_DIR, Manifest

RECORDED = ["trace_train_slice.json", "trace_serve_slice.json"]

# two devices, a window of 10 s; c = compute, ag/ar = collectives
SYNTHETIC = {
    "window": [0.0, 10.0],
    "devices": {
        "/device:TPU:0": [
            ["while.1 () while", 0.0, 4.0],                       # encloses the next two
            ["fusion.1 bf16[8,8] fusion", 0.0, 1.0],
            ["jvp__.2 bf16[4,25,1024,64] custom-call tpu_custom_call", 2.0, 2.0],
            ["all-gather-start.1 bf16[8] all-gather-start", 4.0, 0.5],
            ["fusion.2 bf16[8,8] fusion", 5.0, 2.0],
            ["all-reduce.7 f32[8] all-reduce", 6.5, 1.5],         # half hidden under fusion.2
            ["fusion.9 bf16[8,8] fusion", 9.5, 1.0]],             # half outside the window
        "/device:TPU:1": [
            ["fusion.1 bf16[8,8] fusion", 0.0, 5.0],
            ["all-gather-done.1 bf16[8] all-gather-done", 5.0, 5.0]]},
    "host": [["dispatch", 0.0, 4.6], ["fence", 4.6, 4.95], ["dispatch", 4.95, 10.0],
             ["data", 8.1, 8.2]],
}


def brute(trace, pick, step=1e-5):
    """Seconds of the window covered by the events ``pick`` accepts, a device at a
    time on a grid: slow, and nothing shared with the interval arithmetic."""
    lo, hi = trace["window"]
    n = int(round((hi - lo) / step))
    out = {}
    for dev, events in trace["devices"].items():
        grid = np.zeros(n, bool)
        for name, s, d in events:
            if pick(name):
                a, b = int(round((s - lo) / step)), int(round((s + d - lo) / step))
                grid[max(a, 0):max(min(b, n), 0)] = True
        out[dev] = grid
    return out, step


def test_interval_arithmetic():
    assert tr.union([[3, 4], [0, 1], [0.5, 2], [2, 2]]) == [[0, 2], [3, 4]]
    assert tr.measure(tr.union([[0, 1], [0.5, 2]])) == 2
    assert tr.subtract([[0, 10]], [[1, 2], [5, 6]]) == [[0, 1], [2, 5], [6, 10]]
    assert tr.subtract([[0, 2], [3, 5]], [[1, 4]]) == [[0, 1], [4, 5]]
    assert tr.gaps([[1, 2]], 0, 3) == [[0, 1], [2, 3]]
    assert tr.clip([[0, 5], [7, 9]], 1, 8) == [[1, 5], [7, 8]]


def test_enclosing_events_are_dropped_and_busy_counts_them():
    r = tr.Reduced(SYNTHETIC)
    names = [e[0] for e in r.devices["/device:TPU:0"]]
    assert "while.1 () while" not in names and len(names) == 6
    # device 0 is busy 0-4 (the while), 4-4.5, 5-8, 9.5-10: 8.0 s; device 1 all 10 s
    assert r.busy_s() == pytest.approx((8.0 + 10.0) / 2)
    assert r.idle_share() == pytest.approx(0.1)


def test_exposed_collective_share_by_hand():
    r = tr.Reduced(SYNTHETIC)
    # device 0: all-gather-start 0.5 s exposed, all-reduce 6.5-8 minus fusion 5-7 = 1.0 s;
    # device 1: the all-gather-done waits 5 s with no compute
    assert r.collective_exposed_s() == pytest.approx((1.5 + 5.0) / 2)
    one_chip = {"window": [0, 1], "devices": {"d": [["fusion.1 f32[2] fusion", 0, 1]]}}
    assert tr.Reduced(one_chip).collective_exposed_s() is None


def test_kernel_share_and_roofline_by_hand():
    r = tr.Reduced(SYNTHETIC)
    seconds, count = r.op_seconds(kernels.is_flash)
    assert (seconds, count) == (pytest.approx(1.0), 1)          # 2 s on one of two devices
    model = {"n_embd": 1600, "n_layer": 20, "n_head": 25, "n_positions": 1024}
    need_flops, need_bytes = flops.flash_required(model, 4, 1024)
    assert need_flops == 805_306_368_000 and need_bytes == 3_145_728_000
    least, bound_by = flops.roofline_seconds(need_flops, need_bytes, peaks.peaks_for("TPU v5 lite"))
    assert bound_by == "compute" and least == pytest.approx(805_306_368_000 / 197e12)
    # forward only: the bytes bound it
    f, b = flops.flash_required(model, 4, 1024, training=False)
    assert flops.roofline_seconds(f, b, peaks.peaks_for("TPU v5 lite"))[1] == "compute"
    assert flops.roofline_seconds(1.0, 819e9, peaks.peaks_for("TPU v5 lite")) == (1.0, "memory")
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


def test_breakdown_names_rows_and_labels_gaps():
    b = tr.Reduced(SYNTHETIC).breakdown()
    ops = dict(map(tuple, b["device_ops"]))
    assert ops["fusion bf16[8,8]"] == pytest.approx(1.0 + 2.0 + 0.5)
    assert ops["jvp__ bf16[4,25,1024,64] tpu_custom_call"] == pytest.approx(2.0)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    # the gaps of device 0: 8-9.5 under the second dispatch (the data span inside it is
    # narrower but does not cover the middle), 4.5-5 under the fence
    assert [g[0] for g in b["idle_gaps"]] == ["dispatch", "fence"]
    assert [g[1] for g in b["idle_gaps"]] == pytest.approx([1.5, 0.5])


@pytest.mark.parametrize("text, short, group", [
    ("%fusion.12 = bf16[6400,1600]{1,0:T(8,128)(2,1)} fusion(bf16[4,1024,6400] %x), kind=kOutput",
     "fusion.12 bf16[6400,1600] fusion", "fusion bf16[6400,1600]"),
    ("%copy-start = (bf16[6400,1600]{0,1:T(8,128)(2,1)S(1)}, bf16[6400,1600]{0,1}, u32[]{:S(2)}) "
     "copy-start(bf16[6400,1600]{0,1} %p)", "copy-start bf16[6400,1600] copy-start",
     "copy-start bf16[6400,1600]"),
    ('%jvp__.3 = (bf16[4,25,1024,64]{3,2,1,0}, f32[4,25,1024,128]) custom-call(bf16[] %a), '
     'custom_call_target="tpu_custom_call"',
     "jvp__.3 bf16[4,25,1024,64] custom-call tpu_custom_call",
     "jvp__ bf16[4,25,1024,64] tpu_custom_call"),
    ("%all-gather-start.5 = (bf16[400,1600], bf16[1600,1600]) all-gather-start(bf16[400,1600] %p)",
     "all-gather-start.5 bf16[400,1600] all-gather-start", "all-gather-start bf16[400,1600]"),
    ("dot_general.1", "dot_general.1", "dot_general"),
])
def test_device_operations_are_named_by_what_the_trace_prints(text, short, group):
    assert tr.short_name(text) == short
    assert tr.op_group(short) == group
    assert tr.is_collective(short) == ("all-gather" in text)
    assert kernels.is_flash(short) == ("tpu_custom_call" in text)


@pytest.mark.parametrize("file_name", RECORDED)
def test_recorded_trace_agrees_with_slow_arithmetic(file_name):
    with open(os.path.join(BENCH_DIR, "testdata", file_name)) as f:
        trace = json.load(f)
    r = tr.Reduced(trace)
    grids, step = brute(trace, lambda name: True)
    slack = step * 2 * max(len(v) for v in trace["devices"].values())
    busy = sum(g.sum() * step for g in grids.values()) / len(grids)
    assert r.busy_s() == pytest.approx(busy, abs=slack)
    assert 0.0 <= r.idle_share() <= 1.0
    coll, _ = brute(trace, tr.is_collective)
    comp, _ = brute(trace, lambda name: not tr.is_collective(name))
    # compute is counted over leaves in the reduction; on the grid a parent covers its
    # children anyway, so only a parent with nothing inside it could differ
    exposed = sum((coll[d] & ~comp[d]).sum() * step for d in coll) / len(coll)
    got = r.collective_exposed_s()
    if got is None:
        assert not any(g.any() for g in coll.values())
    else:
        assert got <= r.window_s and got == pytest.approx(exposed, abs=slack + 0.02 * r.window_s)
    flash, _ = brute(trace, kernels.is_flash)
    seconds, count = r.op_seconds(kernels.is_flash)
    assert seconds == pytest.approx(sum(g.sum() * step for g in flash.values()) / len(flash),
                                    abs=slack)
    b = r.breakdown()
    assert b["device_ops"] and all(v > 0 for _, v in b["device_ops"])
    assert sum(v for _, v in b["idle_gaps"]) <= r.window_s - measure_first(r) + 1e-9


def test_step_profile_tells_one_long_step_from_a_slower_run():
    """On the return intervals of a recorded chip run (the host runs one to two steps
    ahead, so they alternate between 20 ms and 365 ms): the run as recorded reads no
    stall, one step made 150 ms longer reads as that, and every step 1 % slower reads a
    higher median and still no stall."""
    from benchmarks import harness
    with open(os.path.join(BENCH_DIR, "testdata", "steps_xl_d20.json")) as f:
        recorded = np.array(json.load(f)["step_interval_ms"])
    assert recorded.max() > 1.8 * np.median(recorded)         # single intervals mislead
    median, stall = harness.step_profile(recorded)
    assert median == pytest.approx(191.4, abs=0.5) and stall < 10.0
    longer = recorded.copy()
    longer[50] += 150.0
    assert harness.step_profile(longer)[1] == pytest.approx(150.0, abs=10.0)
    median, stall = harness.step_profile(recorded * 1.01)
    assert median == pytest.approx(191.4 * 1.01, abs=0.5) and stall < 10.0
    assert harness.step_profile(recorded[:8]) == (None, None)


def measure_first(reduced):
    return tr.measure(reduced.busy[next(iter(reduced.busy))])


@pytest.mark.parametrize("config, matmul_params, params, per_token", [
    # 12 L E^2 + V E; L (12 E^2 + 13 E) + V E + 1024 E + 2 E; 6 x matmul + 3 x L x 2 T E
    ("gpt2-xl-d20", 694_886_400, 696_944_000, 4_365_926_400),
    ("gpt2-xl", 1_555_046_400, 1_557_686_400, 9_802_137_600),
    ("gpt2-medium", 353_501_184, 354_871_296, 2_272_002_048),
])
def test_flops_per_token_against_hand_worked_values(config, matmul_params, params, per_token):
    with open(os.path.join(BENCH_DIR, "configs", config + ".json")) as f:
        doc = json.load(f)
    model, vocab = doc["model"], doc["padded_vocab_size"]
    assert flops.matmul_params(model, vocab) == matmul_params
    assert flops.param_count(model, vocab) == params
    assert flops.train_flops_per_token(model, vocab, 1024) == per_token
    assert flops.attention_flops_per_token_fwd(model, 1024, causal=False) == \
        2 * flops.attention_flops_per_token_fwd(model, 1024)


def test_param_count_matches_the_program(tmp_path):
    """The arithmetic against the program's own parameter tree, at a size that fits a test."""
    import jax
    from benchmarks import harness
    config = {"model": {"vocab_size": 250, "n_positions": 64, "n_embd": 32, "n_head": 2,
                        "n_layer": 3, "layer_norm_epsilon": 1e-5, "initializer_range": 0.02},
              "padded_vocab_size": 256, "compute_dtype": "bfloat16", "use_flash_attention": False}
    model = harness.build_gpt2(config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert flops.param_count(config["model"], 256) == n
