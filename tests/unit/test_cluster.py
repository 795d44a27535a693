"""Cluster observatory tests (docs/cluster.md).

Fast single-process coverage of utils/cluster.py: heartbeat skew math and
straggler naming, clock-offset estimation under injected skew, the hang
watchdog (deadline fire, peer-signal fire, once-per-epoch), the exact
histogram-sketch merge behind the fleet serving rollups, the merged
post-mortem/timeline CLIs, and the core guarantee shared with every prior
observatory: the compiled step program is HLO-instruction-identical with
``telemetry.cluster`` enabled. The real 2-process aggregation path is
exercised by the slow rehearsal in test_launcher.py.
"""

import json
import os
import random
import threading
import time

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.serve.request_trace import HistogramSketch
from deepspeed_tpu.utils import cluster
from deepspeed_tpu.utils.cluster import (
    COL_DISPATCH_MS, COL_STEP_MS, HEARTBEAT_FIELDS, ClusterMonitor,
    HangWatchdog, ScopeTracker, assemble_cluster_report, cluster_dump_main,
    derive_cluster_stats, estimate_clock_offsets, find_straggler_host,
    fleet_latency_sketches, fleet_latency_summary, fleet_serving_totals,
    hang_sim_main, named_scope)
from deepspeed_tpu.utils.hlo import (collective_counts, instruction_count,
                                     optimized_hlo)
from deepspeed_tpu.utils.numerics import (FlightRecorder, load_run_bundles,
                                          merge_first_bad, scan_dump_dir_runs)
from simple_model import SimpleModel, random_dataset, simple_config

HIDDEN = 16


def _row(step, wall, step_ms, dispatch_ms=None, ici=0.0, dcn=0.0, hbm=0.0):
    return [float(step), float(wall), float(step_ms),
            float(step_ms if dispatch_ms is None else dispatch_ms),
            float(ici), float(dcn), float(hbm)]


# ------------------------------------------------------------------ skew math
def test_straggler_rule_names_worst_host():
    # 4x the median -> named; the lower-middle median keeps the baseline an
    # actually-fast host
    s = find_straggler_host([10.0, 11.0, 40.0, 10.5], threshold=3.0)
    assert s["host"] == 2 and s["ratio"] == pytest.approx(40.0 / 10.5)
    # under the threshold -> nobody named
    assert find_straggler_host([10.0, 11.0, 12.0], threshold=3.0) is None
    # a single host can never be a straggler relative to itself
    assert find_straggler_host([10.0], threshold=3.0) is None
    # degenerate zero median -> no division, no naming
    assert find_straggler_host([0.0, 0.0], threshold=3.0) is None


def test_straggler_rule_two_host_world():
    """The regression the LOWER-middle median exists for: with 2 hosts the
    upper-middle median would BE the straggler, capping the ratio at 1."""
    s = find_straggler_host([10.0, 40.0], threshold=3.0)
    assert s == {"host": 1, "ratio": 4.0}


def test_derive_cluster_stats_skew_vs_attribution():
    """Skew scalars come from the step wall; the straggler is attributed from
    the host-local dispatch wall (collectives equalise the step wall)."""
    matrix = [_row(5, 1000.0, 200.0, dispatch_ms=10.0),
              _row(5, 1000.1, 201.0, dispatch_ms=160.0, ici=3.0, dcn=7.0)]
    stats = derive_cluster_stats(matrix, threshold=3.0)
    assert stats["step"] == 5 and stats["hosts"] == 2
    assert stats["step_ms_max"] == 201.0
    assert stats["step_skew"] == pytest.approx(201.0 / 200.0)
    assert stats["dispatch_ms_max"] == 160.0
    assert stats["wire_bytes_ici_total"] == 3.0
    assert stats["wire_bytes_dcn_total"] == 7.0
    # the near-equal step walls name nobody; the dispatch walls name host 1
    assert stats["straggler"] == {"host": 1, "ratio": pytest.approx(16.0)}
    assert list(HEARTBEAT_FIELDS).index("step_ms") == COL_STEP_MS
    assert list(HEARTBEAT_FIELDS).index("dispatch_ms") == COL_DISPATCH_MS


def test_clock_offset_estimation_under_injected_skew():
    # host 1 runs 2.5 ms behind host 0, host 2 runs 4 ms ahead; one outlier
    # heartbeat (a delayed snapshot) must not move the median
    hb = []
    for s in range(7):
        w0 = 1000.0 + s
        jitter = 0.5 if s == 3 else 0.0  # host 1's snapshot delayed once
        hb.append([[s, w0, 1, 1, 0, 0, 0],
                   [s, w0 - 0.0025 + jitter, 1, 1, 0, 0, 0],
                   [s, w0 + 0.004, 1, 1, 0, 0, 0]])
    off = estimate_clock_offsets(hb)
    assert off[0] == 0.0
    assert off[1] == pytest.approx(-0.0025)
    assert off[2] == pytest.approx(0.004)
    assert estimate_clock_offsets([]) == []


# ------------------------------------------------------------- sketch algebra
def test_histogram_sketch_merge_is_exact():
    """N shards merged == one stream: same buckets, same counts, bitwise-same
    percentiles — the property the fleet rollup rests on."""
    rng = random.Random(7)
    vals = [rng.uniform(0.2, 800.0) for _ in range(2000)]
    single = HistogramSketch()
    shards = [HistogramSketch() for _ in range(5)]
    for i, v in enumerate(vals):
        single.add(v)
        shards[i % 5].add(v)
    merged = HistogramSketch.merged(
        HistogramSketch.from_dict(s.to_dict()) for s in shards)
    assert merged.count == single.count
    # buckets and counts are bitwise-identical; only the running float `total`
    # differs (summation order), and percentiles never read it
    md, sd = merged.to_dict(), single.to_dict()
    assert md.pop("total") == pytest.approx(sd.pop("total"))
    assert md == sd
    for p in (50, 90, 95, 99):
        assert merged.percentile(p) == single.percentile(p)


def test_histogram_sketch_geometry_mismatch_refused():
    a, b = HistogramSketch(), HistogramSketch(growth=1.1)
    b.add(1.0)
    with pytest.raises(ValueError, match="geometry"):
        a.merge_from(b)


def test_fleet_latency_summary_matches_single_stream():
    """Round-robin a request stream over 4 virtual replicas; the fleet summary
    from their merged sketches must equal the single-stream summary exactly."""
    rng = random.Random(3)
    metrics = ("ttft_ms", "e2e_ms")
    single = {m: HistogramSketch() for m in metrics}
    replicas = [{m: HistogramSketch() for m in metrics} for _ in range(4)]
    for i in range(600):
        for m in metrics:
            v = rng.uniform(1.0, 400.0)
            single[m].add(v)
            replicas[i % 4][m].add(v)
    bundles = [{"latency_sketches": {m: r[m].to_dict() for m in metrics}}
               for r in replicas]
    fleet = fleet_latency_summary(bundles, ps=(50, 95, 99))
    want = {f"{m}_p{p:g}": single[m].percentile(p)
            for m in metrics for p in (50, 95, 99)}
    assert fleet == want


def test_fleet_summary_empty_replica_folds_as_omission():
    """A replica that finished nothing (empty sketches, or the key absent
    entirely, or a None bundle) must fold bitwise-identically to leaving it
    out — an idle fleet slot cannot move the percentiles."""
    rng = random.Random(11)
    busy = HistogramSketch()
    for _ in range(300):
        busy.add(rng.uniform(0.5, 900.0))
    full = {"latency_sketches": {"ttft_ms": busy.to_dict()}}
    empties = [
        {"latency_sketches": {}},
        {"latency_sketches": {"ttft_ms": HistogramSketch().to_dict()}},
        {},
        None,
    ]
    want = fleet_latency_summary([full], ps=(50, 95, 99))
    for empty in empties:
        assert fleet_latency_summary([full, empty], ps=(50, 95, 99)) == want
        assert fleet_latency_summary([empty, full], ps=(50, 95, 99)) == want
    # the empty-sketch fold is exact at the bucket level too, not just at
    # the percentile read-out
    merged = fleet_latency_sketches(
        [full, {"latency_sketches": {"ttft_ms":
                                     HistogramSketch().to_dict()}}])
    md, bd = merged["ttft_ms"].to_dict(), busy.to_dict()
    assert md.pop("total") == pytest.approx(bd.pop("total"))
    assert md == bd


def test_fleet_merge_refuses_mismatched_sketch_geometry():
    """Two replicas tracing with different histogram geometry cannot merge
    exactly — the fold must refuse loudly, never silently rebucket."""
    a, b = HistogramSketch(), HistogramSketch(growth=1.1)
    a.add(5.0)
    b.add(5.0)
    bundles = [{"latency_sketches": {"ttft_ms": a.to_dict()}},
               {"latency_sketches": {"ttft_ms": b.to_dict()}}]
    with pytest.raises(ValueError, match="geometry mismatch"):
        fleet_latency_sketches(bundles)
    with pytest.raises(ValueError, match="geometry mismatch"):
        fleet_latency_summary(bundles)


def test_fleet_serving_totals_sums_spec_counters():
    """The fleet rollup must carry the speculation economics (and lifecycle
    counts) across the fold instead of silently dropping them."""
    bundles = [
        {"totals": {"drafted_tokens": 10, "accepted_draft_tokens": 7,
                    "wasted_draft_tokens": 3, "prefill_tokens": 100},
         "counts": {"finished": 4, "refused": 1, "shed": 0}},
        {"totals": {"drafted_tokens": 5, "accepted_draft_tokens": 5,
                    "wasted_draft_tokens": 0, "decode_tokens": 40},
         "counts": {"finished": 2, "shed": 3}},
        {},          # an idle replica contributes nothing
        None,        # and a dead one even less
    ]
    out = fleet_serving_totals(bundles)
    assert out["totals"] == {"drafted_tokens": 15,
                             "accepted_draft_tokens": 12,
                             "wasted_draft_tokens": 3,
                             "prefill_tokens": 100, "decode_tokens": 40}
    assert out["counts"] == {"finished": 6, "refused": 1, "shed": 3}
    assert fleet_serving_totals([]) == {"totals": {}, "counts": {}}


# ------------------------------------------------------------- scope tracking
def test_scope_tracker_and_named_scope():
    tr = ScopeTracker()
    assert tr.last_scope() is None
    with named_scope("ds_grad_bucket3", tracker=tr):
        pass
    scope = tr.last_scope()
    assert scope["name"] == "ds_grad_bucket3" and scope["age_s"] >= 0.0

    # inside jit, the entry records at TRACE time — and compiles fine
    tr2 = ScopeTracker()

    def f(x):
        with named_scope("ds_fwd_bwd", tracker=tr2):
            return x * 2.0
    np.testing.assert_allclose(jax.jit(f)(np.float32(3.0)), 6.0)
    assert tr2.last_scope()["name"] == "ds_fwd_bwd"


# ------------------------------------------------------------ hang watchdog
def test_watchdog_deadline_fire_dumps_and_marks(tmp_path):
    rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path), host_id=0,
                         run_id="wdtest")
    tr = ScopeTracker()
    tr.enter("ds_grad_bucket1")
    wd = HangWatchdog(recorder=rec, deadline_s=0.05, dump_dir=str(tmp_path),
                      host_id=0, run_id="wdtest", tracker=tr, poll_s=0.01)
    try:
        wd.arm(4)
        deadline = time.monotonic() + 5.0
        while not wd.fired and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        wd.stop()
    assert len(wd.fired) == 1
    fire = wd.fired[0]
    assert fire["origin"] == "deadline" and fire["step"] == 4
    assert fire["last_scope"] == "ds_grad_bucket1"
    assert any("ds-hang-watchdog" in k for k in fire["threads"])
    # the dump landed, run-namespaced, with the hang event inside
    assert rec.dump_count == 1
    bundle = json.load(open(rec.last_dump_path))
    assert bundle["run"] == "wdtest"
    assert any(e["event"] == "hang" for e in bundle["events"])
    # and the peer marker is in place for the other hosts
    assert os.path.exists(tmp_path / "cluster_hang_wdtest_e4_host0.json")


def test_watchdog_peer_signal_fires_without_ping_pong(tmp_path):
    """Host 1's watchdog sees host 0's marker, dumps with origin peer_signal,
    and writes NO marker of its own; re-scanning never re-fires the epoch."""
    rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path), host_id=1,
                         run_id="wdtest")
    wd = HangWatchdog(recorder=rec, deadline_s=3600.0, dump_dir=str(tmp_path),
                      host_id=1, run_id="wdtest", poll_s=0.01)
    marker = tmp_path / "cluster_hang_wdtest_e2_host0.json"
    marker.write_text(json.dumps(
        {"epoch": 2, "step": 2, "host": 0, "last_scope": "ds_fwd_bwd"}))
    try:
        wd.arm(2)  # arming starts the thread; the long deadline never expires
        deadline = time.monotonic() + 5.0
        while not wd.fired and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.05)  # a few more scan cycles: must not double-fire
    finally:
        wd.stop()
    assert len(wd.fired) == 1
    fire = wd.fired[0]
    assert fire["origin"] == "peer_signal" and fire["peer"] == 0
    assert fire["peer_scope"] == "ds_fwd_bwd"
    assert rec.dump_count == 1
    # no host-1 marker: a peer-signalled fire must not signal back
    assert not os.path.exists(tmp_path / "cluster_hang_wdtest_e2_host1.json")
    # a marker for a DIFFERENT run is ignored entirely
    assert wd.run_id == "wdtest"


def test_watchdog_disarm_prevents_fire(tmp_path):
    wd = HangWatchdog(recorder=None, deadline_s=0.03, dump_dir=str(tmp_path),
                      host_id=0, run_id="wdtest2", poll_s=0.01)
    try:
        wd.arm(1)
        wd.disarm()
        time.sleep(0.15)
    finally:
        wd.stop()
    assert wd.fired == []


# ----------------------------------------------------------- cluster monitor
class _FakeMonitor:
    def __init__(self):
        self.scalars = []
        self.events = []

    def add_scalar(self, name, value, step):
        self.scalars.append((name, value, step))

    def event(self, name, payload, step=None):
        self.events.append((name, payload, step))


def test_cluster_monitor_ingest_emits_and_records():
    mon = _FakeMonitor()
    cm = ClusterMonitor(monitor=mon, host_id=0, n_hosts=2, warmup_steps=1,
                        allgather=lambda row: [row])
    # warmup step: stats recorded, straggler suppressed (compile jitter)
    cm.ingest([_row(0, 1000.0, 9.0, dispatch_ms=2.0),
               _row(0, 1000.0, 9.5, dispatch_ms=90.0)], 0)
    assert cm.last_stats["straggler"] is None and not cm.stragglers
    # post-warmup: host 1's dispatch wall names it
    cm.ingest([_row(1, 1001.0, 9.0, dispatch_ms=2.0, ici=10.0),
               _row(1, 1001.0, 9.5, dispatch_ms=90.0, ici=10.0)], 1)
    assert [s["host"] for s in cm.stragglers] == [1]
    names = {n for n, _, _ in mon.scalars}
    assert {"Cluster/hosts", "Cluster/step_ms_max", "Cluster/step_skew",
            "Cluster/wire_bytes_ici_total", "Cluster/straggler_host"} <= names
    host_scalar = [v for n, v, s in mon.scalars
                   if n == "Cluster/straggler_host"]
    assert host_scalar == [-1, 1]  # -1 while nobody is named
    assert [n for n, _, _ in mon.events] == ["cluster_straggler"]
    b = cm.bundle()
    assert b["kind"] == "cluster" and b["n_hosts"] == 2
    assert b["fields"] == list(HEARTBEAT_FIELDS) and len(b["heartbeats"]) == 2
    s = cm.summary()
    assert s["straggler_host"] == 1 and s["heartbeats"] == 2
    cm.stop()


def test_cluster_monitor_non_rank0_stays_silent():
    mon = _FakeMonitor()
    cm = ClusterMonitor(monitor=mon, host_id=1, n_hosts=2, warmup_steps=0,
                        allgather=lambda row: [row])
    cm.ingest([_row(0, 1000.0, 9.0), _row(0, 1000.0, 9.5)], 0)
    assert mon.scalars == []  # every host derives, only host 0 emits
    assert cm.last_stats is not None
    cm.stop()


# ------------------------------------------------- dump scanning / reporting
def _write_dump(dirpath, name, bundle):
    with open(os.path.join(dirpath, name), "w") as f:
        json.dump(bundle, f)


def test_scan_dump_dir_groups_runs_and_legacy(tmp_path):
    d = str(tmp_path)
    _write_dump(d, "numerics_dump_runA_host0_0.json", {"host": 0})
    _write_dump(d, "numerics_dump_runA_host1_0.json", {"host": 1})
    _write_dump(d, "numerics_dump_host0_0.json", {"host": 0})  # legacy
    _write_dump(d, "not_a_dump.json", {})
    runs = scan_dump_dir_runs(d)
    assert sorted(runs) == ["", "runA"]
    assert [(e["host"], e["index"]) for e in runs["runA"]] == [(0, 0), (1, 0)]

    run_key, by_host = load_run_bundles(d, run="runA")
    assert run_key == "runA" and sorted(by_host) == [0, 1]
    # torn dump: skipped, the intact earlier dump still loads
    _write_dump(d, "numerics_dump_runA_host1_1.json", {"host": 1})
    with open(os.path.join(d, "numerics_dump_runA_host1_1.json"), "w") as f:
        f.write('{"torn": tru')
    _, by_host = load_run_bundles(d, run="runA")
    assert by_host[1] == {"host": 1}


def test_merge_first_bad_picks_min_step_then_host():
    assert merge_first_bad({0: {"first_bad_step": 7},
                            1: {"first_bad_step": 5},
                            2: {"first_bad_step": 5}}) == (5, 1)
    assert merge_first_bad({0: {"first_bad_step": None}}) == (None, None)


def test_assemble_cluster_report_orders_stalls_by_corrected_time(tmp_path):
    """Host 1's clock runs behind; with offsets applied its earlier raw
    timestamp must still order AFTER host 0's genuinely-earlier stall."""
    # heartbeat history says host 1's wall reads 2 s behind host 0's
    heartbeats = [[_row(s, 1000.0 + s, 10.0), _row(s, 998.0 + s, 10.0)]
                  for s in range(4)]

    def bundle(host, t_fire):
        b = {
            "host": host,
            "events": [{"event": "hang", "step": 3, "time": t_fire,
                        "payload": {"origin": "deadline", "epoch": 3,
                                    "step": 3, "host": host,
                                    "last_scope": f"scope{host}"}}],
        }
        if host == 0:
            b["cluster"] = {"heartbeats": heartbeats}
        return b
    by_host = {0: bundle(0, 100.0), 1: bundle(1, 99.0)}
    report = assemble_cluster_report(by_host, "runX")
    # corrected: host0 at 100.0, host1 at 99.0 - (-2.0) = 101.0 -> host 0 first
    assert report["first_stall"]["host"] == 0
    assert report["first_stall"]["scope"] == "scope0"
    assert report["run"] == "runX" and report["n_dumps"] == 2


def test_a_cluster_report_folds_no_alerts_block():
    """Dumps written before the alert plane left may carry an ``alerts`` block: the report reads
    past it, and its keys are the ones the hang-sim golden holds."""
    by_host = {0: {"host": 0, "events": [], "alerts": {"fired": [{"rule": "mfu_drop", "step": 3}]}},
               1: {"host": 1, "events": []}}
    report = assemble_cluster_report(by_host, "runY")
    assert "alerts_fleet" not in report
    assert set(report) == {"version", "kind", "run", "hosts", "n_dumps", "hangs", "first_stall",
                           "first_bad_step", "first_bad_host", "stragglers", "goodput"}
    golden = json.load(open(os.path.join(os.path.dirname(__file__), "golden", "hang_sim_transcript.json")))
    assert set(golden["report"]) == set(report)


# ----------------------------------------------------------------- the CLIs
def _run_hang_sim(tmp_path, tag):
    out = str(tmp_path / f"transcript_{tag}.json")
    dumps = str(tmp_path / f"dumps_{tag}")
    rc = hang_sim_main(["--json", out, "--dump-dir", dumps,
                        "--deadline", "0.1"])
    assert rc == 0
    return out, dumps


def test_hang_sim_deterministic_and_cli_roundtrip(tmp_path, capsys):
    """Two hang-sim runs produce byte-identical transcripts (the property the
    lint gate's golden compare rests on), and cluster-dump over the produced
    dumps names the stalled host and the collective scope it died in."""
    out1, dumps1 = _run_hang_sim(tmp_path, "a")
    out2, _ = _run_hang_sim(tmp_path, "b")
    assert open(out1, "rb").read() == open(out2, "rb").read()
    t = json.load(open(out1))
    assert t["ok"] and t["detected_within_deadline"]
    assert t["stalled_host"] == 1 and t["stall_step"] == 3
    assert [d["host"] for d in t["dumps"]] == [0, 1]
    assert t["report"]["first_stall"] == {
        "host": 1, "step": 3, "scope": "ds_grad_bucket1", "origin": "deadline"}
    capsys.readouterr()

    rc = cluster_dump_main([dumps1])
    text = capsys.readouterr().out
    assert rc == 0
    assert "first stall    : host 1 at step 3 in scope 'ds_grad_bucket1'" in text

    rc = cluster_dump_main([dumps1, "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0 and rep["first_stall"]["host"] == 1

    # merged two-host timeline: one track group per host, host 1 shifted by
    # the heartbeat-estimated clock offset
    from deepspeed_tpu.utils.pipeline_trace import timeline_main
    trace_out = str(tmp_path / "cluster.trace.json")
    rc = timeline_main(["--cluster", dumps1, "--run", "hangsim",
                       "-o", trace_out])
    capsys.readouterr()
    assert rc == 0
    trace = json.load(open(trace_out))
    pids = {ev["pid"] for ev in trace["traceEvents"] if "pid" in ev}
    assert pids == {0, 1}
    # host 1's simulated wall reads 1.5 ms early -> offset -1500 us
    assert trace["otherData"]["clock_offsets_us"] == {"0": 0, "1": -1500}


def test_cluster_dump_empty_dir_is_an_error(tmp_path, capsys):
    assert cluster_dump_main([str(tmp_path)]) == 2
    assert "no flight-recorder dumps" in capsys.readouterr().err


def test_inspect_dump_directory_mode(tmp_path, capsys):
    """inspect-dump pointed at a DIRECTORY merges one run's per-host dumps:
    first bad step/host + a one-liner per host."""
    from deepspeed_tpu.utils.numerics import inspect_dump_main
    d = str(tmp_path)
    _write_dump(d, "numerics_dump_runZ_host0_0.json",
                {"host": 0, "first_bad_step": None, "events": [], "steps": []})
    _write_dump(d, "numerics_dump_runZ_host1_0.json",
                {"host": 1, "first_bad_step": 6, "events": [], "steps": []})
    rc = inspect_dump_main([d, "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert rep["run"] == "runZ"
    assert rep["first_bad_step"] == 6 and rep["first_bad_host"] == 1
    assert sorted(rep["hosts"]) == ["0", "1"]


# ----------------------------------------------------- engine integration
def _build(**overrides):
    model = SimpleModel(HIDDEN)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=simple_config(**overrides))
    return eng


def _batch(n=8, seed=0):
    data = random_dataset(n, HIDDEN, seed=seed)
    return (np.stack([d[0] for d in data]), np.stack([d[1] for d in data]))


def test_engine_cluster_heartbeats_single_process(tmp_path):
    """telemetry.cluster on a single-process engine: heartbeats accumulate
    (the allgather shortcuts to the local row), Cluster/* scalars land in the
    monitor stream, and the dispatch wall is a real sub-interval of the step
    wall."""
    eng = _build(telemetry={
        "enabled": True, "output_path": str(tmp_path), "job_name": "cl",
        "cluster": {"enabled": True, "hang_deadline_s": 30.0,
                    "dump_dir": str(tmp_path / "dumps"), "warmup_steps": 1}})
    assert eng._cluster is not None
    xs, ys = _batch()
    for _ in range(3):
        loss = eng(xs, ys)
        eng.backward(loss)
        eng.step()
    cm = eng._cluster
    assert len(cm.heartbeats) == 3
    assert all(len(m) == 1 and len(m[0]) == len(HEARTBEAT_FIELDS)
               for m in cm.heartbeats)
    assert cm.summary()["straggler_host"] is None  # one host, no straggler
    assert cm.watchdog is not None and cm.watchdog.fired == []
    # dispatch wall <= step wall, both positive once steps flowed
    assert eng.telemetry.last_step_ms > 0
    assert 0 <= eng.telemetry.last_dispatch_ms <= eng.telemetry.last_step_ms
    cm.stop()
    eng.telemetry.close()
    scal = open(os.path.join(str(tmp_path), "cl", "scalars.jsonl")).read()
    assert "Cluster/hosts" in scal and "Cluster/step_skew" in scal


def test_cluster_enabled_is_hlo_identical(tmp_path):
    """The core observatory guarantee: enabling telemetry.cluster changes
    NOTHING in the compiled step program — identical instruction and
    collective counts (everything the plane does is host-side)."""
    eng_off = _build(telemetry={"enabled": True,
                                "output_path": str(tmp_path / "off")})
    eng_on = _build(telemetry={
        "enabled": True, "output_path": str(tmp_path / "on"),
        "cluster": {"enabled": True, "hang_deadline_s": 30.0,
                    "dump_dir": str(tmp_path / "dumps")}})
    xs, ys = _batch()
    hlos = []
    for eng in (eng_off, eng_on):
        hlos.append(optimized_hlo(eng._jit_loss_and_grad, eng.params,
                                  eng.scaler_state.cur_scale, xs, ys))
    assert instruction_count(hlos[0]) > 0
    assert instruction_count(hlos[0]) == instruction_count(hlos[1])
    assert collective_counts(hlos[0]) == collective_counts(hlos[1])
    if eng_on._cluster is not None:
        eng_on._cluster.stop()
