#!/usr/bin/env bash
# Static-analysis gate: `ds-tpu lint --json` over the whole package (AST
# passes) and the representative engine registry (program passes on
# AOT-lowered HLO). Exits nonzero on any non-allowlisted violation OR any
# stale allowlist entry, so CI fails closed in both directions.
#
# The JSON report lands in /tmp/_lint.json (deterministic bytes — diff two
# runs to prove a change is lint-neutral). Environment is pinned to the same
# 8-virtual-device CPU mesh the tier-1 tests use; `bin/ds-tpu lint` re-pins
# it too, so running this on a TPU host is safe.
#
# tests/unit/test_lint_programs.py::test_shipped_registry_lints_clean and
# tests/unit/test_lint_ast.py::test_package_ast_baseline_is_clean_modulo_shipped_allowlist
# run the same two surfaces inside tier-1; this script is the standalone CLI
# entry for CI pipelines that want the JSON artifact.
set -o pipefail
REPO="$(cd "$(dirname "$0")/.." && pwd)"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"
# deterministic JSON report on stdout (CI log) and in the --out artifact;
# engine-build INFO lines go to stderr so stdout stays parseable
timeout -k 10 300 "$REPO/bin/ds-tpu" lint --json --out /tmp/_lint.json
lint_rc=$?
# comm-sim: two-level ICI+DCN schedule replay — per-level wire-byte manifest
# (incl. the >= 8x compressed cross-slice reduction floor); /tmp/_comm_sim.json
# is byte-stable, diff two runs to prove a change is schedule-neutral
timeout -k 10 300 "$REPO/bin/ds-tpu" comm-sim --out /tmp/_comm_sim.json
comm_rc=$?
# serve-sim: seeded 64-request serving replay, SLO-gated (generous wall-clock
# limits so the gate trips on starvation regressions, not machine speed), with
# the request-trace ledger dumped and its Perfetto export byte-compared
# against the committed golden — any schedule or exporter drift fails CI
timeout -k 10 300 "$REPO/bin/ds-tpu" serve-sim --no-mirror \
    --slo-ttft-ms 60000 --slo-tpot-ms 60000 \
    --dump-ledger /tmp/_serve_ledger.json --json /tmp/_serve_sim.json \
    --output /tmp/_serve_sim_telemetry
serve_rc=$?
if [ "$serve_rc" -eq 0 ]; then
    timeout -k 10 60 "$REPO/bin/ds-tpu" serve-timeline /tmp/_serve_ledger.json \
        -o /tmp/_serve_timeline.trace.json \
    && cmp "$REPO/tests/unit/golden/serve_timeline_64.trace.json" \
           /tmp/_serve_timeline.trace.json
    serve_rc=$?
fi
# prefix-cache gate: seeded shared-system-prompt trace run cache-off AND
# cache-on — token identity plus a STRICT cache-on p50 TTFT improvement in
# the deterministic iteration domain, hit-rate in the JSON report; any
# regression in the cache's ability to buy TTFT fails CI
timeout -k 10 300 "$REPO/bin/ds-tpu" serve-sim --shared-prefix 96 \
    --compare-prefix-cache --slo-ttft-ms 60000 --slo-tpot-ms 60000 \
    --json /tmp/_serve_prefix_cache.json \
    --output /tmp/_serve_prefix_cache_telemetry
cache_rc=$?
# speculative-decoding gate: the same seeded shared-prefix trace run
# speculation-off AND speculation-on (self-draft) — emitted tokens must be
# byte-identical, the speculative run must execute STRICTLY fewer target-model
# steps with target_steps_per_token under the 0.75 budget (PERF.md defines the
# metric), and every spec program must compile exactly once
timeout -k 10 300 "$REPO/bin/ds-tpu" serve-sim --shared-prefix 96 \
    --compare-speculate --spec-steps-budget 0.75 \
    --slo-ttft-ms 60000 --slo-tpot-ms 60000 \
    --json /tmp/_serve_spec.json \
    --output /tmp/_serve_spec_telemetry
spec_rc=$?
# sharded-decode gate: the same seeded 64-request trace (greedy + beam)
# through the 2-way model-axis head-sharded engine AND a single-chip engine —
# outputs must be token-identical and every sharded program must still
# compile exactly once (zero recompiles after warmup)
timeout -k 10 300 "$REPO/bin/ds-tpu" serve-sim --sharding 2 \
    --verify-unsharded --json /tmp/_serve_sharded.json \
    --output /tmp/_serve_sharded_telemetry
shard_rc=$?
# hbm: memory-observatory gate — per-buffer attribution parsed from every
# lint-registry program's entry layout, reconciled against the analytic ZeRO
# memory model within the pinned tolerance ON EVERY ENTRY (`ds-tpu hbm`
# exits 1 on any drift), plus the round-5 OOM-frontier forecast re-derived
# offline (every OOMed PERF.md config predicted infeasible, the winner
# feasible, no compile executed). The stable projection (parsed/modeled
# bytes + verdicts, no XLA-scheduler-dependent watermarks) is byte-compared
# against the committed golden so any attribution drift fails CI.
timeout -k 10 300 "$REPO/bin/ds-tpu" hbm --json --out /tmp/_hbm.json \
    --golden-out /tmp/_hbm_golden.json \
&& cmp "$REPO/tests/unit/golden/hbm_registry_sweep.json" \
       /tmp/_hbm_golden.json \
&& timeout -k 10 60 "$REPO/bin/ds-tpu" hbm --forecast round5 \
    --json --out /tmp/_hbm_round5.json
hbm_rc=$?
# crash-sim: seeded kill-point sweep (mid-save, between shard writes,
# auto-resume selection, mid-decode, post-preemption) — every scenario must
# recover (bit-equal retrain / warm token-identical restart), and the
# recovery transcript is byte-compared against the committed golden so any
# drift in recovery behavior (chunk counts, resume selection) fails CI
timeout -k 10 600 "$REPO/bin/ds-tpu" crash-sim --json /tmp/_crash_sim.json \
&& cmp "$REPO/tests/unit/golden/crash_sim_transcript.json" \
       /tmp/_crash_sim.json
crash_rc=$?
# goodput attribution: fault-injected stalls with known ground-truth
# durations (checkpoint fence, kill/restore replay, watchdog hang, rank
# sleep) — the run-lifecycle ledger must bill each to the correct badput
# class within tolerance, and the boolean transcript is byte-compared
# against the committed golden so any attribution drift fails CI
timeout -k 10 300 "$REPO/bin/ds-tpu" crash-sim --goodput \
    --json /tmp/_goodput_attr.json \
&& cmp "$REPO/tests/unit/golden/goodput_attribution.json" \
       /tmp/_goodput_attr.json
goodput_rc=$?
# hang-sim: deterministic two-host hang/watchdog rehearsal — host 1 stalls in
# a grad-bucket scope, host 0 can only dump via the peer marker; transcript is
# byte-compared against the committed golden, and the merged two-host Perfetto
# timeline (clock-offset-corrected) against its golden, so any drift in
# detection, cross-host signalling, or the merge/export path fails CI
timeout -k 10 120 "$REPO/bin/ds-tpu" hang-sim --json /tmp/_hang_sim.json \
    --dump-dir /tmp/_hang_sim_dumps \
&& cmp "$REPO/tests/unit/golden/hang_sim_transcript.json" /tmp/_hang_sim.json \
&& timeout -k 10 60 "$REPO/bin/ds-tpu" timeline --cluster /tmp/_hang_sim_dumps \
    --run hangsim -o /tmp/_cluster_timeline.trace.json \
&& cmp "$REPO/tests/unit/golden/cluster_timeline_2host.trace.json" \
       /tmp/_cluster_timeline.trace.json
hang_rc=$?
# fleet gate: seeded 3-replica shared-prefix fleet with two mid-flight kills —
# affinity routing must emit byte-identical tokens to round-robin while doing
# STRICTLY fewer prefill chunks and a strictly better fleet p50 TTFT, warm
# failover must beat cold on prefill chunks with no request lost (conservation
# via request-trace identity) and the merged goodput_fleet fraction above the
# pinned floor, the fleet percentiles must stay bitwise-equal the
# single-stream sketch, the SLO gate reads the fleet-MERGED percentiles, and
# the iteration-domain run transcript is byte-compared against the committed
# golden so any routing/failover schedule drift fails CI
timeout -k 10 600 "$REPO/bin/ds-tpu" serve-sim --fleet 3 --requests 24 \
    --shared-prefix 96 --compare-affinity \
    --kill 10:0 --kill 30:1 --compare-cold-failover \
    --fleet-goodput-floor 0.8 \
    --slo-ttft-ms 60000 --slo-tpot-ms 60000 \
    --transcript /tmp/_fleet_transcript.json \
    --json /tmp/_serve_fleet.json \
    --output /tmp/_serve_fleet_telemetry \
&& cmp "$REPO/tests/unit/golden/fleet_transcript_24.json" \
       /tmp/_fleet_transcript.json
fleet_rc=$?
[ "$lint_rc" -ne 0 ] && exit "$lint_rc"
[ "$comm_rc" -ne 0 ] && exit "$comm_rc"
[ "$serve_rc" -ne 0 ] && exit "$serve_rc"
[ "$cache_rc" -ne 0 ] && exit "$cache_rc"
[ "$spec_rc" -ne 0 ] && exit "$spec_rc"
[ "$shard_rc" -ne 0 ] && exit "$shard_rc"
[ "$hbm_rc" -ne 0 ] && exit "$hbm_rc"
[ "$crash_rc" -ne 0 ] && exit "$crash_rc"
[ "$goodput_rc" -ne 0 ] && exit "$goodput_rc"
[ "$hang_rc" -ne 0 ] && exit "$hang_rc"
exit "$fleet_rc"
