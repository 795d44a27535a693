"""Static no-host-sync guard for the observability tier (utils/).

The telemetry, numerics and pipeline-trace subsystems promise to add NO host
synchronization to the training step beyond the loss fetch the engine already
performs. That promise is easy to erode one innocent-looking ``device_get``
at a time, so this test enforces it STATICALLY — and since PR 6 it is a thin
wrapper over the lint framework's :class:`HostSyncPass` (the same pass
``ds-tpu lint`` runs), pinned to the same shipped allowlist, so the guard and
the linter cannot drift. Coverage is ALL of ``deepspeed_tpu/utils/`` plus the
serving request-trace ledger (``serve/request_trace.py``), matching the lint
CLI's host-sync surface exactly.
"""

import os

import deepspeed_tpu
from deepspeed_tpu.lint.ast_passes import HostSyncPass, run_ast_passes
from deepspeed_tpu.lint.model import Allowlist

PKG = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
ROOT = os.path.dirname(PKG)
UTILS = os.path.join(PKG, "utils")

# the complete sanctioned set — identical to deepspeed_tpu/lint/allowlist.json
PINNED = {
    "ast-host-sync:device-get:deepspeed_tpu/utils/telemetry.py::TelemetrySession.end_step",
    "ast-host-sync:np-asarray:deepspeed_tpu/utils/telemetry.py::_abstract_signature",
}


def _utils_files():
    out = []
    for dirpath, _dirs, files in os.walk(UTILS):
        out += [os.path.join(dirpath, f) for f in files if f.endswith(".py")]
    assert len(out) >= 8, "utils/ sweep looks truncated"
    out.append(os.path.join(PKG, "serve", "request_trace.py"))
    return sorted(out)


def _scan():
    return run_ast_passes(_utils_files(), (HostSyncPass(),), root=ROOT)


def test_utils_sync_allowlist_is_exact():
    """Every host-sync primitive in utils/ must be one of the two sanctioned
    occurrences; anything new is a failure, not a code-review hope."""
    vids = {v.vid for v in _scan()}
    assert vids <= PINNED, f"new host-sync primitive introduced: {vids - PINNED}"
    # the sanctioned fetch must still exist (the scan itself stays honest)
    assert ("ast-host-sync:device-get:deepspeed_tpu/utils/telemetry.py"
            "::TelemetrySession.end_step") in vids


def test_guard_agrees_with_shipped_allowlist():
    """The CLI's allowlist.json and this guard pin the SAME facts: every
    host-sync vid found in utils/ must be covered by the shipped allowlist,
    and the shipped host-sync entries must all still match something."""
    allow = Allowlist.load(os.path.join(PKG, "lint", "allowlist.json"))
    for v in _scan():
        assert allow.match(v.vid) is not None, f"not in shipped allowlist: {v.vid}"
    stale = [g for g in allow.unused() if g.startswith("ast-host-sync:")]
    assert stale == [], f"stale host-sync allowlist entries: {stale}"


def test_pass_reports_occurrence_counts():
    """end_step holds two sanctioned fetch sites; the pass dedupes to one
    violation per (rule, subject) and carries the count in details."""
    by_vid = {v.vid: v for v in _scan()}
    v = by_vid["ast-host-sync:device-get:deepspeed_tpu/utils/telemetry.py"
               "::TelemetrySession.end_step"]
    assert v.details["occurrences"] >= 1


def test_guard_scans_the_real_files():
    files = _utils_files()
    for name in ("telemetry.py", "numerics.py", "pipeline_trace.py", "hlo.py",
                 os.path.join("serve", "request_trace.py")):
        assert any(f.endswith(name) for f in files), f"{name} missing from sweep"


def test_request_trace_ledger_is_sync_free():
    """The serving request tracer sits INSIDE the decode loop, so unlike
    end_step it gets no sanctioned fetch at all: zero host-sync primitives."""
    rt = os.path.join(PKG, "serve", "request_trace.py")
    vids = {v.vid for v in run_ast_passes([rt], (HostSyncPass(),), root=ROOT)}
    assert vids == set(), f"host-sync primitive in the request ledger: {vids}"
