"""Guard: the tier-1 gate can never pick up tests/perf measurement scripts.

`scripts/tier1.sh` encodes the ROADMAP.md tier-1 command, which collects
`tests/` with pytest's default file patterns (``test_*.py`` / ``*_test.py``).
The perf scripts under tests/perf/ are benchmark drivers — minutes-to-hours of
wall clock, some requiring a real TPU — and keep deliberately non-matching
names so tier-1 never imports them. This suite pins both halves of that
contract: the script stays in sync with ROADMAP.md, and no file under
tests/perf/ matches a collectable pattern.
"""

import re
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def test_tier1_script_matches_roadmap_verbatim():
    roadmap = (REPO / "ROADMAP.md").read_text()
    m = re.search(r"\*\*Tier-1 verify:\*\* `(.+?)`\n", roadmap, re.DOTALL)
    assert m, "ROADMAP.md lost its 'Tier-1 verify:' line"
    script_lines = [ln for ln in (REPO / "scripts" / "tier1.sh").read_text().splitlines()
                    if ln and not ln.startswith("#")]
    assert script_lines == [m.group(1)], (
        "scripts/tier1.sh drifted from the ROADMAP.md tier-1 command — "
        "update them together, verbatim")


def test_perf_scripts_never_collected_by_tier1():
    perf = REPO / "tests" / "perf"
    offenders = [p.name for p in perf.glob("*.py")
                 if p.name.startswith("test_") or p.name.endswith("_test.py")]
    assert not offenders, (
        f"tests/perf/ files {offenders} match pytest's default collection "
        f"patterns and would run (or import-crash) inside the tier-1 gate — "
        f"rename them (the perf drivers are invoked directly, not collected)")


def test_chip_drivers_stay_out_of_tier1():
    """The chip-only probes PERF.md cites (minutes of chip time each) exist as
    direct-invocation drivers and never under a collectable name."""
    perf = REPO / "tests" / "perf"
    for name in ("flash_sweep.py", "moe_exchange_probe.py", "olmoe_precision_probe.py"):
        assert (perf / name).exists()
        assert not (perf / f"test_{name}").exists(), (
            f"{name} must not be collectable: tier-1 would sys.exit on the CPU mesh")


def test_request_trace_suite_is_collectable_and_golden_pinned():
    """The serving observatory's acceptance tests live INSIDE tier-1 (CPU-only,
    seconds of wall clock), so the suite file must match a collectable name and
    its byte-for-byte golden must ship next to the pipeline-trace goldens."""
    unit = REPO / "tests" / "unit"
    assert (unit / "test_request_trace.py").exists()
    golden = unit / "golden" / "serve_timeline_64.trace.json"
    assert golden.exists(), "serve-timeline golden missing — regenerate with " \
        "`ds-tpu serve-sim --no-mirror --dump-ledger L.json && " \
        "ds-tpu serve-timeline L.json -o <golden>`"
    import json
    trace = json.loads(golden.read_text())
    assert trace["otherData"]["generator"] == "ds-tpu serve-timeline"
    assert len(trace["traceEvents"]) > 1000    # a real 64-request timeline


def test_perf_directory_has_no_conftest_collection_override():
    """A conftest.py in tests/perf/ could re-add collection via collect_ignore
    tricks or python_files overrides; keep the directory plugin-free."""
    ini_like = [p.name for p in (REPO / "tests" / "perf").glob("conftest.py")]
    assert not ini_like, "tests/perf/conftest.py could alter tier-1 collection"
