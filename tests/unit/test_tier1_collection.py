"""Guard: the tier-1 gate can never pick up tests/perf measurement scripts.

`scripts/tier1.sh` encodes the ROADMAP.md tier-1 command, which collects
`tests/` with pytest's default file patterns (``test_*.py`` / ``*_test.py``).
The perf scripts under tests/perf/ are benchmark drivers — minutes-to-hours of
wall clock, some requiring a real TPU — and keep deliberately non-matching
names so tier-1 never imports them. This suite pins both halves of that
contract: the script stays in sync with ROADMAP.md, and no file under
tests/perf/ matches a collectable pattern.
"""

import itertools
import re
from pathlib import Path

import pytest

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


def _conftest():
    import importlib.util
    spec = importlib.util.spec_from_file_location("_suite_conftest", REPO / "tests" / "conftest.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("workers, short, a_file", [(6, 900, None), (6, 300, None), (4, 900, None), (2, 60, None),
                                                    (6, 900, 5), (3, 500, 4)])      # every file fits a first chunk: 35 files of 4 in 3 chunks of 53
def test_under_xdist_the_long_files_are_whole_and_apart_in_the_workers_first_chunks(workers, short, a_file):
    """``--dist load`` sends every worker one chunk of consecutive tests before it balances anything
    (a quarter of an even share): ``conftest._in_chunks`` puts each long file whole into one of those
    chunks, the seconds about even, fills the chunks with short tests, and spaces the long files that
    fit none, longest first, evenly through the short tests that are left. Nothing is lost or doubled, and every worker sorts alike."""
    conf = _conftest()
    seconds = conf._SECONDS
    counts = {f: a_file or 3 + 7 * i % 40 for i, f in enumerate(sorted(seconds))}       # few tests a file: all fit
    counts["test_olmoe.py"] = a_file or 107
    items = [(f, i) for f in sorted(seconds, reverse=True) for i in range(counts[f])]
    items += [(f"test_short_{i % 37}.py", i) for i in range(short)]
    name = lambda item: item[0]      # noqa: E731
    order = conf._in_chunks(list(items), name, workers)
    assert sorted(order) == sorted(items) and order == conf._in_chunks(list(items), name, workers)
    chunk = max(len(items) // workers // 4, 2)
    first = [order[w * chunk:(w + 1) * chunk] for w in range(workers)]
    placed = {}
    for w, tests in enumerate(first):
        for f in {name(t) for t in tests} & set(seconds):
            assert sum(name(t) == f for t in tests) == counts[f], (f, "split over a chunk's edge")
            placed[f] = w
    load = [sum(seconds[f] for f, at in placed.items() if at == w) for w in range(workers)]
    late = [f for f in seconds if f not in placed]
    # a file is left for later only where no chunk had room for it, and the chunks' seconds differ by
    # less than the longest file that went in after the first round
    ahead = lambda f, w: sum(counts[g] for g, at in placed.items() if at == w and seconds[g] >= seconds[f])      # noqa: E731
    assert all(counts[f] > chunk - min(ahead(f, w) for w in range(workers)) for f in late)
    assert bool(late) == (a_file is None)
    if not late:
        assert max(load) - min(load) <= sorted(seconds.values())[-workers - 1]
    # the late files whole, longest first, the same number of short tests after each, and a fifth of
    # the short tests after the last of them
    rest = [name(t) for t in order[workers * chunk:]]
    runs = [(f, len(list(run))) for f, run in itertools.groupby("short" if f not in seconds else f for f in rest)]
    assert [(f, n) for f, n in runs if f != "short"] == \
        [(f, counts[f]) for f in sorted(late, key=lambda f: (-seconds[f], f))]
    if late:
        gaps = [n for f, n in runs if f == "short"]
        left = len(rest) - sum(counts[f] for f in late)
        assert len(set(gaps[:-1])) == 1 and gaps[-1] >= gaps[0] + left // 5 and gaps[0] == left * 4 // 5 // len(late)


def test_every_file_the_order_names_is_in_the_suite():
    """A file renamed or removed leaves a stale line in ``conftest._SECONDS``."""
    conf = _conftest()
    here = {p.name for p in (REPO / "tests").rglob("*.py")}
    assert set(conf._SECONDS) <= here and all(s >= 60 for s in conf._SECONDS.values())
