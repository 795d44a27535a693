"""The suite's seconds a file from a run's junit XML, and what ``tests/conftest.py``'s order makes
of them under xdist's ``--dist load``, without running anything.

    python tests/perf/suite_seconds.py /tmp/_t1.xml [--workers 6] [--table]

Prints the case-seconds, the longest files, and the run's length as xdist 3.8's ``LoadScheduling``
would hand the tests out in ``conftest._in_chunks``' order (every worker first gets a chunk of
consecutive tests, a quarter of an even share; a worker is topped up, by half of what is left over
twice the workers, when it has fewer than two slow tests left); ``--table`` prints the files of a
minute or more as the ``_SECONDS`` literal. The ids come from the XML itself, so the tree need not
be the one that ran. With ``-p tests.perf.suite_seconds`` (from the repo's root) the
same file is a pytest plugin that appends ``{id, seconds, compiles, compile_seconds}`` a test to
``$SUITE_SECONDS_OUT`` (``jax.monitoring``'s ``/jax/core/compile/backend_compile_duration``).
"""

import argparse
import collections
import heapq
import importlib.util
import json
import os
import time
import xml.etree.ElementTree as ET

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def read(xml):
    """``[((file, test), seconds)]`` file by file as pytest collects them (the XML has the tests as
    they ended; which short tests fill the first chunks is a tenth of the run's length)."""
    cases = sorted(ET.parse(xml).getroot().iter("testcase"), key=lambda case: case.get("classname"))
    return [((case.get("classname").split(".")[-1] + ".py", case.get("name")), float(case.get("time")))
            for case in cases]


def run_length(order, seconds, workers):
    """Seconds until the last worker ends, by ``xdist.scheduler.load.LoadScheduling``'s rules."""
    pending = list(range(len(order)))
    queue = [collections.deque() for _ in range(workers)]

    def send(worker, count):
        queue[worker].extend(pending[:count])
        del pending[:count]

    for worker in range(workers):
        send(worker, max(len(order) // workers // 4, 2))
    ends = [(seconds[order[q[0]]], w) for w, q in enumerate(queue) if q]
    heapq.heapify(ends)
    last = 0.0
    while ends:
        last, worker = heapq.heappop(ends)
        took = seconds[order[queue[worker].popleft()]]
        left = len(queue[worker])
        if pending and left < max(2, len(pending) // workers // 4) and not (took >= 0.1 and left >= 2):
            send(worker, max(2, len(pending) // workers // 2) - left)
        if queue[worker]:
            heapq.heappush(ends, (last + seconds[order[queue[worker][0]]], worker))
    return last


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("xml")
    ap.add_argument("--workers", type=int, default=6)
    ap.add_argument("--table", action="store_true")
    args = ap.parse_args()
    spec = importlib.util.spec_from_file_location("_suite_conftest", os.path.join(HERE, "..", "conftest.py"))
    conf = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(conf)
    cases = read(args.xml)
    seconds = dict(cases)
    by_file = collections.Counter()
    for (file, _), took in cases:
        by_file[file] += took
    total = sum(by_file.values())
    print(f"{len(cases)} tests, {total:.0f} case-seconds, {total / args.workers:.0f} a worker")
    for file, took in by_file.most_common(12):
        print(f"  {took:7.1f}  {file}  (the table: {conf._SECONDS.get(file, '-')})")
    order = conf._in_chunks([case for case, _ in cases], lambda case: case[0], args.workers)
    print(f"--dist load in conftest's order: {run_length(order, seconds, args.workers):.0f} s + start-up")
    if args.table:
        long = [(f, int(round(s / 5) * 5)) for f, s in by_file.most_common() if s >= 60]
        print("_SECONDS = {" + ", ".join(f'"{f}": {s}' for f, s in long) + "}")


# ---------------------------------------------------------------- as a pytest plugin
_compiles = {"count": 0, "seconds": 0.0}


def _on_duration(event, duration, **kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles["count"] += 1
        _compiles["seconds"] += duration


def pytest_configure(config):
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    since, count, seconds = time.time(), _compiles["count"], _compiles["seconds"]
    yield
    with open(os.environ.get("SUITE_SECONDS_OUT", "suite_seconds.jsonl"), "a") as f:
        f.write(json.dumps({"id": item.nodeid, "seconds": round(time.time() - since, 2),
                            "compiles": _compiles["count"] - count,
                            "compile_seconds": round(_compiles["seconds"] - seconds, 2)}) + "\n")


if __name__ == "__main__":
    main()
