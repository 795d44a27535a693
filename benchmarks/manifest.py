"""The benchmark's files, found by name.

``BENCHMARK.json`` lists cells, configurations and metrics by name; everything that
belongs to one of them sits in a file of that name under the benchmark directory:

    cells/<cell>.json            configs/<config>.json        traffic/<traffic>.json
    generators/<generator>.py    runners/<runner>.py          layer_metrics/<metric>.py
    reference/<model>.py         reference/tolerances.json

A later PR adds a cell, a configuration, a traffic mix or a per-layer metric by adding
files and one entry to ``BENCHMARK.json``; nothing here names any of them.
"""

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class Manifest:
    """``BENCHMARK.json`` and the files beside it. ``bench_dir`` holds the data and
    plug-in directories; ``manifest_path`` defaults to the file one level above."""

    def __init__(self, bench_dir=BENCH_DIR, manifest_path=None):
        self.bench_dir = bench_dir
        self.path = manifest_path or os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json")
        with open(self.path) as f:
            self.doc = json.load(f)

    # ---------------------------------------------------------------- data files
    def _json(self, kind, name):
        path = os.path.join(self.bench_dir, kind, name + ".json")
        with open(path) as f:
            return json.load(f)

    def workload(self, name):
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}")

    def cell(self, name):
        """The cell's own file, checked against its ``BENCHMARK.json`` entry."""
        entry, cell = self.workload(name), self._json("cells", name)
        for key in ("config", "traffic", "chips"):
            if cell[key] != entry[key]:
                raise ValueError(f"cell {name}: {key} is {cell[key]!r} in its file and "
                                 f"{entry[key]!r} in BENCHMARK.json")
        return cell

    def config(self, name):
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(os.path.join(os.path.dirname(self.path), c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r} in {self.path}")

    def traffic(self, name):
        return self._json("traffic", name)

    # ------------------------------------------------------------------ plug-ins
    def _module(self, kind, name):
        path = os.path.join(self.bench_dir, kind, name + ".py")
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no {kind} file {path}")
        ident = "cellbench_%s_%s" % (kind, re.sub(r"[^A-Za-z0-9_]", "_", name))
        spec = importlib.util.spec_from_file_location(ident, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def generator(self, name):
        """``generators/<name>.py``: ``generate(traffic, seed, **sizes)``."""
        return self._module("generators", name).generate

    def runner(self, name):
        """``runners/<name>.py``: ``run(ctx) -> record``."""
        return self._module("runners", name).run

    def reader(self, metric):
        """``layer_metrics/<metric>.py``: ``read(record) -> number or None``."""
        return self._module("layer_metrics", metric).read

    def reference(self, name):
        """``reference/<name>.py``: the configuration's plain float32 reference."""
        return self._module("reference", name)

    def tolerance(self, name):
        return self._json("reference", "tolerances")[name]["value"]

    # ------------------------------------------------------------------- metrics
    def metrics_of(self, section, workload):
        """The metrics of ``end_to_end`` or ``per_layer`` that ``workload`` reports."""
        return [m for m in self.doc[section]
                if "workloads" not in m or workload in m["workloads"]]


def check(manifest):
    """Every rule of the contract that can be checked without a run; returns the
    list of faults (empty when the manifest holds)."""
    doc, faults = manifest.doc, []
    keys = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    if set(doc) != keys:
        faults.append(f"top-level keys {sorted(doc)} != {sorted(keys)}")
    if not (isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51):
        faults.append("run_seconds outside 1..51")
    root = os.path.dirname(manifest.path)

    def name_ok(what, value):
        if not NAME_RE.match(str(value)):
            faults.append(f"{what} {value!r} is not a name")

    seen = set()
    for c in doc["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            faults.append(f"config {c.get('name')}: keys {sorted(c)}")
        name_ok("config", c["name"])
        for key in c["reduced"]:
            name_ok("reduced key", key)
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in doc["paths"]):
            faults.append(f"config {c['name']}: file {c['file']} outside paths")
        if not os.path.isfile(os.path.join(root, c["file"])):
            faults.append(f"config {c['name']}: no file {c['file']}")
        if c["name"] in seen:
            faults.append(f"config {c['name']} twice")
        seen.add(c["name"])
    used, pairs, cells = set(), set(), set()
    for w in doc["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            faults.append(f"workload {w.get('name')}: keys {sorted(w)}")
        for key in ("name", "config", "traffic"):
            name_ok(key, w[key])
        if w["chips"] not in (1, 4):
            faults.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            faults.append(f"workload {w['name']}: why is not one line of 1..200")
        if w["config"] not in seen:
            faults.append(f"workload {w['name']}: unknown config {w['config']}")
        if (w["config"], w["traffic"]) in pairs or w["name"] in cells:
            faults.append(f"workload {w['name']}: listed twice")
        pairs.add((w["config"], w["traffic"]))
        cells.add(w["name"])
        used.add(w["config"])
    if seen - used:
        faults.append(f"configurations no cell uses: {sorted(seen - used)}")
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    if four > max(1, len(doc["workloads"]) // 4):
        faults.append(f"{four} of {len(doc['workloads'])} cells ask for four chips")

    names, e2e = set(), {}
    for m in doc["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound", "source"}:
            faults.append(f"end-to-end {m.get('name')}: keys {sorted(m)}")
        if m["source"] not in ("host_clock", "device_trace"):
            faults.append(f"end-to-end {m['name']}: source {m['source']}")
        if not 0 < m["bound"] <= 0.1:
            faults.append(f"end-to-end {m['name']}: bound {m['bound']}")
        e2e[m["name"]] = set(m.get("workloads", cells))
    if "setup_s" not in e2e or e2e.get("setup_s") != cells:
        faults.append("setup_s is not an end-to-end metric of every cell")
    for m in doc["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source", "layer", "moves"}:
            faults.append(f"per-layer {m.get('name')}: keys {sorted(m)}")
        if m["source"] not in SOURCES:
            faults.append(f"per-layer {m['name']}: source {m['source']}")
        where = set(m.get("workloads", cells))
        if m["moves"] not in e2e:
            faults.append(f"per-layer {m['name']}: moves {m['moves']}, not an end-to-end metric")
        elif not where <= e2e[m["moves"]]:
            faults.append(f"per-layer {m['name']}: {m['moves']} is not reported in "
                          f"{sorted(where - e2e[m['moves']])}")
    for m in doc["end_to_end"] + doc["per_layer"]:
        name_ok("metric", m["name"])
        if not UNIT_RE.match(m["unit"]):
            faults.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            faults.append(f"metric {m['name']}: better {m['better']!r}")
        if m["name"] in names:
            faults.append(f"metric {m['name']} twice")
        names.add(m["name"])
        for w in m.get("workloads", ()):
            if w not in cells:
                faults.append(f"metric {m['name']}: unknown workload {w}")
    for w in doc["workloads"]:
        own = [m for m in doc["end_to_end"] if w["name"] in e2e[m["name"]]]
        if len(own) < 2:
            faults.append(f"workload {w['name']}: no end-to-end metric besides setup_s")
        if not manifest.metrics_of("per_layer", w["name"]):
            faults.append(f"workload {w['name']}: no per-layer metric")
    return faults
