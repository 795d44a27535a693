"""Device seconds of the expert layers, by ``ds_moe_*`` scope.

``program_spans`` names a step's parts by ``ds_embed|attn|mlp|loss``; the expert layer sits
inside ``ds_mlp`` and its own scopes (``ds_moe_router``, ``ds_moe_dispatch``, ``ds_moe_experts``,
``ds_moe_combine``, ``ds_moe_exchange``) nest under it. This module reads them from the same
trace with what ``program_spans`` and ``trace_reduce`` offer: the step programs' catalog
(instruction -> scope path), the assignment of device operations to programs, and the
window. An operation counts under the innermost ``ds_moe_*`` scope of its path, forward
and backward alike, each device on its own and then averaged, as they average. A program
without such scopes (GPT-2's, or a parent commit's) gives an empty table and every reader
returns None.
"""

import json
import os
import re

from benchmarks import program_spans
from benchmarks import trace_reduce as tr

SCOPE_RE = re.compile(r"ds_moe_[a-z]+")
EXCHANGE = "ds_moe_exchange"
EXPERTS = "ds_moe_experts"
OUT_NAME = "moe_spans.last.json"


def analyse(record):
    """``{"scope_s": {scope: seconds}, "exchange_collective_s": seconds, "window_s": s}``
    averaged over the devices, kept on the record; None without a trace or a catalog."""
    if "moe_spans" in record:
        return record["moe_spans"]
    record["moe_spans"] = result = _analyse(record)
    if result is not None:         # the whole table, for PERF.md, beside program_spans' own
        out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, OUT_NAME), "w") as f:
            json.dump(result, f, indent=1)
    return result


def _analyse(record):
    trace = record.get("trace")
    base = program_spans.analyse(record)
    if trace is None or base is None or not trace.devices or trace.window_s <= 0:
        return None
    rec = program_spans.program_recorder()
    try:
        catalog = rec.programs(base["engine"])
    except Exception:          # the catalog compiles; a traced run must still print its line
        return None
    if not catalog:
        return None
    scope_s, collective_s = {}, 0.0
    for events in trace.devices.values():
        events = sorted(events, key=lambda e: e[1])
        programs = program_spans.assign_programs(events, catalog)
        for (name, start, dur), program in zip(events, programs):
            if program is None:
                continue
            path = catalog[program]["ops"].get(program_spans.instruction(name), "")
            found = SCOPE_RE.findall(path)
            if not found:
                continue
            seconds = tr.measure(tr.clip([[start, start + dur]], trace.lo, trace.hi))
            scope_s[found[-1]] = scope_s.get(found[-1], 0.0) + seconds
            if found[-1] == EXCHANGE and tr.is_collective(name):
                collective_s += seconds
    if not scope_s:
        return None
    n = len(trace.devices)
    return {"scope_s": {k: v / n for k, v in sorted(scope_s.items())},
            "exchange_collective_s": collective_s / n, "window_s": trace.window_s}


def share(record, scopes=None):
    """Percent of the window under ``scopes`` (all ``ds_moe_*`` if None)."""
    result = analyse(record)
    if result is None:
        return None
    seconds = sum(v for k, v in result["scope_s"].items() if scopes is None or k in scopes)
    return 100.0 * seconds / result["window_s"]
