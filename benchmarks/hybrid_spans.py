"""Device seconds of the linear-attention mixers, by scope.

``program_spans`` names a step's parts by ``ds_embed|attn|mlp|loss``; a delta-rule mixer sits
inside ``ds_attn`` under ``ds_lin_attn`` (projections, convolution, scan, gated norm), and
the delta rule itself under ``ds_delta_rule`` inside that. This module reads both from the
same trace as ``moe_spans`` reads the expert layers' scopes: the step programs' catalog
(instruction -> scope path), the assignment of device operations to programs, and the
window. An operation counts under every one of the two names its path holds, forward and
backward (and the backward's second forward) alike. A program without such scopes (GPT-2's,
OLMoE's, or a parent commit's) gives None and every reader returns None.
"""

import json
import os

from benchmarks import program_spans
from benchmarks import trace_reduce as tr

LIN_ATTN = "ds_lin_attn"
DELTA_RULE = "ds_delta_rule"
OUT_NAME = "hybrid_spans.last.json"


def analyse(record):
    """``{"scope_s": {scope: seconds}, "window_s": s}`` averaged over the devices, kept
    on the record; None without a trace, a catalog or an operation under either scope."""
    if "hybrid_spans" in record:
        return record["hybrid_spans"]
    record["hybrid_spans"] = result = _analyse(record)
    if result is not None:         # the table, for PERF.md, beside program_spans' own
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
    scope_s = {}
    for events in trace.devices.values():
        events = sorted(events, key=lambda e: e[1])
        programs = program_spans.assign_programs(events, catalog)
        for (name, start, dur), program in zip(events, programs):
            if program is None:
                continue
            path = catalog[program]["ops"].get(program_spans.instruction(name), "")
            if LIN_ATTN not in path:
                continue
            seconds = tr.measure(tr.clip([[start, start + dur]], trace.lo, trace.hi))
            for scope in (LIN_ATTN, DELTA_RULE):
                if scope in path:
                    scope_s[scope] = scope_s.get(scope, 0.0) + seconds
    if not scope_s:
        return None
    n = len(trace.devices)
    return {"scope_s": {k: v / n for k, v in sorted(scope_s.items())}, "window_s": trace.window_s}
