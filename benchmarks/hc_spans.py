"""Device seconds under the scopes a model of hyper-connected blocks names: ``ds_hc`` (everything
a sub-layer's hyper-connection does, INSIDE ``ds_attn`` / ``ds_mlp``) and its two parts,
``ds_hc_coef`` (the flattened norm, the projection, the sigmoids, Sinkhorn-Knopp) and ``ds_hc_mix``
(the mix to the one stream a sub-layer reads, the residual mix and the post-add).

This module reads them from the same trace as ``mla_spans`` reads the latent mixers': the step
programs' catalog (instruction -> scope path), the assignment of device operations to programs,
and the window; forward, recomputed forward and backward alike. A program without such scopes
(any other model's, or a parent commit's) gives None and every reader returns None.
"""

import json
import os

from benchmarks import program_spans
from benchmarks import trace_reduce as tr

HC, COEF, MIX = "ds_hc", "ds_hc_coef", "ds_hc_mix"
OUT_NAME = "hc_spans.last.json"


def analyse(record):
    """``{"scope_s": {name: seconds}, "window_s": s}`` averaged over the devices, kept on the
    record; None without a trace, a catalog or an operation under the scope."""
    if "hc_spans" in record:
        return record["hc_spans"]
    record["hc_spans"] = result = _analyse(record)
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
    try:
        catalog = program_spans.program_recorder().programs(base["engine"])
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
            # both parts' scopes lie under the whole's, whose name theirs begin with
            path = catalog[program]["ops"].get(program_spans.instruction(name), "")
            for scope in (HC, COEF, MIX):
                if scope in path:
                    seconds = tr.measure(tr.clip([[start, start + dur]], trace.lo, trace.hi))
                    scope_s[scope] = scope_s.get(scope, 0.0) + seconds
    if not scope_s:
        return None
    n = len(trace.devices)
    return {"scope_s": {k: v / n for k, v in sorted(scope_s.items())}, "window_s": trace.window_s}


def share(record, scope):
    """Device time under ``scope`` over the traced window, in percent; None where ``analyse``
    finds nothing or nothing under that scope."""
    result = analyse(record)
    if result is None or scope not in result["scope_s"]:
        return None
    return 100.0 * result["scope_s"][scope] / result["window_s"]
