"""Device seconds of the state-space mixers by scope, and of what the backward makes again.

``program_spans`` names a step's parts by ``ds_embed|attn|mlp|loss``; a Mamba-2 mixer sits
inside ``ds_attn`` under ``ds_ssm`` (projections, convolution, scan, gated norm), and the scan
itself under ``ds_ssd_scan`` inside that. JAX names every operation that a ``jax.checkpoint``
makes again in the backward by ``rematted_computation`` in its scope path (the engine's
``checkpoint_wrapper`` around whole blocks, and the small ones inside a mixer). This module
reads all three from the same trace as ``hybrid_spans`` reads the delta-rule mixers': the step
programs' catalog (instruction -> scope path), the assignment of device operations to programs,
and the window. An operation counts under every name its path holds, forward, recomputed
forward and backward alike; one the compiler gave no scope path counts nowhere, so the shares
read low. A program without such scopes (GPT-2's, OLMoE's, Qwen3-Next's, or a parent commit's)
gives None and every reader returns None.
"""

import json
import os

from benchmarks import program_spans
from benchmarks import trace_reduce as tr

SSM = "ds_ssm"
SSD_SCAN = "ds_ssd_scan"
RECOMPUTED = "rematted_computation"
OUT_NAME = "ssm_spans.last.json"


def analyse(record):
    """``{"scope_s": {name: seconds}, "window_s": s}`` averaged over the devices, kept on
    the record; None without a trace, a catalog or an operation under ``ds_ssm``."""
    if "ssm_spans" in record:
        return record["ssm_spans"]
    record["ssm_spans"] = result = _analyse(record)
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
            found = [scope for scope in (SSM, SSD_SCAN, RECOMPUTED) if scope in path]
            if not found:
                continue
            seconds = tr.measure(tr.clip([[start, start + dur]], trace.lo, trace.hi))
            for scope in found:
                scope_s[scope] = scope_s.get(scope, 0.0) + seconds
    if SSM not in scope_s:
        return None
    n = len(trace.devices)
    return {"scope_s": {k: v / n for k, v in sorted(scope_s.items())}, "window_s": trace.window_s}
