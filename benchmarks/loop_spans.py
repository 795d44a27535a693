"""Device seconds of a looped model's passes, and of what the backward makes again.

``program_spans`` names a step's parts by ``ds_embed|attn|mlp|loss``; a looped model
(``deepspeed_tpu/models/ouro.py``) runs its layers several times on one set of weights as ONE
``lax.scan`` over the passes and names a pass's blocks ``ds_loop``, OUTSIDE ``ds_attn`` /
``ds_mlp``, and the exit gate, the exit distribution, the weighting and the entropy ``ds_exit``
inside ``ds_loss``. JAX names every operation that a ``jax.checkpoint`` makes again in the
backward by ``rematted_computation`` in its scope path. This module reads them from the same
trace as ``ssm_spans`` reads the state-space mixers': the step programs' catalog (instruction ->
scope path), the assignment of device operations to programs, and the window.

A pass's operation is its FORWARD's (no ``transpose(`` in its path), its RECOMPUTED forward's
(``rematted_computation``) or its BACKWARD's (the rest). WHICH pass it is the scope cannot say,
and nothing here asks: the passes are turns of one loop body, the same compiled instructions
every turn, so a pass costs what any other costs by construction. An operation the compiler
gave no scope path counts nowhere, so the shares read low. A program without such scopes (any
other model's, or a parent commit's) gives None and every reader returns None.
"""

import json
import os

from benchmarks import program_spans
from benchmarks import trace_reduce as tr

LOOP = "ds_loop"
EXIT = "ds_exit"
RECOMPUTED = "rematted_computation"
OUT_NAME = "loop_spans.last.json"


def phase_of(path):
    if RECOMPUTED in path:
        return "recomputed"
    return "backward" if "transpose(" in path else "forward"


def analyse(record):
    """``{"loop_s": {phase: seconds}, "exit_s": s, "window_s": s}`` averaged over the devices,
    kept on the record; None without a trace, a catalog, a looped model or an operation under
    ``ds_loop``."""
    if "loop_spans" in record:
        return record["loop_spans"]
    record["loop_spans"] = result = _analyse(record)
    if result is not None:         # the table, for PERF.md, beside program_spans' own
        out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, OUT_NAME), "w") as f:
            json.dump(result, f, indent=1)
    return result


def _analyse(record):
    trace = record.get("trace")
    base = program_spans.analyse(record)
    if trace is None or base is None or not record.get("loop_model") or not trace.devices or trace.window_s <= 0:
        return None
    rec = program_spans.program_recorder()
    try:
        catalog = rec.programs(base["engine"])
    except Exception:          # the catalog compiles; a traced run must still print its line
        return None
    if not catalog:
        return None
    loop_s, exit_s = {}, 0.0
    for events in trace.devices.values():
        events = sorted(events, key=lambda e: e[1])
        programs = program_spans.assign_programs(events, catalog)
        for (name, start, dur), program in zip(events, programs):
            if program is None:
                continue
            path = catalog[program]["ops"].get(program_spans.instruction(name), "")
            if LOOP not in path and EXIT not in path:
                continue
            seconds = tr.measure(tr.clip([[start, start + dur]], trace.lo, trace.hi))
            if LOOP in path:
                phase = phase_of(path)
                loop_s[phase] = loop_s.get(phase, 0.0) + seconds
            else:
                exit_s += seconds
    if not loop_s:
        return None
    n = len(trace.devices)
    return {"loop_s": {phase: s / n for phase, s in sorted(loop_s.items())},
            "exit_s": exit_s / n, "window_s": trace.window_s}


def loop_seconds(result, phase=None):
    """Device seconds under ``ds_loop``: all phases, or one."""
    return sum(s for name, s in result["loop_s"].items() if phase in (None, name))
