"""Device seconds of the attention layers by kind: ``ds_attn_window`` and ``ds_attn_full``.

``program_spans`` names a step's parts by ``ds_embed|attn|mlp|loss``; a model whose attention
layers are sliding-window or full (``deepspeed_tpu/models/mellum.py``) names the whole mixer of
a layer by its kind INSIDE ``ds_attn``. This module reads both from the same trace as
``ssm_spans`` reads the state-space mixers': the step programs' catalog (instruction -> scope
path), the assignment of device operations to programs, and the window; forward, recomputed
forward and backward alike. A program without such scopes (any other model's, or a parent
commit's) gives None and every reader returns None.
"""

import json
import os

from benchmarks import program_spans
from benchmarks import trace_reduce as tr

WINDOW, FULL = "ds_attn_window", "ds_attn_full"
OUT_NAME = "swa_spans.last.json"


def analyse(record):
    """``{"scope_s": {name: seconds}, "window_s": s}`` averaged over the devices, kept on the
    record; None without a trace, a catalog or an operation under either scope."""
    if "swa_spans" in record:
        return record["swa_spans"]
    record["swa_spans"] = result = _analyse(record)
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
            path = catalog[program]["ops"].get(program_spans.instruction(name), "")
            for scope in (WINDOW, FULL):
                if scope in path:
                    seconds = tr.measure(tr.clip([[start, start + dur]], trace.lo, trace.hi))
                    scope_s[scope] = scope_s.get(scope, 0.0) + seconds
    if not scope_s:
        return None
    n = len(trace.devices)
    return {"scope_s": {k: v / n for k, v in sorted(scope_s.items())}, "window_s": trace.window_s}


def flash_roofline(record, kernels, forward):
    """The least time the chip could take for the window's calls of ``kernels`` (the forward's
    requirement by ``flops_swa_moe.flash_required``: the pairs inside each layer's band, K and V
    at the key/value heads' width; or the training step's less the forward's) over their time
    in the trace, in percent; ``program_spans.flash_roofline`` with this model's counts."""
    from benchmarks import flops, flops_swa_moe, peaks
    model = record.get("swa_moe_model", {})
    if record.get("kind") != "train" or not flops_swa_moe.is_swa_moe_model(model):
        return None
    seconds = sum((program_spans.trace_value(record, "kernel_s") or {}).get(k, 0.0) for k in kernels)
    if seconds <= 0:
        return None
    tokens = record["batch_per_chip"] * record["seq_len"]
    steps = program_spans.trace_value(record, "window_s") * record["tokens_per_s_chip"] / tokens
    args = (model, record["batch_per_chip"], record["seq_len"])
    need_flops, need_bytes = flops_swa_moe.flash_required(*args, training=False)
    if not forward:
        all_flops, all_bytes = flops_swa_moe.flash_required(*args, training=True)
        need_flops, need_bytes = all_flops - need_flops, all_bytes - need_bytes
    least, _ = flops.roofline_seconds(need_flops * steps, need_bytes * steps,
                                      peaks.peaks_for(record["device_kind"]))
    return 100.0 * least / seconds
