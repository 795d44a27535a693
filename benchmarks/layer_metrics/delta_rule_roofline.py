"""The least time the chip could take for the window's delta-rule calls
(``flops_hybrid.delta_rule_required``: the RECURRENT form's operations, every input read and
the output written once, forward and backward) over the device time under ``ds_delta_rule``
in the trace. The chunked form does more operations than the recurrence and the backward
makes the forward again, so the share reads low, never high."""

from benchmarks import flops, flops_hybrid, hybrid_spans, peaks


def read(record):
    result = hybrid_spans.analyse(record)
    if result is None or record.get("kind") != "train":
        return None
    seconds = result["scope_s"].get(hybrid_spans.DELTA_RULE, 0.0)
    if seconds <= 0 or not flops_hybrid.is_hybrid_model(record.get("hybrid_model", {})):
        return None
    tokens = record["batch_per_chip"] * record["seq_len"]
    steps = result["window_s"] * record["tokens_per_s_chip"] / tokens
    need_flops, need_bytes = flops_hybrid.delta_rule_required(record["hybrid_model"], tokens)
    least, _ = flops.roofline_seconds(need_flops * steps, need_bytes * steps,
                                      peaks.peaks_for(record["device_kind"]))
    return 100.0 * least / seconds
