"""The least time the chip could take for the window's held experts' two products
(``flops_ssm_moe.held_experts_required``: from the measured rows on held experts, each held
expert's two matrices read once forward and twice backward) over the device time under
``ds_moe_experts`` in the trace. That scope also holds the squared ReLU between the products,
and where layers are recomputed (and in the held-range backward, which makes every pass
again) the forward runs more than once: the share reads low, never high."""

from benchmarks import flops, flops_ssm_moe, moe_spans, peaks


def read(record):
    model, moe = record.get("ssm_moe_model", {}), record.get("moe") or {}
    if record.get("kind") != "train":
        return None
    if not flops_ssm_moe.is_ssm_moe_model(model) or moe.get("rows_here_by_layer") is None:
        return None
    result = moe_spans.analyse(record)
    if result is None:
        return None
    seconds = result["scope_s"].get(moe_spans.EXPERTS, 0.0)
    if seconds <= 0:
        return None
    tokens = record["batch_per_chip"] * record["seq_len"]
    steps = result["window_s"] * record["tokens_per_s_chip"] / tokens
    rows = sum(moe["rows_here_by_layer"]) / len(moe["rows_here_by_layer"])
    need_flops, need_bytes = flops_ssm_moe.held_experts_required(model, rows)
    least, _ = flops.roofline_seconds(need_flops * steps, need_bytes * steps,
                                      peaks.peaks_for(record["device_kind"]))
    return 100.0 * least / seconds
