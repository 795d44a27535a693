"""The least time the chip could take for the window's grouped expert matmuls
(``flops_moe.expert_matmul_required``: gate|up and down, forward and backward) over the
device time under ``ds_moe_experts`` in the trace. That scope also holds the gated
activation between the two products, so the share reads low, never high."""

from benchmarks import flops, flops_moe, moe_spans, peaks


def read(record):
    result = moe_spans.analyse(record)
    if result is None or record.get("kind") != "train":
        return None
    seconds = result["scope_s"].get(moe_spans.EXPERTS, 0.0)
    if seconds <= 0 or not flops_moe.is_expert_model(record["model"]):
        return None
    tokens = record["batch_per_chip"] * record["seq_len"]
    steps = result["window_s"] * record["tokens_per_s_chip"] / tokens
    need_flops, need_bytes = flops_moe.expert_matmul_required(record["model"], tokens)
    least, _ = flops.roofline_seconds(need_flops * steps, need_bytes * steps,
                                      peaks.peaks_for(record["device_kind"]))
    return 100.0 * least / seconds
