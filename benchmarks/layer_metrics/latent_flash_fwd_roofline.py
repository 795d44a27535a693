"""The least time the chip could take for the window's flash-attention forward calls (two products a tile: the scores 192 deep, the values 128 wide)
at the latent attention's two widths (``flops_hc_moe.latent_flash_required``: the NEEDED work
over the causal triangle, whatever implements it) over the time of ``ds_flash_fwd`` in the trace.
None without a trace, or for a model that is no hyper-connected latent-attention model."""

from benchmarks import flops, flops_hc_moe, peaks, program_spans


def read(record):
    model = record.get("hc_moe_model", {})
    if record.get("kind") != "train" or not flops_hc_moe.is_hc_moe_model(model):
        return None
    seconds = (program_spans.trace_value(record, "kernel_s") or {}).get("ds_flash_fwd", 0.0)
    if seconds <= 0:
        return None
    steps = program_spans.trace_value(record, "window_s") * record["tokens_per_s_chip"] / (
        record["batch_per_chip"] * record["seq_len"])
    need_flops, need_bytes = flops_hc_moe.latent_flash_required(
        model, record["batch_per_chip"], record["seq_len"], forward=True)
    least, _ = flops.roofline_seconds(need_flops * steps, need_bytes * steps,
                                      peaks.peaks_for(record["device_kind"]))
    return 100.0 * least / seconds
