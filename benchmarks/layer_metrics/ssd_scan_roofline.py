"""The least time the chip could take for the window's state-space scans
(``flops_ssm.ssd_scan_required``: the RECURRENCE's operations, every input read and the output
written once, forward and backward, whatever implements them) over the device time under
``ds_ssd_scan`` in the trace. The chunked form does more operations than the recurrence, and
where blocks are recomputed the forward runs twice: the share reads low, never high."""

from benchmarks import flops, flops_ssm, peaks, ssm_spans


def read(record):
    result = ssm_spans.analyse(record)
    if result is None or record.get("kind") != "train":
        return None
    seconds = result["scope_s"].get(ssm_spans.SSD_SCAN, 0.0)
    if seconds <= 0 or not flops_ssm.is_ssm_model(record.get("ssm_model", {})):
        return None
    tokens = record["batch_per_chip"] * record["seq_len"]
    steps = result["window_s"] * record["tokens_per_s_chip"] / tokens
    need_flops, need_bytes = flops_ssm.ssd_scan_required(record["ssm_model"], tokens)
    least, _ = flops.roofline_seconds(need_flops * steps, need_bytes * steps,
                                      peaks.peaks_for(record["device_kind"]))
    return 100.0 * least / seconds
