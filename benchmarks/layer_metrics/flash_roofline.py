"""The least time the chip could take for the window's flash-attention calls (the
larger of required operations over peak FLOP/s and required bytes over peak bytes/s,
``benchmarks/flops.py``) over the time the calls took in the trace."""

from benchmarks import flops, kernels, peaks


def read(record):
    trace = record.get("trace")
    if trace is None or record.get("kind") != "train":
        return None
    seconds, count = trace.op_seconds(kernels.is_flash)
    if not count or seconds <= 0:
        return None
    # the steps whose kernels ran inside the traced window, on one chip
    steps = trace.window_s * record["tokens_per_s_chip"] / (
        record["batch_per_chip"] * record["seq_len"])
    need_flops, need_bytes = flops.flash_required(
        record["model"], record["batch_per_chip"], record["seq_len"], training=True)
    least, _ = flops.roofline_seconds(need_flops * steps, need_bytes * steps,
                                      peaks.peaks_for(record["device_kind"]))
    return 100.0 * least / seconds
