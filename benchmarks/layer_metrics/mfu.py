"""Model FLOP/s utilization: the operations the forward and backward passes require
per token (``benchmarks/flops.py``; nothing recomputed counts) times the tokens per
second and chip of this window, over the chip's published peak."""

from benchmarks import flops, peaks


def read(record):
    if record.get("kind") != "train":
        return None
    per_token = flops.train_flops_per_token(record["model"], record["vocab"], record["seq_len"])
    peak = peaks.peaks_for(record["device_kind"])["flops_per_s"]
    return 100.0 * per_token * record["tokens_per_s_chip"] / peak
