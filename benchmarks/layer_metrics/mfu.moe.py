"""Model FLOP/s utilization of an expert model: the operations the forward and backward
passes require per token (``benchmarks/flops_moe.py``: attention, the k experts a token is
sent to, router, head; nothing recomputed counts) times the tokens per second and chip of
this window, over the chip's published peak."""

from benchmarks import flops_moe, peaks


def read(record):
    if record.get("kind") != "train" or not flops_moe.is_expert_model(record.get("model", {})):
        return None
    per_token = flops_moe.train_flops_per_token(record["model"], record["vocab"], record["seq_len"])
    peak = peaks.peaks_for(record["device_kind"])["flops_per_s"]
    return 100.0 * per_token * record["tokens_per_s_chip"] / peak
