"""Model FLOP/s utilization of a looped model on this chip: the operations the forward and
backward passes require per token (``benchmarks/flops_loop.py``: every block pass's products,
attention at the packed length, a head a pass, the gate; the recomputed forward is NOT
counted) times the tokens per second and chip of this window, over the chip's published peak."""

from benchmarks import flops_loop, peaks


def read(record):
    model = record.get("loop_model", {})
    if record.get("kind") != "train" or not flops_loop.is_loop_model(model):
        return None
    per_token = flops_loop.train_flops_per_token(model, record["vocab"], record["seq_len"])
    peak = peaks.peaks_for(record["device_kind"])["flops_per_s"]
    return 100.0 * per_token * record["tokens_per_s_chip"] / peak
