"""Model FLOP/s utilization of a hybrid state-space model on this chip: the operations the
forward and backward passes require per token (``benchmarks/flops_ssm.py``: projections,
convolution, the scan as its recurrence, attention, MLP, head; the recomputed forward is NOT
counted) times the tokens per second and chip of this window, over the chip's published peak."""

from benchmarks import flops_ssm, peaks


def read(record):
    model = record.get("ssm_model", {})
    if record.get("kind") != "train" or not flops_ssm.is_ssm_model(model):
        return None
    per_token = flops_ssm.train_flops_per_token(model, record["vocab"], record["seq_len"])
    peak = peaks.peaks_for(record["device_kind"])["flops_per_s"]
    return 100.0 * per_token * record["tokens_per_s_chip"] / peak
