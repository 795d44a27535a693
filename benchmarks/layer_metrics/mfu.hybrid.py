"""Model FLOP/s utilization of a hybrid linear-attention expert model on this chip: the
operations the forward and backward passes require per token
(``benchmarks/flops_hybrid.py``: mixers, delta rule, attention, router, shared expert, head,
and the routed experts counted from the window's measured ``moe_rows_here``, never from k;
nothing recomputed counts) times the tokens per second and chip of this window, over the
chip's published peak."""

from benchmarks import flops_hybrid, peaks


def read(record):
    model, moe = record.get("hybrid_model", {}), record.get("moe") or {}
    if record.get("kind") != "train" or not flops_hybrid.is_hybrid_model(model):
        return None
    if moe.get("rows_here_per_token") is None:
        return None
    per_token = flops_hybrid.train_flops_per_token(model, record["vocab"], record["seq_len"],
                                                   moe["rows_here_per_token"])
    peak = peaks.peaks_for(record["device_kind"])["flops_per_s"]
    return 100.0 * per_token * record["tokens_per_s_chip"] / peak
