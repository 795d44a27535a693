"""Model FLOP/s utilization of a hyper-connected latent-attention expert model on this chip: the
operations the forward and backward passes require per token (``benchmarks/flops_hc_moe.py``: every
block's latent projections and causal triangle at scores 192 deep and values 128 wide, the dense
MLP, routers, shared experts, the hyper-connections' projections and mixes, the head, the routed
experts counted from the window's measured ``moe_rows_here``; the recomputed forward is NOT counted)
times the tokens per second and chip of this window, over the chip's published peak."""

from benchmarks import flops_hc_moe, peaks


def read(record):
    model, moe = record.get("hc_moe_model", {}), record.get("moe") or {}
    if record.get("kind") != "train":
        return None
    if not flops_hc_moe.is_hc_moe_model(model) or moe.get("rows_here_per_token") is None:
        return None
    per_token = flops_hc_moe.train_flops_per_token(model, record["vocab"], record["seq_len"],
                                                   moe["rows_here_per_token"])
    peak = peaks.peaks_for(record["device_kind"])["flops_per_s"]
    return 100.0 * per_token * record["tokens_per_s_chip"] / peak
