"""The least time the chip could take for the window's hyper-connections
(``flops_hc_moe.hc_required``: the four streams read once for the coefficients and the mixed
stream, that stream written, the four streams and the sub-layer's output read, the four streams
written, ``(3 n + 2) C`` bf16 elements a token and sub-layer forward, the second forward counted
where blocks are recomputed, the backward twice a forward; whatever implements them) over the
device time under ``ds_hc`` in the trace. Bound by memory: float32 coefficient passes, Sinkhorn's
rounds and one pass a stream read low, a fused mix would read higher. None without a trace, a
catalog or such a scope."""

from benchmarks import flops, flops_hc_moe, hc_spans, peaks


def read(record):
    model = record.get("hc_moe_model", {})
    if record.get("kind") != "train" or not flops_hc_moe.is_hc_moe_model(model):
        return None
    result = hc_spans.analyse(record)
    if result is None:
        return None
    seconds = result["scope_s"].get(hc_spans.HC, 0.0)
    if seconds <= 0:
        return None
    tokens = record["batch_per_chip"] * record["seq_len"]
    steps = result["window_s"] * record["tokens_per_s_chip"] / tokens
    need_flops, need_bytes = flops_hc_moe.hc_required(model, tokens, record["recomputed"])
    least, _ = flops.roofline_seconds(need_flops * steps, need_bytes * steps,
                                      peaks.peaks_for(record["device_kind"]))
    return 100.0 * least / seconds
