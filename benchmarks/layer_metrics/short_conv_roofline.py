"""The least time the chip could take for the window's gated convolutions BETWEEN the short-conv
operators' two products (``flops_conv_moe.short_conv_gate_required``: the projection's output read
and the gated result written once a forward, the second forward counted where layers are
recomputed; those, ``dy`` and the projection's cotangent in the backward; whatever implements
them) over the device time under ``ds_short_conv_gate`` in the trace. Bound by memory: three
passes over the rows (``B * z``, the convolution, ``C * v``) read low, a fused gate would read
higher. None without a trace, a catalog or such a scope."""

from benchmarks import conv_spans, flops, flops_conv_moe, peaks


def read(record):
    model = record.get("conv_moe_model", {})
    if record.get("kind") != "train" or not flops_conv_moe.is_conv_moe_model(model):
        return None
    result = conv_spans.analyse(record)
    if result is None:
        return None
    seconds = result["scope_s"].get(conv_spans.GATE, 0.0)
    if seconds <= 0:
        return None
    tokens = record["batch_per_chip"] * record["seq_len"]
    steps = result["window_s"] * record["tokens_per_s_chip"] / tokens
    need_flops, need_bytes = flops_conv_moe.short_conv_gate_required(model, tokens, record["recomputed"])
    least, _ = flops.roofline_seconds(need_flops * steps, need_bytes * steps,
                                      peaks.peaks_for(record["device_kind"]))
    return 100.0 * least / seconds
