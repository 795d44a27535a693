"""Device time of the operations under the delta-rule mixers' ``ds_lin_attn`` scope
(projections, convolution, the delta rule, the gated norm; forward and backward) over the
traced window."""

from benchmarks import hybrid_spans


def read(record):
    result = hybrid_spans.analyse(record)
    if result is None:
        return None
    return 100.0 * result["scope_s"].get(hybrid_spans.LIN_ATTN, 0.0) / result["window_s"]
