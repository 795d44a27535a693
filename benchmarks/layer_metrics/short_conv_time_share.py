"""Device time of the gated short-convolution operators (every operation whose scope path holds
``ds_short_conv``: both products, both gates, the convolution's kernels; all conv layers, forward,
recomputed forward and backward) over the traced window. None without a trace, a catalog or such
a scope."""

from benchmarks import conv_spans


def read(record):
    return conv_spans.share(record, conv_spans.SHORT_CONV)
