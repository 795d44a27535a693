"""Device time of the hyper-connections (every operation whose scope path holds ``ds_hc``: a
sub-layer's flattened norm, its projection, the sigmoids and Sinkhorn-Knopp's rounds, the mix to
the stream the sub-layer reads, the residual mix and the post-add; all ten sub-layers, forward,
recomputed forward and backward) over the traced window. None without a trace, a catalog or such
a scope."""

from benchmarks import hc_spans


def read(record):
    return hc_spans.share(record, hc_spans.HC)
