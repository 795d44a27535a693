"""Device time of the multi-token-prediction module (every operation whose scope path holds
``ds_mtp``: its two norms and projection, its whole block, its last norm, the second depth's head
and cross-entropy; forward, recomputed forward and backward) over the traced window. None without
a trace, a catalog or such a scope."""

from benchmarks import mla_spans


def read(record):
    return mla_spans.share(record, mla_spans.MTP)
