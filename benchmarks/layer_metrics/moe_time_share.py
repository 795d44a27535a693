"""Device time of the operations under the expert layers' ``ds_moe_*`` scopes (router,
dispatch, experts, combine, exchange; forward and backward) over the traced window."""

from benchmarks import moe_spans


def read(record):
    return moe_spans.share(record)
