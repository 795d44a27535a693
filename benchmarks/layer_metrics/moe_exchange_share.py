"""Device time of the collectives under ``ds_moe_exchange`` (the experts' weights gathered
for a layer, their gradients summed and scattered to the owners) over the traced window."""

from benchmarks import moe_spans


def read(record):
    result = moe_spans.analyse(record)
    if result is None:
        return None
    return 100.0 * result["exchange_collective_s"] / result["window_s"]
