"""Device time of the operations under ``ds_exit`` (the exit gate, the exit distribution, the
weighting of the exits' losses and the entropy; forward and backward) over the traced window."""

from benchmarks import loop_spans


def read(record):
    result = loop_spans.analyse(record)
    if result is None:
        return None
    return 100.0 * result["exit_s"] / result["window_s"]
