"""Device time of the operations under the passes' ``ds_loop`` scope (the blocks of every
pass: forward, recomputed forward and backward) over the traced window."""

from benchmarks import loop_spans


def read(record):
    result = loop_spans.analyse(record)
    if result is None:
        return None
    return 100.0 * loop_spans.loop_seconds(result) / result["window_s"]
