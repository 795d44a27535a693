"""Device time of the head and its cross-entropy over the traced window: every operation
whose scope path holds ``ds_loss`` (the last norm, the logits' tiles, the loss, and their
gradients), forward and backward. None without a trace or a catalog."""

from benchmarks import program_spans


def read(record):
    rows, window_s = (program_spans.trace_value(record, key) for key in ("device_s", "window_s"))
    if not rows or not window_s:
        return None
    return 100.0 * sum(s for _, part, _, s in rows if part == "ds_loss") / window_s
