"""Summed device time of the flash-attention custom calls over the traced window."""

from benchmarks import kernels


def read(record):
    trace = record.get("trace")
    if trace is None or trace.window_s <= 0:
        return None
    seconds, count = trace.op_seconds(kernels.is_flash)
    if not count:
        return None
    return 100.0 * seconds / trace.window_s
