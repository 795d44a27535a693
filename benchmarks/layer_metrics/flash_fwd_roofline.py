"""The least time the chip could take for the window's flash-attention forward calls
(``flops.flash_required(training=False)``) over the time of ``ds_flash_fwd`` in the trace."""

from benchmarks import program_spans


def read(record):
    return program_spans.flash_roofline(record, ("ds_flash_fwd",), forward=True)
