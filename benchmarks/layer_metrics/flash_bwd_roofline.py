"""The least time the chip could take for the window's flash-attention backward calls
(the training step's requirement less the forward's) over the time of ``ds_flash_bwd_dq``
and ``ds_flash_bwd_dkv`` in the trace."""

from benchmarks import program_spans


def read(record):
    return program_spans.flash_roofline(
        record, ("ds_flash_bwd_dq", "ds_flash_bwd_dkv"), forward=False)
