"""The least time the chip could take for the window's flash-attention FORWARD calls of a model
of sliding-window and full layers (``flops_swa_moe.flash_required(training=False)``: the pairs
inside each layer's band, K and V at the key/value heads' width) over the time of
``ds_flash_fwd`` in the trace. Where layers are recomputed and the kernel's output is not kept
the forward runs twice and the requirement counts it once: the share reads low, never high.
The same work whatever tiles implement it."""

from benchmarks import swa_spans


def read(record):
    return swa_spans.flash_roofline(record, ("ds_flash_fwd",), forward=True)
