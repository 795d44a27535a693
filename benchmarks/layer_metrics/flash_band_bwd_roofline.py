"""The least time the chip could take for the window's flash-attention BACKWARD calls of a
model of sliding-window and full layers (the training step's requirement less the forward's,
``flops_swa_moe.flash_required``: the pairs inside each layer's band twice over, K, V and their
gradients at the key/value heads' width) over the time of ``ds_flash_bwd_dkv`` in the trace."""

from benchmarks import swa_spans


def read(record):
    return swa_spans.flash_roofline(record, ("ds_flash_bwd_dkv",), forward=False)
