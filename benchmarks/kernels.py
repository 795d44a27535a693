"""Which device operations of a trace are which kernel, by the name the trace prints."""


def is_flash(name):
    """The flash-attention forward and backward are the model's only Pallas calls; the
    trace prints them as custom calls to ``tpu_custom_call`` (``jvp__`` on one chip,
    ``shard_map`` under a mesh), and ``trace_reduce.short_name`` keeps the target."""
    return "tpu_custom_call" in name
