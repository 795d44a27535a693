"""Device time of the optimizer pass over the traced window: operations of the update
program, or under ``ds_apply_update`` in a fused step. None where more than 2 % of the
window could be given to no phase."""

from benchmarks import program_spans


def read(record):
    return program_spans.phase_share(record, "optimizer")
