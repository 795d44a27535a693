"""Device time of the backward pass over the traced window: operations of the grad program
whose scope path holds ``transpose(`` (JAX's mark of the backward pass). None where more
than 2 % of the window could be given to no phase."""

from benchmarks import program_spans


def read(record):
    return program_spans.phase_share(record, "backward")
