"""Device time of the forward pass over the traced window: operations of the grad program
whose scope path holds no ``transpose(``. None where more than 2 % of the window could be
given to no phase."""

from benchmarks import program_spans


def read(record):
    return program_spans.phase_share(record, "forward")
