"""Device idle time that falls inside any ``train.*`` span of the program, a step: the
trace's idle gaps on the program's clock, cut at span boundaries; what falls between
two steps is the caller's and is left out."""

from benchmarks import program_spans


def read(record):
    return program_spans.trace_value(record, "stall_ms_per_step")
