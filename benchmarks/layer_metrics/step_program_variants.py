"""Executables built or loaded for the engine's step programs: the sum of the
program's ``program.builds[*]`` counters. Nothing builds inside a window that is
``correct``, so the count after it is the count at its start."""

from benchmarks import program_spans


def read(record):
    return program_spans.host_value(record, "program_builds")
