"""Seconds of the engine's ``train.*`` spans that hold a ``compile.*`` child, up to the
window's start: what set-up spends tracing, lowering, compiling or loading the step
programs and dispatching each for the first time."""

from benchmarks import program_spans


def read(record):
    return program_spans.host_value(record, "build_s")
