"""Median over the window's steps of the ``train.step`` span less its children: the
engine's own Python between its program calls (``benchmarks/program_spans.py``). The
span runs from ``forward()`` to the end of ``step()``, so what the caller does between
its three calls of the engine is in it too: next to nothing in the harness, which makes
its batches before the window."""

from benchmarks import program_spans


def read(record):
    return program_spans.host_value(record, "engine_self_ms_p50")
