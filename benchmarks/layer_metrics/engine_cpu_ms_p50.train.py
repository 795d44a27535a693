"""Median over the window's steps of the CPU seconds of the ``train.step`` span, children
included (``cpu_s``: the thread's CPU clock at the span's two ends): what the host really
spends a step, launches and all. Beside ``engine_self_ms_p50.train``, which is wall time with
the program calls taken out; wall less CPU is the time the host was held
(``benchmarks/host_lead.py``)."""

from benchmarks import host_lead


def read(record):
    return host_lead.host_value(record, "engine_cpu_ms_p50")
