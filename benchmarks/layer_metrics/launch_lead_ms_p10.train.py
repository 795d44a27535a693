"""The tenth percentile, over the window's calls of a step program, of the lead the host had
when the call returned: the moment the first device was free for that execution (the end of
the execution before it) less the end of the ``train.*`` span that launched it, on the trace's
clock. Positive: the program waited in the device's queue. Negative: the device waited for the
host, by that long. Calls and executions are paired from the window's end backwards, program
by program; None where the counts cannot be paired (``benchmarks/host_lead.py``)."""

from benchmarks import host_lead


def read(record):
    return host_lead.trace_value(record, "launch_lead_ms_p10")
