"""The largest ``bytes_in_use`` the window's steps noted when their programs were enqueued
(the most over the process's devices) over the ``bytes_limit`` the engine's first step noted:
the window's own memory, where ``memory_peak_bytes`` is the process's lifetime peak. None
where the backend reports no memory (``benchmarks/host_lead.py``)."""

from benchmarks import host_lead


def read(record):
    memory = host_lead.host_value(record, "memory")
    return None if memory is None else memory["share_max"]
