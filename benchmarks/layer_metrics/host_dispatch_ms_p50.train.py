"""Median time the host spends inside ``engine(...)``, ``backward`` and ``step`` until
they return, no fence added. Near the step time, the host waits in the call; far
below it, the host runs ahead of the device."""

import statistics


def read(record):
    if record.get("kind") != "train" or not record.get("dispatch_ms"):
        return None
    return statistics.median(record["dispatch_ms"])
