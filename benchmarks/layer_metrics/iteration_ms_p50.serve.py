"""Median wall time of one ``InferenceEngine.step()`` on the benchmark's clock."""

import statistics


def read(record):
    if record.get("kind") != "serve" or not record.get("iteration_ms"):
        return None
    return statistics.median(record["iteration_ms"])
