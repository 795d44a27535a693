"""Median time from ``submit`` to the start of the iteration that ran the request's
first prefill chunk: what a request waits for the scheduler before any of its work
runs."""

import statistics


def read(record):
    if record.get("kind") != "serve" or not record.get("prefill_wait_ms"):
        return None
    return statistics.median(record["prefill_wait_ms"])
