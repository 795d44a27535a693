"""Median over the window's steps of ``in_flight``: the engine's earlier steps whose loss the
device had not made when the step began (``jax.Array.is_ready`` over the last eight, nothing
waited for). Higher is better: a pause of the host shorter than its lead costs the device
nothing. ``host_lead.last.json`` also holds the minimum, and 0 there is a drained device."""

from benchmarks import host_lead


def read(record):
    flights = host_lead.host_value(record, "in_flight")
    return None if flights is None else flights["median"]
