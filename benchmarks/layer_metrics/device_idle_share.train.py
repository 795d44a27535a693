"""1 - the union of the device-busy intervals over the traced window (train cells)."""


def read(record):
    trace = record.get("trace")
    if trace is None or record.get("kind") != "train":
        return None
    idle = trace.idle_share()
    return None if idle is None else 100.0 * idle
