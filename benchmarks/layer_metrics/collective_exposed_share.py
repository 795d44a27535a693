"""Share of the traced window in which a collective runs on a device and no compute
does, mean over the devices. None on one chip, where the trace holds no collective."""


def read(record):
    trace = record.get("trace")
    if trace is None or trace.window_s <= 0:
        return None
    exposed = trace.collective_exposed_s()
    return None if exposed is None else 100.0 * exposed / trace.window_s
