"""The longest step of the window over the median step, no fence added: 1 + the longest
stall over the median step, both from the returns of ``engine.step()``
(``harness.step_profile``, which allows for the host running ahead). 1.0x means every
step took the same time; an off run shows here whether one step was long."""

from benchmarks import harness


def read(record):
    if record.get("kind") != "train":
        return None
    median, stall = harness.step_profile(record.get("step_interval_ms", ()))
    if median is None:
        return None
    return 1.0 + stall / median
