"""Which chip this is: the local device's generation by name (``chip_smoke.py`` prints it).

The peaks a roofline is priced against live with the benchmark, in ``benchmarks/peaks.py``,
keyed by ``device_kind``: the one table of peaks. What an operation of a compiled program
HAS to do is counted by ``utils/hlo.instruction_costs``; no rate enters the program.
"""

__all__ = ["detect_chip"]

# jax device_kind substrings -> spec table key, most specific first
_KIND_PATTERNS = (("v6", "tpu-v6e"), ("v5p", "tpu-v5p"), ("v5 lite", "tpu-v5e"),
                  ("v5e", "tpu-v5e"), ("v4", "tpu-v4"))


def detect_chip() -> str:
    """Name of the local device's chip generation: ``cpu-test`` on the CPU backend,
    the matching table entry on an accelerator. An accelerator whose
    ``device_kind`` matches no pattern raises — a device that is not in the
    table is an error, not a default."""
    import jax
    device = jax.local_devices()[0]
    if device.platform == "cpu":
        return "cpu-test"
    kind = device.device_kind.lower()
    for pattern, name in _KIND_PATTERNS:
        if pattern in kind:
            return name
    raise ValueError(f"no chip spec for {device.platform} device_kind "
                     f"{device.device_kind!r}; add it to _KIND_PATTERNS "
                     "(utils/roofline.py)")
