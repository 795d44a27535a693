"""Roofline chip-spec model: peak-rate floors of a step.

The roofline method (Williams et al., 2009) bounds a program's runtime from
below by each hardware resource it must saturate: executed flops can go no
faster than peak matrix throughput, touched bytes no faster than HBM
bandwidth, and collective bytes no faster than the link level they ride
(ICI within a slice, DCN across slices). This module owns the per-chip
peak-rate table and the floor arithmetic, so the numbers live in exactly one
place.

The table entries are approximate public figures on a deliberately simple
convention — dense bf16 peak per chip, aggregate HBM bandwidth per chip, and
an effective per-chip collective bandwidth per link level (not per-link
signaling rates). Every number is overridable through ``resolve_spec``; the
``cpu-test`` spec is a generous upper bound for the 8-virtual-device CI mesh,
chosen so predicted floors always sit below measured CPU step times (the
sanity invariant tests pin).
"""

from typing import Dict, Optional

__all__ = ["ChipSpec", "CHIP_SPECS", "detect_chip", "resolve_spec"]


class ChipSpec:
    """Peak rates of one chip generation. ``peak_tflops`` is dense bf16;
    bandwidths are GB/s (1e9 bytes per second) per chip."""

    __slots__ = ("name", "peak_tflops", "hbm_gbps", "ici_gbps", "dcn_gbps")

    def __init__(self, name: str, peak_tflops: float, hbm_gbps: float,
                 ici_gbps: float, dcn_gbps: float):
        self.name = name
        self.peak_tflops = float(peak_tflops)
        self.hbm_gbps = float(hbm_gbps)
        self.ici_gbps = float(ici_gbps)
        self.dcn_gbps = float(dcn_gbps)

    @property
    def peak_flops(self) -> float:
        return self.peak_tflops * 1e12

    def link_gbps(self, level: str) -> float:
        return self.dcn_gbps if level == "dcn" else self.ici_gbps

    def to_dict(self) -> Dict[str, float]:
        return {"name": self.name, "peak_tflops": self.peak_tflops,
                "hbm_gbps": self.hbm_gbps, "ici_gbps": self.ici_gbps,
                "dcn_gbps": self.dcn_gbps}

    def __repr__(self):
        return (f"ChipSpec({self.name!r}, peak_tflops={self.peak_tflops}, "
                f"hbm_gbps={self.hbm_gbps}, ici_gbps={self.ici_gbps}, "
                f"dcn_gbps={self.dcn_gbps})")


CHIP_SPECS = {
    "tpu-v4": ChipSpec("tpu-v4", 275.0, 1228.0, 270.0, 25.0),
    "tpu-v5e": ChipSpec("tpu-v5e", 197.0, 819.0, 200.0, 25.0),
    "tpu-v5p": ChipSpec("tpu-v5p", 459.0, 2765.0, 600.0, 25.0),
    "tpu-v6e": ChipSpec("tpu-v6e", 918.0, 1640.0, 448.0, 25.0),
    # CI mesh: 8 virtual devices on one CPU. Rates are a deliberate UPPER
    # bound on any CI machine, so floor <= measured holds everywhere.
    "cpu-test": ChipSpec("cpu-test", 100.0, 1000.0, 100.0, 25.0),
}

# jax device_kind substrings -> spec table key, most specific first
_KIND_PATTERNS = (("v6", "tpu-v6e"), ("v5p", "tpu-v5p"), ("v5 lite", "tpu-v5e"),
                  ("v5e", "tpu-v5e"), ("v4", "tpu-v4"))


def detect_chip() -> str:
    """Spec-table key for the local device: ``cpu-test`` on the CPU backend,
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
                     f"{device.device_kind!r}; add it to CHIP_SPECS and "
                     "_KIND_PATTERNS (utils/roofline.py)")


def resolve_spec(chip: str = "", peak_tflops: float = 0.0,
                 hbm_gbps: float = 0.0, ici_gbps: float = 0.0,
                 dcn_gbps: float = 0.0) -> ChipSpec:
    """Spec for ``chip`` ("" = auto-detect) with per-field overrides (0 keeps
    the table value). Unknown chip names raise — a typo'd chip must not
    silently price the roofline off the CPU fallback."""
    name = chip or detect_chip()
    base = CHIP_SPECS.get(name)
    if base is None:
        raise ValueError(f"unknown chip {name!r}; known: "
                         f"{', '.join(sorted(CHIP_SPECS))}")
    return ChipSpec(base.name,
                    peak_tflops or base.peak_tflops,
                    hbm_gbps or base.hbm_gbps,
                    ici_gbps or base.ici_gbps,
                    dcn_gbps or base.dcn_gbps)


def compute_floor_seconds(flops: float, spec: ChipSpec) -> float:
    """Time the executed flops need at peak matrix throughput."""
    return max(float(flops), 0.0) / spec.peak_flops


def hbm_floor_seconds(hbm_bytes: float, spec: ChipSpec) -> float:
    """Time the touched bytes need at full HBM bandwidth."""
    return max(float(hbm_bytes), 0.0) / (spec.hbm_gbps * 1e9)


def comm_seconds(wire_bytes: float, level: str, spec: ChipSpec) -> float:
    """Time ``wire_bytes`` need on the ``level`` ("ici"/"dcn") link."""
    return max(float(wire_bytes), 0.0) / (spec.link_gbps(level) * 1e9)


def roofline(flops: float, hbm_bytes: float, exposed_ici_s: float,
             exposed_dcn_s: float, spec: ChipSpec,
             measured_seconds: Optional[float] = None) -> Dict[str, float]:
    """The roofline decomposition: per-resource floors, the predicted step
    floor (the binding compute/HBM floor plus all exposed communication —
    overlapped comm hides under compute by construction) and the MFU ceiling
    the program structure permits. With ``measured_seconds``, also attributes
    the measured wall time into compute / HBM-bound / exposed-ICI /
    exposed-DCN / host-gap residual."""
    compute_s = compute_floor_seconds(flops, spec)
    hbm_s = hbm_floor_seconds(hbm_bytes, spec)
    bound_s = max(compute_s, hbm_s)
    floor_s = bound_s + max(exposed_ici_s, 0.0) + max(exposed_dcn_s, 0.0)
    out = {
        "compute_floor_s": compute_s,
        "hbm_floor_s": hbm_s,
        "exposed_ici_s": max(exposed_ici_s, 0.0),
        "exposed_dcn_s": max(exposed_dcn_s, 0.0),
        "predicted_floor_s": floor_s,
        "mfu_ceiling": (compute_s / floor_s) if floor_s > 0 else 0.0,
    }
    if measured_seconds is not None:
        measured = max(float(measured_seconds), 0.0)
        out["measured_s"] = measured
        out["compute_s"] = compute_s
        out["hbm_bound_s"] = bound_s - compute_s
        out["host_gap_s"] = max(measured - floor_s, 0.0)
    return out
