"""The chip-spec table and the floor arithmetic of ``utils/roofline.py``: pure math."""

import jax
import pytest

from deepspeed_tpu.utils.roofline import (CHIP_SPECS, ChipSpec, resolve_spec,
                                          roofline)


# ----------------------------------------------------------------- roofline
def test_resolve_spec_table_and_overrides():
    spec = resolve_spec("tpu-v5e")
    assert spec.peak_tflops == CHIP_SPECS["tpu-v5e"].peak_tflops
    over = resolve_spec("tpu-v5e", hbm_gbps=1000.0)
    assert over.hbm_gbps == 1000.0
    assert over.peak_tflops == spec.peak_tflops  # 0 keeps the table value
    with pytest.raises(ValueError, match="unknown chip"):
        resolve_spec("tpu-v9000")


@pytest.mark.parametrize("platform,kind,expect", [
    ("cpu", "cpu", "cpu-test"),
    ("tpu", "TPU v5 lite", "tpu-v5e"),       # the string a v5e chip reports (PR 21)
    ("tpu", "TPU v6 lite", "tpu-v6e"),
    ("tpu", "TPU v9000", None),
    ("gpu", "NVIDIA H100", None),
])
def test_detect_chip_knows_the_device_or_raises(monkeypatch, platform, kind, expect):
    """``cpu-test`` is for the CPU alone: an accelerator the table does not know
    is an error, not a default that prices a roofline off made-up rates."""
    from types import SimpleNamespace
    from deepspeed_tpu.utils.roofline import detect_chip
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [SimpleNamespace(platform=platform, device_kind=kind)])
    if expect is None:
        with pytest.raises(ValueError, match="no chip spec"):
            detect_chip()
    else:
        assert detect_chip() == expect


def test_roofline_floor_and_ceiling_arithmetic():
    spec = ChipSpec("t", peak_tflops=1.0, hbm_gbps=1.0, ici_gbps=1.0,
                    dcn_gbps=1.0)
    # 1e12 flops at 1 TFLOP/s = 1 s compute; 5e8 bytes at 1 GB/s = 0.5 s HBM
    rf = roofline(1e12, 5e8, exposed_ici_s=0.25, exposed_dcn_s=0.25, spec=spec)
    assert rf["compute_floor_s"] == pytest.approx(1.0)
    assert rf["hbm_floor_s"] == pytest.approx(0.5)
    # floor = binding bound (compute) + exposed comm
    assert rf["predicted_floor_s"] == pytest.approx(1.5)
    assert rf["mfu_ceiling"] == pytest.approx(1.0 / 1.5)
    # attribution against a measured time
    rf = roofline(1e12, 5e8, 0.25, 0.25, spec, measured_seconds=2.0)
    assert rf["hbm_bound_s"] == pytest.approx(0.0)   # compute binds, not HBM
    assert rf["host_gap_s"] == pytest.approx(0.5)


def test_roofline_hbm_bound_program():
    spec = ChipSpec("t", peak_tflops=1.0, hbm_gbps=1.0, ici_gbps=1.0,
                    dcn_gbps=1.0)
    rf = roofline(1e10, 2e9, 0.0, 0.0, spec, measured_seconds=3.0)
    assert rf["hbm_floor_s"] == pytest.approx(2.0)
    assert rf["compute_s"] == pytest.approx(0.01)
    assert rf["hbm_bound_s"] == pytest.approx(2.0 - 0.01)
    assert rf["host_gap_s"] == pytest.approx(1.0)
