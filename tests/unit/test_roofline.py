"""``utils/roofline.detect_chip``: the local device's name in the table, or an error."""

import jax
import pytest


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
