"""Checkpoint save/resume integration test across real subprocess boundaries.

Analog of reference ``tests/model/Megatron_GPT2/run_checkpoint_test.py``: train N steps in
one process saving midway, then resume in a FRESH process from the checkpoint and verify
the post-resume loss trajectory exactly tracks an uninterrupted run (engine + optimizer +
LR-scheduler state all round-trip through disk)."""

import pytest

from .test_common import load_config, run_gpt2

STEPS = 8
SAVE_AT = 4


@pytest.mark.parametrize("config_name", [
    "ds_config_func_bs8_zero2.json",
    "ds_config_func_scheduler.json"])
def test_resume_matches_straight_run(config_name, tmp_path, tmp_path_factory):
    cfg = load_config(config_name)
    ckpt = tmp_path / "ckpt"

    straight, _ = run_gpt2(cfg, tmp_path / "straight", steps=STEPS, name="straight")

    _first, _ = run_gpt2(cfg, tmp_path / "first", steps=SAVE_AT, name="first",
                         extra_args=["--save-dir", ckpt, "--save-interval", SAVE_AT])
    resumed, proc = run_gpt2(cfg, tmp_path / "resumed", steps=STEPS, name="resumed",
                             extra_args=["--load-dir", ckpt])

    assert f"resumed_from: {SAVE_AT}" in proc.stdout
    assert [r["step"] for r in resumed] == list(range(SAVE_AT + 1, STEPS + 1))

    tail_straight = [r for r in straight if r["step"] > SAVE_AT]
    assert [r["loss"] for r in resumed] == pytest.approx(
        [r["loss"] for r in tail_straight], rel=1e-4, abs=1e-4), \
        f"resumed trajectory diverged:\n  straight={tail_straight}\n  resumed={resumed}"
    assert [r["lr"] for r in resumed] == pytest.approx(
        [r["lr"] for r in tail_straight], rel=1e-6), "LR schedule state did not resume"
