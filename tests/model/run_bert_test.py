"""BERT QA fine-tuning integration tests (BingBertSquad analog).

Mirrors the reference's ``tests/model/BingBertSquad/test_e2e_squad.py`` intent: run the
fine-tuning workload as a subprocess under fp16 and ZeRO configs and check convergence.
"""

import math
import os

import pytest

from .test_common import THIS_DIR, load_config, run_workload

SCRIPT = os.path.join(THIS_DIR, "bert_squad_finetune.py")
STEPS = 8


def _run_bert(config_name, tmp_path):
    records, proc = run_workload(SCRIPT, load_config(config_name), tmp_path,
                                 steps=STEPS, name="bert")
    return records, proc.stdout


@pytest.mark.parametrize("config_name", [
    "ds_config_func_bs8_zero2.json",
    "ds_config_func_bs8_fp16.json"])
def test_bert_qa_finetune_converges(config_name, tmp_path):
    records, stdout = _run_bert(config_name, tmp_path)
    assert len(records) == STEPS, stdout
    losses = [r["loss"] for r in records]
    assert all(math.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], f"QA loss did not decrease: {losses}"
    assert "training_complete" in stdout
