"""Config system tests (parity with reference tests/unit/test_config.py semantics)."""

import json

import pytest

from deepspeed_tpu.runtime.config import DeepSpeedConfig


def base_dict(**over):
    d = {"train_batch_size": 8, "optimizer": {"type": "adam", "params": {"lr": 1e-3}}}
    d.update(over)
    return d


def test_batch_all_given():
    cfg = DeepSpeedConfig(base_dict(train_batch_size=32, train_micro_batch_size_per_gpu=4,
                                    gradient_accumulation_steps=2), world_size=4)
    assert cfg.train_batch_size == 32
    assert cfg.train_micro_batch_size_per_gpu == 4
    assert cfg.gradient_accumulation_steps == 2


def test_batch_infer_grad_acc():
    cfg = DeepSpeedConfig(base_dict(train_batch_size=32, train_micro_batch_size_per_gpu=4), world_size=4)
    assert cfg.gradient_accumulation_steps == 2


def test_batch_infer_micro():
    cfg = DeepSpeedConfig(base_dict(train_batch_size=32, gradient_accumulation_steps=2), world_size=4)
    assert cfg.train_micro_batch_size_per_gpu == 4


def test_batch_infer_train_batch():
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 4, "gradient_accumulation_steps": 2}, world_size=4)
    assert cfg.train_batch_size == 32


def test_batch_only_train_batch():
    cfg = DeepSpeedConfig(base_dict(train_batch_size=32), world_size=4)
    assert cfg.train_micro_batch_size_per_gpu == 8
    assert cfg.gradient_accumulation_steps == 1


def test_batch_only_micro():
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 4}, world_size=4)
    assert cfg.train_batch_size == 16
    assert cfg.gradient_accumulation_steps == 1


def test_batch_nothing_raises():
    with pytest.raises(AssertionError):
        DeepSpeedConfig({"gradient_accumulation_steps": 2}, world_size=4)


def test_batch_mismatch_raises():
    with pytest.raises(AssertionError):
        DeepSpeedConfig(base_dict(train_batch_size=32, train_micro_batch_size_per_gpu=5,
                                  gradient_accumulation_steps=2), world_size=4)


def test_duplicate_key_rejected(tmp_path):
    p = tmp_path / "cfg.json"
    p.write_text('{"train_batch_size": 8, "train_batch_size": 16}')
    with pytest.raises(ValueError):
        DeepSpeedConfig(str(p), world_size=1)


def test_json_file_load(tmp_path):
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps(base_dict()))
    cfg = DeepSpeedConfig(str(p), world_size=1)
    assert cfg.train_batch_size == 8
    assert cfg.optimizer_name == "adam"
    assert cfg.optimizer_params["lr"] == 1e-3


def test_zero_config():
    cfg = DeepSpeedConfig(base_dict(fp16={"enabled": True},
                                    zero_optimization={"stage": 2, "cpu_offload": True,
                                                       "reduce_bucket_size": 1000}), world_size=1)
    assert cfg.zero_enabled
    assert cfg.zero_optimization_stage == 2
    assert cfg.zero_config.cpu_offload
    assert cfg.zero_config.reduce_bucket_size == 1000


def test_zero_requires_mixed_precision_ok_with_bf16_default():
    cfg = DeepSpeedConfig(base_dict(zero_optimization={"stage": 1}), world_size=1)
    assert cfg.zero_enabled and cfg.bf16_enabled


def test_zero_stage_bounds():
    # stage 3 (parameter sharding) is supported — beyond the v0.3.0 reference;
    # stage 4 does not exist
    cfg = DeepSpeedConfig(base_dict(zero_optimization={"stage": 3}), world_size=1)
    assert cfg.zero_optimization_stage == 3
    with pytest.raises(AssertionError):
        DeepSpeedConfig(base_dict(zero_optimization={"stage": 4}), world_size=1)


def test_cpu_offload_requires_stage2():
    with pytest.raises(AssertionError):
        DeepSpeedConfig(base_dict(zero_optimization={"stage": 1, "cpu_offload": True}), world_size=1)


def test_fp16_loss_scale_knobs():
    cfg = DeepSpeedConfig(base_dict(fp16={"enabled": True, "loss_scale": 0, "initial_scale_power": 16,
                                          "loss_scale_window": 500, "hysteresis": 4, "min_loss_scale": 2}),
                          world_size=1)
    assert cfg.fp16_enabled
    assert not cfg.bf16_enabled
    assert cfg.loss_scale == 0
    assert cfg.initial_scale_power == 16
    assert cfg.loss_scale_window == 500
    assert cfg.hysteresis == 4
    assert cfg.min_loss_scale == 2


def test_scheduler_block():
    cfg = DeepSpeedConfig(base_dict(scheduler={"type": "WarmupLR",
                                               "params": {"warmup_num_steps": 10}}), world_size=1)
    assert cfg.scheduler_name == "WarmupLR"
    assert cfg.scheduler_params["warmup_num_steps"] == 10


def test_sparse_attention_block():
    cfg = DeepSpeedConfig(base_dict(sparse_attention={"mode": "fixed", "block": 16,
                                                      "num_local_blocks": 4}), world_size=1)
    assert cfg.sparse_attention.mode == "fixed"
    assert cfg.sparse_attention.block == 16
    assert cfg.sparse_attention.num_local_blocks == 4


def _capture_warnings(monkeypatch):
    from deepspeed_tpu.utils import logger
    msgs = []
    monkeypatch.setattr(logger, "warning", lambda m, *a: msgs.append(m % a if a else m))
    return msgs


def test_offload_optimizer_block_parses_and_implies_offload():
    cfg = DeepSpeedConfig(base_dict(zero_optimization={
        "stage": 2, "offload_optimizer": {"device": "cpu", "pipeline": True,
                                          "pipeline_depth": 3,
                                          "max_region_elements": 1 << 22}}), world_size=1)
    zc = cfg.zero_config
    assert zc.cpu_offload  # the block implies the legacy enable switch
    assert zc.offload_device == "cpu"
    assert zc.offload_pipeline is True
    assert zc.offload_pipeline_depth == 3
    assert zc.offload_max_region_elements == 1 << 22


def test_offload_optimizer_defaults():
    cfg = DeepSpeedConfig(base_dict(zero_optimization={"stage": 2, "cpu_offload": True}),
                          world_size=1)
    zc = cfg.zero_config
    assert zc.offload_device == "cpu"
    assert zc.offload_pipeline is True
    assert zc.offload_pipeline_depth == 2
    assert zc.offload_max_region_elements == "auto"


def test_offload_optimizer_explicit_disable_wins(monkeypatch):
    msgs = _capture_warnings(monkeypatch)
    cfg = DeepSpeedConfig(base_dict(zero_optimization={
        "stage": 2, "cpu_offload": False, "offload_optimizer": {"pipeline_depth": 4}}),
        world_size=1)
    assert cfg.zero_config.cpu_offload is False  # the explicit boolean wins
    assert cfg.zero_config.offload_pipeline_depth == 4
    assert any("explicitly" in m and "DISABLED" in m for m in msgs), msgs


def test_offload_optimizer_validation():
    with pytest.raises(ValueError, match="must be a dict"):
        DeepSpeedConfig(base_dict(zero_optimization={"stage": 2,
                                                     "offload_optimizer": "cpu"}),
                        world_size=1)
    with pytest.raises(ValueError, match="not supported"):
        DeepSpeedConfig(base_dict(zero_optimization={
            "stage": 2, "offload_optimizer": {"device": "nvme"}}), world_size=1)
    with pytest.raises(ValueError, match="pipeline_depth"):
        DeepSpeedConfig(base_dict(zero_optimization={
            "stage": 2, "offload_optimizer": {"pipeline_depth": 0}}), world_size=1)
    with pytest.raises(ValueError, match="max_region_elements"):
        DeepSpeedConfig(base_dict(zero_optimization={
            "stage": 2, "offload_optimizer": {"max_region_elements": -1}}), world_size=1)


def test_offload_optimizer_unknown_key_warns(monkeypatch):
    msgs = _capture_warnings(monkeypatch)
    DeepSpeedConfig(base_dict(zero_optimization={
        "stage": 2, "offload_optimizer": {"buffer_count": 4}}), world_size=1)
    assert any("unknown" in m and "buffer_count" in m for m in msgs), msgs


def test_comm_dtype_conflict_warns(monkeypatch):
    """allreduce_always_fp32 + a conflicting communication_data_type must warn and
    name the winner (the explicit dtype — engine.py applies it last)."""
    msgs = _capture_warnings(monkeypatch)
    DeepSpeedConfig(base_dict(bf16={"enabled": True}, allreduce_always_fp32=True,
                              communication_data_type="bf16"), world_size=1)
    assert any("communication_data_type wins" in m and "bf16" in m for m in msgs), msgs

    msgs.clear()
    # agreeing settings (fp32 + fp32) stay silent
    DeepSpeedConfig(base_dict(bf16={"enabled": True}, allreduce_always_fp32=True,
                              communication_data_type="fp32"), world_size=1)
    assert not any("communication_data_type wins" in m for m in msgs), msgs
