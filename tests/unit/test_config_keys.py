"""Every accepted config key acts, warns, or errors — never a silent no-op.

Sweeps the TOP_LEVEL_CONFIG_KEYS registry (runtime/constants.py): for each key,
setting a non-default value must either change engine-visible DeepSpeedConfig
state, emit a diagnostic through the package logger, or raise. Mirrors the
reference's error/warning discipline (deepspeed/runtime/config.py:633-670) and
extends it with the TPU-migration diagnostics for keys whose CUDA mechanism
(apex amp, hand-written bucketed collectives, fused-kernel variants) has no
GSPMD analog.
"""

import logging

import numpy as np
import pytest

from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.constants import TOP_LEVEL_CONFIG_KEYS
from deepspeed_tpu.utils import logger


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.records = []

    def emit(self, record):
        self.records.append(record)

    @property
    def text(self):
        return "\n".join(r.getMessage() for r in self.records)


@pytest.fixture
def capture():
    h = _Capture()
    logger.addHandler(h)
    try:
        yield h
    finally:
        logger.removeHandler(h)


BASE = {"train_batch_size": 8}


def _cfg(**over):
    d = dict(BASE)
    d.update(over)
    return DeepSpeedConfig(d, world_size=1)


# key -> (test value, expectation). Expectations:
#   ("attr", name, value)  config attribute takes the value
#   ("warn", substring)    diagnostic emitted containing substring
#   ("raise", exc)         parse rejects the value
# A key may map to a tuple of several (value, expectation) probes.
SWEEP = {
    "train_batch_size": (16, ("attr", "train_batch_size", 16)),
    "train_micro_batch_size_per_gpu": (4, ("attr", "train_micro_batch_size_per_gpu", 4)),
    "train_micro_batch_size_per_device": (4, ("attr", "train_micro_batch_size_per_gpu", 4)),
    "gradient_accumulation_steps": (2, ("attr", "gradient_accumulation_steps", 2)),
    "sparse_gradients": (True, ("attr", "sparse_gradients_enabled", True)),
    "optimizer": ({"type": "Lamb", "params": {"lr": 1e-3}},
                  ("attr", "optimizer_name", "lamb")),
    "scheduler": ({"type": "WarmupLR", "params": {}},
                  ("attr", "scheduler_name", "WarmupLR")),
    "fp16": ({"enabled": True, "loss_scale": 128}, ("attr", "loss_scale", 128)),
    "bf16": ({"enabled": False}, ("attr", "bf16_enabled", False)),
    "amp": ({"enabled": True, "opt_level": "O1"}, ("warn", "bf16")),
    "gradient_clipping": (1.0, ("attr", "gradient_clipping", 1.0)),
    "communication_data_type": (
        ("fp16", ("attr", "communication_data_type", "fp16")),
        ("int8", ("raise", ValueError)),
    ),
    "prescale_gradients": (True, ("attr", "prescale_gradients", True)),
    "fused_step": (True, ("attr", "fused_step", True)),
    "gradient_predivide_factor": (2.0, ("attr", "gradient_predivide_factor", 2.0)),
    "disable_allgather": (True, ("warn", "no effect")),
    "allreduce_always_fp32": (True, ("attr", "allreduce_always_fp32", True)),
    "fp32_allreduce": (True, ("warn", "deprecated")),
    "steps_per_print": (5, ("attr", "steps_per_print", 5)),
    "dump_state": (True, ("attr", "dump_state", True)),
    "vocabulary_size": (1001, ("warn", "aligned")),
    "wall_clock_breakdown": (True, ("attr", "wall_clock_breakdown", True)),
    "memory_breakdown": (True, ("attr", "memory_breakdown", True)),
    "tensorboard": ({"enabled": True, "job_name": "j"},
                    ("attr", "tensorboard_job_name", "j")),
    "telemetry": (
        ({"enabled": True, "peak_tflops": 123.0}, ("attr", "telemetry_peak_tflops", 123.0)),
        ({"enabled": True, "trace_steps": [2, 5]},
         ("attr", "telemetry_trace_steps", (2, 5))),
        ({"enabled": True, "trace_steps": [5, 2]}, ("raise", ValueError)),
        ({"pipeline_trace": {"enabled": True, "capacity": 7}},
         ("attr", "pipeline_trace_capacity", 7)),
        ({"pipeline_trace": {"enabled": True, "dump_dir": "/tmp/pt"}},
         ("attr", "pipeline_trace_dump_dir", "/tmp/pt")),
        ({"pipeline_trace": {"enabled": True, "capacity": 0}}, ("raise", ValueError)),
        ({"enabled": True, "cluster": {"enabled": True}},
         ("attr", "telemetry_cluster_enabled", True)),
        ({"enabled": True, "cluster": {"enabled": True, "heartbeat_interval": 5}},
         ("attr", "telemetry_cluster_heartbeat_interval", 5)),
        ({"enabled": True, "cluster": {"enabled": True, "hang_deadline_s": 90}},
         ("attr", "telemetry_cluster_hang_deadline_s", 90.0)),
        ({"enabled": True, "cluster": {"enabled": True, "dump_dir": "/tmp/cl"}},
         ("attr", "telemetry_cluster_dump_dir", "/tmp/cl")),
        ({"enabled": True, "cluster": {"enabled": True, "straggler_threshold": 2.5}},
         ("attr", "telemetry_cluster_straggler_threshold", 2.5)),
        ({"enabled": True, "cluster": {"enabled": True, "signal_peers": False}},
         ("attr", "telemetry_cluster_signal_peers", False)),
        ({"enabled": True, "cluster": {"enabled": True, "warmup_steps": 3}},
         ("attr", "telemetry_cluster_warmup_steps", 3)),
        ({"enabled": True, "goodput": {"enabled": True}},
         ("attr", "telemetry_goodput_enabled", True)),
        ({"enabled": True, "goodput": {"enabled": True, "ledger_dir": "/tmp/gp"}},
         ("attr", "telemetry_goodput_ledger_dir", "/tmp/gp")),
        ({"enabled": True, "goodput": {"enabled": True, "emit_scalars": False}},
         ("attr", "telemetry_goodput_emit_scalars", False)),
        ({"enabled": True, "goodput": {"enabled": True, "eval_tag": "validation"}},
         ("attr", "telemetry_goodput_eval_tag", "validation")),
        # the ledger closes its step intervals on the telemetry end_step
        # record — no telemetry, no goodput
        ({"goodput": {"enabled": True}}, ("raise", ValueError)),
        ({"enabled": True, "goodput": {"enabled": True, "eval_tag": ""}},
         ("raise", ValueError)),
        ({"enabled": True, "goodput": {"enabled": True, "emit_scalars": 1}},
         ("raise", ValueError)),
        ({"enabled": True, "goodput": {"enabled": True, "ledger_dir": 5}},
         ("raise", ValueError)),
        # the heartbeat rides the telemetry end_step record — no telemetry, no cluster
        ({"cluster": {"enabled": True}}, ("raise", ValueError)),
        ({"enabled": True, "cluster": {"enabled": True, "heartbeat_interval": 0}},
         ("raise", ValueError)),
        ({"enabled": True, "cluster": {"enabled": True, "hang_deadline_s": -1}},
         ("raise", ValueError)),
        ({"enabled": True, "cluster": {"enabled": True, "straggler_threshold": 1.0}},
         ("raise", ValueError)),
        ({"enabled": True, "cluster": {"enabled": True, "warmup_steps": -1}},
         ("raise", ValueError)),
        ({"enabled": True, "cluster": {"enabled": True, "warmup_steps": True}},
         ("raise", ValueError)),
    ),
    "numerics": (
        ({"enabled": True, "audit_interval": 7}, ("attr", "numerics_audit_interval", 7)),
        ({"enabled": True, "subtree_depth": 0}, ("raise", ValueError)),
        ({"enabled": True, "ring_size": 0}, ("raise", ValueError)),
    ),
    "serving": (
        ({"enabled": True, "block_size": 8, "max_model_len": 64},
         ("attr", "serving_block_size", 8)),
        ({"num_blocks": 1025}, ("attr", "serving_num_blocks", 1025)),
        ({"max_seqs": 16}, ("attr", "serving_max_seqs", 16)),
        ({"prefill_chunk": 64}, ("attr", "serving_prefill_chunk", 64)),
        ({"use_pallas_decode": True}, ("attr", "serving_use_pallas_decode", True)),
        ({"num_blocks": 1}, ("raise", ValueError)),     # no room for null page
        ({"block_size": 0}, ("raise", ValueError)),
        # paged gather bit-matches the oracle only when the tiling is exact
        ({"block_size": 16, "max_model_len": 100}, ("raise", ValueError)),
        ({"request_trace": {"enabled": True}},
         ("attr", "serving_request_trace_enabled", True)),
        ({"request_trace": {"enabled": True, "capacity": 33}},
         ("attr", "serving_request_trace_capacity", 33)),
        ({"request_trace": {"iteration_capacity": 99}},
         ("attr", "serving_request_trace_iteration_capacity", 99)),
        ({"request_trace": {"dump_dir": "/tmp/rt"}},
         ("attr", "serving_request_trace_dump_dir", "/tmp/rt")),
        ({"request_trace": {"slo": {"ttft_ms": 250.0}}},
         ("attr", "serving_slo_ttft_ms", 250.0)),
        ({"request_trace": {"slo": {"tpot_ms": 40}}},
         ("attr", "serving_slo_tpot_ms", 40.0)),
        ({"request_trace": {"capacity": 0}}, ("raise", ValueError)),
        ({"request_trace": {"iteration_capacity": 0}}, ("raise", ValueError)),
        ({"request_trace": {"slo": {"ttft_ms": -1}}}, ("raise", ValueError)),
        ({"request_trace": {"slo": {"tpot_ms": True}}}, ("raise", ValueError)),
        ({"sharding": {"model": 2}},
         ("attr", "serving_sharding_model", 2)),
        ({"sharding": {"model": 0}}, ("raise", ValueError)),
        ({"sharding": {"model": True}}, ("raise", ValueError)),
        ({"prefix_cache": {"enabled": True}},
         ("attr", "serving_prefix_cache_enabled", True)),
        ({"speculation": {"enabled": True}},
         ("attr", "serving_speculation_enabled", True)),
        ({"speculation": {"draft_model": "gpt2-124m"}},
         ("attr", "serving_speculation_draft_model", "gpt2-124m")),
        ({"speculation": {"max_draft_tokens": 6}},
         ("attr", "serving_speculation_max_draft_tokens", 6)),
        ({"speculation": {"draft_pool_blocks": 65}},
         ("attr", "serving_speculation_draft_pool_blocks", 65)),
        ({"speculation": {"max_draft_tokens": 0}}, ("raise", ValueError)),
        ({"speculation": {"max_draft_tokens": True}}, ("raise", ValueError)),
        # block 0 is the reserved null page: 1 usable block can't exist
        ({"speculation": {"draft_pool_blocks": 1}}, ("raise", ValueError)),
        ({"fleet": {"replicas": 3}},
         ("attr", "serving_fleet_replicas", 3)),
        ({"fleet": {"policy": "round_robin"}},
         ("attr", "serving_fleet_policy", "round_robin")),
        ({"fleet": {"affinity_weight": 2.5}},
         ("attr", "serving_fleet_affinity_weight", 2.5)),
        ({"fleet": {"max_queue_depth": 12}},
         ("attr", "serving_fleet_max_queue_depth", 12)),
        ({"fleet": {"occupancy_cap": 0.9}},
         ("attr", "serving_fleet_occupancy_cap", 0.9)),
        ({"fleet": {"goodput_floor": 0.85}},
         ("attr", "serving_fleet_goodput_floor", 0.85)),
        ({"fleet": {"replicas": 0}}, ("raise", ValueError)),
        ({"fleet": {"replicas": True}}, ("raise", ValueError)),
        ({"fleet": {"policy": "random"}}, ("raise", ValueError)),
        ({"fleet": {"affinity_weight": -1}}, ("raise", ValueError)),
        ({"fleet": {"max_queue_depth": -2}}, ("raise", ValueError)),
        ({"fleet": {"occupancy_cap": 0.0}}, ("raise", ValueError)),
        ({"fleet": {"occupancy_cap": 1.5}}, ("raise", ValueError)),
        ({"fleet": {"goodput_floor": 2.0}}, ("raise", ValueError)),
        ({"fleet": {"nonsense_key": 1}}, ("warn", "unknown serving.fleet")),
    ),
    "resilience": (
        ({"enabled": True, "save_dir": "/tmp/ckpt"},
         ("attr", "resilience_enabled", True)),
        ({"save_dir": "/tmp/ckpt"}, ("attr", "resilience_save_dir", "/tmp/ckpt")),
        ({"save_dir": "/tmp/ckpt", "save_interval": 50},
         ("attr", "resilience_save_interval", 50)),
        ({"async_save": False}, ("attr", "resilience_async_save", False)),
        ({"auto_resume": True}, ("attr", "resilience_auto_resume", True)),
        ({"save_interval": -1}, ("raise", ValueError)),
        ({"save_interval": True}, ("raise", ValueError)),
        # periodic saves with nowhere to put them is a config bug, not a no-op
        ({"enabled": True, "save_interval": 5}, ("raise", ValueError)),
        ({"nonsense_key": 1}, ("warn", "unknown resilience")),
    ),
    "comm": (
        ({"mode": "hierarchical"}, ("attr", "comm_mode", "hierarchical")),
        ({"mode": "hierarchical_compressed", "compress_start_step": 5},
         ("attr", "comm_compress_start_step", 5)),
        ({"dcn_slices": 2}, ("attr", "comm_dcn_slices", 2)),
        ({"mode": "ring"}, ("raise", ValueError)),
        ({"dcn_slices": -1}, ("raise", ValueError)),
        ({"compress_start_step": -3}, ("raise", ValueError)),
        ({"overlap": {"mode": "bucketed"}},
         ("attr", "comm_overlap_mode", "bucketed")),
        ({"overlap": {"mode": "bucketed", "bucket_mb": 12.5}},
         ("attr", "comm_overlap_bucket_mb", 12.5)),
        ({"overlap": {}}, ("attr", "comm_overlap_mode", "off")),
        ({"overlap": {"mode": "eager"}}, ("raise", ValueError)),
        ({"overlap": {"bucket_mb": 0}}, ("raise", ValueError)),
        ({"overlap": {"bucket_mb": True}}, ("raise", ValueError)),
    ),
    "sparse_attention": ({"mode": "fixed", "block": 16},
                         ("attr_pred", lambda c: c.sparse_attention.mode == "fixed")),
    "sequence_parallel": ({"enabled": True, "schedule": "masked"},
                          ("attr", "sequence_parallel_schedule", "masked")),
    "pipeline": ({"stages": 2}, ("attr_pred", lambda c: c.pipeline["stages"] == 2)),
    "zero_optimization": (
        ({"stage": 2}, ("attr", "zero_optimization_stage", 2)),
        ({"stage": 1, "overlap_comm": True}, ("warn", "no effect")),
        ({"stage": 1, "nonsense_key": 1}, ("warn", "unknown zero_optimization")),
        ({"stage": 1, "elastic_checkpoint": False}, ("warn", "elastic")),
    ),
    "zero_allow_untested_optimizer": (True, ("attr", "zero_allow_untested_optimizer", True)),
    "activation_checkpointing": (
        {"partition_activations": True},
        ("attr_pred", lambda c: c.activation_checkpointing_config.partition_activations)),
    # deprecated boolean-zero companion key: honored with {"zero_optimization": true}
    # (test_deprecated_boolean_zero_reads_allgather_size), warns otherwise
    "allgather_size": (500000000, ("warn", "only honored")),
}


def _run_probe(key, value, expect, capture):
    capture.records.clear()
    if expect[0] == "raise":
        with pytest.raises(expect[1]):
            _cfg(**{key: value})
        return
    cfg = _cfg(**{key: value})
    if expect[0] == "attr":
        assert getattr(cfg, expect[1]) == expect[2], key
    elif expect[0] == "attr_pred":
        assert expect[1](cfg), key
    elif expect[0] == "warn":
        assert expect[1] in capture.text, (key, capture.text)


@pytest.mark.parametrize("key", sorted(TOP_LEVEL_CONFIG_KEYS))
def test_every_registered_key_acts_or_diagnoses(key, capture):
    assert key in SWEEP, f"registry key {key!r} has no sweep probe — add one"
    probes = SWEEP[key]
    if not isinstance(probes[0], tuple):  # single (value, expect) pair
        probes = (probes,)
    for value, expect in probes:
        _run_probe(key, value, expect, capture)


def test_sweep_covers_exactly_the_registry():
    assert set(SWEEP) == set(TOP_LEVEL_CONFIG_KEYS)


def test_unknown_top_level_key_warns(capture):
    _cfg(definitely_not_a_key=1)
    assert "unknown top-level config key" in capture.text
    assert "definitely_not_a_key" in capture.text


def test_unknown_telemetry_key_warns(capture):
    _cfg(telemetry={"enabled": True, "trace_stepz": [2, 5]})
    assert "unknown telemetry config key" in capture.text
    assert "trace_stepz" in capture.text
    # the known-keys hint points at the fix
    assert "trace_steps" in capture.text


def test_unknown_pipeline_trace_key_warns(capture):
    _cfg(telemetry={"pipeline_trace": {"enabled": True, "capactiy": 7}})
    assert "unknown telemetry.pipeline_trace config key" in capture.text
    assert "capactiy" in capture.text


def _state(cfg):
    """Everything a DeepSpeedConfig parsed, the raw dict left out and its
    nested config objects opened."""
    return {k: (v if isinstance(v, (type(None), bool, int, float, str, tuple,
                                    list, dict)) else vars(v))
            for k, v in vars(cfg).items() if k != "_param_dict"}


@pytest.mark.parametrize("block", ["anatomy", "profile", "metrics", "alerts"])
def test_a_removed_telemetry_block_warns_and_changes_nothing(capture, block):
    """``telemetry.anatomy``, ``telemetry.profile``, ``telemetry.metrics`` and
    ``telemetry.alerts`` named modules that are gone: a config that still
    carries one is told so like any unknown key, and parses to what it would
    without it."""
    with_block = _cfg(telemetry={"enabled": True, "trace_steps": [2, 5],
                                 block: {"enabled": True}})
    assert "unknown telemetry config key" in capture.text
    assert f"['{block}']" in capture.text
    without = _cfg(telemetry={"enabled": True, "trace_steps": [2, 5]})
    assert _state(with_block) == _state(without)


def test_unknown_goodput_key_warns(capture):
    _cfg(telemetry={"enabled": True,
                    "goodput": {"enabled": True, "ledger_dirr": "/tmp/gp"}})
    assert "unknown telemetry.goodput config key" in capture.text
    assert "ledger_dirr" in capture.text
    assert "ledger_dir" in capture.text  # the known-keys hint points at the fix


def test_unknown_cluster_key_warns(capture):
    _cfg(telemetry={"enabled": True,
                    "cluster": {"enabled": True, "hang_deadline": 60}})
    assert "unknown telemetry.cluster config key" in capture.text
    assert "hang_deadline" in capture.text
    assert "hang_deadline_s" in capture.text  # the known-keys hint points at the fix


def test_unknown_serving_key_warns(capture):
    _cfg(serving={"enabled": True, "blok_size": 8})
    assert "unknown serving config key" in capture.text
    assert "blok_size" in capture.text


def test_unknown_request_trace_key_warns(capture):
    _cfg(serving={"request_trace": {"enabled": True, "capactiy": 7}})
    assert "unknown serving.request_trace config key" in capture.text
    assert "capactiy" in capture.text


def test_unknown_request_trace_slo_key_warns(capture):
    _cfg(serving={"request_trace": {"slo": {"ttft": 250.0}}})
    assert "unknown serving.request_trace.slo config key" in capture.text
    assert "ttft" in capture.text
    assert "ttft_ms" in capture.text     # the known-keys hint points at the fix


def test_unknown_serving_sharding_key_warns(capture):
    _cfg(serving={"sharding": {"model": 2, "modle": 4}})
    assert "unknown serving.sharding config key" in capture.text
    assert "modle" in capture.text
    assert "model" in capture.text       # the known-keys hint points at the fix


def test_unknown_prefix_cache_key_warns(capture):
    _cfg(serving={"prefix_cache": {"enabled": True, "enabeld": False}})
    assert "unknown serving.prefix_cache config key" in capture.text
    assert "enabeld" in capture.text
    assert "enabled" in capture.text     # the known-keys hint points at the fix


def test_unknown_speculation_key_warns(capture):
    _cfg(serving={"speculation": {"enabled": True, "max_draft_tokns": 4}})
    assert "unknown serving.speculation config key" in capture.text
    assert "max_draft_tokns" in capture.text
    assert "max_draft_tokens" in capture.text  # known-keys hint has the fix


def test_unknown_comm_key_warns(capture):
    _cfg(comm={"mode": "hierarchical", "dcn_slicez": 2})
    assert "unknown comm config key" in capture.text
    assert "dcn_slicez" in capture.text
    assert "dcn_slices" in capture.text  # the known-keys hint points at the fix


def test_unknown_comm_overlap_key_warns(capture):
    _cfg(comm={"overlap": {"mode": "bucketed", "bucket_md": 25}})
    assert "unknown comm.overlap config key" in capture.text
    assert "bucket_md" in capture.text
    assert "bucket_mb" in capture.text   # the known-keys hint points at the fix


def test_unknown_numerics_key_warns(capture):
    _cfg(numerics={"enabled": True, "ring_sz": 4})
    assert "unknown numerics config key" in capture.text
    assert "ring_sz" in capture.text


def test_known_nested_keys_do_not_warn(capture):
    _cfg(telemetry={"enabled": True, "trace_steps": [2, 5],
                    "pipeline_trace": {"enabled": True, "capacity": 7},
                    "goodput": {"enabled": True, "ledger_dir": "/tmp/gp",
                                "emit_scalars": True, "eval_tag": "eval"},
                    "cluster": {"enabled": True, "heartbeat_interval": 2,
                                "hang_deadline_s": 120.0, "dump_dir": "/tmp/cl",
                                "straggler_threshold": 3.0,
                                "signal_peers": True, "warmup_steps": 2}},
         numerics={"enabled": True, "audit_interval": 3},
         serving={"request_trace": {"enabled": True, "capacity": 64,
                                    "slo": {"ttft_ms": 250.0, "tpot_ms": 40.0}}},
         comm={"mode": "hierarchical", "dcn_slices": 2,
               "overlap": {"mode": "bucketed", "bucket_mb": 25.0}})
    assert "unknown" not in capture.text


def test_deprecated_boolean_zero_reads_allgather_size(capture):
    cfg = _cfg(zero_optimization=True, allgather_size=123456)
    assert cfg.zero_optimization_stage == 1
    assert cfg.zero_config.allgather_bucket_size == 123456
    assert "deprecated" in capture.text


def test_amp_plus_fp16_is_an_error():
    with pytest.raises(AssertionError, match="amp"):
        _cfg(amp={"enabled": True}, fp16={"enabled": True})


def test_amp_maps_to_bf16_policy(capture):
    cfg = _cfg(amp={"enabled": True}, bf16={"enabled": False})
    assert cfg.bf16_enabled  # amp overrides the explicit bf16 opt-out
    assert "bf16" in capture.text


def test_legacy_fusion_warns(capture):
    _cfg(optimizer={"type": "Adam", "params": {"lr": 1e-3}, "legacy_fusion": True})
    assert "legacy_fusion" in capture.text


def test_grad_comm_dtype_reaches_the_engine():
    """allreduce_always_fp32 / communication_data_type steer the dtype gradients
    are produced (and psum'd) in — reference engine.py:1016-1089."""
    import jax.numpy as jnp
    import deepspeed_tpu
    from simple_model import SimpleModel, simple_config

    def build(**over):
        model = SimpleModel(4)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=model.init(__import__("jax").random.PRNGKey(0)),
            config_params=simple_config(**over))
        return eng

    assert build(zero_optimization={"stage": 2})._grad_dtype == jnp.bfloat16
    assert build(zero_optimization={"stage": 2},
                 allreduce_always_fp32=True)._grad_dtype == jnp.float32
    assert build(communication_data_type="bf16")._grad_dtype == jnp.bfloat16
    assert build()._grad_dtype == jnp.float32


def test_untested_client_optimizer_under_zero_requires_opt_in():
    import jax
    import deepspeed_tpu
    from simple_model import SimpleModel, simple_config

    def init(params):
        return {}

    def apply(grads, opt_state, params, **kw):
        return params, opt_state

    model = SimpleModel(4)
    with pytest.raises(AssertionError, match="untested"):
        deepspeed_tpu.initialize(
            model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
            optimizer=(init, apply),
            config_params=simple_config(zero_optimization={"stage": 2}))
