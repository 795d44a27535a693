"""Config key constants and defaults.

Mirrors the key surface of the reference's ``deepspeed/runtime/constants.py`` (293 LoC) so a
DeepSpeed JSON config is accepted unchanged. TPU-specific additions are marked; CUDA-only
knobs are accepted and either honored semantically or ignored with a logged warning.
"""

#############################################
# Routes
#############################################
ROUTE_TRAIN = "train"
ROUTE_EVAL = "eval"
ROUTE_PREDICT = "predict"
ROUTE_ENCODE = "encode"

#############################################
# Batch size
#############################################
TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_BATCH_SIZE_DEFAULT = None

TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT = None
# TPU-friendly alias accepted in the JSON.
TRAIN_MICRO_BATCH_SIZE_PER_DEVICE = "train_micro_batch_size_per_device"

GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"
GRADIENT_ACCUMULATION_STEPS_DEFAULT = None

SPARSE_GRADIENTS = "sparse_gradients"
SPARSE_GRADIENTS_DEFAULT = False

#############################################
# Optimizer and lr scheduler
#############################################
OPTIMIZER = "optimizer"
OPTIMIZER_TYPE_DEFAULT = None
OPTIMIZER_PARAMS = "params"
TYPE = "type"
LEGACY_FUSION = "legacy_fusion"
LEGACY_FUSION_DEFAULT = False
SCHEDULER = "scheduler"
SCHEDULER_TYPE_DEFAULT = None
SCHEDULER_PARAMS = "params"
MAX_GRAD_NORM = "max_grad_norm"

#############################################
# Optimizer names recognized by the engine
#############################################
ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
LAMB_OPTIMIZER = "lamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
SGD_OPTIMIZER = "sgd"
DEEPSPEED_OPTIMIZERS = [ADAM_OPTIMIZER, ADAMW_OPTIMIZER, LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER, SGD_OPTIMIZER]

#############################################
# FP16 / mixed precision support
# On TPU "fp16" enables loss-scaled low-precision training; the compute dtype
# defaults to bfloat16 (no scaling needed) unless fp16.actual_dtype=float16.
#############################################
FP16 = "fp16"
FP16_ENABLED = "enabled"
FP16_ENABLED_DEFAULT = False

FP16_LOSS_SCALE = "loss_scale"
FP16_LOSS_SCALE_DEFAULT = 0

FP16_INITIAL_SCALE_POWER = "initial_scale_power"
FP16_INITIAL_SCALE_POWER_DEFAULT = 32

FP16_LOSS_SCALE_WINDOW = "loss_scale_window"
FP16_LOSS_SCALE_WINDOW_DEFAULT = 1000

FP16_HYSTERESIS = "hysteresis"
FP16_HYSTERESIS_DEFAULT = 2

FP16_MIN_LOSS_SCALE = "min_loss_scale"
FP16_MIN_LOSS_SCALE_DEFAULT = 1

# TPU-native bf16 block (default on): {"bf16": {"enabled": true}}
BF16 = "bf16"
BF16_ENABLED = "enabled"
BF16_ENABLED_DEFAULT = True

#############################################
# Apex AMP parity block — accepted, mapped to bf16 policy.
#############################################
AMP = "amp"
AMP_ENABLED = "enabled"
AMP_ENABLED_DEFAULT = False

#############################################
# Gradient clipping
#############################################
GRADIENT_CLIPPING = "gradient_clipping"
GRADIENT_CLIPPING_DEFAULT = 0.0

#############################################
# Communication / reduction
#############################################
COMMUNICATION_DATA_TYPE = "communication_data_type"
COMMUNICATION_DATA_TYPE_DEFAULT = None

PRESCALE_GRADIENTS = "prescale_gradients"
PRESCALE_GRADIENTS_DEFAULT = False

# Fused single-jit train step (forward+backward+optimizer in one program;
# requires gradient_accumulation_steps == 1). TPU-native extension: buys
# ~1 param-tree of HBM headroom by never materializing the grad tree.
FUSED_STEP = "fused_step"
FUSED_STEP_DEFAULT = False

GRADIENT_PREDIVIDE_FACTOR = "gradient_predivide_factor"
GRADIENT_PREDIVIDE_FACTOR_DEFAULT = 1.0

DISABLE_ALLGATHER = "disable_allgather"
DISABLE_ALLGATHER_DEFAULT = False

ALLREDUCE_ALWAYS_FP32 = "allreduce_always_fp32"
ALLREDUCE_ALWAYS_FP32_DEFAULT = False

#############################################
# Steps
#############################################
STEPS_PER_PRINT = "steps_per_print"
STEPS_PER_PRINT_DEFAULT = 10

#############################################
# Training options
#############################################
DUMP_STATE = "dump_state"
DUMP_STATE_DEFAULT = False

VOCABULARY_SIZE = "vocabulary_size"
VOCABULARY_SIZE_DEFAULT = None

#############################################
# Wall block breakdown
#############################################
WALL_CLOCK_BREAKDOWN = "wall_clock_breakdown"
WALL_CLOCK_BREAKDOWN_DEFAULT = False

MEMORY_BREAKDOWN = "memory_breakdown"
MEMORY_BREAKDOWN_DEFAULT = False

#############################################
# Tensorboard
#############################################
TENSORBOARD = "tensorboard"
TENSORBOARD_ENABLED = "enabled"
TENSORBOARD_ENABLED_DEFAULT = False
TENSORBOARD_OUTPUT_PATH = "output_path"
TENSORBOARD_OUTPUT_PATH_DEFAULT = ""
TENSORBOARD_JOB_NAME = "job_name"
TENSORBOARD_JOB_NAME_DEFAULT = "DeepSpeedJobName"

#############################################
# Telemetry (TPU-native observability; no reference key — replaces the
# reference's barrier-heavy wall_clock_breakdown path with non-perturbing
# step metrics, profiler trace windows, a compile watchdog, and an HBM +
# wire-bytes ledger. See docs/telemetry.md.)
#############################################
TELEMETRY = "telemetry"
TELEMETRY_ENABLED = "enabled"
TELEMETRY_ENABLED_DEFAULT = False
TELEMETRY_TRACE_DIR = "trace_dir"
TELEMETRY_TRACE_DIR_DEFAULT = ""
TELEMETRY_TRACE_STEPS = "trace_steps"
TELEMETRY_TRACE_STEPS_DEFAULT = None
TELEMETRY_PERTURBING_BREAKDOWN = "perturbing_breakdown"
TELEMETRY_PERTURBING_BREAKDOWN_DEFAULT = False
TELEMETRY_PEAK_TFLOPS = "peak_tflops"
TELEMETRY_PEAK_TFLOPS_DEFAULT = 0.0
TELEMETRY_MFU_WINDOW = "mfu_window"
TELEMETRY_MFU_WINDOW_DEFAULT = 20
TELEMETRY_RECOMPILE_WARN = "recompile_warn"
TELEMETRY_RECOMPILE_WARN_DEFAULT = 3
TELEMETRY_OUTPUT_PATH = "output_path"
TELEMETRY_OUTPUT_PATH_DEFAULT = ""
TELEMETRY_JOB_NAME = "job_name"
TELEMETRY_JOB_NAME_DEFAULT = "DeepSpeedTelemetry"

# telemetry.pipeline_trace sub-block: per-instruction span timeline for the
# pipeline instruction executor (docs/pipeline-trace.md)
TELEMETRY_PIPELINE_TRACE = "pipeline_trace"
PIPELINE_TRACE_ENABLED = "enabled"
PIPELINE_TRACE_ENABLED_DEFAULT = False
PIPELINE_TRACE_CAPACITY = "capacity"
PIPELINE_TRACE_CAPACITY_DEFAULT = 64
PIPELINE_TRACE_DUMP_DIR = "dump_dir"
PIPELINE_TRACE_DUMP_DIR_DEFAULT = ""

# telemetry.cluster sub-block: cross-host observability plane — heartbeat
# aggregation over the host CPU world, straggler naming, hang watchdog,
# merged post-mortems (docs/cluster.md)
TELEMETRY_CLUSTER = "cluster"
CLUSTER_ENABLED = "enabled"
CLUSTER_ENABLED_DEFAULT = False
CLUSTER_HEARTBEAT_INTERVAL = "heartbeat_interval"
CLUSTER_HEARTBEAT_INTERVAL_DEFAULT = 1
CLUSTER_HANG_DEADLINE_S = "hang_deadline_s"
CLUSTER_HANG_DEADLINE_S_DEFAULT = 0.0  # 0 = watchdog off
CLUSTER_DUMP_DIR = "dump_dir"
CLUSTER_DUMP_DIR_DEFAULT = ""
CLUSTER_STRAGGLER_THRESHOLD = "straggler_threshold"
CLUSTER_STRAGGLER_THRESHOLD_DEFAULT = 3.0
CLUSTER_SIGNAL_PEERS = "signal_peers"
CLUSTER_SIGNAL_PEERS_DEFAULT = True
# steps before the watchdog arms / stragglers are named: the first step(s)
# pay multi-second compiles, which would false-fire any sane deadline
CLUSTER_WARMUP_STEPS = "warmup_steps"
CLUSTER_WARMUP_STEPS_DEFAULT = 1

# telemetry.goodput sub-block: run-lifecycle goodput/badput ledger — classifies
# every wall-clock interval of the run into a closed badput taxonomy (init,
# compile, productive_step, checkpoint_stall, restart_replay, hang,
# straggler_skew, eval, host_gap) with an exact-partition invariant
# (docs/goodput.md). Host-side only; the lowered step program is
# HLO-instruction-identical with the block on or off.
TELEMETRY_GOODPUT = "goodput"
GOODPUT_ENABLED = "enabled"
GOODPUT_ENABLED_DEFAULT = False
# where the per-run ledger JSON lands; "" falls back to the flight-recorder
# dump_dir (numerics.dump_dir) so the ledger sits beside the dumps it prices
GOODPUT_LEDGER_DIR = "ledger_dir"
GOODPUT_LEDGER_DIR_DEFAULT = ""
GOODPUT_EMIT_SCALARS = "emit_scalars"
GOODPUT_EMIT_SCALARS_DEFAULT = True
# tag used for eval intervals in the ledger (and the Run/Goodput scalar name)
GOODPUT_EVAL_TAG = "eval_tag"
GOODPUT_EVAL_TAG_DEFAULT = "eval"

# telemetry.hbm sub-block: HBM memory observatory — installs the engine's
# per-class resident-byte manifest (params / grads / master / optimizer /
# comm-EF) into the telemetry session so end_step emits Memory/* scalars and
# the flight recorder's dump carries OOM forensics (docs/hbm.md). Host-side
# constants only; the lowered step program is HLO-instruction-identical with
# the block on or off.
TELEMETRY_HBM = "hbm"
HBM_ENABLED = "enabled"
HBM_ENABLED_DEFAULT = False

#############################################
# Numerics observatory (TPU-native health layer on top of telemetry; no
# reference key — in-graph per-subtree anomaly sentinel, loss-scale event
# journal, cross-rank desync audit, and black-box flight recorder. See
# docs/numerics.md.)
#############################################
NUMERICS = "numerics"
NUMERICS_ENABLED = "enabled"
NUMERICS_ENABLED_DEFAULT = False
NUMERICS_SUBTREE_DEPTH = "subtree_depth"
NUMERICS_SUBTREE_DEPTH_DEFAULT = 1
NUMERICS_AUDIT_INTERVAL = "audit_interval"
NUMERICS_AUDIT_INTERVAL_DEFAULT = 0  # 0 = desync audit off
NUMERICS_DUMP_DIR = "dump_dir"
NUMERICS_DUMP_DIR_DEFAULT = ""
NUMERICS_RING_SIZE = "ring_size"
NUMERICS_RING_SIZE_DEFAULT = 256
NUMERICS_CONSECUTIVE_SKIP_TRIGGER = "consecutive_skip_trigger"
NUMERICS_CONSECUTIVE_SKIP_TRIGGER_DEFAULT = 8
NUMERICS_TRIGGER_ON_NONFINITE_LOSS = "trigger_on_nonfinite_loss"
NUMERICS_TRIGGER_ON_NONFINITE_LOSS_DEFAULT = True
NUMERICS_INSTALL_SIGNAL_HANDLERS = "install_signal_handlers"
NUMERICS_INSTALL_SIGNAL_HANDLERS_DEFAULT = False

#############################################
# Resilience (TPU-native fault tolerance, no reference key — async sharded
# checkpointing with a torn-write-proof commit protocol, topology-changing
# restore, flight-recorder-driven auto-resume. See docs/resilience.md. All
# hooks are host-side: with the block disabled (the default) the lowered
# step program is HLO-instruction-identical to a build without it.)
#############################################
RESILIENCE = "resilience"
RESILIENCE_ENABLED = "enabled"
RESILIENCE_ENABLED_DEFAULT = False
RESILIENCE_SAVE_DIR = "save_dir"
RESILIENCE_SAVE_DIR_DEFAULT = ""
RESILIENCE_SAVE_INTERVAL = "save_interval"
RESILIENCE_SAVE_INTERVAL_DEFAULT = 0  # 0 = no periodic saves
RESILIENCE_ASYNC_SAVE = "async_save"
RESILIENCE_ASYNC_SAVE_DEFAULT = True
RESILIENCE_AUTO_RESUME = "auto_resume"
RESILIENCE_AUTO_RESUME_DEFAULT = False

#############################################
# Serving (TPU-native inference engine, no reference key — the reference
# 0.3.0 ships no inference path. Block-paged KV cache + continuous batching;
# see docs/serving.md. Sizes are in tokens; the pool holds num_blocks pages of
# block_size tokens per layer, and block 0 is the reserved null page padded
# writes are routed to.)
#############################################
SERVING = "serving"
SERVING_ENABLED = "enabled"
SERVING_ENABLED_DEFAULT = False
SERVING_BLOCK_SIZE = "block_size"
SERVING_BLOCK_SIZE_DEFAULT = 16
SERVING_NUM_BLOCKS = "num_blocks"
SERVING_NUM_BLOCKS_DEFAULT = 257  # 256 usable + the reserved null block
SERVING_MAX_SEQS = "max_seqs"
SERVING_MAX_SEQS_DEFAULT = 8
SERVING_MAX_MODEL_LEN = "max_model_len"
SERVING_MAX_MODEL_LEN_DEFAULT = 256
SERVING_PREFILL_CHUNK = "prefill_chunk"
SERVING_PREFILL_CHUNK_DEFAULT = 32
SERVING_USE_PALLAS_DECODE = "use_pallas_decode"
SERVING_USE_PALLAS_DECODE_DEFAULT = False
# serving.request_trace — the per-request lifecycle ledger
# (serve/request_trace.py): latency percentiles, preemption-waste accounting,
# pool timeline, SLO classification, `ds-tpu serve-timeline` Perfetto export.
# Disabled -> the engine's tracer gate is None (nothing constructed).
SERVING_REQUEST_TRACE = "request_trace"
SERVING_REQUEST_TRACE_ENABLED = "enabled"
SERVING_REQUEST_TRACE_ENABLED_DEFAULT = False
SERVING_REQUEST_TRACE_CAPACITY = "capacity"          # finished-request ring
SERVING_REQUEST_TRACE_CAPACITY_DEFAULT = 256
SERVING_REQUEST_TRACE_ITERATION_CAPACITY = "iteration_capacity"
SERVING_REQUEST_TRACE_ITERATION_CAPACITY_DEFAULT = 4096
SERVING_REQUEST_TRACE_DUMP_DIR = "dump_dir"          # "" = no atexit dump
SERVING_REQUEST_TRACE_DUMP_DIR_DEFAULT = ""
SERVING_REQUEST_TRACE_SLO = "slo"
SERVING_SLO_TTFT_MS = "ttft_ms"                      # 0.0 = metric not gated
SERVING_SLO_TTFT_MS_DEFAULT = 0.0
SERVING_SLO_TPOT_MS = "tpot_ms"
SERVING_SLO_TPOT_MS_DEFAULT = 0.0
# serving.sharding — model-axis tensor parallelism for the serving engine:
# the per-layer KV pools and attention compute are sharded over "model"
# devices by attention head (n_head must divide evenly); activations stay
# replicated and each layer's output projection does one f32 all-reduce.
# model=1 (the default) is the exact single-chip path, byte-identical HLO.
SERVING_SHARDING = "sharding"
SERVING_SHARDING_MODEL = "model"
SERVING_SHARDING_MODEL_DEFAULT = 1
# serving.prefix_cache — cross-request prompt-prefix reuse: full prompt
# blocks are content-keyed at decode start (and at preemption, enabling warm
# restarts), parked in the allocator's LRU cached tier on last free, and
# remapped into new block tables on admission instead of re-prefilled.
SERVING_PREFIX_CACHE = "prefix_cache"
SERVING_PREFIX_CACHE_ENABLED = "enabled"
SERVING_PREFIX_CACHE_ENABLED_DEFAULT = False
# serving.speculation — greedy speculative decoding (Leviathan et al.): a
# draft model proposes up to max_draft_tokens per scheduler iteration against
# its own paged pool; the target verifies all K+1 positions in one batched
# step and a rejection rolls the block table back for free (CoW refcount
# release). Token-identical to the target's own greedy decode. draft_model is
# a human-readable label recorded in reports — the live draft model/params
# arrive via init_inference(draft_model=, draft_parameters=) because a config
# file cannot hold a parameter tree. draft_pool_blocks=0 inherits num_blocks.
SERVING_SPECULATION = "speculation"
SERVING_SPECULATION_ENABLED = "enabled"
SERVING_SPECULATION_ENABLED_DEFAULT = False
SERVING_SPECULATION_DRAFT_MODEL = "draft_model"
SERVING_SPECULATION_DRAFT_MODEL_DEFAULT = ""
SERVING_SPECULATION_MAX_DRAFT_TOKENS = "max_draft_tokens"
SERVING_SPECULATION_MAX_DRAFT_TOKENS_DEFAULT = 4
SERVING_SPECULATION_DRAFT_POOL_BLOCKS = "draft_pool_blocks"
SERVING_SPECULATION_DRAFT_POOL_BLOCKS_DEFAULT = 0
# serving.fleet — the N-replica serving front end (serve/router.py): one
# deterministic router owns "replicas" engine replicas and schedules every
# arrival. "policy" picks the routing rule — prefix-affinity (longest
# cached-prefix match, SGLang's cache-aware-routing insight, weighted against
# load by "affinity_weight"), pure least-loaded, or round-robin (the
# comparison baseline). "max_queue_depth" bounds each replica's waiting queue
# (0 = unbounded) and "occupancy_cap" caps its KV-pool used fraction; an
# arrival no replica can admit under those caps is SHED — a RequestOutput
# with status "shed", recorded in the request trace, never a crash.
# "goodput_floor" gates the merged fleet goodput fraction in `ds-tpu
# serve-sim --fleet` (0 = not gated).
SERVING_FLEET = "fleet"
SERVING_FLEET_REPLICAS = "replicas"
SERVING_FLEET_REPLICAS_DEFAULT = 1
SERVING_FLEET_POLICY = "policy"
SERVING_FLEET_POLICY_AFFINITY = "affinity"
SERVING_FLEET_POLICY_LEAST_LOADED = "least_loaded"
SERVING_FLEET_POLICY_ROUND_ROBIN = "round_robin"
SERVING_FLEET_POLICIES = (SERVING_FLEET_POLICY_AFFINITY,
                          SERVING_FLEET_POLICY_LEAST_LOADED,
                          SERVING_FLEET_POLICY_ROUND_ROBIN)
SERVING_FLEET_POLICY_DEFAULT = SERVING_FLEET_POLICY_AFFINITY
SERVING_FLEET_AFFINITY_WEIGHT = "affinity_weight"
SERVING_FLEET_AFFINITY_WEIGHT_DEFAULT = 1.0
SERVING_FLEET_MAX_QUEUE_DEPTH = "max_queue_depth"
SERVING_FLEET_MAX_QUEUE_DEPTH_DEFAULT = 0
SERVING_FLEET_OCCUPANCY_CAP = "occupancy_cap"
SERVING_FLEET_OCCUPANCY_CAP_DEFAULT = 1.0
SERVING_FLEET_GOODPUT_FLOOR = "goodput_floor"
SERVING_FLEET_GOODPUT_FLOOR_DEFAULT = 0.0

#############################################
# Comm (hierarchical ICI+DCN collectives)
#
# Routes data-parallel gradient exchange through the two-level schedule in
# deepspeed_tpu/comm: reduce-scatter within a slice over ICI, (optionally
# 1-bit sign-compressed) allreduce across slices over DCN, all-gather within
# the slice. "mode" selects flat (single-axis, the historical behaviour),
# hierarchical (two-level, full precision), or hierarchical_compressed
# (two-level with error-feedback sign compression of the cross-slice hop
# after "compress_start_step" warmup steps). "dcn_slices" fixes the slice
# count; 0 derives it from the jax.distributed process topology (one slice
# per process), falling back to a virtual 2x4 factorization of the 8-device
# CPU test mesh.
#############################################
COMM = "comm"
COMM_MODE = "mode"
COMM_MODE_DEFAULT = "flat"
COMM_MODE_FLAT = "flat"
COMM_MODE_HIERARCHICAL = "hierarchical"
COMM_MODE_COMPRESSED = "hierarchical_compressed"
COMM_MODES = (COMM_MODE_FLAT, COMM_MODE_HIERARCHICAL, COMM_MODE_COMPRESSED)
COMM_DCN_SLICES = "dcn_slices"
COMM_DCN_SLICES_DEFAULT = 0
COMM_COMPRESS_START_STEP = "compress_start_step"
COMM_COMPRESS_START_STEP_DEFAULT = 0

# comm.overlap: bucketed overlapped gradient exchange (docs/overlap.md).
# "mode" selects off (monolithic post-backward exchange, the historical
# behaviour — programs stay HLO-instruction-identical) or "bucketed"
# (partition the parameter tree into size-bounded per-subtree buckets and
# issue each bucket's exchange as soon as its backward subtree completes, so
# the collective of bucket k overlaps the remaining backward — and, under a
# hierarchical comm.mode, the DCN hop of bucket k overlaps the ICI phase of
# bucket k+1). "bucket_mb" bounds each bucket's fp32 wire footprint; the
# partition is deterministic for a given parameter tree and bucket_mb
# (DeepSpeed's allreduce_bucket_size, restated for eager issue).
COMM_OVERLAP = "overlap"
COMM_OVERLAP_MODE = "mode"
COMM_OVERLAP_MODE_DEFAULT = "off"
COMM_OVERLAP_OFF = "off"
COMM_OVERLAP_BUCKETED = "bucketed"
COMM_OVERLAP_MODES = (COMM_OVERLAP_OFF, COMM_OVERLAP_BUCKETED)
COMM_OVERLAP_BUCKET_MB = "bucket_mb"
COMM_OVERLAP_BUCKET_MB_DEFAULT = 25.0

#############################################
# Gradient accumulation fp32 buffer
#############################################
FP32_ALLREDUCE = "fp32_allreduce"
FP32_ALLREDUCE_DEFAULT = False

#############################################
# Sparse attention
#############################################
SPARSE_ATTENTION = "sparse_attention"
SPARSE_DENSE_MODE = "dense"
SPARSE_FIXED_MODE = "fixed"
SPARSE_VARIABLE_MODE = "variable"
SPARSE_BIGBIRD_MODE = "bigbird"
SPARSE_BSLONGFORMER_MODE = "bslongformer"
SPARSE_MODE = "mode"
SPARSE_MODE_DEFAULT = SPARSE_FIXED_MODE
SPARSE_BLOCK = "block"
SPARSE_BLOCK_DEFAULT = 16
SPARSE_DIFFERENT_LAYOUT_PER_HEAD = "different_layout_per_head"
SPARSE_DIFFERENT_LAYOUT_PER_HEAD_DEFAULT = False
SPARSE_NUM_LOCAL_BLOCKS = "num_local_blocks"
SPARSE_NUM_LOCAL_BLOCKS_DEFAULT = 4
SPARSE_NUM_GLOBAL_BLOCKS = "num_global_blocks"
SPARSE_NUM_GLOBAL_BLOCKS_DEFAULT = 1
SPARSE_ATTENTION_TYPE = "attention"
SPARSE_ATTENTION_TYPE_DEFAULT = "bidirectional"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION = "horizontal_global_attention"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION_DEFAULT = False
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS = "num_different_global_patterns"
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS_DEFAULT = 1
SPARSE_NUM_RANDOM_BLOCKS = "num_random_blocks"
SPARSE_NUM_RANDOM_BLOCKS_DEFAULT = 0
SPARSE_LOCAL_WINDOW_BLOCKS = "local_window_blocks"
SPARSE_LOCAL_WINDOW_BLOCKS_DEFAULT = [4]
SPARSE_GLOBAL_BLOCK_INDICES = "global_block_indices"
SPARSE_GLOBAL_BLOCK_INDICES_DEFAULT = [0]
SPARSE_GLOBAL_BLOCK_END_INDICES = "global_block_end_indices"
SPARSE_GLOBAL_BLOCK_END_INDICES_DEFAULT = None
SPARSE_NUM_SLIDING_WINDOW_BLOCKS = "num_sliding_window_blocks"
SPARSE_NUM_SLIDING_WINDOW_BLOCKS_DEFAULT = 3

#############################################
# Sequence parallelism (ring attention; TPU-native extension, no reference key)
#############################################
SEQUENCE_PARALLEL = "sequence_parallel"
SEQUENCE_PARALLEL_ENABLED = "enabled"
SEQUENCE_PARALLEL_ENABLED_DEFAULT = False
SEQUENCE_PARALLEL_AXIS = "axis"
SEQUENCE_PARALLEL_AXIS_DEFAULT = "data"
SEQUENCE_PARALLEL_SCHEDULE = "schedule"
SEQUENCE_PARALLEL_SCHEDULE_DEFAULT = "zigzag"

#############################################
# Pipeline (engine-level block; PipelineModule takes most knobs in-code)
#############################################
PIPELINE = "pipeline"
PIPELINE_STAGES = "stages"
PIPELINE_STAGES_DEFAULT = "auto"
PIPELINE_PARTITION = "partition"
PIPELINE_PARTITION_DEFAULT = "best"
PIPELINE_SEED_LAYERS = "seed_layers"
PIPELINE_SEED_LAYERS_DEFAULT = False
PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL = "activation_checkpoint_interval"
PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL_DEFAULT = 0

#############################################
# ZeRO client-optimizer opt-in (reference constants: zero_allow_untested_optimizer)
#############################################
ZERO_ALLOW_UNTESTED_OPTIMIZER = "zero_allow_untested_optimizer"
ZERO_ALLOW_UNTESTED_OPTIMIZER_DEFAULT = False

#############################################
# Key registry
#############################################
from .zero.constants import (ZERO_OPTIMIZATION,
                             ZERO_OPTIMIZATION_ALLGATHER_BUCKET_SIZE_DEPRECATED)
from .activation_checkpointing.config import ACTIVATION_CHKPT

# Every recognized TOP-LEVEL JSON config key. DeepSpeedConfig warns about any
# top-level key not in this set (reference parity: config.py:633-670 error/
# warning checks), and tests/unit/test_config_keys.py sweeps the registry
# asserting each key either changes engine-visible config state or emits a
# diagnostic — no key may silently no-op.
TOP_LEVEL_CONFIG_KEYS = frozenset({
    TRAIN_BATCH_SIZE,
    TRAIN_MICRO_BATCH_SIZE_PER_GPU,
    TRAIN_MICRO_BATCH_SIZE_PER_DEVICE,
    GRADIENT_ACCUMULATION_STEPS,
    SPARSE_GRADIENTS,
    OPTIMIZER,
    SCHEDULER,
    FP16,
    BF16,
    AMP,
    GRADIENT_CLIPPING,
    COMMUNICATION_DATA_TYPE,
    PRESCALE_GRADIENTS,
    FUSED_STEP,
    GRADIENT_PREDIVIDE_FACTOR,
    DISABLE_ALLGATHER,
    ALLREDUCE_ALWAYS_FP32,
    FP32_ALLREDUCE,
    STEPS_PER_PRINT,
    DUMP_STATE,
    VOCABULARY_SIZE,
    WALL_CLOCK_BREAKDOWN,
    MEMORY_BREAKDOWN,
    TENSORBOARD,
    TELEMETRY,
    NUMERICS,
    RESILIENCE,
    SERVING,
    COMM,
    SPARSE_ATTENTION,
    SEQUENCE_PARALLEL,
    PIPELINE,
    ZERO_OPTIMIZATION,
    ZERO_ALLOW_UNTESTED_OPTIMIZER,
    ACTIVATION_CHKPT,
    # deprecated boolean-zero companion (zero/config.py read_zero_config_deprecated)
    ZERO_OPTIMIZATION_ALLGATHER_BUCKET_SIZE_DEPRECATED,
})

# Recognized keys of the nested observability blocks. DeepSpeedConfig warns on
# any unknown key inside these dicts just like the top-level sweep — a typo'd
# "enable" must not silently leave a subsystem off.
TELEMETRY_CONFIG_KEYS = frozenset({
    TELEMETRY_ENABLED,
    TELEMETRY_TRACE_DIR,
    TELEMETRY_TRACE_STEPS,
    TELEMETRY_PERTURBING_BREAKDOWN,
    TELEMETRY_PEAK_TFLOPS,
    TELEMETRY_MFU_WINDOW,
    TELEMETRY_RECOMPILE_WARN,
    TELEMETRY_OUTPUT_PATH,
    TELEMETRY_JOB_NAME,
    TELEMETRY_PIPELINE_TRACE,
    TELEMETRY_CLUSTER,
    TELEMETRY_GOODPUT,
    TELEMETRY_HBM,
})

PIPELINE_TRACE_CONFIG_KEYS = frozenset({
    PIPELINE_TRACE_ENABLED,
    PIPELINE_TRACE_CAPACITY,
    PIPELINE_TRACE_DUMP_DIR,
})

CLUSTER_CONFIG_KEYS = frozenset({
    CLUSTER_ENABLED,
    CLUSTER_HEARTBEAT_INTERVAL,
    CLUSTER_HANG_DEADLINE_S,
    CLUSTER_DUMP_DIR,
    CLUSTER_STRAGGLER_THRESHOLD,
    CLUSTER_SIGNAL_PEERS,
    CLUSTER_WARMUP_STEPS,
})

GOODPUT_CONFIG_KEYS = frozenset({
    GOODPUT_ENABLED,
    GOODPUT_LEDGER_DIR,
    GOODPUT_EMIT_SCALARS,
    GOODPUT_EVAL_TAG,
})

HBM_CONFIG_KEYS = frozenset({
    HBM_ENABLED,
})

NUMERICS_CONFIG_KEYS = frozenset({
    NUMERICS_ENABLED,
    NUMERICS_SUBTREE_DEPTH,
    NUMERICS_AUDIT_INTERVAL,
    NUMERICS_DUMP_DIR,
    NUMERICS_RING_SIZE,
    NUMERICS_CONSECUTIVE_SKIP_TRIGGER,
    NUMERICS_TRIGGER_ON_NONFINITE_LOSS,
    NUMERICS_INSTALL_SIGNAL_HANDLERS,
})

SERVING_CONFIG_KEYS = frozenset({
    SERVING_ENABLED,
    SERVING_BLOCK_SIZE,
    SERVING_NUM_BLOCKS,
    SERVING_MAX_SEQS,
    SERVING_MAX_MODEL_LEN,
    SERVING_PREFILL_CHUNK,
    SERVING_USE_PALLAS_DECODE,
    SERVING_REQUEST_TRACE,
    SERVING_SHARDING,
    SERVING_PREFIX_CACHE,
    SERVING_SPECULATION,
    SERVING_FLEET,
})

SERVING_FLEET_CONFIG_KEYS = frozenset({
    SERVING_FLEET_REPLICAS,
    SERVING_FLEET_POLICY,
    SERVING_FLEET_AFFINITY_WEIGHT,
    SERVING_FLEET_MAX_QUEUE_DEPTH,
    SERVING_FLEET_OCCUPANCY_CAP,
    SERVING_FLEET_GOODPUT_FLOOR,
})

SERVING_SHARDING_CONFIG_KEYS = frozenset({
    SERVING_SHARDING_MODEL,
})

SERVING_PREFIX_CACHE_CONFIG_KEYS = frozenset({
    SERVING_PREFIX_CACHE_ENABLED,
})

SERVING_SPECULATION_CONFIG_KEYS = frozenset({
    SERVING_SPECULATION_ENABLED,
    SERVING_SPECULATION_DRAFT_MODEL,
    SERVING_SPECULATION_MAX_DRAFT_TOKENS,
    SERVING_SPECULATION_DRAFT_POOL_BLOCKS,
})

SERVING_REQUEST_TRACE_CONFIG_KEYS = frozenset({
    SERVING_REQUEST_TRACE_ENABLED,
    SERVING_REQUEST_TRACE_CAPACITY,
    SERVING_REQUEST_TRACE_ITERATION_CAPACITY,
    SERVING_REQUEST_TRACE_DUMP_DIR,
    SERVING_REQUEST_TRACE_SLO,
})

SERVING_SLO_CONFIG_KEYS = frozenset({
    SERVING_SLO_TTFT_MS,
    SERVING_SLO_TPOT_MS,
})

COMM_CONFIG_KEYS = frozenset({
    COMM_MODE,
    COMM_DCN_SLICES,
    COMM_COMPRESS_START_STEP,
    COMM_OVERLAP,
})

COMM_OVERLAP_CONFIG_KEYS = frozenset({
    COMM_OVERLAP_MODE,
    COMM_OVERLAP_BUCKET_MB,
})

RESILIENCE_CONFIG_KEYS = frozenset({
    RESILIENCE_ENABLED,
    RESILIENCE_SAVE_DIR,
    RESILIENCE_SAVE_INTERVAL,
    RESILIENCE_ASYNC_SAVE,
    RESILIENCE_AUTO_RESUME,
})
