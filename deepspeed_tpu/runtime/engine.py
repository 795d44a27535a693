"""DeepSpeedEngine: the core training wrapper.

TPU-native re-design of ``deepspeed/runtime/engine.py`` (DeepSpeedEngine l.96). The API
shape is preserved — ``forward``/``backward``/``step`` with gradient-accumulation boundary
semantics (engine.py:843-852), ``save_checkpoint``/``load_checkpoint``, progress reporting —
but the mechanics are functional JAX:

- the model is a pure function ``model_fn(params, *inputs) -> loss`` (or ``(loss, aux)``;
  of ``aux`` only the entries a model names in ``device_scalars`` leave the program, and
  those it names in ``rule_sums``, which the engine sums over a step for the rule by which
  the model itself updates the leaves it names in ``rule_updated_leaves``: they get no
  optimizer update, no weight decay, no clipping share and no schedule);
  in a functional framework the objective must live inside the traced function, so the
  torch pattern "outputs = engine(x); loss = criterion(outputs); engine.backward(loss)"
  becomes "loss = engine(x, y); engine.backward(loss); engine.step()".
- ``forward`` computes loss AND gradients in one fused jitted call (value_and_grad);
  ``backward`` accumulates them into a (ZeRO-sharded) buffer; ``step`` applies the update
  at the accumulation boundary inside a single jitted function with the overflow-skip,
  clipping, optimizer and loss-scale logic all on device.
- DP/ZeRO communication is not hand-written: batches are sharded over the mesh ``data``
  axis and master/optimizer state carries ZeRO layouts (zero/sharding.py), so XLA emits
  reduce-scatter/all-gather over ICI where the reference called NCCL
  (engine.py:1016-1089, stage2.py:682-745, 1441-1472).
"""

import collections
import functools
import os
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops import adam as adam_opt
from ..ops import lamb as lamb_opt
from ..ops import sgd as sgd_opt
from ..parallel.mesh import DATA_AXIS, build_mesh, mesh_from_mpu
from ..utils import SynchronizedWallClockTimer, log_dist, logger, spans
from ..utils.cluster import named_scope as ds_named_scope
from ..utils.compile_cache import configure_compile_cache
from ..utils.hbm import device_memory_stats
from .config import DeepSpeedConfig
from .constants import (ADAM_OPTIMIZER, ADAMW_OPTIMIZER, LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
                        SGD_OPTIMIZER, ROUTE_TRAIN,
                        COMM_MODE_FLAT, COMM_MODE_COMPRESSED,
                        COMM_OVERLAP_BUCKETED)
from .dataloader import DeepSpeedDataLoader
from .fp16 import loss_scaler as ls
from .lr_schedules import get_scheduler
from .utils import (clip_grads_by_global_norm, detect_overflow, global_norm)
from .zero.sharding import replicated_sharding, zero_sharding

MEMORY_OPT_ALLREDUCE_SIZE = 500000000


class OptimizerHandle:
    """Host-side view of optimizer hyperparameters (the reference's param_groups,
    engine.py:503-650 / fp16/fused_optimizer.py:48-66).

    Group 0 holds the optimizer block's top-level hypers; each ``group_specs`` entry
    adds a group that inherits the base values and applies its overrides (lr,
    weight_decay, betas, eps). Leaf membership is decided elsewhere (the engine's
    group-index tree); the handle only owns the per-group scalars that schedulers
    mutate and ``current_hyper`` ships to the device each step."""

    def __init__(self, name: str, params: dict, group_specs=()):
        self.name = name
        params = params or {}

        def group_dict(overrides: dict) -> dict:
            hyper = adam_opt.hyper_from_params({**params, **overrides})
            return {"lr": hyper["lr"], "betas": (hyper["beta1"], hyper["beta2"]),
                    "eps": hyper["eps"], "weight_decay": hyper["weight_decay"]}

        self.param_groups = [group_dict({})]
        for spec in group_specs or ():
            overrides = {k: v for k, v in dict(spec).items()
                         if k in ("lr", "weight_decay", "betas", "eps")}
            self.param_groups.append(group_dict(overrides))

    def current_hyper(self) -> dict:
        gs = self.param_groups
        if len(gs) == 1:  # single group: 0-d scalars, the historical jit signature
            g = gs[0]
            return dict(lr=jnp.asarray(g["lr"], jnp.float32),
                        beta1=jnp.asarray(g["betas"][0], jnp.float32),
                        beta2=jnp.asarray(g["betas"][1], jnp.float32),
                        eps=jnp.asarray(g["eps"], jnp.float32),
                        weight_decay=jnp.asarray(g["weight_decay"], jnp.float32))
        return dict(
            lr=jnp.asarray([g["lr"] for g in gs], jnp.float32),
            beta1=jnp.asarray([g["betas"][0] for g in gs], jnp.float32),
            beta2=jnp.asarray([g["betas"][1] for g in gs], jnp.float32),
            eps=jnp.asarray([g["eps"] for g in gs], jnp.float32),
            weight_decay=jnp.asarray([g["weight_decay"] for g in gs], jnp.float32))

    def hyper_for_leaf_groups(self) -> list:
        """Host-side per-group hyper dicts (the offload path's view)."""
        return [dict(lr=g["lr"], beta1=g["betas"][0], beta2=g["betas"][1],
                     eps=g["eps"], weight_decay=g["weight_decay"])
                for g in self.param_groups]

    # schedulers poke param_groups[i]['lr'] directly

    def state_dict(self):
        return {"param_groups": [dict(g) for g in self.param_groups]}

    def load_state_dict(self, sd):
        for g, src in zip(self.param_groups, sd["param_groups"]):
            g.update(src)


_OPTIMIZER_APPLY = {
    # "Adam" is classic L2 Adam: the reference's v0.3.0 kernels fold wd*p into the
    # gradient before the moments (csrc/adam/cpu_adam.cpp:81-82,122 `grad = param *
    # _weight_decay + grad`; no adam_w_mode knob existed yet). "AdamW" is decoupled.
    ADAM_OPTIMIZER: (adam_opt.init,
                     functools.partial(adam_opt.apply, adamw=False)),
    ADAMW_OPTIMIZER: (adam_opt.init, adam_opt.apply),
    LAMB_OPTIMIZER: (lamb_opt.init, lamb_opt.apply),
    SGD_OPTIMIZER: (sgd_opt.init, sgd_opt.apply),
}


def make_engine(args=None, model=None, optimizer=None, model_parameters=None, training_data=None,
                lr_scheduler=None, mpu=None, dist_init_required=None, collate_fn=None,
                config_params=None):
    """Engine factory: dispatches to PipelineEngine for PipelineModule models
    (reference deepspeed/__init__.py:111-133)."""
    if dist_init_required is not False:
        # Join the multi-host world when the launcher configured one (reference
        # engine.py:129-149 did dist.init_process_group here). No-op single-process.
        from .dist import init_distributed
        init_distributed()
    from ..parallel.pipe.module import PipelineModule
    if isinstance(model, PipelineModule):
        from .pipe.engine import PipelineEngine
        assert mpu is None, "mpu is mutually exclusive with a PipelineModule model"
        return PipelineEngine(args=args, model=model, optimizer=optimizer,
                              model_parameters=model_parameters, training_data=training_data,
                              lr_scheduler=lr_scheduler, mpu=model.mpu(),
                              dist_init_required=dist_init_required, collate_fn=collate_fn,
                              config_params=config_params)
    return DeepSpeedEngine(args=args, model=model, optimizer=optimizer,
                           model_parameters=model_parameters, training_data=training_data,
                           lr_scheduler=lr_scheduler, mpu=mpu,
                           dist_init_required=dist_init_required, collate_fn=collate_fn,
                           config_params=config_params)


def _traced_under(mesh, fn, room):
    """``fn`` traced with ``mesh`` in context, so that code XLA cannot partition
    for it (the Pallas kernels, ops/pallas/partition.py) sees which axes to split
    itself over. A mesh already in context stays: a ``shard_map`` body carries its
    own, with the axes it made manual. And with ``room``, which gives a chip's bytes beside
    the engine's state, in context too: what the expert layers may keep of the experts they
    fetched (``parallel/moe.room_for_fetched_experts``)."""
    from ..parallel.moe import room_for_fetched_experts
    abstract = mesh.abstract_mesh

    @functools.wraps(fn)
    def traced(*args):
        with room_for_fetched_experts(room):
            if not jax.sharding.get_abstract_mesh().empty:
                return fn(*args)
            with jax.sharding.use_abstract_mesh(abstract):
                return fn(*args)

    return traced


# sentinel marking a fused-step window in the pending-grads / grad-acc slots
# (the gradient tree never exists outside the fused jit)
_FUSED = object()


class DeepSpeedEngine:

    def __init__(self, args=None, model=None, optimizer=None, model_parameters=None,
                 training_data=None, lr_scheduler=None, mpu=None, dist_init_required=None,
                 collate_fn=None, config_params=None, mesh=None, param_shardings=None):
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_data = training_data
        self.collate_fn = collate_fn
        self.mpu = mpu
        self.global_steps = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self.warn_unscaled_loss = True
        self._in_training = True

        # ---- mesh (first: its data-axis size is the config's DP world size) ----
        if mesh is not None:
            self.mesh = mesh
        elif mpu is not None:
            self.mesh = mesh_from_mpu(mpu)
        else:
            self.mesh = build_mesh(model=1, pipe=1)
        self.dp_size = self.mesh.shape[DATA_AXIS]

        # ---- config ----
        config_file = getattr(args, "deepspeed_config", None) if args is not None else None
        if config_params is not None:
            self.config = DeepSpeedConfig(config_params, world_size=self.dp_size)
        else:
            assert config_file is not None, "DeepSpeed requires --deepspeed_config or config_params"
            self.config = DeepSpeedConfig(config_file, world_size=self.dp_size)

        # ---- comm topology (hierarchical ICI+DCN collectives; docs/multislice.md) ----
        # Derived for every engine (the per-level desync audit and wire ledger
        # read the factorization); the MODE decides whether the grad exchange
        # actually routes through the two-level schedule.
        from ..comm import derive_topology
        self._comm_mode = self.config.comm_mode
        self._comm_topo = derive_topology(self.dp_size, self.config.comm_dcn_slices)
        if self._comm_mode != COMM_MODE_FLAT:
            if self.zero_optimization() and self.zero_cpu_offload():
                raise ValueError(
                    f"comm.mode={self._comm_mode!r} does not compose with "
                    "ZeRO-Offload (the host-tier step owns the grad layout)")
            if self.zero_optimization_stage() >= 3:
                raise ValueError(
                    f"comm.mode={self._comm_mode!r} requires ZeRO stage <= 2: the "
                    "two-level exchange runs in a shard_map with replicated "
                    "parameter in_specs, which would re-gather stage-3 sharded "
                    "parameters every step")
            if self.config.sparse_gradients_enabled:
                raise ValueError(
                    f"comm.mode={self._comm_mode!r} does not compose with "
                    "sparse_gradients (the row-sparse reduction owns the grad "
                    "exchange); pick one")
            if (self._comm_mode == COMM_MODE_COMPRESSED
                    and self.gradient_accumulation_steps() > 1
                    and self.config.optimizer_name != ONEBIT_ADAM_OPTIMIZER):
                raise ValueError(
                    "comm.mode='hierarchical_compressed' requires "
                    "gradient_accumulation_steps == 1: error-feedback compression "
                    "of per-micro-batch partial gradients would accumulate "
                    "compression error across the window")
        if self.config.comm_overlap_mode == COMM_OVERLAP_BUCKETED:
            # bucketed overlapped grad exchange (docs/overlap.md) runs the same
            # shard_map scaffold as hierarchical comm, so it inherits the same
            # composition limits even under comm.mode=flat
            if self.zero_optimization() and self.zero_cpu_offload():
                raise ValueError(
                    "comm.overlap.mode='bucketed' does not compose with "
                    "ZeRO-Offload (the host-tier step owns the grad layout)")
            if self.zero_optimization_stage() >= 3:
                raise ValueError(
                    "comm.overlap.mode='bucketed' requires ZeRO stage <= 2: the "
                    "bucketed exchange runs in a shard_map with replicated "
                    "parameter in_specs, which would re-gather stage-3 sharded "
                    "parameters every step")
            if self.config.sparse_gradients_enabled:
                raise ValueError(
                    "comm.overlap.mode='bucketed' does not compose with "
                    "sparse_gradients (the row-sparse reduction owns the grad "
                    "exchange); pick one")

        # ---- persistent compilation cache (utils/compile_cache.py) ----
        configure_compile_cache()

        # ---- model function + params ----
        assert model is not None, "deepspeed.initialize requires a model"
        if hasattr(model, "apply"):
            # flax-style module: apply(params, *inputs)
            self.model_fn = model.apply
        elif callable(model):
            self.model_fn = model
        else:
            raise TypeError("model must be a flax-style module (.apply) or a callable "
                            "model_fn(params, *inputs) -> loss")
        self.module = model
        assert model_parameters is not None, ("model_parameters (the initialized parameter pytree) "
                                              "is required in the functional API")

        # ---- sequence parallelism (ring attention over the mesh axis) ----
        # The ``sequence_parallel`` config block swaps the loss fn for the model's
        # sequence-parallel build: tokens/labels stay in natural order at the API
        # boundary, the model shards them over the axis (zigzag layout by default)
        # and runs ring attention internally.
        if self.config.sequence_parallel_enabled:
            sp_build = getattr(model, "sequence_parallel_loss_fn", None)
            if sp_build is None:
                raise TypeError("sequence_parallel requires a model exposing "
                                "sequence_parallel_loss_fn(mesh, axis, schedule=...)")
            self.model_fn = sp_build(self.mesh, self.config.sequence_parallel_axis,
                                     schedule=self.config.sequence_parallel_schedule)
        self.model_fn = _traced_under(self.mesh, self.model_fn, self._room_beside_state)
        if param_shardings is None and hasattr(model, "engine_shardings"):
            # the model's own layout over this mesh (experts that live split over the
            # data axis); ZeRO claims what it leaves free, as for a caller's layout
            param_shardings = model.engine_shardings(self.mesh)
        # the names a model declares (``device_scalars``) of the per-step device scalars
        # in the dict its apply returns beside the loss; nothing else of that dict is kept
        self._device_scalar_names = tuple(getattr(model, "device_scalars", ()))
        # leaves that the model updates by a rule of its own: ``rule_updated_leaves`` names
        # them (patterns over leaf paths, as ``param_group_patterns``), ``rule_sums`` names the
        # entries of that dict the rule reads, summed over a step's micro-batches (and, the
        # batch being global, over the data axis), and ``apply_rule(leaves, sums)`` is the
        # rule: called once a step inside the update program on the float32 master's named
        # leaves (the tree with every other leaf None), its result written into the master
        # and into the compute copy, which holds such a leaf as the master does (it is no
        # weight: the forward reads what the rule wrote, not a rounding of it)
        self._rule_patterns = tuple(getattr(model, "rule_updated_leaves", ()))
        self._rule_sum_names = tuple(getattr(model, "rule_sums", ())) if self._rule_patterns else ()
        self._rule_fn = getattr(model, "apply_rule", None) if self._rule_patterns else None
        self._rule_sums = self._pending_rule_sums = None     # the window's sums so far

        # ---- precision policy ----
        if self.fp16_enabled():
            self.compute_dtype = jnp.float16
        elif self.bfloat16_enabled():
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32

        # input from outside the program: the standard path would hand an apply
        # marked ``external_master`` a master it never asked for
        if (isinstance(optimizer, tuple) and len(optimizer) == 2
                and getattr(optimizer[1], "external_master", False)):
            raise ValueError("the external-master step path was removed: a client apply "
                             "marked external_master = True is no longer supported")

        # ---- shardings ----
        zero_stage = self.zero_optimization_stage()
        self._repl = lambda tree: replicated_sharding(self.mesh, tree)
        master_fp32 = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), model_parameters)
        # 1-bit Adam needs per-worker (unreduced) gradients: grads are kept stacked with a
        # leading dp axis sharded over 'data' (reference onebit_adam.py:335-336 relies on
        # engine.enable_backward_allreduce=False for the same effect).
        self._use_stacked_grads = (self.config.optimizer_name == ONEBIT_ADAM_OPTIMIZER
                                   and (optimizer is None or isinstance(optimizer, str)))
        if self._use_stacked_grads:
            assert zero_stage == 0, "1-bit Adam does not compose with ZeRO (reference parity)"
            assert param_shardings is None, "1-bit Adam requires replicated parameters"

        # ---- sparse (row-sparse embedding) gradients (reference engine.py:176-187) ----
        # The model declares which leaves are untied embedding tables via
        # sparse_grad_paths() (the reference auto-detected nn.Embedding modules; a
        # functional pytree has no module types to sniff).
        self._sparse_grad_flags = None
        # Optional model hint: sparse_grad_tokens(*batch) -> token positions in the
        # GLOBAL batch. Without it the engine assumes batch arg 0 is the token-id
        # tensor, which silently mis-sizes the row capacity for models whose first
        # positional input is something else.
        self._sparse_tokens_fn = getattr(model, "sparse_grad_tokens", None)
        if self.config.sparse_gradients_enabled and not self._use_stacked_grads:
            if param_shardings is not None or zero_stage >= 3:
                # the sparse-reduction shard_map pins replicated param in_specs,
                # so it is unavailable whenever params are sharded: under stage 3
                # (it would all-gather the sharded params every step — dense
                # reduction keeps the gather at use points only) and under
                # caller-provided layouts
                reason = ("with caller-provided param_shardings"
                          if param_shardings is not None
                          else "under ZeRO stage 3 (sharded parameters)")
                logger.warning(f"[deepspeed_tpu] sparse_gradients is inactive "
                               f"{reason}; using dense gradient reduction")
            elif (patterns := tuple(getattr(model, "sparse_grad_paths",
                                            lambda: ())())):
                from .sparse_tensor import match_sparse_paths
                paths = jax.tree_util.tree_flatten_with_path(master_fp32)[0]
                flags = []
                for path, leaf in paths:
                    pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
                                    for p in path)
                    flags.append(bool(leaf.ndim == 2 and match_sparse_paths(pstr, patterns)))
                self._sparse_grad_flags = jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(master_fp32), flags)
                matched = sum(jax.tree_util.tree_leaves(self._sparse_grad_flags))
                logger.info(f"[deepspeed_tpu] sparse gradients enabled for {matched} "
                            f"embedding leaves (patterns={patterns})")
                if matched == 0:
                    self._sparse_grad_flags = None
            else:
                logger.warning("sparse_gradients requested but the model defines no "
                               "sparse_grad_paths(); falling back to dense reduction")
        if param_shardings is not None:
            # caller-provided layout (pipe-stacked stages, TP-sharded weights, ...);
            # ZeRO composes on top by claiming a free data-divisible axis per leaf
            from .zero.sharding import merge_zero_into
            self._master_shardings = merge_zero_into(self.mesh, param_shardings, master_fp32,
                                                     zero_stage)
            # stage 3: compute params adopt the merged (caller + data-axis) layout —
            # full parameter sharding on top of pipe/TP
            self._param_shardings = (self._master_shardings if zero_stage >= 3
                                     else param_shardings)
            self._grad_shardings = (self._master_shardings if zero_stage >= 2
                                    else param_shardings)
        else:
            self._master_shardings = zero_sharding(self.mesh, master_fp32, zero_stage)
            # stage 3 (parameter sharding — beyond the v0.3.0 reference, which stops
            # at stage 2): the bf16 compute params themselves carry the data-axis
            # layout; XLA all-gathers each leaf at its use point in forward/backward
            # (the later ZeRO-3's gather-on-use, as a GSPMD annotation) and the
            # updated master casts back to the SAME sharded layout — per-device
            # param HBM scales as 1/dp.
            self._param_shardings = (self._master_shardings if zero_stage >= 3
                                     else replicated_sharding(self.mesh, master_fp32))
            if self._use_stacked_grads:
                self._grad_shardings = jax.tree_util.tree_map(
                    lambda _: NamedSharding(self.mesh, P(DATA_AXIS)), master_fp32)
            else:
                # stage 2: accumulated grads live reduce-scattered; stage<=1: replicated
                self._grad_shardings = (zero_sharding(self.mesh, master_fp32, zero_stage)
                                        if zero_stage >= 2 else replicated_sharding(self.mesh, master_fp32))
        self._zero_sharded_fraction = None
        if zero_stage >= 1 and self.dp_size > 1:
            # observability: zero_spec leaves awkward leaves replicated by policy —
            # surface what fraction of master/optimizer bytes actually sharded
            # (Adam moments mirror the master layout, so one count covers both)
            from .zero.sharding import sharding_coverage
            sharded_b, total_b = sharding_coverage(self._master_shardings, master_fp32)
            self._zero_sharded_fraction = sharded_b / max(total_b, 1)
            log_dist(
                f"ZeRO-{zero_stage}: {sharded_b / 2**20:.1f}/{total_b / 2**20:.1f} MiB "
                f"({self._zero_sharded_fraction:.1%}) of master+optimizer"
                + ("+parameter" if zero_stage >= 3 else "")
                + f" state sharded over data={self.dp_size}"
                + ("" if self._zero_sharded_fraction > 0.9 else
                   " — mostly REPLICATED (no dp-divisible axes / leaves under min_size);"
                   " per-rank memory will not scale as 1/dp"),
                ranks=[0])

        # ---- ZeRO-Offload: master weights + optimizer state live in host DRAM ----
        # (reference stage2.py:333-349 keeps fp32 master/grads pinned on host and steps
        # DeepSpeedCPUAdam there; on a TPU-VM "host" is the VM's DRAM tier). The host
        # buffers are PARTITIONED by the ZeRO master layout: each process stores and
        # steps only the regions its addressable devices own (the reference's
        # per-DP-rank single_partition_of_fp32_groups, stage2.py:750-907), so offload
        # composes with multi-host runs and per-host DRAM/compute scale as 1/dp.
        self._offload = None
        if self.zero_optimization() and self.zero_cpu_offload():
            from ..ops.cpu_adam import DeepSpeedCPUAdam
            # non-Adam optimizers are rejected later by _configure_optimizer's
            # Adam/AdamW assert; absent optimizer block defaults to "adam" (L2),
            # matching the _OPTIMIZER_APPLY default for the non-offload path
            _offload_name = self.config.optimizer_name or ADAM_OPTIMIZER
            zc = self.config.zero_config
            self._offload = DeepSpeedCPUAdam(
                master_fp32,
                adamw=(_offload_name == ADAMW_OPTIMIZER),
                shardings=self._master_shardings,
                pipeline=zc.offload_pipeline,
                pipeline_depth=zc.offload_pipeline_depth,
                max_region_elements=zc.offload_max_region_elements)
        else:
            self.master_params = jax.device_put(master_fp32, self._master_shardings)
        self._rule_mask = self._build_rule_mask(master_fp32)
        self.params = jax.device_put(self._compute_copy(master_fp32), self._param_shardings)

        # ---- optimizer ----
        self._configure_optimizer(optimizer)

        # ---- loss scaler state ----
        self._dynamic_scale = self.fp16_enabled() and self.config.loss_scale == 0
        if self.fp16_enabled():
            self.scaler_state = ls.init_state(self.config.loss_scale, self.config.initial_scale_power,
                                              self.config.hysteresis)
        else:
            self.scaler_state = ls.init_state(1.0)  # scale fixed at 1

        # ---- grad accumulation buffer ----
        self._grad_acc = None  # lazily zero-initialized with grad shardings
        self._pending_grads = None
        self._pending_loss = None
        self._window_losses = []  # per-accumulation-window losses for monitor emission
        self._last_grad_norm = None

        # ---- lr scheduler ----
        self._configure_lr_scheduler(lr_scheduler)

        # ---- dataloader ----
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)
        self.data_sharding = NamedSharding(self.mesh, P(DATA_AXIS))

        # ---- timers ----
        self.timers = SynchronizedWallClockTimer()

        # ---- spans (utils/spans.py, docs/telemetry.md): the process's recorder, always
        # on; ``train.step`` stays open from a window's first forward() to _finish_step
        self._spans = spans.recorder()
        self._step_programs = spans.Programs()      # held here, so it goes with the engine
        self._span_engine = self._spans.new_engine(self._step_programs)
        self._step_span = None
        # the last steps' losses, unfetched: how many the device has not made yet when a
        # step begins is the host's lead over it (``in_flight`` of ``train.step``)
        self._step_losses = collections.deque(maxlen=8)

        # module-level activation-checkpointing config (reference engine.py:385-400).
        # Only push settings into the process-global module when THIS config carries
        # the block — a second engine without one must not clobber the first's setup.
        from .activation_checkpointing import checkpointing as act_ckpt
        if self.config.activation_checkpointing_config.configured_in_json:
            act_ckpt.configure(deepspeed_config=self.config, mesh=self.mesh)
        else:
            act_ckpt.set_default_mesh(self.mesh)

        # ---- scalar monitor (reference tensorboard wiring, engine.py:151-152, 246-261) ----
        self.monitor = None
        if self.config.tensorboard_enabled:
            from ..utils.monitor import SummaryMonitor
            self.monitor = SummaryMonitor(self.config.tensorboard_output_path or None,
                                          self.config.tensorboard_job_name)

        # ---- telemetry (docs/telemetry.md): compile watchdog, trace windows,
        # non-perturbing step metrics + resource ledger. Created BEFORE
        # _compile_steps so the step programs compile through the watchdog.
        self.telemetry = None
        if self.config.telemetry_enabled:
            from ..utils.telemetry import TelemetrySession
            self.telemetry = TelemetrySession(
                monitor=self.monitor,
                peak_tflops=self.config.telemetry_peak_tflops or None,
                trace_dir=self.config.telemetry_trace_dir or None,
                trace_steps=self.config.telemetry_trace_steps,
                mfu_window=self.config.telemetry_mfu_window,
                recompile_warn=self.config.telemetry_recompile_warn,
                output_path=self.config.telemetry_output_path or None,
                job_name=self.config.telemetry_job_name)
            if self._comm_topo.is_hierarchical:
                # per-axis wire ledger: split every program's collective bytes
                # into ICI (intra-slice) vs DCN (cross-slice) — installed before
                # _compile_steps so the step programs analyze against it
                self.telemetry.set_comm_topology(
                    self._comm_topo.slice_device_sets(self.mesh))

        # ---- numerics observatory (docs/numerics.md): in-graph sentinel,
        # loss-scale journal, cross-rank desync audit, flight recorder. Built
        # BEFORE _compile_steps so the step programs fold the per-subtree
        # bucketing into the already-jitted update (no extra host syncs).
        self._numerics = None
        self._sentinel_index = None
        self._pending_sentinel = None
        self._audit_fn_cached = None
        if self.config.numerics_enabled:
            from ..utils.numerics import (FlightRecorder, NumericsMonitor,
                                          build_subtree_index)
            self._sentinel_index = build_subtree_index(
                master_fp32, self.config.numerics_subtree_depth)
            journal = None
            if self.fp16_enabled():
                # host shadow of the device scaler — seeded from config, never
                # from a device fetch (ls.init_state uses the same derivation)
                init_scale = (float(self.config.loss_scale)
                              if self.config.loss_scale and self.config.loss_scale > 0
                              else float(2 ** self.config.initial_scale_power))
                journal = ls.LossScaleJournal(
                    self._dynamic_scale, init_scale,
                    scale_window=self.config.loss_scale_window,
                    min_scale=self.config.min_loss_scale,
                    hysteresis=self.config.hysteresis)
            recorder = FlightRecorder(
                capacity=self.config.numerics_ring_size,
                dump_dir=self.config.numerics_dump_dir or "numerics_dumps",
                telemetry=self.telemetry,
                host_id=jax.process_index())
            recorder.install(self.config.numerics_install_signal_handlers)
            self._numerics = NumericsMonitor(
                self._sentinel_index, monitor=self.monitor,
                telemetry=self.telemetry, journal=journal, recorder=recorder,
                audit_interval=self.config.numerics_audit_interval,
                consecutive_skip_trigger=self.config.numerics_consecutive_skip_trigger,
                trigger_on_nonfinite_loss=self.config.numerics_trigger_on_nonfinite_loss)

        # ---- cluster observatory (docs/cluster.md): cross-host heartbeat
        # aggregation, straggler naming, hang watchdog. Entirely host-side —
        # the step programs stay HLO-instruction-identical with this block
        # enabled (tested), same as every other observatory.
        self._cluster = None
        if self.telemetry is not None and self.config.telemetry_cluster_enabled:
            from ..utils.cluster import ClusterMonitor
            cluster_recorder = (self._numerics.recorder
                                if self._numerics is not None else None)
            cluster_dump_dir = None
            if cluster_recorder is None:
                # no numerics recorder to ride: give the watchdog its own
                from ..utils.numerics import FlightRecorder
                cluster_dump_dir = (self.config.telemetry_cluster_dump_dir
                                    or "cluster_dumps")
                cluster_recorder = FlightRecorder(
                    capacity=64, dump_dir=cluster_dump_dir,
                    telemetry=self.telemetry, host_id=jax.process_index())
            self._cluster = ClusterMonitor(
                telemetry=self.telemetry,
                recorder=cluster_recorder,
                heartbeat_interval=self.config.telemetry_cluster_heartbeat_interval,
                hang_deadline_s=self.config.telemetry_cluster_hang_deadline_s,
                straggler_threshold=self.config.telemetry_cluster_straggler_threshold,
                signal_peers=self.config.telemetry_cluster_signal_peers,
                warmup_steps=self.config.telemetry_cluster_warmup_steps,
                dump_dir=cluster_dump_dir)
            # heartbeat history + clock offsets ride along in every dump so
            # cluster-dump / timeline --cluster can merge hosts coherently
            cluster_recorder.cluster = self._cluster

        # ---- run-lifecycle goodput ledger (docs/goodput.md): classifies the
        # run's entire wall-clock into a closed badput taxonomy (init, compile,
        # productive_step, checkpoint_stall, restart_replay, hang,
        # straggler_skew, eval, host_gap) with an exact-partition invariant.
        # Opened HERE, before _compile_steps, so construction-time compiles
        # land in the ledger. Pure host arithmetic over timestamps the other
        # observatories already took — the step programs stay
        # HLO-instruction-identical with this block enabled (tested).
        self._goodput = None
        if self.telemetry is not None and self.config.telemetry_goodput_enabled:
            from ..utils.goodput import RunLedger
            gp_recorder = (self._numerics.recorder
                           if self._numerics is not None else None)
            if gp_recorder is None and self._cluster is not None:
                gp_recorder = self._cluster.recorder
            ledger_dir = (self.config.telemetry_goodput_ledger_dir
                          or (gp_recorder.dump_dir
                              if gp_recorder is not None else None)
                          or "goodput_ledgers")
            if gp_recorder is not None:
                run_id = gp_recorder.run_id
            else:
                from ..utils.numerics import default_run_id
                run_id = default_run_id()
            self._goodput = RunLedger(
                run_id=run_id, host=jax.process_index(),
                ledger_dir=ledger_dir,
                eval_tag=self.config.telemetry_goodput_eval_tag)
            # carve-out baselines: compile seconds, watchdog fires, and
            # checkpoint saves are cumulative counters; the ledger bills
            # per-step deltas
            self._goodput_compile_base = 0.0
            self._goodput_hang_base = 0
            self._goodput_saves_base = 0
            self._goodput_init_open = True
            if self._cluster is not None:
                self._cluster.goodput = self._goodput

        self._compile_steps()

        # ---- HBM observatory (docs/hbm.md): install the per-class resident-
        # byte manifest into the telemetry session. Pure host arithmetic over
        # abstract shapes/shardings — no device work, and the compiled step is
        # HLO-instruction-identical with the block on or off (pinned in tests).
        if self.telemetry is not None and self.config.telemetry_hbm_enabled:
            from ..utils import hbm as _hbm
            manifest = self.memory_manifest()
            _, class_bytes = _hbm.manifest_signatures(manifest)
            self.telemetry.set_memory_manifest(
                class_bytes, geometry=manifest.get("geometry"))

        # ---- resilience (docs/resilience.md): periodic async checkpointing +
        # flight-recorder-driven auto-resume. Everything here is host-side —
        # the save hook snapshots committed step state and commits in a
        # background thread — so with the block disabled the lowered step
        # programs are HLO-instruction-identical to a build without it.
        self._resilience = None
        if self.config.resilience_enabled and self.config.resilience_save_dir:
            from ..resilience.async_ckpt import AsyncCheckpointer
            self._resilience = AsyncCheckpointer(
                self, self.config.resilience_save_dir)
            if self.config.resilience_auto_resume:
                from ..resilience.auto_resume import auto_resume
                _, _, resume_info = auto_resume(
                    self, self.config.resilience_save_dir)
                if self._goodput is not None and resume_info is not None:
                    # restart-replay billing: steps between the restore point
                    # and the pre-crash step are work the run already paid for
                    # once. The pre-crash step is the flight recorder's first
                    # bad step (exclusive — re-running IT is new work) or,
                    # after a clean preemption, the dump's last recorded step.
                    stop = resume_info.get("first_bad_step")
                    if stop is not None:
                        stop = int(stop) - 1
                    elif self._numerics is not None:
                        from ..utils.numerics import scan_dump_dir
                        bundle = scan_dump_dir(
                            self._numerics.recorder.dump_dir) or {}
                        span = bundle.get("span") or {}
                        stop = span.get("last_step")
                    if stop is not None:
                        self._goodput.set_replay_until(int(stop))

        if self.config.dump_state:
            self.config.print("DeepSpeedEngine configuration")

    # ------------------------------------------------------------------ state views
    # Under ZeRO-Offload the fp32 master and Adam moments live in the host-tier flat
    # buffers; these properties materialize fresh tree views on access so checkpointing
    # always sees the current state (leaf views alias the flat buffers where the region
    # layout is contiguous, and are assembled copies otherwise).
    @property
    def master_params(self):
        if getattr(self, "_offload", None) is not None:
            return self._offload.params_tree()
        return self._master_params_store

    @master_params.setter
    def master_params(self, value):
        self._master_params_store = value

    @property
    def opt_state(self):
        if getattr(self, "_offload", None) is not None:
            from ..ops.adam import AdamState
            return AdamState(exp_avg=self._offload.exp_avg_tree(),
                             exp_avg_sq=self._offload.exp_avg_sq_tree())
        return self._opt_state_store

    @opt_state.setter
    def opt_state(self, value):
        self._opt_state_store = value

    # ------------------------------------------------------------------ config accessors
    def train_batch_size(self):
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def steps_per_print(self):
        return self.config.steps_per_print

    def zero_optimization(self):
        return self.config.zero_enabled

    def zero_optimization_stage(self):
        return self.config.zero_optimization_stage

    def zero_cpu_offload(self):
        return self.config.zero_config.cpu_offload

    @property
    def offload_step_timing(self):
        """Last offload step's timing: aggregate lanes (fetch_wait/host_adam/push/total),
        lane busy sums (fetch_busy/push_busy), pipeline shape (pipeline_depth/
        region_cap/n_work_items) and per-region records — None before the first step
        or when offload is disabled. See DeepSpeedCPUAdam.step_regions."""
        return self._offload.last_step_timing if self._offload is not None else None

    def fp16_enabled(self):
        return self.config.fp16_enabled

    def bfloat16_enabled(self):
        return self.config.bf16_enabled

    def gradient_clipping(self):
        return self.config.gradient_clipping

    def allreduce_always_fp32(self):
        return self.config.allreduce_always_fp32

    def wall_clock_breakdown(self):
        # With telemetry active, the barrier-per-section breakdown timers are
        # perturbing instrumentation (each section boundary drains the device
        # queue, serializing the async dispatch telemetry exists to preserve):
        # they run only behind the explicit telemetry.perturbing_breakdown flag.
        if self.telemetry is not None:
            if self.config.telemetry_perturbing_breakdown:
                self.telemetry.warn_perturbing_once()
                return True
            if self.config.wall_clock_breakdown:
                self.telemetry.note_breakdown_suppressed_once()
            return False
        return self.config.wall_clock_breakdown

    def _watch(self, name, jitted):
        """Route a jitted step program through the telemetry compile watchdog
        (identity when telemetry is off)."""
        if self.telemetry is None or jitted is None:
            return jitted
        return self.telemetry.watch(name, jitted)

    def _call_program(self, span, program, jitted, *args):
        """Call one step program under its span. A call that built or loaded an
        executable (``compile.*`` spans inside it) leaves the program's shapes in
        ``_step_programs``, for the catalog ``spans.Recorder.programs`` makes on request."""
        with self._spans.span(span, engine=self._span_engine, program=program) as sp:
            out = jitted(*args)
        if "builds" in sp.attrs:
            self._step_programs.keep(program, jitted, args)
        return out

    def _host_fetch(self):
        """The span around a place where the engine waits for the device by design."""
        return self._spans.span("train.host_fetch", engine=self._span_engine)

    def dynamic_loss_scale(self):
        return self._dynamic_scale

    def loss_scale(self):
        return float(jax.device_get(self.scaler_state.cur_scale))

    def get_lr(self):
        return [g["lr"] for g in self.optimizer.param_groups]

    def get_mom(self):
        return [g["betas"] for g in self.optimizer.param_groups]

    # ------------------------------------------------------------------ setup
    def _build_group_index(self, specs):
        """Per-leaf STATIC group ids from pattern specs: leaf paths matching
        ``specs[i]['pattern']`` (first match wins) belong to group i+1; unmatched
        leaves to the base group 0. The analog of the reference's torch param_groups
        lists (engine.py:503-650) for a functional pytree, where leaves are named by
        path, not identity — the BERT no-decay recipe is
        ``[{"pattern": "bias|LayerNorm|ln_", "weight_decay": 0.0}]``."""
        import re
        treedef = jax.tree_util.tree_structure(self.params)
        compiled = [re.compile(s["pattern"]) for s in specs]
        ids, counts = [], [0] * (len(specs) + 1)
        for pstr in self._leaf_paths(self.params):
            gi = 0
            for i, rx in enumerate(compiled):
                if rx.search(pstr):
                    gi = i + 1
                    break
            ids.append(gi)
            counts[gi] += 1
        log_dist(f"optimizer param groups: {counts[0]} base leaves + "
                 f"{counts[1:]} per pattern group", ranks=[0])
        return jax.tree_util.tree_unflatten(treedef, ids)

    @staticmethod
    def _leaf_paths(tree):
        """Every leaf of a parameter tree as ``a/0/b``, in the tree's order: what a
        pattern over leaf paths is matched against."""
        return ["/".join(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
                         for p in path)
                for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]

    def _build_rule_mask(self, params):
        """``params``' tree with True at the leaves the model updates by its own rule
        (``rule_updated_leaves``), or None where it names none."""
        if not self._rule_patterns:
            return None
        import re
        assert callable(self._rule_fn), "a model that names rule_updated_leaves gives apply_rule"
        compiled = [re.compile(pattern) for pattern in self._rule_patterns]
        mask = [any(rx.search(pstr) for rx in compiled) for pstr in self._leaf_paths(params)]
        assert any(mask), f"no leaf matches rule_updated_leaves {self._rule_patterns}"
        log_dist(f"{sum(mask)} leaves are updated by the model's own rule", ranks=[0])
        return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), mask)

    def _compute_copy(self, master):
        """``master`` as a step's programs read the parameters: every weight in the compute
        dtype; a leaf the model updates by its own rule as the master holds it."""
        def cast(p):
            return p.astype(self.compute_dtype)
        if self._rule_mask is None:
            return jax.tree_util.tree_map(cast, master)
        # a copy: master and compute copy are donated side by side, never one buffer twice
        return jax.tree_util.tree_map(lambda m, p: jnp.copy(p) if m else cast(p),
                                      self._rule_mask, master)

    def _configure_optimizer(self, client_optimizer):
        # per-group hyperparameters: JSON config wins, else an optional model hook
        # (patterns over leaf paths; see _build_group_index)
        specs = (self.config.optimizer_params or {}).get("param_groups")
        if not specs:
            hook = getattr(self.module, "param_group_patterns", None)
            specs = tuple(hook()) if callable(hook) else ()
        specs = tuple(specs or ())
        self._group_index = self._build_group_index(specs) if specs else None
        if self._offload is not None:
            # Host-tier optimizer: the engine steps DeepSpeedCPUAdam directly
            # (reference engine.py:560-566 requires the cpu_adam op under ZeRO-Offload).
            name = self.config.optimizer_name or ADAM_OPTIMIZER
            assert name in (ADAM_OPTIMIZER, ADAMW_OPTIMIZER), \
                f"ZeRO-Offload supports Adam/AdamW (got {name!r})"
            assert client_optimizer is None or isinstance(client_optimizer, str), \
                "ZeRO-Offload steps the host-side DeepSpeedCPUAdam; client optimizers unsupported"
            self.optimizer = OptimizerHandle(name, self.config.optimizer_params or {},
                                             group_specs=specs)
            log_dist("Using ZeRO-Offload: host-tier DeepSpeedCPUAdam "
                     f"({'native' if self._offload._lib is not None else 'numpy'} kernel, "
                     f"{self._offload.numel} local master elements)", ranks=[0])
            return
        if client_optimizer is not None and not isinstance(client_optimizer, str):
            # client-provided (init, apply) pair or OptimizerHandle-compatible object
            if isinstance(client_optimizer, tuple) and len(client_optimizer) == 2:
                assert not specs, ("param_groups require a built-in optimizer; a client "
                                   "(init, apply) pair has no groups kwarg contract")
                if self.config.zero_enabled:
                    # reference engine.py:521-528: unknown optimizers under ZeRO need an
                    # explicit opt-in (sharded state layouts are derived from the state
                    # tree the client's init returns; untested shapes may shard poorly)
                    assert self.config.zero_allow_untested_optimizer, (
                        'You are using an untested ZeRO Optimizer. Please add '
                        '<"zero_allow_untested_optimizer": true> in the configuration '
                        'file to use it.')
                    log_dist("**** You are using ZeRO with an untested optimizer, "
                             "proceed with caution *****", ranks=[0])
                self._opt_init, self._opt_apply = client_optimizer
                self.optimizer = OptimizerHandle("client", self.config.optimizer_params or {})
            else:
                raise TypeError("client optimizer must be an (init_fn, apply_fn) pair; "
                                "torch optimizers are not supported on TPU")
        else:
            name = self.config.optimizer_name or ADAM_OPTIMIZER
            if name == ONEBIT_ADAM_OPTIMIZER:
                assert not specs, "1-bit Adam runs a single param group (compressed " \
                                  "error feedback is not per-group)"
                from ..ops import onebit_adam as onebit
                freeze_step = (self.config.optimizer_params or {}).get("freeze_step", 100000)
                # under a non-flat comm mode the frozen-phase momentum exchange
                # runs the two-level ICI+DCN schedule instead of the flat
                # compressed allreduce (docs/multislice.md)
                onebit_topo = (self._comm_topo
                               if self._comm_mode != COMM_MODE_FLAT else None)
                self._onebit = onebit.OneBitAdam(freeze_step=freeze_step, dp_size=self.dp_size,
                                                 mesh=self.mesh, topology=onebit_topo)
                self._opt_init, self._opt_apply = self._onebit.init, self._onebit.apply
            elif name in _OPTIMIZER_APPLY:
                self._opt_init, self._opt_apply = _OPTIMIZER_APPLY[name]
                if self._group_index is not None:
                    self._opt_apply = functools.partial(self._opt_apply,
                                                        groups=self._group_index)
            else:
                raise ValueError(f"Unrecognized optimizer {name!r}")
            self.optimizer = OptimizerHandle(name, self.config.optimizer_params or {},
                                             group_specs=specs)
        init = self._opt_init
        opt_state_zero = jax.eval_shape(init, self.master_params)
        params_treedef = jax.tree_util.tree_structure(self.master_params)
        # optimizer states mirror the master-param tree (Adam moments, momentum buffers):
        # give each params-shaped field the master sharding so ZeRO/pipe layouts carry over

        def field_shardings(field):
            if jax.tree_util.tree_structure(field) == params_treedef:
                return self._master_shardings
            return replicated_sharding(self.mesh, field)

        if hasattr(self, "_onebit"):
            self._opt_shardings = self._onebit.state_shardings(self.mesh)
        elif hasattr(opt_state_zero, "_fields"):
            self._opt_shardings = type(opt_state_zero)(*[field_shardings(f) for f in opt_state_zero])
        elif jax.tree_util.tree_structure(opt_state_zero) == params_treedef:
            self._opt_shardings = self._master_shardings
        else:
            # Unknown client state shape: replicate rather than guess a wrong ZeRO axis
            # (a caller layout like pipe-stacked stages would otherwise be violated).
            logger.warning("client optimizer state does not mirror the param tree; "
                           "optimizer state will be replicated")
            self._opt_shardings = replicated_sharding(self.mesh, opt_state_zero)
        self.opt_state = jax.jit(init, out_shardings=self._opt_shardings)(self.master_params)
        log_dist(f"Using DeepSpeed Optimizer param name {self.optimizer.name}", ranks=[0])

    def _configure_lr_scheduler(self, client_lr_scheduler):
        if client_lr_scheduler is not None:
            self.lr_scheduler = client_lr_scheduler
        elif self.config.scheduler_name is not None:
            self.lr_scheduler = get_scheduler(self.config.scheduler_name, self.optimizer,
                                              self.config.scheduler_params or {})
            log_dist(f"DeepSpeed using configured LR scheduler = {self.config.scheduler_name}", ranks=[0])
        else:
            self.lr_scheduler = None

    def deepspeed_io(self, dataset, batch_size=None, route=ROUTE_TRAIN, data_sampler=None,
                     collate_fn=None, num_local_io_workers=None):
        if batch_size is None:
            batch_size = self.train_micro_batch_size_per_gpu() * self.dp_size
        return DeepSpeedDataLoader(dataset, batch_size=batch_size,
                                   collate_fn=collate_fn or self.collate_fn,
                                   data_parallel_world_size=self.dp_size)

    # ------------------------------------------------------------------ jitted step functions
    def _compile_steps(self):
        self._run_fused_step = None   # set on the fused gas==1 paths below
        self._fused_pending = None
        self._jit_fused = None        # the fused jit object, for flops_profile
        self._overlap_plan = None     # set when comm.overlap=bucketed is live
        grad_acc_steps = self.gradient_accumulation_steps()
        fp16 = self.fp16_enabled()
        clip = float(self.gradient_clipping() or 0.0)
        compute_dtype = self.compute_dtype
        model_fn = self.model_fn
        opt_apply = getattr(self, "_opt_apply", None)  # None under ZeRO-Offload (host step)
        dynamic = self._dynamic_scale
        scale_window = self.config.loss_scale_window
        min_scale = self.config.min_loss_scale
        hysteresis = self.config.hysteresis
        predivide = float(self.config.gradient_predivide_factor or 1.0)
        prescale = self.config.prescale_gradients
        use_stacked = self._use_stacked_grads
        # numerics sentinel: a STATIC trace-time switch. When None the step
        # functions return their historical tuples with the historical ops —
        # HLO-instruction-identical to pre-sentinel programs by construction.
        sentinel_index = self._sentinel_index
        if sentinel_index is not None:
            from ..utils.numerics import bucket_sumsq
        # ZeRO stage >= 2 and ZeRO-Offload keep device grads in the compute dtype —
        # the reference's fp16 grad partitions (stage2.py:333-349, upcast only at the
        # fp32 master update) — halving the grad HBM footprint that bounds max model
        # size per chip. Stage <= 1 keeps fp32 grads (the reference's fp32 allreduce
        # option); the optimizer update always upcasts per-leaf inside its fused loop.
        # `allreduce_always_fp32` (reference engine.py:1016-1089 upcasts the allreduce
        # tensor) and `communication_data_type` override the default: grads are
        # produced in grad_dtype, so the psum XLA inserts over the data axis rides
        # the wire in exactly this dtype.
        zero_stage_ = self.zero_optimization_stage()
        grad_dtype = (compute_dtype if (self._offload is not None or zero_stage_ >= 2)
                      else jnp.float32)
        if self.config.allreduce_always_fp32:
            grad_dtype = jnp.float32
        if self.config.communication_data_type is not None:
            grad_dtype = {"fp32": jnp.float32, "fp16": jnp.float16,
                          "bf16": jnp.bfloat16}[self.config.communication_data_type]
        self._grad_dtype = grad_dtype

        scalar_names = self._device_scalar_names
        sum_names = self._rule_sum_names
        rule_mask, rule_fn = self._rule_mask, self._rule_fn

        def local_loss_and_grad(params, scale, *batch):
            # named_scope is HLO metadata only (zero instructions — asserted by
            # tests/unit/test_telemetry.py), so the trace annotation is unconditional
            with ds_named_scope("ds_fwd_bwd"):
                def scaled_loss_fn(p):
                    out = model_fn(p, *batch)
                    loss = out[0] if isinstance(out, (tuple, list)) else out
                    factor = scale / grad_acc_steps
                    if prescale:
                        factor = factor / predivide
                    if not (scalar_names or sum_names):
                        return loss * factor, loss
                    # what a model names rides out beside the loss, on every grad path, as
                    # (loss, its device scalars, the sums its own rule reads); either may be empty
                    kept = {name: jax.lax.stop_gradient(out[1][name]) for name in scalar_names}
                    assert all(v.ndim <= 1 for v in kept.values()), "scalars, or one a layer"
                    sums = {name: jax.lax.stop_gradient(out[1][name]).astype(jnp.float32)
                            for name in sum_names}
                    return loss * factor, (loss, kept, sums)
                (_, loss), grads = jax.value_and_grad(scaled_loss_fn, has_aux=True)(params)
                grads = jax.tree_util.tree_map(lambda g: g.astype(grad_dtype), grads)
            return loss, grads

        def shard_mapped_loss_and_grad(reduce_grads, grad_out_specs):
            """shard_map scaffold shared by the stacked (1-bit Adam) and sparse
            reduction modes: replicated params, data-sharded batch, pmean'd loss;
            only the per-leaf grad handling differs."""
            param_specs = jax.tree_util.tree_map(lambda _: P(), self.params)

            def loss_and_grad(params, scale, *batch):
                def local(params, scale, *local_batch):
                    loss, grads = local_loss_and_grad(params, scale, *local_batch)
                    return jax.lax.pmean(loss, DATA_AXIS), reduce_grads(grads, batch)

                batch_specs = tuple(P(DATA_AXIS) for _ in batch)
                fn = jax.shard_map(local, mesh=self.mesh,
                                   in_specs=(param_specs, P()) + batch_specs,
                                   out_specs=(P(), grad_out_specs), check_vma=False)
                return fn(params, scale, *batch)

            return loss_and_grad

        # comm.overlap=bucketed (docs/overlap.md): issue the grad exchange per
        # size-bounded bucket instead of as one monolithic post-backward vector,
        # so each bucket's collectives depend only on its own backward subtree
        # and can overlap the remaining backward compute (and, hierarchically,
        # each other's DCN phase). Inert when another subsystem owns the
        # exchange or there is nothing to exchange (dp == 1).
        overlap_requested = self.config.comm_overlap_mode == COMM_OVERLAP_BUCKETED
        overlap_active = (overlap_requested and not use_stacked
                          and self._sparse_grad_flags is None
                          and self.dp_size > 1 and self._offload is None)
        if overlap_requested and not overlap_active and self.dp_size > 1:
            logger.warning(
                "[deepspeed_tpu] comm.overlap.mode='bucketed' requested but the "
                "gradient exchange is owned elsewhere (1-bit Adam stacked grads "
                "or sparse-gradient reduction); overlap is inert")

        if self._use_stacked_grads:
            # 1-bit Adam path: keep per-worker grads stacked over a leading dp axis
            # instead of letting XLA psum them — the compressed allreduce in the optimizer
            # replaces the gradient averaging (reference disables engine allreduce when
            # frozen, onebit_adam.py:372).
            loss_and_grad = shard_mapped_loss_and_grad(
                lambda grads, batch: jax.tree_util.tree_map(lambda g: g[None], grads),
                jax.tree_util.tree_map(lambda _: P(DATA_AXIS), self.params))
        elif self._sparse_grad_flags is not None and self.dp_size > 1:
            # sparse_gradients mode (reference engine.py:1091-1147): embedding-table
            # grads are reduced by gathering (indices, values) over the data axis
            # instead of a dense psum; all other grads pmean as usual. shard_map
            # replaces XLA's automatic reduction so we control the per-leaf strategy.
            from .sparse_tensor import row_sparse_allreduce
            sparse_flags = self._sparse_grad_flags
            sparse_tokens_fn = self._sparse_tokens_fn
            if sparse_tokens_fn is None:
                logger.warning(
                    "[deepspeed_tpu] sparse_gradients: no sparse_grad_tokens() hint on "
                    "the model; sizing the sparse row capacity from batch arg 0 when it "
                    "is an integer token-id tensor, else falling back to dense reduction")
            dp = self.dp_size

            def reduce_sparse(grads, batch):
                # A token position contributes at most one nonzero row per table,
                # so local token count exactly bounds the sparse row capacity.
                if sparse_tokens_fn is not None:
                    global_tokens = int(sparse_tokens_fn(*batch))
                elif batch and hasattr(batch[0], "dtype") and \
                        jnp.issubdtype(batch[0].dtype, jnp.integer):
                    global_tokens = int(np.prod(batch[0].shape))
                else:
                    # no hint and arg 0 is not a token-id tensor: a guessed capacity
                    # could silently DROP gradient rows — use the dense reduction
                    return jax.tree_util.tree_map(
                        lambda g: jax.lax.pmean(g, DATA_AXIS), grads)
                local_tokens = global_tokens // dp
                flat, treedef = jax.tree_util.tree_flatten(grads)
                flat_flags = jax.tree_util.tree_leaves(sparse_flags)
                reduced = []
                for g, is_sparse in zip(flat, flat_flags):
                    cap = min(local_tokens, g.shape[0]) if is_sparse else 0
                    # sparse gather ships dp*cap rows; dense psum ships rows/...: only
                    # gather when the table is genuinely sparse this step
                    if is_sparse and cap * dp < g.shape[0]:
                        reduced.append(row_sparse_allreduce(g, DATA_AXIS, capacity=cap))
                    else:
                        reduced.append(jax.lax.pmean(g, DATA_AXIS))
                return jax.tree_util.tree_unflatten(treedef, reduced)

            loss_and_grad = shard_mapped_loss_and_grad(
                reduce_sparse, jax.tree_util.tree_map(lambda _: P(), self.params))
        elif overlap_active:
            # bucketed overlapped exchange (docs/overlap.md): the same two-level
            # schedule as the hierarchical branch below, issued once per bucket
            # under a ds_grad_bucket{k} named_scope. Per element the reduction
            # tree is unchanged, so the result is bit-equal to the monolithic
            # exchange given the same topology (and, under comm.mode=flat, each
            # bucket degenerates to a plain psum — the flat exchange up to an
            # exact power-of-two rescale). Under hierarchical_compressed this
            # is also the full-precision warmup phase.
            from ..comm.hierarchical import bucket_plan, bucketed_two_level_mean
            from ..comm.topology import CommTopology
            topo = (self._comm_topo if self._comm_mode != COMM_MODE_FLAT
                    else CommTopology(self.dp_size, 1))
            bucket_bytes = int(self.config.comm_overlap_bucket_mb * (1 << 20))
            plan = bucket_plan(self.params, bucket_bytes, self.dp_size)
            self._overlap_plan = plan
            self._overlap_topo = topo

            def reduce_overlap(grads, batch):
                del batch
                leaves, treedef = jax.tree_util.tree_flatten(grads)
                out = bucketed_two_level_mean(leaves, plan, topo)
                return jax.tree_util.tree_unflatten(treedef, out)

            loss_and_grad = shard_mapped_loss_and_grad(
                reduce_overlap, jax.tree_util.tree_map(lambda _: P(), self.params))
        elif self._comm_mode != COMM_MODE_FLAT and self.dp_size > 1:
            # hierarchical comm (docs/multislice.md): the gradient exchange runs
            # the explicit two-level schedule — reduce-scatter within each slice
            # over ICI, allreduce across slices over DCN, all-gather within the
            # slice — instead of GSPMD's flat single-axis psum. One division at
            # the end, same placement as the flat pmean. Under
            # hierarchical_compressed this full-precision path is also the
            # warmup phase (forward() switches to the compressed program at
            # comm.compress_start_step).
            from ..comm.hierarchical import (flatten_tree, unflatten_tree,
                                             tree_size, two_level_sum,
                                             padded_size)
            topo = self._comm_topo
            dp = self.dp_size
            n_total = tree_size(self.params)
            n_pad = padded_size(n_total, dp)

            def reduce_hier(grads, batch):
                del batch
                vec, recipe = flatten_tree(grads)
                vec = jnp.pad(vec, (0, n_pad - n_total))
                mean = two_level_sum(vec, topo) / dp
                return unflatten_tree(mean[:n_total].astype(grad_dtype), recipe)

            loss_and_grad = shard_mapped_loss_and_grad(
                reduce_hier, jax.tree_util.tree_map(lambda _: P(), self.params))
        else:
            loss_and_grad = local_loss_and_grad

        # The fused single-jit paths inline `loss_and_grad` directly. That
        # historically required the plain local grad path; the bucketed overlap
        # exchange is the one shard_mapped reduction that composes (its
        # value_and_grad runs INSIDE the shard_map body, so nothing
        # differentiates through the shard_map) — except under
        # hierarchical_compressed, whose warmup->compressed program switch in
        # forward() needs the two-jit step.
        fused_grad_ok = (loss_and_grad is local_loss_and_grad
                         or (overlap_active
                             and self._comm_mode != COMM_MODE_COMPRESSED))
        # a rule-updated leaf lives on the plain path alone (the default two-program step and
        # the fused step): elsewhere nothing would sum its sums or call its rule
        assert rule_mask is None or (
            loss_and_grad is local_loss_and_grad and self._offload is None
            and self._comm_mode != COMM_MODE_COMPRESSED), (
            "a model with rule_updated_leaves trains on the plain gradient path: not under "
            "ZeRO-Offload, 1-bit Adam, sparse gradients, or a hierarchical, compressed or "
            "bucketed gradient exchange")
        if self.config.fused_step and not (
                grad_acc_steps == 1 and fused_grad_ok
                and self._offload is None and not self._cpu_checkpointing_active()):
            # warn HERE (the offload path returns early below and would otherwise
            # swallow the flag silently): the user must not believe the fused
            # step's HBM saving is active when it is not
            logger.warning(
                "[deepspeed_tpu] fused_step requested but ineligible (it needs "
                "gradient_accumulation_steps == 1 and the plain local grad path "
                "or the bucketed overlap exchange — no 1-bit Adam stacked "
                "grads, sparse-gradient reduction, non-overlapped hierarchical "
                "comm, compressed comm, ZeRO-Offload, or cpu activation "
                "checkpointing); using the two-jit step")

        # Inputs carry their shardings (params/batch were device_put with the right
        # layouts); out_shardings on the grads is what makes stage-2 store them
        # reduce-scattered instead of materializing full replicas.
        # Exception: host-offloaded remat residuals introduce side-effecting
        # placement custom-calls that XLA's SPMD partitioner refuses to combine
        # with explicit (esp. replicated) out_shardings — there we let XLA pick
        # output layouts and the downstream jits re-shard via their in_shardings.
        # The choice is deferred to first forward (see _jit_loss_and_grad) so a
        # Megatron-style act_ckpt.configure(checkpoint_in_cpu=True) AFTER engine
        # construction still lands on the compatible jit.
        self._loss_and_grad_fn = loss_and_grad
        self._jit_loss_and_grad_cached = None
        self._jit_eval_cached = None

        # ---- compressed comm scaffold (comm.mode=hierarchical_compressed) ----
        # A second grad program carrying the persistent error-feedback buffers:
        # forward() runs it once global_steps reaches comm.compress_start_step
        # (the 1-bit two-phase rule: full-precision warmup, compressed after).
        # EF state is engine-held (it belongs to the EXCHANGE, not the
        # optimizer) and starts zeroed at the phase switch.
        self._loss_and_grad_comm_fn = None
        self._jit_loss_and_grad_comm_cached = None
        self._comm_we = self._comm_se = None
        if (self._comm_mode == COMM_MODE_COMPRESSED and not use_stacked
                and self._sparse_grad_flags is None and self.dp_size > 1):
            from ..comm.hierarchical import (flatten_tree, unflatten_tree,
                                             tree_size, grad_segment_ids,
                                             two_level_compressed,
                                             bucketed_error_state_shapes,
                                             bucketed_two_level_compressed,
                                             error_state_shapes, padded_size)
            topo = self._comm_topo
            if overlap_active:
                # bucketed EF layout (docs/overlap.md): the persistent error
                # buffers hold the per-bucket chunks back to back, and each
                # bucket compresses with its OWN per-tensor scale segments —
                # same telescoping contract per bucket, different (chunked)
                # scale boundaries than the monolithic exchange.
                plan = self._overlap_plan
                param_leaves = jax.tree_util.tree_leaves(self.params)
                seg_consts, n_segs_list = [], []
                for b in plan:
                    sn = grad_segment_ids(
                        [param_leaves[i] for i in b["leaf_indices"]], b["n_pad"])
                    seg_consts.append(jnp.asarray(sn))
                    n_segs_list.append(int(sn.max()) + 1)
                we_shape, se_shape = bucketed_error_state_shapes(plan, topo)
            else:
                n_total = tree_size(self.params)
                n_pad = padded_size(n_total, self.dp_size)
                seg_np = grad_segment_ids(self.params, n_pad)
                n_segs = int(seg_np.max()) + 1
                seg_const = jnp.asarray(seg_np)
                we_shape, se_shape = error_state_shapes(n_pad, topo)
            ef_sharding = NamedSharding(self.mesh, P(DATA_AXIS, None))
            self._comm_we = jax.device_put(jnp.zeros(we_shape, jnp.float32),
                                           ef_sharding)
            self._comm_se = jax.device_put(jnp.zeros(se_shape, jnp.float32),
                                           ef_sharding)
            param_specs = jax.tree_util.tree_map(lambda _: P(), self.params)
            grad_specs = jax.tree_util.tree_map(lambda _: P(), self.params)

            def loss_and_grad_comm(params, scale, we, se, *batch):
                def local(params, scale, we_row, se_row, *local_batch):
                    loss, grads = local_loss_and_grad(params, scale, *local_batch)
                    if overlap_active:
                        leaves, treedef = jax.tree_util.tree_flatten(grads)
                        out, new_we, new_se = bucketed_two_level_compressed(
                            leaves, we_row[0], se_row[0], plan, topo,
                            seg_consts, n_segs_list)
                        grads_out = jax.tree_util.tree_unflatten(treedef, out)
                    else:
                        vec, recipe = flatten_tree(grads)
                        # compression runs in fp32: the sign + per-segment scale
                        # IS the wire format, whatever grad_dtype is
                        vec = jnp.pad(vec.astype(jnp.float32),
                                      (0, n_pad - n_total))
                        out, new_we, new_se = two_level_compressed(
                            vec, we_row[0], se_row[0], topo, seg_const, n_segs)
                        grads_out = unflatten_tree(
                            out[:n_total].astype(grad_dtype), recipe)
                    return (jax.lax.pmean(loss, DATA_AXIS), grads_out,
                            new_we[None], new_se[None])

                batch_specs = tuple(P(DATA_AXIS) for _ in batch)
                fn = jax.shard_map(local, mesh=self.mesh,
                                   in_specs=(param_specs, P(), P(DATA_AXIS, None),
                                             P(DATA_AXIS, None)) + batch_specs,
                                   out_specs=(P(), grad_specs, P(DATA_AXIS, None),
                                              P(DATA_AXIS, None)),
                                   check_vma=False)
                return fn(params, scale, we, se, *batch)

            self._loss_and_grad_comm_fn = loss_and_grad_comm

        # Per-microbatch grads stay in the compute dtype (halves the backward HBM
        # footprint) but the ACCUMULATOR is fp32 when the window spans multiple
        # micro-batches: bf16 a+g loses mantissa bits as the window grows and
        # loss-scaled fp16 sums can overflow mid-window. The reference accumulates into
        # fp32 host buffers (stage2.py async CPU grad accumulation) — matching numerics
        # costs one fp32 accumulator.
        acc_dtype = (jnp.float32 if (grad_dtype != jnp.float32 and grad_acc_steps > 1)
                     else grad_dtype)
        self._acc_dtype = acc_dtype

        def accumulate(acc, grads):
            with ds_named_scope("ds_accumulate"):
                return jax.tree_util.tree_map(lambda a, g: a + g.astype(acc_dtype), acc, grads)

        self._jit_accumulate = self._watch("accumulate", jax.jit(
            accumulate,
            in_shardings=(self._grad_shardings, self._grad_shardings),
            out_shardings=self._grad_shardings,
            donate_argnums=(0,)))
        # (no donation: a compute-dtype buffer can't back the wider fp32 output)
        self._jit_adopt_acc = (None if acc_dtype == grad_dtype else self._watch("adopt_acc", jax.jit(
            lambda g: jax.tree_util.tree_map(lambda x: x.astype(acc_dtype), g),
            in_shardings=(self._grad_shardings,),
            out_shardings=self._grad_shardings)))

        def prep_grads(acc_grads, scaler_state):
            """The update's prologue (two-program and fused step alike): fp16
            overflow check and unscale, optional predivide, global norm, clip.
            With the numerics sentinel enabled, additionally returns per-subtree
            grad sumsq + nonfinite counts (the global norm and overflow bool are
            then DERIVED from those vectors — one pass over the tree either way,
            and no extra collectives)."""
            scale = scaler_state.cur_scale
            overflow, nonfinite = detect_overflow(acc_grads, fp16, sentinel_index)
            if fp16:
                inv = jnp.where(scale > 0, 1.0 / scale, 1.0)

                def unscale(g):
                    # bf16 spans fp32's exponent range, so a power-of-two unscale is
                    # an exact exponent shift in-dtype (no fp32-tree materialization).
                    # fp16's narrow exponent would flush small unscaled grads to zero
                    # — exactly what loss scaling protects — so fp16 unscales through
                    # fp32 (costing the fp32 grad copy the reference also pays at its
                    # fp32 master update, fused there into the optimizer).
                    if g.dtype == jnp.float16:
                        return g.astype(jnp.float32) * inv
                    return g * inv.astype(g.dtype)

                grads = jax.tree_util.tree_map(unscale, acc_grads)
            else:
                grads = acc_grads  # scale fixed at 1
            if prescale and predivide != 1.0:
                grads = jax.tree_util.tree_map(
                    lambda g: g * jnp.asarray(predivide, g.dtype), grads)
            if use_stacked:
                # stacked per-worker grads: the logical gradient is the worker mean —
                # clip/report on that, not on the sqrt(dp)-inflated stacked norm
                norm_tree = jax.tree_util.tree_map(lambda g: jnp.mean(g, axis=0), grads)
            else:
                norm_tree = grads
            if sentinel_index is not None:
                gss = bucket_sumsq(norm_tree, sentinel_index)
                norm = jnp.sqrt(jnp.sum(gss))
                sent = {"grad_sumsq": gss, "grad_nonfinite": nonfinite}
            else:
                norm = global_norm(norm_tree)
                sent = None
            if clip > 0:
                grads = clip_grads_by_global_norm(grads, clip, norm=norm)
            return grads, overflow, norm, sent

        def by_rule(master, opt_state, new_master, new_opt, sums):
            """The optimizer's result with the rule-updated leaves taken from the model's own
            rule instead (called once, here), and their optimizer state left as it was."""
            flat_mask = jax.tree_util.tree_leaves(rule_mask)
            named = jax.tree_util.tree_map(lambda m, x: x if m else None, rule_mask, master)
            moved = iter(jax.tree_util.tree_leaves(rule_fn(named, sums)))
            new_flat, treedef = jax.tree_util.tree_flatten(new_master)
            new_master = jax.tree_util.tree_unflatten(treedef, [
                next(moved).astype(new.dtype) if m else new for m, new in zip(flat_mask, new_flat)])

            def untouched(new_field, old_field):
                if jax.tree_util.tree_structure(new_field) != treedef:
                    return new_field
                return jax.tree_util.tree_map(lambda m, new, old: old if m else new,
                                              rule_mask, new_field, old_field)
            if hasattr(new_opt, "_fields"):
                new_opt = type(new_opt)(*[untouched(n, o) for n, o in zip(new_opt, opt_state)])
            else:
                new_opt = untouched(new_opt, opt_state)
            return new_master, new_opt

        def apply_update(master, opt_state, scaler_state, acc_grads, params, step, hyper,
                         rule_sums=None):
            if rule_mask is not None:      # no share of the norm or of the clipping either
                acc_grads = jax.tree_util.tree_map(
                    lambda m, g: jnp.zeros_like(g) if m else g, rule_mask, acc_grads)
            grads, overflow, norm, sent = prep_grads(acc_grads, scaler_state)

            def do_update(_):
                new_master, new_opt = opt_apply(grads, opt_state, master, step, hyper)
                if rule_mask is None:
                    return new_master, new_opt
                return by_rule(master, opt_state, new_master, new_opt, rule_sums)

            def skip_update(_):
                return master, opt_state

            with ds_named_scope("ds_apply_update"):
                new_master, new_opt = jax.lax.cond(overflow, skip_update, do_update, operand=None)
            new_scaler = ls.update(scaler_state, overflow, dynamic=dynamic, scale_window=scale_window,
                                   min_scale=min_scale, hysteresis=hysteresis)
            # params enter only to donate their buffer to the re-cast output
            del params
            new_params = self._compute_copy(new_master)
            if sent is not None:
                # weight norm + update magnitude per subtree (update is exactly
                # zero on a skipped step — the cond selected the old master)
                sent = dict(sent,
                            weight_sumsq=bucket_sumsq(new_master, sentinel_index),
                            update_sumsq=bucket_sumsq(
                                jax.tree_util.tree_map(lambda a, b: a - b,
                                                       new_master, master),
                                sentinel_index))
                return new_master, new_opt, new_scaler, new_params, overflow, norm, sent
            return new_master, new_opt, new_scaler, new_params, overflow, norm

        if self._offload is not None:
            # Host-tier step: the only device work is (a) one cheap stats pass for the
            # global grad norm + fp16 overflow flag (replicated scalars — XLA inserts
            # the cross-host psum the reference did with allreduce, stage2.py:1399-1415)
            # and (b) the all-gather that turns the pushed master-sharded compute-dtype
            # partitions back into the replicated/caller param layout (the reference's
            # all_gather of updated fp16 partitions, stage2.py:1441-1472).
            scalar = NamedSharding(self.mesh, P())

            def grad_stats(grads):
                overflow, nonfinite = detect_overflow(grads, fp16, sentinel_index)
                if sentinel_index is not None:
                    gss = bucket_sumsq(grads, sentinel_index)
                    return (jnp.sqrt(jnp.sum(gss)), overflow,
                            {"grad_sumsq": gss, "grad_nonfinite": nonfinite})
                return global_norm(grads), overflow

            stats_out = ((scalar, scalar) if sentinel_index is None else
                         (scalar, scalar, {"grad_sumsq": scalar,
                                           "grad_nonfinite": scalar}))
            self._jit_grad_stats = self._watch(
                "grad_stats", jax.jit(grad_stats, out_shardings=stats_out))
            same_layout = all(
                m.is_equivalent_to(p, l.ndim)
                for m, p, l in zip(jax.tree_util.tree_leaves(self._master_shardings),
                                   jax.tree_util.tree_leaves(self._param_shardings),
                                   jax.tree_util.tree_leaves(self.params)))
            self._jit_offload_push = (None if same_layout else self._watch(
                "offload_push", jax.jit(lambda t: t, out_shardings=self._param_shardings)))
            return  # no jitted optimizer update; Adam runs on the host tier

        scalar_shard = NamedSharding(self.mesh, P())
        scaler_shards = jax.tree_util.tree_map(lambda _: scalar_shard, self.scaler_state)
        # per-subtree sentinel vectors are tiny replicated arrays
        sent_shards = {"grad_sumsq": scalar_shard, "grad_nonfinite": scalar_shard,
                       "weight_sumsq": scalar_shard, "update_sumsq": scalar_shard}
        std_out = (self._master_shardings, self._opt_shardings, scaler_shards,
                   self._param_shardings, scalar_shard, scalar_shard)
        if sentinel_index is not None:
            std_out = std_out + (sent_shards,)
        self._jit_apply_update = self._watch("apply_update", jax.jit(
            apply_update,
            out_shardings=std_out,
            donate_argnums=(0, 1, 3, 4)))

        # Opt-in fused step ({"fused_step": true}, gas == 1): forward, backward and
        # update in ONE program, so the grad tree never materializes as jit outputs
        # (XLA frees each grad leaf once the optimizer consumed it), buying ~1
        # param-tree of HBM headroom (the margin that decides the remat policy for
        # large dp=1 runs). The update executes at forward() with master/opt/params
        # adopted immediately (their buffers are donated); step() commits
        # bookkeeping, and strict forward/backward/step rotation is enforced in
        # forward().
        if (self.config.fused_step and grad_acc_steps == 1
                and fused_grad_ok
                and not self._cpu_checkpointing_active()):
            def fused_step(master, opt_state, scaler_state, params, step, hyper,
                           *batch):
                # the whole two-jit pipeline inlined: value_and_grad feeds the
                # SAME apply_update body (overflow skip, scaler, param re-cast)
                loss, grads = loss_and_grad(params, scaler_state.cur_scale,
                                            *batch)
                return (loss,) + apply_update(master, opt_state, scaler_state,
                                              grads, params, step, hyper,
                                              self._loss_scalars_sums(loss)[2])

            jit_fused = self._watch("fused_step", jax.jit(
                fused_step,
                out_shardings=(scalar_shard,) + std_out,
                donate_argnums=(0, 1, 3)))
            self._jit_fused = jit_fused  # exposed for flops_profile

            def run_fused(batch):
                step_no = jnp.asarray(self.global_steps + 1 - self.skipped_steps,
                                      jnp.int32)
                outs = self._call_program(
                    "train.grad_program", "fused_step", jit_fused,
                    self.master_params, self.opt_state, self.scaler_state,
                    self.params, step_no, self.optimizer.current_hyper(), *batch)
                (loss, self.master_params, self.opt_state, self.scaler_state,
                 self.params, overflow, norm, *sent) = outs
                return loss, (overflow, norm, sent[0] if sent else None)

            self._run_fused_step = run_fused

    # ------------------------------------------------------------------ lint hooks
    @staticmethod
    def _lint_dtype_name(dt):
        name = jnp.dtype(dt).name
        return {"float16": "f16", "bfloat16": "bf16", "float32": "f32"}.get(name, name)

    def lint_programs(self, sample_batch):
        """[(name, jitted, args, manifest)] for every jitted program on this
        engine's ACTIVE step path, with the expected-collective manifest the
        program lint passes diff against the optimized HLO (docs/lint.md).

        The manifests encode the claims the bespoke HLO tests pin one path at
        a time: ZeRO>=2 backward crosses the data axis with a reduction (and
        with NOTHING param-scale besides it — a full-parameter all-gather here
        is the regression the suite exists to catch), the update re-gathers
        params only when the engine master is actually scattered, and the
        collective dtype is exactly the resolved grad/comm dtype. Budgets
        count only results above the small-element threshold, so scalar loss
        pmeans and norm reductions ride free.
        """
        batch = tuple(x if isinstance(x, jax.Array) else self.shard_batch(x)
                      for x in sample_batch)
        scale = self.scaler_state.cur_scale
        step = jnp.asarray(1, jnp.int32)
        hyper = self.optimizer.current_hyper()
        compute = self._lint_dtype_name(self.compute_dtype)
        grad_dt = self._lint_dtype_name(self._grad_dtype)
        dp = self.dp_size
        zstage = self.zero_optimization_stage()
        gas = self.gradient_accumulation_steps()

        def grads_like(dt, shardings):
            return jax.tree_util.tree_map(
                lambda p, s: jax.ShapeDtypeStruct(p.shape, dt, sharding=s),
                self.params, shardings)

        # the backward's cross-data reduction rides in exactly grad_dtype; with
        # the bucketed overlap exchange live there is one reduction PER BUCKET
        # (the per-bucket count is the structural claim — a re-fused monolithic
        # exchange would fail this floor)
        n_buckets = len(self._overlap_plan) if self._overlap_plan else 0
        red = ({"min": max(1, n_buckets), "dtypes": [grad_dt]} if dp > 1
               else {"max": 0})
        gather_gate = {"all-gather": {"min": 1, "dtypes": [compute, "f32"]}}
        comm_hier = (self._comm_mode != COMM_MODE_FLAT
                     and not self._use_stacked_grads
                     and self._sparse_grad_flags is None and dp > 1)
        lg_man = {
            "compute_dtype": compute,
            "any_reduction": red,
            # ZeRO-3 re-gathers params in forward; below stage 3 any large
            # all-gather in the backward is an undeclared-collective violation.
            # Hierarchical comm's intra-slice all-gather (level 3 of the
            # two-level schedule, one per bucket when overlapped) is a
            # declared exception.
            "collectives": (dict(gather_gate) if zstage >= 3 else
                            ({"all-gather": {"min": max(1, n_buckets),
                                             "dtypes": sorted({grad_dt, "f32"})}}
                             if comm_hier else {})),
            "donation": {"check_unusable": True},
            "strict": True,
        }
        if n_buckets:
            # bucketing scatters each bucket's chunk over the mesh; the
            # smallest per-bucket shard must still cross the large-collective
            # floor or the per-bucket reduction count could not be enforced
            lg_man["small_element_threshold"] = max(
                8, min(b["n_pad"] for b in self._overlap_plan) // dp - 1)
        local_man = {"compute_dtype": compute, "strict": True,
                     "donation": {"check_unusable": True}}
        progs = []

        if self._offload is not None:
            g_in = grads_like(self._grad_dtype, self._grad_shardings)
            progs.append(("loss_and_grad", self._jit_loss_and_grad,
                          (self.params, scale) + batch, lg_man))
            progs.append(("grad_stats", self._jit_grad_stats, (g_in,),
                          dict(local_man)))
            if self._jit_offload_push is not None:
                push_in = grads_like(self.compute_dtype, self._master_shardings)
                progs.append(("offload_push", self._jit_offload_push, (push_in,),
                              dict(local_man,
                                   collectives={"all-gather": {"min": 1,
                                                               "dtypes": [compute]}})))
            return progs

        scattered_master = any(
            not s.is_fully_replicated
            for s in jax.tree_util.tree_leaves(self._master_shardings))

        if self._run_fused_step is not None:
            f_man = {"compute_dtype": compute, "any_reduction": red,
                     "collectives": dict(gather_gate) if scattered_master else {},
                     "donation": {"check_unusable": True}, "strict": True}
            if n_buckets:
                f_man["small_element_threshold"] = \
                    lg_man["small_element_threshold"]
                if comm_hier:
                    # the bucketed two-level exchange's intra-slice gathers
                    # appear inside the fused step too
                    f_man["collectives"] = dict(
                        f_man["collectives"],
                        **{"all-gather": {"min": max(1, n_buckets),
                                          "dtypes": sorted({grad_dt, "f32",
                                                            compute})}})
            args = (self.master_params, self.opt_state, self.scaler_state,
                    self.params, step, hyper) + batch
            progs.append(("fused_step", self._jit_fused, args, f_man))
            return progs

        progs.append(("loss_and_grad", self._jit_loss_and_grad,
                      (self.params, scale) + batch, lg_man))
        if self._loss_and_grad_comm_fn is not None:
            # frozen-phase compressed exchange: sign payloads ride as packed u8
            # (or raw s8 when the sub-chunk defeats packing) over the DCN
            # all-to-all / all-gather; the per-segment scales and the ICI
            # reduce-scatter stay f32
            comm_man = {
                "compute_dtype": compute,
                "any_reduction": {"min": 1, "dtypes": ["f32"]},
                "collectives": {
                    "all-gather": {"min": max(1, n_buckets),
                                   "dtypes": sorted({"f32", "u8", "s8", grad_dt})},
                    "all-to-all": {"min": max(1, n_buckets),
                                   "dtypes": ["s8", "u8"]},
                },
                # the 1-bit phases ship PACKED signs: n/8 u8 elements, far below
                # the default large-collective floor at test scale — lower it so
                # the sign exchange is linted, while per-segment scale gathers
                # (~n_segs elements) still ride free. Bucketing splits the sign
                # payload per bucket, so the overlapped program needs the floor
                # one notch lower for the smallest bucket's 16-element piece.
                "small_element_threshold": 8 if n_buckets else 16,
                "donation": {"check_unusable": True},
                "strict": True,
            }
            progs.append(("loss_and_grad_comm", self._jit_loss_and_grad_comm,
                          (self.params, scale, self._comm_we, self._comm_se)
                          + batch, comm_man))
        acc_in = grads_like(self._acc_dtype, self._grad_shardings)
        if gas > 1:
            g_in = grads_like(self._grad_dtype, self._grad_shardings)
            progs.append(("accumulate", self._jit_accumulate, (acc_in, g_in),
                          dict(local_man)))
        au_man = {
            "compute_dtype": compute,
            "collectives": dict(gather_gate) if scattered_master else {},
            "donation": {"check_unusable": True},
            "strict": True,
        }
        args = (self.master_params, self.opt_state, self.scaler_state,
                acc_in, self.params, step, hyper, self._rule_sum_shapes(batch))
        progs.append(("apply_update", self._jit_apply_update, args, au_man))
        return progs

    def _loss_scalars_sums(self, out):
        """``(loss, device scalars, rule sums)`` from what a gradient program returns in its
        loss's place: the three where the model names scalars or sums, else the bare loss."""
        return out if self._device_scalar_names or self._rule_sum_names else (out, {}, {})

    def _rule_sum_shapes(self, batch):
        """The update program's last operand, as shapes: the sums the model's rule reads
        (an empty dict without rule-updated leaves)."""
        if not self._rule_sum_names:
            return {}
        out = jax.eval_shape(self._loss_and_grad_fn, self.params, self.scaler_state.cur_scale, *batch)
        return self._loss_scalars_sums(out[0])[2]

    def memory_manifest(self):
        """The memory analogue of ``lint_programs``: every persistent
        device-resident pytree this engine owns, grouped into the HBM
        observatory's attribution classes, plus the geometry the closed-form
        ZeRO predictor needs (utils/hbm.modeled_classes, docs/hbm.md).

        Class leaves may be live arrays or ShapeDtypeStructs — only
        shape/dtype/sharding are read (no device work, no syncs). Classes:

        - ``params``: compute-dtype parameters (sharded at stage >= 3)
        - ``grads``: the persistent grad/accumulation buffer handed between
          programs on the two-jit, accumulation and offload paths; absent on
          the fused path, where the grad tree stays internal and XLA frees
          each leaf as the optimizer consumes it (PERF.md round 5)
        - ``master``/``optimizer``: engine-held fp32 master and moment state
          (absent under ZeRO-Offload — host tier)
        - ``comm_ef``: the compressed exchange's persistent error-feedback
          buffers, when configured
        """
        import jax
        classes = {"params": self.params}
        fused = getattr(self, "_run_fused_step", None) is not None
        offload = self._offload is not None

        def grads_like(dt):
            return jax.tree_util.tree_map(
                lambda p, s: jax.ShapeDtypeStruct(p.shape, dt, sharding=s),
                self.params, self._grad_shardings)

        if offload:
            classes["grads"] = grads_like(self._grad_dtype)
            grad_itemsize = jnp.dtype(self._grad_dtype).itemsize
        elif not fused:
            classes["grads"] = grads_like(self._acc_dtype)
            grad_itemsize = jnp.dtype(self._acc_dtype).itemsize
        else:
            grad_itemsize = jnp.dtype(self._grad_dtype).itemsize
        if not offload:             # else master + moments live in host DRAM
            classes["master"] = self.master_params
            classes["optimizer"] = self.opt_state
        comm_ef_bytes = 0
        if self._comm_we is not None:
            from ..utils.hbm import leaf_signature
            classes["comm_ef"] = [self._comm_we, self._comm_se]
            comm_ef_bytes = sum(leaf_signature(b)[2]
                                for b in (self._comm_we, self._comm_se))
        psi = sum(int(np.prod(l.shape)) if l.shape else 1
                  for l in jax.tree_util.tree_leaves(self.params))
        geometry = {
            "kind": "training",
            "psi": psi,
            "param_itemsize": int(jnp.dtype(self.compute_dtype).itemsize),
            "grad_itemsize": int(grad_itemsize),
            "dp": int(self.dp_size),
            "zero_stage": int(self.zero_optimization_stage()),
            "zero_sharded_fraction": self._zero_sharded_fraction,
            "offload": offload,
            "fused": fused,
            "gas": int(self.gradient_accumulation_steps()),
            "comm_ef_bytes": int(comm_ef_bytes),
            "n_buckets": (len(self._overlap_plan) if self._overlap_plan
                          else 0),
        }
        return {"classes": classes, "geometry": geometry}

    # ------------------------------------------------------------------ train API
    def shard_batch(self, batch):
        """Place a host batch on the mesh, sharded over the data axis (leading dim)."""
        def put(x):
            x = np.asarray(x)
            return jax.device_put(x, NamedSharding(self.mesh, P(*( [DATA_AXIS] + [None] * (x.ndim - 1) ))))
        return jax.tree_util.tree_map(put, batch)

    def train(self, mode=True):
        self._in_training = mode

    def eval(self):
        self.warn_unscaled_loss = True
        self._in_training = False

    def __call__(self, *inputs, **kwargs):
        return self.forward(*inputs, **kwargs)

    def _cpu_checkpointing_active(self) -> bool:
        """Whether host-offloaded remat residuals are in play for this engine's traces.
        An engine WITH a JSON activation_checkpointing block decides from its own
        config (another engine's configure() must not strip its grad shardings);
        an engine WITHOUT one consults the process-global module, since its model's
        checkpoint_wrapper traces against that same global state."""
        from .activation_checkpointing import checkpointing as act_ckpt
        ac = self.config.activation_checkpointing_config
        if ac.configured_in_json:
            return bool(ac.cpu_checkpointing)
        return bool(act_ckpt.cpu_checkpointing_enabled())

    @property
    def _jit_loss_and_grad(self):
        """Built lazily at first training forward so the cpu-checkpointing decision sees
        both this engine's JSON config and any later module-level act_ckpt.configure()
        call (a post-first-step reconfigure cannot retroactively change the jit)."""
        if self._jit_loss_and_grad_cached is None:
            if self._cpu_checkpointing_active():
                jitted = jax.jit(self._loss_and_grad_fn)
            else:
                jitted = jax.jit(
                    self._loss_and_grad_fn,
                    out_shardings=(NamedSharding(self.mesh, P()), self._grad_shardings))
            self._jit_loss_and_grad_cached = self._watch("loss_and_grad", jitted)
        return self._jit_loss_and_grad_cached

    @property
    def _jit_loss_and_grad_comm(self):
        """Compressed-exchange grad program (comm.mode=hierarchical_compressed,
        frozen phase): carries the error-feedback buffers through, donated —
        they are persistent state rewritten every step."""
        if self._jit_loss_and_grad_comm_cached is None:
            ef = NamedSharding(self.mesh, P(DATA_AXIS, None))
            jitted = jax.jit(
                self._loss_and_grad_comm_fn,
                out_shardings=(NamedSharding(self.mesh, P()),
                               self._grad_shardings, ef, ef),
                donate_argnums=(2, 3))
            self._jit_loss_and_grad_comm_cached = self._watch(
                "loss_and_grad_comm", jitted)
        return self._jit_loss_and_grad_comm_cached

    @property
    def _jit_eval(self):
        """Jitted loss-only forward for eval() mode — the train path jits, and an
        op-by-op eval dispatch on a billion-parameter model is pathologically slow.
        Mirrors _jit_loss_and_grad's sharding handling (same cpu-checkpointing caveat)."""
        if self._jit_eval_cached is None:
            model_fn = self.model_fn

            def eval_loss(params, *batch):
                out = model_fn(params, *batch)
                return out[0] if isinstance(out, (tuple, list)) else out

            if self._cpu_checkpointing_active():
                jitted = jax.jit(eval_loss)
            else:
                jitted = jax.jit(eval_loss, out_shardings=NamedSharding(self.mesh, P()))
            self._jit_eval_cached = self._watch("eval_loss", jitted)
        return self._jit_eval_cached

    def forward(self, *inputs):
        """Compute the loss (and cache this micro-batch's gradients for backward)."""
        if (self.telemetry is not None and self._in_training
                and self.micro_steps % self.gradient_accumulation_steps() == 0):
            # first micro-step of an optimizer-step window: trace-window bookkeeping
            self.telemetry.on_step_begin(self.global_steps)
            if self._cluster is not None:
                # arm the hang watchdog deadline around this optimizer step
                self._cluster.on_step_begin(self.global_steps)
            # goodput: construction -> first train step is the init interval
            self._goodput_close_init()
        if self.wall_clock_breakdown():
            self.timers("forward_microstep").start()
        if self._in_training and self.micro_steps % self.gradient_accumulation_steps() == 0:
            if self._step_span is not None:      # a window that never reached step()
                self._spans.end(self._step_span)
            self._step_span = self._spans.begin(
                "train.step", engine=self._span_engine, step=self.global_steps, root=True,
                in_flight=sum(not loss.is_ready() for loss in self._step_losses))
        with self._spans.span("train.put_batch", engine=self._span_engine):
            batch = tuple(self.shard_batch(x) if not isinstance(x, jax.Array) else x
                          for x in inputs)
        if self._in_training:
            use_fused = self._run_fused_step is not None
            if use_fused and self._cpu_checkpointing_active():
                # a post-construction act_ckpt.configure(checkpoint_in_cpu=True):
                # the fused jit's explicit out_shardings cannot combine with
                # host-placement custom-calls (see _jit_loss_and_grad) — fall back
                if not getattr(self, "_warned_fused_cpu_ckpt", False):
                    self._warned_fused_cpu_ckpt = True
                    logger.warning("[deepspeed_tpu] fused_step disabled: cpu "
                                   "activation checkpointing was enabled after "
                                   "engine construction; using the two-jit step")
                use_fused = False
            if use_fused:
                # fused single-jit step (gas==1): the update runs HERE — the old
                # state buffers are donated into the jit and the new state adopted
                # immediately (a checkpoint between forward and step must never see
                # deleted buffers); step() commits only the bookkeeping
                if self._fused_pending is not None:
                    raise RuntimeError(
                        "fused step: the previous forward()'s update was never "
                        "committed — call backward() and step() before the next "
                        "forward() (strict forward/backward/step rotation)")
                loss, self._fused_pending = self._run_fused_step(batch)
                self._pending_grads = _FUSED
            elif (self._loss_and_grad_comm_fn is not None
                  and self.global_steps >= self.config.comm_compress_start_step):
                # compressed phase of hierarchical_compressed: host-side step
                # switch (the two-phase warmup rule) — cheaper than a traced
                # cond around two full backward programs
                loss, grads, self._comm_we, self._comm_se = self._call_program(
                    "train.grad_program", "loss_and_grad_comm",
                    self._jit_loss_and_grad_comm, self.params,
                    self.scaler_state.cur_scale, self._comm_we, self._comm_se, *batch)
                self._pending_grads = grads
            else:
                loss, grads = self._call_program(
                    "train.grad_program", "loss_and_grad", self._jit_loss_and_grad,
                    self.params, self.scaler_state.cur_scale, *batch)
                self._pending_grads = grads
            loss, scalars, sums = self._loss_scalars_sums(loss)     # whichever program ran
            if scalars:
                self._spans.keep_device_scalars(self._span_engine, self.global_steps, scalars)
            # the fused step has called the rule already
            self._pending_rule_sums = sums if sums and not use_fused else None
            self._pending_loss = loss
        else:
            self._goodput_begin_eval()
            loss = self._jit_eval(self.params, *batch)
            self._pending_grads = None
            self._goodput_end_eval()
        if self.wall_clock_breakdown():
            self.timers("forward_microstep").stop()
        return loss

    def backward(self, loss, allreduce_gradients=True, release_loss=False):
        """Accumulate this micro-batch's gradients (engine.py:767-841 semantics)."""
        assert self._pending_grads is not None, \
            "backward() called without a preceding forward() in training mode"
        if self.wall_clock_breakdown():
            self.timers("backward_microstep").start()
        if self._pending_grads is _FUSED:
            # fused step: grads were consumed inside the forward's jit; mark the
            # window ready for step() to commit
            self._pending_grads = None
            self._grad_acc = _FUSED
            if self._pending_loss is not None:
                self._window_losses.append(self._pending_loss)
            self.micro_steps += 1
            if self.wall_clock_breakdown():
                self.timers("backward_microstep").stop()
            return loss
        if self._pending_rule_sums is not None:      # summed over the window like the gradients
            self._rule_sums = self._pending_rule_sums if self._rule_sums is None else \
                jax.tree_util.tree_map(jnp.add, self._rule_sums, self._pending_rule_sums)
            self._pending_rule_sums = None
        with self._spans.span("train.accumulate", engine=self._span_engine):
            if self._grad_acc is None:
                # First micro-batch of the window: adopt the grads directly (they already
                # have the right sharding/dtype) instead of paying a zeros+add pass. With
                # gradient_accumulation_steps == 1 this removes the accumulate kernel
                # entirely. (Offload with accumulation > 1 upcasts to the fp32
                # accumulator dtype here.)
                self._grad_acc = (self._pending_grads if self._jit_adopt_acc is None
                                  else self._jit_adopt_acc(self._pending_grads))
            else:
                self._grad_acc = self._jit_accumulate(self._grad_acc, self._pending_grads)
        self._pending_grads = None
        if self._pending_loss is not None:
            # Defer the device sync: keep the per-micro-batch loss arrays and average at
            # emission time, so the monitor logs the accumulation-window mean (reference
            # logs the accumulated loss, not the last micro-batch's).
            self._window_losses.append(self._pending_loss)
        self.micro_steps += 1
        if self.wall_clock_breakdown():
            self.timers("backward_microstep").stop()
        return loss

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps) % self.gradient_accumulation_steps() == 0

    def zero_grad(self):
        self._grad_acc = None
        self._rule_sums = None
        # Fused-step window (gas==1): the optimizer update was
        # already applied at forward() (its inputs were donated and cannot be
        # restored); zeroing mid-window abandons only the step bookkeeping.
        self._fused_pending = None

    def step(self):
        """Apply the optimizer at the gradient-accumulation boundary (engine.py:903-985)."""
        if self.is_gradient_accumulation_boundary() and self._grad_acc is not None:
            self._take_model_step()
        return None

    def _take_model_step(self):
        if self.telemetry is not None:
            # host-local dispatch boundary: every host-side phase of the step
            # (input pipeline, accumulation, offload prep, injected stalls) is
            # behind us; everything below — the update program and its grad
            # collectives, the overflow/loss fetches — can block on peers, and
            # on a synchronous-dispatch backend does. The cluster observatory
            # attributes stragglers from the window ENDING here: it measures
            # how late this host arrived at the step's barrier, which is the
            # one signal blocking collectives cannot equalise away.
            self.telemetry.mark_step_dispatched()
        if self.wall_clock_breakdown():
            self.timers("step_microstep").start()
        if self._fused_pending is not None:
            # state was adopted at forward() (its buffers were donated); commit the
            # host-side bookkeeping here
            overflow, norm, sent = self._fused_pending
            self._fused_pending = None
            self._last_grad_norm = norm
            self._pending_sentinel = sent
            self._finish_step(self._overflowed(overflow))
            return
        if self._offload is not None:
            with self._spans.span("train.update_program", engine=self._span_engine,
                                  program="offload_step"):
                overflow_bool = self._offload_step()
            self._finish_step(overflow_bool)
            return
        hyper = self.optimizer.current_hyper()
        step = jnp.asarray(self.global_steps + 1 - self.skipped_steps, jnp.int32)
        rule_sums, self._rule_sums = self._rule_sums, None
        outs = self._call_program(
            "train.update_program", "apply_update", self._jit_apply_update,
            self.master_params, self.opt_state, self.scaler_state, self._grad_acc,
            self.params, step, hyper, rule_sums or {})
        # the numerics sentinel, when on, is one more output
        (self.master_params, self.opt_state, self.scaler_state, self.params,
         overflow, self._last_grad_norm, *sent) = outs
        self._pending_sentinel = sent[0] if sent else None
        self._finish_step(self._overflowed(overflow))

    def _overflowed(self, overflow) -> bool:
        """Whether the update skipped: known without asking the device unless fp16 is on."""
        if not self.fp16_enabled():
            return False
        with self._host_fetch():
            return bool(jax.device_get(overflow))

    def _offload_step(self) -> bool:
        """Host-tier optimizer step (ZeRO-Offload), partitioned and overlapped.

        Order of operations (reference stage2.py:750-907 + cpu_adam.cpp
        ds_adam_step_plus_copy):
          1. initiate async D2H of every LOCAL grad region (overlaps the stats jit and
             any still-running device work),
          2. one device stats pass -> global grad norm + fp16 overflow (replicated
             scalars; XLA emits the cross-host reduction),
          3. region-pipelined host step: wait for that region's transfer, run the native
             Adam kernel with loss-scale/clip fused in, async-push the updated
             compute-dtype slice back to its devices,
          4. one all-gather jit re-materializes the replicated/caller param layout from
             the pushed master-sharded partitions.
        Wall-clock ≈ max(D2H, host Adam) + all-gather instead of their sum.
        """
        handles = self._offload.begin_grad_fetch(self._grad_acc)
        if self._sentinel_index is not None:
            norm_dev, overflow_dev, sent_dev = self._jit_grad_stats(self._grad_acc)
        else:
            norm_dev, overflow_dev = self._jit_grad_stats(self._grad_acc)
            sent_dev = None
        with self._host_fetch():
            scale = float(jax.device_get(self.scaler_state.cur_scale))
            overflow = bool(jax.device_get(overflow_dev)) if self.fp16_enabled() else False

        factor = 1.0
        if scale != 1.0 and scale > 0:
            factor = 1.0 / scale
        predivide = float(self.config.gradient_predivide_factor or 1.0)
        if self.config.prescale_gradients and predivide != 1.0:
            factor *= predivide
        with self._host_fetch():
            norm = float(jax.device_get(norm_dev)) * factor
        self._last_grad_norm = norm
        # sumsq of the raw (still loss-scaled) grads; factor**2 converts to the
        # post-unscale semantics the standard path's sentinel reports. Captured
        # BEFORE the clip branch folds the clip coefficient into factor.
        unscale_sq = factor * factor
        clip = float(self.gradient_clipping() or 0.0)
        if clip > 0 and norm > clip:
            factor *= clip / (norm + 1e-6)

        if not overflow:
            group_hypers = self.optimizer.hyper_for_leaf_groups()
            leaf_hypers = None
            if self._group_index is not None:
                leaf_hypers = [group_hypers[gi]
                               for gi in jax.tree_util.tree_leaves(self._group_index)]
            g = group_hypers[0]
            step_count = self.global_steps + 1 - self.skipped_steps
            out_dtype = np.dtype(self.compute_dtype)
            pushed = self._offload.step_regions(
                handles, step_count, lr=g["lr"], beta1=g["beta1"], beta2=g["beta2"],
                eps=g["eps"], weight_decay=g["weight_decay"], grad_scale=factor,
                out_dtype=out_dtype, leaf_hypers=leaf_hypers)
            self.params = (pushed if self._jit_offload_push is None
                           else self._jit_offload_push(pushed))
        self.scaler_state = ls.update(
            self.scaler_state, jnp.asarray(overflow), dynamic=self._dynamic_scale,
            scale_window=self.config.loss_scale_window, min_scale=self.config.min_loss_scale,
            hysteresis=self.config.hysteresis)
        if sent_dev is not None:
            # this path already blocked on overflow/norm above, so the fetch
            # rides the existing sync — no new barrier
            with self._host_fetch():
                host = jax.device_get(sent_dev)
            self._pending_sentinel = {
                "grad_sumsq": host["grad_sumsq"] * unscale_sq,
                "grad_nonfinite": host["grad_nonfinite"],
            }
        return overflow

    def _room_beside_state(self):
        """A chip's bytes for what a gradient program keeps beyond need, by ``utils/hbm``'s rule
        from the device's limit and this engine's state; none where the backend reports no
        limit (the CPU). Read when a model is traced, never in a step."""
        from ..utils import hbm
        stats = [device_memory_stats(d) for d in self.mesh.local_devices]
        if not all(s and "bytes_limit" in s for s in stats):
            return 0
        _, class_bytes = hbm.manifest_signatures(self.memory_manifest())
        return hbm.room_beside_state(min(s["bytes_limit"] for s in stats), class_bytes)

    def _note_memory_in_use(self):
        """``bytes_in_use`` of the open ``train.step``: the most any of this process's
        devices holds now that the step's programs are enqueued (the runtime allocates a
        program's buffers when it is enqueued, not when it runs), and ``bytes_limit`` on
        the engine's first step. Nothing where the backend reports nothing (the CPU)."""
        stats = [s for s in map(device_memory_stats, self.mesh.local_devices) if s]
        if not stats or self._step_span is None:
            return
        self._step_span.attrs["bytes_in_use"] = max(s.get("bytes_in_use", 0) for s in stats)
        if not self._step_losses and all("bytes_limit" in s for s in stats):   # no step before
            self._step_span.attrs["bytes_limit"] = min(s["bytes_limit"] for s in stats)

    def _finish_step(self, overflowed: bool):
        self._note_memory_in_use()
        self._grad_acc = None
        if overflowed:
            self.skipped_steps += 1
            logger.info("[deepspeed_tpu] OVERFLOW! Skipping step.")
        else:
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
        report_progress = self.global_steps == 0 or (self.global_steps + 1) % self.steps_per_print() == 0
        if report_progress:
            self._report_progress(self.global_steps + 1)
        self.global_steps += 1
        if self.monitor is not None:
            # reference scalars: Train/Samples/train_loss + lr + loss_scale
            # (engine.py:779-790, 920-936)
            samples = self.global_steps * self.train_batch_size()
            with self._host_fetch():
                window = [float(l) for l in jax.device_get(self._window_losses)]
                scale = self.loss_scale() if self.fp16_enabled() else None
                norm = (None if self._last_grad_norm is None
                        else float(jax.device_get(self._last_grad_norm)))
            if window:
                self.monitor.add_scalar("Train/Samples/train_loss",
                                        sum(window) / len(window), samples)
            lr = self.get_lr()
            if lr:
                self.monitor.add_scalar("Train/Samples/lr", lr[0], samples)
            if scale is not None:
                self.monitor.add_scalar("Train/Samples/loss_scale", scale, samples)
            if norm is not None:
                self.monitor.add_scalar("Train/Samples/grad_norm", norm, samples)
            self.monitor.flush()  # reference flushes per emission (engine.py:790)
        numerics_host = None
        if self.telemetry is not None:
            # non-perturbing step boundary: rides the loss fetch (above, or here
            # when no monitor is attached) — no extra barrier enters the step
            with self._host_fetch():
                numerics_host = self.telemetry.end_step(
                    self.global_steps, self.train_batch_size(),
                    pending=self._window_losses, numerics=self._pending_sentinel,
                    run_goodput=self._goodput_scalars())
        elif self._pending_sentinel is not None:
            with self._host_fetch():
                numerics_host = jax.device_get(self._pending_sentinel)
        if self._numerics is not None:
            self._commit_numerics(numerics_host, overflowed, self._window_losses)
        if self._cluster is not None:
            # disarm the watchdog and allgather this step's heartbeat on the
            # host CPU world; host 0 derives and emits the Cluster/* scalars
            self._cluster.on_step_end(self.global_steps)
        if self._window_losses:
            self._step_losses.append(self._window_losses[-1])
        self._window_losses = []
        interval = self.config.resilience_save_interval
        if (self._resilience is not None and interval > 0
                and self.global_steps % interval == 0):
            # snapshot on this thread (device->host of committed step state),
            # commit in the background — the next step never fences on the
            # filesystem. async_save=False degrades to the synchronous path.
            self._resilience.save(tag=f"global_step{self.global_steps}")
            if not self.config.resilience_async_save:
                self._resilience.wait()
        # goodput: close this step's wall-clock interval AFTER the save hook,
        # so its snapshot fence is carved out of this step, not the next
        self._goodput_close_train_step()
        if self.wall_clock_breakdown():
            self.timers("step_microstep").stop()
            self.timers.log(["forward_microstep", "backward_microstep", "step_microstep"],
                            memory_breakdown=self.config.memory_breakdown)
        self._spans.end(self._step_span)
        self._step_span = None

    def _report_progress(self, step):
        lr = self.get_lr()
        mom = self.get_mom()
        log_dist(f"step={step}, skipped={self.skipped_steps}, lr={lr}, mom={mom}", ranks=[0])

    # ------------------------------------------------------------------ numerics
    def _commit_numerics(self, numerics_host, overflowed, pending_losses):
        """Feed one step's host-side sentinel values into the numerics monitor
        and run the cross-rank desync audit when its interval is due. Every
        input is already on the host (the sentinel rode the loss fetch), so
        this adds no sync point to the step."""
        self._pending_sentinel = None
        loss_host = None
        if pending_losses:
            # these loss scalars were fetched above for the monitor/telemetry;
            # device_get on an already-materialized array is a copy, not a sync
            loss_host = float(jax.device_get(pending_losses[-1]))
        gn = None
        if self._last_grad_norm is not None:
            gn = float(jax.device_get(self._last_grad_norm))
        self._numerics.commit_step(self.global_steps, numerics_host,
                                   loss=loss_host, overflowed=bool(overflowed),
                                   grad_norm=gn)
        if self._numerics.audit_due(self.global_steps):
            self._desync_audit()

    # ------------------------------------------------------------------ goodput
    # Run-lifecycle ledger hooks (docs/goodput.md). All pure host arithmetic
    # over counters the other observatories already maintain — nothing here
    # touches a device value, so the no-host-sync guard and the HLO-identity
    # tests hold with the block enabled.

    def _goodput_scalars(self):
        """Run/Goodput/* scalar dict for end_step — the ledger's state through
        the PREVIOUS step boundary (this step's interval closes after the
        save hook below)."""
        if self._goodput is None \
                or not self.config.telemetry_goodput_emit_scalars:
            return None
        return dict(self._goodput.scalar_items())

    def _goodput_compile_delta(self):
        """Compile seconds accrued since the last carve, from the compile
        watchdog's cumulative record wall."""
        if self.telemetry is None or self.telemetry.watchdog is None:
            return 0.0
        comp = self.telemetry.watchdog.compile_seconds()
        delta = comp - self._goodput_compile_base
        self._goodput_compile_base = comp
        return max(delta, 0.0)

    def _goodput_close_init(self):
        """Close the construction -> first-step interval as init, with the
        construction-time compiles (_compile_steps) carved out."""
        if self._goodput is None or not self._goodput_init_open:
            return
        self._goodput_init_open = False
        self._goodput.close("init",
                            {"compile": self._goodput_compile_delta()})

    def _goodput_begin_eval(self):
        """The span between the last boundary and eval dispatch is host gap,
        not eval — classify it before the eval interval opens."""
        if self._goodput is None:
            return
        self._goodput_close_init()
        self._goodput.close("host_gap")

    def _goodput_end_eval(self):
        if self._goodput is None:
            return
        self._goodput.close("eval",
                            {"compile": self._goodput_compile_delta()})

    def _goodput_close_train_step(self):
        """Close one train step's interval: carve compile, the checkpoint
        snapshot fence (when a save ran this step), and this host's dispatch
        skew above the fleet median; a step during which the hang watchdog
        fired bills its remainder to hang, a replayed step to restart_replay,
        everything else to productive_step."""
        if self._goodput is None:
            return
        self._goodput_close_init()
        carve = {"compile": self._goodput_compile_delta()}
        if self._resilience is not None:
            started = self._resilience.saves_started
            if started != self._goodput_saves_base:
                self._goodput_saves_base = started
                carve["checkpoint_stall"] = \
                    self._resilience.last_stall_ms / 1000.0
        hang = False
        if self._cluster is not None:
            skew = self._cluster.last_local_skew_s
            if skew > 0.0:
                carve["straggler_skew"] = skew
                # consumed: a skipped-heartbeat step must not re-bill it
                self._cluster.last_local_skew_s = 0.0
            if self._cluster.watchdog is not None:
                fired = len(self._cluster.watchdog.fired)
                hang = fired != self._goodput_hang_base
                self._goodput_hang_base = fired
        self._goodput.close_step(self.global_steps, carve, hang=hang)

    def _desync_audit(self):
        """Cross-rank replica-consistency audit (docs/numerics.md §audit): one
        small all-gather of per-subtree uint32 checksums, ONLY on audit steps."""
        if self.dp_size <= 1:
            return
        if self._audit_fn_cached is None:
            try:
                self._audit_fn_cached = self._build_audit_fn() or False
            except Exception as e:
                logger.warning(f"[numerics] desync audit unavailable: {e!r}")
                self._audit_fn_cached = False
        if self._audit_fn_cached is False:
            return
        fn, names = self._audit_fn_cached
        try:
            t0 = time.perf_counter()
            matrix = jax.device_get(fn(
                self.params,
                getattr(self, "opt_state", None) if self._offload is None else None))
            seconds = time.perf_counter() - t0
        except Exception as e:
            logger.warning(f"[numerics] desync audit failed, disabling: {e!r}")
            self._audit_fn_cached = False
            return
        slice_rows = (self._comm_topo.slice_rows
                      if (self._comm_mode != COMM_MODE_FLAT
                          and self._comm_topo.is_hierarchical) else None)
        self._numerics.commit_audit(self.global_steps, matrix, names,
                                    seconds=seconds, slice_rows=slice_rows)

    def _build_audit_fn(self):
        """Compile the audit program once: per-subtree uint32 checksums of every
        REPLICATED param/optimizer leaf, all-gathered over the data axis so the
        host can compare rows. shard_map with replicated in_specs is what makes
        this observable — under plain GSPMD the compiler assumes replicated
        arrays are bit-identical across replicas and would fold the comparison
        away; shard_map hands the local copy of each replica to the program."""
        from ..utils.numerics import leaf_checksum, subtree_name

        depth = self.config.numerics_subtree_depth
        repl = NamedSharding(self.mesh, P())
        trees = [("params", self.params, self._param_shardings, depth)]
        opt_state = getattr(self, "opt_state", None)
        opt_shardings = getattr(self, "_opt_shardings", None)
        if self._offload is None and opt_state is not None and opt_shardings is not None:
            # optimizer pytrees nest one level deeper (e.g. {"m": {...}, "v": {...}})
            trees.append(("opt", opt_state, opt_shardings, depth + 1))

        names, name_to_id, seg, picks = [], {}, [], []
        for ti, (tag, tree, shardings, d) in enumerate(trees):
            leaves_p = jax.tree_util.tree_flatten_with_path(tree)[0]
            sh_leaves = jax.tree_util.tree_leaves(shardings)
            for li, ((path, leaf), sh) in enumerate(zip(leaves_p, sh_leaves)):
                try:
                    if not sh.is_equivalent_to(repl, leaf.ndim):
                        continue  # sharded leaf: local shards legitimately differ
                except Exception:
                    continue
                name = f"{tag}/{subtree_name(path, d)}"
                if name not in name_to_id:
                    name_to_id[name] = len(names)
                    names.append(name)
                seg.append(name_to_id[name])
                picks.append((ti, li))
        if not picks:
            return None
        seg_arr = jnp.asarray(seg, jnp.int32)
        n = len(names)

        def local(*leaves):
            vals = jnp.stack([leaf_checksum(l) for l in leaves])
            vec = jax.ops.segment_sum(vals, seg_arr, num_segments=n)
            return jax.lax.all_gather(vec, DATA_AXIS)  # [dp, n_subtrees]

        mapped = jax.shard_map(local, mesh=self.mesh,
                               in_specs=tuple(P() for _ in picks),
                               out_specs=P(), check_vma=False)
        n_trees = len(trees)

        def audit(params, opt_state):
            flat = [jax.tree_util.tree_leaves(params)]
            if n_trees > 1:
                flat.append(jax.tree_util.tree_leaves(opt_state))
            return mapped(*[flat[ti][li] for ti, li in picks])

        return self._watch("desync_audit", jax.jit(audit)), names

    # ------------------------------------------------------------------ checkpointing
    def _ckpt_export(self, tree, kind):
        """Convert an in-memory state tree to the canonical on-disk representation.

        Identity here. Engines whose runtime layout differs from the layer-keyed
        checkpoint layout (the SPMD pipeline's pipe-stacked stages) override this so
        checkpoints stay topology-portable — the reference's layer-keyed pipeline
        checkpoints reload under a different stage count (pipe/module.py:536-567).
        ``kind`` is one of {"params", "master", "opt"}."""
        del kind
        return tree

    def _ckpt_import(self, tree, kind):
        """Inverse of ``_ckpt_export``: canonical on-disk tree -> runtime layout."""
        del kind
        return tree

    def flops_profile(self, *inputs, peak_tflops=None):
        """Cost analysis of THIS engine's compiled train step (fwd + bwd + update)
        from XLA's own numbers — see ``utils/flops_profiler.py``. ``inputs`` is one
        micro-batch (host arrays fine; shapes are what matter). Under ZeRO-Offload
        the optimizer update runs on the host tier and only the device programs are
        counted. Returns the report dict (add ``peak_tflops`` for the roofline step
        time). ``report["flops"]`` covers one micro-batch plus one optimizer
        update; for gradient_accumulation_steps > 1 aggregate from
        ``report["program_flops"]``: ``gas * loss_and_grad + apply_update``
        (the update runs once per window)."""
        from ..utils.flops_profiler import profile as _profile
        batch = tuple(x if isinstance(x, (jax.Array, jax.ShapeDtypeStruct))
                      else self.shard_batch(x) for x in inputs)
        step_no = jnp.asarray(1, jnp.int32)
        hyper = self.optimizer.current_hyper()
        if self._jit_fused is not None:
            report = _profile(self._jit_fused, self.master_params, self.opt_state,
                              self.scaler_state, self.params, step_no, hyper,
                              *batch, peak_tflops=peak_tflops)
            report["programs"] = ["fused_step"]
            report["program_flops"] = {"fused_step": report["flops"]}
        else:
            report = _profile(self._jit_loss_and_grad, self.params,
                              self.scaler_state.cur_scale, *batch,
                              peak_tflops=peak_tflops)
            report["programs"] = ["loss_and_grad"]
            report["program_flops"] = {"loss_and_grad": report["flops"]}
            if self._offload is None:
                # 1-bit Adam stacked grads carry a leading per-worker dp axis
                lead = (self.dp_size,) if self._use_stacked_grads else ()
                grads = jax.tree_util.tree_map(
                    lambda sh, l: jax.ShapeDtypeStruct(lead + l.shape,
                                                       self._acc_dtype,
                                                       sharding=sh),
                    self._grad_shardings, self.params)
                upd = _profile(self._jit_apply_update, self.master_params,
                               self.opt_state, self.scaler_state, grads,
                               self.params, step_no, hyper, self._rule_sum_shapes(batch))
                for k in ("flops", "bytes_accessed"):
                    report[k] += upd[k]
                report["program_flops"]["apply_update"] = upd["flops"]
                report["temp_bytes"] = max(report["temp_bytes"], upd["temp_bytes"])
                report["arithmetic_intensity"] = (
                    report["flops"] / report["bytes_accessed"]
                    if report["bytes_accessed"] else 0.0)
                if peak_tflops:
                    report["optimal_seconds"] = report["flops"] / (peak_tflops * 1e12)
                report["programs"].append("apply_update")
        from .utils import param_count
        report["params"] = param_count(self.params)
        return report

    def save_checkpoint(self, save_dir, tag=None, client_state={}, save_latest=True):
        from ..checkpoint.checkpointing import save_checkpoint as _save
        return _save(self, save_dir, tag=tag, client_state=client_state, save_latest=save_latest)

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True):
        from ..checkpoint.checkpointing import load_checkpoint as _load
        return _load(self, load_dir, tag=tag, load_optimizer_states=load_optimizer_states,
                     load_lr_scheduler_states=load_lr_scheduler_states)
