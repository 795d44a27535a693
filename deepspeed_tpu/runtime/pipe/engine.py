"""Pipeline engine: SPMD ppermute executor with an instruction-stream fallback.

TPU-native re-design of ``deepspeed/runtime/pipe/engine.py`` (PipelineEngine l.45).
``deepspeed.initialize(model=PipelineModule)`` — the reference's production multi-GPU
pipelining entry point (deepspeed/__init__.py:111-133) — routes onto ONE of two
executors:

1. **SPMD mode** (default when eligible): homogeneous stages (the layout
   ``partition_balanced`` yields for transformer stacks — an optional stage-0 prefix
   like an embedding, S identical core blocks, an optional last-stage suffix like a
   head) lower onto ``parallel/pipeline_spmd.py``: core stage params are STACKED on a
   leading axis sharded over the ``pipe`` mesh axis, micro-batches stream through a
   ``lax.scan`` whose stage→stage hand-off is a single ``lax.ppermute`` riding ICI,
   and the whole 1F1B-equivalent window compiles into ONE jitted train step (XLA
   derives the backward pipeline — see pipeline_spmd.py). This is the path that runs
   the pipe axis of a real multi-chip mesh; the base engine supplies fp16/ZeRO/
   monitoring unchanged (the accumulation window folds into the scan, so the base
   sees ``gradient_accumulation_steps == 1``).
2. **Instruction mode** (fallback / ``{"pipeline": {"spmd": false}}``): the
   single-controller executor below, which interprets the reference's exact
   instruction vocabulary and 1F1B stream (schedule.py) with jitted per-stage
   forwards/backwards — the debug/heterogeneous-stage path, parity-tested against
   the schedule semantics.

Checkpoints are layer-keyed in BOTH modes (the SPMD stacking is undone on save via
``_ckpt_export``), so stage boundaries and executor modes can change between save
and load exactly like the reference (pipe/module.py:536-567).

Instruction-mode execution model vs the reference:

- The reference runs one process per stage, eager autograd per micro-batch, and blocking
  p2p broadcasts (pipe/p2p.py). Here a single controller executes every stage's stream
  (merged by step index) with **jitted per-stage forward/backward functions**; the p2p
  sends/recvs become buffer hand-offs whose device placement XLA manages, and each
  micro-batch is sharded over the mesh ``data`` axis so DP gradient reduction is emitted
  by XLA (no NCCL allreduce). Within one merged step all Sends execute before any Recv —
  the scheduling invariant that lets the reference's blocking broadcasts rendezvous.
- BackwardPass recomputes the stage forward inside the jitted VJP (activation
  checkpointing per stage — the JAX analog of the reference's retained autograd graphs
  per pipe buffer; SURVEY §7 "hard parts").
- Tied layers (TiedLayerSpec) share one parameter entry; their gradient contributions sum
  during the backward merge — ``ReduceTiedGrads`` (reference pipe/module.py:405-474)
  needs no separate collective.
- ``OptimizerStep`` reuses the base engine's jitted sharded update (ZeRO over ``data``).

``forward``/``backward``/``step`` are blocked in pipeline mode exactly like the reference
(pipe/engine.py:1034-1044): use ``train_batch``/``eval_batch``.

For *multi-chip pipe-axis* execution with homogeneous transformer stages, see
``parallel/pipeline_spmd.py`` (shard_map + ppermute inside one jit).
"""

import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...parallel.mesh import DATA_AXIS, PIPE_AXIS, build_mesh
from ...parallel.pipe.module import LayerSpec, PipelineModule, TiedLayerSpec
from ...parallel.pipeline_spmd import pipeline_apply
from ...utils import log_dist, logger
from ..engine import DeepSpeedEngine
from . import schedule

# params-dict key holding the pipe-stacked core stage parameters in SPMD mode
# (namespaced so it can never collide with canonical 'layer_N' / 'tied::' keys)
STACKED_KEY = "pipe_stages::stacked"


def _raw_config_dict(args, config_params):
    """The raw JSON config dict before DeepSpeedConfig exists — the SPMD routing
    decision must happen before super().__init__ parses the config."""
    if isinstance(config_params, dict):
        return config_params
    path = getattr(args, "deepspeed_config", None) if args is not None else None
    if path:
        try:
            import json
            with open(path) as f:
                return json.load(f)
        except Exception:
            return {}
    return {}


def _spec_signature(spec):
    """Comparable identity of a layer spec for stage-homogeneity checks. None marks
    a spec that cannot be proven identical across stages (tied layers — their shared
    storage cannot stack — or specs whose constructor args defeat comparison)."""
    if isinstance(spec, TiedLayerSpec):
        return None
    if isinstance(spec, LayerSpec):
        try:
            return ("spec", id(spec.typename), repr(spec))
        except Exception:
            return None
    if callable(spec):
        return ("callable", id(spec))
    return None



def _assert_ring_bound(chan, src_stage, receiver_ring, direction):
    """The reference's per-stage buffer-ring memory contract
    (deepspeed/runtime/pipe/engine.py:133-148) as a tested invariant: payloads
    in flight from ``src_stage`` never exceed the RECEIVER's num_pipe_buffers()."""
    in_flight = sum(1 for (src, _) in chan if src == src_stage)
    assert in_flight <= receiver_ring, (
        f"stage {src_stage} {direction} channel holds {in_flight} payloads "
        f"> receiver num_pipe_buffers()={receiver_ring}")


class PipelineError(Exception):
    """Errors related to the use of deepspeed.PipelineEngine."""


_SEND_CMDS = (schedule.SendActivation, schedule.SendGrad, schedule.LoadMicroBatch)


class PipelineEngine(DeepSpeedEngine):

    def __init__(self, args=None, model=None, optimizer=None, model_parameters=None,
                 training_data=None, lr_scheduler=None, mpu=None, dist_init_required=None,
                 collate_fn=None, config_params=None, mesh=None):
        assert isinstance(model, PipelineModule), "model must be a PipelineModule"
        assert not getattr(model, "rule_updated_leaves", ()), \
            "the pipeline engine has no step for leaves a model updates by a rule of its own"
        self.pipe_module = model
        self.num_stages = model.num_stages

        canonical, layer_keys = self._canonicalize_params(model, model_parameters)
        self._layer_keys = layer_keys

        # ---- executor selection (SPMD ppermute path vs instruction fallback) ----
        self._spmd = False
        self._spmd_decomp = None
        raw_cfg = _raw_config_dict(args, config_params)
        spmd_opt = (raw_cfg.get("pipeline") or {}).get("spmd", "auto")
        opt_name = str(((raw_cfg.get("optimizer") or {}).get("type") or "")).lower()
        has_param_groups = bool(((raw_cfg.get("optimizer") or {}).get("params") or {})
                                .get("param_groups"))
        n_dev = (int(np.prod(list(mesh.shape.values()))) if mesh is not None
                 else len(jax.devices()))
        eligible = (spmd_opt in (True, "auto")
                    and self.num_stages > 1
                    and model.loss_fn is not None
                    and n_dev % self.num_stages == 0
                    # 1-bit Adam needs replicated params; param-group regex patterns
                    # are written against canonical layer paths
                    and opt_name != "onebitadam"
                    and not has_param_groups
                    and (mesh is None or mesh.shape.get(PIPE_AXIS, 1) == self.num_stages))
        if eligible:
            self._spmd_decomp = self._find_spmd_decomposition(model, layer_keys, canonical)
            if self._spmd_decomp is None and spmd_opt is True:
                raise ValueError(
                    "pipeline.spmd=true but the stage partition is not homogeneous "
                    f"(parts={model.parts}): the SPMD executor needs S identical core "
                    "blocks (plus optional stage-0 prefix / last-stage suffix)")

        if self._spmd_decomp is not None:
            self._spmd = True
            if mesh is None:
                mesh = build_mesh(pipe=self.num_stages)
            spmd_params = self._canonical_to_spmd(canonical)
            shardings = self._spmd_shardings(mesh, spmd_params)
            model_fn = self._build_spmd_model_fn(mesh)
            super().__init__(args=args, model=model_fn, optimizer=optimizer,
                             model_parameters=spmd_params, training_data=training_data,
                             lr_scheduler=lr_scheduler, mpu=None,
                             dist_init_required=dist_init_required, collate_fn=collate_fn,
                             config_params=config_params, mesh=mesh,
                             param_shardings=shardings)
            self._spmd_treedef = jax.tree_util.tree_structure(self.master_params)
            # the canonical dict built above has exactly the round-trip structure —
            # no need to materialize an unstack just for its treedef
            self._canonical_treedef = jax.tree_util.tree_structure(canonical)
        else:
            super().__init__(args=args, model=self._whole_model_fn, optimizer=optimizer,
                             model_parameters=canonical, training_data=training_data,
                             lr_scheduler=lr_scheduler, mpu=None,
                             dist_init_required=dist_init_required,
                             collate_fn=collate_fn, config_params=config_params, mesh=mesh)
        assert self._offload is None, \
            "cpu_offload is not supported with pipeline parallelism (the pipeline " \
            "optimizer step runs on device; reference pairs offload with plain ZeRO-2 only)"

        # the REAL accumulation window (SPMD mode reports 1 to the base engine — the
        # window folds into the jitted scan; see gradient_accumulation_steps)
        self.micro_batches = self.config.gradient_accumulation_steps
        if not self._spmd:
            self._compile_stage_fns()
        self.agg_train_loss = None

        # ---- pipeline schedule observatory (docs/pipeline-trace.md) ----
        # Disabled (the default) leaves ``pipe_trace`` as None: the executor
        # takes the untraced branch and the compiled stage programs are
        # HLO-instruction-identical to a build without the subsystem.
        self.pipe_trace = None
        if getattr(self.config, "pipeline_trace_enabled", False):
            if self._spmd:
                logger.warning(
                    "[deepspeed_tpu] telemetry.pipeline_trace: the SPMD executor "
                    "folds the whole schedule into one jitted scan — there is no "
                    "instruction stream to trace; set pipeline.spmd=false to "
                    "record spans")
            else:
                from ...utils.pipeline_trace import PipelineTracer
                self.pipe_trace = PipelineTracer(
                    stages=self.num_stages,
                    capacity=self.config.pipeline_trace_capacity,
                    dump_dir=self.config.pipeline_trace_dump_dir or None,
                    host_id=jax.process_index())
                rec = getattr(self._numerics, "recorder", None) if self._numerics else None
                if rec is not None:
                    rec.pipeline_trace = self.pipe_trace

        d = self._spmd_decomp
        log_dist(
            f"PipelineEngine[{'SPMD' if self._spmd else 'instruction'}]: "
            f"{self.num_stages} stages, parts={model.parts}"
            + (f", core={d['L']} layers/stage, prefix={len(d['prefix'])}, "
               f"suffix={len(d['suffix'])}, mesh={dict(self.mesh.shape)}"
               if self._spmd else ""),
            ranks=[0])

    def gradient_accumulation_steps(self):
        # SPMD mode folds the whole micro-batch window into ONE jitted call (the
        # scan inside pipeline_apply): the base engine sees a window of 1 so each
        # train_batch is exactly one forward/backward/step.
        if getattr(self, "_spmd", False):
            return 1
        return super().gradient_accumulation_steps()

    # ------------------------------------------------------------- params
    def _canonicalize_params(self, module: PipelineModule, model_parameters):
        """Per-layer params list → dict keyed by layer id; tied layers collapse onto one
        'tied::<key>' entry (shared storage, summed grads)."""
        if model_parameters is None:
            raise ValueError("PipelineEngine requires model_parameters: the list returned "
                             "by PipelineModule.init_params(rng, sample_input)")
        assert len(model_parameters) == module.num_layers(), \
            f"expected {module.num_layers()} per-layer param entries"
        canonical: Dict[str, Any] = {}
        layer_keys: List[Optional[str]] = []
        for idx, (spec, p) in enumerate(zip(module._layer_specs, model_parameters)):
            if p is None:
                layer_keys.append(None)
                continue
            key = f"tied::{spec.key}" if isinstance(spec, TiedLayerSpec) else f"layer_{idx}"
            if key not in canonical:
                canonical[key] = p
            layer_keys.append(key)
        return canonical, layer_keys

    # ------------------------------------------------------------- SPMD executor
    def _find_spmd_decomposition(self, module, layer_keys, canonical):
        """Homogeneity detection: can the stage partition be expressed as
        ``[prefix] + S x (identical core block stack) + [suffix]``?

        Returns ``{"starts": per-stage core start index, "L": core length,
        "prefix": stage-0-only layer indices, "suffix": last-stage-only indices}``
        or None when the partition is heterogeneous (→ instruction fallback).
        Matching is by layer-spec identity (same class + constructor args) AND
        param-tree structure/shape/dtype at every core position, so stacking over
        the pipe axis is guaranteed well-formed."""
        S = module.num_stages
        parts = module.parts
        counts = [parts[s + 1] - parts[s] for s in range(S)]
        sigs = [_spec_signature(spec) for spec in module._layer_specs]

        def try_core(L):
            if counts[0] < L or counts[-1] < L:
                return None
            if any(counts[s] != L for s in range(1, S - 1)):
                return None
            starts = [parts[1] - L] + [parts[s] for s in range(1, S)]
            pattern = sigs[starts[0]:starts[0] + L]
            if any(p is None for p in pattern):
                return None
            for s in range(1, S):
                if sigs[starts[s]:starts[s] + L] != pattern:
                    return None
            for j in range(L):
                keys = [layer_keys[starts[s] + j] for s in range(S)]
                if any((k is None) != (keys[0] is None) for k in keys):
                    return None
                if keys[0] is None:
                    continue
                trees = [canonical[k] for k in keys]
                t0 = jax.tree_util.tree_structure(trees[0])
                leaves0 = jax.tree_util.tree_leaves(trees[0])
                for t in trees[1:]:
                    if jax.tree_util.tree_structure(t) != t0:
                        return None
                    for a, b in zip(leaves0, jax.tree_util.tree_leaves(t)):
                        if a.shape != b.shape or a.dtype != b.dtype:
                            return None
            return starts

        if S > 2:
            candidates = [counts[1]]  # middle stages fix the core length
        else:
            candidates = range(min(counts), 0, -1)  # S=2: maximal core first
        for L in candidates:
            starts = try_core(L)
            if starts is not None:
                return {"starts": starts, "L": L,
                        "prefix": list(range(0, parts[1] - L)),
                        "suffix": list(range(parts[S - 1] + L, parts[S]))}
        return None

    def _canonical_to_spmd(self, canonical):
        """Layer-keyed dict -> SPMD layout: core stage params stack on a leading
        S axis (one entry under STACKED_KEY); prefix/suffix keep canonical keys."""
        d = self._spmd_decomp
        S, L, starts = self.num_stages, d["L"], d["starts"]
        out = {}
        for idx in d["prefix"] + d["suffix"]:
            k = self._layer_keys[idx]
            if k is not None:
                out[k] = canonical[k]
        stacked = []
        for j in range(L):
            if self._layer_keys[starts[0] + j] is None:
                stacked.append(None)
                continue
            per_stage = [canonical[self._layer_keys[starts[s] + j]] for s in range(S)]
            stacked.append(jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage))
        out[STACKED_KEY] = tuple(stacked)
        return out

    def _spmd_to_canonical(self, spmd):
        """Inverse of _canonical_to_spmd (works on any tree with the params
        structure — Adam moments included)."""
        d = self._spmd_decomp
        S, starts = self.num_stages, d["starts"]
        out = {k: v for k, v in spmd.items() if k != STACKED_KEY}
        for j, ent in enumerate(spmd[STACKED_KEY]):
            if ent is None:
                continue
            for s in range(S):
                out[self._layer_keys[starts[s] + j]] = jax.tree_util.tree_map(
                    lambda a, s=s: a[s], ent)
        return out

    def _spmd_shardings(self, mesh, spmd_params):
        """Core stacks shard their leading (stage) axis over ``pipe``; prefix/suffix
        params replicate (ZeRO composes on top via merge_zero_into)."""
        repl = NamedSharding(mesh, P())

        def leaf(a):
            return NamedSharding(mesh, P(*([PIPE_AXIS] + [None] * (a.ndim - 1))))

        out = {k: jax.tree_util.tree_map(lambda _: repl, v)
               for k, v in spmd_params.items() if k != STACKED_KEY}
        out[STACKED_KEY] = jax.tree_util.tree_map(leaf, spmd_params[STACKED_KEY])
        return out

    def _build_spmd_model_fn(self, mesh):
        """``(params, x_microbatches, labels_microbatches) -> mean loss`` through the
        ppermute pipeline. The prefix runs as pipeline_apply's first_stage_fn, the
        suffix + loss as its last_stage_fn; both draw their params from the SAME
        params dict the core stack lives in, so tied prefix/suffix layers (shared
        canonical entry) get their gradient contributions summed by autodiff."""
        d = self._spmd_decomp
        layers = self.pipe_module._built_layers
        keys = self._layer_keys
        core_idx0 = [d["starts"][0] + j for j in range(d["L"])]
        core_keys = [keys[i] for i in core_idx0]
        prefix, suffix = d["prefix"], d["suffix"]
        pkeys = list(dict.fromkeys(k for i in prefix
                                   if (k := keys[i]) is not None))
        skeys = list(dict.fromkeys(k for i in suffix
                                   if (k := keys[i]) is not None))
        loss_fn = self.pipe_module.loss_fn
        apply_layer = self._apply_layer

        def stage_body(stage_params, x):
            with jax.named_scope("ds_pipe_stage"):
                for j, idx in enumerate(core_idx0):
                    x = (layers[idx](x) if core_keys[j] is None
                         else layers[idx].apply(stage_params[j], x))
            return x

        # remat the stage body: backward recomputes the stage forward per scan step,
        # the same memory/compute trade the instruction executor's jitted VJPs make
        stage_fn = jax.checkpoint(stage_body)

        first_fn = None
        if prefix:
            def first_fn(x, *pvals):
                with jax.named_scope("ds_pipe_first"):
                    env = dict(zip(pkeys, pvals))
                    for idx in prefix:
                        x = apply_layer(idx, env, x)
                return x

        def last_fn(y, labels_all, *rest):
            with jax.named_scope("ds_pipe_last"):
                svals, mb = rest[:-1], rest[-1]
                env = dict(zip(skeys, svals))
                for idx in suffix:
                    y = apply_layer(idx, env, y)
                return loss_fn(y, labels_all[mb])

        def model_fn(params, x_mb, labels_mb):
            last_args = (labels_mb,) + tuple(params[k] for k in skeys)
            lspecs = ((P(*([None, DATA_AXIS] + [None] * (labels_mb.ndim - 2))),)
                      + tuple(P() for _ in skeys))
            return pipeline_apply(
                stage_fn, params[STACKED_KEY], x_mb, mesh=mesh,
                last_stage_fn=last_fn, last_stage_args=last_args,
                first_stage_fn=first_fn,
                first_stage_args=tuple(params[k] for k in pkeys),
                last_stage_args_specs=lspecs,
                first_stage_args_specs=tuple(P() for _ in pkeys))

        return model_fn

    # canonical (layer-keyed) <-> runtime layout for checkpoints; reference parity:
    # pipeline checkpoints reload under a different stage count (module.py:536-567)
    def _map_opt(self, opt, fn, params_treedef):
        def conv(field):
            return (fn(field)
                    if jax.tree_util.tree_structure(field) == params_treedef else field)
        if hasattr(opt, "_fields"):
            return type(opt)(*[conv(f) for f in opt])
        return conv(opt)

    def _ckpt_export(self, tree, kind):
        if not self._spmd:
            return tree
        if kind == "opt":
            return self._map_opt(tree, self._spmd_to_canonical, self._spmd_treedef)
        return self._spmd_to_canonical(tree)

    def _ckpt_import(self, tree, kind):
        if not self._spmd:
            return tree
        if kind == "opt":
            return self._map_opt(tree, self._canonical_to_spmd, self._canonical_treedef)
        return self._canonical_to_spmd(tree)

    def canonical_master_params(self):
        """fp32 master params keyed by layer (the checkpoint representation)
        regardless of executor mode — SPMD mode stores core stages pipe-stacked."""
        return self._ckpt_export(self.master_params, "master")

    def _apply_layer(self, idx: int, params, x):
        layer = self.pipe_module._built_layers[idx]
        key = self._layer_keys[idx]
        spec = self.pipe_module._layer_specs[idx]
        if key is None:
            return layer(x)
        fwd = spec.forward_fn if isinstance(spec, TiedLayerSpec) and spec.forward_fn else None
        if fwd is not None:
            return fwd(layer, params[key], x)
        return layer.apply(params[key], x)

    def _whole_model_fn(self, params, *batch):
        """Sequential full-model apply (eval path / reference semantics; accepts
        either the canonical or the SPMD params layout)."""
        if getattr(self, "_spmd", False) and STACKED_KEY in params:
            params = self._spmd_to_canonical(params)
        x = batch[0]
        for idx in range(self.pipe_module.num_layers()):
            x = self._apply_layer(idx, params, x)
        if self.pipe_module.loss_fn is not None and len(batch) > 1:
            return self.pipe_module.loss_fn(x, batch[1])
        return x

    # ------------------------------------------------------------- stage functions
    def _stage_fn(self, stage_id: int) -> Callable:
        lo, hi = self.pipe_module.parts[stage_id], self.pipe_module.parts[stage_id + 1]
        interval = self.pipe_module.activation_checkpoint_interval

        def run_range(start, end):
            def range_fn(stage_params, x):
                for idx in range(start, end):
                    x = self._apply_layer(idx, stage_params, x)
                return x
            return range_fn

        if interval and interval > 0:
            # remat each interval-sized chunk (reference PipelineModule.forward,
            # pipe/module.py:292-346: exec_range_func wrapped per interval)
            from ..activation_checkpointing.checkpointing import checkpoint_wrapper
            chunks = [(s, min(s + interval, hi)) for s in range(lo, hi, interval)]

            def fn(stage_params, x):
                for start, end in chunks:
                    x = checkpoint_wrapper(run_range(start, end))(stage_params, x)
                return x
            return fn

        return run_range(lo, hi)

    def _stage_param_keys(self, stage_id: int) -> List[str]:
        lo, hi = self.pipe_module.parts[stage_id], self.pipe_module.parts[stage_id + 1]
        keys = []
        for idx in range(lo, hi):
            k = self._layer_keys[idx]
            if k is not None and k not in keys:
                keys.append(k)
        return keys

    def _compile_stage_fns(self):
        self._stage_fwd = []
        self._stage_bwd = []
        self._stage_last_bwd = None
        loss_fn = self.pipe_module.loss_fn
        for s in range(self.num_stages):
            fn = self._stage_fn(s)
            self._stage_fwd.append(jax.jit(fn))

            def bwd(stage_params, x, g, _fn=fn):
                _, vjp = jax.vjp(_fn, stage_params, x)
                dparams, dx = vjp(g)
                return dparams, dx

            self._stage_bwd.append(jax.jit(bwd))

            if s == self.num_stages - 1 and loss_fn is not None:
                def last_bwd(stage_params, x, labels, scale, _fn=fn):
                    # ``scale`` folds 1/micro_batches AND the fp16 loss scale: grads
                    # leave every stage loss-scaled (the dx flowing upstream carries
                    # the factor), and _jit_apply_update unscales by cur_scale with
                    # the overflow check intact (reference loss_scaler.py:51-53).
                    def f(p, xx):
                        return loss_fn(_fn(p, xx), labels) * scale
                    loss, (dparams, dx) = jax.value_and_grad(f, argnums=(0, 1))(stage_params, x)
                    return loss / scale, dparams, dx

                self._stage_last_bwd = jax.jit(last_bwd)

                def last_eval(stage_params, x, labels, _fn=fn):
                    return loss_fn(_fn(stage_params, x), labels)

                self._stage_last_eval = jax.jit(last_eval)

    # ------------------------------------------------------------------ lint hooks
    def lint_programs(self, sample_batch):
        """Pipeline manifests for the lint suite (docs/lint.md).

        SPMD path: the base-engine programs, with the forward/backward budget
        extended by the collective-permute traffic that moves activations over
        the pipe axis (the reference's p2p.send/recv). Instruction-executor
        path: the per-stage jits are LOCAL programs — zero large collectives
        is the invariant — chained through ``jax.eval_shape`` so each stage's
        input aval is the previous stage's output.
        """
        if self._spmd:
            progs = []
            for name, jitted, args, man in super().lint_programs(sample_batch):
                if name in ("loss_and_grad", "fused_step"):
                    man = dict(man)
                    coll = dict(man.get("collectives", {}))
                    coll["collective-permute"] = {"min": 1}
                    man["collectives"] = coll
                progs.append((name, jitted, args, man))
            return progs

        compute = self._lint_dtype_name(self.compute_dtype)
        local_man = {"compute_dtype": compute, "strict": True,
                     "donation": {"check_unusable": True}}
        x = sample_batch[0]
        labels = sample_batch[1] if len(sample_batch) > 1 else None

        def sds(a):
            a = np.asarray(a)
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        scale = self.scaler_state.cur_scale
        progs = []
        x_in = sds(x)
        for s in range(self.num_stages):
            p_s = self._select_params(s)
            last = s == self.num_stages - 1
            progs.append((f"stage{s}_fwd", self._stage_fwd[s], (p_s, x_in),
                          dict(local_man)))
            x_out = jax.eval_shape(self._stage_fwd[s], p_s, x_in)
            if last and self._stage_last_bwd is not None and labels is not None:
                progs.append((f"stage{s}_last_bwd", self._stage_last_bwd,
                              (p_s, x_in, sds(labels), scale), dict(local_man)))
            else:
                progs.append((f"stage{s}_bwd", self._stage_bwd[s],
                              (p_s, x_in, x_out), dict(local_man)))
            x_in = x_out
        return progs

    def memory_manifest(self):
        """SPMD path: the base-engine manifest (the step programs are the
        base programs). Instruction-executor path: the per-stage jits are
        LOCAL programs, so the live param working set of any one program is
        the largest stage subtree, not the full tree — the manifest keeps the
        full tree for classification (every stage's leaves must classify as
        params) and declares the per-stage maximum for the model."""
        if self._spmd:
            return super().memory_manifest()
        from ...utils import hbm as _hbm
        stage_bytes = []
        for s in range(self.num_stages):
            leaves = jax.tree_util.tree_leaves(self._select_params(s))
            stage_bytes.append(sum(_hbm.leaf_signature(l)[2] for l in leaves))
        return {
            "classes": {"params": self.params},
            "geometry": {"kind": "pipeline_local",
                         "num_stages": int(self.num_stages),
                         "stage_param_bytes_max": max(stage_bytes, default=0)},
        }

    # ------------------------------------------------------------- blocked base API
    def forward(self, *args, **kwargs):
        raise PipelineError("Only train_batch() is accessible in pipeline mode.")

    def backward(self, *args, **kwargs):
        raise PipelineError("Only train_batch() is accessible in pipeline mode.")

    def step(self, *args, **kwargs):
        raise PipelineError("Only train_batch() is accessible in pipeline mode.")

    # ------------------------------------------------------------- train/eval
    def _next_micro_batch(self, data_iter):
        batch = next(data_iter)
        if isinstance(batch, (tuple, list)):
            return tuple(self.shard_batch(b) for b in batch)
        return (self.shard_batch(batch),)

    def _stack_window(self, data_iter):
        """Pull the accumulation window's micro-batches and stack them on a leading
        M axis, sharded over ``data`` on the batch dim (dim 1) — the layout
        pipeline_apply streams through the scan."""
        xs, ys = [], []
        for _ in range(self.micro_batches):
            batch = next(data_iter)
            if not (isinstance(batch, (tuple, list)) and len(batch) >= 2):
                raise PipelineError(
                    "SPMD pipeline mode expects (inputs, labels) batches; pass "
                    '{"pipeline": {"spmd": false}} for the instruction executor')
            xs.append(np.asarray(batch[0]))
            ys.append(np.asarray(batch[1]))

        def put(a):
            spec = P(*([None, DATA_AXIS] + [None] * (a.ndim - 2)))
            return jax.device_put(a, NamedSharding(self.mesh, spec))

        return put(np.stack(xs)), put(np.stack(ys))

    def _train_batch_spmd(self, data_iter):
        """One optimizer step: the ENTIRE micro-batch window runs inside one jitted
        forward/backward (scan + ppermute over the pipe axis of the mesh); the base
        engine's fp16/ZeRO/monitoring machinery applies unchanged."""
        x, y = self._stack_window(data_iter)
        loss = DeepSpeedEngine.forward(self, x, y)
        DeepSpeedEngine.backward(self, loss)
        DeepSpeedEngine.step(self)
        self.agg_train_loss = loss
        return loss

    def train_batch(self, data_iter=None):
        """Run one full micro-batch window to an optimizer step (reference
        pipe/engine.py:229-303): the SPMD scan executor when routed there, else the
        1F1B instruction stream."""
        if data_iter is None:
            if self.training_dataloader is None:
                raise PipelineError("train_batch() requires a data iterator or training_data")
            if not hasattr(self, "_repeating_iter"):
                from ..dataloader import RepeatingLoader
                self._repeating_iter = iter(RepeatingLoader(self.training_dataloader))
            data_iter = self._repeating_iter
        if self._spmd:
            return self._train_batch_spmd(data_iter)

        if self.telemetry is not None:
            self.telemetry.on_step_begin(self.global_steps)
        # goodput: construction -> first train step is the init interval
        self._goodput_close_init()
        tracer = self.pipe_trace
        mb = self.micro_batches
        S = self.num_stages
        scheds = [schedule.TrainSchedule(micro_batches=mb, stages=S, stage_id=s)
                  for s in range(S)]
        streams = [list(iter(sc)) for sc in scheds]
        ring_size = [sc.num_pipe_buffers() for sc in scheds]  # see _assert_ring_bound

        act_in = [dict() for _ in range(S)]    # stage -> buffer_id -> input activation
        act_out = [dict() for _ in range(S)]   # stage -> buffer_id -> output activation
        dx_buf = [dict() for _ in range(S)]    # stage -> buffer_id -> input-grad to send back
        grad_in = [dict() for _ in range(S)]   # stage -> buffer_id -> received output-grad
        # Channels are keyed by (sending stage, micro-batch id): adjacent stages size their
        # buffer rings differently (num_pipe_buffers is per-stage), so receiver-local buffer
        # ids do NOT line up across stages. Micro-batch ids are globally consistent; each
        # stage forwards/retires/receives micro-batches strictly in order.
        chan_act = {}
        chan_grad = {}
        in_mb = [dict() for _ in range(S)]     # stage -> buffer_id -> micro-batch id
        labels_by_mb = {}
        fwd_count = [0] * S
        bwd_count = [0] * S
        recv_act_count = [0] * S
        recv_grad_count = [0] * S
        micro_losses = []
        grads_total: Optional[Dict[str, Any]] = None
        # fold the fp16 loss scale into the per-micro-batch factor (weak-spot fix:
        # stage backwards must produce loss-scaled grads for the overflow machinery
        # in _jit_apply_update to mean anything under fp16)
        scale = jnp.asarray(1.0 / mb, jnp.float32)
        if self.fp16_enabled():
            scale = scale * self.scaler_state.cur_scale

        breakdown = self.wall_clock_breakdown()
        _TIMER_BY_CMD = {
            schedule.LoadMicroBatch: "batch_input",
            schedule.ForwardPass: "forward_microstep",
            schedule.BackwardPass: "backward_microstep",
            schedule.SendActivation: "pipe_send_output",
            schedule.RecvActivation: "pipe_recv_input",
            schedule.SendGrad: "pipe_send_grad",
            schedule.RecvGrad: "pipe_recv_grad",
            schedule.OptimizerStep: "step_microstep",
        }
        if breakdown:
            self.timers("train_batch").start()

        def merge_grads(total, delta):
            if total is None:
                return dict(delta)
            merged = dict(total)
            for k, v in delta.items():
                merged[k] = (jax.tree_util.tree_map(lambda a, b: a + b, merged[k], v)
                             if k in merged else v)
            return merged

        def exec_cmd(s, cmd):
            nonlocal grads_total
            if isinstance(cmd, schedule.LoadMicroBatch):
                if s == 0:
                    batch = self._next_micro_batch(data_iter)
                    act_in[0][cmd.buffer_id] = batch[0]
                    in_mb[0][cmd.buffer_id] = fwd_count[0]
                    labels_by_mb[fwd_count[0]] = batch[1] if len(batch) > 1 else None
                # last stage: labels were stashed when stage 0 loaded this micro-batch
            elif isinstance(cmd, schedule.ForwardPass):
                x = act_in[s].pop(cmd.buffer_id)
                mb_id = in_mb[s][cmd.buffer_id]
                act_in[s][("saved", cmd.buffer_id)] = x
                if s < S - 1 or self.pipe_module.loss_fn is None:
                    act_out[s][cmd.buffer_id] = (mb_id, self._stage_fwd[s](self._select_params(s), x))
                fwd_count[s] += 1
            elif isinstance(cmd, schedule.SendActivation):
                mb_id, payload = act_out[s].pop(cmd.buffer_id)
                chan_act[(s, mb_id)] = payload
                _assert_ring_bound(chan_act, s, ring_size[s + 1], "activation")
            elif isinstance(cmd, schedule.RecvActivation):
                mb_id = recv_act_count[s]
                recv_act_count[s] += 1
                act_in[s][cmd.buffer_id] = chan_act.pop((s - 1, mb_id))
                in_mb[s][cmd.buffer_id] = mb_id
            elif isinstance(cmd, schedule.BackwardPass):
                x = act_in[s].pop(("saved", cmd.buffer_id))
                mb_id = in_mb[s].pop(cmd.buffer_id)
                if s == S - 1 and self.pipe_module.loss_fn is not None:
                    labels = labels_by_mb[mb_id]
                    loss, dparams, dx = self._stage_last_bwd(self._select_params(s), x, labels, scale)
                    micro_losses.append(loss)
                else:
                    g = grad_in[s].pop(cmd.buffer_id)
                    dparams, dx = self._stage_bwd[s](self._select_params(s), x, g)
                grads_total = merge_grads(grads_total, dparams)
                if s > 0:
                    dx_buf[s][cmd.buffer_id] = (mb_id, dx)
                bwd_count[s] += 1
            elif isinstance(cmd, schedule.SendGrad):
                mb_id, payload = dx_buf[s].pop(cmd.buffer_id)
                chan_grad[(s, mb_id)] = payload
                _assert_ring_bound(chan_grad, s, ring_size[s - 1], "grad")
            elif isinstance(cmd, schedule.RecvGrad):
                mb_id = recv_grad_count[s]
                recv_grad_count[s] += 1
                grad_in[s][cmd.buffer_id] = chan_grad.pop((s + 1, mb_id))
            elif isinstance(cmd, (schedule.ReduceTiedGrads, schedule.ReduceGrads)):
                pass  # tied grads summed in merge_grads; DP reduce emitted by XLA
            elif isinstance(cmd, schedule.OptimizerStep):
                if s == 0:
                    self._pipeline_optimizer_step(grads_total)

        def timed_exec(s, cmd):
            name = _TIMER_BY_CMD.get(type(cmd)) if breakdown else None
            if name is None:
                exec_cmd(s, cmd)
                return
            self.timers(name).start()
            exec_cmd(s, cmd)
            self.timers(name).stop()

        def trace_mb(s, cmd):
            # best-effort micro-batch attribution from the live buffer state
            # (read BEFORE exec_cmd mutates it; Load/Recv use their counters)
            if isinstance(cmd, schedule.LoadMicroBatch):
                return fwd_count[s]
            if isinstance(cmd, (schedule.ForwardPass, schedule.BackwardPass)):
                return in_mb[s].get(cmd.buffer_id)
            if isinstance(cmd, schedule.SendActivation):
                return (act_out[s].get(cmd.buffer_id) or (None,))[0]
            if isinstance(cmd, schedule.SendGrad):
                return (dx_buf[s].get(cmd.buffer_id) or (None,))[0]
            if isinstance(cmd, schedule.RecvActivation):
                return recv_act_count[s]
            if isinstance(cmd, schedule.RecvGrad):
                return recv_grad_count[s]
            return None

        def traced_exec(s, cmd, step_id):
            if tracer is None:
                timed_exec(s, cmd)
                return
            mb_id = trace_mb(s, cmd)
            t0 = time.perf_counter()
            timed_exec(s, cmd)
            tracer.record(s, step_id, cmd.name, mb_id,
                          getattr(cmd, "buffer_id", None), t0, time.perf_counter())

        if tracer is not None:
            tracer.begin_step(self.global_steps, "TrainSchedule", mb)
        self._run_streams(streams, traced_exec)
        goodput = tracer.end_step() if tracer is not None else None

        self.agg_train_loss = jnp.mean(jnp.stack(micro_losses)) if micro_losses else None
        self.global_steps += 1
        self.micro_steps += mb
        pending_losses = [self.agg_train_loss] if self.agg_train_loss is not None else None
        numerics_host = None
        if self.telemetry is not None:
            numerics_host = self.telemetry.end_step(
                self.global_steps, self.train_batch_size(),
                pending=pending_losses, numerics=self._pending_sentinel,
                schedule_goodput=goodput,
                run_goodput=self._goodput_scalars())
        elif self._pending_sentinel is not None:
            numerics_host = jax.device_get(self._pending_sentinel)
        if self._numerics is not None:
            self._commit_numerics(numerics_host,
                                  getattr(self, "_pipe_overflowed", False),
                                  pending_losses or [])
        self._goodput_close_train_step()
        if breakdown:
            self.timers("train_batch").stop()
            if self.global_steps % self.steps_per_print() == 0:
                # per-instruction wall-clock buckets (reference pipe/engine.py:964-984)
                self.timers.log(["batch_input", "forward_microstep", "backward_microstep",
                                 "pipe_send_output", "pipe_recv_input", "pipe_send_grad",
                                 "pipe_recv_grad", "step_microstep", "train_batch"],
                                reset=True)
        if self.global_steps == 1 or self.global_steps % self.steps_per_print() == 0:
            self._report_progress(self.global_steps)
        return self.agg_train_loss

    @staticmethod
    def _run_streams(streams, exec_cmd):
        """Execute per-stage instruction streams merged by step index. Within one
        merged step all Sends/Loads run before any Recv — the scheduling invariant
        that lets the reference's blocking p2p broadcasts rendezvous (its even/odd
        orderings serialize to exactly this). ``exec_cmd`` receives the merged
        step index so the pipeline tracer can stamp spans with their schedule
        position."""
        S = len(streams)
        for step_id in range(len(streams[0])):
            for s in range(S):
                for cmd in streams[s][step_id]:
                    if isinstance(cmd, _SEND_CMDS):
                        exec_cmd(s, cmd, step_id)
            for s in range(S):
                for cmd in streams[s][step_id]:
                    if not isinstance(cmd, _SEND_CMDS):
                        exec_cmd(s, cmd, step_id)

    def _select_params(self, stage_id):
        return {k: self.params[k] for k in self._stage_param_keys(stage_id)}

    def _pipeline_optimizer_step(self, grads_total):
        full_grads = {}
        for k, p in self.master_params.items():
            if grads_total is not None and k in grads_total:
                full_grads[k] = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32),
                                                       grads_total[k])
            else:
                full_grads[k] = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, jnp.float32), p)
        hyper = self.optimizer.current_hyper()
        step = jnp.asarray(self.global_steps + 1 - self.skipped_steps, jnp.int32)
        outs = self._jit_apply_update(
            self.master_params, self.opt_state, self.scaler_state, full_grads,
            self.params, step, hyper)
        if self._sentinel_index is not None:
            (self.master_params, self.opt_state, self.scaler_state, self.params,
             overflow, self._last_grad_norm, self._pending_sentinel) = outs
        else:
            (self.master_params, self.opt_state, self.scaler_state, self.params,
             overflow, self._last_grad_norm) = outs
        self._pipe_overflowed = False
        if self.fp16_enabled() and bool(jax.device_get(overflow)):
            # jit already skipped the master update and backed off the scale; mirror
            # the host-side accounting (reference _take_model_step overflow branch)
            self._pipe_overflowed = True
            self.skipped_steps += 1
            logger.info("[deepspeed_tpu] OVERFLOW! Skipping pipeline step.")
        elif self.lr_scheduler is not None:
            self.lr_scheduler.step()

    def eval_batch(self, data_iter):
        """Forward-only evaluation executing the InferenceSchedule instruction stream
        through the per-stage jitted forwards (reference pipe/engine.py:305-372 runs
        InferenceSchedule through _exec_schedule; the two-buffer ring and the even/odd
        send/recv ordering of schedule.InferenceSchedule are preserved). SPMD mode
        evaluates the same jitted pipeline forward loss-only."""
        if self._spmd:
            x, y = self._stack_window(data_iter)
            self._goodput_begin_eval()
            loss = self._jit_eval(self.params, x, y)
            self._goodput_end_eval()
            return loss
        tracer = self.pipe_trace
        self._goodput_begin_eval()
        mb = self.micro_batches
        S = self.num_stages
        scheds = [schedule.InferenceSchedule(micro_batches=mb, stages=S, stage_id=s)
                  for s in range(S)]
        streams = [list(iter(sc)) for sc in scheds]
        ring_size = [sc.num_pipe_buffers() for sc in scheds]  # two-buffer ring

        act_in = [dict() for _ in range(S)]    # stage -> buffer_id -> input activation
        act_out = [dict() for _ in range(S)]   # stage -> buffer_id -> output activation
        chan_act = {}                           # (sending stage, mb id) -> payload
        in_mb = [dict() for _ in range(S)]     # stage -> buffer_id -> micro-batch id
        labels_by_mb = {}
        load_count = [0] * S
        recv_act_count = [0] * S
        micro_losses = []

        def exec_cmd(s, cmd):
            if isinstance(cmd, schedule.LoadMicroBatch):
                mb_id = load_count[s]
                load_count[s] += 1
                if s == 0:
                    batch = self._next_micro_batch(data_iter)
                    act_in[0][cmd.buffer_id] = batch[0]
                    in_mb[0][cmd.buffer_id] = mb_id
                    labels_by_mb[mb_id] = batch[1] if len(batch) > 1 else None
                # last stage: its LoadMicroBatch picks up the labels stage 0 stashed
                # (the reference's first/last stages share the data loader)
            elif isinstance(cmd, schedule.ForwardPass):
                x = act_in[s].pop(cmd.buffer_id)
                mb_id = in_mb[s].pop(cmd.buffer_id)
                if s == S - 1 and self.pipe_module.loss_fn is not None:
                    micro_losses.append(
                        self._stage_last_eval(self._select_params(s), x, labels_by_mb[mb_id]))
                else:
                    out = self._stage_fwd[s](self._select_params(s), x)
                    if s == S - 1:
                        micro_losses.append(out)
                    else:
                        act_out[s][cmd.buffer_id] = (mb_id, out)
            elif isinstance(cmd, schedule.SendActivation):
                mb_id, payload = act_out[s].pop(cmd.buffer_id)
                chan_act[(s, mb_id)] = payload
                _assert_ring_bound(chan_act, s, ring_size[s + 1], "activation")
            elif isinstance(cmd, schedule.RecvActivation):
                mb_id = recv_act_count[s]
                recv_act_count[s] += 1
                act_in[s][cmd.buffer_id] = chan_act.pop((s - 1, mb_id))
                in_mb[s][cmd.buffer_id] = mb_id

        def trace_mb(s, cmd):
            if isinstance(cmd, schedule.LoadMicroBatch):
                return load_count[s]
            if isinstance(cmd, schedule.ForwardPass):
                return in_mb[s].get(cmd.buffer_id)
            if isinstance(cmd, schedule.SendActivation):
                return (act_out[s].get(cmd.buffer_id) or (None,))[0]
            if isinstance(cmd, schedule.RecvActivation):
                return recv_act_count[s]
            return None

        def traced_exec(s, cmd, step_id):
            if tracer is None:
                exec_cmd(s, cmd)
                return
            mb_id = trace_mb(s, cmd)
            t0 = time.perf_counter()
            exec_cmd(s, cmd)
            tracer.record(s, step_id, cmd.name, mb_id,
                          getattr(cmd, "buffer_id", None), t0, time.perf_counter())

        if tracer is not None:
            tracer.begin_step(self.global_steps, "InferenceSchedule", mb, kind="eval")
        self._run_streams(streams, traced_exec)
        if tracer is not None:
            tracer.end_step()
        self._goodput_end_eval()
        return jnp.mean(jnp.stack(micro_losses))
