"""Activation checkpointing (rematerialization) with partitioned / host-offloaded
saveables and deterministic RNG.

TPU-native analog of ``deepspeed/runtime/activation_checkpointing/checkpointing.py``
(746 LoC, Megatron-derived). The reference re-ran forward in backward with exact
CPU+CUDA RNG restore (CudaRNGStatesTracker, l.147-223), optionally narrowed saved
input activations to 1/mp_size per rank (l.265-311) and moved them to CPU
(``PA_TO_CPU``, l.370-413). Under JAX each concern collapses into existing machinery:

- recompute-in-backward       → ``jax.checkpoint`` (this module adds the config layer)
- exact RNG restore           → free: PRNG keys are explicit values, so the remat
                                replay is bit-identical by construction; the
                                ``RNGTracker`` here exists for Megatron-API parity
- partition_activations       → sharding constraints on the wrapped function's inputs
                                over the ``model`` mesh axis; GSPMD all-gathers them
                                back in backward exactly like l.281-311
- cpu_checkpointing (PA_TO_CPU) → ``save_and_offload_only_these_names`` policy moving
                                named residuals to ``pinned_host`` memory
- contiguous_memory/profile   → accepted for config parity; XLA owns memory layout,
                                profiling maps to named-scope annotations
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ...utils import logger

# Name tag for residuals this module saves/offloads.
_ACT_NAME = "ds_activation"

# module-level config, set by configure() (reference checkpointing.py:654-700)
_config = {
    "partition_activations": False,
    "cpu_checkpointing": False,
    "contiguous_memory_optimization": False,
    "number_checkpoints": None,
    "synchronize": False,
    "profile": False,
    "model_axis": "model",
    "mesh": None,
    "mesh_explicit": False,
    "configured": False,
}


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None, checkpoint_in_cpu=None,
              synchronize=None, profile=None, mesh=None, model_axis: Optional[str] = None):
    """Configure the module (reference checkpointing.py:654-700). Accepts either a
    DeepSpeedConfig (uses its activation_checkpointing block) or explicit flags."""
    if deepspeed_config is not None:
        ac = deepspeed_config.activation_checkpointing_config
        _config["partition_activations"] = ac.partition_activations
        _config["cpu_checkpointing"] = ac.cpu_checkpointing
        _config["contiguous_memory_optimization"] = ac.contiguous_memory_optimization
        _config["number_checkpoints"] = ac.number_checkpoints
        _config["synchronize"] = ac.synchronize_checkpoint_boundary
        _config["profile"] = ac.profile
    for key, val in (("partition_activations", partition_activations),
                     ("contiguous_memory_optimization", contiguous_checkpointing),
                     ("number_checkpoints", num_checkpoints),
                     ("cpu_checkpointing", checkpoint_in_cpu),
                     ("synchronize", synchronize),
                     ("profile", profile)):
        if val is not None:
            _config[key] = val
    if mesh is not None:
        _config["mesh"] = mesh
        _config["mesh_explicit"] = True
    if model_axis is not None:
        _config["model_axis"] = model_axis
    _config["configured"] = True
    logger.info(f"[deepspeed_tpu] activation checkpointing configured: "
                f"partition={_config['partition_activations']} "
                f"cpu={_config['cpu_checkpointing']} num={_config['number_checkpoints']}")


def set_default_mesh(mesh, model_axis: Optional[str] = None):
    """Publish a mesh for the partition constraint without flipping any flags or marking
    the module configured. The engine calls this so a later Megatron-style
    ``configure(partition_activations=True)`` — which has no mesh parameter — still
    shards saveables over the model axis instead of silently no-opping. Latest engine
    wins (a discarded engine's mesh must not linger), but a mesh passed explicitly to
    ``configure(mesh=...)`` is never overridden."""
    if not _config.get("mesh_explicit"):
        _config["mesh"] = mesh
        if model_axis is not None:
            _config["model_axis"] = model_axis


def is_configured() -> bool:
    return _config["configured"]


def cpu_checkpointing_enabled() -> bool:
    return bool(_config["cpu_checkpointing"])


def reset():
    """Reference checkpointing.py reset() dropped the contiguous buffers; here it
    just restores defaults."""
    _config.update(partition_activations=False, cpu_checkpointing=False,
                   contiguous_memory_optimization=False, number_checkpoints=None,
                   synchronize=False, profile=False, mesh=None, model_axis="model",
                   configured=False, mesh_explicit=False)


def _offload_policy():
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=[],
        names_which_can_be_offloaded=[_ACT_NAME],
        offload_src="device", offload_dst="pinned_host")


def _partition_constraint(x: jnp.ndarray):
    """Shard a saveable over the model axis along its largest divisible dim
    (reference narrowed saved activations to 1/mp_size per rank, l.265-311).
    Inside jit, GSPMD inserts the gather on the backward replay."""
    mesh = _config["mesh"]
    axis = _config["model_axis"]
    if mesh is None or axis not in mesh.shape or mesh.shape[axis] <= 1 or x.ndim == 0:
        return x
    mp = mesh.shape[axis]
    from jax.sharding import NamedSharding, PartitionSpec as P
    for dim in sorted(range(x.ndim), key=lambda d: -x.shape[d]):
        if x.shape[dim] % mp == 0:
            spec = [None] * x.ndim
            spec[dim] = axis
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
    return x


def _flash_policy(exclude="qkv", keep_qkv=False):
    """Replay-free attention remat policies: save the flash kernel's named
    residuals (out, lse) plus no-batch-dims dots, minus a width-signature-chosen
    exclusion that funds the attention saves in HBM.

    Measured at GPT-2 1.5B, batch 8, one v5e (PERF.md round-5 remat table):
    'dots' replays the flash fwd kernel in backward (the custom_vjp residuals
    are not dots) and plain 'dots+attn' overshoots HBM by ~60 MB. Exclusions by
    2-D-rhs width signature (unique among the transformer's dots):
    - "qkv" (policy 'flash'): rhs [E, 3E] — frees 3E per layer (3.7 GB) but the
      replay re-runs the widest projection;
    - "square" (policy 'dots+attn-lean'): rhs [E, E], the attention output
      projection — frees E per layer (1.25 GB) and the replay is one cheap dot
      whose input (attn_out) is itself saved.

    Dot classification is tag-first: attention call sites announce their dots by
    emitting ``checkpoint_name(x, "ds_dot:qkv")`` / ``"ds_dot:proj"`` on the
    dot's INPUT immediately before the dot (gpt2 ``_attention`` and the fused
    transformer kernel do). The jaxpr records equations in trace order, so the
    announcement reaches this policy before its dot_general; once ANY ``ds_dot``
    tag is seen in a trace the width heuristic below is OFF and only announced
    dots can be excluded — a square MoE expert or a 3E-wide vocab head in a
    tagged model can no longer be misclassified.

    UNTAGGED FALLBACK: models that never announce keep the pure shape-based
    classification, which is only sound when each width signature is UNIQUE
    among the model's dots. Each returned policy instance tracks the distinct
    (contracted, out) rhs shapes it excludes across its trace and raises instead
    of misclassifying: a second distinct shape in the same exclusion class, or a
    square width that disagrees with the qkv-implied embed width, is an error
    directing the caller to tags or an explicit policy."""
    names = jax.checkpoint_policies.save_only_these_names("attn_out", "attn_lse")
    # per-instance (== per checkpoint_wrapper call, i.e. per trace) signature log:
    # class name -> set of distinct (contracted, out_w) rhs shapes observed. qkv
    # signatures are recorded even when kept so the square check can cross-validate
    # against the qkv-implied embed width.
    seen = {"qkv": set(), "square": set()}
    # tag-gating state: 'tagged' flips on the first ds_dot announcement; each
    # announcement queues (class, input-shape) until its dot_general consumes it
    # (shape-matched so unrelated interleaved dots pass through untouched).
    tag_state = {"tagged": False, "pending": []}

    def _record(cls, shape, excluding):
        seen[cls].add(shape)
        if excluding and len(seen[cls]) > 1:
            raise ValueError(
                f"remat policy width-signature collision: {sorted(seen[cls])} both "
                f"classify as the '{cls}' exclusion — the shape heuristic cannot "
                f"tell them apart, so one would silently lose its save. Pass an "
                f"explicit jax.checkpoint_policies callable (or use 'dots+attn') "
                f"for this model.")
        if exclude == "square" and seen["qkv"] and seen["square"]:
            e_widths = {c for c, _ in seen["qkv"]}
            for e_sq, _ in seen["square"]:
                if e_sq not in e_widths:
                    raise ValueError(
                        f"remat policy width-signature collision: square dot "
                        f"[{e_sq}, {e_sq}] does not match the fused-qkv embed "
                        f"width(s) {sorted(e_widths)}, so it is not the attention "
                        f"output projection (an MoE/router square?) and would "
                        f"silently lose its save. Pass an explicit "
                        f"jax.checkpoint_policies callable (or use 'dots+attn') "
                        f"for this model.")

    def eff_policy(prim, *avals, **params):
        if names(prim, *avals, **params):
            return True
        pname = getattr(prim, "name", "")
        if pname == "name":
            tag = str(params.get("name", ""))
            if tag.startswith("ds_dot:"):
                tag_state["tagged"] = True
                cls = tag.split(":", 2)[1]
                shape = tuple(getattr(avals[0], "shape", ())) if avals else ()
                tag_state["pending"].append((cls, shape))
            return False
        if pname != "dot_general":
            return False
        (lc, rc), (lb, rb) = params["dimension_numbers"]
        if lb or rb:
            return False
        if tag_state["tagged"]:
            # tag-gated mode: only announced dots may be excluded. The pending
            # announcement is consumed by the first dot whose lhs matches the
            # tagged input's shape (trace order puts it right after the tag).
            pending = tag_state["pending"]
            lhs_shape = tuple(getattr(avals[0], "shape", ())) if avals else ()
            if pending and pending[0][1] == lhs_shape:
                cls, _ = pending.pop(0)
                if cls == "qkv" and not keep_qkv:
                    return False  # fused-qkv projection: recompute, don't save
                if cls == "proj" and exclude == "square":
                    return False  # attn output projection: recompute from attn_out
            return True
        if len(avals) >= 2 and getattr(avals[1], "ndim", 0) == 2 and len(rc) == 1:
            rhs = avals[1]
            contracted, out_w = rhs.shape[rc[0]], rhs.shape[1 - rc[0]]
            if out_w == 3 * contracted:
                _record("qkv", (contracted, out_w), excluding=not keep_qkv)
                if not keep_qkv:
                    return False  # fused-qkv projection: recompute, don't save
            if exclude == "square" and out_w == contracted:
                _record("square", (contracted, out_w), excluding=True)
                return False  # attention output projection: recompute from attn_out
        return True

    return eff_policy


def checkpoint_wrapper(fn, policy=None):
    """Wrap ``fn(*args)`` so its forward is rematerialized in backward, honoring the
    configured saveable placement. The TPU analog of CheckpointFunction
    (reference checkpointing.py:314-576).

    ``policy`` selects what escapes recompute: None saves only the block inputs (full
    remat, the reference's semantics); ``"dots"`` additionally saves matmul outputs
    (``dots_with_no_batch_dims_saveable``) so backward replays only cheap elementwise
    ops — the sweet spot on TPU where HBM is larger relative to flops than the
    reference's V100s and full recompute wastes MXU cycles. A configured
    ``checkpoint_in_cpu`` overrides ``policy`` with the host-offload policy."""

    @functools.wraps(fn)
    def inner(*args):
        # Tag+place the block inputs: they are the residuals jax.checkpoint saves.
        def placed(*inner_args):
            processed = []
            for a in inner_args:
                if isinstance(a, jnp.ndarray) and jnp.issubdtype(a.dtype, jnp.inexact):
                    if _config["cpu_checkpointing"]:
                        a = checkpoint_name(a, _ACT_NAME)
                    if _config["partition_activations"]:
                        a = _partition_constraint(a)
                processed.append(a)
            return fn(*processed)

        if _config["cpu_checkpointing"]:
            eff_policy = _offload_policy()
        elif policy == "dots":
            eff_policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif policy == "attn":
            # save only attention OUTPUTS ("attn_out"/"attn_lse": the flash kernel's forward rule names them, most models
            # again at the call): backward skips replaying the flash kernel — the priciest recompute — for one [B, H, T]
            # and one [B, T, E] residual per layer and NAME (two with a model's own tag: compiled for a v5e, PR 38)
            eff_policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "attn_lse")
        elif policy == "dots+attn":
            # dots AND the flash kernel's (out, lse): backward replays ONLY cheap
            # elementwise ops (layernorm/gelu/adds) — the kernel's own residuals
            # (q,k,v) are saved dots, out/lse are the named saves, so the flash
            # bwd kernels run with zero fwd-kernel replay. The extra HBM over 'dots' is one [B,H,T] per layer and one
            # [B,T,E] for each name on the kernel's output: the forward rule's and, in every model but Ouro, the caller's.
            eff_policy = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names("attn_out", "attn_lse"))
        elif policy == "flash":
            eff_policy = _flash_policy()
        elif policy == "dots+attn-lean":
            # dots+attn minus the SQUARE-rhs dots (the attention output
            # projection, rhs [E, E]): its replay is ONE cheap dot from the
            # saved attn_out, and dropping the save frees a [B, T, E] per layer
            # (1.25 GB at 1.5B/batch 8) — the margin that lets the replay-free
            # attention saves fit in HBM (see PERF.md round-5 remat table)
            eff_policy = _flash_policy(exclude="square", keep_qkv=True)
        elif policy is None or callable(policy):
            eff_policy = policy
        else:
            raise ValueError(f"unknown remat policy {policy!r}: expected None, 'dots', "
                             f"'attn', 'dots+attn', 'dots+attn-lean', 'flash', or a "
                             f"jax.checkpoint_policies callable")
        ckpt = jax.checkpoint(placed, policy=eff_policy)
        if _config["profile"]:
            with jax.named_scope("ds_activation_checkpoint"):
                return ckpt(*args)
        return ckpt(*args)

    return inner


def checkpoint(function, *args):
    """Reference-style call: ``checkpoint(run_function, *args)``
    (checkpointing.py:739-746)."""
    return checkpoint_wrapper(function)(*args)


# ---------------------------------------------------------------------------
# RNG parity API (reference CudaRNGStatesTracker, checkpointing.py:147-223).
# JAX PRNG keys are explicit, so remat replay is deterministic with zero effort;
# this tracker exists so Megatron-style callers keep working.
# ---------------------------------------------------------------------------

class RNGTracker:
    """Named PRNG streams. ``fork(name)`` returns a fresh subkey each call;
    inside a remat replay the same sequence is regenerated bit-identically
    because the stream state is a pure value captured in the trace."""

    def __init__(self):
        self._keys = {}

    def reset(self):
        self._keys = {}

    def get_states(self):
        return dict(self._keys)

    def set_states(self, states):
        self._keys = dict(states)

    def add(self, name: str, seed: int):
        if name in self._keys:
            raise ValueError(f"RNG state {name} already exists")
        self._keys[name] = jax.random.PRNGKey(seed)

    def fork(self, name: str = "model-parallel-rng"):
        if name not in self._keys:
            raise KeyError(f"RNG state {name} not added")
        self._keys[name], sub = jax.random.split(self._keys[name])
        return sub


_RNG_TRACKER = RNGTracker()


def get_rng_tracker() -> RNGTracker:
    return _RNG_TRACKER


# reference alias (checkpointing.py:218)
get_cuda_rng_tracker = get_rng_tracker


def model_parallel_seed(seed: int, axis: Optional[str] = None):
    """Per-model-parallel-rank PRNG key (reference model_parallel_cuda_manual_seed,
    checkpointing.py:223-262): dropout must differ across TP ranks while staying
    reproducible. Call inside shard_map/jit with the mesh axis bound; outside a
    bound axis it returns the base key."""
    key = jax.random.PRNGKey(seed)
    axis = axis or _config["model_axis"]
    try:
        idx = jax.lax.axis_index(axis)
    except NameError:
        return key
    return jax.random.fold_in(key, idx)


def model_parallel_cuda_manual_seed(seed: int):
    """Parity shim: seeds the tracker's default streams (reference l.223-262)."""
    _RNG_TRACKER.reset()
    _RNG_TRACKER.add("model-parallel-rng", seed + 2718)
    _RNG_TRACKER.add("data-parallel-rng", seed)
