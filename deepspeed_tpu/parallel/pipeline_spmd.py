"""SPMD pipeline parallelism: the multi-chip pipe-axis executor.

This is the TPU-native execution path for pipeline parallelism, replacing the reference's
per-stage processes + blocking p2p broadcasts (``deepspeed/runtime/pipe/p2p.py``) with a
single jitted program over the mesh:

- stage weights are *stacked* along a leading axis sharded over ``pipe`` — each device
  holds only its stage's parameters (true pipeline memory scaling, unlike replication);
- micro-batches stream through ``jax.lax.scan``; stage→stage transfer is a single
  ``lax.ppermute`` over the ``pipe`` axis riding ICI (reference p2p.send/recv);
- the loop is **differentiable**: ``jax.grad`` of the scan yields the reverse pipeline
  (ppermute transposes to the reverse ring), so the backward schedule needs no separate
  instruction stream — XLA derives it. Combined with ``jax.checkpoint`` on the stage
  body, activation memory matches GPipe (inputs-per-microbatch only);
- the data axis composes orthogonally: micro-batches stay sharded over ``data``, so DP
  gradient reduction is still emitted by XLA → this file + zero/sharding.py is the 3-D
  (pipe x data x model) story (reference PipeModelDataParallelTopology, topology.py:246).

Schedule/memory note (vs the reference's 1F1B, runtime/pipe/schedule.py:182-289): the
scan realizes a GPipe-order schedule with jax.checkpoint on the stage body, so the
forward stores only each scan step's STAGE INPUT (one [mb, T, E] tensor per step), not
per-layer activations. Measured on the compiled program (8-virtual-device CPU,
GPT-2 8L/256E/S=4, bf16): temp memory grows ~2.3 MB per extra micro-batch ≈ 0.9x the
stage-input size per step, while 1F1B WITHOUT remat holds up to S in-flight
micro-batches x full per-layer activations (~12x stage-input per stage for 2-layer
stages) regardless of M. For the training configs this engine targets (M <= ~4S
micro-batches per accumulation window), GPipe+remat live memory is at or below
1F1B-without-remat. At M >> S, ``pipeline_apply`` automatically splits the window
into rematerialized SEGMENTS of <= 4S micro-batches, restoring the bound: measured
at M = 16S (GPT-2 2L/128E/S=2, T=512, mb-batch 16, grad of the full loss, peak RSS
on the 8-virtual-device CPU) single flush 4529 MB vs segmented 2287 MB. By default
the segments are STREAMED (``_streamed_apply``): the pipe buffer is a scan carry
across the checkpoint segments, so the whole window pays the (S-1)-step fill ONCE —
the reference 1F1B's single-fill discipline (schedule.py:182-289) — instead of per
flush: at M=16S, S=8, cap=4S the lockstep step count drops 156 -> 135 (bubble 17.9%
-> 5.2%; ``flush_schedule`` is the accounting). The legacy drain-per-flush schedule
(``_flushed_apply``) stays available via ``stream_segments=False`` as a comparison
oracle.

Requires homogeneous stages (equal per-stage blocks) — the layout GPT/BERT stacks
naturally have. Heterogeneous first/last work (embedding, LM head, loss) runs inside the
same shard_map: ``first_stage_fn``/``post_fn`` may use pipe-axis collectives, so large
IO parameters (the embedding table) can be SHARDED over ``pipe`` instead of replicated —
see GPT2Pipe's vocab-parallel embedding/head, which stores 1/S of the vocab table per
pipe rank (the reference replicated tied embeddings on first+last stage and all-reduced
their grads across the tied group, runtime/pipe/module.py TiedLayerSpec; sharding the
table over pipe makes the tie free and the memory ∝ 1/S).
"""

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS, PIPE_AXIS


def stack_stage_params(per_stage_params):
    """Stack a list of per-stage pytrees into leading-axis-S leaves (shard over pipe)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage_params)


def stacked_param_sharding(mesh: Mesh, stacked_tree):
    """NamedShardings placing each stage's slice on its pipe rank."""
    def leaf(x):
        spec = [PIPE_AXIS] + [None] * (x.ndim - 1)
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map(leaf, stacked_tree)


def flush_schedule(M: int, S: int, cap: int, streamed: bool = True):
    """Compiled-step accounting for an M-micro-batch window on an S-stage pipe with
    checkpoint segments of ``cap`` micro-batches (the memory bound).

    ``ideal_steps`` is the single-fill optimum ``M + S - 1`` (the reference 1F1B's
    per-optimizer-step discipline, reference schedule.py:182-289). The STREAMED
    schedule achieves it exactly — the pipe buffer is carried across checkpoint
    segments so segment i+1's fill IS segment i's drain. The legacy per-flush
    schedule drains every flush: ``(M / cap) * (cap + S - 1)`` steps.

    Returns ``{steps, ideal_steps, n_segments, bubble_fraction}`` where
    bubble_fraction = 1 - M / steps (fraction of lockstep steps in which at least
    one stage computes no real micro-batch)."""
    assert M % cap == 0, f"window M={M} must divide into segments of {cap}"
    n = M // cap
    steps = (M + S - 1) if streamed else n * (cap + S - 1)
    return {"steps": steps, "ideal_steps": M + S - 1, "n_segments": n,
            "bubble_fraction": 1.0 - M / steps}


def _infer_specs(stacked_params, x_microbatches, last_stage_args, first_stage_args,
                 last_stage_args_specs, first_stage_args_specs, stacked_param_specs, M):
    """Default shard_map specs shared by the unsplit and streamed paths: stacked
    params over pipe, micro-batches data-sharded on dim 1, everything else
    replicated. A last_stage_args leaf that LOOKS micro-batched ([M, batch, ...]
    — e.g. labels, but equally a weight whose leading dim happens to equal M)
    is ambiguous, and guessing data-sharded would silently mis-shard the weight
    case; like the drain-per-flush schedule (which additionally CHUNKS
    micro-batched args), refuse and demand explicit last_stage_args_specs."""
    x_spec = P(*([None, DATA_AXIS] + [None] * (x_microbatches.ndim - 2)))
    stacked_spec = (stacked_param_specs if stacked_param_specs is not None
                    else jax.tree_util.tree_map(
                        lambda a: P(*([PIPE_AXIS] + [None] * (a.ndim - 1))),
                        stacked_params))

    if last_stage_args_specs is None:
        for path, a in jax.tree_util.tree_flatten_with_path(last_stage_args)[0]:
            if hasattr(a, "ndim") and a.ndim >= 2 and a.shape[0] == M:
                raise ValueError(
                    f"pipeline_apply: last_stage_args leaf "
                    f"'{jax.tree_util.keystr(path) or '<root>'}' (shape {a.shape}) has "
                    f"leading dim == M={M} and could be either a micro-batched input "
                    "(P(None, 'data')) or a replicated weight (P()) — pass explicit "
                    "last_stage_args_specs instead of relying on shape inference.")
    last_spec = (last_stage_args_specs if last_stage_args_specs is not None
                 else jax.tree_util.tree_map(lambda _: P(), last_stage_args))
    first_spec = (first_stage_args_specs if first_stage_args_specs is not None
                  else jax.tree_util.tree_map(lambda _: P(), first_stage_args))
    return x_spec, stacked_spec, last_spec, first_spec


def _streamed_apply(stage_fn, stacked_params, x_microbatches, cap, *, mesh,
                    last_stage_fn, last_stage_args, first_stage_fn, first_stage_args,
                    last_stage_args_specs, first_stage_args_specs, stacked_param_specs,
                    last_stage_collective):
    """Checkpoint-segmented pipeline WITHOUT per-segment drain: the pipe buffer is a
    scan carry across segments, so micro-batches stream continuously and the whole
    window pays the (S-1)-step fill exactly once — the single-fill discipline of the
    reference's 1F1B (schedule.py:182-289) with GPipe-order remat memory (backward
    replays one ``cap``-micro-batch segment at a time; live memory is one segment's
    stage inputs + the running grads, same bound as ``_flushed_apply``).

    vs. the per-flush schedule this removes (M/cap - 1) * (S-1) lockstep steps:
    at M=16S, cap=4S, the step count drops 156 -> 135 (S=8) — see flush_schedule."""
    M = x_microbatches.shape[0]
    S = mesh.shape[PIPE_AXIS]
    n = M // cap

    x_spec, stacked_spec, last_spec, first_spec = _infer_specs(
        stacked_params, x_microbatches, last_stage_args, first_stage_args,
        last_stage_args_specs, first_stage_args_specs, stacked_param_specs, M)

    def inner(stacked_local, x_mb, last_args, first_args):
        # ONE shard_map for the whole window: the pipe buffer lives entirely
        # inside it (segments are an inner checkpointed scan), so its cotangent
        # never crosses a shard_map boundary — routing it through per-segment
        # shard_map calls dropped/corrupted exactly the boundary micro-batches'
        # first-stage grads (measured: mbs {cap-S+1 mod cap} wrong, loss exact).
        s = jax.lax.axis_index(PIPE_AXIS)
        is_first = s == 0
        is_last = s == S - 1
        my_params = jax.tree_util.tree_map(lambda a: a[0], stacked_local)

        def ingest(g):
            x0 = x_mb[jnp.clip(g, 0, M - 1)]
            if first_stage_fn is not None:
                x0 = first_stage_fn(x0, *first_args)
            return x0

        def step(ingest_real):
            def body(carry, g):
                buf, loss_acc = carry
                if ingest_real:  # static: the drain never ingests
                    # ingest runs UNCONDITIONALLY on every rank (it may contain
                    # pipe collectives — vocab-parallel embedding — which must
                    # stay uniform); only the SELECT is rank-dependent
                    x_ing = ingest(g)
                    x_in = jnp.where(is_first, x_ing, buf) if x_ing.ndim == 0 else \
                        jax.lax.select(jnp.broadcast_to(is_first, ()), x_ing, buf)
                else:
                    x_in = buf
                y = stage_fn(my_params, x_in)
                mb = g - (S - 1)
                valid = jnp.logical_and(mb >= 0, mb < M)
                if last_stage_collective:
                    def do_head(_):
                        y_b = jax.lax.psum(
                            jnp.where(is_last, 1.0, 0.0).astype(y.dtype) * y, PIPE_AXIS)
                        return last_stage_fn(y_b, *last_args, jnp.clip(mb, 0, M - 1))

                    loss_acc = loss_acc + jax.lax.cond(
                        valid, do_head, lambda _: jnp.zeros((), jnp.float32),
                        operand=None)
                else:
                    take = jnp.logical_and(is_last, valid)
                    loss_acc = loss_acc + jax.lax.cond(
                        take,
                        lambda _: last_stage_fn(y, *last_args, jnp.clip(mb, 0, M - 1)),
                        lambda _: jnp.zeros((), jnp.float32), operand=None)
                perm = [(i, (i + 1) % S) for i in range(S)]
                return (jax.lax.ppermute(y, PIPE_AXIS, perm), loss_acc), None

            return body

        @jax.checkpoint
        def segment(carry, f):
            # cap lockstep steps; backward replays ONE segment's forward at a
            # time — the same live-memory bound as the per-flush schedule, but
            # the (buf, loss) carry streams on so the pipe never drains
            carry, _ = jax.lax.scan(step(True), carry, f * cap + jnp.arange(cap))
            return carry, None

        x0_example = jax.eval_shape(ingest, jax.ShapeDtypeStruct((), jnp.int32))
        carry0 = (jnp.zeros(x0_example.shape, x0_example.dtype),
                  jnp.zeros((), jnp.float32))
        carry, _ = jax.lax.scan(segment, carry0, jnp.arange(n))
        if S > 1:
            carry, _ = jax.lax.scan(step(False), carry, M + jnp.arange(S - 1))
        _, loss_acc = carry
        if last_stage_collective:
            # the collective head already accumulates uniformly over pipe
            return jax.lax.pmean(loss_acc / M, DATA_AXIS)
        loss = jax.lax.psum(jnp.where(is_last, loss_acc, 0.0), PIPE_AXIS) / M
        return jax.lax.pmean(loss, DATA_AXIS)

    fn = jax.shard_map(inner, mesh=mesh,
                       in_specs=(stacked_spec, x_spec, last_spec, first_spec),
                       out_specs=P(), check_vma=False)
    return fn(stacked_params, x_microbatches, last_stage_args, first_stage_args)


def _flushed_apply(stage_fn, stacked_params, x_microbatches, cap, *, mesh,
                   last_stage_fn, last_stage_args, first_stage_fn, first_stage_args,
                   last_stage_args_specs, first_stage_args_specs, stacked_param_specs,
                   last_stage_collective):
    """Split an M-micro-batch window into M/cap pipeline flushes and scan over them
    with a ``jax.checkpoint``-wrapped flush body.

    The scan serializes the flushes (a Python-unrolled loop lets the runtime
    overlap independent flush recomputations, which RAISES peak memory) and the
    checkpoint discards each flush's interior residuals, so backward live memory is
    one flush's stage inputs + the running grads — bounded in M. Measured (8-virtual-
    device CPU peak RSS, 256-step scan analog): whole 1291 MB vs scanned flushes
    657 MB; Python-unrolled flushes regressed to 1625 MB."""
    M = x_microbatches.shape[0]
    n = M // cap

    def is_microbatched(a, spec):
        # micro-batched last_stage_args (labels) scan with the flushes; weights and
        # scalars ride the closure. ONLY a leading None in the explicit spec marks
        # the micro-batch dim (P() means replicated — a weight whose leading dim
        # happens to equal M must NOT be chunked), and a [M] 1-D leaf (per-micro-
        # batch weights) qualifies.
        if not (hasattr(a, "ndim") and a.ndim >= 1 and a.shape and a.shape[0] == M):
            return False
        return len(spec) > 0 and spec[0] is None

    flat_args, args_treedef = jax.tree_util.tree_flatten(last_stage_args)
    if last_stage_args_specs is None and flat_args:
        # A shape heuristic here (leading dim == M) would silently chunk a weight
        # whose leading dim coincides with M across flushes — demand the explicit
        # contract instead of guessing.
        raise ValueError(
            f"pipeline_apply: the {M}-micro-batch window splits into flushes of "
            f"{cap}, which requires explicit last_stage_args_specs to tell "
            "micro-batched leaves (leading-None PartitionSpec, e.g. P(None, 'data')) "
            "from per-flush constants (P()). Pass last_stage_args_specs, or "
            "max_microbatches_per_flush=0 to disable splitting.")
    if last_stage_args_specs is not None:
        # specs may be a PREFIX tree (one P covering a whole subtree, as shard_map
        # accepts): broadcast each prefix leaf over its matching args subtree
        is_p = lambda x: isinstance(x, P)
        broadcast = jax.tree_util.tree_map(
            lambda spec, sub: jax.tree_util.tree_map(lambda _: spec, sub),
            last_stage_args_specs, last_stage_args, is_leaf=is_p)
        flat_specs = jax.tree_util.tree_leaves(broadcast, is_leaf=is_p)
    else:
        flat_specs = [P()] * len(flat_args)
    mb_flags = [is_microbatched(a, sp) for a, sp in zip(flat_args, flat_specs)]

    x_chunks = x_microbatches.reshape((n, cap) + x_microbatches.shape[1:])
    scanned = [a.reshape((n, cap) + a.shape[1:]) for a, f in zip(flat_args, mb_flags) if f]

    @jax.checkpoint
    def flush(acc, chunk_and_mb):
        chunk, mb_leaves = chunk_and_mb
        it = iter(mb_leaves)
        largs = jax.tree_util.tree_unflatten(
            args_treedef, [next(it) if f else a for a, f in zip(flat_args, mb_flags)])
        loss = pipeline_apply(
            stage_fn, stacked_params, chunk, mesh=mesh,
            last_stage_fn=last_stage_fn, last_stage_args=largs,
            first_stage_fn=first_stage_fn, first_stage_args=first_stage_args,
            last_stage_args_specs=last_stage_args_specs,
            first_stage_args_specs=first_stage_args_specs,
            stacked_param_specs=stacked_param_specs,
            last_stage_collective=last_stage_collective,
            max_microbatches_per_flush=0)
        return acc + loss, None

    total, _ = jax.lax.scan(flush, jnp.zeros((), jnp.float32),
                            (x_chunks, tuple(scanned)))
    return total / n


def pipeline_apply(stage_fn: Callable,
                   stacked_params,
                   x_microbatches,
                   *,
                   mesh: Mesh,
                   last_stage_fn: Callable = None,
                   last_stage_args=(),
                   first_stage_fn: Callable = None,
                   first_stage_args=(),
                   last_stage_args_specs=None,
                   first_stage_args_specs=None,
                   stacked_param_specs=None,
                   last_stage_collective: bool = False,
                   max_microbatches_per_flush: int = None,
                   stream_segments: bool = True):
    """Run micro-batches through the pipe-axis pipeline inside shard_map.

    When the window exceeds ``max_microbatches_per_flush`` (default ``4 * n_stages``,
    the M <= ~4S regime where GPipe+remat live memory matches 1F1B — see module
    docstring), the loss path automatically splits into ``ceil(M / cap)``
    ``jax.checkpoint`` segments: the backward of segment i replays only segment i's
    forward, so live memory is bounded by one segment's stage inputs regardless of M.
    With ``stream_segments=True`` (default) the pipe buffer is CARRIED across
    segments — micro-batches stream continuously and the whole window pays the
    (S-1)-step fill exactly once (the reference 1F1B's single-fill discipline,
    schedule.py:182-289; see ``flush_schedule`` for the step accounting). With
    ``stream_segments=False`` each segment drains fully before the next fills (the
    legacy per-flush schedule: (M/cap)(cap+S-1) steps — kept as a comparison
    oracle). Pass ``max_microbatches_per_flush=0`` to disable splitting.

    Args:
      stage_fn: homogeneous per-stage function ``(stage_params, x) -> y``; applied by
        every pipe rank to its own parameter slice.
      stacked_params: pytree with leading dim = n_stages on every leaf (see
        ``stack_stage_params``), sharded over ``pipe``.
      x_microbatches: [M, ...] micro-batched activations entering stage 0 (replicated
        over pipe, sharded over data on the batch dim).
      last_stage_fn: optional ``(y, *last_stage_args, mb_index) -> scalar`` applied to
        each micro-batch's final activation at the last stage (e.g. head+loss). Returns
        the mean over micro-batches, psum-broadcast over pipe. When None, returns the
        [M, ...] outputs broadcast over pipe.
      first_stage_fn: optional ``(x_mb, *first_stage_args) -> activation`` applied at
        stage 0 before the first block (e.g. embedding lookup inside the pipeline).
        Runs inside shard_map on every pipe rank, so it MAY use pipe-axis collectives
        over pipe-sharded first_stage_args (vocab-parallel embedding).
      first_stage_args_specs: optional PartitionSpecs for first_stage_args (defaults to
        replicated); pass P(pipe, ...) leaves to shard IO params over the pipe axis.
        first_stage_args must NOT be micro-batched ([M, ...]-leading): they ride the
        flush closure whole and are never scanned — put per-micro-batch inputs in
        ``x_microbatches`` (or labels-like data in ``last_stage_args``) instead.
      last_stage_collective: when True, last_stage_fn runs on EVERY pipe rank against
        the per-step psum-broadcast final activation and MAY use pipe-axis collectives
        over pipe-sharded last_stage_args (the vocab-parallel tied head+loss). Only one
        [mb, ...] activation is live per step — no [M, ...] buffer.

    Differentiable in stacked_params / x_microbatches / *args.
    """
    M = x_microbatches.shape[0]
    S = mesh.shape[PIPE_AXIS]
    cap = 4 * S if max_microbatches_per_flush is None else max_microbatches_per_flush
    if last_stage_fn is not None and cap > 0 and M > cap:
        # equal-size flushes so the global mean is the mean of flush means; the
        # largest divisor of M <= cap keeps one compile and one scan shape
        cap_eff = max(d for d in range(1, cap + 1) if M % d == 0)
        if cap_eff < max(2, cap // 2):
            # M has no divisor near the cap (prime/awkward window): either the
            # memory bound silently lapses (cap_eff < 2 -> unsplit) or tiny flushes
            # crater pipeline utilization — surface it instead of both
            import logging
            logging.getLogger("DeepSpeedTPU").warning(
                f"pipeline flush split: window M={M} has no divisor near the cap "
                f"{cap} (best {cap_eff}); %s. Choose M a multiple of a value <= "
                f"{cap} for the documented memory bound.",
                "running a SINGLE unsplit flush (memory grows with M)"
                if cap_eff < 2 else f"running {M // cap_eff} flushes of {cap_eff}")
        if cap_eff >= 2:
            impl = _streamed_apply if stream_segments else _flushed_apply
            return impl(
                stage_fn, stacked_params, x_microbatches, cap_eff, mesh=mesh,
                last_stage_fn=last_stage_fn, last_stage_args=last_stage_args,
                first_stage_fn=first_stage_fn, first_stage_args=first_stage_args,
                last_stage_args_specs=last_stage_args_specs,
                first_stage_args_specs=first_stage_args_specs,
                stacked_param_specs=stacked_param_specs,
                last_stage_collective=last_stage_collective)

    def inner(stacked_local, x_mb, last_args, first_args):
        S = jax.lax.axis_size(PIPE_AXIS)
        s = jax.lax.axis_index(PIPE_AXIS)
        is_first = s == 0
        is_last = s == S - 1
        # shard_map gives leading dim 1 for the pipe-sharded stack; take our slice
        my_params = jax.tree_util.tree_map(lambda a: a[0], stacked_local)

        total_steps = M + S - 1
        act_shape = None

        def ingest(t):
            idx = jnp.clip(t, 0, M - 1)
            x0 = x_mb[idx]
            if first_stage_fn is not None:
                x0 = first_stage_fn(x0, *first_args)
            return x0

        # abstract-eval only: ingest may contain pipe collectives (vocab-parallel
        # embedding) that must not execute just to size the carry buffers
        x0_example = jax.eval_shape(ingest, jax.ShapeDtypeStruct((), jnp.int32))
        carry_init = (jnp.zeros(x0_example.shape, x0_example.dtype),  # arriving activation
                      jnp.zeros((), jnp.float32),            # loss accumulator (last stage)
                      (jnp.zeros((M,) + x0_example.shape, x0_example.dtype)
                       if last_stage_fn is None else jnp.zeros((), jnp.float32)))

        def step(carry, t):
            buf, loss_acc, out_acc = carry
            # stage 0 ingests micro-batch t; others use the activation permuted to them
            x_in = jnp.where(is_first, ingest(t), buf) if x0_example.ndim == 0 else \
                jax.lax.select(jnp.broadcast_to(is_first, ()), ingest(t), buf)
            y = stage_fn(my_params, x_in)
            # last stage finishes micro-batch mb = t - (S - 1)
            mb = t - (S - 1)
            valid = jnp.logical_and(mb >= 0, mb < M)
            take = jnp.logical_and(is_last, valid)
            if last_stage_fn is None:
                out_acc = jax.lax.cond(
                    take,
                    lambda o: o.at[jnp.clip(mb, 0, M - 1)].set(y),
                    lambda o: o,
                    out_acc)
            elif last_stage_collective:
                # run the broadcast + collective head on every rank, but only on
                # steps that finish a micro-batch: ``valid`` depends only on the scan
                # counter (uniform across ranks), so lax.cond keeps collective
                # execution uniform while skipping the S-1 warmup/drain steps' head
                def do_head(_):
                    y_b = jax.lax.psum(
                        jnp.where(is_last, 1.0, 0.0).astype(y.dtype) * y, PIPE_AXIS)
                    return last_stage_fn(y_b, *last_args, jnp.clip(mb, 0, M - 1))

                contrib = jax.lax.cond(valid, do_head,
                                       lambda _: jnp.zeros((), jnp.float32),
                                       operand=None)
                loss_acc = loss_acc + contrib
            else:
                contrib = jax.lax.cond(
                    take,
                    lambda _: last_stage_fn(y, *last_args, jnp.clip(mb, 0, M - 1)),
                    lambda _: jnp.zeros((), jnp.float32),
                    operand=None)
                loss_acc = loss_acc + contrib
            # rotate activations one stage forward over ICI
            perm = [(i, (i + 1) % S) for i in range(S)]
            buf_next = jax.lax.ppermute(y, PIPE_AXIS, perm)
            return (buf_next, loss_acc, out_acc), None

        (buf, loss_acc, out_acc), _ = jax.lax.scan(step, carry_init, jnp.arange(total_steps))

        if last_stage_fn is None:
            # broadcast last stage's outputs to every pipe rank (differentiable psum)
            mask = jnp.where(is_last, 1.0, 0.0)
            out = jax.lax.psum(out_acc * mask.astype(out_acc.dtype), PIPE_AXIS)
            return out
        if last_stage_collective:
            # the collective head already made loss_acc uniform over pipe
            return jax.lax.pmean(loss_acc / M, DATA_AXIS)
        loss = jax.lax.psum(jnp.where(is_last, loss_acc, 0.0), PIPE_AXIS) / M
        # the user's last_stage_fn returns a mean over its LOCAL batch shard; average the
        # equal-sized shards to the global mean (and replicate over data for out_spec P())
        loss = jax.lax.pmean(loss, DATA_AXIS)
        return loss

    # shardings: stacked params split over pipe (caller-provided layouts, e.g.
    # model-axis TP dims, pass through); everything else replicated over pipe
    # (data-dim sharding of the micro-batches is preserved by P(None, 'data', ...)).
    x_spec, stacked_spec, last_spec, first_spec = _infer_specs(
        stacked_params, x_microbatches, last_stage_args, first_stage_args,
        last_stage_args_specs, first_stage_args_specs, stacked_param_specs, M)
    out_spec = P() if last_stage_fn is not None else x_spec

    fn = jax.shard_map(inner, mesh=mesh,
                       in_specs=(stacked_spec, x_spec, last_spec, first_spec),
                       out_specs=out_spec,
                       check_vma=False)
    return fn(stacked_params, x_microbatches, last_stage_args, first_stage_args)
