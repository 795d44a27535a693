"""Ring attention: sequence/context parallelism over a mesh axis.

TPU-first long-context capability beyond the reference's feature set (the reference's
long-sequence answer is block-sparse attention, ops/sparse_attention/*; it has no
sequence parallelism). Here the SEQUENCE dimension shards over a mesh axis: each rank
holds a [B, H, T/n, D] slice of q/k/v, k/v chunks rotate around the ring via
``lax.ppermute`` (ICI neighbor exchanges), and each visit runs the local flash kernel
(ops/pallas/flash_attention.py) against the visiting chunk, combining the per-chunk
``(out, lse)`` pairs with the standard online-softmax merge. Per-chip attention state
is O(T/n) and the flash kernel only ever sees chunk-sized operands — this is the
supported path past the single-chip kernel's whole-K/V VMEM cap (T >= ~16k at d=64)
and, composed with the ``data``/``model``/``pipe`` axes, the 4th parallelism
dimension.

Differentiability comes for free: ``flash_attention_with_lse`` is differentiable in
BOTH outputs (its lse cotangent folds into the flash backward's delta term), so
``jax.grad`` of the ring — combine, ppermute rotations and all — yields the correct
backward ring (ppermute transposes to the reverse rotation; no hand-written
gradient ring). Memory note: the autodiff residuals hold each visiting k/v chunk,
i.e. O(T_total x D) per rank for k/v — linear in sequence length (the O(T^2) score
matrix never exists), matching published ring-attention implementations that save
rotated chunks; wrap the model in ``jax.checkpoint`` to trade that for a second
forward ring.

Causal mode has two schedules:

``schedule="masked"`` (the original ring, kept as oracle): ranks hold contiguous
chunks; the diagonal chunk applies the in-kernel triangular mask, strictly-past
chunks attend fully, strictly-future chunks are computed then neutralized by
setting their lse to -inf before the merge — collective uniformity across ranks
at a ~2x causal compute tax (rank 0 sees n-1 all-future visits).

``schedule="zigzag"`` (the default causal path): the sequence is re-sharded so
rank ``i`` of an ``n``-ring holds global chunks ``i`` and ``2n-1-i`` of size
``C = T/(2n)`` (``zigzag_shard``; Brandon et al. 2023, "Striped Attention"). Each
rank's local [2C] block is an early+late interleave, so EVERY (rank, rotation)
pair contains useful work: rotation 0 is one interleaved causal flash call (the
local order is globally monotone, so the kernel's block pruning is exact), and
every later rotation is exactly two fully-unmasked C x C calls — the visiting
low chunk is always past for the local high half, and one where-routed call
covers the remaining past half-chunk (low->low for past sources, high->high for
future sources). k/v rotate as before (same ppermute count and bytes), no
compute is ever discarded, and the per-rank work is identical across ranks
(``ring_work_schedule`` is the accounting). Dropout stays exact: every call
hashes GLOBAL coordinates via the kernel's offset/segment operand.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.pallas.flash_attention import _merge_partial, flash_attention_with_lse
from .mesh import DATA_AXIS

SCHEDULES = ("zigzag", "masked")


# --------------------------------------------------------------------- zigzag layout
def _zigzag_chunk_order(n: int):
    """Global chunk index (of 2n chunks) at each position of the rank-concatenated
    zigzag layout: rank i holds [chunk i, chunk 2n-1-i]."""
    order = []
    for i in range(n):
        order.extend((i, 2 * n - 1 - i))
    return order


def zigzag_shard(x, n: int, axis: int = 2):
    """Reorder a contiguous global sequence dim into the zigzag ring layout.

    Splits dim ``axis`` (length T, requires ``T % 2n == 0``) into ``2n`` chunks and
    concatenates them in rank order ``[0, 2n-1, 1, 2n-2, ...]``, so sharding the
    result contiguously over an ``n``-way mesh axis gives rank ``i`` global chunks
    ``(i, 2n-1-i)`` — every rank holds a balanced early+late mix of positions.
    A static gather; the inverse is ``zigzag_unshard``.
    """
    T = x.shape[axis]
    assert T % (2 * n) == 0, f"zigzag_shard: seq {T} must be divisible by 2n={2 * n}"
    c = T // (2 * n)
    idx = np.concatenate([np.arange(j * c, (j + 1) * c)
                          for j in _zigzag_chunk_order(n)])
    return jnp.take(x, jnp.asarray(idx), axis=axis)


def zigzag_unshard(x, n: int, axis: int = 2):
    """Inverse of ``zigzag_shard``: zigzag ring layout back to contiguous order."""
    T = x.shape[axis]
    assert T % (2 * n) == 0, f"zigzag_unshard: seq {T} must be divisible by 2n={2 * n}"
    c = T // (2 * n)
    fwd = np.concatenate([np.arange(j * c, (j + 1) * c)
                          for j in _zigzag_chunk_order(n)])
    inv = np.argsort(fwd)
    return jnp.take(x, jnp.asarray(inv), axis=axis)


def ring_work_schedule(n: int, schedule: str = "zigzag"):
    """Per-(rotation, rank) work accounting for the causal ring, in units of
    ``C x C`` score blocks where ``C = T/(2n)`` (half a rank's local sequence).

    ``computed`` counts blocks the flash kernel actually runs (after its in-kernel
    block pruning); ``useful`` counts non-masked score blocks (diagonal blocks are
    half-masked and count 1 computed / 0.5 useful). The masked schedule computes 4
    blocks every rotation on every rank but only past-source visits are useful;
    zigzag computes exactly the useful blocks, identically on every rank.
    Returns ``{"schedule", "n", "rotations": [{"r", "computed_per_rank",
    "useful_min", "useful_max"}], "total_computed", "total_useful"}`` with totals
    per rank summed over rotations.
    """
    assert schedule in SCHEDULES, f"schedule must be one of {SCHEDULES}"
    rotations = []
    for r in range(n):
        if r == 0:
            # both schedules: one causal call on the local [2C] block — the kernel
            # prunes to 3 computed blocks (two diagonal, one full)
            computed, useful = (3.0, 2.0)
            u_min = u_max = useful
        elif schedule == "masked":
            computed = 4.0  # full [2C x 2C] visit, masked or not
            # rank i's visit r is useful iff src=(i-r)%n < i, i.e. i >= r
            u_min, u_max = 0.0, 4.0
        else:
            computed = 2.0  # two C x C calls, both fully unmasked
            u_min = u_max = 2.0
        rotations.append({"r": r, "computed_per_rank": computed,
                          "useful_min": u_min, "useful_max": u_max})
    total_computed = sum(row["computed_per_rank"] for row in rotations)
    if schedule == "masked":
        # useful totals: rank i gets 2 (diagonal) + 4*i (past visits); average over
        # ranks = 2 + 2(n-1)
        total_useful = 2.0 + 2.0 * (n - 1)
    else:
        total_useful = 2.0 + 2.0 * (n - 1)
    return {"schedule": schedule, "n": n, "rotations": rotations,
            "total_computed": total_computed, "total_useful": total_useful}


# ------------------------------------------------------------------------- schedules
def _masked_ring(q, k, v, axis_name, causal, sm_scale, interpret, rate, seed):
    """Contiguous-layout ring: rank r holds positions [r*T_local, (r+1)*T_local)."""
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    T_local = q.shape[2]
    # chunks step to the NEXT rank each rotation: after r steps rank i holds the
    # k/v chunk originally at rank (i - r) mod n
    perm = [(i, (i + 1) % n) for i in range(n)]

    o = lse = None
    kc, vc = k, v
    for r in range(n):
        # named_scope: rotations show up as ring_rot{r} in profiler traces
        # (HLO metadata only — zero instructions, identical wire schedule)
        with jax.named_scope(f"ring_rot{r}"):
            if r > 0:
                kc = jax.lax.ppermute(kc, axis_name, perm)
                vc = jax.lax.ppermute(vc, axis_name, perm)
            out_r, lse_r = flash_attention_with_lse(
                q, kc, vc, causal=(causal and r == 0), sm_scale=sm_scale,
                interpret=interpret, dropout_rate=rate,
                dropout_seed=seed,
                dropout_q_offset=rank * T_local,
                dropout_k_offset=((rank - r) % n) * T_local)
            if causal and r > 0:
                src = (rank - r) % n
                keep = src < rank  # strictly-past chunks attend; future contribute zero
                lse_r = jnp.where(keep, lse_r, -jnp.inf)
                out_r = jnp.where(keep, out_r, jnp.zeros((), out_r.dtype))
            if o is None:
                o, lse = out_r.astype(jnp.float32), lse_r
            else:
                # online-softmax merge of normalized partials (shared with the
                # single-chip chunked flash path)
                o, lse = _merge_partial(o, lse, out_r, lse_r)
    return o.astype(q.dtype)


def _zigzag_ring(q, k, v, axis_name, sm_scale, interpret, rate, seed):
    """Zigzag-layout causal ring: rank i holds global chunks (i, 2n-1-i), each of
    size C = T_local/2. See the module docstring for the schedule; the masked
    schedule above is the oracle it must match after ``zigzag_unshard``."""
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    T_local = q.shape[2]
    assert T_local % 2 == 0, f"zigzag needs an even local seq, got {T_local}"
    C = T_local // 2
    perm = [(i, (i + 1) % n) for i in range(n)]

    lo_off = rank * C                 # global start of the local low (early) chunk
    hi_off = (2 * n - 1 - rank) * C   # global start of the local high (late) chunk
    q_lo, q_hi = q[:, :, :C], q[:, :, C:]

    # rotation 0: ONE interleaved causal call over the whole local [2C] block. The
    # local order is globally monotone (chunk i entirely precedes chunk 2n-1-i) and
    # q/k segment maps are identical, so the kernel's local causal pruning is exact;
    # the segment operand puts mask + dropout in global coordinates.
    with jax.named_scope("ring_rot0"):
        out0, lse0 = flash_attention_with_lse(
            q, k, v, causal=True, sm_scale=sm_scale, interpret=interpret,
            dropout_rate=rate, dropout_seed=seed,
            q_segments=(lo_off, hi_off), k_segments=(lo_off, hi_off))
        o_lo, lse_lo = out0[:, :, :C].astype(jnp.float32), lse0[:, :, :C]
        o_hi, lse_hi = out0[:, :, C:].astype(jnp.float32), lse0[:, :, C:]

    kc, vc = k, v
    for r in range(1, n):
      with jax.named_scope(f"ring_rot{r}"):
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        src = (rank - r) % n
        k_lo, k_hi = kc[:, :, :C], kc[:, :, C:]
        v_lo, v_hi = vc[:, :, :C], vc[:, :, C:]
        src_lo = src * C
        src_hi = (2 * n - 1 - src) * C

        # call A: q_hi x src's low chunk — ALWAYS fully past (src <= n-1 implies
        # src*C + C <= n*C <= hi_off), so no mask and no wasted work on any rank.
        out_a, lse_a = flash_attention_with_lse(
            q_hi, k_lo, v_lo, causal=False, sm_scale=sm_scale, interpret=interpret,
            dropout_rate=rate, dropout_seed=seed,
            dropout_q_offset=hi_off, dropout_k_offset=src_lo)
        o_hi, lse_hi = _merge_partial(o_hi, lse_hi, out_a, lse_a)

        # call B: the remaining past half-chunk, where-routed so every rank issues
        # the same shapes (uniform SPMD program). Past source (src < rank): its low
        # chunk strictly precedes ours -> q_lo x k_lo. Future source: its HIGH
        # chunk strictly precedes our high chunk (2n-1-src < 2n-1-rank) ->
        # q_hi x k_hi. Both are fully unmasked; dropout offsets route with them.
        past = src < rank
        q_b = jnp.where(past, q_lo, q_hi)
        k_b = jnp.where(past, k_lo, k_hi)
        v_b = jnp.where(past, v_lo, v_hi)
        out_b, lse_b = flash_attention_with_lse(
            q_b, k_b, v_b, causal=False, sm_scale=sm_scale, interpret=interpret,
            dropout_rate=rate, dropout_seed=seed,
            dropout_q_offset=jnp.where(past, lo_off, hi_off),
            dropout_k_offset=jnp.where(past, src_lo, src_hi))
        # route the partial into the half it belongs to; the -inf lse gates the
        # other half's merge to a no-op (grad-safe — same mechanism the masked
        # schedule uses to neutralize future chunks)
        zero = jnp.zeros((), out_b.dtype)
        o_lo, lse_lo = _merge_partial(o_lo, lse_lo,
                                      jnp.where(past, out_b, zero),
                                      jnp.where(past, lse_b, -jnp.inf))
        o_hi, lse_hi = _merge_partial(o_hi, lse_hi,
                                      jnp.where(past, zero, out_b),
                                      jnp.where(past, -jnp.inf, lse_b))
    return jnp.concatenate([o_lo, o_hi], axis=2).astype(q.dtype)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None,
                   interpret: Optional[bool] = None,
                   dropout_rate: float = 0.0, dropout_seed=None,
                   schedule: str = "zigzag", window: Optional[int] = None):
    """Attention over a sequence sharded on ``axis_name`` (call inside shard_map).

    Args:
      q, k, v: LOCAL [B, H, T_local, D] shards. Layout depends on the causal
        schedule: the non-causal ring and ``schedule="masked"`` use ring order
        (rank r holds positions [r*T_local, (r+1)*T_local)); the default causal
        ``schedule="zigzag"`` expects the ``zigzag_shard`` layout (rank i holds
        global chunks i and 2n-1-i of size T_local/2).
      axis_name: mesh axis the sequence is sharded over.
      dropout_rate/dropout_seed: in-kernel attention dropout. Each call hashes
        GLOBAL coordinates (via scalar offsets or the zigzag segment operand), so
        the sampled mask is identical to a single-chip kernel's over the full
        sequence — ``dropout_keep_reference`` at global T stays the oracle, and
        the mask is invariant to ring size and schedule.
      schedule: causal schedule, ``"zigzag"`` (balanced, no masked-compute tax;
        default) or ``"masked"`` (contiguous layout, kept as the oracle).
        Ignored when ``causal=False``.
      window: a sliding window is refused here (``ValueError``): the ring has no band.
    Returns the LOCAL [B, H, T_local, D] attention output (same layout as the
    inputs). Differentiable in q/k/v.
    """
    assert schedule in SCHEDULES, f"schedule must be one of {SCHEDULES}, got {schedule!r}"
    if window is not None:
        raise ValueError(f"ring_attention: a window of {window}: the ring (schedule "
                         f"{schedule!r}) has no band; it would run the whole triangle")
    if causal and schedule == "zigzag":
        return _zigzag_ring(q, k, v, axis_name, sm_scale, interpret,
                            dropout_rate, dropout_seed)
    return _masked_ring(q, k, v, axis_name, causal, sm_scale, interpret,
                        dropout_rate, dropout_seed)


def ring_attention_sharded(q, k, v, mesh: Mesh, seq_axis: str = DATA_AXIS,
                           causal: bool = False, sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           dropout_rate: float = 0.0, dropout_seed=None,
                           schedule: str = "zigzag"):
    """Convenience wrapper: global [B, H, T, D] arrays in natural sequence order,
    sharded over ``seq_axis`` (dim 2). Places inputs if they aren't already
    sharded. For the causal zigzag schedule the wrapper converts to/from the
    zigzag layout (two cheap static gathers), so callers always see natural
    order — the layout is an internal detail of the ring."""
    n = mesh.shape[seq_axis]
    assert q.shape[2] % n == 0, \
        f"seq {q.shape[2]} must divide over {seq_axis}={n}"
    zig = causal and schedule == "zigzag"
    if zig:
        assert q.shape[2] % (2 * n) == 0, \
            f"zigzag needs seq {q.shape[2]} divisible by 2*{n} (use schedule='masked')"
        q, k, v = (zigzag_shard(x, n, axis=2) for x in (q, k, v))
    spec = P(None, None, seq_axis, None)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (x if getattr(x, "sharding", None) == sharding else
               jax.device_put(x, sharding) for x in (q, k, v))
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                          sm_scale=sm_scale, interpret=interpret,
                          dropout_rate=dropout_rate, dropout_seed=dropout_seed,
                          schedule=schedule),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    out = fn(q, k, v)
    if zig:
        # the unshard gather drops the sequence sharding; pin it back so callers
        # keep the same layout contract as the masked path
        out = jax.device_put(zigzag_unshard(out, n, axis=2), sharding)
    return out
