"""Two-level ICI+DCN collectives: the topology-aware gradient-exchange schedule.

Generalizes ``runtime/custom_collectives.compressed_allreduce`` from its flat
single-axis form to the two-network reality a :class:`~.topology.CommTopology`
describes. Every data-parallel exchange becomes three steps:

1. **ICI reduce-scatter** within each slice (exact, full-precision): device
   ``(s, l)`` ends up owning chunk ``l`` of its slice's local sum — the cheap
   network does the high-bandwidth work.
2. **DCN exchange** across slices, one group per chunk position. Uncompressed
   mode runs a plain ``psum``; compressed mode runs the reference's
   error-feedback two-phase sign compression (1 bit/element bit-packed into
   uint8 + per-segment fp32 RMS scales) among the ``num_slices`` peers — the
   slow network ships ~n/16 bytes instead of 4n.
3. **ICI all-gather** within each slice reassembles the full vector.

With ``slice_size == 1`` the schedule degenerates to exactly the flat
compressed allreduce (every device is its own slice; the DCN group is the
whole axis); with ``num_slices == 1`` it degenerates to a flat psum.

Numerics contract: the two-level UNCOMPRESSED mean reassociates the reduction
(slice-sums first), so on generic fp32 data it is bit-equal to XLA's flat
all-reduce only when every partial sum is exact (integer-valued grids, data
with shared exponents) — tests pin bit-equality on such data and tolerance
parity on real training (docs/multislice.md). Error-feedback state for the
compressed mode: ``worker_error`` is per-device over its ICI chunk
``(dp, n / slice_size)`` and ``server_error`` per-device over its DCN
sub-chunk ``(dp, n / dp)`` — the flat layout with ``slice_size == 1`` keeps
the historical ``(dp, n)`` shape.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS
from ..utils.cluster import named_scope as ds_named_scope
from ..runtime.custom_collectives import _signs_collective, padded_size
from .topology import CommTopology

__all__ = [
    "flatten_tree", "unflatten_tree", "tree_size", "grad_segment_ids",
    "two_level_sum", "two_level_compressed",
    "two_level_allreduce", "two_level_compressed_allreduce",
    "error_state_shapes", "padded_size",
    "bucket_partition", "bucket_plan", "bucketed_error_state_shapes",
    "bucketed_two_level_mean", "bucketed_two_level_compressed",
    "GRAD_BUCKET_SCOPE",
]

# named_scope prefix stamped on every bucketed exchange: it survives into the
# optimized HLO as instruction metadata (op_name), which is how a reader of
# the program or of a trace tells an eagerly-issued bucket collective from
# the monolithic exchange (docs/overlap.md)
GRAD_BUCKET_SCOPE = "ds_grad_bucket"


# ---------------------------------------------------------------- tree plumbing
def tree_size(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def flatten_tree(tree):
    """Tree -> (n,) vector plus the restore recipe (leaf order = tree order)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    flat = jnp.concatenate([l.reshape(-1) for l in leaves])
    return flat, (treedef, sizes, [l.shape for l in leaves])


def unflatten_tree(vec, recipe):
    treedef, sizes, shapes = recipe
    offsets = np.cumsum([0] + sizes)
    leaves = [vec[offsets[i]:offsets[i + 1]].reshape(shapes[i])
              for i in range(len(sizes))]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def grad_segment_ids(tree, n_pad: int) -> np.ndarray:
    """Element -> leaf-index segment map over the flattened padded vector, the
    padded tail in its own segment (its zeros must not drag a real tensor's
    RMS scale down — same per-tensor semantics as 1-bit Adam's state)."""
    sizes = [int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree)]
    ids = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    if n_pad > ids.shape[0]:
        ids = np.concatenate([ids, np.full(n_pad - ids.shape[0], len(sizes),
                                           np.int32)])
    assert ids.shape[0] == n_pad, f"tree has {ids.shape[0]} elements > n_pad={n_pad}"
    return ids


def error_state_shapes(n_pad: int, topo: CommTopology):
    """((dp, worker_cols), (dp, server_cols)) for the compressed exchange's
    persistent error-feedback buffers on an ``n_pad``-element vector."""
    dp = topo.dp
    assert n_pad % dp == 0
    return (dp, n_pad // topo.slice_size), (dp, n_pad // dp)


# --------------------------------------------------------------- bucketing
def bucket_partition(tree, bucket_bytes: int):
    """Greedy deterministic partition of the tree's leaves (tree order) into
    contiguous size-bounded buckets: a leaf opens a new bucket when appending
    it would push the current bucket past ``bucket_bytes``. Sizes are priced
    at 4 bytes/element (the fp32 wire width) so the partition depends only on
    the parameter SHAPES and ``bucket_bytes`` — never on dtype or data. A
    single leaf larger than the bound gets its own (oversized) bucket.
    Returns a list of leaf-index lists covering every leaf exactly once."""
    leaves = jax.tree_util.tree_leaves(tree)
    buckets, cur, cur_bytes = [], [], 0
    for i, leaf in enumerate(leaves):
        nbytes = int(np.prod(leaf.shape)) * 4
        if cur and cur_bytes + nbytes > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def bucket_plan(tree, bucket_bytes: int, dp: int):
    """``bucket_partition`` plus the static per-bucket exchange geometry:
    ``[{"leaf_indices", "sizes", "n", "n_pad"}]`` where ``n_pad`` rounds each
    bucket up to a multiple of ``dp`` (the two-level schedule's scatter
    granularity). Deterministic for a given tree / bucket_bytes / dp."""
    leaves = jax.tree_util.tree_leaves(tree)
    plan = []
    for idxs in bucket_partition(tree, bucket_bytes):
        sizes = tuple(int(np.prod(leaves[i].shape)) for i in idxs)
        n = sum(sizes)
        plan.append({"leaf_indices": tuple(idxs), "sizes": sizes,
                     "n": n, "n_pad": padded_size(n, dp)})
    return plan


def bucketed_error_state_shapes(plan, topo: CommTopology):
    """((dp, worker_cols), (dp, server_cols)) for the bucketed compressed
    exchange's persistent error-feedback buffers: the per-bucket chunks laid
    out back to back in plan order. The total exceeds the monolithic
    ``error_state_shapes`` by the per-bucket padding — bucketed EF state is a
    different (per-bucket) layout, not a re-slicing of the monolithic one."""
    dp = topo.dp
    we_cols = sum(b["n_pad"] // topo.slice_size for b in plan)
    se_cols = sum(b["n_pad"] // dp for b in plan)
    return (dp, we_cols), (dp, se_cols)


def _bucket_vec(leaves, bucket):
    """One bucket's padded flat vector (in the leaves' own dtype)."""
    parts = [leaves[i].reshape(-1) for i in bucket["leaf_indices"]]
    vec = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return jnp.pad(vec, (0, bucket["n_pad"] - bucket["n"]))


def _bucket_unpack(mean, bucket, leaves, out):
    """Scatter one bucket's exchanged vector back onto its leaves."""
    off = 0
    for i, sz in zip(bucket["leaf_indices"], bucket["sizes"]):
        out[i] = mean[off:off + sz].reshape(leaves[i].shape) \
            .astype(leaves[i].dtype)
        off += sz


def bucketed_two_level_mean(leaves, plan, topo: CommTopology,
                            axis_name: str = DATA_AXIS):
    """Per-bucket exact two-level MEAN of a flat leaf list (inside shard_map).

    Each bucket runs the same reduce-scatter -> DCN psum -> all-gather
    schedule as the monolithic ``two_level_sum`` (plain psum on a flat
    topology), under its own ``ds_grad_bucket{k}`` named_scope, and depends
    only on its OWN leaves — so the compiler is free to issue bucket k's
    exchange while the backward producing bucket k-1's leaves is still
    running, and the DCN hop of bucket k runs concurrently with the ICI
    phase of bucket k+1. Per element the reduction tree is identical to the
    monolithic exchange, so the result is bit-equal to it for any fixed
    bucket assignment (bucketing reorders issue, not math)."""
    dp = topo.dp
    out = [None] * len(leaves)
    for k, bucket in enumerate(plan):
        with ds_named_scope(f"{GRAD_BUCKET_SCOPE}{k}"):
            mean = two_level_sum(_bucket_vec(leaves, bucket), topo,
                                 axis_name) / dp
            _bucket_unpack(mean, bucket, leaves, out)
    return out


def bucketed_two_level_compressed(leaves, we_local, se_local, plan,
                                  topo: CommTopology, seg_consts, n_segs,
                                  axis_name: str = DATA_AXIS):
    """Per-bucket error-feedback compressed MEAN of a flat leaf list (inside
    shard_map): ``two_level_compressed`` over each bucket's padded vector,
    with the persistent worker/server error buffers laid out per bucket
    (``bucketed_error_state_shapes``). ``seg_consts``/``n_segs`` are the
    static per-bucket scale-segment maps (one per plan entry). NOT bit-equal
    to the monolithic compressed exchange — per-segment RMS scales are
    chunked per bucket — but the EF telescoping contract holds per bucket.
    Returns (out leaves, new_we, new_se)."""
    L, dp = topo.slice_size, topo.dp
    out = [None] * len(leaves)
    new_we, new_se = [], []
    we_off = se_off = 0
    for k, bucket in enumerate(plan):
        n_pad = bucket["n_pad"]
        wcols, scols = n_pad // L, n_pad // dp
        with ds_named_scope(f"{GRAD_BUCKET_SCOPE}{k}"):
            vec = _bucket_vec(leaves, bucket).astype(jnp.float32)
            mean, we_k, se_k = two_level_compressed(
                vec, we_local[we_off:we_off + wcols],
                se_local[se_off:se_off + scols], topo, seg_consts[k],
                n_segs[k], axis_name)
            _bucket_unpack(mean, bucket, leaves, out)
        new_we.append(we_k)
        new_se.append(se_k)
        we_off += wcols
        se_off += scols
    return (out, jnp.concatenate(new_we) if len(new_we) > 1 else new_we[0],
            jnp.concatenate(new_se) if len(new_se) > 1 else new_se[0])


# ------------------------------------------------------------ in-context bodies
# These run INSIDE an existing shard_map over the data axis (the engine's grad
# scaffold); the wrappers below add the shard_map for standalone callers.

def two_level_sum(x_local, topo: CommTopology, axis_name: str = DATA_AXIS):
    """Exact two-level SUM of per-device vectors: reduce-scatter over ICI,
    psum over DCN, all-gather over ICI. ``x_local`` length must divide by
    ``slice_size``. Caller divides for a mean (one division, same placement
    as XLA's flat pmean)."""
    if not topo.is_hierarchical:
        return jax.lax.psum(x_local, axis_name)
    part = jax.lax.psum_scatter(x_local, axis_name, scatter_dimension=0,
                                axis_index_groups=topo.ici_groups, tiled=True)
    part = jax.lax.psum(part, axis_name, axis_index_groups=topo.dcn_groups)
    return jax.lax.all_gather(part, axis_name,
                              axis_index_groups=topo.ici_groups, tiled=True)


def two_level_compressed(x_local, we_local, se_local, topo: CommTopology,
                         seg_const, n_segs: int, axis_name: str = DATA_AXIS):
    """Two-level error-feedback sign-compressed MEAN of per-device vectors.

    Args (per-device, inside shard_map):
      x_local: (n,) — this device's local contribution.
      we_local: (n / slice_size,) worker error over this device's ICI chunk.
      se_local: (n / dp,) server error over this device's DCN sub-chunk.
      seg_const: (n,) int32 scale-segment map (static).
      n_segs: static segment count (max id + 1).

    Returns (out (n,) ~= mean over dp of x_local, new_we, new_se).
    """
    n = x_local.shape[0]
    S, L = topo.num_slices, topo.slice_size
    assert n % (S * L) == 0, f"vector size {n} must divide by dp={S * L} (pad first)"
    C = n // L          # my ICI chunk after the reduce-scatter
    csize = C // S      # my DCN server sub-chunk
    idx = jax.lax.axis_index(axis_name)
    l = idx % L         # position within my slice == which chunk of n I own
    s = idx // L        # my slice == my position within my DCN group

    def seg_rms(buf, ids):
        counts = jnp.maximum(
            jax.ops.segment_sum(jnp.ones(buf.shape, jnp.float32), ids,
                                num_segments=n_segs), 1.0)
        ss = jax.ops.segment_sum(jnp.square(buf), ids, num_segments=n_segs)
        return jnp.sqrt(ss / counts)

    # Level 1 (ICI, exact): slice-local reduce-scatter, then the slice mean so
    # the DCN server mean over slices composes to the grand mean — the same
    # magnitude the flat schedule compresses, keeping error-feedback residual
    # scales comparable across topologies.
    chunk = jax.lax.psum_scatter(
        x_local.astype(jnp.float32), axis_name, scatter_dimension=0,
        axis_index_groups=topo.ici_groups, tiled=True) / L          # (C,)

    # Level 2 phase 1 (DCN): compress my chunk, ship sub-chunk j to slice j.
    seg_chunk = jax.lax.dynamic_slice(seg_const, (l * C,), (C,))
    corrected = chunk + we_local
    wscale = seg_rms(corrected, seg_chunk)                           # (n_segs,)
    signs = jnp.where(corrected >= 0, 1, -1).astype(jnp.int8)
    new_we = corrected - wscale[seg_chunk] * signs.astype(jnp.float32)

    packed = csize % 8 == 0
    recv = _signs_collective(
        lambda t: jax.lax.all_to_all(t, axis_name, split_axis=0, concat_axis=0,
                                     tiled=False,
                                     axis_index_groups=topo.dcn_groups),
        signs.reshape(S, csize), packed)                             # (S, csize)
    wscales = jax.lax.all_gather(wscale, axis_name,
                                 axis_index_groups=topo.dcn_groups)  # (S, n_segs)

    # Server reduction over the S slice peers, with my persistent server error.
    seg_server = jax.lax.dynamic_slice(seg_const, (l * C + s * csize,), (csize,))
    per_elem_wscale = jnp.take_along_axis(
        wscales, seg_server[None, :].repeat(S, 0), axis=1)           # (S, csize)
    server_m = jnp.mean(recv.astype(jnp.float32) * per_elem_wscale, axis=0)
    corrected_s = server_m + se_local
    sscale = seg_rms(corrected_s, seg_server)
    s_signs = jnp.where(corrected_s >= 0, 1, -1).astype(jnp.int8)
    new_se = corrected_s - sscale[seg_server] * s_signs.astype(jnp.float32)

    # Level 2 phase 2 (DCN): gather the S compressed server sub-chunks back.
    all_signs = _signs_collective(
        lambda t: jax.lax.all_gather(t, axis_name,
                                     axis_index_groups=topo.dcn_groups),
        s_signs, packed)                                             # (S, csize)
    sscales = jax.lax.all_gather(sscale, axis_name,
                                 axis_index_groups=topo.dcn_groups)  # (S, n_segs)
    per_elem_sscale = jnp.take_along_axis(sscales, seg_chunk.reshape(S, csize),
                                          axis=1)
    my_chunk = (all_signs.astype(jnp.float32) * per_elem_sscale).reshape(C)

    # Level 3 (ICI): reassemble the full mean from the L slice chunks.
    out = jax.lax.all_gather(my_chunk, axis_name,
                             axis_index_groups=topo.ici_groups, tiled=True)
    return out, new_we, new_se


# --------------------------------------------------------- standalone wrappers
def two_level_allreduce(mesh: Mesh, x, topo: CommTopology,
                        axis_name: str = DATA_AXIS):
    """Uncompressed two-level MEAN of per-worker rows: (dp, n) sharded
    ``P(data, None)`` -> (n,) replicated."""
    dp = topo.dp
    assert mesh.shape[axis_name] == dp

    def body(x_row):
        total = two_level_sum(x_row[0], topo, axis_name)
        return total / dp

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis_name, None),),
                       out_specs=P(), check_vma=False)
    return fn(x)


def two_level_compressed_allreduce(mesh: Mesh, x, worker_error, server_error,
                                   topo: CommTopology,
                                   axis_name: str = DATA_AXIS, seg_ids=None):
    """Two-level generalization of ``custom_collectives.compressed_allreduce``.

    Args:
      x: (dp, n) fp32 per-worker rows, sharded ``P(data, None)``.
      worker_error: (dp, n / slice_size) fp32 persistent, same sharding.
      server_error: (dp, n / dp) fp32 persistent, same sharding.
      topo: the slice factorization (flat ``slice_size == 1`` reproduces the
        historical flat layout and math exactly).
      seg_ids: optional STATIC (n,) int segment map (per-tensor scales).

    Returns (out (n,) replicated compressed mean, new_worker_error,
    new_server_error).
    """
    dp = topo.dp
    assert mesh.shape[axis_name] == dp
    n = x.shape[-1]
    seg_np = (np.zeros((n,), np.int32) if seg_ids is None
              else np.asarray(seg_ids, np.int32))
    assert seg_np.shape == (n,), f"seg_ids must be ({n},), got {seg_np.shape}"
    n_segs = int(seg_np.max()) + 1
    seg_const = jnp.asarray(seg_np)

    def body(x_row, we_row, se_row):
        out, new_we, new_se = two_level_compressed(
            x_row[0], we_row[0], se_row[0], topo, seg_const, n_segs, axis_name)
        return out, new_we[None], new_se[None]

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis_name, None),) * 3,
                       out_specs=(P(), P(axis_name, None), P(axis_name, None)),
                       check_vma=False)
    return fn(x, worker_error, server_error)
