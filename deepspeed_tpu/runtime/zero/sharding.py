"""ZeRO sharding policies as GSPMD layouts.

This is the TPU-native core of what ``runtime/zero/stage1.py`` (983 LoC) and ``stage2.py``
(1850 LoC) implement with hand-rolled flatten/partition/reduce-scatter/all-gather over NCCL:

- stage 0: optimizer state + master weights replicated; gradients all-reduced over ``data``.
- stage 1 (optimizer-state sharding, stage1.py:302-442): master fp32 weights and Adam
  moments carry a data-axis-sharded layout; XLA turns the backward's gradient all-reduce
  + local update + param broadcast into reduce-scatter → sharded update → all-gather.
- stage 2 (+gradient sharding, stage2.py:590-745): additionally the gradient accumulation
  buffer carries the sharded layout, so accumulated grads are stored reduce-scattered —
  the IPG-bucket machinery becomes a sharding annotation.

``zero_spec`` picks, per parameter, the largest axis divisible by the DP degree to shard;
parameters too small to split stay replicated (the reference pads flat buffers instead —
on TPU padding tiny tensors wastes ICI latency for nothing).
"""

from typing import Optional

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...parallel.mesh import DATA_AXIS


def zero_spec(shape, dp_size: int, min_size: int = 1024, existing_spec: P = P()) -> P:
    """PartitionSpec sharding the largest *unclaimed* dp-divisible axis over 'data'.

    ``existing_spec`` lets ZeRO compose with a layout that already shards some axes
    (pipe-stacked stages, TP weights): only axes the existing spec leaves None are
    candidates, and the existing placements are preserved.
    """
    spec = list(existing_spec) + [None] * (len(shape) - len(existing_spec))
    if dp_size <= 1 or int(np.prod(shape)) < min_size or DATA_AXIS in spec:
        return P(*spec)     # a leaf the layout already splits over 'data' (experts) stays
    best_axis = -1
    best_dim = 0
    for i, d in enumerate(shape):
        if spec[i] is None and d % dp_size == 0 and d > best_dim:
            best_axis = i
            best_dim = d
    if best_axis >= 0:
        spec[best_axis] = DATA_AXIS
    return P(*spec)


def zero_sharding(mesh: Mesh, tree, stage: int, min_size: int = 1024):
    """Tree of NamedShardings for optimizer state / master params under the given stage."""
    import jax
    dp = mesh.shape[DATA_AXIS]

    def leaf(p):
        if stage >= 1:
            return NamedSharding(mesh, zero_spec(p.shape, dp, min_size))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(leaf, tree)


def sharding_coverage(shardings_tree, tree):
    """(sharded_bytes, total_bytes) over the tree — how much state the ZeRO layout
    actually partitioned vs left replicated. zero_spec legitimately leaves a leaf
    replicated (no dp-divisible axis, or under min_size), but a user at dp=32 with
    awkward shapes could believe they run ZeRO-2 while most state is replicated;
    the engine logs this at construction and tests pin >90% for flagship configs."""
    import jax
    total = sharded = 0
    for sh, a in zip(jax.tree_util.tree_leaves(shardings_tree),
                     jax.tree_util.tree_leaves(tree)):
        nbytes = int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
        total += nbytes
        if not sh.is_fully_replicated:
            sharded += nbytes
    return sharded, total


def chunk_spans(total: int, cap: Optional[int]):
    """Partition the flat range [0, total) into pipeline work spans of at most ``cap``
    elements: ``(lo, hi, win)`` triples where [lo, hi) is the span and ``win`` is the
    start of the fixed-width fetch window that covers it.

    Every window is exactly ``cap`` wide (the last one is right-aligned at
    ``total - cap``, overlapping its predecessor) so a single compiled fixed-width
    device slice serves every chunk of a region — the overlap re-fetches identical
    elements, which the consumer simply doesn't write twice. With ``cap`` None/0 or
    ``total <= cap`` the region stays whole: one span, window 0.
    """
    if not cap or cap <= 0 or total <= cap:
        return [(0, total, 0)]
    spans = []
    for lo in range(0, total, cap):
        hi = min(lo + cap, total)
        spans.append((lo, hi, lo if hi - lo == cap else total - cap))
    return spans


def elastic_split(arr, dp: int):
    """Split a host array into the ``dp`` flat checkpoint shards of the elastic
    optimizer-state layout (checkpoint/checkpointing.py). np.array_split
    semantics — first ``size % dp`` shards get one extra element — which is
    exactly what ``_merge_elastic`` concatenates back, so save@dp_a →
    restore@dp_b round-trips bit-exactly for any (dp_a, dp_b)."""
    return np.array_split(np.asarray(arr).reshape(-1), dp)


def replicated_sharding(mesh: Mesh, tree):
    import jax
    return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), tree)


def merge_zero_into(mesh: Mesh, sharding_tree, tree, stage: int, min_size: int = 1024):
    """Compose ZeRO data-axis sharding into an existing layout (e.g. pipe-stacked stages).

    For each leaf, if stage >= 1, shard the largest *unsharded* dp-divisible axis over
    'data' on top of the leaf's existing PartitionSpec. This is how ZeRO composes with
    pipeline/tensor layouts into true 3-D parallelism.
    """
    import jax
    dp = mesh.shape[DATA_AXIS]

    def leaf(sh: NamedSharding, a):
        if stage < 1:
            return NamedSharding(mesh, sh.spec)
        return NamedSharding(mesh, zero_spec(a.shape, dp, min_size, existing_spec=sh.spec))

    return jax.tree_util.tree_map(leaf, sharding_tree, tree)
