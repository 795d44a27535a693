"""Pallas kernels under a multi-device jit.

XLA's SPMD partitioner refuses a compiled Pallas kernel ("Mosaic kernels cannot
be automatically partitioned. Please wrap the call in a shard_map") — which the
interpreter on a CPU mesh never shows, because an interpreted kernel is plain HLO.
So a kernel call traced under a mesh whose axes XLA partitions automatically is
split here instead: attention is independent per batch row and per head, so the
batch dimension goes over the ``data`` axis and the head dimension over the
``model`` axis wherever they divide, and every other axis sees replicas. The
kernel's lowering wants every axis of the mesh manual, those of size one included.

The mesh is the one in context (``jax.sharding.get_abstract_mesh``): the engine
traces its step programs under its own mesh. With no mesh in context, on one
device, or inside a ``shard_map`` that already made every axis manual, the call
is direct.
"""

import math

import jax
from jax.sharding import PartitionSpec as P


def shard_over_mesh(fn, operands, dims, out_dims="bh", heads=None):
    """``fn(*operands)`` with every operand split over the context mesh.

    ``dims[i]`` says which dimensions operand ``i`` has: ``"bh"`` (batch, heads, ...),
    ``"bth"`` (batch, positions, the heads side by side in the last axis: the layout a
    projection writes), ``"b"`` (batch, ...) or ``""`` (neither: replicated); the first
    operand has a batch. ``fn`` returns an array or a tuple of arrays, laid out as
    ``out_dims`` says (one word for all, or one each). ``heads`` is the number of pieces
    the heads may be cut in at most (a caller of ``"bth"`` operands gives it: a width does
    not say how many heads it holds); None: what the ``"bh"`` operands share. ``fn`` is also
    handed the index of its shard among the batch-and-head shards (0 when the call is direct)."""
    from ...parallel.mesh import DATA_AXIS, MODEL_AXIS  # parallel/ imports this package
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or all(mesh.shape[a] == 1 for a in mesh.auto_axes):
        return fn(0, *operands)

    def axis_for(name, dim):
        ok = name in mesh.auto_axes and mesh.shape[name] > 1 and dim % mesh.shape[name] == 0
        return name if ok else None

    # K and V may have fewer heads than the queries they serve (a group of query heads a
    # key/value head): the heads are split only as finely as every such operand allows
    batch = operands[0].shape[0]
    if heads is None:
        heads = math.gcd(*(o.shape[1] for o, d in zip(operands, dims) if d == "bh"))
    b_axis, h_axis = axis_for(DATA_AXIS, batch), axis_for(MODEL_AXIS, heads)
    spec = {"bh": P(b_axis, h_axis), "bth": P(b_axis, None, h_axis), "b": P(b_axis), "": P()}
    out_specs = spec[out_dims] if isinstance(out_dims, str) else tuple(spec[d] for d in out_dims)

    def body(*local):
        index = 0
        for axis in (b_axis, h_axis):
            if axis is not None:
                index = index * mesh.shape[axis] + jax.lax.axis_index(axis)
        return fn(index, *local)

    return jax.shard_map(body, in_specs=tuple(spec[d] for d in dims), out_specs=out_specs,
                         axis_names=frozenset(mesh.auto_axes), check_vma=False)(*operands)
