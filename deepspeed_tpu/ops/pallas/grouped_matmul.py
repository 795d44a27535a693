"""The grouped products of an expert layer as Pallas kernels of our own: ``ds_gmm``
(``out[m] = lhs[m] @ rhs[g(m)]`` for rows sorted by group) and ``ds_tgmm`` (``out[g] =
lhs[rows of g].T @ rhs[rows of g]``), behind ``parallel/moe.grouped_matmul`` and
``grouped_matmul_weight_grad``.

They take what JAX's megablox kernels take (``jax.experimental.pallas.ops.tpu.megablox``:
``group_offset`` names the first group a piece of the experts holds, ``existing_out`` the
buffer a chain of pieces fills) and walk the same schedule, megablox's own
``make_group_metadata``: one grid step a (group, row tile) pair, a row tile that a group's
boundary cuts visited once a group and stored under a mask. What differs is the fast memory a
call may take. megablox's ``pallas_call`` states no ``vmem_limit_bytes``, so its blocks and the
compiler's temporaries share the 16 MiB a kernel is given by default, and a contraction over
1,024 had to be cut in pieces; each piece read the float32 accumulator ``[tm, tn]``, added and
wrote it, and the weights' block changed at every grid step, so it was fetched again for every
row tile. Here the limit is reckoned from the blocks (``vmem_limit``), and where the contraction
is ONE tile the weights' block does not change between the row tiles of a group (fetched once a
group and column tile), the product goes to the output under the store mask, and there is no
accumulator and no zeroing step. The masks are megablox's, at every step: a row tile that lies
whole inside its group skipping them (a branch round the store, a second product in ``ds_tgmm``)
read 2-10 % SLOWER a call on the chip in ``ds_gmm``, level in ``ds_tgmm`` at twice the compile
(PERF.md, PR 55); at the same tiles these kernels read what megablox's read.

bfloat16 or float32 operands, float32 accumulation, one rounding at the store, as megablox.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

_F32 = jnp.float32
_TN = (((0,), (0,)), ((), ()))      # dot_general dimension numbers of A^T.B
# the most a call asks for: a v5e has 128 MiB, and what the compiler adds to the blocks (the
# product before its store, a masked tile's selects) is reckoned in ``vmem_limit``
VMEM_CAP = 100 * 2 ** 20


def gmm_block_bytes(tiles, k, itemsize, existing_out=False):
    """The fast memory ``ds_gmm``'s blocks take: two buffers an operand and the output (and the
    existing output, where a chain of pieces fills one buffer), and the float32 accumulator
    ``[tm, tn]`` where the contraction ``k`` is cut (``tk < k``)."""
    tm, tk, tn = tiles
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn * (2 if existing_out else 1)) + (4 * tm * tn if tk < k else 0)


def tgmm_block_bytes(tiles, itemsize):
    """``ds_tgmm``'s: its operands are the two row blocks, its output block ``[tk, tn]`` and a
    float32 accumulator of that shape, kept over a group's row tiles."""
    tm, tk, tn = tiles
    return 2 * itemsize * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn


def vmem_limit(blocks, product):
    """``vmem_limit_bytes`` for a call whose blocks take ``blocks`` bytes and whose product a
    step is ``product`` float32 values: the product before it is stored or added and as much
    again for a masked tile's selects and conversions, a quarter of the blocks beside."""
    return min(VMEM_CAP, max(16 * 2 ** 20, blocks + blocks // 4 + 2 * 4 * product))       # no less than a kernel is given unasked


def _in_hbm(interpret):
    """``(operand -> operand, (shape, dtype) -> out_shape)`` that keep a call's operands and output
    in HBM. Where a program is small (the benchmark's set-up reads one layer's gradients on 1,024
    tokens) XLA lays a kernel's operands and outputs out in fast memory (``S(1)``), an expert
    array's 80 MB among them, beside the kernel's own scoped region; with the limits these kernels
    ask for that program never ended on the chip (PERF.md, PR 55), where the same calls among
    operands in HBM run. The interpreter knows no memory spaces."""
    if interpret:
        return (lambda x: x), jax.ShapeDtypeStruct
    return (lambda x: pltpu.with_memory_space_constraint(x, pltpu.HBM)), pltpu.HBM


def _rows_in_group(offsets, group, tile, tm, width):
    """``[tm, width]``: whether a row of row tile ``tile`` belongs to ``group``."""
    rows = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return (rows >= offsets[group]) & (rows < offsets[group + 1])


def _schedule(group_sizes, m, tm, group_offset, groups, visit_empty_groups):
    """megablox's walk over (group, row tile) pairs for the ``groups`` groups from
    ``group_offset`` on: ``(offsets [G + 1], group a step, row tile a step)``, the first group
    as a ``[1]`` array beside them for the scalar prefetch, and the steps to take."""
    first = jnp.zeros((1,), jnp.int32) if group_offset is None else jnp.asarray(group_offset, jnp.int32).reshape(1)
    metadata, steps = make_group_metadata(group_sizes=group_sizes, m=m, tm=tm, start_group=first[0],
                                          num_nonzero_groups=groups, visit_empty_groups=visit_empty_groups)
    return (*metadata, first), steps


def _gmm_kernel(offsets, groups, m_tiles, first, lhs, rhs, *rest, tm, tiles_k, k_rem, transpose_rhs,
                has_existing):
    del first
    existing = rest[0] if has_existing else None
    out = rest[1 if has_existing else 0]
    acc = rest[-1] if tiles_k > 1 else None
    step, k_i = pl.program_id(1), pl.program_id(2)
    group, tile = groups[step], m_tiles[step]

    if has_existing:
        # a row tile is seen for the first time by the first group that visits it: the rows of
        # the groups this call does not hold are the existing output's
        @pl.when((k_i == 0) & ((step == 0) | (m_tiles[jnp.maximum(step - 1, 0)] != tile)))
        def _():
            out[...] = existing[...]

    def product(last):
        a, b = lhs[...], rhs[...]
        if last and k_rem:       # the last piece of a contraction its tile does not divide
            keep = lambda x, dim: jnp.where(                                     # noqa: E731
                lax.broadcasted_iota(jnp.int32, x.shape, dim) < k_rem, x.astype(_F32), 0.0).astype(x.dtype)
            a, b = keep(a, 1), keep(b, 1 if transpose_rhs else 0)
        return lax.dot_general(a, b, (((1,), (1 if transpose_rhs else 0,)), ((), ())),
                               preferred_element_type=_F32)

    def store(total):
        # the rows of the tile that are the group's; the others keep what an earlier group of the
        # tile (or the existing output) left there
        mine = _rows_in_group(offsets, group, tile, tm, out.shape[1])
        out[...] = jnp.where(mine, total, out[...].astype(_F32)).astype(out.dtype)

    if tiles_k == 1:
        store(product(True))
        return

    @pl.when(k_i == 0)
    def _():
        acc[...] = product(False)

    @pl.when((k_i > 0) & (k_i < tiles_k - 1))
    def _():
        acc[...] += product(False)

    @pl.when(k_i == tiles_k - 1)
    def _():
        store(acc[...] + product(True))


@functools.partial(jax.jit, static_argnames=("preferred_element_type", "tiling", "transpose_rhs", "interpret"),
                   inline=True)
def gmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32, tiling=(128, 128, 128),
        group_offset=None, existing_out=None, transpose_rhs=False, interpret=False):
    """``out [m, n]``: ``lhs[rows of g] @ rhs[g - group_offset]`` for the groups ``rhs`` holds.
    ``lhs [m, k]``, ``rhs [groups, k, n]`` (``[groups, n, k]`` with ``transpose_rhs``),
    ``group_sizes [G]`` int32 (``G >= groups``), ``tiling = (tm, tk, tn)`` with ``m`` whole row
    tiles. The rows of the other groups are ``existing_out``'s (it is written in place), or
    unspecified without one."""
    m, k = lhs.shape
    groups, n = rhs.shape[0], rhs.shape[1 if transpose_rhs else 2]
    tm, tk, tn = tiling
    tiles_k, k_rem = pl.cdiv(k, tk), k % tk
    if existing_out is not None and existing_out.dtype != preferred_element_type:
        raise ValueError("the existing output has another type than the output asked for")
    scalars, steps = _schedule(group_sizes, m, tm, group_offset, groups, visit_empty_groups=False)

    def rhs_block(n_i, step, k_i, offsets, group_ids, m_tiles, first):
        return (group_ids[step] - first[0], *((n_i, k_i) if transpose_rhs else (k_i, n_i)))

    out_spec = pl.BlockSpec((tm, tn), lambda n_i, step, k_i, offsets, group_ids, m_tiles, first: (m_tiles[step], n_i))
    in_specs = [pl.BlockSpec((tm, tk), lambda n_i, step, k_i, offsets, group_ids, m_tiles, first: (m_tiles[step], k_i)),
                pl.BlockSpec((None, tn, tk) if transpose_rhs else (None, tk, tn), rhs_block)]
    operands = [lhs, rhs]
    if existing_out is not None:
        in_specs.append(out_spec)
        operands.append(existing_out)
    blocks = gmm_block_bytes(tiling, k, lhs.dtype.itemsize, existing_out is not None)
    in_hbm, out_in_hbm = _in_hbm(interpret)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k, k_rem=k_rem, transpose_rhs=transpose_rhs,
                          has_existing=existing_out is not None),
        out_shape=out_in_hbm((m, n), preferred_element_type),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, in_specs=in_specs, out_specs=out_spec,
            grid=(pl.cdiv(n, tn), steps, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), _F32)] if tiles_k > 1 else []),
        input_output_aliases={6: 0} if existing_out is not None else {},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                             vmem_limit_bytes=vmem_limit(blocks, tm * tn)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            # the rows once a column tile; a group's weights once where K is whole, else once a step
            bytes_accessed=(lhs.size * pl.cdiv(n, tn) + k * n * (groups if tiles_k == 1 else scalars[1].size))
            * lhs.dtype.itemsize + m * n * jnp.dtype(preferred_element_type).itemsize),
        interpret=interpret,
        name="ds_gmm",
    )(*scalars, *map(in_hbm, operands))


def _tgmm_kernel(offsets, groups, m_tiles, first, lhs, rhs, out, acc, *, tm):
    del first
    step, last = pl.program_id(2), pl.num_programs(2) - 1
    group, tile = groups[step], m_tiles[step]

    @pl.when((step == 0) | (groups[jnp.maximum(step - 1, 0)] != group))
    def _():
        acc[...] = jnp.zeros_like(acc)

    # the other groups' rows of the tile count as zeros in both operands; an empty group (it has a
    # step of its own, so that its output is written) adds nothing
    @pl.when(offsets[group + 1] > offsets[group])
    def _():
        mine = lambda x: jnp.where(_rows_in_group(offsets, group, tile, tm, x.shape[1]),     # noqa: E731
                                   x[...].astype(_F32), 0.0).astype(x.dtype)
        acc[...] += lax.dot_general(mine(lhs), mine(rhs), _TN, preferred_element_type=_F32)

    @pl.when((step == last) | (groups[jnp.minimum(step + 1, last)] != group))
    def _():
        out[...] = acc[...].astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("preferred_element_type", "tiling", "num_actual_groups", "interpret"),
                   inline=True)
def tgmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32, tiling=(128, 128, 128),
         group_offset=None, num_actual_groups=None, interpret=False):
    """``out [groups, k, n]``: ``lhs[rows of g].T @ rhs[rows of g]`` for the ``num_actual_groups``
    groups from ``group_offset`` on (all ``G`` of ``group_sizes`` by default; an empty group's is
    zero). ``lhs [m, k]`` (the ROWS first: megablox takes it transposed and turns it back),
    ``rhs [m, n]``, ``tiling = (tm, tk, tn)``: the rows a step contracts and the output's tile."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tm, tk, tn = tiling
    groups = group_sizes.shape[0] if num_actual_groups is None else num_actual_groups
    scalars, steps = _schedule(group_sizes, m, tm, group_offset, groups, visit_empty_groups=True)
    in_hbm, out_in_hbm = _in_hbm(interpret)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=out_in_hbm((groups, k, n), preferred_element_type),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((tm, tk), lambda n_i, k_i, step, offsets, group_ids, m_tiles, first: (m_tiles[step], k_i)),
                      pl.BlockSpec((tm, tn), lambda n_i, k_i, step, offsets, group_ids, m_tiles, first: (m_tiles[step], n_i))],
            out_specs=pl.BlockSpec((None, tk, tn), lambda n_i, k_i, step, offsets, group_ids, m_tiles, first:
                                   (group_ids[step] - first[0], k_i, n_i)),
            grid=(pl.cdiv(n, tn), pl.cdiv(k, tk), steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), _F32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(tgmm_block_bytes(tiling, lhs.dtype.itemsize), tk * tn)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * pl.cdiv(n, tn) + rhs.size * pl.cdiv(k, tk)) * lhs.dtype.itemsize
            + groups * k * n * jnp.dtype(preferred_element_type).itemsize),
        interpret=interpret,
        name="ds_tgmm",
    )(*scalars, in_hbm(lhs), in_hbm(rhs))
