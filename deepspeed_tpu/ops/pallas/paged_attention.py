"""Paged decode attention Pallas kernel (single-query, block-table gather).

One grid cell per (slot, page): the scalar-prefetched block table steers each
cell's k/v ``BlockSpec`` index map straight at the slot's page in the HBM pool
``[n_layer, num_blocks, block_size, n_head, head_dim]`` — the pages are DMA'd
by table indirection, never gathered into a contiguous [slots, max_len, ...]
buffer (that gather is exactly what the XLA fallback in serve/paged.py pays
for). Online-softmax (m, l, acc) scratch carries the reduction across a slot's
pages, vLLM's PagedAttention shape specialized to decode (query length 1).

Numerics: scores and the softmax accumulate in f32 regardless of pool dtype;
the result matches the dense path to float tolerance, NOT bitwise (the dense
path computes one flat softmax over max_len, this kernel reduces page by
page). Hence the engine default is the bitwise XLA gather path; this kernel
is opt-in via ``serving.use_pallas_decode`` and pinned by an allclose parity
test (tests/unit/test_paged_attention.py).

``interpret=True`` (automatic off-TPU) runs the same grid sequentially on
CPU — scratch persistence across the page dimension matches TPU semantics.

Head sharding: under ``serving.sharding.model`` the engine invokes this kernel
inside ``shard_map`` with the pool's head axis already split, so ``n_head``
here is the PER-SHARD head count and the pool refs are the shard-local pages.
Nothing in the kernel is head-global — the softmax reduces over each head's
own pages independently — so the same kernel body serves both layouts; the
cross-shard f32 psum lives in the caller's projection, not here.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # python float: a jnp scalar would be a captured constant


def _decode_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_size, head_dim):
    """Grid (slots, pages): accumulate one page of one slot's KV history into
    the slot's online-softmax state; finalize on the last page."""
    b = pl.program_id(1)
    s = pl.program_id(0)
    num_pages = pl.num_programs(1)

    @pl.when(b == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)                     # [nh, hd]
    k = k_ref[...].astype(jnp.float32)                     # [BS, nh, hd]
    v = v_ref[...].astype(jnp.float32)

    # One query row per head: the scores are a multiply and a lane reduction on
    # the page as it lies in the pool, [BS, nh, hd]. (A dot_general batched over
    # the middle axis has no non-contracting dimension on the query side, which
    # the TPU lowering refuses.) The page axis stays leading throughout, so the
    # reductions over it are plain vector adds.
    scores = jnp.sum(k * q[None], axis=-1, keepdims=True) / math.sqrt(head_dim)  # [BS, nh, 1]

    # causal frontier: token index within the whole history
    idx = b * block_size + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    scores = jnp.where(idx < lengths_ref[s], scores, _NEG_INF)

    m_prev = m_ref[...]                                    # [nh, 1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=0))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new[None])                      # [BS, nh, 1]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0)
    acc_ref[...] = acc_ref[...] * alpha + jnp.sum(p * v, axis=0)   # [nh, hd]
    m_ref[...] = m_new

    @pl.when(b == num_pages - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("li", "block_size", "interpret"))
def _paged_decode(q, k_pool, v_pool, tables, lengths, *, li, block_size,
                  interpret):
    S, nh, _, hd = q.shape
    MB = tables.shape[1]
    BS = block_size            # static argname; already an int (see wrapper)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables, lengths steer the DMA
        grid=(S, MB),
        in_specs=[
            pl.BlockSpec((None, nh, hd), lambda s, b, t, ln: (s, 0, 0)),
            # the paged gather: page (li, tables[s, b]) of the pool
            pl.BlockSpec((None, None, BS, nh, hd),
                         lambda s, b, t, ln: (li, t[s, b], 0, 0, 0)),
            pl.BlockSpec((None, None, BS, nh, hd),
                         lambda s, b, t, ln: (li, t[s, b], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, nh, hd), lambda s, b, t, ln: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, 1), jnp.float32),   # running max
            pltpu.VMEM((nh, 1), jnp.float32),   # running denominator
            pltpu.VMEM((nh, hd), jnp.float32),  # running numerator
        ],
    )
    kernel = functools.partial(_decode_kernel, block_size=BS, head_dim=hd)
    # the kernel sees the single query row as [nh, hd]: a block whose last two
    # dimensions are (1, hd) cannot be stored from a [nh, hd] vector on the TPU
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lengths, q.reshape(S, nh, hd), k_pool, v_pool)
    return out.reshape(S, nh, 1, hd)


def paged_decode_attention(q, k_pool, v_pool, li, tables, lengths, *,
                           block_size, interpret=None):
    """Decode attention through the block table.

    q [slots, n_head, 1, head_dim]; k_pool/v_pool the layer-major page pools
    [n_layer, num_blocks, block_size, n_head, head_dim]; ``li`` the (static)
    layer; tables [slots, max_blocks] int32 page ids; lengths [slots] valid
    history lengths (pos + 1). Returns [slots, n_head, 1, head_dim] in
    q.dtype. ``interpret`` defaults to True off-TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _paged_decode(q, k_pool, v_pool,
                         tables.astype(jnp.int32), lengths.astype(jnp.int32),
                         li=int(li), block_size=int(block_size),
                         interpret=bool(interpret))
