"""Flash attention Pallas kernel (fwd + bwd) for TPU.

TPU-native replacement for the reference's fused attention-softmax CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, ``general_kernels.cu`` attention-score path of
N1): a blocked online-softmax attention that never materializes the [T, T] score matrix.

Design (v5e; measured rows in ``_resolve`` and PERF.md, PR 25):
- every tile is computed TRANSPOSED, S^T = K.Q^T as [block_k, block_q]: the softmax's
  per-query statistics (m, l, lse, delta) are [1, block_q] rows, reduced down the
  sublanes, never across the lanes of each 8 query rows, and never re-laid-out.
- forward: grid over (batch*heads, q-tiles); the k/v stream is a ``lax.fori_loop`` over
  k-tiles with running (m, l, acc^T) online-softmax state (FlashAttention-2 order).
- backward: ONE pass, grid over (batch*heads, k-tiles) with a loop over the q-tiles
  that see the k-tile. A visit computes the tile once (five matmuls, one exp2) and
  feeds all three gradients: dK and dV of the k-tile ride the loop, dQ^T accumulates in
  a float32 VMEM scratch [D, T] across the k-tiles of one (batch, head) and is rounded
  once. Residuals are (q, k, v, out, lse) — O(T) memory.
- causal: ``causal_k_tiles`` / ``causal_q_tiles`` are the schedule — every tile the
  triangle touches is visited once, none wholly above the diagonal, and the mask runs
  only on tiles the diagonal crosses. ``_resolve`` picks square tiles (512; 1024 for
  non-causal calls and from T = 8192), so a diagonal tile holds nothing wholly masked.
- ``window`` (static, causal calls): a query sees the ``window`` keys up to itself and the
  schedule is a BAND (``band_k_loops`` / ``band_q_loops``): a q-tile starts at the first
  k-tile the window's lower edge touches, the masked body (both edges) runs on the tiles
  an edge crosses, the backward's loop over q-tiles ends where the window ends.
  ``band_pairs`` counts what the schedule visits against what the mask allows.
- each kernel computes its VMEM budget from T, D and the tile sizes (``_vmem_limit``);
  head_dim <= 256.
- the values may be narrower (or wider) than the queries and keys (latent attention at 192 | 128):
  ``v``, ``out``, ``dO`` and ``dV`` are ``v.shape[-1]`` wide, ``q``, ``k``, ``dQ`` and ``dK``
  ``q.shape[-1]``; the width is a static property of the shapes, and a call at equal widths is the
  program it was before the kernels took two.
- layout: ``flash_attention`` takes and gives ``[B, H, T, D]``. ``flash_attention_rows`` gives the
  output ``[B, T, H * Dv]`` as the output projection reads it (and reads its cotangent so), and
  takes each of q, k and v where its producer leaves it: ``[B, T, heads * width]`` from a
  projection, or head-major from a pass the compiler folds the turn of the axes into. A head's
  ``[rows, width]`` tile is lane block ``h`` of a row-major operand wherever ``width`` is a multiple
  of the 128 lanes, so the kernel bodies, tiles and names are the same and only the index maps
  differ (``lanes``: ``_head_spec``; one ``pallas_call`` a kernel serves both layouts).
  ``layout_of`` chooses by what the call shows, its widths and its band; any other call is turned
  head-major by the entry (width 64, two heads a lane block, among them: a kernel that computes
  both in one grid cell ran 3.4 % faster end to end at GPT-2 XL's 25 heads and took 2.3 times as
  long to compile, a layer; the band's backward ran 6.9 % slower on row-major operands: PERF.md,
  PR 60). ``delta`` is a kernel of its own there (``ds_flash_delta``), and a group's dK and dV
  are summed as slices of the last axis: the chip's compiler lays a ``[B, T, heads, width]`` view
  of such an array out head-major and copies.
- ``interpret=True`` fallback keeps CPU tests honest; a dense reference implementation
  (``dense_attention``) is the numerics oracle.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .partition import shard_over_mesh

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def dense_attention(q, k, v, causal=False, sm_scale=None, bias=None, dropout_keep=None,
                    window=None):
    """Reference dense attention ([B,H,T,D] inputs), fp32 softmax.

    ``window``: a causal query ``i`` sees the keys ``i - window < j <= i`` only.

    ``bias``: additive key bias [B, 1, T_k] (the BERT padding mask).
    ``dropout_keep``: pre-scaled multiplicative mask on the post-softmax probs
    (e.g. from ``dropout_keep_reference``) — the numerics oracle for the kernel.
    """
    assert window is None or causal, "a window belongs to a causal call"
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)[:, :, None, :]  # [B,1,1,Tk]
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), jnp.bool_))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((T, T), jnp.bool_), -window)
        scores = jnp.where(mask, scores, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_keep is not None:
        probs = probs * dropout_keep.astype(probs.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# ---------------------------------------------------------------------------
# in-kernel attention dropout
# ---------------------------------------------------------------------------
# Stateless counter-based dropout: a lowbias32-style integer avalanche over the
# ABSOLUTE coordinate (batch*head, q position, k position) plus the step seed. Because
# the bits depend only on coordinates — never on block shapes or grid order — the
# forward kernel and the backward kernel regenerate bit-identical masks, remat
# replays them exactly (the seed is a traced operand), and a pure-jnp oracle
# (``dropout_keep_reference``) exists for parity tests. This replaces the reference's
# CUDA RNG state tracker + curand path (csrc/transformer/dropout_kernels.cu).

def _dropout_bits(seed_u32, bh_u32, q_pos, k_pos):
    """uint32 hash; inputs broadcast, q_pos/k_pos int32 arrays."""
    x = (q_pos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + k_pos.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         + bh_u32 * jnp.uint32(0xC2B2AE3D)
         + seed_u32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _keep_threshold(rate: float) -> int:
    return min(int(rate * 4294967296.0), 4294967295)


def dropout_keep_reference(seed, B, H, T_q, T_k, rate: float):
    """[B, H, T_q, T_k] pre-scaled keep mask identical to the in-kernel stream."""
    seed_u32 = jnp.asarray(seed, jnp.int32).reshape(()).astype(jnp.uint32)
    bh = jnp.arange(B * H, dtype=jnp.uint32)[:, None, None]
    qp = jnp.arange(T_q, dtype=jnp.int32)[None, :, None]
    kp = jnp.arange(T_k, dtype=jnp.int32)[None, None, :]
    bits = _dropout_bits(seed_u32, bh, qp, kp)
    keep = (bits >= jnp.uint32(_keep_threshold(rate))).astype(jnp.float32)
    return (keep / (1.0 - rate)).reshape(B, H, T_q, T_k)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

LOG2E = 1.4426950408889634  # 1/ln(2): softmax runs in base 2 (exp2 is the cheaper
# VPU transcendental, and folding sm_scale*log2e into q kills a per-tile scale pass)


def _read_seed_ref(seed_ref, seg):
    """Unpack the SMEM seed/offset operand.

    Contiguous form (3,): ``[seed, q_off, k_off]`` — global position is local
    position plus the scalar offset.
    Segmented form (7,): ``[seed, q_off0, k_off0, q_half, q_off1, k_half, k_off1]``
    — the local sequence is two concatenated global segments (zigzag ring layout):
    local positions ``< *_half`` start at ``*_off0``, the rest at ``*_off1``.
    Returns ``(seed_u32, map_q, map_k)`` where the maps take local int32 position
    arrays to global coordinates.
    """
    seed_u32 = seed_ref[0].astype(jnp.uint32)
    q_off, k_off = seed_ref[1], seed_ref[2]
    if seg:
        q_half, q_off1 = seed_ref[3], seed_ref[4]
        k_half, k_off1 = seed_ref[5], seed_ref[6]
        map_q = lambda p: p + jnp.where(p < q_half, q_off, q_off1 - q_half)
        map_k = lambda p: p + jnp.where(p < k_half, k_off, k_off1 - k_half)
    else:
        map_q = lambda p: p + q_off
        map_k = lambda p: p + k_off
    return seed_u32, map_q, map_k


def causal_k_tiles(q_tile, block_q, block_k, window=None):
    """The k-tiles a causal q-tile visits, as ``(n_full, last)``: tiles ``[0, n_full)``
    lie wholly on or below the diagonal (largest key <= smallest query: no mask),
    tiles ``[n_full, last)`` are crossed by it (masked body), and no tile from
    ``last`` on holds an unmasked element. Plain integer arithmetic, so it takes a
    Python int (the schedule test) or a traced ``program_id`` (the forward kernel);
    ``last`` never passes the number of k-tiles when both tile sizes divide T.

    With a ``window`` (query ``i`` sees the keys ``i - window < j <= i``) the triangle
    becomes a band and the visit ``(first, full_from, n_full, last)``: no tile before
    ``first`` holds an allowed key, tiles ``[first, full_from)`` are crossed by the
    window's lower edge (masked), tiles ``[full_from, n_full)`` lie wholly inside the
    band, tiles ``[n_full, last)`` are crossed by the diagonal. Where ``full_from``
    passes ``n_full`` (a window smaller than the tiles) the tiles between are crossed
    by both edges, and none is full: ``band_k_loops`` has the three loops.

    The indices are LOCAL — exact for segmented layouts too, because causal segmented
    calls require identical, monotone q/k segment maps (zigzag: both sides are the
    same [chunk i, chunk 2n-1-i] interleave), under which local order equals global
    order."""
    n_full = (q_tile * block_q + 1) // block_k
    last = ((q_tile + 1) * block_q + block_k - 1) // block_k
    if window is None:
        return n_full, last
    # the smallest key the tile's first query sees, and the smallest the last one sees
    first = _bound(q_tile * block_q - window + 1, low=0) // block_k
    full_from = (_bound((q_tile + 1) * block_q - window, low=0) + block_k - 1) // block_k
    return first, full_from, n_full, last


def causal_q_tiles(k_tile, block_q, block_k, window=None):
    """The q-tiles that visit a causal k-tile, as ``(first, full_from)``: no q-tile
    before ``first`` holds an unmasked element against it, tiles ``[first, full_from)``
    are crossed by the diagonal, tiles from ``full_from`` on lie wholly on or below it
    (smallest query >= largest key). The backward kernel's view of the same schedule
    as ``causal_k_tiles``.

    With a ``window``, ``(first, full_from, full_to, end)``: tiles ``[full_from,
    full_to)`` lie wholly inside the band, tiles ``[full_to, end)`` are crossed by the
    window's far edge (the last query that sees the tile's first key is ``window - 1``
    past it), and no tile from ``end`` on sees the k-tile: the backward's loop ends there
    (``band_q_loops`` holds it to the number of q-tiles)."""
    first = (k_tile * block_k) // block_q
    full_from = ((k_tile + 1) * block_k + block_q - 2) // block_q
    if window is None:
        return first, full_from
    full_to = (k_tile * block_k + window) // block_q
    end = ((k_tile + 1) * block_k + window - 2) // block_q + 1
    return first, full_from, full_to, end


def _bound(x, low=None, top=None):
    """``x`` no smaller than ``low`` and no larger than ``top``: a Python int where all three
    are (the schedule test), else traced (``program_id`` in a kernel)."""
    if all(isinstance(v, int) for v in (x, low, top) if v is not None):
        x = x if low is None else max(x, low)
        return x if top is None else min(x, top)
    x = x if low is None else jnp.maximum(x, low)
    return x if top is None else jnp.minimum(x, top)


def band_k_loops(q_tile, block_q, block_k, window):
    """A banded q-tile's visit as three loops over k-tiles, ``((lo, hi), masked)`` each:
    the tiles an edge crosses run the masked body (which applies both edges), the ones
    between run the plain one. Where the window is smaller than the tiles the middle
    loop is empty and the masked ones meet."""
    first, full_from, n_full, last = causal_k_tiles(q_tile, block_q, block_k, window)
    m1 = _bound(full_from, top=last)
    m2 = _bound(n_full, low=m1)
    return ((first, m1), True), ((m1, m2), False), ((m2, last), True)


def band_q_loops(k_tile, block_q, block_k, window, num_q_tiles):
    """The backward's view of ``band_k_loops``: a k-tile's visiting q-tiles as three loops."""
    first, full_from, full_to, end = causal_q_tiles(k_tile, block_q, block_k, window)
    end = _bound(end, top=num_q_tiles)
    m1 = _bound(full_from, top=end)
    m2 = _bound(full_to, low=m1, top=end)
    return ((first, m1), True), ((m1, m2), False), ((m2, end), True)


def band_pairs(T, block_q, block_k, window):
    """``(visited, needed)`` query-key pairs of one causal call over ``T`` positions: what
    the tiles of the schedule above hold, and what the band (the triangle where ``window``
    is None) holds. Plain integers; a counter that costs nothing in a program."""
    visited = 0
    for i in range(T // block_q):
        if window is None:
            visited += causal_k_tiles(i, block_q, block_k)[1] * block_q * block_k
        else:
            loops = band_k_loops(i, block_q, block_k, window)
            visited += sum(hi - lo for (lo, hi), _ in loops) * block_q * block_k
    w = T if window is None else min(window, T)
    return visited, w * (w + 1) // 2 + (T - w) * w


_NT = (((1,), (1,)), ((), ()))     # dot_general dimension numbers of A.B^T
_TN = (((0,), (0,)), ((), ()))     # ... and of A^T.B


def _seen(q_pos, k_pos, window):
    """Where a causal query sees a key: on or below the diagonal and, with a ``window``,
    fewer than ``window`` positions back."""
    if window is None:
        return q_pos >= k_pos
    return (q_pos >= k_pos) & (q_pos - k_pos < window)


def _tile_positions(k_start, q_start, shape, maps):
    """(q, k) sequence coordinates of every element of a transposed tile [keys, queries]
    that starts at local key ``k_start`` and query ``q_start``; ``maps`` = (map_q, map_k)
    of ``_read_seed_ref`` takes them to global coordinates, ``None`` leaves them local."""
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (maps[0](q_pos), maps[1](k_pos)) if maps else (q_pos, k_pos)


def _split_refs(refs, has_seed, has_bias):
    """(seed_ref, bias_ref, the rest): the optional SMEM seed/offset operand and the
    bias row lead a kernel's refs, in that order, when present."""
    refs = list(refs)
    seed_ref = refs.pop(0) if has_seed else None
    bias_ref = refs.pop(0) if has_bias else None
    return seed_ref, bias_ref, refs


def _fwd_kernel(*refs, sm_scale, causal, block_k, seq_len, has_bias, rate, threshold,
                has_seed, seg, window=None):
    """Grid cell ``(b, i)`` holds q-tile ``i`` and walks the k-tiles it sees with the
    running (m, l, acc) of the online softmax. The tile is computed TRANSPOSED,
    S^T = K.Q^T as [block_k, block_q], so the softmax's per-query statistics are
    [1, block_q] rows: the max and the sum over keys run down the sublanes (elementwise
    across vregs) instead of across the lanes of every 8 query rows, and ``lse`` is
    stored as the row it is kept as. The accumulator is acc^T = V^T.P^T [D, block_q]
    (the V tile contracts its leading axis), turned once, when the q-tile is written."""
    seed_ref, bias_ref, (q_ref, k_ref, v_ref, o_ref, lse_ref) = _split_refs(
        refs, has_seed, has_bias)
    bq, d = q_ref.shape
    q_blk_idx = pl.program_id(1)
    # keep MXU operands in the input dtype (bf16): bf16-in/fp32-accumulate is the MXU's
    # native mode — upcasting to fp32 before the dot ran the matmuls many times slower.
    # sm_scale*log2e is pre-folded into q: scores come out of the MXU in base-2 units.
    q = (q_ref[...].astype(jnp.float32) * (sm_scale * LOG2E)).astype(q_ref.dtype)
    if has_seed:
        # see _read_seed_ref: the operand translates this call's LOCAL positions into
        # GLOBAL sequence coordinates for the dropout hash (and, in the segmented
        # zigzag layout, the causal mask), so chunked long-context tiles and
        # ring-attention shards regenerate the same bit stream / mask a single
        # whole-sequence kernel would.
        seed_u32, *maps = _read_seed_ref(seed_ref, seg)
        bh_u32 = pl.program_id(0).astype(jnp.uint32)
    else:
        maps = None
    if rate > 0:
        inv_keep = 1.0 / (1.0 - rate)
    if causal and window is None:
        n_full, last_blk = causal_k_tiles(q_blk_idx, bq, block_k)
    else:
        n_full = last_blk = seq_len // block_k

    m0 = jnp.full((1, bq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((1, bq), jnp.float32)
    acc0 = jnp.zeros((v_ref.shape[-1], bq), jnp.float32)

    def make_body(masked):
        def body(kb, carry):
            m, l, acc = carry
            keys = pl.ds(kb * block_k, block_k)
            s = jax.lax.dot_general(k_ref[keys, :], q, _NT,
                                    preferred_element_type=jnp.float32)  # [bk, bq] base-2
            if has_bias:
                s = s + (bias_ref[:, keys] * LOG2E).reshape(block_k, 1)
            if masked or rate > 0:
                q_glob, k_glob = _tile_positions(kb * block_k, q_blk_idx * bq,
                                                 (block_k, bq), maps)
            if masked:
                s = jnp.where(_seen(q_glob, k_glob, window), s, DEFAULT_MASK_VALUE)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp2(s - m_new)
            alpha = jnp.exp2(m - m_new)
            # the normalizer uses the UNdropped probabilities (torch dropout(softmax(s)))
            l_new = l * alpha + jnp.sum(p, axis=0, keepdims=True)
            if rate > 0:
                bits = _dropout_bits(seed_u32, bh_u32, q_glob, k_glob)
                p = p * ((bits >= jnp.uint32(threshold)).astype(jnp.float32) * inv_keep)
            pv = jax.lax.dot_general(v_ref[keys, :], p.astype(v_ref.dtype), _TN,
                                     preferred_element_type=jnp.float32)     # [dv, bq]
            return m_new, l_new, acc * alpha + pv
        return body

    if window is not None:
        # a band: the tiles an edge crosses masked, the ones between plain. A query whose
        # keys in the first tile are all masked carries m = the mask's value and weights
        # of one until its first allowed key arrives, whose alpha = exp2(mask - score) is
        # exactly zero: nothing of them is left (every query sees itself)
        carry = (m0, l0, acc0)
        for (lo, hi), masked in band_k_loops(q_blk_idx, bq, block_k, window):
            carry = jax.lax.fori_loop(lo, hi, make_body(masked), carry)
    else:
        carry = jax.lax.fori_loop(0, n_full, make_body(False), (m0, l0, acc0))
        if causal:
            carry = jax.lax.fori_loop(n_full, last_blk, make_body(True), carry)
    m, l, acc = carry
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l).T.astype(o_ref.dtype)
    # stored LSE stays in natural-log units (m is base-2)
    lse_ref[...] = m / LOG2E + jnp.log(l)


def _is_segmented(seed) -> bool:
    """Whether a packed seed/offset operand carries the (7,) segmented layout."""
    return seed is not None and np.shape(seed)[-1] == 7


def _aux_operands(seed, bias, B, H, T, rate, block_k_map=None):
    """(operands, in_specs) for the optional seed/bias inputs shared by all kernels.

    ``block_k_map``: None -> each grid cell sees the full [1, T] bias row; otherwise a
    (block, index_map) pair for k-blocked bias tiles.
    """
    operands, specs = [], []
    if seed is not None:
        # packed (3,) or (7,) offset operand — see _read_seed_ref on the
        # global-coordinate contract for the dropout hash and segmented causal mask
        operands.append(jnp.asarray(seed, jnp.int32))
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if bias is not None:
        operands.append(jnp.asarray(bias, jnp.float32).reshape(B, 1, T))
        if block_k_map is None:
            specs.append(pl.BlockSpec((None, 1, T), lambda b, i, H=H: (b // H, 0, 0)))
        else:
            blk, imap = block_k_map
            specs.append(pl.BlockSpec((None, 1, blk), imap))
    return operands, specs


def _per_shard(kernel_fn, arrays, seed, bias, rate, dims=None, out_dims="bh", heads=None):
    """``kernel_fn(*arrays, seed, bias)`` on each shard of the context mesh
    (partition.py): ``arrays`` are [B, H, ...] (or as ``dims`` says, the results as
    ``out_dims``, the heads cut in ``heads`` pieces at most), ``bias`` [B, 1, T], ``seed`` the
    packed dropout operand. The dropout hash counts (batch, head) from a shard's
    own first row, so every shard but the first folds its index into the seed:
    shards then draw different masks, and one device draws the reference's."""
    operands, dims = list(arrays), list(dims or ["bh"] * len(arrays))
    if seed is not None:
        operands.append(jnp.asarray(seed, jnp.int32))
        dims.append("")
    if bias is not None:
        operands.append(jnp.asarray(bias, jnp.float32).reshape(arrays[0].shape[0], 1, -1))
        dims.append("b")

    def local(shard, *ops):
        ops = list(ops)
        b = ops.pop() if bias is not None else None
        s = ops.pop() if seed is not None else None
        if s is not None and rate > 0 and not isinstance(shard, int):
            s = s.at[0].add(shard.astype(jnp.int32) * jnp.int32(-1640531527))
        return kernel_fn(*ops, s, b)

    return shard_over_mesh(local, operands, dims, out_dims, heads)


# ---------------------------------------------------------------------------
# VMEM budget
# ---------------------------------------------------------------------------
# Each kernel tells the compiler how much VMEM its blocks and working tiles take,
# computed from T, D and the tile sizes; below the compiler's own default nothing
# changes. A v5e core has 128 MiB, of which the compiler grants 16 MiB unasked.

_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


def _vmem_limit(need):
    return max(_DEFAULT_SCOPED_VMEM, need + need // 4)


def _padded(rows, D, itemsize):
    """Bytes of a [rows, D] block in VMEM: the head dimension pads to the 128 lanes."""
    return rows * (-(-D // 128) * 128) * itemsize


def _fwd_vmem_bytes(T, D, Dv, block_q, block_k, itemsize):
    """K and V whole (two pipeline buffers each), the q and out tiles and the lse row,
    and four float32 tiles of working set (s, p and the mask/dropout temporaries); K and q
    are ``D`` wide, V and out ``Dv``."""
    return (2 * (_padded(T, D, itemsize) + _padded(T, Dv, itemsize))
            + 2 * (_padded(block_q, D, itemsize) + _padded(block_q, Dv, itemsize))
            + 2 * 8 * block_q * 4 + 4 * block_q * block_k * 4)


def _bwd_vmem_bytes(T, D, Dv, block_q, block_k, itemsize):
    """q and dO whole (two pipeline buffers each), the lse and delta rows (a [1, T]
    float32 block pads to 8 sublanes), the k/v/dk/dv tiles, the dq block and its
    float32 accumulator [D, T], and six float32 tiles of working set (s, p, dp, ds and
    the mask/dropout temporaries); q, k, dq and dk are ``D`` wide, v, dO and dv ``Dv``."""
    return (2 * (_padded(T, D, itemsize) + _padded(T, Dv, itemsize)) + 2 * 2 * 8 * T * 4
            + 2 * 2 * (_padded(block_k, D, itemsize) + _padded(block_k, Dv, itemsize))
            + 2 * _padded(T, D, itemsize) + max(D, 8) * T * 4
            + 6 * block_q * block_k * 4)


def _flash_fwd(q, k, v, seed, bias, sm_scale, causal, rate, block_q, block_k, interpret,
               window=None):
    return _per_shard(
        functools.partial(_flash_fwd_local, sm_scale=sm_scale, causal=causal, rate=rate,
                          block_q=block_q, block_k=block_k, interpret=interpret,
                          window=window),
        (q, k, v), seed, bias, rate)


LANES = 128


def _is_rows(a):
    """Whether an operand lies as its projection wrote it, [B, T, heads * width] (else [B, heads, T, width])."""
    return a.ndim == 3


def _rows_dims(q, k, v, widths):
    """(B, T, H, Hkv, group) of operands ``widths = (D, Dv)`` wide a head, each row-major or
    head-major: what a shard holds, read off its own operands."""
    count = lambda a, width: a.shape[-1] // width if _is_rows(a) else a.shape[1]      # noqa: E731
    B, T = q.shape[0], q.shape[1 if _is_rows(q) else 2]
    H, Hkv = count(q, widths[0]), count(k, widths[0])
    return B, T, H, Hkv, H // Hkv


def _head_spec(like, rows, width, H, group, tiled):
    """The BlockSpec of an operand or a result laid out as ``like``: ``rows`` of head
    ``b % H // group`` of batch row ``b // H`` (``group`` 1: a query head's own), the grid's
    second index counting them where ``tiled``. That is row ``b // group`` of ``[B * heads, T,
    width]`` (``_flat``), or lane block ``head`` of ``[B, T, heads * width]``."""
    at = (lambda j: j) if tiled else (lambda j: 0)
    if not _is_rows(like):
        head = (lambda b: b) if group == 1 else (lambda b: b // group)
        return pl.BlockSpec((None, rows, width), lambda b, j: (head(b), at(j), 0))
    # lax.div / lax.rem, not ``//`` / ``%``: the grid's indices are never negative, and the flooring
    # forms trace and lower a sign and a select each, in every index map of every call
    div, rem = jax.lax.div, jax.lax.rem
    return pl.BlockSpec((None, rows, width), lambda b, j: (div(b, H), at(j), div(rem(b, H), group)))


def _flat(a):
    """An operand as a kernel takes it: row-major as it is, head-major with batch and heads merged."""
    return a if _is_rows(a) else a.reshape(-1, *a.shape[2:])


def _flat_shape(like, B, T, H, width):
    """The ``_flat`` shape of a result laid out as ``like`` at ``H`` heads of ``width``."""
    return jax.ShapeDtypeStruct((B, T, H * width) if _is_rows(like) else (B * H, T, width), like.dtype)


def _flash_fwd_local(q, k, v, seed, bias, *, sm_scale, causal, rate, block_q, block_k,
                     interpret, window=None, widths=None):
    """The forward kernel on one shard. ``widths`` None: ``[B, heads, T, width]`` operands, and the
    output so. ``widths = (D, Dv)`` (``flash_attention_rows``): each operand where it lies
    (``_is_rows``) and the output ``[B, T, H * Dv]``: the same kernel, a head's tiles found as lane
    blocks (``_head_spec``). ``lse`` is ``[B, H, T]`` either way."""
    D, Dv = widths or (q.shape[-1], v.shape[-1])       # q and k | v and the output
    B, T, H, _, group = _rows_dims(q, k, v, (D, Dv))   # group: query heads a key/value head serves, side by side
    out_like = jax.ShapeDtypeStruct((B, T, H * Dv) if widths else (B, H, T, Dv), q.dtype)

    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_k=block_k, seq_len=T, has_bias=bias is not None,
                               rate=rate, threshold=_keep_threshold(rate),
                               has_seed=seed is not None, seg=_is_segmented(seed),
                               window=window)
    aux, aux_specs = _aux_operands(seed, bias, B, H, T, rate)
    call = pl.pallas_call(
        kernel,
        grid=(B * H, pl.cdiv(T, block_q)),
        in_specs=aux_specs + [
            _head_spec(q, block_q, D, H, 1, True),
            _head_spec(k, T, D, H, group, False),
            _head_spec(v, T, Dv, H, group, False),
        ],
        out_specs=[
            _head_spec(out_like, block_q, Dv, H, 1, True),
            pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            _flat_shape(out_like, B, T, H, Dv),
            # LSE carried as [B*H, 1, T]: TPU block shapes need the trailing two dims
            # tileable, so the per-row scalar rides in a (1, block_q) lane layout
            jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem_limit(_fwd_vmem_bytes(T, D, Dv, block_q, block_k,
                                                         q.dtype.itemsize))),
        interpret=interpret,
        name="ds_flash_fwd",
    )
    with jax.named_scope("ds_flash_fwd"):
        out, lse = call(*aux, _flat(q), _flat(k), _flat(v))
    return out.reshape(out_like.shape), lse.reshape(B, H, T)


# ---------------------------------------------------------------------------
# backward kernel
# ---------------------------------------------------------------------------

def _bwd_kernel(*refs, sm_scale, causal, block_q, seq_len, has_bias, rate, threshold,
                has_seed, seg, window=None):
    """One pass over the tiles of one (batch, head): grid cell ``(b, j)`` holds k-tile
    ``j`` and walks the q-tiles that see it; every visit computes the tile once (five
    matmuls, one ``exp2``) and feeds all three gradients. dK and dV of the k-tile ride
    the loop; dQ accumulates TRANSPOSED in the float32 scratch ``dq_acc`` [D, T] across
    the cells of one ``b`` (the k axis of the grid is sequential) and is turned and
    rounded once, after the last k-tile. No partial dQ goes through HBM.

    The tile is computed TRANSPOSED, S^T = K.Q^T as [block_k, block_q]: ``lse`` and
    ``delta`` are used as the [1, block_q] rows they are stored as, and dS^T is the
    left operand of dK += dS^T.Q and the right operand of dQ^T += K^T.dS^T as it
    stands (as P^T is of dV += P^T.dO); only the small K tile contracts its leading
    axis."""
    seed_ref, bias_ref, rest = _split_refs(refs, has_seed, has_bias)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dq_ref, dk_ref, dv_ref, dq_acc) = rest
    bk, d = k_ref.shape
    k_blk_idx = pl.program_id(1)
    num_q_blocks = seq_len // block_q

    @pl.when(k_blk_idx == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k = k_ref[...]
    v = v_ref[...]
    if has_bias:
        bias2 = (bias_ref[...] * LOG2E).reshape(bk, 1)     # this k-tile's keys, once a cell
    if has_seed:
        seed_u32, *maps = _read_seed_ref(seed_ref, seg)
        bh_u32 = pl.program_id(0).astype(jnp.uint32)
    else:
        maps = None
    if rate > 0:
        inv_keep = 1.0 / (1.0 - rate)

    def make_body(masked):
        def body(qb, carry):
            dk, dv = carry
            rows = pl.ds(qb * block_q, block_q)
            q_blk = q_ref[rows, :]
            do_blk = do_ref[rows, :]
            lse2 = lse_ref[:, rows] * LOG2E                # [1, bq] natural -> base-2
            delta = delta_ref[:, rows]
            # base-2 softmax with sm_scale*log2e folded into q, exactly as the forward
            # rounds it: the scores here are the scores ``lse`` was taken from. dK = dS^T.Q
            # takes q as it came.
            q_s = (q_blk.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q_blk.dtype)
            s = jax.lax.dot_general(k, q_s, _NT, preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do_blk, _NT, preferred_element_type=jnp.float32)
            if has_bias:
                s = s + bias2
            if masked or rate > 0:
                q_glob, k_glob = _tile_positions(k_blk_idx * bk, qb * block_q,
                                                 (bk, block_q), maps)
            if masked:
                s = jnp.where(_seen(q_glob, k_glob, window), s, DEFAULT_MASK_VALUE)
            p = jnp.exp2(s - lse2)
            if rate > 0:
                bits = _dropout_bits(seed_u32, bh_u32, q_glob, k_glob)
                keep = (bits >= jnp.uint32(threshold)).astype(jnp.float32) * inv_keep
                p_drop = p * keep
                dp = dp * keep
            else:
                p_drop = p
            ds = (p * (dp - delta)).astype(q_blk.dtype)
            p_drop = p_drop.astype(do_blk.dtype)
            dv = dv + jnp.dot(p_drop, do_blk, preferred_element_type=jnp.float32)
            dk = dk + jnp.dot(ds, q_blk, preferred_element_type=jnp.float32)
            dq_acc[:, rows] += jax.lax.dot_general(k, ds, _TN,
                                                   preferred_element_type=jnp.float32)
            return dk, dv
        return body

    init = (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, v_ref.shape[-1]), jnp.float32))
    if window is not None:
        dk, dv = init
        for (lo, hi), masked in band_q_loops(k_blk_idx, block_q, bk, window, num_q_blocks):
            dk, dv = jax.lax.fori_loop(lo, hi, make_body(masked), (dk, dv))
    elif causal:
        first_blk, full_from = causal_q_tiles(k_blk_idx, block_q, bk)
        carry = jax.lax.fori_loop(first_blk, full_from, make_body(True), init)
        dk, dv = jax.lax.fori_loop(full_from, num_q_blocks, make_body(False), carry)
    else:
        dk, dv = jax.lax.fori_loop(0, num_q_blocks, make_body(False), init)
    dk_ref[...] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(k_blk_idx == pl.num_programs(1) - 1)
    def _():
        dq_ref[...] = (dq_acc[...].T * sm_scale).astype(dq_ref.dtype)


def _flash_bwd(res, g, seed, bias, sm_scale, causal, rate, block_q, block_k, interpret,
               g_lse=None, window=None):
    q, k, v, out, lse = res
    do = g
    # delta = rowsum(do * o): the softmax-normalization correction term (valid under
    # dropout too: do.o = sum_j probs_j * keep_j * (do.v_j) = sum_j probs_j * dprobs_j)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B,H,T]
    if g_lse is not None:
        # An LSE cotangent folds into delta: dL/ds_ij gains g_lse_i * p_ij (softmax
        # jacobian of logsumexp), so ds = p*(dp - (delta - g_lse)) — the whole lse
        # gradient costs one subtraction. dv is untouched (lse doesn't read V).
        delta = delta - g_lse.astype(jnp.float32)
    return _per_shard(
        functools.partial(_flash_bwd_local, sm_scale=sm_scale, causal=causal, rate=rate,
                          block_q=block_q, block_k=block_k, interpret=interpret,
                          window=window),
        (q, k, v, do, lse, delta), seed, bias, rate)


def _sum_lane_blocks(a, group, width):
    """``[B, T, n * group * width] -> [B, T, n * width]``: the sum, in float32, of each ``group``
    neighbouring blocks of ``width`` lanes, as static slices of the last axis (a reduction over
    ``[B, T, n, group, width]`` makes the chip's compiler lay the whole array out anew)."""
    block = lambda i: a[..., i * width:(i + 1) * width].astype(jnp.float32)      # noqa: E731
    return jnp.concatenate([sum(block(c * group + g) for g in range(group)).astype(a.dtype)
                            for c in range(a.shape[-1] // (group * width))], axis=-1)


def _delta_kernel(o_ref, do_ref, delta_ref):
    """``delta`` of one head's rows: the sum of ``o * dO`` over its lanes, as the [1, rows] row the
    backward reads (the product turned once, summed down the sublanes)."""
    turned = (o_ref[...].astype(jnp.float32) * do_ref[...].astype(jnp.float32)).T
    delta_ref[...] = jnp.sum(turned, axis=0, keepdims=True)


_DELTA_ROWS = 512      # of a head, a grid cell of ``_delta_rows``


def _delta_rows(out, do, width, interpret):
    """``rowsum(do * o)`` a head (``_flash_bwd``'s ``delta``) from the output and its cotangent as
    they lie, ``[B, T, H * width]``: ``[B, H, T]`` float32. A kernel of its own: a reduction over
    ``[B, T, H, width]`` makes the chip's compiler lay both arrays out anew."""
    B, T, lanes = out.shape
    H = lanes // width
    rows = math.gcd(_DELTA_ROWS, T)
    tile = pl.BlockSpec((None, rows, width), lambda b, i: (jax.lax.div(b, H), i, jax.lax.rem(b, H)))
    with jax.named_scope("ds_flash_delta"):
        return pl.pallas_call(
            _delta_kernel,
            grid=(B * H, T // rows),
            in_specs=[tile, tile],
            out_specs=pl.BlockSpec((None, 1, rows), lambda b, i: (b, 0, i)),
            out_shape=jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32),
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="ds_flash_delta",
        )(out, do).reshape(B, H, T)


def _flash_bwd_local(q, k, v, do, lse, delta, seed, bias, *, sm_scale, causal, rate,
                     block_q, block_k, interpret, window=None, widths=None):
    """The backward kernel on one shard; ``widths`` as ``_flash_fwd_local`` takes it. Under
    ``widths`` ``do`` lies as the output went, ``[B, T, H * Dv]``, ``delta`` is that OUTPUT (its
    row sums with ``do`` are made here, ``_delta_rows``), and dQ, dK and dV go out laid as q, k
    and v came."""
    D, Dv = widths or (q.shape[-1], v.shape[-1])
    B, T, H, Hkv, group = _rows_dims(q, k, v, (D, Dv))
    if widths:
        delta = _delta_rows(delta, do, Dv, interpret)

    # the grid walks k-tiles, so the bias operand is tiled per k-tile
    aux, aux_specs = _aux_operands(
        seed, bias, B, H, T, rate,
        block_k_map=(block_k, lambda b, j, H=H: (b // H, 0, j)))
    row = pl.BlockSpec((None, 1, T), lambda b, j: (b, 0, 0))
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, seq_len=T, has_bias=bias is not None,
                          rate=rate, threshold=_keep_threshold(rate),
                          has_seed=seed is not None, seg=_is_segmented(seed),
                          window=window),
        grid=(B * H, T // block_k),
        # q, k, dq and dk are D wide; v, dO and dv as wide as the values
        in_specs=aux_specs + [_head_spec(q, T, D, H, 1, False), _head_spec(k, block_k, D, H, group, True),
                              _head_spec(v, block_k, Dv, H, group, True), _head_spec(do, T, Dv, H, 1, False),
                              row, row],
        # a query head's dK and dV each: laid out as k and v, at H heads
        out_specs=[_head_spec(q, T, D, H, 1, False), _head_spec(k, block_k, D, H, 1, True),
                   _head_spec(v, block_k, Dv, H, 1, True)],
        out_shape=[_flat_shape(q, B, T, H, D), _flat_shape(k, B, T, H, D), _flat_shape(v, B, T, H, Dv)],
        scratch_shapes=[pltpu.VMEM((D, T), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(_bwd_vmem_bytes(T, D, Dv, block_q, block_k,
                                                         q.dtype.itemsize))),
        interpret=interpret,
        name="ds_flash_bwd_dkv",
    )
    with jax.named_scope("ds_flash_bwd_dkv"):
        dq, dk, dv = call(*aux, _flat(q), _flat(k), _flat(v), _flat(do), lse.reshape(B * H, 1, T),
                          delta.reshape(B * H, 1, T))
        if group > 1:      # a query head's dK and dV each: summed over the group in float32
            dk, dv = (_sum_lane_blocks(a, group, a.shape[-1] // H) if _is_rows(like) else
                      jnp.sum(a.reshape(B, H // group, group, T, a.shape[-1]).astype(jnp.float32),
                              axis=2).astype(k.dtype) for a, like in ((dk, k), (dv, v)))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_attention_core(q, k, v, bias, seed, causal, sm_scale, rate, block_q, block_k,
                          interpret, window=None):
    out, _ = _core_fwd_rule(q, k, v, bias, seed, causal, sm_scale, rate, block_q, block_k,
                            interpret, window)
    return out


def _resolve(q, sm_scale, block_q, block_k, causal, interpret, window=None):
    T = q.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if block_q is None or block_k is None:
        # Square tiles, so a causal diagonal tile holds nothing wholly masked. Sizes from
        # tests/perf/flash_sweep.py on a v5e (PR 25; device ms a call, D = 64, bf16,
        # forward / backward):
        #   [4, 25, 1024] causal: 128 1.37 / 1.60   256 0.59 / 0.70   512 0.32 / 0.71
        #                         1024 0.37 / 0.75 (one tile, half of it masked)
        #   [1, 16, 4096] causal: 512 0.54 / 1.14   1024 0.53 / 1.17
        #   [1, 16, 8192] causal: 512 1.99 / 4.14   1024 1.84 / 4.03
        #   [1, 16, 4096] full:   512 0.94 / 1.86   1024 0.80 / 1.73
        #   [1, 16, 8192] full:   512 3.70 / 7.34   1024 3.14 / 6.81
        # A tile under 512 leaves the MXU waiting on the loop; past 512 a causal call
        # pays for the masked half of ever larger diagonal tiles until T is long.
        # A windowed call (PR 45; ``--rows band``: D = 128, 32 query heads over 4 key/value
        # heads, bf16, [1, 32 over 4, 8192], forward / backward):
        #   window 1024: 256 3.31 / 3.58   512 1.86 / 3.60   1024 2.22 / 4.18
        #                512x256 2.23 / 3.73   256x512 3.20 / 3.84   1024x512 2.15 / 4.17
        #   no window:   256 10.64 / 10.48   512 4.91 / 9.12   1024 4.68 / 8.87
        # The band visits 1.25 / 1.50 / 2.00 times the pairs it needs at 256 / 512 / 1024:
        # 512 wins both ways (the forward of 256-tiles waits on its loop, 1024-tiles run a
        # masked body on every tile they visit), so a windowed call takes 512 at most: the
        # choice follows ``window`` alone, no key and no environment variable.
        side = 1024 if not causal or T >= 8192 else 512
        if window is not None:
            side = min(side, 512)
        block_q = block_q or side
        block_k = block_k or side

    def fit(b):
        # largest power-of-two-reduced block that divides the sequence length
        b = min(b, T)
        while T % b != 0:
            b //= 2
        return max(b, 1)

    block_q = fit(block_q)
    block_k = fit(block_k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return sm_scale, block_q, block_k, interpret


def _count_call(way, layout, heads, widths, T):
    """While a step program is traced every call leaves in the recorder which layout it took:
    ``flash.<fwd|bwd>.<lanes|heads_major>[<program>] <H>/<Hkv>x<D>|<Dv> at <T>``, once a
    trace of the call (``docs/telemetry.md``)."""
    from ...utils import spans
    spans.recorder().count_in_program(f"flash.{way}.{layout}", " %d/%dx%d|%d at %d" % (*heads, *widths, T))


def _count_heads_major(way, q, k, v):
    _count_call(way, "heads_major", (q.shape[1], k.shape[1]), (q.shape[-1], v.shape[-1]), q.shape[2])


def _core_fwd_rule(q, k, v, bias, seed, causal, sm_scale, rate, block_q, block_k,
                   interpret, window=None):
    sm_scale_, bq, bk, interp = _resolve(q, sm_scale, block_q, block_k, causal,
                                         interpret, window)
    _count_heads_major("fwd", q, k, v)
    assert q.shape[2] % bq == 0 and q.shape[2] % bk == 0, \
        f"seq_len {q.shape[2]} must be divisible by block sizes ({bq}, {bk})"
    out, lse = _flash_fwd(q, k, v, seed, bias, sm_scale_, causal, rate, bq, bk, interp,
                          window)
    # Name what a jax.checkpoint round the call may keep, and hand the NAMED ``out`` on as
    # the primal too, so that the residual and the value the caller goes on with are one
    # variable. A name on the residual alone keeps the backward kernels' operand and still
    # re-runs this kernel for the primal (Ouro's gradient program compiled for a v5e, PR 38:
    # 12 forward calls, 6 of them rematted, with or without it); with a second name at the
    # caller the tensor is kept twice. So: save_only_these_names("attn_out", "attn_lse")
    # keeps ``out`` once and replays no forward kernel. (The name lowers to nothing: a
    # program with no jax.checkpoint round the call is the same program, byte for byte.)
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out, "attn_out")
    return out, (q, k, v, out, checkpoint_name(lse, "attn_lse"), bias, seed)


def _core_bwd_rule(causal, sm_scale, rate, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse, bias, seed = res
    sm_scale_, bq, bk, interp = _resolve(q, sm_scale, block_q, block_k, causal,
                                         interpret, window)
    _count_heads_major("bwd", q, k, v)
    dq, dk, dv = _flash_bwd((q, k, v, out, lse), g, seed, bias, sm_scale_, causal, rate,
                            bq, bk, interp, window=window)
    # bias is the (non-trainable) padding mask: cotangent is zero by contract; seed is
    # integer-valued, whose tangent space is float0
    dbias = None if bias is None else jnp.zeros_like(bias)
    dseed = None if seed is None else np.zeros(np.shape(seed), jax.dtypes.float0)
    return dq, dk, dv, dbias, dseed


_flash_attention_core.defvjp(_core_fwd_rule, _core_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_attention_core_lse(q, k, v, bias, seed, causal, sm_scale, rate, block_q,
                              block_k, interpret):
    out, res = _core_fwd_rule(q, k, v, bias, seed, causal, sm_scale, rate, block_q,
                              block_k, interpret)
    return out, res[4]


def _core_lse_fwd(q, k, v, bias, seed, causal, sm_scale, rate, block_q, block_k,
                  interpret):
    out, res = _core_fwd_rule(q, k, v, bias, seed, causal, sm_scale, rate, block_q,
                              block_k, interpret)
    return (out, res[4]), res


def _core_lse_bwd(causal, sm_scale, rate, block_q, block_k, interpret, res, g):
    g_out, g_lse = g
    q, k, v, out, lse, bias, seed = res
    sm_scale_, bq, bk, interp = _resolve(q, sm_scale, block_q, block_k, causal,
                                         interpret)
    _count_heads_major("bwd", q, k, v)
    dq, dk, dv = _flash_bwd((q, k, v, out, lse), g_out, seed, bias, sm_scale_, causal,
                            rate, bq, bk, interp, g_lse=g_lse)
    dbias = None if bias is None else jnp.zeros_like(bias)
    dseed = None if seed is None else np.zeros(np.shape(seed), jax.dtypes.float0)
    return dq, dk, dv, dbias, dseed


_flash_attention_core_lse.defvjp(_core_lse_fwd, _core_lse_bwd)


def _seed_vec(seed, q_offset, k_offset):
    """Pack (seed, global q offset, global k offset) into the (3,) int32 operand the
    kernels read from SMEM. Offsets may be traced (ring attention derives them from
    ``axis_index``)."""
    return jnp.stack([jnp.asarray(seed, jnp.int32).reshape(()),
                      jnp.asarray(q_offset, jnp.int32).reshape(()),
                      jnp.asarray(k_offset, jnp.int32).reshape(())])


def _seed_vec_seg(seed, q_segments, k_segments, T_q, T_k,
                  q_offset=0, k_offset=0):
    """Pack the (7,) segmented operand ``[seed, q_off0, k_off0, q_half, q_off1,
    k_half, k_off1]`` (see ``_read_seed_ref``). A ``*_segments`` pair gives the
    global start offsets of the two equal halves of that side's local sequence;
    ``None`` means the side is contiguous at the plain scalar offset (its half
    boundary is pushed past the end so the first branch always wins)."""
    if q_segments is not None:
        q0, q1, qh = q_segments[0], q_segments[1], T_q // 2
    else:
        q0, q1, qh = q_offset, 0, T_q
    if k_segments is not None:
        k0, k1, kh = k_segments[0], k_segments[1], T_k // 2
    else:
        k0, k1, kh = k_offset, 0, T_k
    return jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                      for x in (seed, q0, k0, qh, q1, kh, k1)])


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             sm_scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             dropout_rate: float = 0.0, dropout_seed=None,
                             dropout_q_offset=0, dropout_k_offset=0,
                             q_segments=None, k_segments=None, window=None):
    """Flash attention returning ``(out, lse)``, BOTH differentiable.

    ``lse`` is the per-row log-sum-exp of the scaled scores ([B, H, T_q], natural
    log) — the quantity sequence-parallel/ring attention combines across k/v chunks
    (parallel/ring_attention.py). The lse cotangent folds into the standard flash
    backward's delta term, so the extra gradient is effectively free.

    ``dropout_q_offset``/``dropout_k_offset`` translate this call's local positions
    into global sequence coordinates for the dropout PRNG, so chunk/ring callers
    sample the same mask a whole-sequence kernel would (they may be traced values).

    ``q_segments``/``k_segments``: optional ``(off0, off1)`` pairs declaring that
    side's local sequence to be TWO concatenated global segments of equal length
    (the zigzag ring's [chunk i, chunk 2n-1-i] interleave): local position ``p``
    maps to global ``off0 + p`` in the first half and ``off1 + (p - half)`` in the
    second. Both the causal mask and the dropout hash then run in global
    coordinates. A causal segmented call requires q_segments == k_segments with
    ``off0 < off1`` (identical monotone maps keep the kernel's local block-pruning
    bounds exact); offsets may be traced. Overrides ``dropout_*_offset`` for the
    segmented side.
    """
    if window is not None:
        raise ValueError(
            f"flash_attention_with_lse: a window of {window}: the partial-attention path "
            "(the chunked long-context kernel, the ring and its zigzag segments) has no "
            "band; it would run the whole triangle")
    rate = float(dropout_rate)
    if rate > 0:
        assert dropout_seed is not None, "dropout_rate > 0 requires a dropout_seed"
    segmented = q_segments is not None or k_segments is not None
    if segmented and causal:
        assert q_segments is not None and k_segments is not None, (
            "causal segmented attention requires BOTH q_segments and k_segments "
            "(identical maps keep local block pruning exact)")
    if segmented and (causal or rate > 0):
        seed = _seed_vec_seg(dropout_seed if dropout_seed is not None else 0,
                             q_segments, k_segments, q.shape[2], k.shape[2],
                             dropout_q_offset, dropout_k_offset)
    elif rate > 0:
        seed = _seed_vec(dropout_seed, dropout_q_offset, dropout_k_offset)
    else:
        seed = None
    return _flash_attention_core_lse(q, k, v, None, seed, bool(causal), sm_scale,
                                     rate, block_q, block_k, interpret)


def _merge_partial(o, lse, o_new, lse_new):
    """Online-softmax merge of normalized partials (fp32 accumulator)."""
    lse_out = jnp.logaddexp(lse, lse_new)
    o_out = (o * jnp.exp(lse - lse_out)[..., None]
             + o_new.astype(jnp.float32) * jnp.exp(lse_new - lse_out)[..., None])
    return o_out, lse_out


# The whole-K/V-resident kernel exceeds scoped VMEM (16 MB) past this sequence
# length at d=64 (measured: T=16384 needs 16.16 MB); longer single-chip sequences
# stream K/V in chunks below.
_RESIDENT_T_LIMIT = 8192


def _flash_attention_chunked(q, k, v, causal, sm_scale, interpret, chunk,
                             rate=0.0, seed=None, block_q=None, block_k=None):
    """Single-chip long-context flash: decompose the [T, T] attention into equal
    ``chunk x chunk`` tiles, run the resident kernel per (q-chunk, k-chunk) pair
    and merge each q-chunk's (out, lse) partials — the sequential analog of ring
    attention's combine (same `flash_attention_with_lse` + online merge, so fully
    differentiable; one compiled kernel shape reused for every pair). Causal is
    EXACT with no wasted compute: a q-chunk visits only its <= k-chunks, the
    diagonal pair with the in-kernel triangular mask. Attention dropout works at
    any length: each tile hashes GLOBAL (q, k) coordinates via the per-tile
    offsets, so the sampled mask equals the whole-sequence kernel's
    (``dropout_keep_reference`` at full T is the oracle)."""
    B, H, T, D = q.shape
    n = T // chunk
    rows = []
    for i in range(n):
        qi = q[:, :, i * chunk:(i + 1) * chunk]
        o = lse = None
        for c in range(i + 1 if causal else n):
            ks = k[:, :, c * chunk:(c + 1) * chunk]
            vs = v[:, :, c * chunk:(c + 1) * chunk]
            oc, lc = flash_attention_with_lse(qi, ks, vs, causal=(causal and c == i),
                                              sm_scale=sm_scale, interpret=interpret,
                                              block_q=block_q, block_k=block_k,
                                              dropout_rate=rate, dropout_seed=seed,
                                              dropout_q_offset=i * chunk,
                                              dropout_k_offset=c * chunk)
            if o is None:  # adopt the first partial; no merge against -inf init
                o, lse = oc.astype(jnp.float32), lc
            else:
                o, lse = _merge_partial(o, lse, oc, lc)
        rows.append(o)
    return jnp.concatenate(rows, axis=2).astype(q.dtype)


def _chunk_for(T: int) -> int:
    """Largest divisor of T not exceeding the resident VMEM ceiling (halving from
    the limit keeps chunks 128-aligned for any even T)."""
    c = _RESIDENT_T_LIMIT
    while c > 1 and T % c != 0:
        c //= 2
    return c


def flash_attention(q, k, v, causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None, block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    bias=None, dropout_rate: float = 0.0, dropout_seed=None,
                    window: Optional[int] = None):
    """Blocked flash attention on [B, H, T, D] tensors. Differentiable in q/k/v.

    ``bias``: optional additive key bias, any shape squeezable to [B, T_k] (the BERT
    padding mask [B,1,1,T] included) — fused into the in-kernel softmax, replacing the
    reference's scale+mask softmax kernel (csrc/transformer/softmax_kernels.cu).
    ``bias`` receives NO gradient (it is stop_gradient'ed here): it is a padding/attention
    mask, not a learnable table. Route learnable additive biases (ALiBi slopes, relative
    position tables) through q/k instead.
    ``dropout_rate``/``dropout_seed``: in-kernel attention dropout over the post-softmax
    probabilities (csrc/transformer/dropout_kernels.cu); the seed is a traced operand so
    remat replays identical masks. ``dropout_keep_reference`` reproduces the exact mask
    for parity tests.
    ``window``: static; a causal query ``i`` sees the keys ``i - window < j <= i`` (a
    sliding-window layer): the tile schedule is a band (``band_k_loops``) and tiles outside
    it are never visited. Causal calls at ``T <= 8192`` only.
    """
    rate = float(dropout_rate)
    if rate > 0:
        assert dropout_seed is not None, "dropout_rate > 0 requires a dropout_seed"
    T_k = k.shape[2]
    if window is not None:
        assert causal and int(window) >= 1, "a window belongs to a causal call, and is >= 1"
        window = int(window)
        if T_k > _RESIDENT_T_LIMIT:
            raise ValueError(
                f"flash_attention: a window of {window} at seq_len {T_k}: the chunked "
                f"long-context path (T > {_RESIDENT_T_LIMIT}) has no band; it would run the "
                "whole triangle")
    if T_k > _RESIDENT_T_LIMIT and not (interpret or jax.default_backend() != "tpu"):
        # Past the resident kernel's scoped-VMEM ceiling (the K/V operands are
        # whole-sequence-resident regardless of block sizes): decompose into chunk
        # tiles. Dropout works at any length (tiles hash global coordinates); an
        # additive bias or non-square attention cannot take the chunked path, and
        # silently compiling the resident kernel would fail deep inside Mosaic —
        # raise the constraint instead.
        chunk = _chunk_for(T_k)
        if q.shape[2] == T_k and bias is None and chunk >= 1024:
            return _flash_attention_chunked(q, k, v, bool(causal), sm_scale, interpret,
                                            chunk=chunk, rate=rate, seed=dropout_seed,
                                            block_q=block_q, block_k=block_k)
        reasons = []
        if q.shape[2] != T_k:
            reasons.append(f"q_len ({q.shape[2]}) != k_len ({T_k}) — chunking assumes "
                           "square self-attention")
        if bias is not None:
            reasons.append("an additive bias is not supported on the chunked path "
                           "(fold padding into shorter sequences or segment masks)")
        if chunk < 1024:
            reasons.append(f"seq_len {T_k} has no divisor chunk >= 1024 (largest: "
                           f"{chunk}) — pad the sequence to a multiple of 1024")
        raise ValueError(
            f"flash_attention: seq_len {T_k} exceeds the whole-K/V-resident kernel's "
            f"scoped-VMEM ceiling (T <= {_RESIDENT_T_LIMIT}) and the chunked "
            f"long-context path is ineligible: {'; '.join(reasons)}.")
    seed = _seed_vec(dropout_seed, 0, 0) if rate > 0 else None
    if bias is not None:
        B, T_k = q.shape[0], k.shape[2]
        # no-grad contract made explicit in the jaxpr: a learnable bias passed here
        # would otherwise silently train with zero gradient (see docstring)
        bias = jax.lax.stop_gradient(jnp.asarray(bias, jnp.float32).reshape(B, 1, T_k))
    return _flash_attention_core(q, k, v, bias, seed, bool(causal), sm_scale, rate,
                                 block_q, block_k, interpret, window)


# ---------------------------------------------------------------------------
# the output where the output projection reads it, each operand where its producer leaves it
# ---------------------------------------------------------------------------
# A projection writes, and reads, [B, T, H * D]. Head ``h``'s [rows, D] tile is lane block ``h``
# of that array wherever D is a multiple of the 128 lanes, so the kernels above read and write it
# through their index maps alone (``lanes``, ``_head_spec``). Any other call is turned head-major by
# the entry. An operand may still come head-major, [B, heads, T, width] (``_is_rows``): a rotary turn
# or a norm a head between a projection and the kernel is a pass that writes that layout for nothing,
# and the chip's compiler lays a [B, T, heads, width] result out head-major whatever the program says.

def layout_of(D, Dv, window=None):
    """Which way a call runs: ``lanes`` (a head is a lane block of a row-major array) or
    ``heads_major``. What the call itself shows decides: its widths, and whether it is banded. The
    band's backward reads whole strided blocks for a few tiles' worth of work, and measured 6.9 %
    slower a call on row-major operands (``mellum2_ep4_d4_train_1chip`` -0.33 %: PERF.md, PR 60)."""
    return "lanes" if D % LANES == 0 and Dv % LANES == 0 and window is None else "heads_major"


def _turned_shape(a, heads):
    """``(B, heads, T, width)`` of an operand that holds ``heads`` heads, whichever way it lies."""
    return (a.shape[0], heads, a.shape[1], a.shape[2] // heads) if _is_rows(a) else a.shape


def _rows_plan(q, k, v, heads, sm_scale, block_q, block_k, causal, interpret):
    """``(widths, T, pieces, tiles...)`` of a call whose operands each lie row-major or head-major:
    ``_resolve``'s tiles at the head-major shape, and the most pieces the heads may be cut in over
    a mesh."""
    H, Hkv = heads
    like = jax.ShapeDtypeStruct(_turned_shape(q, H), q.dtype)
    T, widths = like.shape[2], (like.shape[3], _turned_shape(v, Hkv)[3])
    return (widths, T, math.gcd(H, Hkv)) + _resolve(like, sm_scale, block_q, block_k, causal, interpret)


def _dims(*arrays):
    return ["bth" if _is_rows(a) else "bh" for a in arrays]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_rows_core(q, k, v, seed, heads, causal, sm_scale, rate, block_q, block_k, interpret):
    return _rows_fwd_rule(q, k, v, seed, heads, causal, sm_scale, rate, block_q, block_k, interpret)[0]


def _rows_fwd_rule(q, k, v, seed, heads, causal, sm_scale, rate, block_q, block_k, interpret):
    from jax.ad_checkpoint import checkpoint_name
    widths, T, pieces, sm_scale_, bq, bk, interp = _rows_plan(
        q, k, v, heads, sm_scale, block_q, block_k, causal, interpret)
    assert T % bq == 0 and T % bk == 0, f"seq_len {T} must be divisible by block sizes ({bq}, {bk})"
    _count_call("fwd", "lanes", heads, widths, T)
    out, lse = _per_shard(
        functools.partial(_flash_fwd_local, widths=widths, sm_scale=sm_scale_, causal=causal,
                          rate=rate, block_q=bq, block_k=bk, interpret=interp),
        (q, k, v), seed, None, rate, dims=_dims(q, k, v), out_dims=("bth", "bh"), heads=pieces)
    # the names ``_core_fwd_rule`` gives, for the same reason: what a jax.checkpoint keeps is
    # the residual and the primal at once
    out = checkpoint_name(out, "attn_out")
    return out, (q, k, v, out, checkpoint_name(lse, "attn_lse"), seed)


def _rows_bwd_rule(heads, causal, sm_scale, rate, block_q, block_k, interpret, res, g):
    q, k, v, out, lse, seed = res
    widths, T, pieces, sm_scale_, bq, bk, interp = _rows_plan(
        q, k, v, heads, sm_scale, block_q, block_k, causal, interpret)
    _count_call("bwd", "lanes", heads, widths, T)
    dq, dk, dv = _per_shard(
        functools.partial(_flash_bwd_local, widths=widths, sm_scale=sm_scale_, causal=causal,
                          rate=rate, block_q=bq, block_k=bk, interpret=interp),
        (q, k, v, g, lse, out), seed, None, rate,
        dims=_dims(q, k, v, g) + ["bh", "bth"], out_dims=tuple(_dims(q, k, v)), heads=pieces)
    dseed = None if seed is None else np.zeros(np.shape(seed), jax.dtypes.float0)
    return dq, dk, dv, dseed


_flash_rows_core.defvjp(_rows_fwd_rule, _rows_bwd_rule)


def flash_attention_rows(q, k, v, num_heads: int, num_kv_heads: Optional[int] = None,
                         causal: bool = False, sm_scale: Optional[float] = None,
                         block_q: Optional[int] = None, block_k: Optional[int] = None,
                         interpret: Optional[bool] = None, dropout_rate: float = 0.0,
                         dropout_seed=None, window: Optional[int] = None):
    """``flash_attention`` giving its output as the output projection reads it, ``[B, T, H * Dv]``,
    on operands where their producers leave them: each of ``q``, ``k`` and ``v`` is either what a
    projection wrote, ``[B, T, heads * width]`` (three axes), or head-major ``[B, heads, T,
    width]`` (four: where a rotary turn or a norm a head sits between the projection and the kernel
    the compiler folds the turn of the axes into that pass, and writes head-major for nothing);
    ``num_kv_heads`` None: every head its own. The cotangents come back as each primal lay, the
    output's is read ``[B, T, H * Dv]``. No head is turned to the front where the shapes let the
    kernels find it in place (``layout_of``): at widths that are multiples of 128 a head is a lane
    block of a row-major operand. Any other width, a banded call (``window``) and a sequence past the
    resident kernel's ``_RESIDENT_T_LIMIT`` are turned head-major here and go through ``flash_attention``
    as it is. ``dropout_*`` and ``window`` as there: a seed draws the mask ``flash_attention`` draws for it."""
    H = int(num_heads)
    Hkv = H if num_kv_heads is None else int(num_kv_heads)
    assert H % Hkv == 0, f"{H} query heads over {Hkv} key/value heads"
    split = lambda a, n: a.reshape(*a.shape[:2], n, -1).transpose(0, 2, 1, 3) if _is_rows(a) else a      # noqa: E731
    B, _, T, D = _turned_shape(q, H)
    Dv = _turned_shape(v, Hkv)[-1]
    assert _turned_shape(k, Hkv) == (B, Hkv, T, D) and _turned_shape(v, Hkv)[:3] == (B, Hkv, T), \
        f"{q.shape} / {k.shape} / {v.shape} do not hold {H} over {Hkv} heads at one length and width"
    rate = float(dropout_rate)
    if rate > 0:
        assert dropout_seed is not None, "dropout_rate > 0 requires a dropout_seed"
    if layout_of(D, Dv, window) == "heads_major" or T > _RESIDENT_T_LIMIT:
        y = flash_attention(split(q, H), split(k, Hkv), split(v, Hkv), causal, sm_scale, block_q,
                            block_k, interpret, dropout_rate=rate, dropout_seed=dropout_seed,
                            window=window)
        return y.transpose(0, 2, 1, 3).reshape(B, T, H * Dv)
    seed = _seed_vec(dropout_seed, 0, 0) if rate > 0 else None
    return _flash_rows_core(q, k, v, seed, (H, Hkv), bool(causal), sm_scale, rate, block_q,
                            block_k, interpret)
