"""Error-feedback sign-compressed allreduce over the mesh ``data`` axis.

TPU-native re-design of the reference's MPI+cupy compressed allreduce
(``deepspeed/runtime/custom_collectives.py:10-154`` and the two-phase algorithm in
``deepspeed/runtime/fp16/onebit_adam.py:104-228``):

- Phase 1 (reference ``gather_cuda/gather_host``): every worker sign-compresses its buffer
  (1 bit/element + one fp32 RMS scale) and sends chunk *j* to server *j*. Here that is one
  ``lax.all_to_all`` of **bit-packed uint8** signs (8/byte) inside ``shard_map`` — packed
  bytes stay on the ICI wire, the unpack + fp32 upcast happen after receipt — plus an
  ``all_gather`` of the dp scalar scales.
- Server reduction: each device averages the dp received sign·scale chunks, applies its
  server error feedback, and re-compresses (reference onebit_adam.py:168-189).
- Phase 2 (reference ``allgather_cuda/allgather_host``): ``all_gather`` of the bit-packed
  server signs + scalar server scales reconstructs the full averaged buffer everywhere.

Wire volume per device: signs are BIT-PACKED — 8 per uint8 byte (XLA has no
sub-byte wire type, so the pack/unpack is explicit VPU bit arithmetic around the
collectives) — so each phase ships n/8 bytes + O(dp·n_segs) fp32 scales, ~n/4
bytes total vs 7n for a ring fp32 allreduce: ~28× less communication at the
large-n asymptote, past the reference's packed-bits "5x" headline. Chunks not
divisible by 8 (callers using ``padded_size`` always are) fall back to int8
signs (1 byte each, the round-3 wire format).

The caller keeps persistent ``worker_error`` (dp, n) and ``server_error`` (dp, n/dp)
buffers sharded ``P('data', None)`` so each device's row is resident exactly where the
shard_map body needs it.
"""

from functools import partial

import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS

def _pack_signs(signs):
    """(..., m) int8 in {-1, +1} -> (..., m/8) uint8, 8 signs per byte (set bit
    = element positive). Lossless; m must be divisible by 8."""
    return jnp.packbits(signs > 0, axis=-1, bitorder="little")


def _unpack_signs(packed):
    """Inverse of ``_pack_signs``: (..., m/8) uint8 -> (..., m) int8 in {-1, +1}."""
    bits = jnp.unpackbits(packed, axis=-1, bitorder="little")
    return jnp.where(bits, jnp.int8(1), jnp.int8(-1))


def _signs_collective(collective, signs, packed):
    """Run ``collective`` over a signs array, bit-packed on the wire when the
    last dim divides by 8 (``packed``); shapes are unchanged either way."""
    if packed:
        return _unpack_signs(collective(_pack_signs(signs)))
    return collective(signs)


def compressed_allreduce(mesh: Mesh, x, worker_error, server_error,
                         axis_name: str = DATA_AXIS, seg_ids=None):
    """Average per-worker buffers ``x`` across the ``data`` axis with 1-bit compression.

    Args:
      mesh: the device mesh (collectives run over its ``axis_name`` axis).
      x: (dp, n) fp32 — row *i* is worker *i*'s buffer; sharded ``P(data, None)``.
      worker_error: (dp, n) fp32 persistent worker error feedback, sharded ``P(data, None)``.
      server_error: (dp, n // dp) fp32 persistent server error feedback, same sharding.
        ``n`` must be divisible by dp.
      seg_ids: optional STATIC (n,) int array mapping each element to a scale segment.
        The reference compresses per parameter TENSOR — each tensor gets its own RMS
        scale (onebit_adam.py keeps per-param state). A single global scale over the
        fused buffer overscales small-momentum tensors (LN scales, biases) to the
        buffer-wide RMS, and the error feedback then oscillates unboundedly — measured
        as training divergence a few steps after freeze_step. Segment scales restore the
        reference's per-tensor semantics at the cost of shipping an extra (n_segs,) fp32
        vector per phase. None = one segment (a single scale).

    Returns:
      (out, new_worker_error, new_server_error): ``out`` is the (n,) compressed average,
      replicated; the error buffers keep their (dp, ...) sharded layout.
    """
    dp = mesh.shape[axis_name]
    n = x.shape[-1]
    assert n % dp == 0, f"buffer size {n} must be divisible by dp={dp} (pad first)"
    chunk = n // dp
    seg_np = (np.zeros((n,), np.int32) if seg_ids is None
              else np.asarray(seg_ids, np.int32))
    assert seg_np.shape == (n,), f"seg_ids must be ({n},), got {seg_np.shape}"
    n_segs = int(seg_np.max()) + 1
    seg_const = jnp.asarray(seg_np)
    seg_counts = jnp.asarray(np.maximum(np.bincount(seg_np, minlength=n_segs), 1)
                             .astype(np.float32))

    def _seg_rms(buf, ids, counts):
        ss = jax.ops.segment_sum(jnp.square(buf), ids, num_segments=n_segs)
        return jnp.sqrt(ss / counts)

    def body(x_row, we_row, se_row):
        # Per-device shapes: x_row/we_row (1, n); se_row (1, chunk).
        corrected = x_row[0] + we_row[0]
        wscale = _seg_rms(corrected, seg_const, seg_counts)          # (n_segs,)
        signs = jnp.where(corrected >= 0, 1, -1).astype(jnp.int8)
        new_we = corrected - wscale[seg_const] * signs.astype(jnp.float32)

        # Phase 1: chunk j of my signs -> server j. Signs ride the wire
        # bit-packed (uint8, 8 signs/byte) when the chunk allows.
        packed = chunk % 8 == 0
        recv = _signs_collective(
            lambda s: jax.lax.all_to_all(s, axis_name, split_axis=0,
                                         concat_axis=0, tiled=False),
            signs.reshape(dp, chunk), packed)
        wscales = jax.lax.all_gather(wscale, axis_name)              # (dp, n_segs)

        my = jax.lax.axis_index(axis_name)
        seg_chunk = jax.lax.dynamic_slice(seg_const, (my * chunk,), (chunk,))
        per_elem_wscale = jnp.take_along_axis(wscales, seg_chunk[None, :]
                                              .repeat(dp, 0), axis=1)  # (dp, chunk)
        server_m = jnp.mean(recv.astype(jnp.float32) * per_elem_wscale, axis=0)
        corrected_s = server_m + se_row[0]
        chunk_counts = jnp.maximum(jax.ops.segment_sum(jnp.ones((chunk,), jnp.float32),
                                                       seg_chunk, num_segments=n_segs), 1.0)
        sscale = _seg_rms(corrected_s, seg_chunk, chunk_counts)      # (n_segs,)
        s_signs = jnp.where(corrected_s >= 0, 1, -1).astype(jnp.int8)
        new_se = corrected_s - sscale[seg_chunk] * s_signs.astype(jnp.float32)

        # Phase 2: allgather the compressed server chunks (bit-packed too).
        all_signs = _signs_collective(
            lambda s: jax.lax.all_gather(s, axis_name), s_signs, packed)
        sscales = jax.lax.all_gather(sscale, axis_name)              # (dp, n_segs)
        seg_by_chunk = seg_const.reshape(dp, chunk)
        per_elem_sscale = jnp.take_along_axis(sscales, seg_by_chunk, axis=1)
        out = (all_signs.astype(jnp.float32) * per_elem_sscale).reshape(n)
        return out, new_we[None], new_se[None]

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis_name, None), P(axis_name, None), P(axis_name, None)),
                       out_specs=(P(), P(axis_name, None), P(axis_name, None)),
                       check_vma=False)
    return fn(x, worker_error, server_error)


def padded_size(n: int, dp: int, lanes: int = 128) -> int:
    """Round ``n`` up so each of the dp server chunks is a whole multiple of the TPU
    lane width (reference pads to ``size * divider``, onebit_adam.py:294-299)."""
    quantum = dp * lanes
    return ((n + quantum - 1) // quantum) * quantum
