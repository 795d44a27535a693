"""Block-sparse matrix multiply ops (SDD / DSD / DDS modes).

TPU-native rebuild of the reference's Triton-backed ``MatMul``
(``deepspeed/ops/sparse_attention/matmul.py:595-729``; LUT builders l.90-320; the CUDA
``sdd_segment`` LUT segmenter ``csrc/sparse_attention/utils.cpp:14-119``). The reference
launches hand-written Triton kernels over a lookup table of nonzero blocks; here the same
semantics are expressed as XLA gather → nnz-batched ``einsum`` → scatter-add, which the
TPU compiler maps onto batched MXU matmuls. The LUT is just the row-major nonzero list of
the layout — no greedy segmentation pass is needed because XLA tiles the batched matmul
itself.

Sparse operands/results use a flat block format: ``[batch, nnz, block, block]`` where
``nnz`` enumerates ``layout.nonzero()`` in row-major ``(head, row_block, col_block)``
order (the same canonical order as ``block_sparse_attention.build_luts``).

Performance (earlier rig, not re-measured; BigBird block 128 at seq
4096/8192 bf16): the composed sdd→softmax→dsd attention runs at ~2.3–2.6× the fused
``block_sparse_attention`` Pallas kernel's time, and 6×/149× FASTER than dense
unfused XLA attention — these ops are a usable building block for custom sparse
patterns, but route hot attention paths through the fused kernel.

Modes (dense operands are ``[batch, heads, rows, cols]``):
- ``sdd``: dense @ dense -> sparse (only layout-active output blocks are computed)
- ``dsd``: sparse @ dense -> dense
- ``dds``: dense @ sparse -> dense
``trans_a`` / ``trans_b`` transpose the corresponding operand logically (for a sparse
operand this swaps its row/col LUTs and transposes each block), matching the reference's
use in backward passes.
"""

import jax.numpy as jnp
import numpy as np

__all__ = ["MatMul", "dense_to_sparse", "sparse_to_dense"]


def _lut(layout: np.ndarray):
    """Row-major nonzero list of a [heads, Mb, Nb] layout -> (h, i, j) index arrays."""
    layout = np.asarray(layout)
    assert layout.ndim == 3, f"layout must be [heads, blocks, blocks], got {layout.shape}"
    h, i, j = layout.nonzero()
    return h.astype(np.int32), i.astype(np.int32), j.astype(np.int32)


def dense_to_sparse(dense: jnp.ndarray, layout: np.ndarray, block: int) -> jnp.ndarray:
    """[B, H, M, N] dense -> [B, nnz, block, block] values of the layout-active blocks."""
    B, H, M, N = dense.shape
    hh, ii, jj = _lut(layout)
    blocked = dense.reshape(B, H, M // block, block, N // block, block)
    blocked = blocked.transpose(0, 1, 2, 4, 3, 5)  # [B, H, Mb, Nb, block, block]
    return blocked[:, hh, ii, jj]


def sparse_to_dense(vals: jnp.ndarray, layout: np.ndarray, block: int,
                    fill: float = 0.0) -> jnp.ndarray:
    """[B, nnz, block, block] values -> [B, H, M, N] dense with `fill` in inactive blocks."""
    layout = np.asarray(layout)
    H, Mb, Nb = layout.shape
    B = vals.shape[0]
    hh, ii, jj = _lut(layout)
    out = jnp.full((B, H, Mb, Nb, block, block), fill, vals.dtype)
    out = out.at[:, hh, ii, jj].set(vals)
    return out.transpose(0, 1, 2, 4, 3, 5).reshape(B, H, Mb * block, Nb * block)


class MatMul:
    """Block-sparse matmul with a fixed layout (reference matmul.py:595 ``MatMul``)."""

    def __init__(self, layout: np.ndarray, block: int, mode: str,
                 trans_a: bool = False, trans_b: bool = False):
        if mode not in ("sdd", "dsd", "dds"):
            raise NotImplementedError(f"Supported modes are: sdd, dsd, dds — got {mode!r}")
        self.layout = np.asarray(layout)
        self.block = int(block)
        self.mode = mode
        self.trans_a = trans_a
        self.trans_b = trans_b
        self.lut_h, self.lut_i, self.lut_j = _lut(self.layout)
        self.nnz = len(self.lut_h)

    # ---------------------------------------------------------------- helpers
    def _sparse_luts(self, transposed: bool):
        """(row, col) LUTs of the sparse operand, honoring a logical transpose."""
        if transposed:
            return self.lut_j, self.lut_i
        return self.lut_i, self.lut_j

    def _check_blocks(self, name, nblocks, axis_len):
        """JAX clamps out-of-bounds gather indices, which would silently duplicate the
        last block — validate dense operand extents against the layout instead."""
        if axis_len != nblocks * self.block:
            raise ValueError(
                f"{name} extent {axis_len} does not match layout: expected "
                f"{nblocks} blocks x block={self.block} = {nblocks * self.block}")

    def __call__(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        H, Mb, Nb = self.layout.shape
        if self.mode == "sdd":
            self._check_blocks("a rows", Nb if self.trans_a else Mb,
                               a.shape[-1] if self.trans_a else a.shape[-2])
            self._check_blocks("b cols", Mb if self.trans_b else Nb,
                               b.shape[-2] if self.trans_b else b.shape[-1])
        elif self.mode == "dsd":
            if a.shape[1] != self.nnz:
                raise ValueError(f"sparse operand nnz={a.shape[1]} != layout nnz={self.nnz}")
            self._check_blocks("b rows", Mb if self.trans_a else Nb,
                               b.shape[-1] if self.trans_b else b.shape[-2])
        else:  # dds
            if b.shape[1] != self.nnz:
                raise ValueError(f"sparse operand nnz={b.shape[1]} != layout nnz={self.nnz}")
            self._check_blocks("a cols", Nb if self.trans_b else Mb,
                               a.shape[-2] if self.trans_a else a.shape[-1])
        return getattr(self, f"_{self.mode}")(a, b)

    # ---------------------------------------------------------------- modes
    def _sdd(self, a, b):
        """dense [B,H,M,K] @ dense [B,H,K,N] -> sparse [B,nnz,block,block]."""
        blk = self.block
        if self.trans_a:
            a = a.swapaxes(-1, -2)
        if not self.trans_b:
            b = b.swapaxes(-1, -2)          # -> [B, H, N, K] (row-gatherable)
        B, H, M, K = a.shape
        a_blocks = a.reshape(B, H, M // blk, blk, K)[:, self.lut_h, self.lut_i]
        b_blocks = b.reshape(B, H, b.shape[2] // blk, blk, K)[:, self.lut_h, self.lut_j]
        # [B, nnz, blk, K] x [B, nnz, blk, K] -> [B, nnz, blk, blk]
        return jnp.einsum("bnik,bnjk->bnij", a_blocks, b_blocks,
                          preferred_element_type=jnp.float32).astype(a.dtype)

    def _dsd(self, a, b):
        """sparse [B,nnz,blk,blk] @ dense [B,H,K,N] -> dense [B,H,M,N]."""
        blk = self.block
        rows, cols = self._sparse_luts(self.trans_a)
        vals = a.swapaxes(-1, -2) if self.trans_a else a
        if self.trans_b:
            b = b.swapaxes(-1, -2)
        B, H, K, N = b.shape
        Mb = self.layout.shape[2] if self.trans_a else self.layout.shape[1]
        b_blocks = b.reshape(B, H, K // blk, blk, N)[:, self.lut_h, cols]  # [B,nnz,blk,N]
        prod = jnp.einsum("bnij,bnjk->bnik", vals, b_blocks,
                          preferred_element_type=jnp.float32).astype(b.dtype)
        out = jnp.zeros((B, H, Mb, blk, N), prod.dtype)
        out = out.at[:, self.lut_h, rows].add(prod)
        return out.reshape(B, H, Mb * blk, N)

    def _dds(self, a, b):
        """dense [B,H,M,K] @ sparse [B,nnz,blk,blk] -> dense [B,H,M,N]."""
        blk = self.block
        rows, cols = self._sparse_luts(self.trans_b)
        vals = b.swapaxes(-1, -2) if self.trans_b else b
        if self.trans_a:
            a = a.swapaxes(-1, -2)
        B, H, M, K = a.shape
        Nb = self.layout.shape[1] if self.trans_b else self.layout.shape[2]
        # gather a's K-blocks (the sparse operand's row dim): [B,H,Kb,M,blk]
        a_blocks = a.reshape(B, H, M, K // blk, blk).transpose(0, 1, 3, 2, 4)
        a_strips = a_blocks[:, self.lut_h, rows]                 # [B, nnz, M, blk]
        prod = jnp.einsum("bnmi,bnij->bnmj", a_strips, vals,
                          preferred_element_type=jnp.float32).astype(a.dtype)
        out = jnp.zeros((B, H, Nb, M, blk), prod.dtype)
        out = out.at[:, self.lut_h, cols].add(prod)
        return out.transpose(0, 1, 3, 2, 4).reshape(B, H, M, Nb * blk)
