"""Row-sparse gradient support for embedding tables.

TPU-native analog of ``deepspeed/runtime/csr_tensor.py`` (CSRTensor) and the engine's
CSR allreduce (``deepspeed/runtime/engine.py:1091-1147``): embedding gradients are
row-sparse (a token's backward touches exactly one table row), so data-parallel
reduction ships (indices, values) instead of the dense [vocab, width] array.

The reference used dynamic-size nonzero + padded all_gathers. Under XLA everything
must be static-shaped, so ``SparseTensor`` carries a **fixed capacity** k of rows:
``from_dense`` selects up to k nonzero rows (k = local token count bounds the true
nonzero count for gather-transpose gradients, making this exact, not approximate);
``all_gather`` over the mesh axis then needs no padding dance at all — every shard
contributes exactly k rows. Empty slots point at row 0 with all-zero values, so the
scatter-add in ``to_dense`` is a harmless no-op for them.
"""

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


class SparseTensor:
    """Fixed-capacity row-sparse tensor (reference csr_tensor.py:11-59).

    ``indices``: int32 [k] row ids (unused slots = 0), ``values``: [k, cols]
    (unused slots = 0), ``dense_shape``: (rows, cols) static.
    """

    def __init__(self, indices: jnp.ndarray, values: jnp.ndarray,
                 dense_shape: Tuple[int, int]):
        self.indices = indices
        self.values = values
        self.dense_shape = tuple(dense_shape)

    @staticmethod
    def type() -> str:
        return "deepspeed_tpu.SparseTensor"

    @classmethod
    def from_dense(cls, dense: jnp.ndarray, capacity: Optional[int] = None) -> "SparseTensor":
        """Extract up to ``capacity`` nonzero rows (by any-nonzero test, reference
        csr_tensor.py:16-18 used sum!=0 which misses cancelling rows; we use abs-sum).
        Rows beyond capacity are dropped — pass a capacity that upper-bounds the true
        nonzero count (token count for embedding grads) for exactness."""
        rows, _ = dense.shape
        k = rows if capacity is None else min(capacity, rows)
        row_mass = jnp.sum(jnp.abs(dense), axis=1)
        (idx,) = jnp.nonzero(row_mass, size=k, fill_value=0)
        # nonzero() pads the tail with fill_value=0; a positional mask (slot < true
        # nnz) distinguishes padding from a genuinely-nonzero row 0.
        nnz = jnp.sum(row_mass > 0)
        valid = jnp.arange(k) < nnz
        values = dense[idx] * valid[:, None].astype(dense.dtype)
        return cls(idx.astype(jnp.int32), values, dense.shape)

    def to_dense(self) -> jnp.ndarray:
        """Scatter-add rows back (reference csr_tensor.py:29-35). Duplicate indices
        accumulate, so gathered multi-worker tensors densify correctly."""
        out = jnp.zeros(self.dense_shape, self.values.dtype)
        return out.at[self.indices].add(self.values)

    def sparse_size(self) -> Tuple[int, int]:
        index_size = self.indices.shape[0]
        value_size = self.values.shape[0] * self.values.shape[1]
        dense_size = self.dense_shape[0] * self.dense_shape[1]
        return index_size + value_size, dense_size

    def add(self, other: "SparseTensor") -> "SparseTensor":
        """Concatenate entries (reference csr_tensor.py:45-48); duplicates resolve
        at to_dense time."""
        assert self.dense_shape == other.dense_shape
        return SparseTensor(jnp.concatenate([self.indices, other.indices]),
                            jnp.concatenate([self.values, other.values]),
                            self.dense_shape)

    def __repr__(self):
        sparse_size, dense_size = self.sparse_size()
        return (f"SparseTensor(k={self.indices.shape[0]}, dense_shape={self.dense_shape}, "
                f"reduction_factor={dense_size / max(sparse_size, 1):.1f})")


def row_sparse_allreduce(dense_local: jnp.ndarray, axis_name: str, capacity: int,
                         mean: bool = True) -> jnp.ndarray:
    """Average a row-sparse gradient over a mesh axis by gathering (indices, values)
    instead of psum-ing the dense table (reference engine.py:1105-1127).

    Must be called inside shard_map/pmap with ``axis_name`` bound. Comm volume is
    world*k*(cols+1) vs rows*cols for a dense psum — a win when k << rows/world.
    """
    st = SparseTensor.from_dense(dense_local, capacity)
    # Static capacity per shard → plain all_gathers, no size exchange or padding
    # (the reference needed an extra scalar all_gather + fill, engine.py:1116-1140).
    all_idx = jax.lax.all_gather(st.indices, axis_name)      # [world, k]
    all_val = jax.lax.all_gather(st.values, axis_name)       # [world, k, cols]
    gathered = SparseTensor(all_idx.reshape(-1), all_val.reshape(-1, all_val.shape[-1]),
                            st.dense_shape)
    dense = gathered.to_dense()
    if mean:
        dense = dense / jax.lax.axis_size(axis_name)
    return dense.astype(dense_local.dtype)


def match_sparse_paths(path_str: str, patterns: Sequence[str]) -> bool:
    """Leaf-path matcher for the engine's sparse-grad selection (the reference keyed
    on ``isinstance(module, nn.Embedding)``, engine.py:180-187; a functional pytree
    keys on leaf path substrings instead)."""
    return any(p in path_str for p in patterns)


# Reference-name alias (deepspeed/runtime/csr_tensor.py exports CSRTensor; the TPU
# rebuild is row-sparse rather than true CSR, but the role and API surface match).
CSRTensor = SparseTensor
