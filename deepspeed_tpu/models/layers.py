"""Block pieces that more than one model calls: the chunked LM-head cross-entropy, RMSNorm
and rotary position embedding. Pure functions of arrays; no parameters of their own."""

import math

import jax
import jax.numpy as jnp
import numpy as np


# Bytes of float32 logits the head's cross-entropy makes at once on a chip: what the TPU
# compiler keeps in a v5e's 128 MiB of VMEM from the product to the last read of it (4 x 128
# positions of a 50,304-word vocabulary are 98 MiB). A tile four times as large goes through
# HBM and the whole function takes a tenth to a sixth longer (PERF.md, PR 29).
LOGITS_TILE_BYTES = 100 << 20


def _tiling(B, T, V):
    """``(shards, positions, tiles)``: the ways the batch is split over chips, and as many
    positions of all of a chip's rows as keep its float32 logits at or under
    ``LOGITS_TILE_BYTES``, the tiles of one length (and dividing ``T`` where that takes at
    most twice the tiles). The batch is split over the context
    mesh's ``data`` axis (the engine traces its programs under its mesh and splits the
    batch over that axis); inside a ``shard_map`` and without a mesh it is whole."""
    from ..parallel.mesh import DATA_AXIS
    mesh = jax.sharding.get_abstract_mesh()
    shards = 1 if mesh.empty or DATA_AXIS not in mesh.auto_axes else mesh.shape[DATA_AXIS]
    if B % shards:
        shards = 1
    tiles = -(-T // max(1, LOGITS_TILE_BYTES // (B // shards * V * 4)))
    # a count that divides T, where one lies within twice the least, has no filled tail: at
    # T = 8192 over 18,992 words six tiles of 1,366 (8,196 positions) took the TPU compiler
    # 48 s and the step 5 ms of copies that eight of 1,024 do not (PERF.md, PR 31)
    tiles = next((n for n in range(tiles, 2 * tiles + 1) if T % n == 0), tiles)
    return shards, -(-T // tiles), tiles


def _in_tiles(a, shards, positions, tiles, fill=0):
    """``[B, T, ...]`` as ``[tiles, shards, rows, positions, ...]``, ``T`` filled up to the
    tiles' end: the batch stays apart from the positions and each chip's rows from the
    others', so that under a mesh a tile is every chip's own rows and nothing else."""
    B, T = a.shape[:2]
    pad = [(0, 0), (0, tiles * positions - T)] + [(0, 0)] * (a.ndim - 2)
    a = jnp.pad(a, pad, constant_values=fill) if pad[1][1] else a
    return jnp.moveaxis(a.reshape(shards, B // shards, tiles, positions, *a.shape[2:]), 2, 0)


def _cross_entropy_tiles(x, head, labels, keep, a_position=False):
    """The sum of the valid positions' losses, a tile of positions at a time; with ``keep``
    also every tile's ``softmax - onehot`` ``[tiles, shards, rows, positions, V]``, taken
    while the tile's logits exist and rounded to the products' dtype as it stands, within
    [-1, 1], so that no scale can make it underflow. With ``a_position`` the losses
    themselves ``[tiles, shards, rows, positions]`` (0 where the label is negative or the
    position filled) in the sum's place."""
    V = head.shape[0]
    shards, positions, tiles = _tiling(*labels.shape, V)
    xs = _in_tiles(x, shards, positions, tiles)
    ls = _in_tiles(labels, shards, positions, tiles, fill=-1)    # a filled position: ignored
    w = head.astype(x.dtype)

    def tile(total, xc_lc):
        xc, lc = xc_lc
        # contract against the UNtransposed table (dot_general picks the dim): a
        # materialized wte.T costs a 153 MB HBM temp at GPT-2 1.5B
        logits = jnp.einsum("srch,vh->srcv", xc, w, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = lc >= 0                                # < 0 = ignored (BERT's -100)
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        losses = jnp.where(valid, lse - gold, 0.0)
        # a chip's own sum, one a shard: the sums cross the chips once, after the scan
        total = total + jnp.sum(losses, axis=(1, 2))
        if not keep:
            return total, (losses if a_position else None, None)
        onehot = jnp.arange(V, dtype=lc.dtype) == lc[..., None]
        g = jnp.where(valid[..., None], jnp.exp(logits - lse[..., None]) - onehot, 0.0)
        return total, (losses if a_position else None, g.astype(w.dtype))

    total, (losses, gs) = jax.lax.scan(tile, jnp.zeros((shards,), jnp.float32), (xs, ls))
    return (losses if a_position else jnp.sum(total)), gs


def _valid_count(labels):
    return jnp.maximum(jnp.sum(labels >= 0).astype(jnp.float32), 1.0)


@jax.custom_vjp
def chunked_cross_entropy(x, head, labels):
    """Fused LM-head + softmax cross-entropy, a tile of sequence positions at a time, so
    that the (B, T, vocab) float32 logits never exist: at GPT-2's vocabulary a 16 x 1024
    batch of them is 3.3 GB. ``head`` is the ``[V, H]`` table (GPT-2's tied ``wte``,
    OLMoE's untied head); negative labels are ignored. Returns the mean over the valid
    positions.

    Its backward is its own. When a gradient is asked for, the forward makes each tile's
    logits ONCE and keeps their gradient in the products' dtype (``[B, T, vocab]``, half
    the logits' bytes; JAX's derivative of a rematted scan made every tile twice and
    rewrote the table's gradient once a chunk, under a mesh reducing it over the chips
    each time). The backward rule is two whole products, each accumulated in float32,
    scaled there by the incoming cotangent over the count of valid labels, and rounded
    once; under a mesh the table's gradient crosses the chips once."""
    with jax.named_scope("ds_loss"):
        return _cross_entropy_tiles(x, head, labels, False)[0] / _valid_count(labels)


def _chunked_cross_entropy_fwd(x, head, labels):
    with jax.named_scope("ds_loss"):
        count = _valid_count(labels)
        total, gs = _cross_entropy_tiles(x, head, labels, True)
        return total / count, (gs, x, head, count)


def _chunked_cross_entropy_bwd(res, ct):
    gs, x, head, count = res
    with jax.named_scope("ds_loss"):
        tiles, shards, _, positions, _ = gs.shape
        scale = ct.astype(jnp.float32) / count
        xs = _in_tiles(x, shards, positions, tiles)
        dxs = scale * jnp.einsum("nsrcv,vh->nsrch", gs, head.astype(x.dtype),
                                 preferred_element_type=jnp.float32)
        d_head = scale * jnp.einsum("nsrcv,nsrch->vh", gs, xs,
                                    preferred_element_type=jnp.float32)
        dx = jnp.moveaxis(dxs, 0, 2).reshape(x.shape[0], -1, x.shape[2])[:, :x.shape[1]]
        return dx.astype(x.dtype), d_head.astype(head.dtype), None


chunked_cross_entropy.defvjp(_chunked_cross_entropy_fwd, _chunked_cross_entropy_bwd)


def _out_of_tiles(a, B, T):
    """``_in_tiles`` undone: ``[tiles, shards, rows, positions, ...]`` as ``[B, T, ...]``."""
    return jnp.moveaxis(a, 0, 2).reshape(B, -1, *a.shape[4:])[:, :T]


@jax.custom_vjp
def chunked_cross_entropy_a_position(x, head, labels):
    """``chunked_cross_entropy`` before its mean: the float32 loss of EVERY position
    ``[B, T]`` (0 where the label is negative), on the same tiles and under the same scope,
    for a loss that weighs positions itself (a looped model's exits, each position of each
    exit by its own differentiated weight: ``models/ouro.py``).

    Its backward is its own too, and takes a cotangent a position: the forward keeps each
    tile's ``softmax - onehot`` as above; the rule is the same two whole products, a
    position's row of the input's gradient scaled by its cotangent AFTER the product, in
    float32, and the input's rows scaled by it BEFORE the table's (in the products' dtype,
    whose exponent is float32's): the kept ``[B, T, vocab]`` is read twice and never
    written again."""
    with jax.named_scope("ds_loss"):
        losses, _ = _cross_entropy_tiles(x, head, labels, False, a_position=True)
        return _out_of_tiles(losses, *labels.shape)


def _chunked_cross_entropy_a_position_fwd(x, head, labels):
    with jax.named_scope("ds_loss"):
        losses, gs = _cross_entropy_tiles(x, head, labels, True, a_position=True)
        return _out_of_tiles(losses, *labels.shape), (gs, x, head)


def _chunked_cross_entropy_a_position_bwd(res, ct):
    gs, x, head = res
    with jax.named_scope("ds_loss"):
        tiles, shards, _, positions, _ = gs.shape
        cts = _in_tiles(ct.astype(jnp.float32), shards, positions, tiles)[..., None]
        xs = _in_tiles(x, shards, positions, tiles)
        dxs = cts * jnp.einsum("nsrcv,vh->nsrch", gs, head.astype(x.dtype),
                               preferred_element_type=jnp.float32)
        d_head = jnp.einsum("nsrcv,nsrch->vh", gs, (cts * xs).astype(x.dtype),
                            preferred_element_type=jnp.float32)
        return _out_of_tiles(dxs, *x.shape[:2]).astype(x.dtype), d_head.astype(head.dtype), None


chunked_cross_entropy_a_position.defvjp(_chunked_cross_entropy_a_position_fwd,
                                        _chunked_cross_entropy_a_position_bwd)


def rms_norm(x, scale, eps, zero_centred=False):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, in float32; with
    ``zero_centred`` the stored weight is the scale's distance from one (``* (1 + scale)``,
    Qwen3-Next's norms, whose weights start at zero)."""
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    scale = scale.astype(jnp.float32)
    return (out * (1.0 + scale if zero_centred else scale)).astype(x.dtype)


def rope_frequencies(head_dim, theta, scaling=None):
    """``(inv_freq [head_dim / 2] float32, factor)``: the rotary frequencies as a value, and
    what cos and sin are both multiplied by. ``scaling`` is a published ``rope_parameters``
    entry (None or ``rope_type`` ``default``: ``theta^(-2i/D)`` and 1). ``yarn``: pair ``i``
    turns ``factor`` times slower from the pair that makes ``beta_slow`` turns over
    ``original_max_position_embeddings`` positions on, unchanged up to the pair that makes
    ``beta_fast`` turns, a linear ramp between (its ends floored and ceiled unless
    ``truncate`` is false), and cos and sin times ``attention_factor`` (``0.1 ln(factor) +
    1`` where the key is absent). The table is static: it does not follow the length."""
    D = head_dim
    inv_freq = theta ** (-np.arange(0, D, 2, dtype=np.float64) / D)
    kind = (scaling or {}).get("rope_type", "default")
    assert kind in ("default", "yarn"), f"rope_type {kind!r} is not built"
    if kind == "default":
        return inv_freq.astype(np.float32), 1.0
    factor, original = scaling["factor"], scaling["original_max_position_embeddings"]

    def pair_of(turns):         # the pair that makes ``turns`` turns over the original length
        return D * math.log(original / (2 * math.pi * turns)) / (2 * math.log(theta))
    low, high = pair_of(scaling.get("beta_fast", 32)), pair_of(scaling.get("beta_slow", 1))
    if scaling.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, D - 1)
    ramp = np.clip((np.arange(D // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = inv_freq * ((1.0 - ramp) + ramp / factor)
    attention_factor = scaling.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(attention_factor)


def rope(x, positions, theta, width=None, inv_freq=None, factor=1.0):
    """Rotary embedding in the half-split convention (``rotate_half``): ``x`` is
    ``[B, H, T, D]``, ``positions`` ``[T]``; pair ``i`` of the first and second half
    of the first ``width`` features (all ``D`` where None) turns by
    ``pos * theta^(-2i/width)``, the features past ``width`` pass unchanged. Angles and
    the rotation in float32. ``inv_freq`` and ``factor`` (``rope_frequencies``) replace
    ``theta``'s frequencies and scale cos and sin."""
    if width is not None and width < x.shape[-1]:
        return jnp.concatenate([rope(x[..., :width], positions, theta, None, inv_freq, factor),
                                x[..., width:]], axis=-1)
    D = x.shape[-1]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]      # [T, D/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
