"""The selective state-space scan of a Mamba-2 layer, in its chunked (state-space-duality,
SSD) form.

A head keeps a state ``S [P, N]`` (``P`` the head's width, ``N`` the state size) and sees a
token at a time:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;      y_t = S_t C_t + D x_t

with ``A < 0`` and ``D`` a head, ``dt_t > 0`` a head and token, ``B_t``, ``C_t`` ``[N]`` one
for all heads (one group).

Training does not run that recurrence. Inside a chunk of ``chunk`` tokens the state's part
in the outputs is a masked matrix product, ``Y = ((C B^T) * L) (dt x)`` with
``L_ij = exp(sum_{j < m <= i} dt_m A)`` for ``i >= j``, each chunk leaves the state its own
tokens build, a ``lax.scan`` hands the float32 state from chunk to chunk, and what a chunk
was handed is read by ``C`` and decayed to each token. The decays inside a chunk come from
the segment sums themselves (a cumulative sum that starts under the diagonal), not from the
difference of two cumulative sums, whose rounding grows with the chunk's whole decay.

The matrix products take their operands in ``x``'s dtype (bfloat16 in a step; the decay
matrix, ``dt`` folded in, is rounded once for the product, as the carried state is for its
read by ``C``) and accumulate in float32; ``dt``, ``A``, the decays and the carried state are
float32. Float32 operands go through the products at ``highest`` precision. Plain ``lax`` and
``jnp``, differentiated by JAX.
"""

import jax
import jax.numpy as jnp

CHUNK = 256       # the published ``mamba_chunk_size``
HEADS_AT_ONCE = 8   # heads whose decay matrices exist together


def _mm(spec, a, b):
    """``einsum`` accumulated in float32; float32 operands at ``highest`` precision (on the
    TPU a float32 product is one bfloat16 pass unless asked otherwise)."""
    precision = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32, precision=precision)


def segment_sums(a):
    """``a [..., Q]`` -> ``[..., Q, Q]``: ``sum_{j < m <= i} a_m`` at ``[i, j]`` for
    ``i >= j``, ``-inf`` above the diagonal. Column ``j`` is a cumulative sum that starts at
    ``m = j + 1``, so an entry's rounding is that of the segment it spans."""
    q = jnp.arange(a.shape[-1])
    below = q[:, None] > q[None, :]
    sums = jnp.cumsum(jnp.where(below, a[..., :, None], 0.0), axis=-2)
    return jnp.where(q[:, None] >= q[None, :], sums, -jnp.inf)


def _within_chunks(a, dt, G, xc):
    """``((C B^T) * L * dt) x`` ``[Bt, n, Q, H, P]`` in float32 from the log decays ``a`` and
    steps ``dt`` ``[Bt, n, H, Q]``, ``G = C B^T [Bt, n, Q, Q]`` and ``xc [Bt, n, Q, H, P]``,
    ``HEADS_AT_ONCE`` heads at a time: a head's ``Q x Q`` float32 decays of every chunk, their
    cumulative sums and their cotangents are 2 GB for 64 heads at 8,192 tokens, and exist
    for a few heads only. The backward makes a group's matrices again from ``a`` and ``dt``."""
    Bt, n, H, Q = a.shape
    g = next(d for d in range(min(HEADS_AT_ONCE, H), 0, -1) if H % d == 0)

    @jax.checkpoint
    def some_heads(of):
        a_g, dt_g, x_g = of
        M = G[:, :, None] * jnp.exp(segment_sums(a_g)) * dt_g[..., None, :]
        return _mm("bchij,bcjhp->bcihp", M.astype(x_g.dtype), x_g)

    ys = [some_heads((a[:, :, h:h + g], dt[:, :, h:h + g], xc[:, :, :, h:h + g]))
          for h in range(0, H, g)]
    return jnp.concatenate(ys, axis=3)


def ssd_scan(x, dt, A, B, C, D, chunk=CHUNK):
    """``y [Bt, T, H, P]`` (in ``x``'s dtype) of the recurrence above from a zero state, a
    sequence a row: ``x [Bt, T, H, P]``, ``dt [Bt, T, H]`` (after its softplus), ``A``,
    ``D`` ``[H]``, ``B``, ``C`` ``[Bt, T, N]``. Any ``T``: the end is filled up to a whole
    chunk with tokens that change nothing (``dt``, ``x``, ``B``, ``C`` zero)."""
    Bt, T, H, P = x.shape
    N = B.shape[-1]
    f32 = jnp.float32
    with jax.named_scope("ds_ssd_scan"):
        fill = -T % chunk
        if fill:
            x, dt, B, C = (jnp.pad(a, ((0, 0), (0, fill)) + ((0, 0),) * (a.ndim - 2))
                           for a in (x, dt, B, C))
        n = (T + fill) // chunk
        xc = x.reshape(Bt, n, chunk, H, P)
        Bc, Cc = B.reshape(Bt, n, chunk, N), C.reshape(Bt, n, chunk, N)
        dth = jnp.moveaxis(dt.astype(f32).reshape(Bt, n, chunk, H), 3, 2)      # [Bt, n, H, Q]
        a = dth * A.astype(f32)[:, None]                      # a token's log decay, <= 0
        # inside a chunk: Y = ((C B^T) * L * dt) x
        y = _within_chunks(a, dth, _mm("bcin,bcjn->bcij", Cc, Bc), xc)
        # the state a chunk's own tokens leave at its end: token j's part decays over j < m < Q
        after = jnp.pad(a[..., 1:], ((0, 0),) * 3 + ((0, 1),))
        to_end = jnp.moveaxis(jnp.exp(jnp.cumsum(after[..., ::-1], axis=-1)[..., ::-1]) * dth, 2, 3)
        own = _mm("bcjhp,bcjn->bchpn", (xc * to_end[..., None]).astype(x.dtype), Bc)
        # from chunk to chunk, float32
        run = jnp.cumsum(a, axis=-1)                          # sum_{0 <= m <= i} a_m
        whole = jnp.exp(run[..., -1])                                          # [Bt, n, H]

        def hand_on(S, chunk_c):
            own_c, whole_c = chunk_c
            return whole_c[..., None, None] * S + own_c, S

        _, handed = jax.lax.scan(hand_on, jnp.zeros((Bt, H, P, N), f32),
                                 (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
        handed = jnp.moveaxis(handed, 0, 1).astype(x.dtype)                    # [Bt, n, H, P, N]
        from_start = jnp.moveaxis(jnp.exp(run), 2, 3)                          # [Bt, n, Q, H]
        y = y + _mm("bcin,bchpn->bcihp", Cc, handed) * from_start[..., None]
        y = y + D.astype(f32)[:, None] * xc.astype(f32)
        return y.reshape(Bt, n * chunk, H, P)[:, :T].astype(x.dtype)
