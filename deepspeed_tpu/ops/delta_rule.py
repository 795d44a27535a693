"""The gated delta rule of a linear-attention layer (Gated DeltaNet, as Qwen3-Next's three
layers in four use it), and the short causal convolution in front of it.

A head keeps a state ``S [Dk, Dv]`` and sees a token at a time:

    S <- exp(g_t) S;   d = beta_t (v_t - S^T k_t);   S <- S + k_t d^T;   o_t = S^T q_t

with q and k of unit length a head and q scaled by ``Dk^-1/2``.

Training does not run that recurrence. ``gated_delta_rule`` is the chunked (WY) form: inside
a chunk of ``CHUNK`` tokens the rank-one updates are gathered into one unit lower-triangular
system ``(I + tril(beta K K^T * decay, -1)) U = beta V`` whose solution is every token's
update as if the chunk had started from a zero state, and the chunks are then joined by a
scan that carries ``S``: four products a chunk and head. The state, the cumulative decays
and the triangular system are float32 and every product that touches them runs at full
precision (on the TPU a float32 product is otherwise rounded to bfloat16 on its way in).
The backward is JAX's derivative of this same program, made again a block of chunks at a
time (``jax.checkpoint``): nothing but q, k, v, g, beta and the state between blocks is
kept for it.
"""

import functools

import jax
import jax.numpy as jnp

CHUNK = 64
BLOCK_CHUNKS = 16          # chunks a block: what the backward holds at once
L2_EPS = 1e-6
_HIGHEST = jax.lax.Precision.HIGHEST
_dot = functools.partial(jnp.einsum, precision=_HIGHEST, preferred_element_type=jnp.float32)


@functools.partial(jax.checkpoint, static_argnums=(2,))
def causal_conv(x, w, silu=False):
    """Depthwise causal convolution over time, no bias: ``x [B, T, C]``, ``w [W, C]``,
    ``y_t = sum_j w[j] x_{t - (W - 1) + j}`` with zeros before the first token, then SiLU
    where ``silu``. Summed in float32, returned in ``x``'s dtype; the backward makes the
    sum again from ``x`` and ``w`` (elementwise passes), so that no float32 copy of the
    ``W`` shifted inputs is kept."""
    W, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    y = sum(xp[:, j:j + T].astype(jnp.float32) * wf[j] for j in range(W))
    return (jax.nn.silu(y) if silu else y).astype(x.dtype)


@jax.custom_vjp
def _inverse_unit_lower(below):
    """``inv(I + below)``, ``below [..., n, n]`` strictly lower triangular, n a power of two:
    block forward substitution, the blocks doubling. With ``X`` the inverse of the diagonal
    blocks of one size and ``L`` what lies under them inside the blocks of twice the size,
    ``inv([[A, 0], [C, B]]) = [[inv A, 0], [-inv B . C . inv A, inv B]]`` is ``X - X L X``:
    whole-matrix products under constant masks, ``log2 n - 1`` pairs of them (the first
    doubling is ``I - L``). No power series that a run of equal keys could blow up, and no
    slice or concatenation: as a recursion over the halves the assembly alone took a fifth
    of a mixer's time on a v5e, and its batched products of 2 x 2 halves 6.7 s (PERF.md
    PR 31). The cotangent is the closed form ``-X^T g X^T``, two products, where JAX's own
    derivative of the doublings would make twenty-four."""
    n = below.shape[-1]
    rows = jnp.arange(n)
    inv, size = None, 1
    while size < n:
        block, half = rows // (2 * size), rows // size % 2
        under = (block[:, None] == block[None, :]) & (half[:, None] == 1) & (half[None, :] == 0)
        low = jnp.where(under, below, 0.0)
        inv = (jnp.eye(n, dtype=below.dtype) - low if inv is None
               else inv - _dot("...ij,...jk,...kl->...il", inv, low, inv))
        size *= 2
    return inv


def _inverse_unit_lower_fwd(below):
    inv = _inverse_unit_lower(below)
    return inv, inv


def _inverse_unit_lower_bwd(inv, g):
    return (-_dot("...ji,...jk,...lk->...il", inv, g, inv),)


_inverse_unit_lower.defvjp(_inverse_unit_lower_fwd, _inverse_unit_lower_bwd)


def _block(S, q, k, v, g, beta):
    """One block of whole chunks from the carried state ``S [B, Hk, r, Dk, Dv]``:
    ``(the state after it, o [B, t, Hv, Dv] float32)``; ``t`` a multiple of ``CHUNK``."""
    B, t, Hk, Dk = k.shape
    Hv, Dv = v.shape[2:]
    r, C = Hv // Hk, CHUNK
    N = t // C
    f32 = jnp.float32

    def chunks(a, heads):
        """``[B, t, *heads, ...]`` float32 as ``[B, *heads, N, C, ...]``."""
        a = a.astype(f32).reshape(B, N, C, *a.shape[2:])
        return jnp.moveaxis(a, (1, 2), (1 + heads, 2 + heads))

    q, k = chunks(q, 1), chunks(k, 1)                                  # [B, Hk, N, C, Dk]
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)  # noqa: E731
    q, k = unit(q) * Dk ** -0.5, unit(k)
    v = chunks(v.reshape(B, t, Hk, r, Dv), 2)                          # [B, Hk, r, N, C, Dv]
    g, beta = (chunks(a.reshape(B, t, Hk, r), 2) for a in (g, beta))   # [B, Hk, r, N, C]

    gc = jnp.cumsum(g, axis=-1)                                        # decay since the chunk began
    rows = jnp.arange(C)
    lower = rows[:, None] >= rows[None, :]
    # exp(gc_i - gc_j) for j <= i: masked BEFORE the exponential, which above the diagonal
    # would overflow
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    kk = _dot("bhncd,bhnmd->bhncm", k, k)[:, :, None]                  # [B, Hk, 1, N, C, C]
    system = jnp.where(rows[:, None] > rows[None, :], beta[..., None] * kk * decay, 0.0)
    inv = _inverse_unit_lower(system)
    u = _dot("bhrncm,bhrnmd->bhrncd", inv, v * beta[..., None])        # updates from a zero state
    w = _dot("bhrncm,bhrnm,bhnmd->bhrncd", inv, beta * jnp.exp(gc), k)  # what the state takes off
    local = _dot("bhncd,bhnmd->bhncm", q, k)[:, :, None] * decay       # [B, Hk, r, N, C, C]
    q_in = jnp.exp(gc)[..., None] * q[:, :, None]                      # q against the carried state
    total = gc[..., -1]                                                # [B, Hk, r, N]
    k_out = jnp.exp(total[..., None] - gc)[..., None] * k[:, :, None]  # k as the chunk's end sees it

    def step(S, xs):
        u_n, w_n, local_n, q_n, k_n, total_n = xs
        new = u_n - _dot("bhrcd,bhrde->bhrce", w_n, S)
        o = _dot("bhrcd,bhrde->bhrce", q_n, S) + _dot("bhrcm,bhrme->bhrce", local_n, new)
        S = jnp.exp(total_n)[..., None, None] * S + _dot("bhrcd,bhrce->bhrde", k_n, new)
        return S, o

    over_chunks = [jnp.moveaxis(a, 3, 0) for a in (u, w, local, q_in, k_out, total)]
    S, o = jax.lax.scan(step, S, over_chunks)
    o = jnp.moveaxis(o, (0, 4), (1, 2))                                # [B, N, C, Hk, r, Dv]
    return S, o.reshape(B, t, Hv, Dv)


def gated_delta_rule(q, k, v, g, beta):
    """``o [B, T, Hv, Dv]`` (in ``v``'s dtype) of the recurrence above from a zero state, a
    sequence a row: ``q``, ``k`` ``[B, T, Hk, Dk]`` as the convolution leaves them (here each
    is L2-normalised a head in float32, ``x / sqrt(sum x^2 + 1e-6)``, and q scaled by
    ``Dk^-1/2``), ``v`` ``[B, T, Hv, Dv]``, ``g`` (log decay, <= 0) and ``beta``
    ``[B, T, Hv]``; key head h serves the value heads ``h * Hv / Hk`` onwards. Any ``T``:
    the end is filled with tokens that change nothing (k, v, beta, g zero).

    The sequence goes through in blocks of ``BLOCK_CHUNKS`` chunks that hand the state on.
    The backward keeps the inputs and the state between blocks and makes a block again as
    it reaches it, so what one block's chunked form builds (the triangular systems, the
    updates, a state a chunk) exists for one block at a time."""
    B, T = k.shape[:2]
    Hk, Dk, Dv = k.shape[2], k.shape[3], v.shape[3]
    padded = -(-T // CHUNK) * CHUNK
    block = min(padded, BLOCK_CHUNKS * CHUNK)
    padded = -(-padded // block) * block

    def blocks(a):
        """``[B, T, ...]`` as ``[blocks, B, block, ...]``, zeros to the end."""
        a = jnp.pad(a, [(0, 0), (0, padded - T)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(B, padded // block, block, *a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one_block(S, xs):
        with jax.named_scope("ds_delta_rule"):
            S, o = _block(S, *xs)
            return S, o.astype(v.dtype)

    with jax.named_scope("ds_delta_rule"):
        S0 = jnp.zeros((B, Hk, v.shape[2] // Hk, Dk, Dv), jnp.float32)
        _, o = jax.lax.scan(one_block, S0, tuple(blocks(a) for a in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1).reshape(B, padded, *v.shape[2:])[:, :T]
