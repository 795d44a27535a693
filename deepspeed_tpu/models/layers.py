"""Block pieces that more than one model calls: the chunked LM-head cross-entropy, RMSNorm
and rotary position embedding. Pure functions of arrays; no parameters of their own."""

import jax
import jax.numpy as jnp


def chunked_cross_entropy(x, head, labels, chunk):
    """Fused LM-head + softmax cross-entropy, scanned over sequence chunks so the
    (B, T, vocab) fp32 logits tensor never materializes — at GPT-2 vocab (50k) full
    logits for a 16×1024 batch are 3.3 GB and dominate HBM. The rematted scan body
    recomputes each chunk's logits in backward from the (tiny) hidden states.

    ``head`` is the ``[V, H]`` table (GPT-2's tied ``wte``, OLMoE's untied head);
    negative labels are ignored. Returns the mean over the valid positions."""
    B, T, H = x.shape
    n = T // chunk
    xs = x.reshape(B, n, chunk, H).swapaxes(0, 1)     # (n, B, C, H)
    ls = labels.reshape(B, n, chunk).swapaxes(0, 1)   # (n, B, C)
    w = head.astype(x.dtype)                          # (V, H)

    def body(tot, xc_lc):
        xc, lc = xc_lc
        # contract against the UNtransposed table (dot_general picks the dim):
        # a materialized wte.T costs a 153 MB HBM temp at GPT-2 1.5B — measured
        # as an AllocateBuffer in the fused-step OOM breakdown
        logits = jnp.einsum("bch,vh->bcv", xc, w,
                            preferred_element_type=jnp.float32)  # (B, C, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = (lc >= 0).astype(jnp.float32)  # < 0 = ignored (BERT's -100)
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None],
                                   axis=-1)[..., 0]
        return (tot[0] + jnp.sum((lse - gold) * valid),
                tot[1] + jnp.sum(valid)), None

    (total, n_valid), _ = jax.lax.scan(
        jax.checkpoint(body),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (xs, ls))
    return total / jnp.maximum(n_valid, 1.0)


def loss_chunk_for(T, loss_chunk):
    """The largest divisor of ``T`` not above ``loss_chunk`` (static shapes for XLA)."""
    return next(cc for cc in range(min(loss_chunk, T), 0, -1) if T % cc == 0)


def rms_norm(x, scale, eps):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta):
    """Rotary embedding in the half-split convention (``rotate_half``): ``x`` is
    ``[B, H, T, D]``, ``positions`` ``[T]``; pair ``i`` of the first and second half
    of ``D`` turns by ``pos * theta^(-2i/D)``. Angles and the rotation in float32."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]      # [T, D/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
