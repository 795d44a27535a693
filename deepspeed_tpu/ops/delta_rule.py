"""The gated delta rule of a linear-attention layer (Gated DeltaNet, as Qwen3-Next's three
layers in four use it), and the short causal convolution in front of it.

A head keeps a state ``S [Dk, Dv]`` and sees a token at a time:

    S <- exp(g_t) S;   d = beta_t (v_t - S^T k_t);   S <- S + k_t d^T;   o_t = S^T q_t

with q and k of unit length a head and q scaled by ``Dk^-1/2``.

Training does not run that recurrence. ``gated_delta_rule`` is the chunked (WY) form: inside
a chunk of ``CHUNK`` tokens the rank-one updates are gathered into one unit lower-triangular
system ``(I + tril(beta K K^T * decay, -1)) New = beta (V - decay K S)`` whose solution is
every token's update, and the chunks hand ``S`` on. It is a ``jax.custom_vjp`` over two Pallas
kernels (``ops/pallas/delta_rule.py``): the chunk's system, its inverse and the carried state
stay in VMEM, float32, and no product rounds them; the backward is a kernel of its own that
walks the sequence in reverse from the state the forward kept at every block's start.
"""

import functools

import jax
import jax.numpy as jnp

from .pallas import causal_conv as conv_kernels
from .pallas import delta_rule as kernels
from .pallas.delta_rule import CHUNK

BLOCK_CHUNKS = 8           # chunks a grid step: the spacing of the states the backward is given


@functools.partial(jax.checkpoint, static_argnums=(2,))
def plain_causal_conv(x, w, silu=False, bias=None):
    """``causal_conv`` as plain ``jnp``: what the kernels are compared with, and what runs
    where the channels do not fill whole registers of 128 lanes. Summed in float32, returned
    in ``x``'s dtype; the backward (JAX's own) makes the sum again from ``x`` and ``w``."""
    W, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    y = sum(xp[:, j:j + T].astype(jnp.float32) * wf[j] for j in range(W))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return (jax.nn.silu(y) if silu else y).astype(x.dtype)


# ``how``: the kernels' static arguments, ``(start, C, rows, lanes, silu, interpret)``
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv(x, w, bias, how):
    return conv_kernels.causal_conv_fwd(x, w, bias, *how)


def _conv_fwd(x, w, bias, how):
    return conv_kernels.causal_conv_fwd(x, w, bias, *how), (x, w, bias)


def _conv_bwd(how, res, dy):
    # traced under the scopes of the call (a caller's ``ds_ssm`` or ``ds_lin_attn``, and
    # ``ds_conv`` below), as the forward is; nothing is kept of the forward but its operands
    x, w, bias = res
    start, C = how[:2]
    dx, dw, dbias = conv_kernels.causal_conv_bwd(x, w, bias, dy, *how)
    # the window's cotangent in its place in the operand's, as a slice's transpose puts it
    dx = jnp.pad(dx, ((0, 0), (0, 0), (start, x.shape[2] - start - C)))
    return dx, dw.astype(w.dtype), None if bias is None else dbias.astype(bias.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv(x, w, silu=False, bias=None, columns=None, interpret=None):
    """Depthwise causal convolution over time: ``x [B, T, C]``, ``w [W, C]``,
    ``y_t = sum_j w[j] x_{t - (W - 1) + j}`` with zeros before the first token, plus
    ``bias [C]`` where one is given (a Mamba-2 mixer's; the delta-rule mixer's has none), then
    SiLU where ``silu``. Summed in float32 in that order, returned in ``x``'s dtype.
    ``columns = (start, stop)``: ``x`` is wider, a projection's whole output, and its channels
    ``[start, stop)`` are what is meant; the kernels read them where they lie, no copy is made.

    Two Pallas kernels under a ``jax.custom_vjp`` (``ops/pallas/causal_conv.py``) where the
    channels, and the window's start, fill whole registers of 128 lanes: one pass over the rows
    forward, one backward that keeps nothing but ``x``, ``w`` and ``bias`` and makes the sum
    again. Off the TPU they run interpreted (``interpret`` None), as the other kernels do. Any
    other width takes ``plain_causal_conv``, the same sum in plain ``jnp``."""
    start, stop = columns or (0, x.shape[2])
    with jax.named_scope("ds_conv"):
        if (stop - start) % 128 or start % 128:
            return plain_causal_conv(x[..., start:stop], w, silu, bias)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        C = stop - start
        return _conv(x, w, bias, (start, C, *conv_kernels.sizes(x.shape[1], C, start), silu, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, chunks, interpret):
    return kernels.delta_rule_fwd(q, k, v, g, beta, chunks, interpret)[0]


def _rule_fwd(q, k, v, g, beta, chunks, interpret):
    o, *kept = kernels.delta_rule_fwd(q, k, v, g, beta, chunks, interpret)
    return o, (q, k, v, g, beta, *kept)


def _rule_bwd(chunks, interpret, res, do):
    # traced under the scopes of the call (a caller's ``ds_lin_attn`` and ``ds_delta_rule``
    # below), as the forward is: the benchmark finds the rule's time by them
    return tuple(kernels.delta_rule_bwd(*res, do, chunks, interpret))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, interpret=None):
    """``o [B, T, Hv, Dv]`` (in ``v``'s dtype) of the recurrence above from a zero state, a
    sequence a row: ``q``, ``k`` ``[B, T, Hk, Dk]`` as the convolution leaves them (here each
    is L2-normalised a head in float32, ``x / sqrt(sum x^2 + 1e-6)``, and q scaled by
    ``Dk^-1/2``), ``v`` ``[B, T, Hv, Dv]``, ``g`` (log decay, <= 0) and ``beta``
    ``[B, T, Hv]``; key head h serves the value heads ``h * Hv / Hk`` onwards. Any ``T``:
    the end is filled with tokens that change nothing (k, v, beta, g zero).

    The kernels take the sequence in blocks of ``BLOCK_CHUNKS`` chunks; off the TPU they run
    interpreted (``interpret`` None), as the flash kernels do. On the TPU the head widths are
    multiples of 128."""
    B, T, Hk, Dk = k.shape
    Hv, Dv = v.shape[2:]
    r = Hv // Hk
    chunks = min(BLOCK_CHUNKS, -(-T // CHUNK))
    padded = -(-T // (chunks * CHUNK)) * chunks * CHUNK
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def rows(a):
        """``[B, T, heads, D]`` as ``[B, padded, heads * D]``, zeros to the end."""
        return jnp.pad(a.reshape(B, T, -1), ((0, 0), (0, padded - T), (0, 0)))

    def gate(a):
        """``[B, T, Hv]`` as float32 ``[B, Hk, padded / CHUNK, r / G, G * CHUNK]``: a chunk
        of ``G`` value heads side by side in the lanes."""
        G = kernels.heads_together(r)
        a = jnp.pad(a.astype(jnp.float32), ((0, 0), (0, padded - T), (0, 0)))
        a = a.reshape(B, padded // CHUNK, CHUNK, Hk, r // G, G).transpose(0, 3, 1, 4, 5, 2)
        return a.reshape(B, Hk, padded // CHUNK, r // G, G * CHUNK)

    with jax.named_scope("ds_delta_rule"):
        o = _rule(rows(q), rows(k), rows(v), gate(g), gate(beta), chunks, interpret)
        return o[:, :T].reshape(B, T, Hv, Dv)
