"""Pallas TPU kernels of the mixers' short causal convolution (``ops/delta_rule.causal_conv``
has the sum and the entry point), forward and backward.

    y_t = act(sum_j w[j] x_{t - (W - 1) + j} + bias),      zeros before a row's first token

A grid step is one tile of ``lanes`` channels, one row of the batch and one block of ``rows``
tokens. The ``W - 1`` earlier tokens a block's first outputs need are a HALO: a second view
of the same operand, the sixteen rows before the block (zeros at a row's first block), so
that no block waits for another. A block is widened to float32 once, into a VMEM scratch
with its halo in front; an inner loop then takes ``CHUNK`` rows at a time through registers:
the ``W`` taps are sublane rotations of one float32 window, summed in the plain form's order
(``j = 0 .. W - 1``), then the bias where there is one, SiLU where asked, and one write in the
operand's dtype. x is read once and y written once.

The backward keeps nothing of the forward but its operands. One pass over ``x`` and ``dy``:
the pre-activation is made again from ``x`` (halos on both sides now: ``dx_t`` needs
``g_{t .. t + W - 1}``, ``g = dy * act'(pre)``, and those need x up to ``t + W - 1``),
``dx_t = sum_s w[W - 1 - s] g_{t + s}``, and ``dw[j] = sum_t g_t x_{t - (W - 1) + j}``,
``dbias = sum_t g_t`` are added up in float32 in the output's VMEM block across the row blocks
and the batch (the grid's sequential axes), eight partial sums a channel, and written once.

The operand may be a window of channels ``[start, start + C)`` of a wider array, as a
projection leaves it: the window is a block index, not a copy. Any ``T``: a short last block
reads past the array, what it reads is masked to zero in the backward (in the forward it
only reaches outputs past the end, which are not written).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
HALO = 16          # rows of a halo's view: a whole bfloat16 register; the nearest 8 are used
EDGE = 8           # rows of a halo that a block is given: a whole float32 register, >= W - 1
ROWS = 2048        # tokens a grid step: PERF.md, PR 36, has the sweep
LANES = 256        # channels a grid step, at most
CHUNK = 64         # rows a turn of the inner loop: what stays in registers


def sizes(T, C, start):
    """``(rows, lanes)`` of a grid step for ``T`` tokens of ``C`` channels that begin at
    channel ``start`` of the operand: ``ROWS`` or the whole sequence where that is shorter, in
    whole chunks, and the most lanes up to ``LANES`` that are whole registers of 128 and divide
    both the window and its start."""
    lanes = next(n for n in range(min(LANES, C) // 128 * 128, 0, -128) if C % n == 0 and start % n == 0)
    return min(ROWS, -(-T // CHUNK) * CHUNK), lanes


def _act_grad(pre):
    """SiLU's derivative at ``pre``."""
    s = jax.nn.sigmoid(pre)
    return s * (1.0 + pre * (1.0 - s))


def _taps(window, W):
    """``window [EDGE + n, lanes]`` float32 moved down by ``0 .. W - 1`` rows, less its first
    ``EDGE`` rows (into which a rotation wraps): ``out[s][i] = window[EDGE + i - s]``."""
    return [window[EDGE:]] + [pltpu.roll(window, s, 0)[EDGE:] for s in range(1, W)]


def _pre(taps, w, bias):
    """The sum in the plain form's order, ``j = 0 .. W - 1`` (tap ``j`` reads ``W - 1 - j``
    rows back), then the bias."""
    W = len(taps)
    acc = taps[W - 1] * w[0:1]
    for j in range(1, W):
        acc = acc + taps[W - 1 - j] * w[j:j + 1]
    return acc if bias is None else acc + bias


def _put(into_ref, at, block, keep=True, held=None):
    """``block`` (float32) into ``into_ref`` from row ``at``: zeros where ``keep`` is false (a
    halo beyond a row's end) and, where only ``held`` rows of it lie inside the array (a short
    last block reads past it), from there on."""
    if held is not None:
        keep = keep & (jax.lax.broadcasted_iota(jnp.int32, block.shape, 0) < held)
    into_ref[at:at + block.shape[0], :] = block if keep is True else jnp.where(keep, block, 0.0)


def _rows_before(ref):
    """The ``EDGE`` rows nearest the block of the halo's view in front of it, float32."""
    return ref[...].astype(_F32)[HALO - EDGE:]


def _rows_after(ref):
    return ref[...].astype(_F32)[:EDGE]


def _fwd_kernel(*refs, silu, biased):
    x_ref, before_ref, w_ref = refs[:3]
    y_ref, xs_ref = refs[-2:]
    R, W = x_ref.shape[0], w_ref.shape[0]
    # the block behind EDGE rows of what came before it
    _put(xs_ref, 0, _rows_before(before_ref), pl.program_id(2) > 0)
    _put(xs_ref, EDGE, x_ref[...].astype(_F32))
    w = w_ref[...]
    bias = refs[3][...] if biased else None

    def chunk(i, carry):
        r = pl.multiple_of(i * CHUNK, CHUNK)
        pre = _pre(_taps(xs_ref[pl.ds(r, CHUNK + EDGE), :], W), w, bias)
        y_ref[pl.ds(r, CHUNK), :] = (pre * jax.nn.sigmoid(pre) if silu else pre).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, R // CHUNK, chunk, 0)


def _bwd_kernel(*refs, silu, biased, T):
    x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref = refs[:6]
    dx_ref, sums_ref, xs_ref, dys_ref = refs[-4:]
    R, W = x_ref.shape[0], w_ref.shape[0]
    b, t, last = pl.program_id(1), pl.program_id(2), pl.num_programs(2) - 1
    # where T is no whole number of blocks: the rows from the block's first that the array holds
    held = T - t * R if T % R else None
    behind = None if held is None else held - R
    # x from EDGE rows before the block to EDGE after it, dy on the block and EDGE after it
    _put(xs_ref, 0, _rows_before(before_ref), t > 0)
    _put(xs_ref, EDGE, x_ref[...].astype(_F32), held=held)
    _put(xs_ref, EDGE + R, _rows_after(after_ref), t < last, behind)
    _put(dys_ref, 0, dy_ref[...].astype(_F32), held=held)
    _put(dys_ref, R, _rows_after(dy_after_ref), t < last, behind)
    w = w_ref[...]
    bias = refs[6][...] if biased else None

    def fold(a):
        """``a [CHUNK, lanes]`` summed to eight partial sums a channel: whole registers added."""
        out = a[0:8]
        for k in range(8, CHUNK, 8):
            out = out + a[k:k + 8]
        return out

    def chunk(i, sums):
        r = pl.multiple_of(i * CHUNK, CHUNK)
        taps = _taps(xs_ref[pl.ds(r, CHUNK + 2 * EDGE), :], W)
        g = dys_ref[pl.ds(r, CHUNK + EDGE), :]
        if silu:
            g = g * _act_grad(_pre(taps, w, bias))
        n = CHUNK + EDGE
        dx = g * w[W - 1:W]
        for s in range(1, W):
            dx = dx + pltpu.roll(g, n - s, 0) * w[W - 1 - s:W - s]
        dx_ref[pl.ds(r, CHUNK), :] = dx[:CHUNK].astype(dx_ref.dtype)
        own = g[:CHUNK]
        return tuple(acc + fold(own * taps[W - 1 - j][:CHUNK]) for j, acc in
                     enumerate(sums[:W])) + (sums[W] + fold(own),)

    nothing = jnp.zeros((8, x_ref.shape[1]), _F32)
    sums = jax.lax.fori_loop(0, R // CHUNK, chunk, (nothing,) * (W + 1))

    @pl.when((b == 0) & (t == 0))
    def _():
        sums_ref[...] = jnp.zeros(sums_ref.shape, _F32)

    for j in range(W + 1):
        sums_ref[j] += sums[j]


def _specs(T, W, start, R, L):
    """Block specs by kind for a grid ``(C / L, B, T / R)``: the operand's blocks and halos
    (its window begins ``start / L`` tiles in), the compact arrays', the weights'."""
    k, halos, first = R // HALO, -(-T // HALO), start // L

    def before(t):
        return jnp.maximum(t * k - 1, 0)

    def after(t):
        return jnp.minimum((t + 1) * k, halos - 1)

    return dict(
        x=pl.BlockSpec((None, R, L), lambda c, b, t: (b, t, first + c)),
        x_before=pl.BlockSpec((None, HALO, L), lambda c, b, t: (b, before(t), first + c)),
        x_after=pl.BlockSpec((None, HALO, L), lambda c, b, t: (b, after(t), first + c)),
        y=pl.BlockSpec((None, R, L), lambda c, b, t: (b, t, c)),
        y_after=pl.BlockSpec((None, HALO, L), lambda c, b, t: (b, after(t), c)),
        w=pl.BlockSpec((W, L), lambda c, b, t: (0, c)),
        bias=pl.BlockSpec((1, L), lambda c, b, t: (0, c)),
        sums=pl.BlockSpec((W + 1, 8, L), lambda c, b, t: (0, 0, c)),
    )


def _checked(x, w, start, C, rows, lanes, interpret):
    B, T, wide = x.shape
    W = w.shape[0]
    assert W - 1 <= EDGE and w.shape == (W, C) and 0 <= start and start + C <= wide, (x.shape, w.shape, start)
    assert rows % CHUNK == 0 and C % lanes == 0 and start % lanes == 0, (rows, lanes, start, C)
    assert interpret or lanes % 128 == 0, \
        f"the convolution's kernels take channels in whole registers of 128 lanes, not {lanes} of {C}"
    return B, T, W


def _row(bias):
    """The bias as the kernels' last operand ``[1, C]`` float32, or no operand."""
    return [] if bias is None else [bias.astype(_F32)[None]]


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=64 * 2 ** 20)


# jitted and inlined, as the scan's kernels are (``ops/pallas/ssd.py``): a body is traced once
# a shape, not once a call, and every call is still an equation under its own scopes
_inlined = functools.partial(jax.jit, static_argnames=("start", "C", "rows", "lanes", "silu", "interpret"),
                             inline=True)


@_inlined
def causal_conv_fwd(x, w, bias, start, C, rows, lanes, silu, interpret):
    """``y [B, T, C]`` in ``x``'s dtype from channels ``[start, start + C)`` of ``x [B, T, wide]``,
    ``w [W, C]`` and ``bias [C]`` or None, ``rows`` tokens and ``lanes`` channels a grid step
    (``sizes``)."""
    B, T, W = _checked(x, w, start, C, rows, lanes, interpret)
    spec = _specs(T, W, start, rows, lanes)
    biased = bias is not None
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, silu=silu, biased=biased),
        grid=(C // lanes, B, -(-T // rows)),
        in_specs=[spec["x"], spec["x_before"], spec["w"]] + [spec["bias"]] * biased,
        out_specs=spec["y"],
        out_shape=jax.ShapeDtypeStruct((B, T, C), x.dtype),
        scratch_shapes=[pltpu.VMEM((EDGE + rows, lanes), _F32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        name="ds_causal_conv_fwd",
    )
    with jax.named_scope("ds_causal_conv_fwd"):
        return call(x, x, w.astype(_F32), *_row(bias))


@_inlined
def causal_conv_bwd(x, w, bias, dy, start, C, rows, lanes, silu, interpret):
    """From ``dy [B, T, C]``: ``(dx [B, T, C]`` in ``x``'s dtype (the window's; the caller puts
    it in its place), ``dw [W, C]``, ``dbias [C])`` in float32."""
    B, T, W = _checked(x, w, start, C, rows, lanes, interpret)
    spec = _specs(T, W, start, rows, lanes)
    biased = bias is not None
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, silu=silu, biased=biased, T=T),
        grid=(C // lanes, B, -(-T // rows)),
        in_specs=[spec["x"], spec["x_before"], spec["x_after"], spec["y"], spec["y_after"], spec["w"]]
        + [spec["bias"]] * biased,
        out_specs=[spec["y"], spec["sums"]],
        out_shape=[jax.ShapeDtypeStruct((B, T, C), x.dtype), jax.ShapeDtypeStruct((W + 1, 8, C), _F32)],
        scratch_shapes=[pltpu.VMEM((EDGE + rows + EDGE, lanes), _F32), pltpu.VMEM((rows + EDGE, lanes), _F32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="ds_causal_conv_bwd",
    )
    with jax.named_scope("ds_causal_conv_bwd"):
        dx, sums = call(x, x, x, dy, dy, w.astype(_F32), *_row(bias))
        sums = jnp.sum(sums, axis=1)
    return dx, sums[:W], sums[W]
