"""Pallas TPU kernels of a sub-layer's hyper-connection (``models/hyper_connections.py`` has the
mathematics, the entry point ``connected`` and the rule that picks these kernels or its own ``jnp``
form): four kernels that each take a TILE OF TOKENS, hold that tile's ``n`` streams ``[tm, n C]`` in
fast memory, and do everything that needs them before letting them go. A token's coefficients
depend on its own row alone, so a tile is self-contained and the grid is one axis of token tiles.

    ds_hc_read        X -> the flattened norm, the projection onto the n (n + 2) columns (MXU), the
                      gates, both sigmoids, exp(clip(.)) and ALL the Sinkhorn-Knopp rounds,
                      u = sum_i H_pre[i] X[i]                           out: u, the coefficients
    ds_hc_write       X, f, the coefficients -> X'[i] = sum_j H_res[i, j] X[j] + H_post[i] f
    ds_hc_write_bwd   dX', X, f, the coefficients -> df, dX_res[j] = sum_i H_res[i, j] dX'[i], and
                      in the same pass  dH_post[i] = <dX'[i], f>,  dH_res[i, j] = <dX'[i], X[j]>
    ds_hc_read_bwd    X, du, dX_res, those cotangents -> dH_pre[i] = <du, X[i]>, the rounds made
                      again and pulled back (all of them), the sigmoids, the gates, d proj,
                      d x~ = d proj Phi^T (MXU), the norm's backward,
                      dX = dX_res + H_pre du + (the norm's part), and d Phi^T = d proj^T x~ (MXU)
                      and the norm weight's gradient summed over the token tiles

TWO LAYOUTS of a tile's coefficients. Between kernels they travel ``[T, 128]`` float32, a token a
row, column ``PRE + i`` holding ``H_pre[i]``, ``POST + i`` ``H_post[i]`` and ``RES + 8 i + j``
``H_res[i, j]``: a coefficient is a column ``[tm, 1]``, broadcast along the lanes of a stream's
``[tm, C]`` block. The rounds run on the TRANSPOSE ``[128, tm]``, the tokens in the lanes: every
group above is eight sublanes (a whole register's rows: ``n <= 8``), a matrix row ``H_res[i, :]``
is one slab ``[8, tm]`` whose rows past ``n`` are zero, the sum over ``j`` is a sum over a slab's
sublanes and the sum over ``i`` a sum of slabs. The projection's output ``[tm, 128]`` is laid out in
those columns by the padded ``Phi`` itself; column ``RSTD`` of what the forward keeps of it holds the
norm's ``1 / rms``.

Everything after the projection is float32, and so is every sum over ``C``; the streams are rounded
where the ``jnp`` form rounds them (``x~`` before the product, ``u`` and ``X'`` at the store); the
cotangents of ``x~`` and ``Phi`` are not rounded to the streams' type on their way, as XLA's are.
The products take the streams' type (float32 streams: ``Precision.HIGHEST``), accumulated in float32.
One reading differs from the ``jnp`` form's by a rounding: the norm's backward needs
``sum_c x~[c] dx~[c]``, which is ``<d proj, x~ Phi>``; the kernel takes the KEPT projection for
``x~ Phi`` (made of the rounded ``x~``) where XLA sums over the ``n C`` features again.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import _TN, VMEM_CAP, _in_hbm, vmem_limit

_F32 = jnp.float32
LANES = 128
PRE, POST, RES = 0, 8, 16           # a group's first column (row, transposed); matrix row i at RES + 8 i
RSTD = LANES - 1                    # the column of the kept projection that holds 1 / rms
CHUNK = 512                         # columns of a stream a loop's turn takes: [tm, CHUNK] float32 temporaries
TILES = (256, 128)                  # tokens a tile, the largest that divides and fits (PERF.md, PR 59: the sweep)


def columns(n):
    """The ``n (n + 2)`` columns in the ``jnp`` form's order: pre, post, ``H_res`` row by row."""
    return ([PRE + i for i in range(n)] + [POST + i for i in range(n)]
            + [RES + 8 * i + j for i in range(n) for j in range(n)])


def _rows(n):
    return RES + 8 * n


def _chunk(C):
    return next(ck for ck in (CHUNK, 256, LANES) if C % ck == 0)


def block_bytes(kind, tm, n, C, itemsize):
    """The fast memory a kernel's blocks take at ``tm`` tokens a tile, two buffers each."""
    stream, one, coef = tm * n * C * itemsize, tm * C * itemsize, tm * LANES * 4
    weights = n * C * LANES
    return 2 * {
        "read": stream + one + 2 * coef + weights * itemsize + 8 * n * C * 4,
        "write": 2 * stream + one + coef,
        "write_bwd": 3 * stream + 2 * one + 2 * coef,
        "read_bwd": 3 * stream + one + 3 * coef + n * C * (LANES * itemsize + _rows(n) * 4) + 2 * 8 * n * C * 4,
    }[kind] + {"write_bwd": n * (n + 1), "read_bwd": n}.get(kind, 0) * coef


def _limit(kind, tm, n, C, itemsize):
    return vmem_limit(block_bytes(kind, tm, n, C, itemsize), 8 * tm * _chunk(C))


def tile(tokens, n, C, itemsize):
    """Tokens a tile for ``tokens`` tokens of ``n`` streams ``C`` wide, or None where the kernels
    do not take the shapes: whole registers (``C`` a multiple of 128, ``n`` at most a register's
    sublanes), whole tiles, and every kernel's blocks inside the fast memory its call states."""
    if C % LANES or not 1 <= n <= 8:
        return None
    for tm in TILES:
        if tokens % tm == 0 and all(_limit(kind, tm, n, C, itemsize) < VMEM_CAP
                                    for kind in ("read", "write", "write_bwd", "read_bwd")):
            return tm
    return None


# ------------------------------------------------------------- the coefficients, tokens in the lanes
def _chain(pt, gate, bias, n, iters, eps, clamp):
    """``pt [R, tm]`` the projection transposed, ``gate``, ``bias`` ``[R, 1]``: ``(z, H_pre [8, tm],
    H_post [8, tm], exp(clip(.)) and H_res as n slabs [8, tm], every half round's (output, divisor))``."""
    z = gate * pt + bias
    pre, post = jax.nn.sigmoid(z[PRE:PRE + 8]), 2.0 * jax.nn.sigmoid(z[POST:POST + 8])
    valid = lax.broadcasted_iota(jnp.int32, (8, pt.shape[1]), 0) < n
    m = m0 = [jnp.where(valid, jnp.exp(jnp.clip(z[RES + 8 * i:RES + 8 * i + 8], *clamp)), 0.0) for i in range(n)]
    rounds = []
    for _ in range(iters):
        over_j = [jnp.sum(mi, axis=0, keepdims=True) + eps for mi in m]
        m = [mi / d for mi, d in zip(m, over_j)]
        rounds.append((m, over_j))
        over_i = jnp.where(valid, sum(m) + eps, 1.0)      # not eps in the empty rows: twenty of them multiplied are zero
        m = [mi / over_i for mi in m]
        rounds.append((m, [over_i] * n))
    return z, pre, post, m0, m, rounds


def _chain_bwd(z, pre, post, m0, rounds, dpre, dpost, dm, clamp):
    """``dz [R, tm]`` from the cotangents of ``H_pre``, ``H_post`` ``[8, tm]`` and ``H_res`` (n slabs):
    ``y = m / d`` with ``d = sum(m) + eps`` pulls ``dy`` back to ``(dy - sum(dy y)) / d``, the sum over
    the axis the half round summed over; the rounds in reverse, every one of them."""
    for turn, (y, d) in reversed(list(enumerate(rounds))):
        if turn % 2:            # over i: a sum of slabs
            inner = sum(dyi * yi for dyi, yi in zip(dm, y))
            dm = [(dyi - inner) / di for dyi, di in zip(dm, d)]
        else:                   # over j: a slab's sublanes
            dm = [(dyi - jnp.sum(dyi * yi, axis=0, keepdims=True)) / di for dyi, yi, di in zip(dm, y, d)]
    inside = lambda zi: (zi > clamp[0]) & (zi < clamp[1])     # noqa: E731
    dres = [jnp.where(inside(z[RES + 8 * i:RES + 8 * i + 8]), dmi * m0i, 0.0) for i, (dmi, m0i) in enumerate(zip(dm, m0))]
    return jnp.concatenate([dpre * pre * (1.0 - pre), dpost * post * (1.0 - 0.5 * post)] + dres, axis=0)


def _tokens_in_rows(slabs, tm):
    """``[tm, 128]`` from slabs ``[8, tm]``, slab ``s`` the columns ``8 s .. 8 s + 7``."""
    rows = sum(s.shape[0] for s in slabs)
    return jnp.concatenate(slabs + [jnp.zeros((LANES - rows, tm), _F32)], axis=0).T


def _column(a, lane, k):
    """Column ``k`` of ``a [tm, 128]`` as ``[tm, 1]``."""
    return jnp.sum(jnp.where(lane == k, a, 0.0), axis=1, keepdims=True)


def _fold(a):
    """``[tm, 128]``: the sum of ``a [tm, ck]``'s registers along the lanes."""
    return sum(a[:, k:k + LANES] for k in range(0, a.shape[1], LANES))


def _chunks(C, ck, body):
    """``body(offset)`` for every chunk of ``ck`` columns of a stream ``C`` wide."""
    lax.fori_loop(0, C // ck, lambda c, carry: body(pl.multiple_of(c * ck, ck)) or carry, 0)


def _f32(ref, start, size):
    return ref[:, pl.ds(start, size)].astype(_F32)


# ------------------------------------------------------------- ds_hc_read
def _read_kernel(x_ref, g_ref, phi_ref, cols_ref, u_ref, co_ref, proj_ref, acc_ref, *,
                 n, C, ck, iters, eps, clamp, norm_eps, precision):
    tm = x_ref.shape[0]
    R = _rows(n)
    lane = lax.broadcasted_iota(jnp.int32, (tm, LANES), 1)

    acc_ref[0] = jnp.zeros((tm, LANES), _F32)

    def squares(off):
        xs = _f32(x_ref, off, ck)
        acc_ref[0] += _fold(xs * xs)
    _chunks(n * C, ck, squares)
    rstd = lax.rsqrt(jnp.sum(acc_ref[0], axis=1, keepdims=True) / (n * C) + norm_eps)      # [tm, 1]

    acc_ref[0] = jnp.zeros((tm, LANES), _F32)

    def project(off):
        normed = ((_f32(x_ref, off, ck) * rstd) * g_ref[0:1, pl.ds(off, ck)]).astype(x_ref.dtype)
        acc_ref[0] += jnp.dot(normed, phi_ref[pl.ds(off, ck), :], preferred_element_type=_F32, precision=precision)
    _chunks(n * C, ck, project)
    proj = acc_ref[0]
    proj_ref[...] = jnp.where(lane == RSTD, rstd, proj)

    _, pre, post, _, m, _ = _chain(proj.T[:R], cols_ref[0, :R, 0:1], cols_ref[1, :R, 0:1], n, iters, eps, clamp)
    co = _tokens_in_rows([pre, post] + m, tm)
    co_ref[...] = co
    h_pre = [co[:, PRE + i:PRE + i + 1] for i in range(n)]

    def mix(off):
        u = sum(h_pre[i] * _f32(x_ref, i * C + off, ck) for i in range(n))
        u_ref[:, pl.ds(off, ck)] = u.astype(u_ref.dtype)
    _chunks(C, ck, mix)


def _call(kernel, name, kind, tm, n, C, operands, in_blocks, outs, scratch, interpret, flops):
    """One ``pallas_call`` over the token tiles. ``in_blocks`` / ``outs``: a block's shape and
    whether it follows the token tile (else the one block, kept over the grid)."""
    tokens = operands[0].shape[0]
    in_hbm, out_in_hbm = _in_hbm(interpret)

    def spec(shape, tiled):
        zeros = (0,) * (len(shape) - 1)
        return pl.BlockSpec(shape, (lambda t: (t,) + zeros) if tiled else (lambda t: (0,) + zeros))

    out_shapes = [((tokens,) + shape[1:] if tiled else shape, dt) for shape, tiled, dt in outs]
    moved = sum(o.size * o.dtype.itemsize for o in operands) + sum(
        jnp.dtype(dt).itemsize * math.prod(s) for s, dt in out_shapes)
    return pl.pallas_call(
        kernel,
        out_shape=[out_in_hbm(s, dt) for s, dt in out_shapes],
        grid=(tokens // tm,),
        in_specs=[spec(shape, tiled) for shape, tiled in in_blocks],
        out_specs=[spec(shape, tiled) for shape, tiled, _ in outs],
        scratch_shapes=scratch,
        # a kept block (the gradients summed over the tiles) needs the tiles in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=_limit(kind, tm, n, C, operands[0].dtype.itemsize)),
        cost_estimate=pl.CostEstimate(flops=flops, transcendentals=0, bytes_accessed=moved),
        interpret=interpret,
        name=name,
    )(*map(in_hbm, operands))


def _precision(dtype):
    # a float32 operand goes through the MXU whole: three bfloat16 pieces, six passes
    return lax.Precision.HIGHEST if dtype == _F32 else None


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "clamp", "norm_eps", "tm", "interpret"), inline=True)
def read(x, g, phi, cols, *, n, iters, eps, clamp, norm_eps, tm, interpret=False):
    """``(u [T, C], the coefficients [T, 128] float32, the projection [T, 128] float32 with
    1 / rms in column RSTD)`` from the flat streams ``x [T, n C]``, the norm's weight ``g [8, n C]``
    float32 (eight equal rows), ``phi [n C, 128]`` in the streams' type and ``cols [2, 128, 128]``
    float32, whose first columns hold the gate and the bias of every row of the transposed layout."""
    T, nC = x.shape
    C = nC // n
    kernel = functools.partial(_read_kernel, C=C, ck=_chunk(C), precision=_precision(x.dtype), norm_eps=norm_eps,
                               n=n, iters=iters, eps=eps, clamp=clamp)
    return _call(kernel, "ds_hc_read", "read", tm, n, C, (x, g, phi, cols),
                 [((tm, nC), True), ((8, nC), False), ((nC, LANES), False), ((2, LANES, LANES), False)],
                 [((tm, C), True, x.dtype), ((tm, LANES), True, _F32), ((tm, LANES), True, _F32)],
                 [pltpu.VMEM((1, tm, LANES), _F32)], interpret, 2 * T * nC * (LANES + 3))


# ------------------------------------------------------------- ds_hc_write
def _coefficient_columns(co, n):
    return ([co[:, POST + i:POST + i + 1] for i in range(n)],
            [[co[:, RES + 8 * i + j:RES + 8 * i + j + 1] for j in range(n)] for i in range(n)])


def _write_kernel(x_ref, f_ref, co_ref, y_ref, *, n, C, ck):
    h_post, h_res = _coefficient_columns(co_ref[...], n)

    def mix(off):
        f = _f32(f_ref, off, ck)
        xs = [_f32(x_ref, j * C + off, ck) for j in range(n)]
        for i in range(n):
            y = sum(h_res[i][j] * xs[j] for j in range(n)) + h_post[i] * f
            y_ref[:, pl.ds(i * C + off, ck)] = y.astype(y_ref.dtype)
    _chunks(C, ck, mix)


@functools.partial(jax.jit, static_argnames=("n", "tm", "interpret"), inline=True)
def write(x, f, co, *, n, tm, interpret=False):
    """``X' [T, n C]`` from the flat streams ``x``, the sub-layer's output ``f [T, C]`` and the
    coefficients ``co [T, 128]``."""
    T, nC = x.shape
    C = nC // n
    return _call(functools.partial(_write_kernel, n=n, C=C, ck=_chunk(C)), "ds_hc_write", "write", tm, n, C,
                 (x, f, co), [((tm, nC), True), ((tm, C), True), ((tm, LANES), True)],
                 [((tm, nC), True, x.dtype)], [], interpret, 2 * T * nC * (n + 1))[0]


# ------------------------------------------------------------- ds_hc_write_bwd
def _write_bwd_kernel(dy_ref, x_ref, f_ref, co_ref, dxr_ref, df_ref, dco_ref, acc_ref, *, n, C, ck):
    tm = dy_ref.shape[0]
    h_post, h_res = _coefficient_columns(co_ref[...], n)
    acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def mix(off):
        f = _f32(f_ref, off, ck)
        dys = [_f32(dy_ref, i * C + off, ck) for i in range(n)]
        xs = [_f32(x_ref, j * C + off, ck) for j in range(n)]
        df_ref[:, pl.ds(off, ck)] = sum(h_post[i] * dys[i] for i in range(n)).astype(df_ref.dtype)
        for j in range(n):
            dxr_ref[:, pl.ds(j * C + off, ck)] = sum(h_res[i][j] * dys[i] for i in range(n)).astype(dxr_ref.dtype)
        for i in range(n):
            acc_ref[i] += _fold(dys[i] * f)
            for j in range(n):
                acc_ref[n + i * n + j] += _fold(dys[i] * xs[j])
    _chunks(C, ck, mix)

    lane = lax.broadcasted_iota(jnp.int32, (tm, LANES), 1)
    dco = jnp.zeros((tm, LANES), _F32)
    for i in range(n):
        dco = jnp.where(lane == POST + i, jnp.sum(acc_ref[i], axis=1, keepdims=True), dco)
        for j in range(n):
            dco = jnp.where(lane == RES + 8 * i + j, jnp.sum(acc_ref[n + i * n + j], axis=1, keepdims=True), dco)
    dco_ref[...] = dco


@functools.partial(jax.jit, static_argnames=("n", "tm", "interpret"), inline=True)
def write_bwd(dy, x, f, co, *, n, tm, interpret=False):
    """``(dX_res [T, n C], df [T, C], the cotangents of H_post and H_res [T, 128] float32)``."""
    T, nC = x.shape
    C = nC // n
    return _call(functools.partial(_write_bwd_kernel, n=n, C=C, ck=_chunk(C)), "ds_hc_write_bwd", "write_bwd", tm, n, C,
                 (dy, x, f, co), [((tm, nC), True), ((tm, nC), True), ((tm, C), True), ((tm, LANES), True)],
                 [((tm, nC), True, x.dtype), ((tm, C), True, x.dtype), ((tm, LANES), True, _F32)],
                 [pltpu.VMEM((n * (n + 1), tm, LANES), _F32)], interpret, 4 * T * nC * (n + 1))


# ------------------------------------------------------------- ds_hc_read_bwd
def _read_bwd_kernel(x_ref, du_ref, dxa_ref, dco_ref, proj_ref, g_ref, phit_ref, cols_ref,
                     dx_ref, dz_ref, dphit_ref, dg_ref, acc_ref, *, n, C, ck, iters, eps, clamp, precision):
    tm = x_ref.shape[0]
    R = _rows(n)
    lane = lax.broadcasted_iota(jnp.int32, (tm, LANES), 1)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphit_ref[...] = jnp.zeros(dphit_ref.shape, _F32)
        dg_ref[...] = jnp.zeros(dg_ref.shape, _F32)

    kept = proj_ref[...]
    rstd = _column(kept, lane, RSTD)
    proj = jnp.where(lane == RSTD, 0.0, kept)

    acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def read_products(off):
        du = _f32(du_ref, off, ck)
        for i in range(n):
            acc_ref[i] += _fold(du * _f32(x_ref, i * C + off, ck))
    _chunks(C, ck, read_products)
    dco = dco_ref[...]
    for i in range(n):
        dco = jnp.where(lane == PRE + i, jnp.sum(acc_ref[i], axis=1, keepdims=True), dco)

    gate = cols_ref[0, :R, 0:1]
    z, pre, post, m0, _, rounds = _chain(proj.T[:R], gate, cols_ref[1, :R, 0:1], n, iters, eps, clamp)
    dcot = dco.T
    dzt = _chain_bwd(z, pre, post, m0, rounds, dcot[PRE:PRE + 8], dcot[POST:POST + 8],
                     [dcot[RES + 8 * i:RES + 8 * i + 8] for i in range(n)], clamp)
    dz_ref[...] = _tokens_in_rows([dzt], tm)
    dproj = _tokens_in_rows([gate * dzt], tm)
    # sum_c x~[c] dx~[c] = <d proj, x~ Phi>, a token: the kept projection for x~ Phi
    inner = jnp.sum(dproj * proj, axis=1, keepdims=True) / (n * C)
    dproj = dproj.astype(x_ref.dtype)
    co = _tokens_in_rows([pre], tm)
    h_pre = [co[:, PRE + i:PRE + i + 1] for i in range(n)]

    def pull_back(off):
        du = _f32(du_ref, off, ck)
        for i in range(n):
            at = pl.ds(i * C + off, ck)
            g = g_ref[0:1, at]
            xhat = x_ref[:, at].astype(_F32) * rstd
            dnormed = jnp.dot(dproj, phit_ref[:, at], preferred_element_type=_F32, precision=precision)
            dphit_ref[:, at] += lax.dot_general(dproj, (xhat * g).astype(x_ref.dtype), _TN,
                                                preferred_element_type=_F32, precision=precision)[:R]
            dg_ref[:, at] += jnp.sum(dnormed * xhat, axis=0, keepdims=True)
            dx = rstd * (dnormed * g - xhat * inner) + h_pre[i] * du + dxa_ref[:, at].astype(_F32)
            dx_ref[:, at] = dx.astype(dx_ref.dtype)
    _chunks(C, ck, pull_back)


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "clamp", "tm", "interpret"), inline=True)
def read_bwd(x, du, dxa, dco, proj, g, phit, cols, *, n, iters, eps, clamp, tm, interpret=False):
    """``(dX [T, n C], dz [T, 128] float32: the cotangent of gate * proj + bias, d Phi^T float32, its
    ``16 + 8 n`` rows that hold a column, the norm weight's gradient [8, n C] float32, eight equal rows)`` from the streams, the
    cotangents ``du [T, C]`` of ``u``, ``dxa [T, n C]`` of the streams as ``write`` read them and
    ``dco [T, 128]`` of the coefficients, the kept projection, and ``read``'s parameters
    (``phit [128, n C]``: ``phi`` transposed)."""
    T, nC = x.shape
    C = nC // n
    kernel = functools.partial(_read_bwd_kernel, C=C, ck=_chunk(C), precision=_precision(x.dtype),
                               n=n, iters=iters, eps=eps, clamp=clamp)
    return _call(kernel, "ds_hc_read_bwd", "read_bwd", tm, n, C, (x, du, dxa, dco, proj, g, phit, cols),
                 [((tm, nC), True), ((tm, C), True), ((tm, nC), True), ((tm, LANES), True), ((tm, LANES), True),
                  ((8, nC), False), ((LANES, nC), False), ((2, LANES, LANES), False)],
                 [((tm, nC), True, x.dtype), ((tm, LANES), True, _F32), ((_rows(n), nC), False, _F32), ((8, nC), False, _F32)],
                 [pltpu.VMEM((n, tm, LANES), _F32)], interpret, 2 * T * nC * (2 * LANES + 8))
