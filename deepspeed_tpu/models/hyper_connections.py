"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on hyper-connections,
arXiv:2409.19606): a block's residual path as ``n`` streams that every sub-layer reads through one
mix and writes back through a per-token doubly-stochastic matrix. Pure functions of arrays and one
sub-layer's parameter dict; ``models/xing_moe.py`` is the caller.

    X [n, C] a token;  x~ = rms(vec(X)) g       (ONE RMSNorm over all n C features)
    H~_pre  = a_pre  (x~ Phi_pre)  + b_pre       H_pre  = sigmoid(H~_pre)
    H~_post = a_post (x~ Phi_post) + b_post      H_post = 2 sigmoid(H~_post)
    H~_res  = a_res mat(x~ Phi_res) + B_res      M = exp(clip(H~_res, lo, hi))
    ``iters`` times:  M <- M / (rowsum(M) + eps);  M <- M / (colsum(M) + eps);   H_res = M
    u = sum_i H_pre[i] X[i]                      (what the sub-layer F reads)
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] F(u)

The streams are carried FLAT, ``[B, T, n C]`` with stream ``i`` the columns ``i C .. (i + 1) C``
(``vec(X)`` of ``[B, T, n, C]``, the same bytes): the flattened norm and the projection read the
array as it stands, a stream is a lane-aligned slice, and no axis of length ``n`` ever lies in a
tile's sublanes. The coefficients are float32 and laid out ``[n, B, T]`` / ``[n, n, B, T]``, the
tokens in the lanes: Sinkhorn-Knopp's sums over rows and columns are sums of whole arrays. The one
product (``n C`` deep onto ``n (n + 2)`` columns) is the compute dtype's, accumulated in float32,
like every other product of a model; everything after it is float32. A mix is written as ``n`` (or
``n n``) multiply-adds over whole streams, which XLA fuses into one pass over the streams.

``connected`` is a sub-layer inside its connection, and picks how it is made from what it can
observe. On the TPU, at streams of whole registers and whole token tiles on one device, it is four
Pallas kernels (``ops/pallas/hyper_connection.py``: ``ds_hc_read`` and ``ds_hc_write``, and their
backwards ``ds_hc_read_bwd`` and ``ds_hc_write_bwd`` behind two ``jax.custom_vjp``s) that each read a
tile's streams once; anywhere else it is the ``jnp`` form above, which is also the kernels'
reference in the tests. The same numbers either way, to the rounding of a sum's order.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.pallas import hyper_connection as kernels
from .layers import rms_norm

SCOPE, COEF_SCOPE, MIX_SCOPE = "ds_hc", "ds_hc_coef", "ds_hc_mix"
# the projection's output by name, for a checkpoint policy: 24 float32 a token a sub-layer. Kept, a
# block's second forward runs no n C-deep product in the ``jnp`` form (its norm and its Sinkhorn-Knopp
# rounds are made again all the same: their own backward reads them). Naming the three coefficient
# sets instead buys nothing (PERF.md, PR 58). The kernels name nothing: ``ds_hc_read`` makes the
# product again under its tile's transfer (PERF.md, PR 59).
KEPT_NAME = "hc_proj"
READINGS = ("hc_res_err_max", "hc_res_diag_mean")       # ``readings``' two device scalars a sub-layer
GATE_INIT, RES_DIAGONAL_INIT = 0.01, 4.0     # no published key: a plain residual, nearly, at the start


def init(rng, n, width, std):
    """One sub-layer's parameters: ``Phi_*`` N(0, ``std``), the flattened norm's weight 1, the
    gates ``a_pre, a_post, a_res`` at 0.01, ``b_pre = b_post = 0`` and ``B_res = 4 I``, so that
    ``H_res`` starts near the identity (a diagonal of 0.948 after 20 rounds at n = 4)."""
    k = jax.random.split(rng, 3)
    normal = lambda key, cols: jax.random.normal(key, (n * width, cols), jnp.float32) * std   # noqa: E731
    return {"norm": jnp.ones((n * width,), jnp.float32),
            "phi_pre": normal(k[0], n), "phi_post": normal(k[1], n), "phi_res": normal(k[2], n * n),
            "b_pre": jnp.zeros((n,), jnp.float32), "b_post": jnp.zeros((n,), jnp.float32),
            "b_res": RES_DIAGONAL_INIT * jnp.eye(n, dtype=jnp.float32),
            "gates": jnp.full((3,), GATE_INIT, jnp.float32)}


def sinkhorn(m, iters, eps):
    """``m [n, n, ...]`` positive: ``iters`` rounds of rows then columns over their sums
    (``+ eps`` in both divisions), unrolled; gradients go through every round."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def coefficients(x, hp, n, iters, eps, clamp, norm_eps):
    """``(H_pre [n, B, T], H_post [n, B, T], H_res [n, n, B, T])`` float32 from the flat streams
    ``x [B, T, n C]``."""
    with jax.named_scope(COEF_SCOPE):
        f32 = lambda a: a.astype(jnp.float32)      # noqa: E731
        normed = rms_norm(x, hp["norm"], norm_eps)
        phi = jnp.concatenate([hp["phi_pre"], hp["phi_post"], hp["phi_res"]], axis=1).astype(x.dtype)
        # [n (n + 2), B, T]: the tokens in the lanes from here on
        proj = jnp.moveaxis(jnp.dot(normed, phi, preferred_element_type=jnp.float32), -1, 0)
        proj = checkpoint_name(proj, KEPT_NAME)
        a_pre, a_post, a_res = f32(hp["gates"])
        pre = a_pre * proj[:n] + f32(hp["b_pre"])[:, None, None]
        post = a_post * proj[n:2 * n] + f32(hp["b_post"])[:, None, None]
        res = a_res * proj[2 * n:].reshape(n, n, *proj.shape[1:]) + f32(hp["b_res"])[:, :, None, None]
        h_res = sinkhorn(jnp.exp(jnp.clip(res, *clamp)), iters, eps)
        return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), h_res


def streams_of(x, n):
    return jnp.split(x, n, axis=-1)


def read(x, h_pre):
    """``u = sum_i H_pre[i] X[i]`` in float32, rounded to the streams' dtype."""
    with jax.named_scope(MIX_SCOPE):
        parts = streams_of(x, h_pre.shape[0])
        u = sum(h_pre[i][..., None] * parts[i].astype(jnp.float32) for i in range(len(parts)))
        return u.astype(x.dtype)


def write(x, f, h_post, h_res):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] f``, flat like ``x``."""
    with jax.named_scope(MIX_SCOPE):
        n = h_post.shape[0]
        parts = [p.astype(jnp.float32) for p in streams_of(x, n)]
        f = f.astype(jnp.float32)
        out = [sum(h_res[i, j][..., None] * parts[j] for j in range(n)) + h_post[i][..., None] * f
               for i in range(n)]
        return jnp.concatenate(out, axis=-1).astype(x.dtype)


def readings(h_res):
    """Two device scalars of one sub-layer: the largest ``|row sum - 1|`` or ``|column sum - 1|``
    of ``H_res`` over the tokens (how doubly stochastic the matrix the step used was), and the
    mean of its diagonal (how near the identity the mixing stays)."""
    n, h_res = h_res.shape[0], jax.lax.stop_gradient(h_res)
    err = jnp.maximum(jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0)),
                      jnp.max(jnp.abs(jnp.sum(h_res, axis=0) - 1.0)))
    diagonal = sum(jnp.mean(h_res[i, i]) for i in range(n)) / n
    return dict(zip(READINGS, (err, diagonal)))


# ------------------------------------------------------------- a sub-layer inside its connection
def connected(x, hp, sub_layer, n, iters, eps, clamp, norm_eps):
    """One sub-layer inside its hyper-connection on the flat streams ``x [..., n C]``: ``(X',
    stats)``, ``stats`` what ``sub_layer(u) -> (f, stats)`` said and ``H_res``'s two readings."""
    tm = kernel_tile(x, n)
    if tm is not None:
        return connected_by_kernels(x, hp, sub_layer, n, iters, eps, clamp, norm_eps, tm)
    with jax.named_scope(SCOPE):
        h_pre, h_post, h_res = coefficients(x, hp, n, iters, eps, clamp, norm_eps)
        u = read(x, h_pre)
    f, stats = sub_layer(u)
    with jax.named_scope(SCOPE):
        return write(x, f, h_post, h_res), dict(stats, **readings(h_res))


def kernel_tile(x, n):
    """The tokens a tile of the kernels' for these streams, or None where the ``jnp`` form runs:
    off the TPU, under a mesh XLA would have to partition a kernel over, or at shapes the
    kernels do not take (``ops/pallas/hyper_connection.tile``)."""
    if jax.default_backend() != "tpu" or x.shape[-1] % n:
        return None
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and any(mesh.shape[a] > 1 for a in mesh.auto_axes):
        return None
    return kernels.tile(x.size // x.shape[-1], n, x.shape[-1] // n, x.dtype.itemsize)


def _spread(pre, post, res, n):
    """``[..., 128]``: the kernels' columns from ``pre``, ``post`` ``[..., n]`` and ``res [..., n n]``."""
    groups = [pre, post] + [res[..., i * n:(i + 1) * n] for i in range(n)]
    wide = lambda a, to: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, to - a.shape[-1])])     # noqa: E731
    return wide(jnp.concatenate([wide(g, 8) for g in groups], axis=-1), kernels.LANES)


def _packed(hp, n):
    """``(Phi [n C, 128], the gate and the bias of every column [2, 128])`` float32."""
    f32 = lambda a: a.astype(jnp.float32)      # noqa: E731
    a_pre, a_post, a_res = f32(hp["gates"])
    gate = _spread(jnp.full((n,), a_pre), jnp.full((n,), a_post), jnp.full((n * n,), a_res), n)
    bias = _spread(f32(hp["b_pre"]), f32(hp["b_post"]), f32(hp["b_res"]).reshape(-1), n)
    return _spread(f32(hp["phi_pre"]), f32(hp["phi_post"]), f32(hp["phi_res"]), n), jnp.stack([gate, bias])


def _operands(x, norm, gate_bias):
    """The norm's weight as eight equal rows and the columns' gates and biases as the first
    column of a square each: the blocks the kernels read them in."""
    g = jnp.broadcast_to(norm.astype(jnp.float32), (8, x.shape[-1]))
    return g, jnp.zeros((2, kernels.LANES, kernels.LANES), jnp.float32).at[:, :, 0].set(gate_bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _read(x, norm, phi, gate_bias, static):
    return _read_fwd(x, norm, phi, gate_bias, static)[0]


def _read_fwd(x, norm, phi, gate_bias, static):
    """``(u, the coefficients [T, 128], the streams again)``: ``_write`` reads the streams from
    the third, so that its cotangent for them arrives here and ``ds_hc_read_bwd`` adds it in its
    own pass (added by JAX, the two cotangents cost a pass of their own). ``static``: ``(n, iters,
    eps, clamp, norm_eps, tm, interpret)``."""
    n, iters, eps, clamp, norm_eps, tm, interpret = static
    g, cols = _operands(x, norm, gate_bias)
    u, co, proj = kernels.read(x, g, phi.astype(x.dtype), cols, n=n, iters=iters, eps=eps, clamp=clamp,
                               norm_eps=norm_eps, tm=tm, interpret=interpret)
    return (u, co, x), (x, proj, norm, phi, gate_bias)


def _read_bwd(static, kept, cotangents):
    # JAX traces a backward under the scopes of its forward's call (``ds_hc/ds_hc_coef`` here)
    n, iters, eps, clamp, _, tm, interpret = static
    x, proj, norm, phi, gate_bias = kept
    du, dco, dxa = cotangents
    g, cols = _operands(x, norm, gate_bias)
    dx, dz, dphit, dg = kernels.read_bwd(x, du, dxa, dco, proj, g, phi.T.astype(x.dtype), cols, n=n, iters=iters,
                                         eps=eps, clamp=clamp, tm=tm, interpret=interpret)
    # z = gate * proj + bias, a column: dz's column RSTD is zero, where proj holds 1 / rms
    dphi = jnp.pad(dphit.T, [(0, 0), (0, kernels.LANES - dphit.shape[0])])
    return dx, dg[0].astype(norm.dtype), dphi, jnp.stack([jnp.sum(dz * proj, axis=0), jnp.sum(dz, axis=0)])


_read.defvjp(_read_fwd, _read_bwd)


def _write_by_kernel(x, f, co, static):
    n, tm, interpret = static
    return kernels.write(x, f, co, n=n, tm=tm, interpret=interpret)


def _write_bwd(static, kept, dy):
    n, tm, interpret = static
    return tuple(kernels.write_bwd(dy, *kept, n=n, tm=tm, interpret=interpret))


_write = jax.custom_vjp(_write_by_kernel, nondiff_argnums=(3,))
_write.defvjp(lambda x, f, co, static: (_write_by_kernel(x, f, co, static), (x, f, co)), _write_bwd)


def connected_by_kernels(x, hp, sub_layer, n, iters, eps, clamp, norm_eps, tm, interpret=False):
    """``connected`` as the four kernels at ``tm`` tokens a tile. ``ds_hc_read`` and its backward
    (the coefficients AND the mix to ``u``) lie under ``ds_hc_coef``, ``ds_hc_write`` and its
    backward under ``ds_hc_mix``, all under ``ds_hc``. ``f`` takes the streams' type."""
    lead, width = x.shape[:-1], x.shape[-1] // n
    with jax.named_scope(SCOPE), jax.named_scope(COEF_SCOPE):
        phi, gate_bias = _packed(hp, n)
        u, co, streams = _read(x.reshape(-1, n * width), hp["norm"], phi, gate_bias,
                               (n, iters, eps, tuple(clamp), norm_eps, tm, interpret))
    f, stats = sub_layer(u.reshape(lead + (width,)))
    with jax.named_scope(SCOPE):
        with jax.named_scope(MIX_SCOPE):
            out = _write(streams, f.reshape(-1, width).astype(x.dtype), co, (n, tm, interpret))
        # H_res [n, n, ...] of the coefficients' columns, for the readings alone
        h_res = jnp.stack([co[:, kernels.RES + 8 * i:kernels.RES + 8 * i + n].T for i in range(n)])
        return out.reshape(x.shape), dict(stats, **readings(h_res.reshape((n, n) + lead)))
