"""The selective state-space scan of a Mamba-2 layer, in its chunked (state-space-duality,
SSD) form.

A head keeps a state ``S [P, N]`` (``P`` the head's width, ``N`` the state size) and sees a
token at a time:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;      y_t = S_t C_t + D x_t

with ``A < 0`` and ``D`` a head, ``dt_t > 0`` a head and token, ``B_t``, ``C_t`` ``[N]`` one
for all heads (one group), or one for each of ``G`` groups of ``H / G`` successive heads
(head ``h`` reads group ``h // (H / G)``).

Training does not run that recurrence. Inside a tile of tokens the state's part in the
outputs is a masked matrix product, ``Y = ((C B^T) * L * dt) x`` with
``L_ij = exp(sum_{j < m <= i} dt_m A)`` for ``i >= j``, each tile leaves the state its own
tokens build, the float32 state goes from tile to tile, and what a tile was handed is read by
``C`` and decayed to each token. ``ssd_scan`` is a ``jax.custom_vjp`` over two Pallas kernels
(``ops/pallas/ssd.py``): a tile's decay matrices and the carried state stay in VMEM, and the
backward is a kernel of its own that walks the sequence in reverse from the states the
forward kept.

The matrix products take their operands in ``x``'s dtype (bfloat16 in a step; the decay
matrix, ``dt`` folded in, is rounded once for the product, as the carried state is for its
read by ``C``) and accumulate in float32; ``dt``, ``A``, the decays and the carried state are
float32. Float32 operands go through the same kernels as three exact bfloat16 terms each.
"""

import functools

import jax
import jax.numpy as jnp

from .pallas import ssd as kernels

CHUNK = 256       # the published ``mamba_chunk_size``
TILE = 128        # tokens a grid step of the kernels: PERF.md, PR 34, has the sweep
HEADS = 64        # heads a grid step: all of Granite's, so that a tile is one step


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(x, dt, A, B, C, D, heads, interpret, groups):
    return kernels.ssd_scan_fwd(x, dt, A, B, C, D, heads, interpret, groups)[0]


def _scan_fwd(x, dt, A, B, C, D, heads, interpret, groups):
    y, start = kernels.ssd_scan_fwd(x, dt, A, B, C, D, heads, interpret, groups)
    return y, (x, dt, A, B, C, D, start)


def _scan_bwd(heads, interpret, groups, res, dy):
    # traced under the scopes of the call (a caller's ``ds_ssm`` and ``ds_ssd_scan`` below),
    # as the forward is: the benchmark finds the scan's time by them
    x, dt, A, B, C, D, _ = res
    dx, da, ddt, dB, dC, dD = kernels.ssd_scan_bwd(*res, dy, heads, interpret, groups)
    # the log decay is dt A: its cotangent reaches both
    return (dx, ddt + A * da, jnp.sum(dt * da, axis=(0, 1, 3))[:, None],
            dB.astype(B.dtype), dC.astype(C.dtype), jnp.sum(dD, axis=(0, 1)))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, A, B, C, D, chunk=CHUNK, interpret=None):
    """``y [Bt, T, H, P]`` (in ``x``'s dtype) of the recurrence above from a zero state, a
    sequence a row: ``x [Bt, T, H, P]``, ``dt [Bt, T, H]`` (after its softplus), ``A``,
    ``D`` ``[H]``, ``B``, ``C`` ``[Bt, T, N]`` (one group) or ``[Bt, T, G, N]``. Any ``T``: the
    end is filled up to a whole tile with tokens that change nothing (``dt``, ``x``, ``B``,
    ``C`` zero).

    The kernels take the sequence in tiles of ``min(chunk, TILE)`` tokens (the result does not
    depend on the tile beyond rounding) and ``HEADS`` heads a grid step; off the TPU they run
    interpreted (``interpret`` None), as the flash kernels do. On the TPU the heads fill
    whole registers of 128 lanes. With several groups a grid step takes one group's heads
    (``HEADS`` of them where a group has more)."""
    Bt, T, H, P = x.shape
    f32 = jnp.float32
    tile = min(chunk, TILE)
    groups = 1 if B.ndim == 3 else B.shape[2]
    B, C = B.reshape(Bt, T, -1), C.reshape(Bt, T, -1)
    assert H % groups == 0, f"{H} heads in {groups} groups"
    heads = HEADS if (H // groups) % HEADS == 0 else H // groups
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    with jax.named_scope("ds_ssd_scan"):
        fill = -T % tile

        def rows(a):
            return jnp.pad(a, ((0, 0), (0, fill)) + ((0, 0),) * (a.ndim - 2))

        # a tile's steps with its tokens in the lanes: [Bt, tiles, H, tile]
        steps = jnp.swapaxes(rows(dt.astype(f32)).reshape(Bt, (T + fill) // tile, tile, H), 2, 3)
        y = _scan(rows(x.reshape(Bt, T, H * P)), steps, A.astype(f32)[:, None], rows(B), rows(C),
                  jnp.repeat(D.astype(f32), P)[None], heads, interpret, groups)
        return y[:, :T].reshape(Bt, T, H, P)
