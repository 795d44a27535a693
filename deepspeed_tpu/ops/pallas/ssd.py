"""Pallas TPU kernels of the chunked state-space (SSD) scan (``ops/ssd.py`` has the
recurrence and the entry point), forward and backward.

A grid step is one batch row, one tile of ``Q`` tokens and one group of ``Hg`` heads; the
tiles of a row and, inside a tile, its groups of heads are the sequential axes, so that ``B``
and ``C`` (one for all heads) are fetched once a tile, ``G = C B^T`` is made once a tile
(a scratch), and the backward adds the heads' cotangents of ``B`` and ``C`` up in VMEM. Where
the heads come in ``groups`` that each have a ``B`` and ``C`` of their own (``b``, ``c``
``[B, T, groups * N]``, a group's ``N`` side by side), a grid step is the heads of one such
group or a divisor of them: the ``b``/``c`` block follows the step's group, ``G`` is made
again when the group changes, and the cotangents are summed into the group's own block. The
heads' states live across the tiles in a VMEM scratch ``[H / Hg, N, Hg * P]`` float32 (a
head's ``S^T [N, P]`` side by side in the lanes, zero at the first tile). Inside a tile, with
``cs_i = sum_{m <= i} dt_m A`` a head's cumulative log decay:

    M   = G * exp(cs_i - cs_j) * dt_j   (j <= i)      the decay matrix, ``dt`` folded in
    Y   = M x + exp(cs) (C S^T) + D x
    S^T <- exp(cs_last) S^T + B^T (exp(cs_last - cs) dt x)

Everything a tile builds stays in VMEM: x, dt, B, C are read once and y written once. For
the backward the forward also writes the states every tile starts from; the backward kernel
walks the tiles in reverse with the states' cotangent in scratch, makes a tile's decays again
and has its own closed forms for the cotangent of every operand.

Decays. ``cs_i - cs_j`` is not the difference of two float32 cumulative sums, which would
round at the tile's WHOLE decay (820 at ``dt A`` = 6.4 a token) where the segment's own sum
is small: the prefix sums are made as unevaluated float32 pairs ``hi + lo`` (a log-step scan
of error-free two-sums over ``[Hg, Q]``: a few registers), and ``(hi_i - hi_j) + (lo_i -
lo_j)`` rounds at the size of the segment it spans, as a sum over the segment alone does. A
product with the ones under the diagonal (``ops/pallas/delta_rule.py``) gives the same sums
for three ``Q x Q x Q`` MXU products a head and tile, more than all the rest of the scan.

Layout. ``dt`` arrives with tokens in the lanes, ``[B, T / Q, H, Q]``, and the heads' prefix
sums and decays are made in that layout, ``[Hg, Q]``. Their columns, ``[Q, lanes]`` with a head
a lane, come from ONE product with the identity over exact bfloat16 terms (``_Tile.columns``);
in the backward a head's sums over its own lanes are put a head a lane too, all heads' way
back to the log decays is taken together, and the result returns to rows, summed from the
tile's end, by a product with the ones on and under the diagonal (a ``[Q, 1]`` column costs
a register for every eight tokens whatever it holds: arithmetic on them a head at a time took a
fifth of the backward). A head of ``P`` = 64 fills half the lanes: the ``k`` heads that share
a register's 128 lanes go through the MXU together (``M_h`` times the ``k`` heads' x, of
which the head's own lanes are kept), at the cost the MXU's idle columns had anyway.

Precision. The state, its cotangent, ``dt``, ``A``, the prefix sums and the decays are
float32. Products take x, B, C and y's cotangent as the bfloat16 terms that sum to them
exactly (``_parts``: one term where they arrive in bfloat16, three for float32) and
accumulate in float32; the float32 factors that the plain form rounded to the operands' dtype
for a product (``M``, the state read by ``C``, the scaled x that builds the state, and their
cotangents) are rounded to it once here too: to bfloat16 in a step, not at all on float32
arrays.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_rule import _BF16, _F32, _NN, _NT, _TN, _by_head, _colsum, _mm, _parts, _rowsum, _stack


def heads_together(heads, P):
    """How many heads of width ``P`` share a register's lanes: the fewest that fill whole
    registers of 128 and divide the ``heads`` of a grid step, else one."""
    k = next((k for k in (1, 2, 4, 8, 16) if (k * P) % 128 == 0), 1)
    return k if heads % k == 0 else 1


def _prefix_sums(a):
    """The inclusive prefix sums of ``a [rows, Q]`` along the lanes as an unevaluated pair
    ``hi + lo``: a log-step scan whose every addition keeps its rounding error (two-sum)."""
    Q = a.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    hi, lo = a, jnp.zeros_like(a)
    shift = 1
    while shift < Q:
        behind = lane >= shift
        hi_b = jnp.where(behind, pltpu.roll(hi, shift, 1), 0.0)
        lo_b = jnp.where(behind, pltpu.roll(lo, shift, 1), 0.0)
        s = hi + hi_b
        v = s - hi
        lo = lo + lo_b + ((hi - (s - v)) + (hi_b - v))
        hi = s + lo
        lo = lo - (hi - s)
        shift *= 2
    return hi, lo


class _Tile:
    """The constant masks of a tile of ``Q`` tokens and the ways between the heads' rows
    ``[Hg, Q]`` (tokens in the lanes) and their columns ``[Q, lanes]`` (a head a lane)."""

    def __init__(self, Q, Hg):
        self.Q, self.Hg = Q, Hg
        self.lanes = -(-Hg // 128) * 128          # whole registers, a head a lane
        rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        self.lower = rows >= cols
        self.eye = jnp.where(rows == cols, 1.0, 0.0).astype(_BF16)
        self.ones_lower = jnp.where(self.lower, 1.0, 0.0).astype(_BF16)

    def columns(self, rows):
        """``rows``, a list of ``[Hg, Q]``, each as ``[Q, lanes]`` whose lane ``j`` is head
        ``j``'s column, exactly (one product with the identity for all of them)."""
        fill = [jnp.zeros((self.lanes - self.Hg, self.Q), _F32)] if self.lanes > self.Hg else []
        out = _mm(_NT, ((self.eye,), _parts(_stack([x for row in rows for x in [row] + fill], 0))))
        return [out[:, q * self.lanes:(q + 1) * self.lanes] for q in range(len(rows))]

    def rows_of(self, columns, summed):
        """``[Q, lanes]`` columns as rows ``[Hg, Q]``; ``summed``: each summed from the tile's
        end, row ``m`` holding ``sum_{i >= m}``."""
        return _mm(_TN, (_parts(columns), (self.ones_lower if summed else self.eye,)))[:self.Hg]

    def put(self, into, j, column):
        """``into [Q, lanes]`` with lane ``j`` set to ``column [Q, 1]``."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, self.lanes), 1)
        return jnp.where(lane == j, column, into)


def _in_rows(rows):
    """A list of ``[1, Q]`` as ``[n, Q]``, one a row, ``n`` whole bfloat16 registers of 16."""
    n = -(-len(rows) // 16) * 16
    at = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    out = jnp.zeros((n, rows[0].shape[1]), _F32)
    for j, row in enumerate(rows):
        out = jnp.where(at == j, row, out)
    return out


def _whole_rows(x):
    """``[rows, Q]`` with zero rows up to whole bfloat16 registers of 16."""
    fill = -x.shape[0] % 16
    return jnp.concatenate([x, jnp.zeros((fill, x.shape[1]), x.dtype)], axis=0) if fill else x


def _decays(dt, A, tile):
    """What a tile's heads decay by, from their steps ``dt [Hg, Q]`` and ``A [Hg, 1]``: the
    prefix sums of the log decays as pairs ``hi + lo``, ``start = exp(cs)`` (from the tile's
    start to a token), ``end = exp(cs_last - cs)`` (from a token to the tile's end; the state
    takes a token's x times ``leaf = end dt``) and ``whole = exp(cs_last) [Hg, 1]``. ``rows``
    has them as made, tokens in the lanes; ``cols`` the first five with a head a lane,
    ``[Q, lanes]``; ``col(name, j)`` is head ``j``'s column ``[Q, 1]``."""
    hi, lo = _prefix_sums(dt * A)
    last = jax.lax.broadcasted_iota(jnp.int32, hi.shape, 1) == hi.shape[1] - 1
    end_hi, end_lo = (_rowsum(jnp.where(last, x, 0.0)) for x in (hi, lo))
    end = jnp.exp((end_hi - hi) + (end_lo - lo))
    rows = dict(hi=hi, lo=lo, start=jnp.exp(hi + lo), end=end, leaf=end * dt, dt=dt,
                whole=jnp.exp(end_hi + end_lo))
    names = ("hi", "lo", "start", "end", "leaf")
    cols = dict(zip(names, tile.columns([rows[name] for name in names])))
    return dict(rows=rows, cols=cols, col=lambda name, j: cols[name][:, j:j + 1])


def _segment_sums(dec, j):
    """Head ``j``'s ``cs_i - cs_j [Q, Q]``: the pairs' high parts cancel exactly where the
    segment is short, so that an entry rounds at its own size."""
    rows = dec["rows"]
    return (dec["col"]("hi", j) - rows["hi"][j:j + 1]) + (dec["col"]("lo", j) - rows["lo"][j:j + 1])


def _within(dec, j, tile):
    """Head ``j``'s ``L_ij = exp(cs_i - cs_j)`` for ``j <= i``, zero above the diagonal, and
    its steps as a row ``[1, Q]``. Masked BEFORE the exponential."""
    return jnp.exp(jnp.where(tile.lower, _segment_sums(dec, j), -1e30)), dec["rows"]["dt"][j:j + 1]


def _own_lanes(W, P, i):
    """The lanes of the ``i``-th of the heads that share ``W`` lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    return (lane >= i * P) & (lane < (i + 1) * P)


def _whole(dec, Hg, k, P):
    """The heads' ``whole`` over their own lanes, ``[1, Hg P]``."""
    whole = dec["rows"]["whole"]
    return _stack([jnp.broadcast_to(_by_head([whole[j:j + 1] for j in range(p * k, (p + 1) * k)], P), (1, k * P))
                   for p in range(Hg // k)], 1)


def _first_of_group(g, steps):
    """Whether grid step ``g`` is the first of the ``steps`` that share a ``B`` and ``C``:
    a traced flag, or True where every step is (``steps`` 1). ``steps`` None: one ``B`` and
    ``C`` for all heads, the first step of a tile."""
    if steps is None:
        return g == 0
    return True if steps == 1 else g % steps == 0


def _later_in_group(g, steps):
    """The complement of ``_first_of_group``: a traced flag, or False where no step is."""
    if steps is None:
        return g > 0
    return False if steps == 1 else g % steps != 0


def _when(flag, fn):
    """``fn()`` where ``flag`` holds; a flag known at trace time costs no branch."""
    if flag is True:
        fn()
    elif flag is not False:
        pl.when(flag)(fn)


def _begin(state_ref, G_ref, b_ref, c_ref, steps):
    """A row's first tile starts from zero; the first step of the heads that share a ``B``
    and ``C`` makes ``C B^T``. Returns the step."""
    t, g = pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _():
        state_ref[g] = jnp.zeros(state_ref.shape[1:], _F32)

    def make():
        G_ref[...] = _mm(_NT, (_parts(c_ref[...]), _parts(b_ref[...])))

    _when(_first_of_group(g, steps), make)
    return g


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, start_ref, S_ref, G_ref, *, P, k,
                steps):
    g = _begin(S_ref, G_ref, b_ref, c_ref, steps)
    Q, Hg = x_ref.shape[0], dt_ref.shape[0]
    W, dtype = k * P, x_ref.dtype
    S = S_ref[g]
    start_ref[...] = S
    tile = _Tile(Q, Hg)
    dec = _decays(dt_ref[...], a_ref[...], tile)
    col = dec["col"]
    G = G_ref[...]
    read = _mm(_NN, (_parts(c_ref[...]), _parts(S.astype(dtype))))            # [Q, Hg P]
    leaves = []
    for p in range(Hg // k):
        lanes, heads = slice(p * W, (p + 1) * W), range(p * k, (p + 1) * k)
        xp = x_ref[:, lanes]
        y = None
        for i, j in enumerate(heads):
            L, dt_row = _within(dec, j, tile)
            mine = _mm(_NN, (_parts((G * L * dt_row).astype(dtype)), _parts(xp)))
            y = mine if y is None else jnp.where(_own_lanes(W, P, i), mine, y)
        xf = xp.astype(_F32)
        y = y + _by_head([col("start", j) for j in heads], P) * read[:, lanes] + d_ref[:, lanes] * xf
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        leaves.append((xf * _by_head([col("leaf", j) for j in heads], P)).astype(dtype))
    S_ref[g] = _whole(dec, Hg, k, P) * S + _mm(_TN, (_parts(b_ref[...]), _parts(_stack(leaves, 1))))


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, start_ref, dy_ref,
                dx_ref, da_ref, ddt_ref, db_ref, dc_ref, dd_ref, dS_ref, G_ref, *, P, k, steps):
    g = _begin(dS_ref, G_ref, b_ref, c_ref, steps)
    Q, Hg = x_ref.shape[0], dt_ref.shape[0]
    W, dtype = k * P, x_ref.dtype
    S, dS = start_ref[...], dS_ref[g]
    tile = _Tile(Q, Hg)
    dec = _decays(dt_ref[...], a_ref[...], tile)
    col, cols = dec["col"], dec["cols"]
    G = G_ref[...]
    bp, cp = _parts(b_ref[...]), _parts(c_ref[...])
    Sp, dSp = _parts(S.astype(dtype)), _parts(dS.astype(dtype))
    read = _mm(_NN, (cp, Sp))                        # C S^T       [Q, Hg P]
    built = _mm(_NN, (bp, dSp))                      # B dS^T      [Q, Hg P]
    total = _colsum(dS * S)                                                   # [1, Hg P]
    dG = jnp.zeros((Q, Q), _F32)
    into_read, leaves, d_dt_rows = [], [], []
    # a head's sums over its own lanes, a head a lane: the rest of their way is taken by all
    # heads together, after the loop
    nothing = jnp.zeros((Q, tile.lanes), _F32)
    within, d_start, d_leaf, d_whole = nothing, nothing, nothing, nothing[:1]
    for p in range(Hg // k):
        lanes, heads = slice(p * W, (p + 1) * W), range(p * k, (p + 1) * k)
        xp, dyp = x_ref[:, lanes], dy_ref[:, lanes]
        xf, dyf = xp.astype(_F32), dyp.astype(_F32)
        dx = None
        by_start, by_leaf = dyf * read[:, lanes], xf * built[:, lanes]
        for i, j in enumerate(heads):
            own = _own_lanes(W, P, i)
            L, dt_row = _within(dec, j, tile)
            GL = G * L
            # Y = M x: M's cotangent is dY x^T over the head's own lanes, x's M^T dY
            dM = _mm(_NT, (_parts(jnp.where(own, dyp, jnp.zeros_like(dyp))), _parts(xp)))
            mine = _mm(_TN, (_parts((GL * dt_row).astype(dtype)), _parts(dyp)))
            dx = mine if dx is None else jnp.where(own, mine, dx)
            dG = dG + dM * (L * dt_row)
            by_dt = dM * GL                            # M = G L dt: what dt_j is multiplied by
            d_dt_rows.append(_colsum(by_dt))
            # L_ij = exp(cs_i - cs_j): cs_i takes its row's sum (cs_j its column's, below)
            within = tile.put(within, j, _rowsum(by_dt * dt_row))
            # y += start (C S^T); S' = whole S + B^T (leaf x), whole = exp(cs_last)
            d_start = tile.put(d_start, j, _rowsum(jnp.where(own, by_start, 0.0)))
            d_leaf = tile.put(d_leaf, j, _rowsum(jnp.where(own, by_leaf, 0.0)))
            d_whole = tile.put(d_whole, j, _rowsum(jnp.where(own, total[:, lanes], 0.0)))
        start_l = _by_head([col("start", j) for j in heads], P)
        leaf_l = _by_head([col("leaf", j) for j in heads], P)
        dx = dx + leaf_l * built[:, lanes] + d_ref[:, lanes] * dyf
        dx_ref[:, lanes] = dx.astype(dx_ref.dtype)
        into_read.append((start_l * dyf).astype(dtype))
        leaves.append((xf * leaf_l).astype(dtype))
        dd_ref[:, lanes] = _colsum(xf * dyf)
    into_read, leaves = _parts(_stack(into_read, 1)), _parts(_stack(leaves, 1))
    dS_ref[g] = _whole(dec, Hg, k, P) * dS + _mm(_TN, (cp, into_read))
    # G = C B^T, the group's heads together; the state's reads and leaves
    dGp = _parts(dG.astype(dtype))
    db = _mm(_TN, (dGp, cp)) + _mm(_NT, (leaves, dSp))
    dc = _mm(_NN, (dGp, bp)) + _mm(_NT, (into_read, Sp))

    def write():
        db_ref[...] = db
        dc_ref[...] = dc

    def add():
        db_ref[...] += db
        dc_ref[...] += dc

    # the first of the steps that share a B and C starts their sum, the others add to it
    _when(_first_of_group(g, steps), write)
    _when(_later_in_group(g, steps), add)

    # every head's cs together, a head a lane: start = exp(cs), end = exp(cs_last - cs), and
    # the last token's also takes whole = exp(cs_last) = its start
    d_end = d_leaf * cols["leaf"]
    last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    at_last = _colsum(d_end) + d_whole * cols["start"][Q - 1:]
    d_cs = within + d_start * cols["start"] - d_end + jnp.where(last, at_last, 0.0)
    d_dt_rows = _in_rows(d_dt_rows)
    # a = dt A reaches cs_i for i >= m: the cotangents summed from the tile's end; the
    # columns' cs_j gave back what dt_j was multiplied by, times dt_j
    d_cs_rows = -d_dt_rows * _whole_rows(dec["rows"]["dt"])
    da_ref[...] = tile.rows_of(d_cs, True) + _mm(_NN, (_parts(d_cs_rows), (tile.ones_lower,)))[:Hg]
    ddt_ref[...] = tile.rows_of(d_leaf * cols["end"], False) + d_dt_rows[:Hg]


def _specs(T, H, P, N, Q, Hg, reverse, steps):
    """Block specs of the operands by kind, for a grid ``(B, T / Q, H / Hg)``; ``steps`` grid
    steps share a block of ``b`` and ``c`` (None: all of them, one block)."""
    tiles = T // Q
    at = (lambda t: tiles - 1 - t) if reverse else (lambda t: t)
    group = (lambda g: 0) if steps is None else (lambda g: g // steps)
    return dict(
        x=pl.BlockSpec((None, Q, Hg * P), lambda b, t, g: (b, at(t), g)),
        bc=pl.BlockSpec((None, Q, N), lambda b, t, g: (b, at(t), group(g))),
        dt=pl.BlockSpec((None, None, Hg, Q), lambda b, t, g: (b, at(t), g, 0)),
        a=pl.BlockSpec((Hg, 1), lambda b, t, g: (g, 0)),
        d=pl.BlockSpec((1, Hg * P), lambda b, t, g: (0, g)),
        start=pl.BlockSpec((None, None, None, N, Hg * P), lambda b, t, g: (b, at(t), g, 0, 0)),
        dd=pl.BlockSpec((None, None, 1, Hg * P), lambda b, t, g: (b, at(t), 0, g)),
    )


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                vmem_limit_bytes=64 * 2 ** 20)


def _sizes(x, dt, b, heads, groups, interpret):
    """The sizes, and the grid steps that share a ``b``/``c`` block (None with one group)."""
    B, T = x.shape[:2]
    H, Q = dt.shape[2:]
    P, N = x.shape[2] // H, b.shape[2] // groups
    k = heads_together(heads, P)
    assert H % groups == 0 and (groups == 1 or (H // groups) % heads == 0), \
        f"a grid step's {heads} heads are one of the {groups} groups' {H // groups} or a divisor"
    assert interpret or ((k * P) % 128 == 0 and N % 128 == 0 and Q % 64 == 0
                         and (heads % 8 == 0 or heads == H)), \
        f"the scan's kernels take heads that fill whole registers of 128 lanes, a state of " \
        f"128s and a tile of 64s, not {heads} heads of {P}, a state of {N}, a tile of {Q}"
    assert H % heads == 0 and T % Q == 0
    return B, T, H, P, N, Q, k, (None if groups == 1 else H // groups // heads)


# jitted and inlined: the jaxpr of a kernel's body (sixty-four heads unrolled) is made once
# for a shape, not once for every call of a program (twenty-seven in a step of nine layers
# with their blocks recomputed), and each call still carries the scopes it was made under
_inlined = functools.partial(jax.jit, static_argnames=("heads", "groups", "interpret"), inline=True)


@_inlined
def ssd_scan_fwd(x, dt, A, b, c, D, heads, interpret, groups=1):
    """``x [B, T, H * P]``, ``dt`` float32 ``[B, T / Q, H, Q]`` (a tile's tokens in the lanes),
    ``A`` float32 ``[H, 1]``, ``b``, ``c`` ``[B, T, groups * N]`` (head ``h`` reads group
    ``h // (H / groups)``), ``D`` float32 ``[1, H * P]`` (a head's over its lanes), ``heads`` a
    grid step: ``(y`` as ``x``, the float32 states every tile starts from
    ``[B, T / Q, H / heads, N, heads * P])``."""
    B, T, H, P, N, Q, k, steps = _sizes(x, dt, b, heads, groups, interpret)
    spec = _specs(T, H, P, N, Q, heads, False, steps)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, k=k, steps=steps),
        grid=(B, T // Q, H // heads),
        in_specs=[spec["x"], spec["dt"], spec["a"], spec["bc"], spec["bc"], spec["d"]],
        out_specs=[spec["x"], spec["start"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, T // Q, H // heads, N, heads * P), _F32)],
        scratch_shapes=[pltpu.VMEM((H // heads, N, heads * P), _F32), pltpu.VMEM((Q, Q), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="ds_ssd_scan_fwd",
    )
    with jax.named_scope("ds_ssd_scan_fwd"):
        return call(x, dt, A, b, c, D)


@_inlined
def ssd_scan_bwd(x, dt, A, b, c, D, start, dy, heads, interpret, groups=1):
    """From y's cotangent and the states the forward kept: the cotangents of ``x`` (as
    ``x``), of the log decays ``dt A`` and of ``dt`` where it is a factor (each as ``dt``),
    of ``b`` and ``c`` (float32, a group's heads' summed), and ``sum_t x dy`` a tile
    ``[B, T / Q, 1, H * P]`` (``D``'s, to be summed)."""
    B, T, H, P, N, Q, k, steps = _sizes(x, dt, b, heads, groups, interpret)
    spec = _specs(T, H, P, N, Q, heads, True, steps)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, k=k, steps=steps),
        grid=(B, T // Q, H // heads),
        in_specs=[spec["x"], spec["dt"], spec["a"], spec["bc"], spec["bc"], spec["d"],
                  spec["start"], spec["x"]],
        out_specs=[spec["x"], spec["dt"], spec["dt"], spec["bc"], spec["bc"], spec["dd"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32), jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(b.shape, _F32), jax.ShapeDtypeStruct(c.shape, _F32),
                   jax.ShapeDtypeStruct((B, T // Q, 1, H * P), _F32)],
        scratch_shapes=[pltpu.VMEM((H // heads, N, heads * P), _F32), pltpu.VMEM((Q, Q), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="ds_ssd_scan_bwd",
    )
    with jax.named_scope("ds_ssd_scan_bwd"):
        return call(x, dt, A, b, c, D, start, dy)
