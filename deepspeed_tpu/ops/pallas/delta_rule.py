"""Pallas TPU kernels of the chunked gated delta rule (``ops/delta_rule.py`` has the
recurrence and the entry point), forward and backward.

A grid step is one batch row, one key head and one block of ``chunks`` chunks of ``CHUNK``
tokens; the blocks of a row are the sequential axis, and the states ``S [Dk, Dv]`` of the key
head's ``r`` value heads live side by side in a VMEM scratch ``[Dk, r * Dv]`` across them
(zero at the first). Inside a chunk, with ``gc`` the cumulative log decay, ``Kn``/``Qn`` the
unit-length keys and scaled queries and ``D_ij = exp(gc_i - gc_j)`` for ``j <= i``:

    A = tril(beta_i (Kn Kn^T)_ij D_ij, -1)          X = inv(I + A)
    New = X (beta (V - exp(gc) Kn S))               every token's update
    O   = exp(gc) Qn S + (Qn Kn^T * D) New
    S  <- exp(gc_last) S + (exp(gc_last - gc) Kn)^T New

Everything a chunk builds stays in VMEM: q, k, v, g and beta are read once and o written
once. For the backward the forward also writes the states at the start of every block, every
chunk's ``X`` and ``New``; the backward kernel walks the blocks in reverse with the states'
cotangent in scratch, makes the block's states again from the kept ones, and has its own
closed forms (the system's is ``dA = -(X^T dNew) New^T``: no derivative of the inverse).

Layout. A ``[C, C]`` matrix of a value head (D, A, X, ...) never stands alone: ``G`` heads'
stand side by side in the lanes, ``[C, G * C]`` (``G * C`` = 128 where ``r`` is even), so
that the vector unit works on full registers, and a product with such a matrix on the right
takes it block-diagonal, ``[G * C, G * C]`` (``_Heads.diag``): one full MXU tile serves
``G`` heads. Gates arrive as ``[groups, G * C]`` rows a chunk for the same reason.

Precision. The state, the decays, ``X`` and ``New`` are float32, and no product rounds one of
them: an operand goes to the MXU as bfloat16 terms that sum to it exactly (``_parts``), a
bfloat16 array as itself, a float32 one as three, and ``_mm`` is ONE product over the terms
laid end to end along the contraction (every pair that float32 can still see), so the MXU
accumulates them. q, k, v and o's cotangent thus cost one term where they arrive in bfloat16
(their L2 scale is a float32 factor a row, applied outside the product), and a float32 array
that holds bfloat16 values goes through the same kernel with two further terms that are
exactly zero.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
L2_EPS = 1e-6
_LOG_CHUNK = CHUNK.bit_length() - 1
_F32, _BF16 = jnp.float32, jnp.bfloat16
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))      # contracting dimensions


def heads_together(r):
    """How many of a key head's ``r`` value heads share the lanes: two fill 128."""
    return 2 if r % 2 == 0 else 1


def _parts(x):
    """``x`` as bfloat16 terms that sum to it exactly: itself where it is bfloat16, else its
    top eight mantissa bits, the next eight and the last eight."""
    if x.dtype == _BF16:
        return (x,)
    x = x.astype(_F32)
    parts = []
    for _ in range(3):
        top = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536), _F32)
        parts.append(top.astype(_BF16))
        x = x - top
    return tuple(parts)


def _mm(dims, *products):
    """The float32 sum of the products ``a . b`` of arrays given as ``_parts``, as ONE MXU
    product: of every product, every pair of terms that float32 still sees (``i + j < 3``),
    laid end to end along the contraction, so that the MXU accumulates them (a left-hand
    side's terms one under the other against each term of the right, the results' row
    blocks added by the vector unit, made the forward kernel a fifth slower: PERF.md,
    PR 32). Where a term's length along the contraction is not whole registers (toy widths,
    interpreted) the pairs go one by one."""
    (ca,), (cb,) = dims
    lhs, rhs = [], []
    for a, b in products:
        for i in range(len(a)):
            for j in range(len(b)):
                if i + j < 3:
                    lhs.append(a[i])
                    rhs.append(b[j])
    dot = functools.partial(jax.lax.dot_general, dimension_numbers=(dims, ((), ())),
                            preferred_element_type=_F32)
    whole = all(x.shape[c] % (128 if c == 1 else 16) == 0
                for x, c in zip(lhs + rhs, [ca] * len(lhs) + [cb] * len(rhs)))
    if len(lhs) > 1 and whole:
        return dot(jnp.concatenate(lhs, axis=ca), jnp.concatenate(rhs, axis=cb))
    out = dot(lhs[-1], rhs[-1])
    for x, y in zip(lhs[-2::-1], rhs[-2::-1]):           # the smallest first
        out = out + dot(x, y)
    return out


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _colsum(x):
    return jnp.sum(x, axis=0, keepdims=True)


def _stack(xs, axis):
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=axis)


class _Heads:
    """``G`` value heads' ``[C, C]`` matrices side by side, ``[C, G * C]``: the constant masks,
    and the ways between a head's column ``[C, 1]``, such a matrix and its block-diagonal
    form."""

    def __init__(self, G):
        C, self.G = CHUNK, G
        self.rows = jax.lax.broadcasted_iota(jnp.int32, (C, G * C), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (C, G * C), 1)
        self.cols, self.head = lane & (C - 1), lane >> _LOG_CHUNK
        self.eye = self.rows == self.cols
        self.lower = self.rows >= self.cols
        self.strict = self.rows > self.cols

    def col(self, x, g):
        """Head ``g``'s row sums ``[C, 1]``."""
        return _rowsum(x if self.G == 1 else jnp.where(self.head == g, x, 0.0))

    def cols_of(self, x):
        return [self.col(x, g) for g in range(self.G)]

    def spread(self, cols):
        """Each head's column ``[C, 1]`` (or scalar ``[1, 1]``) over its head's lanes."""
        out = cols[0]
        for g in range(1, self.G):
            out = jnp.where(self.head[:cols[g].shape[0]] == g, cols[g], out)
        return out

    def diag(self, p):
        """``[C, G * C]`` as ``[G * C, G * C]``: head ``g``'s matrix in the ``g``-th diagonal
        block, zeros elsewhere."""
        return _stack([p if self.G == 1 else jnp.where(self.head == g, p, jnp.zeros_like(p))
                       for g in range(self.G)], 0)

    def diag_parts(self, x):
        """The exact bfloat16 terms of ``x [C, G * C]``, each block-diagonal."""
        return tuple(self.diag(p) for p in _parts(x))

    def under(self, level):
        """What lies under the diagonal blocks of size ``2 ** level`` inside those of twice
        the size."""
        r, c = self.rows >> level, self.cols >> level
        return ((r >> 1) == (c >> 1)) & ((r & 1) == 1) & ((c & 1) == 0)

    def inverse_unit_lower(self, A):
        """``inv(I + A)`` a head, ``A`` strictly lower triangular: block forward substitution,
        the blocks doubling. With ``X`` the inverse of the diagonal blocks of one size and
        ``L`` what lies under them inside the blocks of twice the size, ``inv([[P, 0], [L, Q]])
        = [[inv P, 0], [-inv Q . L . inv P, inv Q]]`` is ``X - X L X`` (the first doubling is
        ``I - L``). No power series that a run of equal keys could blow up."""
        X = jnp.where(self.eye, 1.0, 0.0) - jnp.where(self.under(0), A, 0.0)
        Ap = _parts(A)
        for level in range(1, _LOG_CHUNK):
            Xp = _parts(X)
            low = tuple(self.diag(jnp.where(self.under(level), p, jnp.zeros_like(p))) for p in Ap)
            XL = _mm(_NN, (Xp, low))
            X = X - _mm(_NN, (_parts(XL), tuple(self.diag(p) for p in Xp)))
        return X

    def gates(self, g_row, beta_row):
        """The heads' decays in a chunk from their log decays and steps, each ``[1, G * C]``."""
        low = jnp.where(self.lower, g_row, 0.0)
        gc = self.cols_of(low)                                            # sum over m <= i
        after = self.cols_of(jnp.where(self.lower, 0.0, g_row))           # sum over m > i
        # D_ij = exp(gc_i - gc_j) for j <= i, from the sum over j < m <= i itself (a product
        # with the ones under the diagonal): the difference of two cumulative sums would carry
        # the rounding of a whole chunk's decay into neighbours'. Masked BEFORE the exponential.
        ones = self.diag(jnp.where(self.strict, 1.0, 0.0).astype(_BF16))
        D = jnp.exp(jnp.where(self.lower, _mm(_NN, (_parts(low), (ones,))), -1e30))
        return dict(beta=self.cols_of(jnp.where(self.eye, beta_row, 0.0)),
                    a=[jnp.exp(x) for x in gc], b=[jnp.exp(x) for x in after],
                    tau=[jnp.exp(x[CHUNK - 1:, :]) for x in gc], D=D)


def _keys(qt, kt, hd):
    """What a chunk's value heads share: the terms of q and k as they arrive, their L2 scales
    (q's with ``Dk^-1/2``), and ``P = Kn Kn^T`` and ``M = Qn Kn^T``, each ``G`` times side by
    side."""
    qp, kp = _parts(qt), _parts(kt)
    both = tuple(jnp.concatenate([k, q], axis=0) for k, q in zip(kp, qp))
    kkqk = _mm(_NT, (both, tuple(_stack([k] * hd.G, 0) for k in kp)))     # [2 C, G C]
    kk, qk = kkqk[:CHUNK], kkqk[CHUNK:]
    squares = jnp.where(hd.eye, kk, 0.0)
    ks_col = jax.lax.rsqrt(hd.col(squares, 0) + L2_EPS)
    ks_row = jax.lax.rsqrt(_colsum(squares) + L2_EPS)
    qf = qt.astype(_F32)
    qs_col = jax.lax.rsqrt(_rowsum(qf * qf) + L2_EPS) * qt.shape[1] ** -0.5
    return dict(qp=qp, kp=kp, both=both, ks_col=ks_col, ks_row=ks_row, qs_col=qs_col,
                P=kk * ks_col * ks_row, M=qk * qs_col * ks_row)


def _head_lanes(group, G, Dv):
    """``(g, the lanes of its [.., r * Dv] slab)`` for each of a group's ``G`` value heads."""
    return [(g, slice((group * G + g) * Dv, (group * G + g + 1) * Dv)) for g in range(G)]


def _by_head(scalars, Dv):
    """``[1, r * Dv]``: head ``j``'s ``[1, 1]`` over its ``Dv`` lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, len(scalars) * Dv), 1)
    out = scalars[0]
    for j in range(1, len(scalars)):
        out = jnp.where(lane >= j * Dv, scalars[j], out)
    return out


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, start_ref, x_ref, new_ref, S_ref,
                *, chunks, r, Dv):
    @pl.when(pl.program_id(2) == 0)
    def _():
        S_ref[...] = jnp.zeros_like(S_ref)

    start_ref[...] = S_ref[...]
    G = heads_together(r)

    def chunk(c, carry):
        hd = _Heads(G)
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        kq = _keys(q_ref[rows, :], k_ref[rows, :], hd)
        S = S_ref[...]
        KQS = _mm(_NN, (kq["both"], _parts(S)))                           # [2 C, r Dv]
        leaves, taus = [], []
        for group in range(r // G):
            gt = hd.gates(g_ref[c, group:group + 1], beta_ref[c, group:group + 1])
            X = hd.inverse_unit_lower(jnp.where(hd.strict, hd.spread(gt["beta"]) * kq["P"] * gt["D"], 0.0))
            x_ref[c, group] = X
            heads = _head_lanes(group, G, Dv)
            R = _stack([gt["beta"][g] * (v_ref[rows, lanes].astype(_F32)
                                         - kq["ks_col"] * gt["a"][g] * KQS[:CHUNK, lanes])
                        for g, lanes in heads], 0)
            new = _mm(_NN, (hd.diag_parts(X), _parts(R)))           # [G C, Dv]
            local = _mm(_NN, (hd.diag_parts(kq["M"] * gt["D"]), _parts(new)))
            for g, lanes in heads:
                mine = slice(g * CHUNK, (g + 1) * CHUNK)
                o = kq["qs_col"] * gt["a"][g] * KQS[CHUNK:, lanes] + local[mine]
                o_ref[rows, lanes] = o.astype(o_ref.dtype)
                new_ref[rows, lanes] = new[mine]
                leaves.append(kq["ks_col"] * gt["b"][g] * new[mine])
            taus += gt["tau"]
        S_ref[...] = _by_head(taus, Dv) * S + _mm(_TN, (kq["kp"], _parts(_stack(leaves, 1))))
        return carry

    jax.lax.fori_loop(0, chunks, chunk, None)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, x_ref, new_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dS_ref, states_ref,
                *, chunks, r, Dv):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dS_ref[...] = jnp.zeros_like(dS_ref)

    Dk = q_ref.shape[1]
    G = heads_together(r)

    # the states every chunk of the block starts from, out of the block's own
    states_ref[0] = start_ref[...]

    def state(c, carry):
        hd = _Heads(G)
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        kt = k_ref[rows, :]
        kf = kt.astype(_F32)
        ks_col = jax.lax.rsqrt(_rowsum(kf * kf) + L2_EPS)
        leaves, taus = [], []
        for group in range(r // G):
            g_row = g_ref[c, group:group + 1]
            after = hd.cols_of(jnp.where(hd.lower, 0.0, g_row))
            for g, lanes in _head_lanes(group, G, Dv):
                leaves.append(ks_col * jnp.exp(after[g]) * new_ref[rows, lanes])
                taus.append(jnp.exp(_rowsum(jnp.where(hd.head[:1] == g, g_row, 0.0))))
        states_ref[c + 1] = _by_head(taus, Dv) * states_ref[c] + _mm(
            _TN, (_parts(kt), _parts(_stack(leaves, 1))))
        return carry

    jax.lax.fori_loop(0, chunks - 1, state, None)

    def chunk(i, carry):
        hd = _Heads(G)
        c = chunks - 1 - i
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        qt, kt = q_ref[rows, :], k_ref[rows, :]
        kq = _keys(qt, kt, hd)
        qp, kp, ks_col, qs_col = kq["qp"], kq["kp"], kq["ks_col"], kq["qs_col"]
        kf = kt.astype(_F32)
        S, dS = states_ref[c], dS_ref[...]
        KQS = _mm(_NN, (kq["both"], _parts(S)))                           # [2 C, r Dv]
        KdS = _mm(_NN, (kp, _parts(dS)))                                  # [C, r Dv]
        dQn = jnp.zeros((CHUNK, Dk), _F32)
        dKn = jnp.zeros((CHUNK, Dk), _F32)
        dP = jnp.zeros((CHUNK, G * CHUNK), _F32)
        dM = jnp.zeros((CHUNK, G * CHUNK), _F32)
        into_q, into_k, taus = [], [], []
        for group in range(r // G):
            gt = hd.gates(g_ref[c, group:group + 1], beta_ref[c, group:group + 1])
            D, X = gt["D"], x_ref[c, group]
            heads = _head_lanes(group, G, Dv)
            new = [new_ref[rows, lanes] for _, lanes in heads]
            do = [do_ref[rows, lanes] for _, lanes in heads]
            dof = [x.astype(_F32) for x in do]
            newp = _parts(_stack(new, 0))                                 # [G C, Dv]
            local, PD = kq["M"] * D, kq["P"] * D
            # head g's New in the g-th diagonal block of [G C, G Dv]: a product with it on the
            # right, transposed, gives the heads' [C, C] results side by side
            new_diag = tuple(_stack([_stack([p[g * CHUNK:(g + 1) * CHUNK] if h == g else
                                             jnp.zeros((CHUNK, Dv), _BF16) for h in range(G)], 1)
                                     for g in range(G)], 0) for p in newp)
            # O = qs a (Q S) + local New; New also leaves into S' = tau S + (b Kn)^T New
            d_local = jnp.where(hd.lower, _mm(_NT, (_parts(_stack(do, 1)), new_diag)), 0.0)
            d_new = _mm(_TN, (hd.diag_parts(local), _parts(_stack(do, 0))))
            d_new = d_new + _stack([ks_col * gt["b"][g] * KdS[:, lanes] for g, lanes in heads], 0)
            # New = X R: the system's cotangent is -(X^T dNew) New^T
            d_r = _mm(_TN, (hd.diag_parts(X), _parts(d_new)))        # [G C, Dv]
            d_rs = [d_r[g * CHUNK:(g + 1) * CHUNK] for g in range(G)]
            d_A = -jnp.where(hd.strict, _mm(_NT, (_parts(_stack(d_rs, 1)), new_diag)), 0.0)
            d_a, d_b, d_beta, d_tau = [], [], [], []
            for g, lanes in heads:
                KS, QS = KQS[:CHUNK, lanes], KQS[CHUNK:, lanes]
                Sg, dSg = S[:, lanes], dS[:, lanes]
                # R = beta (V - a (Kn S))
                z = v_ref[rows, lanes].astype(_F32) - ks_col * gt["a"][g] * KS
                d_z = gt["beta"][g] * d_rs[g]
                dv_ref[rows, lanes] = d_z.astype(dv_ref.dtype)
                d_kns = -gt["a"][g] * d_z
                d_kb = _mm(_NT, (_parts(new[g]), _parts(dSg)))
                dQn = dQn + gt["a"][g] * _mm(_NT, (_parts(do[g]), _parts(Sg)))
                dKn = dKn + gt["b"][g] * d_kb + _mm(_NT, (_parts(d_kns), _parts(Sg)))
                d_a.append((qs_col * _rowsum(dof[g] * QS) - ks_col * _rowsum(d_z * KS)) * gt["a"][g])
                d_b.append(ks_col * _rowsum(d_kb * kf) * gt["b"][g])
                d_beta.append(_rowsum(d_rs[g] * z))
                # the total's: S' = tau S, tau = exp(gc_last)
                d_tau.append(_colsum(_rowsum(dSg * Sg)) * gt["tau"][g])
                into_q.append(qs_col * gt["a"][g] * dof[g])
                into_k.append(ks_col * d_kns)
            taus += gt["tau"]
            # A = beta P D under the diagonal, local = M D on and under it, D = exp(gc_i - gc_j)
            beta_w = hd.spread(gt["beta"])
            E = d_A * beta_w * PD + d_local * local
            dP = dP + d_A * beta_w * D
            dM = dM + d_local * D
            d_beta_w = hd.spread(d_beta) + hd.spread(hd.cols_of(d_A * PD))
            d_gc = (hd.spread(d_a) + hd.spread(hd.cols_of(E))
                    - hd.spread(hd.cols_of(jnp.where(hd.eye, _colsum(E), 0.0))))
            # g reaches gc_i for i >= m, what follows token i for i < m, and the chunk's total
            dg_ref[c, group:group + 1] = (_colsum(jnp.where(hd.lower, d_gc, hd.spread(d_b)))
                                          + hd.spread(d_tau))
            dbeta_ref[c, group:group + 1] = _colsum(jnp.where(hd.eye, d_beta_w, 0.0))
        dS_ref[...] = _by_head(taus, Dv) * dS + _mm(
            _TN, (qp, _parts(_stack(into_q, 1))), (kp, _parts(_stack(into_k, 1))))
        # P = Kn Kn^T and M = Qn Kn^T, the value heads' cotangents together: side by side on
        # the left of k stacked G times they add up over the heads
        onto = _mm(_NN, (_parts(jnp.concatenate([kq["ks_row"] * dP, kq["ks_row"] * dM], axis=0)),
                         tuple(_stack([k] * G, 0) for k in kp)))
        back = _mm(_TN, (_parts(ks_col * dP), kp), (_parts(qs_col * dM), qp))          # [G C, Dk]
        dKn = dKn + onto[:CHUNK] + sum(back[g * CHUNK:(g + 1) * CHUNK] for g in range(G))
        dQn = dQn + onto[CHUNK:]
        # through the L2 scales: x_n = s x, s = c (sum x^2 + eps)^-1/2
        Kn, Qn = ks_col * kf, qs_col * qt.astype(_F32)
        dk_ref[rows, :] = (ks_col * (dKn - Kn * _rowsum(dKn * Kn))).astype(dk_ref.dtype)
        dq_ref[rows, :] = (qs_col * (dQn - Qn * (Dk * _rowsum(dQn * Qn)))).astype(dq_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, None)


def _specs(T, r, Dk, Dv, chunks, reverse):
    """Block specs of the operands by kind, and the blocks a row has, for a grid
    ``(B, Hk, blocks)``."""
    blocks = T // (chunks * CHUNK)
    G = heads_together(r)
    at = (lambda t: blocks - 1 - t) if reverse else (lambda t: t)
    return dict(
        qk=pl.BlockSpec((None, chunks * CHUNK, Dk), lambda b, h, t: (b, at(t), h)),
        v=pl.BlockSpec((None, chunks * CHUNK, r * Dv), lambda b, h, t: (b, at(t), h)),
        gate=pl.BlockSpec((None, None, chunks, r // G, G * CHUNK), lambda b, h, t: (b, h, at(t), 0, 0)),
        start=pl.BlockSpec((None, None, None, Dk, r * Dv), lambda b, h, t: (b, h, at(t), 0, 0)),
        x=pl.BlockSpec((None, None, chunks, r // G, CHUNK, G * CHUNK),
                       lambda b, h, t: (b, h, at(t), 0, 0, 0)),
    ), blocks


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                vmem_limit_bytes=64 * 2 ** 20)


def _sizes(q, v, g, interpret):
    B, T = q.shape[:2]
    Hk, groups = g.shape[1], g.shape[3]
    r = groups * g.shape[4] // CHUNK
    Dk, Dv = q.shape[2] // Hk, v.shape[2] // (Hk * r)
    assert interpret or (Dk % 128 == 0 and Dv % 128 == 0), \
        f"the delta rule's kernels take head widths that are multiples of 128 lanes, not {Dk}, {Dv}"
    return B, T, Hk, r, Dk, Dv


def delta_rule_fwd(q, k, v, g, beta, chunks, interpret):
    """``q``, ``k`` ``[B, T, Hk * Dk]``, ``v`` ``[B, T, Hv * Dv]``, ``g``, ``beta`` float32
    ``[B, Hk, T / CHUNK, r / G, G * CHUNK]`` (``G = heads_together(r)`` value heads' chunk
    side by side), ``T`` whole blocks of ``chunks`` chunks: ``(o`` as ``v``, the states each
    block starts from ``[B, Hk, blocks, Dk, r * Dv]``, every chunk's inverses
    ``[B, Hk, T / CHUNK, r / G, CHUNK, G * CHUNK]``, its updates ``[B, T, Hv * Dv])``, the
    last three float32, for the backward."""
    B, T, Hk, r, Dk, Dv = _sizes(q, v, g, interpret)
    G = heads_together(r)
    spec, blocks = _specs(T, r, Dk, Dv, chunks, False)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, chunks=chunks, r=r, Dv=Dv),
        grid=(B, Hk, blocks),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["gate"], spec["gate"]],
        out_specs=[spec["v"], spec["start"], spec["x"], spec["v"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, Hk, blocks, Dk, r * Dv), _F32),
                   jax.ShapeDtypeStruct((B, Hk, T // CHUNK, r // G, CHUNK, G * CHUNK), _F32),
                   jax.ShapeDtypeStruct(v.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((Dk, r * Dv), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="ds_delta_rule_fwd",
    )
    with jax.named_scope("ds_delta_rule_fwd"):
        return call(q, k, v, g, beta)


def delta_rule_bwd(q, k, v, g, beta, start, x, new, do, chunks, interpret):
    """The cotangents of ``delta_rule_fwd``'s first five operands, each in its operand's
    shape and dtype, from o's and what the forward kept."""
    B, T, Hk, r, Dk, Dv = _sizes(q, v, g, interpret)
    spec, blocks = _specs(T, r, Dk, Dv, chunks, True)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, chunks=chunks, r=r, Dv=Dv),
        grid=(B, Hk, blocks),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["gate"], spec["gate"],
                  spec["start"], spec["x"], spec["v"], spec["v"]],
        out_specs=[spec["qk"], spec["qk"], spec["v"], spec["gate"], spec["gate"]],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v, g, beta)],
        scratch_shapes=[pltpu.VMEM((Dk, r * Dv), _F32), pltpu.VMEM((chunks, Dk, r * Dv), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="ds_delta_rule_bwd",
    )
    with jax.named_scope("ds_delta_rule_bwd"):
        return call(q, k, v, g, beta, start, x, new, do)
