"""Slope-based kernel timing: one kernel's time, without the host in it.

A host loop around a ms-scale kernel times the dispatch as much as the kernel.
This harness iterates the kernel ON DEVICE inside one jit (serial dependency
defeats CSE/overlap) at two iteration counts and reports the SLOPE, in which the
constant costs of a call (dispatch, the wait for the result) cancel:

    t = (T(n2) - T(n1)) / (n2 - n1)

A negative result means run-to-run variance still exceeds the compute delta: raise
n1/n2. ``timeit_slope`` returns the best (min) slope; ``timeit_slope_stats``
returns a median with its spread, escalating the on-device iteration counts until
the spread pins below a target. A kernel's time in the served or trained program
comes from a profiler trace (utils/profile_ingest.py), not from here.
"""

import time

import jax
import jax.numpy as jnp


def _make_loop(fn, inner):
    @jax.jit
    def many(*a):
        def body(_, s):
            # Serial dependency XLA cannot fold away: the carry enters the
            # kernel input scaled by a nonzero constant (a literal ``* 0``
            # would constant-fold, making the body loop-invariant and
            # hoistable, flattening the slope). The dtype's smallest NORMAL
            # value is nonzero in every float dtype (a fixed 1e-30 would
            # itself round to literal 0.0 in fp16 and restore the fold) and
            # perturbs inputs by less than one ulp.
            tiny = jnp.asarray(jnp.finfo(a[0].dtype).tiny, a[0].dtype)
            out = fn(a[0] + s.astype(a[0].dtype) * tiny, *a[1:])
            return jnp.sum(out.astype(jnp.float32)) * 1e-30
        return jax.lax.fori_loop(0, inner, body, jnp.zeros((), jnp.float32))
    return many


def _slopes(fn, args, n1, n2, reps):
    """Per-rep slope estimates (seconds/call) at the given iteration counts."""
    f1, f2 = _make_loop(fn, n1), _make_loop(fn, n2)
    for f in (f1, f2):
        jax.block_until_ready(f(*args))  # compiles
    out = []
    for _ in range(reps):
        t0 = time.time()
        jax.block_until_ready(f1(*args))
        ta = time.time() - t0
        t0 = time.time()
        jax.block_until_ready(f2(*args))
        tb = time.time() - t0
        out.append((tb - ta) / (n2 - n1))
    return out


def timeit_slope(fn, *args, n1=10, n2=50, reps=3):
    """Per-call seconds of ``fn(*args)`` (first arg must be a float array)."""
    return min(_slopes(fn, args, n1, n2, reps))


def timeit_slope_stats(fn, *args, n1=10, n2=50, reps=5, target_spread=0.10,
                       max_scale=8):
    """Reproducible per-call seconds: (median, spread, n_scale).

    Runs ``reps`` slope estimates and, while their spread ((max-min)/median)
    exceeds ``target_spread`` or the median is non-positive, DOUBLES the on-device
    iteration counts (more compute per call, so the constant costs weigh less).
    Each escalation costs two fresh jit compiles; ``max_scale`` bounds it.
    """
    scale = 1
    while True:
        s = sorted(_slopes(fn, args, n1 * scale, n2 * scale, reps))
        med = s[len(s) // 2]
        spread = (s[-1] - s[0]) / med if med > 0 else float("inf")
        if (med > 0 and spread <= target_spread) or scale >= max_scale:
            return med, spread, scale
        scale *= 2
