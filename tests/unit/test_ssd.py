"""The chunked state-space (SSD) scan of ``deepspeed_tpu/ops/ssd.py`` against the
token-at-a-time recurrence of the plain reference, outputs and the gradient of every operand,
and the one ``causal_conv`` both mixers call, with and without its bias."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite_hybrid_reference as ref
from deepspeed_tpu.ops.delta_rule import causal_conv
from deepspeed_tpu.ops.pallas import ssd as kernels
from deepspeed_tpu.ops.ssd import CHUNK, ssd_scan

NAMES = ("x", "dt", "A", "B", "C", "D")


def operands(T, seed=0, Bt=2, H=4, P=8, N=16, dtype=jnp.float32):
    """Heads from slow (A dt near 1e-3) to fast (A dt up to 6.4), as the family initialises."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    step = jnp.exp(jax.random.uniform(ks[1], (Bt, T, H), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    step = step.at[..., 0].set(1e-3).at[..., -1].set(0.1)
    A = -jnp.asarray([1.0, 2.0, 30.0, 64.0])[:H]
    return (jax.random.normal(ks[0], (Bt, T, H, P)).astype(dtype), step, A,
            jax.random.normal(ks[2], (Bt, T, N)).astype(dtype),
            jax.random.normal(ks[3], (Bt, T, N)).astype(dtype),
            1.0 + 0.1 * jax.random.normal(ks[4], (H,)))


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("T, chunk", [(5, 8), (8, 8), (9, 8), (24, 8), (37, 8), (37, 16), (64, 64), (100, 32)],
                         ids=lambda v: str(v))
def test_the_chunked_scan_is_the_recurrence(T, chunk, highest):
    """Below, at and across chunk boundaries, and a length that is no multiple of the chunk."""
    args = operands(T)
    want = jax.jit(ref.ssm_recurrent)(*args)
    got = jax.jit(lambda *a: ssd_scan(*a, chunk))(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-6 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("T, chunk", [(8, 8), (21, 8), (40, 16)], ids=lambda v: str(v))
def test_the_gradient_of_every_operand_is_the_recurrences(T, chunk, highest):
    args = operands(T, seed=1)
    cot = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    grad = lambda fn: jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * cot), argnums=tuple(range(6))))   # noqa: E731
    got, want = grad(lambda *a: ssd_scan(*a, chunk))(*args), grad(ref.ssm_recurrent)(*args)
    for name, g, w in zip(NAMES, got, want):
        assert np.linalg.norm(g - w) <= 1e-5 * np.linalg.norm(w), name


@pytest.mark.parametrize("head, rate", [(0, "slow"), (3, "fast")], ids=lambda v: str(v))
def test_a_slow_and_a_fast_head_alone(head, rate, highest):
    """A dt = 1e-3 a token keeps a thousand tokens (the state crosses every chunk boundary
    nearly whole); A dt = 6.4 forgets within a token (the decay matrix is all but diagonal)."""
    x, dt, A, B, C, D = operands(200, seed=2)
    one = (x[:, :, head:head + 1], dt[:, :, head:head + 1], A[head:head + 1], B, C, D[head:head + 1])
    want = jax.jit(ref.ssm_recurrent)(*one)
    np.testing.assert_allclose(jax.jit(lambda *a: ssd_scan(*a, 32))(*one), want, atol=2e-6 * float(jnp.abs(want).max()))
    through = jnp.abs(want - D[head] * one[0]).max()       # what the state adds to the skip
    assert through > 1e-3


def segment_sums(a):
    """What the kernels make a tile's decays from: one head's ``sum_{j < m <= i} a_m [Q, Q]``
    out of the prefix sums' pairs, run as the kernels run it."""
    from jax.experimental import pallas as pl
    Q = a.shape[0]

    def kernel(a_ref, out_ref):
        tile = kernels._Tile(Q, 1)
        dec = kernels._decays(a_ref[...], jnp.ones((1, 1), jnp.float32), tile)
        out_ref[...] = kernels._segment_sums(dec, 0)

    return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct((Q, Q), jnp.float32), interpret=True)(a[None])


def test_segment_sums_start_under_the_diagonal():
    a = -jnp.asarray([0.5, 1.0, 2.0, 4.0])
    s = segment_sums(a)
    assert np.array_equal(np.diag(s), np.zeros(4))
    assert float(s[3, 0]) == -7.0 and float(s[2, 1]) == -2.0 and float(s[1, 0]) == -1.0
    # a long fast tile: a difference of two float32 cumulative sums would round at the tile's
    # whole decay (1,600); the pairs' difference is exact to the segment's own size
    a = jnp.full((256,), -6.4).at[255].set(-1e-3)
    s = segment_sums(a)
    assert float(s[255, 254]) == pytest.approx(-1e-3, rel=1e-6)
    assert float(s[254, 253]) == pytest.approx(-6.4, rel=1e-6)
    assert float(s[255, 0]) == pytest.approx(-6.4 * 254 - 1e-3, rel=1e-6)
    # and the decays are masked above the diagonal before the exponential
    tile = kernels._Tile(4, 1)
    assert np.array_equal(np.asarray(tile.lower), np.tril(np.ones((4, 4), bool)))


def pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr and of the jaxprs its equations hold."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += pallas_calls(sub)
    return found


def test_the_scan_keeps_its_inputs_dtype_and_a_float32_state():
    """bfloat16 operands give a bfloat16 output near the float32 one, and the state's scratch
    and the states the backward is kept are float32 whatever the operands."""
    args32 = operands(64, seed=3)
    args16 = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a for i, a in enumerate(args32))
    got = jax.jit(lambda *a: ssd_scan(*a, 16))(*args16)
    assert got.dtype == jnp.bfloat16
    rounded = tuple(a.astype(jnp.float32) for a in args16)
    want = jax.jit(ref.ssm_recurrent)(*rounded)
    assert np.linalg.norm(got.astype(jnp.float32) - want) <= 2e-2 * np.linalg.norm(want)
    call, = pallas_calls(jax.make_jaxpr(lambda *a: ssd_scan(*a, 16))(*args16).jaxpr)
    assert call.params["name"] == "ds_ssd_scan_fwd"
    y, kept = call.outvars
    assert y.aval.dtype == jnp.bfloat16 and kept.aval.dtype == jnp.float32
    # the state's scratch (and the tile's C B^T) beside the operands
    scratch = call.params["jaxpr"].invars[-2:]
    assert [v.aval.dtype for v in scratch] == [jnp.float32] * 2
    assert scratch[0].aval.shape[1:] == kept.aval.shape[-2:] == (16, 4 * 8)
    assert CHUNK == 256


# ------------------------------------------------------------------ the convolution
def plain_conv(x, w, b, silu):
    """The convolution written out: four shifted products, the bias, then the activation."""
    padded = jnp.pad(x, ((0, 0), (w.shape[0] - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + x.shape[1]] * w[j] for j in range(w.shape[0])) + b
    return jax.nn.silu(y) if silu else y


@pytest.mark.parametrize("silu", [False, True])
def test_the_convolutions_bias_is_added_before_the_activation(silu):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    b = jax.random.normal(jax.random.PRNGKey(2), (6,))
    np.testing.assert_allclose(causal_conv(x, w, silu, b), plain_conv(x, w, b, silu), atol=1e-6)
    grad = lambda fn: jax.grad(lambda x, w, b: jnp.sum(fn(x, w, b) ** 2), argnums=(0, 1, 2))(x, w, b)   # noqa: E731
    for got, want in zip(grad(lambda x, w, b: causal_conv(x, w, silu, b)),
                         grad(lambda x, w, b: plain_conv(x, w, b, silu))):
        np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_without_a_bias_the_convolution_is_the_delta_rule_mixers_bit_for_bit(dtype):
    """The delta-rule mixer's call (no bias) computes what it did before the bias came:
    the same sum in the same order, and a zero bias changes no bit either."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 8)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    before = jax.nn.silu(sum(padded[:, j:j + 40].astype(jnp.float32) * w[j] for j in range(4))).astype(dtype)
    assert np.array_equal(np.asarray(causal_conv(x, w, True), np.float32), np.asarray(before, np.float32))
    assert np.array_equal(np.asarray(causal_conv(x, w, True, jnp.zeros((8,))), np.float32),
                          np.asarray(before, np.float32))
