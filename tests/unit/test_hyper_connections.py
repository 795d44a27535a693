"""The hyper-connection alone (``deepspeed_tpu/models/hyper_connections.py``) on a handful of tokens:
``H_res`` doubly stochastic within Sinkhorn-Knopp's error after 20 rounds, the closed form with the
gates at zero, the flat layout against an explicit ``[n, C]`` axis, and the gradients through all 20
rounds against the plain reference's (``benchmarks/reference/xing_moe_reference.py``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import xing_moe_reference as ref
from deepspeed_tpu.models import hyper_connections as hc
from deepspeed_tpu.models.layers import rms_norm

N, C, B, T = 4, 8, 2, 6
M = {"hc_mult": N, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
     "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-6}
ARGS = (N, 20, 1e-6, (-30.0, 30.0), 1e-6)


@pytest.fixture(scope="module")
def toy():
    """``(parameters off their initial values, streams [B, T, n C])``."""
    hp = hc.init(jax.random.PRNGKey(0), N, C, 0.5)
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    hp = dict(hp, norm=hp["norm"] + 0.1 * jax.random.normal(k[0], hp["norm"].shape),
              b_pre=0.3 * jax.random.normal(k[1], (N,)), b_post=0.3 * jax.random.normal(k[2], (N,)),
              b_res=hp["b_res"] + 0.5 * jax.random.normal(k[3], (N, N)), gates=jnp.asarray([0.4, -0.3, 0.5]))
    return hp, jax.random.normal(k[4], (B, T, N * C))


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def connected(x, hp, f, iters=20):
    h_pre, h_post, h_res = hc.coefficients(x, hp, N, iters, *ARGS[2:])
    return hc.write(x, f(hc.read(x, h_pre)), h_post, h_res)


def test_h_res_is_doubly_stochastic_within_the_rounds_error(toy, highest):
    hp, x = toy
    h_pre, h_post, h_res = jax.jit(lambda x: hc.coefficients(x, hp, *ARGS))(x)
    assert h_pre.shape == h_post.shape == (N, B, T) and h_res.shape == (N, N, B, T)
    assert np.all(h_res > 0) and np.all((h_pre > 0) & (h_pre < 1)) and np.all((h_post > 0) & (h_post < 2))
    # the columns were normalised last: exact but for eps; the rows within what 20 rounds leave
    np.testing.assert_allclose(jnp.sum(h_res, axis=0), 1.0, atol=3e-6)
    np.testing.assert_allclose(jnp.sum(h_res, axis=1), 1.0, atol=5e-2)
    readings = hc.readings(h_res)
    assert set(readings) == set(hc.READINGS) and 0 < float(readings["hc_res_err_max"]) < 5e-2
    assert float(readings["hc_res_err_max"]) == pytest.approx(float(jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1))), rel=1e-5)
    assert 0.5 < float(readings["hc_res_diag_mean"]) < 1
    # fewer rounds leave the rows further from one: the rounds are run, all of them
    fewer = jax.jit(lambda x: hc.coefficients(x, hp, N, 5, *ARGS[2:]))(x)[2]
    assert float(hc.readings(fewer)["hc_res_err_max"]) > 3 * float(readings["hc_res_err_max"])
    # and the reference's coefficients are these, stream axis and all
    want = ref.coefficients(x.reshape(B, T, N, C), hp, M)
    for got, w in zip((h_pre, h_post, h_res), want):
        np.testing.assert_allclose(jnp.moveaxis(got, (-2, -1), (0, 1)), w, atol=2e-6)


def test_zero_gates_and_a_large_b_res_make_the_coefficients_static(toy, highest):
    hp, x = toy
    static = dict(hp, gates=jnp.zeros((3,)), b_res=30.0 * jnp.eye(N))
    h_pre, h_post, h_res = jax.jit(lambda x: hc.coefficients(x, static, *ARGS))(x)
    np.testing.assert_allclose(h_res, jnp.broadcast_to(jnp.eye(N)[:, :, None, None], h_res.shape), atol=1e-6)
    np.testing.assert_allclose(h_pre, jnp.broadcast_to(jax.nn.sigmoid(hp["b_pre"])[:, None, None], h_pre.shape), atol=1e-7)
    np.testing.assert_allclose(h_post, jnp.broadcast_to(2 * jax.nn.sigmoid(hp["b_post"])[:, None, None], h_post.shape),
                               atol=1e-7)
    # the clamp holds: B_res far past it is B_res at it
    clamped = jax.jit(lambda x: hc.coefficients(x, dict(static, b_res=1e4 * jnp.eye(N)), *ARGS))(x)[2]
    assert np.all(np.isfinite(clamped))
    np.testing.assert_allclose(clamped, h_res, atol=1e-6)


def test_a_static_sub_layer_is_its_closed_form(toy, highest):
    """``X'[i] = X[i] + H_post[i] F(rms(sum_j H_pre[j] X[j]))`` with ``H_pre = sigmoid(b_pre)``,
    ``H_post = 2 sigmoid(b_post)``: a plain residual a stream, scaled."""
    hp, x = toy
    static = dict(hp, gates=jnp.zeros((3,)), b_res=30.0 * jnp.eye(N))
    g = 1.0 + 0.1 * jnp.arange(C)
    F = lambda u: jnp.tanh(rms_norm(u, g, 1e-6)) * 3.0        # noqa: E731
    got = jax.jit(lambda x: connected(x, static, F))(x).reshape(B, T, N, C)
    X = x.reshape(B, T, N, C)
    u = jnp.einsum("j,btjc->btc", jax.nn.sigmoid(hp["b_pre"]), X)
    want = X + (2 * jax.nn.sigmoid(hp["b_post"]))[None, None, :, None] * F(u)[:, :, None, :]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_flat_streams_are_the_stream_axis_flattened(toy, highest):
    hp, x = toy
    F = lambda u: jnp.sin(u)                                   # noqa: E731
    got = jax.jit(lambda x: connected(x, hp, F))(x)
    want, u, h_res = ref.connected(x.reshape(B, T, N, C), hp, M, F)
    np.testing.assert_allclose(got.reshape(B, T, N, C), want, atol=1e-5)
    h_pre = jax.jit(lambda x: hc.coefficients(x, hp, *ARGS))(x)[0]
    np.testing.assert_allclose(hc.read(x, h_pre), u, atol=1e-5)
    assert [p.shape for p in hc.streams_of(x, N)] == [(B, T, C)] * N
    np.testing.assert_array_equal(hc.streams_of(x, N)[2], x.reshape(B, T, N, C)[:, :, 2])


def test_the_gradients_go_through_all_twenty_rounds_as_the_references_do(toy, highest):
    hp, x = toy
    cot = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    F = lambda u: jnp.sin(u)                                   # noqa: E731
    mine = jax.jit(jax.grad(lambda hp, x: jnp.sum(connected(x, hp, F) * cot), argnums=(0, 1)))(hp, x)
    theirs = jax.jit(jax.grad(lambda hp, x: jnp.sum(
        ref.connected(x.reshape(B, T, N, C), hp, M, F)[0].reshape(x.shape) * cot), argnums=(0, 1)))(hp, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(mine)[0], jax.tree_util.tree_leaves(theirs)):
        assert float(jnp.linalg.norm(a - b)) <= 2e-4 * float(jnp.linalg.norm(b)), jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(b)) > 0
    # a gradient that stopped after a round fewer is another gradient
    fewer = jax.jit(jax.grad(lambda hp: jnp.sum(connected(x, hp, F, iters=3) * cot)))(hp)
    assert float(jnp.linalg.norm(fewer["b_res"] - theirs[0]["b_res"])) > 1e-2 * float(jnp.linalg.norm(theirs[0]["b_res"]))


def test_init_starts_near_a_plain_residual():
    hp = hc.init(jax.random.PRNGKey(0), N, C, 0.02)
    assert {k: v.shape for k, v in hp.items()} == {
        "norm": (N * C,), "phi_pre": (N * C, N), "phi_post": (N * C, N), "phi_res": (N * C, N * N),
        "b_pre": (N,), "b_post": (N,), "b_res": (N, N), "gates": (3,)}
    assert sum(v.size for v in hp.values()) == N * C * N * (N + 2) + N * C + N * (N + 2) + 3
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, N * C))
    h_pre, h_post, h_res = hc.coefficients(x, hp, *ARGS)
    np.testing.assert_allclose(h_pre, 0.5, atol=1e-2)
    np.testing.assert_allclose(h_post, 1.0, atol=2e-2)
    assert 0.94 < float(hc.readings(h_res)["hc_res_diag_mean"]) < 0.96
