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


# ------------------------------------------------------------- the Pallas kernels (PR 59), interpreted
# ``ops/pallas/hyper_connection.py``: on the TPU ``hc.connected`` is four kernels; here they run in
# ``interpret`` mode against the ``jnp`` form above, on one lane-aligned shape (the toy's is none).
from deepspeed_tpu.ops.pallas import hyper_connection as kernels      # noqa: E402

WIDE, TOKENS = 128, (2, 128)          # n = 4 streams of 128, 256 tokens: two tiles of 128
LEAVES = ("norm", "phi_pre", "phi_post", "phi_res", "b_pre", "b_post", "b_res", "gates")
READ = ("u", "coefficients", "streams", "f") + LEAVES        # what each kernel's case reads, by name
LIMIT = {"float32": 1e-5, "bfloat16": 1e-2}                     # relative L2; bf16: the rounding of an output or a cotangent


def wide_toy(dtype):
    hp = hc.init(jax.random.PRNGKey(0), N, WIDE, 0.05)
    k = jax.random.split(jax.random.PRNGKey(1), 8)
    hp = dict(hp, norm=hp["norm"] + 0.1 * jax.random.normal(k[0], hp["norm"].shape),
              b_pre=0.3 * jax.random.normal(k[1], (N,)), b_post=0.3 * jax.random.normal(k[2], (N,)),
              b_res=hp["b_res"] + 0.5 * jax.random.normal(k[3], (N, N)), gates=jnp.asarray([0.4, -0.3, 0.5]))
    draw = lambda key, width: jax.random.normal(key, TOKENS + (width,)).astype(dtype)       # noqa: E731
    return hp, draw(k[4], N * WIDE), draw(k[5], WIDE), draw(k[6], N * WIDE), draw(k[7], WIDE)


def everything(form, hp, x, f_in, cot, cot_u):
    """``{name: value}`` of one connection round ``F(u) = sin(u) + f_in``: ``u``, ``X'``, the
    readings, and the gradients of ``<X', cot> + <u, cot_u>`` by the streams, ``f`` and every leaf."""
    f32 = lambda a: a.astype(jnp.float32)      # noqa: E731

    def loss(hp, x, f_in):
        out, stats = form(x, hp, lambda u: ((jnp.sin(f32(u)) + f32(f_in)).astype(u.dtype), {"u": u}))
        return jnp.sum(f32(out) * f32(cot)) + jnp.sum(f32(stats["u"]) * f32(cot_u)), (out, stats)

    (_, (out, stats)), (by_leaf, by_streams, by_f) = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(hp, x, f_in)
    return dict(by_leaf, out=out, streams=by_streams, f=by_f, **stats)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both_forms(request):
    """``(the streams' type, the jnp form's values, the interpreted kernels' values, the
    coefficients [T, 128] of ds_hc_read alone)`` on the lane-aligned shape."""
    hp, *arrays = wide_toy(jnp.dtype(request.param))
    with jax.default_matmul_precision("highest"):
        want = everything(lambda x, hp, F: hc.connected(x, hp, F, *ARGS), hp, *arrays)
        got = everything(lambda x, hp, F: hc.connected_by_kernels(x, hp, F, *ARGS, tm=128, interpret=True), hp, *arrays)
        x = arrays[0].reshape(-1, N * WIDE)
        phi, gate_bias = hc._packed(hp, N)
        g, cols = hc._operands(x, hp["norm"], gate_bias)
        _, co, _ = kernels.read(x, g, phi.astype(x.dtype), cols, n=N, iters=20, eps=1e-6, clamp=(-30.0, 30.0),
                                norm_eps=1e-6, tm=128, interpret=True)
        want["coefficients"] = jnp.concatenate([jnp.moveaxis(h.reshape(-1, *TOKENS), 0, -1).reshape(x.shape[0], -1)
                                                for h in hc.coefficients(arrays[0], hp, *ARGS)], axis=-1)
        got["coefficients"] = co[:, np.asarray(kernels.columns(N))]
    return request.param, want, got


def apart(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("name", ("out",) + READ)
def test_the_kernels_read_what_the_jnp_form_reads(both_forms, name):
    """``ds_hc_read`` (``u``, the 24 coefficients), ``ds_hc_write`` (``X'``), ``ds_hc_write_bwd``
    (the gradient by ``f``) and ``ds_hc_read_bwd`` (by the streams and every leaf, through all 20
    rounds): float32 streams to 1e-5, bf16 streams to the rounding of an output."""
    dtype, want, got = both_forms
    assert got[name].shape == want[name].shape and got[name].dtype == want[name].dtype
    assert float(np.linalg.norm(np.asarray(want[name], np.float64))) > 0
    assert apart(got[name], want[name]) <= LIMIT[dtype], (name, apart(got[name], want[name]))


def test_the_kernels_readings_are_the_jnp_forms(both_forms):
    dtype, want, got = both_forms
    for name in hc.READINGS:
        assert float(got[name]) == pytest.approx(float(want[name]), rel=1e-5 if dtype == "float32" else 1e-3)


def test_the_kernels_gradients_stop_a_round_short_are_other_gradients():
    """All 20 rounds are pulled back: the kernels at 19 rounds give ``B_res`` another gradient."""
    hp, *arrays = wide_toy(jnp.float32)
    run = lambda iters: everything(lambda x, hp, F: hc.connected_by_kernels(      # noqa: E731
        x, hp, F, N, iters, *ARGS[2:], tm=128, interpret=True), hp, *arrays)["b_res"]
    with jax.default_matmul_precision("highest"):
        full, short = run(20), run(3)
    assert apart(short, full) > 1e-2


@pytest.mark.parametrize("tokens, width, n, dtype, tile", [
    (4096, 3584, 4, jnp.bfloat16, 256),       # xing4_ep8_d5_train_1chip's step
    (1024, 3584, 4, jnp.bfloat16, 256),       # its set-up's sequences
    (256, 128, 4, jnp.float32, 256), (384, 128, 4, jnp.float32, 128),
    (12, 8, 4, jnp.float32, None),            # the toy: no whole register
    (4096, 3584 + 64, 4, jnp.bfloat16, None), (4000, 3584, 4, jnp.bfloat16, None), (4096, 128, 9, jnp.bfloat16, None),
    (4096, 8192, 4, jnp.float32, None)])      # four float32 streams of 8,192: no tile's blocks fit
def test_the_tile_goes_by_the_shapes(tokens, width, n, dtype, tile):
    assert kernels.tile(tokens, n, width, jnp.dtype(dtype).itemsize) == tile
    if tile is not None:
        assert all(kernels._limit(kind, tile, n, width, jnp.dtype(dtype).itemsize) < kernels.VMEM_CAP
                   for kind in ("read", "write", "write_bwd", "read_bwd"))


def test_shapes_the_kernels_do_not_take_fall_to_the_jnp_form(toy, highest, monkeypatch):
    """Off the TPU, and on it at the toy's shapes (streams of 8), ``connected`` is the ``jnp`` form:
    the same numbers as ``coefficients`` / ``read`` / ``write`` called one by one."""
    hp, x = toy
    F = lambda u: (jnp.sin(u), {})                             # noqa: E731
    wide = jnp.zeros(TOKENS + (N * WIDE,), jnp.bfloat16)
    assert hc.kernel_tile(x, N) is None and hc.kernel_tile(wide, N) is None       # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert hc.kernel_tile(x, N) is None and hc.kernel_tile(wide, N) == 256
    assert hc.kernel_tile(wide[:, :100], N) is None                                # no whole tile of tokens
    got, stats = jax.jit(lambda x: hc.connected(x, hp, F, *ARGS))(x)
    np.testing.assert_array_equal(got, jax.jit(lambda x: connected(x, hp, lambda u: F(u)[0]))(x))
    assert set(stats) == set(hc.READINGS)
