"""The short causal convolution's Pallas kernels (``ops/pallas/causal_conv.py`` behind
``ops/delta_rule.causal_conv``), interpreted: against the plain form, forward and all three
gradients, over blocks the sequence fills, does not fill and does not reach; the halo across a
block's edge at the exact rows; a window of a wider operand read in place; what the gradient's
program holds and under which scopes. ``test_ssd.py`` and ``test_qwen3_next.py`` have the
convolution at toy widths, which the plain form takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import delta_rule
from deepspeed_tpu.ops.delta_rule import causal_conv, plain_causal_conv
from deepspeed_tpu.ops.pallas import causal_conv as kernels
from test_delta_rule_kernel import _calls, rel

ROWS = 64       # a grid step of the tests: two blocks in 128 tokens


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(kernels, "ROWS", ROWS)


def inputs(B, T, C, dtype=jnp.float32, bias=True, wide=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + T), 4)
    x = jax.random.normal(ks[0], (B, T, wide or C)).astype(dtype)
    w = jax.random.uniform(ks[1], (4, C), jnp.float32, -0.5, 0.5)
    b = jax.random.uniform(ks[2], (C,), jnp.float32, -0.5, 0.5) if bias else None
    return (x, w, b), jax.random.normal(ks[3], (B, T, C)).astype(dtype)


def pulled_back(fn, args, cot):
    """``fn(*args)`` and the cotangents of x, w and the bias (where there is one) for ``cot``."""
    y, back = jax.vjp(fn, *args)
    return y, [g for g in back(cot) if g is not None]


def kernel_names(fn, *args):
    found = _calls(jax.make_jaxpr(fn)(*args).jaxpr, {"kernels": [], "others": set()})
    return found["kernels"], found["others"]


# ------------------------------------------------------------------ against the plain form
@pytest.mark.parametrize("T", [128, 100, 40], ids=["two-blocks", "a-short-last-block", "under-a-block"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("silu", [True, False], ids=["silu", "linear"])
def test_the_kernels_are_the_plain_form(silu, bias, dtype, T, monkeypatch):
    """Two rows of the batch, 256 channels (two tiles of 128 lanes): the result and the
    gradients of x, w and the bias. In float32 to rounding; in bfloat16 the forward differs in
    a last bit here and there and the input's gradient by its own rounding (the plain form
    rounds each tap's part to bfloat16 before it adds them, the kernel adds in float32)."""
    monkeypatch.setattr(kernels, "LANES", 128)
    args, cot = inputs(2, T, 256, dtype, bias)
    got, got_grads = pulled_back(lambda *a: causal_conv(a[0], a[1], silu, a[2]), args, cot)
    want, want_grads = pulled_back(lambda *a: plain_causal_conv(a[0], a[1], silu, a[2]), args, cot)
    low = dtype == jnp.bfloat16
    assert got.shape == want.shape and got.dtype == dtype
    assert rel(got, want) < (1e-3 if low else 1e-6)
    assert len(got_grads) == 2 + bias
    for name, g, w in zip(("x", "w", "bias"), got_grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert rel(g, w) < (1e-2 if low and name == "x" else 2e-6 if not low else 1e-4), name


@pytest.mark.parametrize("T", [128, 100, 40], ids=["two-blocks", "a-short-last-block", "under-a-block"])
def test_one_row_of_the_batch(T):
    args, cot = inputs(1, T, 128)
    got, got_grads = pulled_back(lambda *a: causal_conv(a[0], a[1], True, a[2]), args, cot)
    want, want_grads = pulled_back(lambda *a: plain_causal_conv(a[0], a[1], True, a[2]), args, cot)
    assert rel(got, want) < 1e-6
    assert all(rel(g, w) < 2e-6 for g, w in zip(got_grads, want_grads))


def test_the_bfloat16_gradient_of_the_input_is_nearer_the_float32_one_than_the_plain_forms():
    args, cot = inputs(2, 128, 128, jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in args)
    exact = pulled_back(lambda *a: plain_causal_conv(a[0], a[1], True, a[2]), wide, cot.astype(jnp.float32))[1][0]
    ours = pulled_back(lambda *a: causal_conv(a[0], a[1], True, a[2]), args, cot)[1][0]
    plain = pulled_back(lambda *a: plain_causal_conv(a[0], a[1], True, a[2]), args, cot)[1][0]
    assert ours.dtype == plain.dtype == jnp.bfloat16
    assert rel(ours, exact) < rel(plain, exact) < 1e-2


@pytest.mark.parametrize("C", [96, 200], ids=["under-a-register", "over-a-register"])
def test_a_width_the_lanes_do_not_divide_takes_the_plain_form(C):
    """Same numbers, bit for bit, and no kernel in the program."""
    args, cot = inputs(2, 40, C)
    fn = lambda *a: causal_conv(a[0], a[1], True, a[2])      # noqa: E731
    got, got_grads = pulled_back(fn, args, cot)
    want, want_grads = pulled_back(lambda *a: plain_causal_conv(a[0], a[1], True, a[2]), args, cot)
    assert np.array_equal(got, want)
    assert all(np.array_equal(g, w) for g, w in zip(got_grads, want_grads))
    assert kernel_names(fn, *args)[0] == []


# ------------------------------------------------------------------ a window of a wider operand
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_a_window_of_a_wider_operand_is_read_where_it_lies(dtype):
    """A projection's output 400 wide, the convolution's channels 128-383 of it: the kernels
    take the whole array, the result is the slice's, and the operand's cotangent is zero
    outside the window. No slice of the operand is in the program."""
    (x, w, b), cot = inputs(2, 100, 256, dtype, wide=400)
    fn = lambda x, w, b: causal_conv(x, w, True, b, columns=(128, 384))      # noqa: E731
    got, got_grads = pulled_back(fn, (x, w, b), cot)
    want, want_grads = pulled_back(lambda x, w, b: plain_causal_conv(x[..., 128:384], w, True, b), (x, w, b), cot)
    low = dtype == jnp.bfloat16
    assert got.shape == (2, 100, 256) and rel(got, want) < (1e-3 if low else 1e-6)
    assert got_grads[0].shape == x.shape and got_grads[0].dtype == dtype
    assert not np.asarray(got_grads[0][..., :128], np.float32).any()
    assert not np.asarray(got_grads[0][..., 384:], np.float32).any()
    for g, w_ in zip(got_grads, want_grads):
        assert rel(g, w_) < (1e-2 if low else 2e-6)
    calls, others = kernel_names(fn, x, w, b)
    assert [name for name, _ in calls] == ["ds_causal_conv_fwd"] and "slice" not in others
    # the most lanes that divide the window and its start: Granite's 4,352 from 4,096 take 256
    assert kernels.sizes(100, 256, 128) == (ROWS, 128) and kernels.sizes(8192, 4352, 4096) == (ROWS, 256)


def test_a_window_that_starts_inside_a_register_takes_the_plain_form():
    (x, w, b), _ = inputs(1, 40, 128, wide=300)
    fn = lambda x, w, b: causal_conv(x, w, True, b, columns=(100, 228))      # noqa: E731
    assert np.array_equal(fn(x, w, b), plain_causal_conv(x[..., 100:228], w, True, b))
    assert kernel_names(fn, x, w, b)[0] == []


# ------------------------------------------------------------------ the halo, causality, the batch
def test_the_halo_crosses_a_blocks_edge_at_the_exact_rows():
    """Block two starts at token 64: its first three outputs read tokens 61-63 of block one
    (and token 67 reads none of them), and the gradient of tokens 61-63 takes the cotangents of
    64-66 from the block after."""
    (x, w, _), _ = inputs(1, 128, 128, bias=False)
    y = np.asarray(causal_conv(x, w))
    xs, ws = np.asarray(x, np.float64), np.asarray(w, np.float64)
    for t in (64, 65, 66, 67):
        by_hand = sum(ws[j] * xs[0, t - 3 + j] for j in range(4))
        np.testing.assert_allclose(y[0, t], by_hand, rtol=1e-5, atol=1e-6)
    moved = np.asarray(causal_conv(x.at[0, 63].add(1.0), w)) - y
    assert np.abs(moved[0, 64:67]).min() > 0 and not moved[0, 67:].any() and not moved[0, :63].any()
    np.testing.assert_allclose(moved[0, 64], ws[2], rtol=1e-4, atol=1e-6)
    # backward: a cotangent on tokens 64-66 alone
    cot = jnp.zeros_like(x).at[0, 64:67].set(1.0)
    dx = np.asarray(jax.vjp(lambda x: causal_conv(x, w), x)[1](cot)[0])
    for t in (61, 62, 63):
        by_hand = sum(ws[3 - s] for s in range(4) if 64 <= t + s <= 66)
        np.testing.assert_allclose(dx[0, t], by_hand, rtol=1e-5, atol=1e-6)
    assert not dx[0, :61].any() and not dx[0, 67:].any()


@pytest.mark.parametrize("silu", [False, True], ids=["linear", "silu"])
def test_the_kernels_see_no_later_token(silu):
    (x, w, b), _ = inputs(2, 128, 128)
    want = np.asarray(causal_conv(x, w, silu, b))
    later = x.at[:, 70:].set(7.0)           # the rest of block two, and nothing of block one
    np.testing.assert_array_equal(np.asarray(causal_conv(later, w, silu, b))[:, :70], want[:, :70])
    # and a token's gradient takes nothing from the tokens before it
    cot = jnp.zeros_like(x).at[:, :70].set(1.0)
    dx = np.asarray(jax.vjp(lambda x: causal_conv(x, w, silu, b), x)[1](cot)[0])
    assert not dx[:, 70:].any() and np.abs(dx[:, 69]).min() > 0


def test_a_row_of_the_batch_never_sees_the_row_before_it():
    (x, w, b), cot = inputs(2, 100, 128)
    both, both_grads = pulled_back(lambda *a: causal_conv(a[0], a[1], True, a[2]), (x, w, b), cot)
    alone, alone_grads = pulled_back(lambda *a: causal_conv(a[0], a[1], True, a[2]), (x[1:], w, b), cot[1:])
    np.testing.assert_array_equal(np.asarray(both[1]), np.asarray(alone[0]))
    np.testing.assert_array_equal(np.asarray(both_grads[0][1]), np.asarray(alone_grads[0][0]))
    first = plain_causal_conv(x[1:, :3], w, True, b)      # zeros before a row's first token
    np.testing.assert_allclose(both[1, :3], first[0], atol=1e-6)


# ------------------------------------------------------------------ the program
def test_the_gradients_program_is_the_two_kernels_and_keeps_nothing_but_the_operands():
    (x, w, b), cot = inputs(2, 100, 128, jnp.bfloat16)
    fn = lambda x, w, b: causal_conv(x, w, True, b)      # noqa: E731
    calls, others = kernel_names(lambda *a: pulled_back(fn, a, cot), x, w, b)
    assert sorted(name for name, _ in calls) == ["ds_causal_conv_bwd", "ds_causal_conv_fwd"]
    # what is left outside the kernels casts the weights, adds the eight partial sums up and
    # takes the weights' and the bias's rows of them
    assert not others & {"exp", "logistic", "dot_general", "scan", "while", "mul", "dynamic_slice"}, others
    _, kept = jax.vjp(fn, x, w, b)
    leaves = jax.tree_util.tree_leaves(kept)
    assert sorted(a.shape for a in leaves) == sorted(a.shape for a in (x, w, b))
    assert all(a.dtype != jnp.float32 or a.ndim < 3 for a in leaves)       # no float32 residual


def under(path, mixer):
    """``ds_conv`` inside the mixer's scope in a kernel's scope path (a transform wraps the
    scopes it was applied under: ``jvp(ds_ssm)/ds_conv/...``)."""
    return mixer in path and "ds_conv/" in path and path.index(mixer) < path.index("ds_conv/")


def granite():
    from test_granite_hybrid import build, published
    # 16 heads of 8 and a state of 64: the convolution's 256 channels begin at channel 128
    return build(published(mamba_n_heads=16, mamba_d_state=64, mamba_expand=4))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "under-checkpoint"])
def test_both_kernels_run_under_the_state_space_mixers_scopes(remat):
    """``benchmarks/ssm_spans.py`` counts an operation under ``ds_ssm`` by its scope path and a
    recomputed forward by JAX's ``rematted_computation``: the backward kernel, which a
    transpose traces, carries ``ds_ssm/ds_conv`` as the forward does."""
    _, model, params = granite()
    mp = params["layers"][0]["mixer"]
    assert mp["conv_w"].shape == (4, 256)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 32))
    loss = lambda x, mp: jnp.sum(model.mamba_mixer(x, mp) ** 2)      # noqa: E731
    loss = jax.checkpoint(loss) if remat else loss
    calls, _ = kernel_names(jax.grad(loss, argnums=(0, 1)), x, mp)
    mine = [(name, path) for name, path in calls if name.startswith("ds_causal_conv")]
    names = [name for name, _ in mine]
    assert names.count("ds_causal_conv_bwd") == 1 and names.count("ds_causal_conv_fwd") == 1 + remat
    for name, path in mine:
        assert under(path, "ds_ssm") and path.endswith(name), (name, path)
        # (under a checkpoint the backward's equations lie inside the transposed call, whose
        # own path holds the ``transpose(``)
        assert remat or ("transpose(" in path) == (name == "ds_causal_conv_bwd"), (name, path)
    if remat:
        again = [path for name, path in mine if "rematted_computation" in path]
        assert len(again) == 1 and again[0].endswith("ds_ssm/ds_conv/ds_causal_conv_fwd/ds_causal_conv_fwd")


def test_the_mixer_with_the_kernels_is_the_mixer_with_the_plain_form(monkeypatch):
    """The whole Mamba-2 mixer, output and every gradient, through the kernels (the window of
    the projection read in place) and through the plain form on the slice."""
    _, model, params = granite()
    mp = params["layers"][0]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 100, 32))
    loss = lambda x, mp: jnp.sum(model.mamba_mixer(x, mp) ** 2)      # noqa: E731
    got = jax.value_and_grad(loss, argnums=(0, 1))(x, mp)
    monkeypatch.setattr(delta_rule, "causal_conv", lambda x, w, silu, bias, columns:
                        plain_causal_conv(x[..., columns[0]:columns[1]], w, silu, bias))
    want = jax.value_and_grad(loss, argnums=(0, 1))(x, mp)
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * abs(float(want[0]))
    for g, w in zip(jax.tree_util.tree_leaves(got[1]), jax.tree_util.tree_leaves(want[1])):
        assert rel(g, w) < 2e-5


def test_both_kernels_run_under_the_delta_rule_mixers_scopes():
    from test_qwen3_next import build, published
    # 2 key heads and 4 value heads of 16: q, k and v are 128 channels from channel 0
    _, model, params = build(published(linear_key_head_dim=16, linear_value_head_dim=16))
    mp = params["layers"][0]["mixer"]
    assert mp["conv_w"].shape == (4, 128)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 32))
    loss = lambda x, mp: jnp.sum(model.linear_mixer(x, mp) ** 2)      # noqa: E731
    calls, _ = kernel_names(jax.grad(loss, argnums=(0, 1)), x, mp)
    mine = [(name, path) for name, path in calls if name.startswith("ds_causal_conv")]
    assert sorted(name for name, _ in mine) == ["ds_causal_conv_bwd", "ds_causal_conv_fwd"]
    for name, path in mine:
        assert under(path, "ds_lin_attn") and path.endswith(name), (name, path)


def test_the_compiled_kernels_refuse_what_the_lanes_do_not_divide():
    (x, w, b), _ = inputs(1, 64, 96)
    with pytest.raises(AssertionError, match="whole registers of 128 lanes"):
        jax.eval_shape(lambda x, w, b: kernels.causal_conv_fwd(x, w, b, 0, 96, 64, 96, True, False), x, w, b)
    with pytest.raises(AssertionError):          # a window that runs past the operand
        jax.eval_shape(lambda x, w, b: kernels.causal_conv_fwd(x, w, b, 32, 96, 64, 32, True, True), x, w, b)
