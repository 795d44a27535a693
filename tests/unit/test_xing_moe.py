"""Xing4.0 (``deepspeed_tpu/models/xing_moe.py``) against its plain float32 reference
(``benchmarks/reference/xing_moe_reference.py``) at a toy width, one dense and one expert block
inside four residual streams: the loss, the logits, the expert choices, ``H_res``'s readings and every
leaf's gradient; through ``deepspeed_tpu.initialize`` in float32 and in bfloat16 with blocks
recomputed; what a block keeps; the scopes the benchmark reads, pinned in the compiled programs; the
kernel's interpreted path at keys wider than the values.

One toy a share, its parameters, the reference's forward and its jitted loss-and-gradient are built
ONCE a module (``toy``, ``reference_forward``, ``reference_step``): no case compiles for itself what
another compiled."""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.reference import xing_moe_reference as ref
from deepspeed_tpu.models import xing_moe
from deepspeed_tpu.utils import spans
from test_ouro import kernels_in_the_backward
from xing_toy import batch, build, published

SHARES = {"absent-left-out": (4, 4, False), "held-stand-in": (4, 4, True)}
BLOCKS = 2
BIAS = "['router_bias']"
# leaves that have no gradient whatever the data: the first sub-layer's H_pre only scales what an
# RMSNorm reads (every stream is the embedding), the last one's H_res has columns that sum to one
# and its streams are summed
NO_GRADIENT = ("['layers'][0]['hc_attn']['phi_pre']", "['layers'][0]['hc_attn']['b_pre']",
               "['layers'][1]['hc_mlp']['phi_res']", "['layers'][1]['hc_mlp']['b_res']")


def cut(share):
    first, count, stand_in = share
    return published(n_routed_experts=count, router_width=8, first_expert=first, stand_in=stand_in)


@functools.lru_cache(maxsize=None)
def toy(share):
    """``(keys, model, params, tokens, labels)`` of a share: built once a module."""
    keys, model, params = build(cut(SHARES[share]))
    return (keys, model, params) + batch(seed=2, rows=8, T=24)


@functools.lru_cache(maxsize=None)
def reference_forward(share):
    """The reference's forward on the toy's first two sequences, run once a share."""
    keys, _, params, tokens, labels = toy(share)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p: ref.forward(p, tokens[:2], labels[:2], keys, last=16))(params)


@functools.lru_cache(maxsize=None)
def reference_step(share):
    """``(loss, every leaf's gradient, the counts [Le, E])`` of the reference on the toy's batch."""
    keys, _, params, tokens, labels = toy(share)

    def loss_and_counts(p):
        out = ref.forward(p, tokens, labels, keys, last=1, keep_inputs=False)
        return out["loss"], out["counts"]
    with jax.default_matmul_precision("highest"):
        (loss, counts), grads = jax.jit(jax.value_and_grad(loss_and_counts, has_aux=True))(params)
    return loss, grads, counts


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("share", SHARES)
def test_the_loss_the_logits_the_choices_and_h_res_match_the_reference(highest, share):
    (keys, model, params, tokens, labels), want = toy(share), reference_forward(share)
    got = jax.jit(lambda p: model.forward_details(p, tokens[:2], labels[:2], 16))(params)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), rel=2e-5)
    for name in ("logits", "attn_in", "mlp_in"):
        np.testing.assert_allclose(got[name], want[name], atol=2e-4, err_msg=name)
    assert np.array_equal(got["experts"], want["experts"]) and got["experts"].shape == (1, 2, 24, 2)
    assert np.array_equal(got["counts"], want["counts"]) and got["attn_in"].shape == (BLOCKS, 2, 24, 32)
    np.testing.assert_allclose(jax.nn.sigmoid(got["router_logits"]), want["scores"], atol=1e-5)
    # every sub-layer's H_res, as far from doubly stochastic and as near the identity as the reference's
    assert got["hc_res_err_max"].shape == got["hc_res_diag_mean"].shape == (2 * BLOCKS,)
    np.testing.assert_allclose(got["hc_res_err_max"], want["hc_res_err_max"], rtol=2e-2, atol=1e-7)
    np.testing.assert_allclose(got["hc_res_diag_mean"], want["hc_res_diag_mean"], atol=1e-5)
    loss, stats = jax.jit(model.apply)(params, tokens[:2], labels[:2])
    assert float(loss) == pytest.approx(float(want["loss"]), rel=2e-5)
    assert set(stats) == set(model.device_scalars) | set(model.rule_sums)
    np.testing.assert_allclose(stats["hc_res_diag_mean"], want["hc_res_diag_mean"], atol=1e-5)
    # what landed on held experts is what the reference's choices say; every assignment where the
    # held ones stand in
    first, count, stand_in = SHARES[share]
    here = np.sum((want["experts"] >= first) & (want["experts"] < first + count) | stand_in, axis=(1, 2, 3))
    assert np.array_equal(stats["moe_rows_here"], here) and np.array_equal(stats["moe_counts"], want["counts"])
    assert float(stats["moe_bias_abs_max"][0]) == pytest.approx(float(jnp.abs(params["layers"][1]["moe"]["router_bias"]).max()))
    # without labels: the logits
    np.testing.assert_allclose(jax.jit(model.apply)(params, tokens[:2])[:, -16:], want["logits"], atol=2e-4)


@pytest.mark.parametrize("share", ["absent-left-out", "held-stand-in"])
def test_the_engine_computes_the_reference_loss_every_gradient_and_the_rules_update(highest, share):
    """Through ``deepspeed_tpu.initialize`` in float32 with plain SGD: the step's loss is the
    reference's; what one step took off every parameter, over the rate, is its gradient (every leaf
    of both hyper-connections of both blocks too, through all 20 rounds); the selection bias is the
    reference's ``b + u sign(mean(c) - c)`` on the reference's own counts."""
    (keys, model, params, tokens, labels), (want_loss, want, counts) = toy(share), reference_step(share)
    rate = 0.5
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": False},
        "optimizer": {"type": "SGD", "params": {"lr": rate}}, "steps_per_print": 10 ** 9})
    before = jax.device_get(engine.master_params)
    loss = engine(tokens, labels)
    engine.backward(loss)
    engine.step()
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
    after = dict(jax.tree_util.tree_flatten_with_path(jax.device_get(engine.master_params))[0])
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    moved = iter(ref.updated_biases(before, counts, model.config.bias_update_rate))
    biases = connections = 0
    for path, b in jax.tree_util.tree_flatten_with_path(before)[0]:
        name, a = jax.tree_util.keystr(path), np.asarray(after[path])
        if name.endswith(BIAS):
            assert not np.any(flat_want[path]), "the reference's gradient of a selection bias is zero"
            np.testing.assert_allclose(a, next(moved), rtol=0, atol=1e-7)
            assert np.abs(np.abs(a - b) - 1e-3 * (a != b)).max() < 1e-7 and np.any(a != b)
            biases += 1
            continue
        got, w = (np.asarray(b) - a) / rate, np.asarray(flat_want[path])
        # what a step took off a float32 leaf is known to the leaf's own spacing, over the rate
        coarse = np.sqrt(b.size) * np.spacing(np.abs(np.asarray(b)).max()) / rate
        assert np.linalg.norm(got - w) <= 2e-3 * np.linalg.norm(w) + coarse, name
        if "['hc_" in name:
            connections += 1
            # the leaves with no gradient by structure have none in the reference either
            assert (np.linalg.norm(w) < 1e-6) == (name in NO_GRADIENT), (name, np.linalg.norm(w))
    assert biases == 1 and connections == 2 * BLOCKS * 8
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    assert set(kept[-1][1]) == set(model.device_scalars)
    assert kept[-1][1]["hc_res_err_max"].shape == (2 * BLOCKS,) and kept[-1][1]["moe_rows_here"].shape == (1,)


def test_a_step_in_bfloat16_with_blocks_recomputed_moves_every_leaf():
    _, model, params = build(cut(SHARES["held-stand-in"]), bias_spread=0.0, compute_dtype=jnp.bfloat16,
                             initializer_range=0.02, remat=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 3e-3}}, "steps_per_print": 10 ** 9})
    tokens, labels = batch(seed=4, T=24)
    before = jax.device_get(engine.master_params)
    loss = engine(tokens, labels)
    engine.backward(loss)
    engine.step()
    assert np.isfinite(float(loss)) and engine.skipped_steps == 0
    # the forward reads a selection bias as the master holds it, float32 in the compute copy too
    assert engine.params["layers"][1]["moe"]["router_bias"].dtype == jnp.float32
    assert engine.params["layers"][1]["hc_mlp"]["phi_res"].dtype == jnp.bfloat16
    # Adam's first step moves every leaf (the embedding's rows of tokens the batch lacks apart, and
    # a hyper-connection's leaf whose gradient lies round Adam's epsilon in part), and the rule
    # every bias by u or not at all
    for (path, b), a in zip(jax.tree_util.tree_flatten_with_path(before)[0],
                            jax.tree_util.tree_leaves(jax.device_get(engine.master_params))):
        name = jax.tree_util.keystr(path)
        if name.endswith(BIAS):
            assert np.abs(np.abs(a - b) - 1e-3 * (a != b)).max() < 1e-7 and np.any(a != b), name
        elif name not in NO_GRADIENT:
            assert np.mean(a != b) > (0.2 if "embed" in name or "['hc_" in name else 0.9), name


@functools.lru_cache(maxsize=None)
def recomputed(dtype, kept="as it is"):
    """``(the compiled gradient program, its loss and every gradient)`` of the stand-in toy with its
    blocks recomputed, ``xing_moe.KEPT_BY_A_LAYER`` replaced by ``kept`` (``"as it is"``: left; None:
    only a block's input), compiled so that a value is the same bits wherever it is made; once a
    (dtype, kept set) for the cases that read it."""
    _, model, params = build(cut(SHARES["held-stand-in"]), remat=True, compute_dtype=getattr(jnp, dtype))
    tokens, labels = batch(seed=6, rows=2, T=24)
    with pytest.MonkeyPatch.context() as patch:
        if kept != "as it is":
            patch.setattr(xing_moe, "KEPT_BY_A_LAYER", kept)
        compiled = jax.jit(jax.value_and_grad(lambda p: model.apply(p, tokens, labels)[0])).lower(params).compile(
            compiler_options={"xla_allow_excess_precision": False})
    return compiled, jax.device_get(compiled(params))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_what_a_block_keeps_changes_no_bit_of_the_loss_and_little_of_a_gradient(dtype):
    """The kept tensors (the kernel's output and row sums, the experts' first product, both
    sub-layers' projections onto the coefficient columns) are the values the second forward would have made again:
    the loss is the same bits under the kept set and under ``policy=None`` (only a block's input),
    and every leaf's gradient the same to the last float32 bits (XLA:CPU sums some in another order)."""
    (_, (loss, got)), (_, (want_loss, want)) = recomputed(dtype), recomputed(dtype, None)
    assert float(loss) == float(want_loss) and np.isfinite(float(loss))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= (1e-5 if dtype == "float32" else 2e-2) * np.linalg.norm(b) + 1e-12, name
        assert np.any(a) != name.endswith(BIAS)


def test_the_scopes_the_benchmark_reads_are_in_the_compiled_programs():
    """Everything a sub-layer's hyper-connection does lies under ``ds_hc`` INSIDE ``ds_attn`` /
    ``ds_mlp``, in two parts, ``ds_hc_coef`` and ``ds_hc_mix``: forward, second forward and backward;
    a block's mixer under ``ds_attn/ds_attn_latent``, the expert layers' ``ds_moe_*`` under ``ds_mlp``;
    the second forward makes the coefficients again (their own backward reads the rounds) and runs no
    flash kernel."""
    _, model, params = build(cut(SHARES["held-stand-in"]), remat=True)
    tokens, labels = batch(seed=6, rows=2, T=24)
    grad = jax.grad(lambda p, t, l: model.apply(p, t, l)[0])
    text = recomputed("float32")[0].as_text()          # the kept-set case's program: compiled once
    for path in (r"ds_attn\)?/ds_hc/ds_hc_coef", r"ds_attn\)?/ds_hc/ds_hc_mix", r"ds_mlp\)?/ds_hc/ds_hc_coef",
                 r"ds_mlp\)?/ds_hc/ds_hc_mix", r"ds_attn\)?/ds_attn_latent/ds_flash_fwd",
                 r"checkpoint/rematted_computation/ds_attn/ds_hc/ds_hc_mix",
                 r"checkpoint/rematted_computation/ds_mlp/ds_hc/ds_hc_mix",
                 r"checkpoint/rematted_computation/ds_attn/ds_attn_latent/",
                 r"checkpoint/ds_attn/ds_hc/ds_hc_coef", r"checkpoint/ds_attn/ds_hc/ds_hc_mix",
                 r"checkpoint/ds_mlp/ds_hc/ds_hc_coef", r"checkpoint/ds_mlp/ds_hc/ds_hc_mix",
                 r"checkpoint/ds_attn/ds_attn_latent/ds_flash_bwd_dkv",
                 r"ds_mlp\)?/\S*ds_moe_router", r"ds_mlp\)?/\S*ds_moe_experts", r"ds_mlp\)?/ds_moe_shared",
                 "ds_embed", "ds_loss"):
        assert re.search(path, text), path
    assert not re.search(r"ds_hc\S*/ds_attn_latent", text) and not re.search(r"ds_hc\S*/ds_moe_", text)
    assert not re.search(r"rematted_computation/ds_attn/ds_attn_latent/ds_flash_fwd", text)
    for kept in ("as it is", None):        # the rounds are made again under either set, under the same scopes
        assert re.search(r"rematted_computation/ds_attn/ds_hc/ds_hc_coef", recomputed("float32", kept)[0].as_text())
    # by the jaxpr: in the blocks' backward no forward kernel, one backward kernel a block
    assert kernels_in_the_backward(jax.make_jaxpr(grad)(params, tokens, labels).jaxpr) == \
        {"ds_flash_bwd_dkv": BLOCKS}
    # the rule runs inside the update program, under the optimizer's scope and its own
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, "steps_per_print": 10 ** 9})
    (_, jitted, args), = [(name, jitted, args) for name, jitted, args, _ in engine.lint_programs(batch(seed=6, T=24))
                          if name == "apply_update"]
    text = jitted.lower(*args).as_text(debug_info=True)
    assert text.index("ds_apply_update") < text.index("ds_moe_bias_update")


def test_the_latent_mixer_under_yarn_and_m_squared_matches_the_reference(highest):
    """The mixer alone against the reference's, output and ``W_kva``'s gradient (its 4 rotary columns
    take theirs from all four heads), and against the reference with the plain frequencies, the scale
    without ``m^2``, the rotary key left out or a key of its own a head."""
    keys, model, params, *_ = toy("held-stand-in")
    ap = params["layers"][1]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 32))
    cot = jax.random.normal(jax.random.PRNGKey(9), (2, 24, 32))
    by = lambda f: jax.jit(jax.value_and_grad(lambda p: jnp.sum(f(p) * cot), has_aux=False))      # noqa: E731
    (got, g), (want, w) = by(lambda p: model.attention(x, p))(ap), by(lambda p: ref.attention(x, p, keys))(ap)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_allclose(g["wkv_a"], w["wkv_a"], atol=2e-5 * float(jnp.abs(w["wkv_a"]).max()))
    assert float(jnp.abs(w["wkv_a"][:, 12:]).max()) > 0
    y = jax.jit(lambda p: ref.attention(x, p, keys))(ap)
    for fault in ({"yarn": False}, {"m_squared": False}, {"rotary_key": "left_out"}, {"rotary_key": "a_head_its_own"}):
        wrong = jax.jit(lambda p: ref.attention(x, p, keys, **fault))(ap)
        assert np.linalg.norm(wrong - y) > 3e-3 * np.linalg.norm(y), fault
    # m = 0.1 ln(64) + 1 at mscale_all_dim 1, and cos and sin times the ratio of two equal m's
    (inv_freq, factor), scale = model.config.rotary()
    assert factor == 1.0 and scale == pytest.approx((0.1 * np.log(64) + 1) ** 2 / 4.0) and inv_freq.shape == (2,)
    np.testing.assert_allclose(inv_freq, ref.yarn_inverse_frequencies(4, 10000, keys["rope_scaling"]), rtol=1e-6)
    # the earliest token attends to itself alone: its output is its value (8 wide) through W_o
    kv = (xing_moe.hc.rms_norm(x @ ap["wkv_a"][:, :12], ap["kv_norm"], 1e-6) @ ap["wkv_b"]).reshape(2, 24, 4, 20)
    np.testing.assert_allclose(jax.jit(model.attention)(x, ap)[:, 0], kv[:, 0, :, 12:].reshape(2, 32) @ ap["wo"],
                               atol=1e-5)


@pytest.mark.parametrize("widths", [(192, 128), (64, 64), (64, 128)], ids=lambda w: f"{w[0]}|{w[1]}")
def test_the_flash_kernel_interpreted_at_two_widths_matches_dense_attention(widths):
    """Keys 192 wide beside values of 128 (the cell's), equal widths (the numbers every other model
    has), and values wider than the keys: the kernel's interpreted path at three tiles of 64, a
    length no 128 divides, against ``dense_attention``: the output and all three gradients."""
    from deepspeed_tpu.ops.pallas.flash_attention import dense_attention, flash_attention
    deep, wide = widths
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (1, 4, 192, deep), jnp.float32) for i in range(2))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 192, wide), jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(3), v.shape)
    flash = lambda *a: flash_attention(*a, True, 0.11, block_q=64, block_k=64, interpret=True)       # noqa: E731
    dense = lambda *a: dense_attention(*a, True, 0.11)                                              # noqa: E731
    out = flash(q, k, v)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, dense(q, k, v), atol=2e-5)
    got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * cot), argnums=(0, 1, 2))(q, k, v) for f in (flash, dense))
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-4)
