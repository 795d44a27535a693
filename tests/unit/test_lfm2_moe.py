"""LFM2-MoE (``deepspeed_tpu/models/lfm2_moe.py``) against its plain float32 reference
(``benchmarks/reference/lfm2_moe_reference.py``) at a toy width, a dense short-conv layer, an
attention and a short-conv layer with experts: the loss, the logits, the expert choices and every
leaf's gradient; through ``deepspeed_tpu.initialize`` in float32 and in bfloat16 with layers
recomputed; what a layer keeps; the scopes the benchmark reads, pinned in the compiled programs; the
convolution's kernels at three taps with no bias and no SiLU.

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
from benchmarks.reference import lfm2_moe_reference as ref
from deepspeed_tpu.models import lfm2_moe
from deepspeed_tpu.ops.delta_rule import causal_conv, plain_causal_conv
from deepspeed_tpu.utils import spans
from lfm2_toy import EPS, KINDS, batch, build, published
from test_ouro import kernels_in_the_backward

SHARES = {"absent-left-out": (4, 4, False), "held-stand-in": (4, 4, True)}
EXPERT_LAYERS = 2
BIAS = "['router_bias']"


def cut(share):
    first, count, stand_in = share
    return published(num_experts=count, router_width=8, first_expert=first, stand_in=stand_in)


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
        return jax.jit(lambda p: ref.forward(p, tokens[:2], labels[:2], keys, EPS, last=16))(params)


@functools.lru_cache(maxsize=None)
def reference_step(share):
    """``(loss, every leaf's gradient, the counts [Le, E])`` of the reference on the toy's batch."""
    keys, _, params, tokens, labels = toy(share)

    def loss_and_counts(p):
        out = ref.forward(p, tokens, labels, keys, EPS, last=1)
        return out["loss"], out["counts"]
    with jax.default_matmul_precision("highest"):
        (loss, counts), grads = jax.jit(jax.value_and_grad(loss_and_counts, has_aux=True))(params)
    return loss, grads, counts


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("share", SHARES)
def test_the_loss_the_logits_and_the_choices_match_the_reference(highest, share):
    (keys, model, params, tokens, labels), want = toy(share), reference_forward(share)
    got = jax.jit(lambda p: model.forward_details(p, tokens[:2], labels[:2], 16))(params)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), rel=2e-5)
    for name in ("logits", "op_in", "ff_in"):
        np.testing.assert_allclose(got[name], want[name], atol=2e-4, err_msg=name)
    assert np.array_equal(got["experts"], want["experts"]) and got["experts"].shape == (EXPERT_LAYERS, 2, 24, 2)
    assert np.array_equal(got["counts"], want["counts"]) and got["op_in"].shape == (len(KINDS), 2, 24, 128)
    np.testing.assert_allclose(jax.nn.sigmoid(got["router_logits"]), want["scores"], atol=1e-5)
    loss, stats = jax.jit(model.apply)(params, tokens[:2], labels[:2])
    assert float(loss) == pytest.approx(float(want["loss"]), rel=2e-5)
    assert set(stats) == set(model.device_scalars) | set(model.rule_sums)
    # what landed on held experts is what the reference's choices say; every assignment where all
    # are held or the held ones stand in
    first, count, stand_in = SHARES[share]
    here = np.sum((want["experts"] >= first) & (want["experts"] < first + count) | stand_in, axis=(1, 2, 3))
    assert np.array_equal(stats["moe_rows_here"], here) and np.array_equal(stats["moe_counts"], want["counts"])
    biases = [lp["moe"]["router_bias"] for lp in params["layers"] if "moe" in lp]
    np.testing.assert_allclose(stats["moe_bias_abs_max"], [np.abs(b).max() for b in biases], rtol=1e-6)
    # without labels: the logits, through the embedding table
    np.testing.assert_allclose(jax.jit(model.apply)(params, tokens[:2])[:, -16:], want["logits"], atol=2e-4)


@pytest.mark.parametrize("share", SHARES)
def test_the_engine_computes_the_reference_loss_every_gradient_and_the_rules_update(highest, share):
    """Through ``deepspeed_tpu.initialize`` in float32 with plain SGD: the step's loss is the
    reference's; what one step took off every parameter, over the rate, is its gradient (the tied
    table's from the lookups and from the head); every selection bias is the reference's ``b + u
    sign(mean(c) - c)`` on the reference's own counts."""
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
    biases = 0
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
    assert biases == EXPERT_LAYERS
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    assert set(kept[-1][1]) == set(model.device_scalars) and kept[-1][1]["moe_rows_here"].shape == (EXPERT_LAYERS,)


def test_a_step_in_bfloat16_with_layers_recomputed_moves_every_leaf():
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
    assert engine.params["layers"][2]["moe"]["router_bias"].dtype == jnp.float32
    assert engine.params["layers"][2]["conv"]["conv_w"].dtype == jnp.bfloat16
    # Adam's first step moves every leaf (every row of the tied table: it is the head too), and the
    # rule every bias by u or not at all
    for (path, b), a in zip(jax.tree_util.tree_flatten_with_path(before)[0],
                            jax.tree_util.tree_leaves(jax.device_get(engine.master_params))):
        name = jax.tree_util.keystr(path)
        if name.endswith(BIAS):
            assert np.abs(np.abs(a - b) - 1e-3 * (a != b)).max() < 1e-7 and np.any(a != b), name
        else:
            assert np.mean(a != b) > 0.9, name


@functools.lru_cache(maxsize=None)
def recomputed(dtype, kept="as it is"):
    """``(the compiled gradient program, its loss and every gradient)`` of the stand-in toy with its
    layers recomputed, ``lfm2_moe.KEPT_BY_A_LAYER`` replaced by ``kept`` (``"as it is"``: left; None:
    only a layer's input), compiled so that a value is the same bits wherever it is made; once a
    (dtype, kept set) for the cases that read it."""
    _, model, params = build(cut(SHARES["held-stand-in"]), remat=True, compute_dtype=getattr(jnp, dtype))
    tokens, labels = batch(seed=6, rows=2, T=24)
    with pytest.MonkeyPatch.context() as patch:
        if kept != "as it is":
            patch.setattr(lfm2_moe, "KEPT_BY_A_LAYER", kept)
        compiled = jax.jit(jax.value_and_grad(lambda p: model.apply(p, tokens, labels)[0])).lower(params).compile(
            compiler_options={"xla_allow_excess_precision": False})
    return compiled, jax.device_get(compiled(params))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_what_a_layer_keeps_changes_no_bit_of_the_loss_or_of_a_gradient(dtype):
    """The kept tensors are the values the second forward would have made again, in the dtype the
    forward made them in: the loss and every leaf's gradient are the same bits under the kept set
    and under ``policy=None`` (only a layer's input); in bfloat16 XLA:CPU sums the per-head norms'
    gradients in another order there (their last float32 bits)."""
    (_, (loss, got)), (_, (want_loss, want)) = recomputed(dtype), recomputed(dtype, None)
    assert float(loss) == float(want_loss) and np.isfinite(float(loss))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if dtype == "bfloat16" and name.endswith(("['q_norm']", "['k_norm']")):
            np.testing.assert_allclose(a, b, rtol=1e-5, err_msg=name)
        else:
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), name
        assert np.any(np.asarray(a, np.float32)) != name.endswith(BIAS)


def test_the_scopes_the_benchmark_reads_are_in_the_compiled_programs():
    """A conv layer's whole operator lies under ``ds_short_conv`` INSIDE ``ds_attn``, and what lies
    between its two products under ``ds_short_conv_gate`` inside that, the convolution's kernels
    under ``ds_conv`` inside both: forward, second forward (``rematted_computation``) and backward
    alike; the products lie outside the gate's scope; the attention layer under ``ds_attn`` alone;
    the expert layers keep ``ds_moe_*`` under ``ds_mlp``; a recomputed layer's backward runs no
    second flash forward kernel (it keeps the kernel's output by name), no ``W_in`` product (kept by
    name too) and the convolution's forward kernel once more a conv layer (nothing of the gate is
    kept)."""
    _, model, params = build(cut(SHARES["held-stand-in"]), remat=True)
    tokens, labels = batch(seed=6, rows=2, T=24)
    grad = jax.grad(lambda p, t, l: model.apply(p, t, l)[0])
    text = recomputed("float32")[0].as_text()          # the kept-set case's program: compiled once
    gate = r"ds_attn\)?/ds_short_conv/ds_short_conv_gate"
    for path in (gate + r"/ds_conv/\S*causal_conv_fwd", gate + r"/mul",
                 r"checkpoint/rematted_computation/ds_attn/ds_short_conv/ds_short_conv_gate/ds_conv/\S*causal_conv_fwd",
                 r"checkpoint/rematted_computation/ds_attn/ds_short_conv/ds_short_conv_gate/split",
                 r"checkpoint/ds_attn/ds_short_conv/ds_short_conv_gate/ds_conv/\S*causal_conv_bwd",
                 r"checkpoint/ds_attn/ds_short_conv/ds_short_conv_gate/mul",
                 r"ds_attn\)?/ds_short_conv/dot_general", r"checkpoint/ds_attn/ds_short_conv/dot_general",
                 r"ds_attn\)?/ds_flash_fwd", r"checkpoint/ds_attn/ds_flash_bwd_dkv",
                 r"ds_mlp\)?/\S*ds_moe_router", r"ds_mlp\)?/\S*ds_moe_experts", "ds_embed", "ds_loss"):
        assert re.search(path, text), path
    assert not re.search(r"ds_short_conv_gate/dot_general", text)
    assert not re.search(r"ds_mlp/\S*ds_short_conv", text) and not re.search(r"ds_short_conv/\S*ds_flash", text)
    assert not re.search(r"rematted_computation/ds_attn/ds_flash_fwd", text)
    # the first product's output is kept by name: the second forward makes the gate again from it, no product
    assert not re.search(r"rematted_computation/ds_attn/ds_short_conv/dot_general", text)
    # by the jaxpr: in the layers' backward the convolution's forward kernel again and its backward
    # kernel a conv layer, and one flash backward kernel the attention layer
    kernels = kernels_in_the_backward(jax.make_jaxpr(grad)(params, tokens, labels).jaxpr)
    assert kernels == {"ds_flash_bwd_dkv": KINDS.count("full_attention"),
                       "ds_causal_conv_fwd": KINDS.count("conv"), "ds_causal_conv_bwd": KINDS.count("conv")}, kernels
    # the rule runs inside the update program, under the optimizer's scope and its own
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, "steps_per_print": 10 ** 9})
    (_, jitted, args), = [(name, jitted, args) for name, jitted, args, _ in engine.lint_programs(batch(seed=6, T=24))
                          if name == "apply_update"]
    text = jitted.lower(*args).as_text(debug_info=True)
    assert text.index("ds_apply_update") < text.index("ds_moe_bias_update")


def test_the_short_convolution_alone_matches_the_reference_and_sees_no_later_token(highest):
    """The operator alone against the reference's, output and every gradient; the reference with its
    taps reversed or its window a token ahead reads far off; the earliest token's output is its own
    ``C * (w[last] B z)`` through ``W_out`` (zeros before it), and a later token moves no earlier one."""
    keys, model, params, *_ = toy("held-stand-in")
    cp = params["layers"][2]["conv"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 128))
    cot = jax.random.normal(jax.random.PRNGKey(9), (2, 24, 128))
    by = lambda f: jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(f(p, x) * cot), argnums=(0, 1)))      # noqa: E731
    (got, g), (want, w) = by(lambda p, x: model.short_conv(x, p))(cp, x), by(lambda p, x: ref.short_conv(x, p, keys))(cp, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(w)):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))
    y = jax.jit(lambda p: ref.short_conv(x, p, keys))(cp)
    for fault in ("reversed", "ahead"):
        wrong = jax.jit(lambda p: ref.short_conv(x, p, keys, taps=fault))(cp)
        assert np.linalg.norm(wrong - y) > 0.1 * np.linalg.norm(y), fault
    b, c, z = jnp.split(x[:, 0] @ cp["w_in"], 3, axis=-1)
    mine = jax.jit(model.short_conv)(x, cp)
    np.testing.assert_allclose(mine[:, 0], (c * (cp["conv_w"][-1] * b * z)) @ cp["w_out"], atol=1e-5)
    moved = jax.jit(model.short_conv)(x.at[:, 12].add(1.0), cp)
    assert np.array_equal(moved[:, :12], mine[:, :12]) and not np.allclose(moved[:, 12:15], mine[:, 12:15])
    assert np.array_equal(moved[:, 15:], mine[:, 15:])          # three taps: a token reaches two on


@pytest.mark.parametrize("T", [24, 200], ids=["T24-one-short-block", "T200-no-whole-chunk"])
def test_the_convolutions_kernels_at_three_taps_without_bias_or_silu_match_the_plain_form(T):
    """``causal_conv`` as the short-conv operator calls it (three taps, no bias, no SiLU, an operand
    of its own), interpreted, against ``plain_causal_conv``, forward and backward, at a length that
    is no whole block of the kernels (nor a whole chunk of 64 rows)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, T, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 256), jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    kernel = lambda x, w: causal_conv(x, w, silu=False, bias=None, interpret=True)      # noqa: E731
    plain = lambda x, w: plain_causal_conv(x, w, False, None)                           # noqa: E731
    np.testing.assert_allclose(jax.jit(kernel)(x, w), plain(x, w), atol=1e-5)
    got, want = (jax.jit(jax.grad(lambda x, w: jnp.sum(f(x, w) * cot), argnums=(0, 1)))(x, w) for f in (kernel, plain))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()))
