"""GLM-4.7-Flash (``deepspeed_tpu/models/glm_moe.py``) against its plain float32 reference
(``benchmarks/reference/glm_moe_reference.py``) at a toy width, one dense block, one expert block
and the prediction module: both depths' losses and logits, the expert choices and every leaf's
gradient; through ``deepspeed_tpu.initialize`` in float32 and in bfloat16 with blocks recomputed;
what a block keeps; the scopes the benchmark reads, pinned in the compiled programs; the kernel's
interpreted path at the cell's head geometry.

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
from benchmarks.reference import glm_moe_reference as ref
from deepspeed_tpu.models import glm_moe
from deepspeed_tpu.utils import spans
from glm_toy import MTP_WEIGHT, batch, build, published
from test_ouro import kernels_in_the_backward

SHARES = {"absent-left-out": (4, 4, False), "held-stand-in": (4, 4, True)}
BLOCKS = 3           # the toy's two blocks and the module's
BIAS = "['router_bias']"


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
        return jax.jit(lambda p: ref.forward(p, tokens[:2], labels[:2], keys, MTP_WEIGHT, last=16))(params)


@functools.lru_cache(maxsize=None)
def reference_step(share):
    """``(loss, every leaf's gradient, the counts [Le, E])`` of the reference on the toy's batch."""
    keys, _, params, tokens, labels = toy(share)

    def loss_and_counts(p):
        out = ref.forward(p, tokens, labels, keys, MTP_WEIGHT, last=1)
        return out["loss"], out["counts"]
    with jax.default_matmul_precision("highest"):
        (loss, counts), grads = jax.jit(jax.value_and_grad(loss_and_counts, has_aux=True))(params)
    return loss, grads, counts


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("share", SHARES)
def test_both_depths_losses_logits_and_the_choices_match_the_reference(highest, share):
    (keys, model, params, tokens, labels), want = toy(share), reference_forward(share)
    got = jax.jit(lambda p: model.forward_details(p, tokens[:2], labels[:2], 16))(params)
    for name in ("loss", "loss_main", "loss_mtp"):
        assert float(got[name]) == pytest.approx(float(want[name]), rel=2e-5), name
    assert float(got["loss"]) == pytest.approx(float(got["loss_main"]) + MTP_WEIGHT * float(got["loss_mtp"]), rel=1e-6)
    for name in ("logits", "logits_mtp", "attn_in", "mlp_in", "mtp_in"):
        np.testing.assert_allclose(got[name], want[name], atol=2e-4, err_msg=name)
    assert np.array_equal(got["experts"], want["experts"]) and got["experts"].shape == (2, 2, 24, 2)
    assert np.array_equal(got["counts"], want["counts"]) and got["attn_in"].shape == (BLOCKS, 2, 24, 32)
    np.testing.assert_allclose(jax.nn.sigmoid(got["router_logits"]), want["scores"], atol=1e-5)
    loss, stats = jax.jit(model.apply)(params, tokens[:2], labels[:2])
    assert float(loss) == pytest.approx(float(want["loss"]), rel=2e-5)
    assert set(stats) == set(model.device_scalars) | set(model.rule_sums)
    assert float(stats["loss_mtp"]) == pytest.approx(float(want["loss_mtp"]), rel=2e-5)
    # what landed on held experts is what the reference's choices say; every assignment where all
    # are held or the held ones stand in
    first, count, stand_in = SHARES[share]
    here = np.sum((want["experts"] >= first) & (want["experts"] < first + count) | stand_in, axis=(1, 2, 3))
    assert np.array_equal(stats["moe_rows_here"], here) and np.array_equal(stats["moe_counts"], want["counts"])
    # without labels: the first depth's logits
    np.testing.assert_allclose(jax.jit(model.apply)(params, tokens[:2])[:, -16:], want["logits"], atol=2e-4)


@pytest.mark.parametrize("share", ["absent-left-out", "held-stand-in"])
def test_the_engine_computes_the_reference_loss_every_gradient_and_the_rules_update(highest, share):
    """Through ``deepspeed_tpu.initialize`` in float32 with plain SGD: the step's loss is the
    reference's ``L_1 + 0.3 L_2``; what one step took off every parameter, over the rate, is its
    gradient (the shared embedding's and head's from both depths); every selection bias, the module's
    block's too, is the reference's ``b + u sign(mean(c) - c)`` on the reference's own counts."""
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
    assert biases == 2
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    assert set(kept[-1][1]) == set(model.device_scalars)
    assert float(kept[-1][1]["loss_main"]) + MTP_WEIGHT * float(kept[-1][1]["loss_mtp"]) == \
        pytest.approx(float(loss), rel=1e-6)


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
    assert engine.params["mtp"]["block"]["moe"]["router_bias"].dtype == jnp.float32
    assert engine.params["mtp"]["w_eh"].dtype == jnp.bfloat16
    # Adam's first step moves every leaf (the embedding's rows of tokens the batch lacks apart), and
    # the rule every bias by u or not at all
    for (path, b), a in zip(jax.tree_util.tree_flatten_with_path(before)[0],
                            jax.tree_util.tree_leaves(jax.device_get(engine.master_params))):
        name = jax.tree_util.keystr(path)
        if name.endswith(BIAS):
            assert np.abs(np.abs(a - b) - 1e-3 * (a != b)).max() < 1e-7 and np.any(a != b), name
        else:
            assert np.mean(a != b) > (0.2 if "embed" in name else 0.9), name


@functools.lru_cache(maxsize=None)
def recomputed(dtype, kept="as it is"):
    """``(the compiled gradient program, its loss and every gradient)`` of the stand-in toy with its
    blocks recomputed, ``glm_moe.KEPT_BY_A_LAYER`` replaced by ``kept`` (``"as it is"``: left; None:
    only a block's input), compiled so that a value is the same bits wherever it is made; once a
    (dtype, kept set) for the cases that read it."""
    _, model, params = build(cut(SHARES["held-stand-in"]), remat=True, compute_dtype=getattr(jnp, dtype))
    tokens, labels = batch(seed=6, rows=2, T=24)
    with pytest.MonkeyPatch.context() as patch:
        if kept != "as it is":
            patch.setattr(glm_moe, "KEPT_BY_A_LAYER", kept)
        compiled = jax.jit(jax.value_and_grad(lambda p: model.apply(p, tokens, labels)[0])).lower(params).compile(
            compiler_options={"xla_allow_excess_precision": False})
    return compiled, jax.device_get(compiled(params))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_what_a_block_keeps_changes_no_bit_of_the_loss_or_of_a_gradient(dtype):
    """The kept tensors are the values the second forward would have made again, in the dtype the
    forward made them in: the loss and every leaf's gradient are the same bits under the kept set
    and under ``policy=None`` (only a block's input); in bfloat16 XLA:CPU sums the ``q_norm``
    gradients in another order there (their last float32 bits)."""
    (_, (loss, got)), (_, (want_loss, want)) = recomputed(dtype), recomputed(dtype, None)
    assert float(loss) == float(want_loss) and np.isfinite(float(loss))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if dtype == "bfloat16" and name.endswith("['q_norm']"):
            np.testing.assert_allclose(a, b, rtol=1e-5, err_msg=name)
        else:
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), name
        assert np.any(np.asarray(a, np.float32)) != name.endswith(BIAS)


def test_the_scopes_the_benchmark_reads_are_in_the_compiled_programs():
    """A block's whole mixer lies under ``ds_attn_latent`` INSIDE ``ds_attn``, the module whole under
    ``ds_mtp`` (its block's mixer under both, its cross-entropy under ``ds_mtp/ds_loss``), forward,
    second forward and backward; the expert layers keep ``ds_moe_*`` under ``ds_mlp``; a recomputed
    block's backward runs no second forward kernel (it keeps the kernel's output by name)."""
    _, model, params = build(cut(SHARES["held-stand-in"]), remat=True)
    tokens, labels = batch(seed=6, rows=2, T=24)
    grad = jax.grad(lambda p, t, l: model.apply(p, t, l)[0])
    text = recomputed("float32")[0].as_text()          # the kept-set case's program: compiled once
    for path in (r"ds_attn\)?/ds_attn_latent/ds_flash_fwd", r"ds_mtp\)?/\S*ds_attn/ds_attn_latent/ds_flash_fwd",
                 r"checkpoint/rematted_computation/ds_attn/ds_attn_latent/",
                 r"ds_mtp\)?/checkpoint/rematted_computation/ds_attn/ds_attn_latent/",
                 r"checkpoint/ds_attn/ds_attn_latent/ds_flash_bwd_dkv",
                 r"ds_mtp\)?/checkpoint/ds_attn/ds_attn_latent/ds_flash_bwd_dkv",
                 r"ds_mlp\)?/\S*ds_moe_router", r"ds_mlp\)?/\S*ds_moe_experts", r"ds_mlp\)?/ds_moe_shared",
                 r"ds_mtp\)?/\S*ds_mlp/\S*ds_moe_experts", r"ds_mtp\)?/ds_loss", r"ds_mtp\)?/ds_embed",
                 "ds_embed", "ds_loss"):
        assert re.search(path, text), path
    assert not re.search(r"ds_mlp/\S*ds_attn_latent", text) and not re.search(r"ds_attn_latent/\S*ds_mtp", text)
    assert not re.search(r"rematted_computation/ds_attn/ds_attn_latent/ds_flash_fwd", text)
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


def test_the_rotary_key_is_one_head_and_its_gradient_the_heads_sum(highest):
    """The latent mixer alone against the reference's, output and ``W_kva``'s gradient (its 4 rotary
    columns take theirs from all four heads), and against the reference with the rotary key left
    out or a key of its own a head (the other faults: the rehearsal's probe)."""
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
    for fault in ("left_out", "a_head_its_own"):
        wrong = jax.jit(lambda p: ref.attention(x, p, keys, rotary_key=fault))(ap)
        assert np.linalg.norm(wrong - y) > 1e-2 * np.linalg.norm(y), fault
    # the earliest token attends to itself alone: its output is its value through W_o
    kv = (glm_moe.rms_norm(x @ ap["wkv_a"][:, :12], ap["kv_norm"], 1e-5) @ ap["wkv_b"]).reshape(2, 24, 4, 28)
    np.testing.assert_allclose(jax.jit(model.attention)(x, ap)[:, 0], kv[:, 0, :, 12:].reshape(2, 64) @ ap["wo"],
                               atol=1e-5)


def test_the_flash_kernel_interpreted_at_twenty_heads_of_256_matches_dense_attention():
    """The cell's head geometry, 20 query over 20 key/value heads of 256, at a short length: the
    kernel's interpreted path against ``dense_attention``, output and gradients."""
    from deepspeed_tpu.ops.pallas.flash_attention import dense_attention, flash_attention
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 20, 128, 256), jnp.float32) for i in range(3))
    cot = jax.random.normal(jax.random.PRNGKey(3), q.shape)
    flash = lambda *a: flash_attention(*a, True, interpret=True)       # noqa: E731
    dense = lambda *a: dense_attention(*a, True)                       # noqa: E731
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * cot), argnums=(0, 1, 2))(q, k, v) for f in (flash, dense))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)
