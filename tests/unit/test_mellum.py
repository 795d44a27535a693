"""Mellum 2 (``deepspeed_tpu/models/mellum.py``) against its plain float32 reference
(``benchmarks/reference/mellum_reference.py``) at a toy width, two periods of (sliding, sliding,
sliding, full): the whole model's loss, logits and expert choices; every leaf's gradient through
``deepspeed_tpu.initialize``; recomputed layers; the scopes the benchmark reads, pinned in the
compiled program."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.reference import mellum_reference as ref
from deepspeed_tpu.utils import spans
from mellum_toy import AUX_COEF, batch, build, published


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def toy():
    """One period, every expert held."""
    return build(published(num_hidden_layers=4))


STAND_IN = pytest.mark.parametrize("share", [None, (4, 4, False), (4, 4, True)],
                                   ids=["all-held", "absent-left-out", "held-stand-in"])


def cut(share, layers=4):
    """All eight experts held over two periods, or a share of them over one."""
    if share is None:
        return published()
    first, count, stand_in = share
    return published(num_experts=count, router_width=8, first_expert=first, stand_in=stand_in,
                     num_hidden_layers=layers)


@STAND_IN
def test_loss_logits_and_choices_match_the_reference(highest, share):
    keys, model, params = build(cut(share))
    tokens, labels = batch(rows=2, T=24)
    L = keys["num_hidden_layers"]
    want = jax.jit(lambda p: ref.forward(p, tokens, labels, keys, AUX_COEF, last=16))(params)
    got = jax.jit(lambda p: model.forward_details(p, tokens, labels, 16))(params)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), rel=2e-5)
    assert float(got["aux"]) == pytest.approx(float(want["aux"]), rel=1e-5)
    np.testing.assert_allclose(got["logits"], want["logits"], atol=2e-4)
    assert np.array_equal(got["experts"], want["experts"]) and got["experts"].shape == (L, 2, 24, 2)
    np.testing.assert_allclose(got["attn_in"], want["attn_in"], atol=2e-4)
    np.testing.assert_allclose(got["expert_in"], want["expert_in"], atol=2e-4)
    np.testing.assert_allclose(jax.nn.softmax(got["router_logits"], axis=-1), want["probs"], atol=1e-5)
    loss, stats = jax.jit(model.apply)(params, tokens, labels)
    assert float(loss) == pytest.approx(float(want["loss"]), rel=2e-5)
    assert set(stats) == set(model.device_scalars) and stats["moe_rows_here"].shape == (L,)
    # what landed on held experts is what the reference's choices say; every assignment where
    # all are held or the held ones stand in
    first, count, stand_in = share or (0, 8, True)
    here = np.sum((want["experts"] >= first) & (want["experts"] < first + count) | stand_in, axis=(1, 2, 3))
    assert np.array_equal(stats["moe_rows_here"], here)


def test_the_kinds_differ_where_they_should(highest, toy):
    """A sliding layer with its window lifted, or a full layer given one, or either under the
    other's rotary table, is another model: the reference at fault reads far from the system."""
    keys, model, params = toy
    tokens, labels = batch(rows=1, T=24)
    got = float(jax.jit(lambda p: model.apply(p, tokens, labels)[0])(params))
    table = {kind: ref.rotary_table(keys, kind) for kind in ("sliding_attention", "full_attention")}
    faults = {"no window": {"sliding_attention": {"window": None}},
              "a window on the full layers": {"full_attention": {"window": 8}},
              "the tables swapped": {"sliding_attention": {"table": table["full_attention"]},
                                     "full_attention": {"table": table["sliding_attention"]}},
              "attention_factor dropped": {"full_attention": {"table": ref.rotary_table(
                  keys, "full_attention", scaled=False)}},
              "strided key/value heads": {kind: {"kv_head": "strided"} for kind in table}}
    for name, fault in faults.items():
        at_fault = float(jax.jit(lambda p: ref.forward(p, tokens, labels, keys, AUX_COEF, last=1,
                                                       attention_faults=fault)["loss"])(params))
        assert abs(at_fault - got) > 1e-4 * got, name
    want = float(jax.jit(lambda p: ref.loss(p, tokens, labels, keys, AUX_COEF))(params))
    assert got == pytest.approx(want, rel=2e-5)


def test_the_engine_computes_the_reference_loss_and_every_gradient(highest):
    """Through ``deepspeed_tpu.initialize`` in float32 with plain SGD: the step's loss is the
    reference's, and what one step took off every parameter, over the rate, is its gradient."""
    keys, model, params = build(cut((4, 4, True)))
    tokens, labels = batch(seed=2, T=24)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, labels, keys, AUX_COEF)))(params)
    rate = 0.5
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": False},
        "optimizer": {"type": "SGD", "params": {"lr": rate}}, "steps_per_print": 10 ** 9})
    assert engine.compute_dtype == jnp.float32
    before = jax.device_get(engine.master_params)
    loss = engine(tokens, labels)
    engine.backward(loss)
    engine.step()
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
    after = dict(jax.tree_util.tree_flatten_with_path(jax.device_get(engine.master_params))[0])
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, b in jax.tree_util.tree_flatten_with_path(before)[0]:
        got, w = (np.asarray(b) - np.asarray(after[path])) / rate, np.asarray(flat_want[path])
        # what a step took off a float32 leaf is known to the leaf's own spacing, over the rate
        coarse = np.sqrt(b.size) * np.spacing(np.abs(np.asarray(b)).max()) / rate
        assert np.linalg.norm(got - w) <= 2e-3 * np.linalg.norm(w) + coarse, jax.tree_util.keystr(path)
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    assert set(kept[-1][1]) == set(model.device_scalars)


def test_a_step_in_bfloat16_with_layers_recomputed_moves_every_leaf():
    keys = dict(cut((4, 4, True), layers=2), layer_types=["sliding_attention", "full_attention"])
    _, model, params = build(keys, compute_dtype=jnp.bfloat16, initializer_range=0.02, remat=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 3e-3}}, "steps_per_print": 10 ** 9})
    tokens, _ = batch(seed=4, T=24)
    before = jax.device_get(engine.master_params)
    loss = engine(tokens, np.roll(tokens, -1, 1))
    engine.backward(loss)
    engine.step()
    assert np.isfinite(float(loss)) and engine.skipped_steps == 0
    # Adam's first step moves every leaf (the embedding's rows of tokens the batch lacks apart)
    for (path, b), a in zip(jax.tree_util.tree_flatten_with_path(before)[0],
                            jax.tree_util.tree_leaves(jax.device_get(engine.master_params))):
        assert np.mean(a != b) > (0.2 if "embed" in jax.tree_util.keystr(path) else 0.9), jax.tree_util.keystr(path)


def test_recomputed_layers_give_the_same_loss_and_gradients(toy):
    _, kept, params = toy
    _, again, _ = build(published(num_hidden_layers=4), remat=True)
    tokens, labels = batch(seed=5, rows=2, T=24)
    loss = lambda m: (lambda p, t, l: m.apply(p, t, l)[0])      # noqa: E731
    (l0, g0), (l1, g1) = (jax.jit(jax.value_and_grad(loss(m)))(params, tokens, labels) for m in (kept, again))
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(a).max()) + 1e-7)


def test_the_scopes_the_benchmark_reads_are_in_the_compiled_program():
    """A layer's whole mixer lies under its kind's scope INSIDE ``ds_attn``; the kernels keep
    their names, so a recomputed layer's backward kernel reads
    ``…/rematted_computation/ds_attn/ds_attn_window/ds_flash_bwd_dkv``, and its forward kernel is
    not made again (a layer keeps the kernel's output by name)."""
    _, model, params = build(published(num_hidden_layers=4), remat=True)
    tokens, labels = batch(seed=6, rows=2, T=24)
    grad = jax.grad(lambda p, t, l: model.apply(p, t, l)[0])
    text = jax.jit(grad).lower(params, tokens, labels).compile().as_text()
    for path in (r"ds_attn\)?/ds_attn_window/ds_flash_fwd", r"ds_attn\)?/ds_attn_full/ds_flash_fwd",
                 r"checkpoint/rematted_computation/ds_attn/ds_attn_window/", r"checkpoint/rematted_computation/ds_attn/ds_attn_full/",
                 r"checkpoint/ds_attn/ds_attn_window/ds_flash_bwd_dkv", r"checkpoint/ds_attn/ds_attn_full/ds_flash_bwd_dkv",
                 r"ds_mlp\)?/\S*ds_moe_router", r"ds_mlp\)?/\S*ds_moe_experts", "ds_embed", "ds_loss"):
        assert re.search(path, text), path
    assert not re.search(r"ds_mlp/\S*ds_attn_", text) and not re.search(r"ds_attn_window/\S*ds_attn_full", text)
    assert not re.search(r"rematted_computation/ds_attn/ds_attn_\w+/ds_flash_fwd", text)
    # by the jaxpr: in the layers' backward no forward kernel, one backward kernel a layer
    from test_ouro import kernels_in_the_backward
    assert kernels_in_the_backward(jax.make_jaxpr(grad)(params, tokens, labels).jaxpr) == {"ds_flash_bwd_dkv": 4}
