"""Ouro on the normal path against its plain float32 reference
(``benchmarks/reference/ouro_reference.py``) on seeded weights at a tiny size: the whole model
through ``deepspeed_tpu.initialize`` (loss, every exit's logits, the exit distribution, the
gradient of every leaf, with whole blocks recomputed and without), a shared leaf's gradient as
the sum over untied copies, one pass as a plain decoder, the exit distribution, the head a
position, the scopes the benchmark's readers find a pass by, and what a recomputed block pass
keeps beside its input."""

import collections
import contextlib
import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.reference import ouro_reference as ref
from deepspeed_tpu.models import layers, ouro
from deepspeed_tpu.models.ouro import OuroConfig, OuroModel, exit_distribution
from deepspeed_tpu.utils import spans

BETA = 0.05


def published(**more):
    keys = dict(vocab_size=96, hidden_size=32, intermediate_size=48, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4, head_dim=8, total_ut_steps=4,
                early_exit_threshold=1, rms_norm_eps=1e-6, rope_theta=1000000, rope_scaling=None,
                hidden_act="silu", tie_word_embeddings=False, use_sliding_window=False,
                sliding_window=None, layer_types=["full_attention"] * 4, model_type="ouro")
    return dict(keys, **more)


def build(keys=None, **more):
    keys = keys or published()
    more = dict(dict(compute_dtype=jnp.float32, initializer_range=0.1, exit_entropy_coef=BETA), **more)
    model = OuroModel(OuroConfig.from_published(keys, **more))
    params = model.init(jax.random.PRNGKey(3))
    # norm weights and the gate's bias off their initial ones, so that a dropped one shows
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p,
        params)
    return keys, model, params


def batch(seed=1, rows=8, T=40):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 96, (rows, T)).astype(np.int32),
            rng.integers(0, 96, (rows, T)).astype(np.int32))


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------------------------------------ the whole model
def test_loss_exits_and_the_exit_distribution_match_the_reference(highest):
    keys, model, params = build()
    tokens, labels = batch(rows=2)
    labels[0, 5:9] = -100
    want = jax.jit(lambda p: ref.forward(p, tokens, labels, keys, BETA, last=16))(params)
    got = jax.jit(lambda p: model.forward_details(p, tokens, labels, 16))(params)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), rel=2e-5)
    np.testing.assert_allclose(got["exit_ce"], want["exit_ce"], rtol=2e-5)
    np.testing.assert_allclose(got["logits"], want["logits"], atol=2e-4)         # EVERY exit's
    np.testing.assert_allclose(got["p"], want["p"], atol=1e-6)
    np.testing.assert_allclose(got["entropy"], want["entropy"], atol=1e-5)
    loss, stats = jax.jit(model.apply)(params, tokens, labels)
    assert float(loss) == pytest.approx(float(want["loss"]), rel=2e-5)
    assert set(stats) == set(model.device_scalars) == {"exit_mass", "exit_ce", "exit_entropy"}
    assert stats["exit_mass"].shape == stats["exit_ce"].shape == (4,) and stats["exit_entropy"].shape == ()
    assert float(jnp.sum(stats["exit_mass"])) == pytest.approx(1.0, abs=1e-6)
    np.testing.assert_allclose(stats["exit_ce"], want["exit_ce"], rtol=2e-5)
    # without labels: the LAST pass's logits (early_exit_threshold 1 takes no exit early)
    np.testing.assert_allclose(jax.jit(model.apply)(params, tokens)[:, -16:], want["logits"][-1], atol=2e-4)


@pytest.mark.parametrize("remat", [False, True], ids=["blocks-kept", "blocks-recomputed"])
def test_the_engine_computes_the_reference_loss_and_the_gradient_of_every_leaf(remat, highest):
    """Through ``deepspeed_tpu.initialize`` in float32 with plain SGD: the step's loss is the
    reference's, and what one step took off every parameter, over the rate, its gradient; the
    leaves of a layer appear once however many passes use them."""
    keys, model, params = build(remat=remat)
    tokens, labels = batch(seed=2)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, tokens, labels, keys, BETA)))(params)
    rate = 0.5
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": False},
        "optimizer": {"type": "SGD", "params": {"lr": rate}}, "steps_per_print": 10 ** 9})
    assert engine.compute_dtype == jnp.float32
    assert len(engine.master_params["layers"]) == 2 and len(jax.tree_util.tree_leaves(engine.master_params)) == 27
    before = jax.device_get(engine.master_params)
    loss = engine(tokens, labels)
    engine.backward(loss)
    engine.step()
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
    after = dict(jax.tree_util.tree_flatten_with_path(jax.device_get(engine.master_params))[0])
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, b in jax.tree_util.tree_flatten_with_path(before)[0]:
        got, w = (np.asarray(b) - np.asarray(after[path])) / rate, np.asarray(flat_want[path])
        # a float32 master holds the difference to half a unit in its last place
        ulp = np.spacing(np.abs(np.asarray(b)).max()) / rate * np.sqrt(b.size)
        assert np.linalg.norm(w) > 0, jax.tree_util.keystr(path)
        assert np.linalg.norm(got - w) <= 2e-3 * np.linalg.norm(w) + ulp, jax.tree_util.keystr(path)
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    assert len(kept) == 1 and float(np.sum(kept[0][1]["exit_mass"])) == pytest.approx(1.0, abs=1e-6)


def test_a_shared_leafs_gradient_is_the_sum_over_untied_copies_of_the_layers(highest):
    """The reference with ``total_ut_steps`` x ``num_hidden_layers`` untied copies of the
    layers, a copy a pass: every copy has a gradient of its own, and the system's gradient by
    a layer's leaf is their sum over the passes."""
    keys, model, params = build()
    tokens, labels = batch(rows=2, seed=8)
    got = jax.jit(jax.grad(lambda p: model.apply(p, tokens, labels)[0]))(params)["layers"]
    copies = [[dict(lp) for lp in params["layers"]] for _ in range(4)]
    by_copy = jax.jit(jax.grad(lambda u: ref.loss(params, tokens, labels, keys, BETA, untied=u)))(copies)
    assert len(by_copy) == 4 and len(by_copy[0]) == 2
    for l in range(2):
        for name, g in got[l].items():
            parts = [np.asarray(by_copy[t][l][name]) for t in range(4)]
            assert all(np.linalg.norm(part) > 0 for part in parts), (l, name)
            assert np.linalg.norm(parts[0] - parts[3]) > 1e-3 * np.linalg.norm(parts[0]), (l, name)
            assert np.linalg.norm(g - sum(parts)) <= 1e-4 * np.linalg.norm(sum(parts)), (l, name)
    # ONE compiled program for the reference's sum and for the passes' parts it is the sum of (eagerly
    # the gradient of four untied passes is some hundred programs, and it was made twice)
    of = (params, tokens, labels, keys, BETA, 0, ("wq", "w_down"))
    shared, parts = jax.jit(lambda: (ref.shared_gradient(*of), ref.shared_gradient_by_pass(*of)))()
    for name in ("wq", "w_down"):
        np.testing.assert_allclose(shared[name], sum(np.asarray(by_copy[t][0][name]) for t in range(4)),
                                   rtol=1e-4, atol=1e-6)
    # the head's gradients written out are jax.grad's
    x, head = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 32)), params["head"]
    cot = jax.random.normal(jax.random.PRNGKey(6), (2, 40))
    want = jax.grad(lambda x, h: jnp.sum(ref.cross_entropy(x, h, labels)[0] * cot), argnums=(0, 1))(x, head)
    for g, w in zip(ref.head_gradients(x, head, labels, cot), want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)
    # added in bfloat16 a pass at a time, the sum is another number
    # (summed outside the program, where no compiler may keep a bfloat16 sum in float32)
    rounded = ref.sum_over_passes([{"wq": part["wq"]} for part in parts], sum_dtype=jnp.bfloat16)
    assert 1e-4 < np.linalg.norm(rounded["wq"] - shared["wq"]) / np.linalg.norm(shared["wq"]) < 2e-2


def test_one_pass_with_the_gates_term_dropped_is_a_plain_decoder(highest):
    """``total_ut_steps`` 1: the one exit has all the mass, the entropy is zero, and the loss is
    the mean cross-entropy of embedding -> blocks -> norm_f -> head."""
    keys, model, params = build(published(total_ut_steps=1))
    tokens, labels = batch(rows=2, seed=4)
    loss, stats = jax.jit(model.apply)(params, tokens, labels)
    x = params["embed"][tokens]
    for lp in params["layers"]:
        x = ref.block(x, lp, keys)
    x = ref._norm(x, params["norm_f"], keys["rms_norm_eps"])
    plain = layers.chunked_cross_entropy(x, params["head"], labels)
    assert float(loss) == pytest.approx(float(plain), rel=2e-5)
    assert float(stats["exit_entropy"]) == 0.0 and stats["exit_mass"].tolist() == [1.0]
    grads = jax.jit(jax.grad(lambda p: model.apply(p, tokens, labels)[0]))(params)
    assert not np.asarray(grads["gate"]["w"]).any() and not np.asarray(grads["gate"]["b"]).any()
    # four passes on the same leaves are another model
    _, looped, _ = build()
    assert abs(float(looped.apply(params, tokens, labels)[0]) - float(loss)) > 1e-3


def test_recomputed_blocks_give_the_same_loss_gradients_and_the_passes_scopes():
    _, kept, params = build()
    _, again, _ = build(remat=True)
    tokens, labels = batch(seed=5, rows=2)
    loss_of = lambda m: (lambda p, t, l: m.apply(p, t, l)[0])                      # noqa: E731
    (l0, g0), (l1, g1) = (jax.jit(jax.value_and_grad(loss_of(m)))(params, tokens, labels) for m in (kept, again))
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    text = jax.jit(jax.grad(loss_of(again))).lower(params, tokens, labels).as_text(debug_info=True)
    # the passes' scope lies OUTSIDE ds_attn / ds_mlp, inside the one loop over the passes, and JAX
    # names what the backward makes again: the benchmark's readers (benchmarks/loop_spans.py) find the
    # passes' blocks and their second forward by these, and a pass by the turn of its loop
    assert "ds_loop)/ds_attn" in text or "ds_loop/ds_attn" in text
    assert "rematted_computation/ds_loop/ds_attn" in text and "rematted_computation/ds_loop/ds_mlp" in text
    assert "ds_loss)/ds_exit" in text and "ds_loop/ds_loss" not in text and "ds_loop/ds_exit" not in text
    # ONE loop body holds the layers' blocks: two flash calls a direction, not eight
    assert text.count("stablehlo.while") >= 2
    assert "rematted_computation/ds_loop" not in jax.jit(jax.grad(loss_of(kept))).lower(
        params, tokens, labels).as_text(debug_info=True)


# ------------------------------------------------------------------ what a recomputed block pass keeps
@pytest.mark.parametrize("other", ["blocks-kept", "only-the-input-kept"])
def test_what_a_block_pass_keeps_changes_no_bit_of_a_gradient(other, monkeypatch):
    """The kept tensors are the values the second forward would have made again: in float32
    on the CPU every leaf's gradient is the same bits with nothing recomputed and with
    everything but a block's input recomputed."""
    _, model, params = build(remat=True)
    tokens, labels = batch(seed=6, rows=2)
    # under the CPU's MLIR fusion emitters, which ``tests/conftest.py`` turns off for the suite's
    # compile seconds: under the elemental emitters the two programs' gradients of the shared
    # table are an ulp apart (``xla_cpu_use_fusion_emitters=false`` fuses differently)
    grad_of = lambda m: jax.jit(jax.value_and_grad(lambda p: m.apply(p, tokens, labels)[0])).lower(   # noqa: E731
        params).compile(compiler_options={"xla_cpu_use_fusion_emitters": True})(params)
    loss, got = grad_of(model)
    if other == "blocks-kept":
        want_loss, want = grad_of(build(remat=False)[1])
    else:
        monkeypatch.setattr(ouro, "KEPT_BY_A_BLOCK_PASS", None)
        want_loss, want = grad_of(model)
    assert float(loss) == float(want_loss)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), jax.tree_util.keystr(path)


def equations_by_path(jaxpr, path=()):
    """``(enclosing primitives, equation)`` over a jaxpr and what its equations hold."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations_by_path(inner, path + (eqn.primitive.name,))


def primitives_by_path(jaxpr):
    """``{(enclosing primitives, primitive): count}`` over a jaxpr and what its equations hold."""
    return collections.Counter((path, eqn.primitive.name) for path, eqn in equations_by_path(jaxpr))


def kernels_in_the_backward(jaxpr):
    """``{kernel's name: count}`` over the Pallas calls inside a gradient's top-level
    ``remat2`` equations: the recomputed layers' second forward and their backward proper."""
    return collections.Counter(
        eqn.params["name"] for outer in jaxpr.eqns if outer.primitive.name == "remat2"
        for eqn in outer.params["jaxpr"].eqns if eqn.primitive.name == "pallas_call")


def residuals_by_shape(function, params, *rest, rows=()):
    """``{shape: count}`` of the activations of two sequences that ``function(params, *rest)``
    keeps for its backward: no leaf, no constant, not what ``norm_f`` keeps of its own. ``rows``:
    leading sizes that count beside the two sequences' (an expert layer's flat rows)."""
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        jax.ad_checkpoint.print_saved_residuals(lambda params, *rest: function(params, *rest), params, *rest)
    shapes = (re.match(r"\w+\[([\d,]+)\] (?!from the argument params|from a constant)", line)
              for line in printed.getvalue().splitlines() if "(rms_norm)" not in line)
    return collections.Counter(s for s in (tuple(map(int, m.group(1).split(","))) for m in shapes if m)
                               if (s[0] == 2 and len(s) > 2 or s[0] in rows and len(s) > 1) and s[-1] > 1)


def test_the_second_forward_runs_no_flash_kernel_and_one_product_fewer_a_layer(monkeypatch):
    """What the backward makes again of a block: six products where policy None makes
    seven (``w_down``'s is kept), and no flash forward kernel; the two scopes the benchmark's
    readers find the second forward by are still there."""
    _, model, params = build(remat=True)
    tokens, labels = batch(seed=5, rows=2)

    def read():          # a new function each time: a trace is cached by its function, not by what the model keeps
        grad = jax.grad(lambda p: model.apply(p, tokens, labels)[0])
        found = primitives_by_path(jax.make_jaxpr(grad)(params).jaxpr)
        # in the blocks' backward: the second forward and the backward proper
        again = {what: sum(n for (path, name), n in found.items() if name == what and path == ("scan", "remat2"))
                 for what in ("dot_general", "pallas_call")}
        compiled = jax.jit(grad).lower(params).compile().as_text()
        return again, compiled, [line for line in compiled.splitlines() if "rematted_computation" in line]
    layers_ = len(params["layers"])
    again, compiled, remade = read()
    assert again == {"dot_general": layers_ * (6 + 14), "pallas_call": layers_}        # the backward kernel alone
    assert not any("ds_flash_fwd" in line for line in remade) and "ds_flash_fwd" in compiled
    assert any("rematted_computation/ds_loop/ds_attn" in line for line in remade)
    assert any("rematted_computation/ds_loop/ds_mlp" in line for line in remade)
    monkeypatch.setattr(ouro, "KEPT_BY_A_BLOCK_PASS", None)
    again, _, remade = read()
    assert again == {"dot_general": layers_ * (7 + 14), "pallas_call": 2 * layers_}
    assert any("ds_flash_fwd" in line for line in remade)


@pytest.mark.parametrize("shape, a_layer, more", [((2, 4, 40, 8), 1, 0), ((2, 4, 40), 1, 0), ((2, 40, 32), 2, 1)],
                         ids=["attn_out", "attn_lse", "input-and-mlp_out"])
def test_a_block_pass_keeps_each_named_tensor_once(shape, a_layer, more, monkeypatch):
    """The residuals of one pass through two recomputed blocks, by shape: a layer keeps ONE
    kernel output, ONE set of row sums, its input and ``w_down``'s output, none of them
    twice; the last block's output is ``norm_f``'s to keep (``more``)."""
    _, model, params = build(remat=True)

    kept = lambda: residuals_by_shape(model.one_pass, params, jnp.ones((2, 40, 32)))      # noqa: E731
    layers_ = len(params["layers"])
    found = kept()
    assert found[shape] == a_layer * layers_ + more and sum(found.values()) == 4 * layers_ + 1, found
    monkeypatch.setattr(ouro, "KEPT_BY_A_BLOCK_PASS", None)
    assert kept() == {(2, 40, 32): layers_ + 1}


def test_it_trains_in_bfloat16_through_initialize_with_blocks_recomputed():
    _, model, params = build(compute_dtype=jnp.bfloat16, initializer_range=0.02, remat=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 3e-3}}, "steps_per_print": 10 ** 9})
    tokens, _ = batch(seed=4)
    losses = []
    for _ in range(4):
        loss = engine(tokens, np.roll(tokens, -1, 1))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    assert len(kept) == 4 and all(abs(float(np.sum(s["exit_mass"])) - 1.0) <= 1e-5 for _, s in kept)


@pytest.mark.parametrize("wrong", [
    dict(tie_word_embeddings=True), dict(num_key_value_heads=2), dict(hidden_act="gelu"),
    dict(rope_scaling={"type": "yarn"}), dict(use_sliding_window=True), dict(sliding_window=128),
    dict(early_exit_threshold=0.5), dict(layer_types=["full_attention", "sliding_attention"])],
    ids=lambda w: "-".join(w))
def test_from_published_refuses_what_the_block_cannot_do(wrong):
    OuroConfig.from_published(published())
    with pytest.raises(AssertionError):
        OuroConfig.from_published(published(**wrong))


# ------------------------------------------------------------------ the exit distribution
def test_the_exit_distribution_sums_to_one_and_its_last_entry_takes_the_remainder():
    g = jnp.asarray(np.random.default_rng(0).normal(size=(3, 5, 7)) * 3, jnp.float32)
    log_p, p = exit_distribution(g)
    lam = np.asarray(jax.nn.sigmoid(g), np.float64)
    assert p.shape == (4, 5, 7)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(p[3], np.prod(1 - lam, axis=0), rtol=1e-4, atol=1e-7)       # what is left
    np.testing.assert_allclose(p, ref.exit_distribution(jnp.asarray(lam, jnp.float32)), atol=1e-6)
    np.testing.assert_allclose(np.exp(log_p), p, rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(p, np.float64).sum(axis=0) - 1).max() <= 2e-7      # the last entry IS the remainder
    # a gate that is sure: no mass is lost and no logarithm is of zero
    log_p, p = exit_distribution(jnp.asarray([[60.0], [-60.0], [0.0]]))
    assert np.isfinite(log_p).all() and float(p.sum()) == pytest.approx(1.0) and float(p[0, 0]) == pytest.approx(1.0)
    # one pass: all of it
    log_p, p = exit_distribution(jnp.zeros((0, 2)))
    assert p.tolist() == [[1.0, 1.0]] and log_p.tolist() == [[0.0, 0.0]]


# ------------------------------------------------------------------ the head a position
def plain_losses(x, head, labels):
    logits = jnp.einsum("bth,vh->btv", x.astype(jnp.float32), head.astype(jnp.float32), precision="highest")
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.where(labels >= 0, -gold, 0.0)


def head_inputs(dtype, t=48, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(4, t, 32)), dtype)
    head = jnp.asarray(rng.normal(size=(160, 32)) * 0.3, dtype)
    labels = rng.integers(0, 160, (4, t))
    labels[:, -1] = -100
    labels[1, 3:11] = -100
    return x, head, jnp.asarray(labels, jnp.int32), jnp.asarray(rng.normal(size=(4, t)), jnp.float32)


@pytest.mark.parametrize("dtype, t, positions", [(jnp.float32, 48, 16), (jnp.float32, 47, 16), (jnp.float32, 48, 48),
                                                 (jnp.bfloat16, 48, 16)], ids=["f32", "f32-T-prime", "f32-one-tile", "bf16"])
def test_the_head_a_position_matches_full_logits_under_a_cotangent_a_position(dtype, t, positions, monkeypatch):
    monkeypatch.setattr(layers, "LOGITS_TILE_BYTES", 4 * positions * 160 * 4)
    x, head, labels, cot = head_inputs(dtype, t)

    def grads_of(fn, *a):
        return jax.jit(jax.value_and_grad(lambda x, h: jnp.sum(fn(x, h, labels) * cot), argnums=(0, 1), has_aux=False))(*a)

    got_l = jax.jit(layers.chunked_cross_entropy_a_position)(x, head, labels)
    want_l = plain_losses(x, head, labels)
    assert got_l.shape == (4, t) and got_l.dtype == jnp.float32
    assert not np.asarray(got_l)[np.asarray(labels) < 0].any()           # 0 where the label is negative
    _, (dx, dh) = grads_of(layers.chunked_cross_entropy_a_position, x, head)
    _, (wx, wh) = grads_of(plain_losses, x.astype(jnp.float32), head.astype(jnp.float32))
    assert dx.dtype == x.dtype and dh.dtype == head.dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    rel = lambda a, b: np.linalg.norm(np.asarray(a, np.float32) - np.asarray(b)) / np.linalg.norm(np.asarray(b))   # noqa: E731
    assert rel(got_l, want_l) <= (1e-6 if dtype == jnp.float32 else 2e-3)
    assert rel(dx, wx) <= tol and rel(dh, wh) <= tol
    assert not np.asarray(dx, np.float32)[np.asarray(labels) < 0].any()  # an ignored position: no gradient
    # the mean over the valid positions is the older entry point's loss, and a uniform
    # cotangent gives its gradients
    count = float(jnp.sum(labels >= 0))
    assert float(jnp.sum(got_l)) / count == pytest.approx(float(layers.chunked_cross_entropy(x, head, labels)), rel=1e-5)


def parents_tiles(x, head, labels, keep):
    """``layers._cross_entropy_tiles`` as it stood before the head learnt to return a loss a
    position (PR 36), verbatim: the yardstick of 'unchanged bit for bit'."""
    V = head.shape[0]
    shards, positions, tiles = layers._tiling(*labels.shape, V)
    xs = layers._in_tiles(x, shards, positions, tiles)
    ls = layers._in_tiles(labels, shards, positions, tiles, fill=-1)
    w = head.astype(x.dtype)

    def tile(total, xc_lc):
        xc, lc = xc_lc
        logits = jnp.einsum("srch,vh->srcv", xc, w, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = lc >= 0
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        total = total + jnp.sum(jnp.where(valid, lse - gold, 0.0), axis=(1, 2))
        if not keep:
            return total, None
        onehot = jnp.arange(V, dtype=lc.dtype) == lc[..., None]
        g = jnp.where(valid[..., None], jnp.exp(logits - lse[..., None]) - onehot, 0.0)
        return total, g.astype(w.dtype)

    total, gs = jax.lax.scan(tile, jnp.zeros((shards,), jnp.float32), (xs, ls))
    return jnp.sum(total), gs


@pytest.mark.parametrize("dtype, t", [(jnp.float32, 48), (jnp.float32, 47), (jnp.bfloat16, 48), (jnp.float16, 48)],
                         ids=["f32", "f32-T-prime", "bf16", "f16"])
def test_the_older_entry_point_is_unchanged_bit_for_bit(dtype, t, monkeypatch):
    monkeypatch.setattr(layers, "LOGITS_TILE_BYTES", 4 * 16 * 160 * 4)
    x, head, labels, _ = head_inputs(dtype, t, seed=3)
    for keep in (False, True):
        got = jax.jit(lambda *a: layers._cross_entropy_tiles(*a, keep))(x, head, labels)
        want = jax.jit(lambda *a: parents_tiles(*a, keep))(x, head, labels)
        assert np.asarray(got[0]).tobytes() == np.asarray(want[0]).tobytes()
        assert (got[1] is None) == (want[1] is None) == (not keep)
        if keep:
            assert np.asarray(got[1]).tobytes() == np.asarray(want[1]).tobytes()
    # and the two entry points' kept gradients are the same array
    _, kept = jax.jit(lambda *a: layers._cross_entropy_tiles(*a, True, a_position=True))(x, head, labels)
    assert np.asarray(kept).tobytes() == np.asarray(want[1]).tobytes()
