"""Granite 4.0-H on the normal path against its plain float32 reference
(``benchmarks/reference/granite_hybrid_reference.py``) on seeded weights at a tiny size: the
whole model through ``deepspeed_tpu.initialize`` (loss, logits, the gradient of every leaf,
with whole blocks recomputed and without), what a recomputed block keeps by name, the
gate-then-norm of the Mamba-2 mixer, the attention's published scale and its lack of
positions, the four multipliers, the tied table."""

import collections
import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.reference import granite_hybrid_reference as ref
from deepspeed_tpu.models import granite_hybrid
from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig, GraniteHybridModel
from deepspeed_tpu.models.layers import rope
from test_ouro import kernels_in_the_backward, primitives_by_path, residuals_by_shape

KINDS = ["mamba", "mamba", "attention", "mamba"]


def published(**more):
    keys = dict(vocab_size=96, hidden_size=32, num_hidden_layers=4, layer_types=KINDS,
                shared_intermediate_size=48, intermediate_size=48, num_attention_heads=4,
                num_key_value_heads=2, attention_multiplier=0.0625, mamba_n_heads=8, mamba_d_head=8,
                mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=16, mamba_expand=2, mamba_n_groups=1,
                mamba_conv_bias=True, mamba_proj_bias=False, embedding_multiplier=6.0,
                residual_multiplier=0.5, logits_scaling=4.0, rms_norm_eps=1e-5, hidden_act="silu",
                position_embedding_type="nope", num_local_experts=0, num_experts_per_tok=0,
                tie_word_embeddings=True, attention_bias=False, normalization_function="rmsnorm")
    return dict(keys, **more)


def build(keys=None, **more):
    keys = keys or published()
    more = dict(dict(compute_dtype=jnp.float32, initializer_range=0.1), **more)
    model = GraniteHybridModel(GraniteHybridConfig.from_published(keys, **more))
    params = model.init(jax.random.PRNGKey(3))
    # norm weights, D and the biases off their initial ones, so that a dropped one shows
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
def test_loss_logits_and_every_mixers_input_match_the_reference(highest):
    keys, model, params = build()
    tokens, labels = batch(rows=2)
    want = jax.jit(lambda p: ref.forward(p, tokens, labels, keys, last=16))(params)
    got = jax.jit(lambda p: model.forward_details(p, tokens, labels, 16))(params)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), rel=2e-5)
    np.testing.assert_allclose(got["logits"], want["logits"], atol=2e-4)
    np.testing.assert_allclose(got["mixer_in"], want["mixer_in"], atol=2e-4)
    assert float(jax.jit(model.apply)(params, tokens, labels)) == pytest.approx(float(want["loss"]), rel=2e-5)
    np.testing.assert_allclose(jax.jit(model.apply)(params, tokens)[:, -16:], want["logits"], atol=2e-4)


@pytest.mark.parametrize("remat", [False, True], ids=["blocks-kept", "blocks-recomputed"])
def test_the_engine_computes_the_reference_loss_and_the_gradient_of_every_leaf(remat, highest):
    """Through ``deepspeed_tpu.initialize`` in float32 with plain SGD: the step's loss is the
    reference's, and what one step took off every parameter, over the rate, its gradient."""
    keys, model, params = build(remat=remat)
    tokens, labels = batch(seed=2)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, tokens, labels, keys)))(params)
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
        # a float32 master holds the difference to half a unit in its last place
        ulp = np.spacing(np.abs(np.asarray(b)).max()) / rate * np.sqrt(b.size)
        assert np.linalg.norm(got - w) <= 2e-3 * np.linalg.norm(w) + ulp, jax.tree_util.keystr(path)


def test_recomputed_blocks_give_the_same_loss_and_gradients():
    _, kept, params = build()
    _, again, _ = build(remat=True)
    tokens, labels = batch(seed=5, rows=2)
    (l0, g0), (l1, g1) = (jax.jit(jax.value_and_grad(m.apply))(params, tokens, labels) for m in (kept, again))
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    text = jax.jit(jax.grad(again.apply)).lower(params, tokens, labels).as_text(debug_info=True)
    # JAX names what the backward makes again: the benchmark's reader finds it by that name
    assert "rematted_computation/ds_attn/ds_ssm/ds_ssd_scan" in text and "rematted_computation/ds_mlp" in text
    assert "rematted_computation/ds_attn" not in jax.jit(jax.grad(kept.apply)).lower(
        params, tokens, labels).as_text(debug_info=True)


# ------------------------------------------------------------------ what a recomputed block keeps
MAMBA_BLOCKS, ATTENTION_BLOCKS = KINDS.count("mamba"), KINDS.count("attention")
ONLY_THE_INPUT = "only-the-input-kept"


@contextlib.contextmanager
def keeping(what):
    """The model's kept set, or ``policy=None`` in its place (``ONLY_THE_INPUT``)."""
    with pytest.MonkeyPatch.context() as patch:
        if what == ONLY_THE_INPUT:
            patch.setattr(granite_hybrid, "KEPT_BY_A_BLOCK", None)
        yield


@functools.lru_cache(maxsize=None)
def loss_and_gradients(dtype, what):
    """The loss and every leaf's gradient, with whole blocks recomputed under the kept set or
    under ``policy=None``, or with nothing recomputed (``"blocks-kept"``), compiled so that a
    value is the same bits wherever it is made: no rounding to bfloat16 dropped between two
    operations that happen to be fused (``xla_allow_excess_precision``), and multipliers that
    are powers of two (the CPU backend contracts ``12 e + r m`` into one rounding where both
    are made in one fusion and not where ``12 e`` is read back; the toy's ``r`` is 0.5)."""
    _, model, params = build(published(embedding_multiplier=8.0), remat=what != "blocks-kept",
                             compute_dtype=getattr(jnp, dtype))
    tokens, labels = batch(seed=6, rows=2)
    with keeping(what):
        compiled = jax.jit(jax.value_and_grad(lambda p: model.apply(p, tokens, labels))).lower(params).compile(
            compiler_options={"xla_allow_excess_precision": False})
    return jax.device_get(compiled(params))


@pytest.mark.parametrize("dtype, other", [("float32", ONLY_THE_INPUT), ("bfloat16", ONLY_THE_INPUT),
                                          ("float32", "blocks-kept")])
def test_what_a_block_keeps_changes_no_bit_of_the_loss_or_of_a_gradient(dtype, other):
    """The kept tensors are the values the second forward would have made again, in the dtype
    the forward made them in: the loss and every leaf's gradient are the same bits as under
    ``policy=None``, in float32 and in bfloat16, and as with nothing recomputed."""
    (loss, got), (want_loss, want) = loss_and_gradients(dtype, "the-kept-set"), loss_and_gradients(dtype, other)
    assert float(loss) == float(want_loss) and np.isfinite(float(loss))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), jax.tree_util.keystr(path)
        assert np.any(np.asarray(a, np.float32)), jax.tree_util.keystr(path)


@pytest.mark.parametrize("what", ["the-kept-set", ONLY_THE_INPUT])
def test_the_second_forward_runs_no_flash_kernel_and_three_products_fewer_a_period(what):
    """What the backward makes again, by block: a Mamba-2 block's second forward runs ONE
    product (the MLP's first) where ``policy=None`` runs three (the mixer's two besides), the
    attention block's three (``wq``, ``wkv``, the MLP's first) where it runs four (``wo``
    besides) and no flash forward kernel. The MLP's LAST product is in neither: nothing in a
    block's backward reads its output (PERF.md, PR 41). A product's backward is two products."""
    _, model, params = build(remat=True)
    tokens, labels = batch(seed=5, rows=2)
    with keeping(what):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.apply(p, tokens, labels)))(params).jaxpr
    found, kernels = primitives_by_path(jaxpr), kernels_in_the_backward(jaxpr)
    again = {"the-kept-set": (1, 3, 0), ONLY_THE_INPUT: (3, 4, 1)}[what]       # mamba, attention, flash
    assert found[("remat2",), "dot_general"] == MAMBA_BLOCKS * (again[0] + 2 * 4) + ATTENTION_BLOCKS * (again[1] + 2 * 5)
    assert +kernels == +collections.Counter({
        "ds_flash_fwd": ATTENTION_BLOCKS * again[2], "ds_flash_bwd_dkv": ATTENTION_BLOCKS,
        "ds_ssd_scan_fwd": MAMBA_BLOCKS, "ds_ssd_scan_bwd": MAMBA_BLOCKS})
    # the first forward is the same either way: every kernel and every product once
    assert found[(), "pallas_call"] == MAMBA_BLOCKS + ATTENTION_BLOCKS
    assert found[(), "dot_general"] >= MAMBA_BLOCKS * 4 + ATTENTION_BLOCKS * 5


@functools.lru_cache(maxsize=None)
def kept_by_the_blocks(what):
    """``{shape: count}`` of the activations that the blocks of two sequences keep for their backward."""
    _, model, params = build(remat=True)
    tokens, _ = batch(seed=5, rows=2)
    with keeping(what):
        return residuals_by_shape(lambda p: model._backbone(p, tokens), params)


@pytest.mark.parametrize("shape, count", [
    ((2, 4, 40, 8), ATTENTION_BLOCKS), ((2, 4, 40), ATTENTION_BLOCKS),
    ((2, 40, 2 * 64 + 2 * 16 + 8), MAMBA_BLOCKS), ((2, 40, 8), MAMBA_BLOCKS),
    ((2, 40, 32), 2 * (MAMBA_BLOCKS + ATTENTION_BLOCKS) + 1)],
    ids=["attn_out", "attn_lse", "ssm_in", "ssm_dt", "input-and-mixer_out"])
def test_a_block_keeps_each_named_tensor_once(shape, count):
    """The residuals of the blocks by shape: an attention block keeps ONE kernel output (no
    second ``attn_out`` at the call) and ONE set of row sums, a Mamba-2 block its first
    product's output once in the compute dtype and the ``dt`` columns once, every block its
    input and its mixer's output, and nothing else (the last block's output is ``norm_f``'s to
    keep); under ``policy=None`` the inputs alone."""
    found = kept_by_the_blocks("the-kept-set")
    assert found[shape] == count, found
    assert sum(found.values()) == 4 * MAMBA_BLOCKS + 4 * ATTENTION_BLOCKS + 1, found
    assert kept_by_the_blocks(ONLY_THE_INPUT) == {(2, 40, 32): MAMBA_BLOCKS + ATTENTION_BLOCKS + 1}


def test_the_names_are_nothing_where_no_block_is_recomputed(monkeypatch):
    """With ``remat=False`` (the reference comparison's path) a name lowers to nothing: the
    gradient program is the same text with every name of this file taken out (but for the
    numbers JAX gives its private functions, which count the traces before)."""
    _, model, params = build(compute_dtype=jnp.bfloat16)
    tokens, labels = batch(seed=5, rows=2)
    lowered = lambda: re.sub(r"@(\w+?)_\d+\b", r"@\1", jax.jit(jax.value_and_grad(      # noqa: E731
        lambda p: model.apply(p, tokens, labels))).lower(params).as_text())
    named = lowered()
    monkeypatch.setattr(granite_hybrid, "checkpoint_name", lambda x, name: x)
    assert lowered() == named and "stablehlo.dot_general" in named
    assert not hasattr(model.config, "remat_policy")        # the policy is the model's, no key


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


@pytest.mark.parametrize("wrong", [
    dict(num_local_experts=8), dict(num_experts_per_tok=2), dict(position_embedding_type="rope"),
    dict(layer_types=["mamba", "mamba", "sliding", "mamba"]), dict(layer_types=["mamba"]),
    dict(hidden_act="gelu"), dict(tie_word_embeddings=False), dict(mamba_n_groups=2),
    dict(mamba_conv_bias=False), dict(mamba_expand=4)], ids=lambda w: "-".join(w))
def test_from_published_refuses_what_the_block_cannot_do(wrong):
    GraniteHybridConfig.from_published(published())
    with pytest.raises(AssertionError):
        GraniteHybridConfig.from_published(published(**wrong))


def test_the_period_of_the_published_pattern_survives_the_cut():
    kinds = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    c = GraniteHybridConfig.from_published(published(layer_types=kinds, num_hidden_layers=10))
    assert c.layer_types == tuple(kinds[:10]) and [c.kind(l) for l in (4, 5, 6)] == ["mamba", "attention", "mamba"]
    params = jax.eval_shape(GraniteHybridModel(c).init, jax.random.PRNGKey(0))
    assert ["wq" in lp["mixer"] for lp in params["layers"]] == [k == "attention" for k in kinds[:10]]


# ------------------------------------------------------------------ the mixers
def test_the_mixer_gates_then_norms_over_all_channels(highest):
    keys, model, params = build()
    mp = params["layers"][0]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 40, 32))
    got = jax.jit(model.mamba_mixer)(x, mp)
    want = jax.jit(lambda x, mp: ref.mamba_mixer(x, mp, keys))(x, mp)
    other = jax.jit(lambda x, mp: ref.mamba_mixer(x, mp, keys, gate_first=False))(x, mp)
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)
    assert np.linalg.norm(other - want) > 0.1 * np.linalg.norm(want)       # the two orders differ


def test_the_skip_and_the_convolutions_bias_are_in_the_mixer(highest):
    keys, model, params = build()
    mp = params["layers"][0]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 40, 32))
    want = jax.jit(model.mamba_mixer)(x, mp)
    for leaf in ("D", "conv_b", "dt_bias"):
        without = jax.jit(model.mamba_mixer)(x, dict(mp, **{leaf: jnp.zeros_like(mp[leaf])}))
        assert np.linalg.norm(without - want) > 1e-2 * np.linalg.norm(want), leaf


def test_attention_has_the_published_scale_and_no_positions(highest):
    keys, model, params = build()
    mp = params["layers"][2]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, 32))
    got = jax.jit(model.attention)(x, mp)
    want = ref.attention(x, mp, keys)
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)
    # 1/16 here is not D^-1/2 = 8^-1/2: the default scale is another result
    default = ref.attention(x, mp, dict(keys, attention_multiplier=8 ** -0.5))
    assert np.linalg.norm(default - want) > 1e-2 * np.linalg.norm(want)
    # no positional embedding: a rotary one would change it
    B, T, nq, nkv, D = 2, 40, 4, 2, 8
    q = (x @ mp["wq"]).reshape(B, T, nq, D).transpose(0, 2, 1, 3)
    k, v = jnp.split((x @ mp["wkv"]).reshape(B, T, 2 * nkv, D).transpose(0, 2, 1, 3), 2, axis=1)

    def dense(q, k):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, 1)) * keys["attention_multiplier"]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        y = jnp.einsum("bhqk,bhkd->bqhd", jax.nn.softmax(s, -1), jnp.repeat(v, 2, 1))
        return y.reshape(B, T, nq * D) @ mp["wo"]
    assert np.linalg.norm(dense(q, k) - want) <= 1e-5 * np.linalg.norm(want)
    turned = dense(rope(q, jnp.arange(T), 1e4), rope(k, jnp.arange(T), 1e4))
    assert np.linalg.norm(turned - want) > 1e-2 * np.linalg.norm(want)
    # the earliest token attends to itself alone
    np.testing.assert_allclose(got[:, 0], (jnp.repeat(v, 2, 1)[:, :, 0].reshape(B, nq * D)) @ mp["wo"], atol=1e-5)


@pytest.mark.parametrize("name", ["embedding_multiplier", "residual_multiplier", "logits_scaling",
                                  "attention_multiplier"])
def test_each_multiplier_is_caught_when_dropped(name, highest):
    """The system at the published multipliers is the reference's; the reference with one
    multiplier left at 1 is another model, by the loss or by the last logits."""
    keys, model, params = build()
    tokens, labels = batch(rows=2, seed=6)
    got = jax.jit(lambda p: model.forward_details(p, tokens, labels, 16))(params)
    want = ref.forward(params, tokens, labels, keys, last=16)
    dropped = ref.forward(params, tokens, labels, dict(keys, **{name: 1.0}), last=16)
    size = float(jnp.abs(want["logits"]).max())
    assert float(jnp.abs(got["logits"] - want["logits"]).max()) <= 1e-4 * size
    assert float(jnp.abs(dropped["logits"] - want["logits"]).max()) > 1e-2 * size
    assert GraniteHybridConfig.from_published(keys).__dict__[name] == keys[name] != 1.0


def test_the_tied_tables_gradient_is_the_sum_of_both_uses(highest):
    keys, model, params = build()
    tokens, labels = batch(rows=2, seed=8)
    whole = jax.grad(model.apply)(params, tokens, labels)["embed"]

    def apart(embed, head):
        x = model._backbone(dict(params, embed=embed), tokens)
        return deepspeed_tpu.models.layers.chunked_cross_entropy(x, head, labels)
    as_embedding, as_head = jax.grad(apart, argnums=(0, 1))(params["embed"], params["embed"])
    assert np.linalg.norm(as_embedding) > 0 and np.linalg.norm(as_head) > 0
    np.testing.assert_allclose(whole, as_embedding + as_head, rtol=1e-5, atol=1e-7)
    want = jax.grad(lambda p: ref.loss(p, tokens, labels, keys))(params)["embed"]
    assert np.linalg.norm(whole - want) <= 1e-4 * np.linalg.norm(want)


def test_the_initialisation_is_the_familys():
    c = GraniteHybridConfig.from_published(published())
    mp = GraniteHybridModel(c).init(jax.random.PRNGKey(0))["layers"][0]["mixer"]
    np.testing.assert_allclose(np.exp(mp["A_log"]), np.arange(1, 9), rtol=1e-6)
    step = jax.nn.softplus(mp["dt_bias"])
    assert np.all(step >= 1e-3 * 0.999) and np.all(step <= 1e-1 * 1.001)
    assert np.all(mp["D"] == 1) and np.all(mp["norm"] == 1)
    assert np.abs(mp["conv_w"]).max() <= 0.5 and np.abs(mp["conv_b"]).max() <= 0.5
    assert mp["w_in"].shape == (32, 2 * 64 + 2 * 16 + 8) and mp["conv_w"].shape == (4, 64 + 32)
