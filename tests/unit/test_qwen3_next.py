"""Qwen3-Next on the normal path against its plain float32 reference
(``benchmarks/reference/qwen3_next_reference.py``) on seeded weights at a tiny size: the
whole model through ``deepspeed_tpu.initialize`` (loss, logits, the gradient of every leaf),
the chunked delta rule against the token-at-a-time recurrence, the held-range expert
layer's shares against the uncut layer, grouped-query flash attention against repeated
keys and values, the rotary width and the zero-centred norm."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.reference import qwen3_next_reference as ref
from deepspeed_tpu.models.layers import rms_norm, rope
from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextModel
from deepspeed_tpu.ops import delta_rule
from deepspeed_tpu.ops.delta_rule import causal_conv, gated_delta_rule
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.parallel.moe import DroplessMoE
from deepspeed_tpu.utils import spans

AUX = 0.001


def published(**more):
    keys = dict(vocab_size=96, hidden_size=32, num_hidden_layers=4, full_attention_interval=4,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25,
                rope_theta=1e7, linear_num_key_heads=2, linear_num_value_heads=4,
                linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel_dim=4,
                num_experts=4, router_width=16, first_expert=4, num_experts_per_tok=4,
                moe_intermediate_size=16, shared_expert_intermediate_size=16, norm_topk_prob=True,
                rms_norm_eps=1e-6, hidden_act="silu", rope_scaling=None, tie_word_embeddings=False)
    return dict(keys, **more)


def build(keys=None, **more):
    keys = keys or published()
    more = dict(dict(compute_dtype=jnp.float32, initializer_range=0.1, router_aux_loss_coef=AUX), **more)
    model = Qwen3NextModel(Qwen3NextConfig.from_published(keys, **more))
    params = model.init(jax.random.PRNGKey(3))
    # norm weights off their initial zeros and ones, so that a wrong centring shows
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p,
        params)
    return keys, model, params


def batch(seed=1, rows=8, T=128):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 96, (rows, T)).astype(np.int32),
            rng.integers(0, 96, (rows, T)).astype(np.int32))


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------------------------------------ the whole model
def test_loss_logits_and_expert_choices_match_the_reference(highest):
    keys, model, params = build()
    tokens, labels = batch(rows=2)
    want = jax.jit(lambda p: ref.forward(p, tokens, labels, keys, AUX, last=16))(params)
    got = jax.jit(lambda p: model.forward_details(p, tokens, labels, 16))(params)
    for name in ("loss", "ce", "aux"):
        assert float(got[name]) == pytest.approx(float(want[name]), rel=2e-5), name
    np.testing.assert_allclose(got["logits"], want["logits"], atol=2e-4)
    assert np.array_equal(got["experts"], want["experts"])
    for name in ("mixer_in", "expert_in"):
        np.testing.assert_allclose(got[name], want[name], atol=2e-4)
    loss, stats = jax.jit(model.apply)(params, tokens, labels)
    assert set(stats) == set(model.device_scalars) == {"moe_load_max_over_mean", "moe_rows_here"}
    assert stats["moe_rows_here"].shape == (4,)
    # the held experts are 4 of 16: what landed here is what the reference's choices say
    here = np.sum((want["experts"] >= 4) & (want["experts"] < 8), axis=(1, 2, 3))
    assert np.array_equal(stats["moe_rows_here"], here)


def test_the_engine_computes_the_reference_loss_and_the_gradient_of_every_leaf(highest):
    """Through ``deepspeed_tpu.initialize`` in float32 with plain SGD: the step's loss is the
    reference's, and what one step took off every parameter, over the rate, its gradient."""
    keys, model, params = build()
    tokens, labels = batch(seed=2)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, tokens, labels, keys, AUX)))(params)
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
    after = jax.device_get(engine.master_params)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, b in jax.tree_util.tree_flatten_with_path(before)[0]:
        a = dict(jax.tree_util.tree_flatten_with_path(after)[0])[path]
        got, w = (np.asarray(b) - np.asarray(a)) / rate, np.asarray(flat_want[path])
        assert np.linalg.norm(got - w) <= 2e-3 * np.linalg.norm(w) + 1e-7, jax.tree_util.keystr(path)
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    assert set(kept[-1][1]) == {"moe_load_max_over_mean", "moe_rows_here"}


def test_it_trains_in_bfloat16_through_initialize():
    # one delta-rule layer and one full-attention layer: the case reads that a step trains, not how
    # deep the model is (the four-layer toy's gradient program is most of this file's compile time)
    _, model, params = build(published(num_hidden_layers=2, full_attention_interval=2),
                             compute_dtype=jnp.bfloat16, initializer_range=0.02)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 3e-3}}, "steps_per_print": 10 ** 9})
    tokens, _ = batch(seed=4, T=64)           # one chunk of the delta rule: the interpreted kernels' time follows T
    losses = []
    for _ in range(4):
        loss = engine(tokens, np.roll(tokens, -1, 1))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_from_published_refuses_what_the_block_cannot_do():
    Qwen3NextConfig.from_published(published())
    for wrong in (dict(hidden_act="gelu"), dict(tie_word_embeddings=True), dict(decoder_sparse_step=2),
                  dict(mlp_only_layers=[0]), dict(rope_scaling={"type": "yarn"})):
        with pytest.raises(AssertionError):
            Qwen3NextConfig.from_published(published(**wrong))
    whole = Qwen3NextModel(Qwen3NextConfig.from_published(published(router_width=None, first_expert=0)))
    assert whole.moe.held is None and whole.moe.num_experts == 4


# ------------------------------------------------------------------ the delta rule
def delta_inputs(T, seed=0, B=2, Hk=2, Hv=4, Dk=16, Dv=8, decay=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    rates = jax.random.uniform(ks[6], (Hv,), minval=0.001, maxval=16.0)
    g = -rates * jax.nn.softplus(jax.random.normal(ks[3], (B, T, Hv)) + 1) * decay
    return ((jax.random.normal(ks[0], (B, T, Hk, Dk)), jax.random.normal(ks[1], (B, T, Hk, Dk)),
             jax.random.normal(ks[2], (B, T, Hv, Dv)), g,
             jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, Hv)))),
            jax.random.normal(ks[5], (B, T, Hv, Dv)))


def recurrence(q, k, v, g, beta):
    r = v.shape[2] // k.shape[2]
    return ref.delta_rule_recurrent(jnp.repeat(ref.unit_scaled(q, True), r, axis=2),
                                    jnp.repeat(ref.unit_scaled(k, False), r, axis=2), v, g, beta)


@functools.lru_cache(maxsize=None)
def value_and_gradients(fn, block_chunks=None):
    """``(*args, cot) -> (fn(*args), the gradients of sum(fn * cot) by q, k, v, g, beta)`` as ONE
    jitted program a function (and a ``BLOCK_CHUNKS``, which a trace reads): the cases that differ
    in their values alone (the decay) share its compile, where an eager call and a ``jax.grad`` of a
    new lambda compiled both afresh in every case."""
    def both(*args_and_cot):
        *args, cot = args_and_cot
        out, pull = jax.vjp(fn, *args)
        return out, pull(cot.astype(out.dtype))
    return jax.jit(both)


@pytest.mark.parametrize("block_chunks", [16, 1], ids=["one-block", "a-chunk-a-block"])
@pytest.mark.parametrize("decay", [0.02, 1.0], ids=["slow-decay", "fast-decay"])
@pytest.mark.parametrize("T", [64, 100, 200, 7], ids=lambda t: f"T{t}")
def test_the_chunked_delta_rule_is_the_recurrence(T, decay, block_chunks, highest, monkeypatch):
    """Forward and backward, lengths the chunk does not divide included, and with the state
    handed from block to block."""
    monkeypatch.setattr(delta_rule, "BLOCK_CHUNKS", block_chunks)
    args, cot = delta_inputs(T, seed=T, decay=decay)
    (got, grads), (want, want_grads) = (value_and_gradients(gated_delta_rule, block_chunks)(*args, cot),
                                        value_and_gradients(recurrence)(*args, cot))
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=3e-6)
    for g, w in zip(grads, want_grads):
        assert float(jnp.linalg.norm(g - w)) <= 5e-5 * float(jnp.linalg.norm(w)) + 1e-9


def test_a_run_of_equal_keys_does_not_blow_the_triangular_system_up(highest):
    """Every token the same key and a step near one: the chunk's system is as far from the
    identity as it gets (a power series of it would reach 1e17), and forward substitution
    still gives the recurrence."""
    (q, k, v, g, beta), cot = delta_inputs(128, decay=0.001)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.full_like(beta, 0.999)
    args = (q, k, v, g, beta)
    (got, grads), (want, want_grads) = (value_and_gradients(gated_delta_rule, delta_rule.BLOCK_CHUNKS)(*args, cot),
                                        value_and_gradients(recurrence)(*args, cot))
    np.testing.assert_allclose(got, want, atol=1e-5)
    for at in (2, 4):                   # the values' and the steps' gradients
        assert float(jnp.linalg.norm(grads[at] - want_grads[at])) <= 1e-4 * float(jnp.linalg.norm(want_grads[at]))


def test_the_delta_rule_keeps_its_inputs_dtype_and_a_float32_state():
    args, _ = delta_inputs(128)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    out = gated_delta_rule(*low)
    assert out.dtype == jnp.bfloat16
    want = recurrence(*(a.astype(jnp.float32) for a in low))
    assert float(jnp.linalg.norm(out.astype(jnp.float32) - want) / jnp.linalg.norm(want)) < 5e-3
    # a state rounded to bfloat16 after every token is twenty times further off
    r = 2
    rounded = ref.delta_rule_recurrent(
        jnp.repeat(ref.unit_scaled(low[0].astype(jnp.float32), True), r, axis=2),
        jnp.repeat(ref.unit_scaled(low[1].astype(jnp.float32), False), r, axis=2),
        low[2].astype(jnp.float32), *low[3:], state_dtype=jnp.bfloat16)
    exact = gated_delta_rule(*(a.astype(jnp.float32) for a in low))
    assert float(jnp.linalg.norm(rounded - want)) > 20 * float(jnp.linalg.norm(exact - want))


@pytest.mark.parametrize("silu", [False, True])
def test_the_causal_convolution_sees_no_later_token(silu):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    want = sum(padded[:, j:j + 12] * w[j] for j in range(4))
    want = jax.nn.silu(want) if silu else want
    np.testing.assert_allclose(causal_conv(x, w, silu), want, atol=1e-6)
    later = x.at[:, 7:].set(0.0)
    np.testing.assert_allclose(causal_conv(later, w, silu)[:, :7], want[:, :7], atol=1e-6)
    grads = jax.grad(lambda x, w: jnp.sum(causal_conv(x, w, silu) ** 2), argnums=(0, 1))(x, w)
    assert all(np.isfinite(g).all() for g in grads)


# ------------------------------------------------------------------ the held range
def expert_params(E=16, H=32, F=16, seed=0):
    layer = DroplessMoE(H, F, E, 4, norm_topk_prob=True)
    return layer, layer.init(jax.random.PRNGKey(seed), 0.5)


def part_of(params, first, count):
    return dict(params, w_gate_up=params["w_gate_up"][first:first + count],
                w_down=params["w_down"][first:first + count])


@pytest.mark.parametrize("tokens", [24, 96], ids=["several-passes", "one-pass"])
def test_the_shares_add_up_to_the_uncut_layer(tokens):
    """16 experts as 4 ranges of 4: the four parts of the routed result, and the shared
    expert counted once, sum to what the uncut reference gives for the whole layer; so do
    the gradients. With 2 x 24 tokens a range receives more rows than one pass holds."""
    whole, params = expert_params()
    shared = {"w_gate_up": jax.random.normal(jax.random.PRNGKey(5), (32, 32)) * 0.3,
              "w_down": jax.random.normal(jax.random.PRNGKey(6), (16, 32)) * 0.3,
              "w_gate": jax.random.normal(jax.random.PRNGKey(7), (32, 1)) * 0.3}
    keys = dict(num_experts=16, num_experts_per_tok=4, moe_intermediate_size=16,
                shared_expert_intermediate_size=16, norm_topk_prob=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, tokens, 32))
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    flat = lambda a: a.reshape(-1, 32)                              # noqa: E731

    def uncut(x, moe):
        return ref.expert_layer(flat(x), {"moe": moe, "shared": shared}, keys)[0].reshape(x.shape)

    def shared_alone(x):
        nothing = {"moe": part_of(params, 0, 1), "shared": shared}
        zero = dict(keys, num_experts=1, router_width=16)
        routed = jax.tree_util.tree_map(jnp.zeros_like, nothing["moe"])
        return ref.expert_layer(flat(x), dict(nothing, moe=dict(routed, router_w=params["router_w"])),
                                zero)[0].reshape(x.shape)

    want = uncut(x, params)
    want_dx, want_dp = jax.grad(lambda x, p: jnp.sum(uncut(x, p) * cot), argnums=(0, 1))(x, params)
    total, rows, dx = shared_alone(x), 0.0, jax.grad(lambda x: jnp.sum(shared_alone(x) * cot))(x)
    d_router, whole_aux, shared_part = 0.0, float(whole.apply(params, x)[1]), flat(shared_alone(x))
    for first in range(0, 16, 4):
        part = DroplessMoE(32, 16, 16, 4, norm_topk_prob=True, held=(first, 4))
        mine = part_of(params, first, 4)
        # one forward and its pull-back a range (an ``apply`` and a ``grad`` were two forwards)
        y, pull, (aux, stats) = jax.vjp(lambda x, p: (lambda out: (out[0], out[1:]))(part.apply(p, x)),
                                        x, mine, has_aux=True)
        g_x, g_p = pull(cot)
        total, rows, dx = total + y, rows + float(stats["rows_here"]), dx + g_x
        d_router = d_router + g_p["router_w"]
        for name in ("w_gate_up", "w_down"):
            np.testing.assert_allclose(g_p[name], want_dp[name][first:first + 4], atol=2e-4)
        # what the held range gives is what the reference's held form gives
        held = ref.expert_layer(flat(x), {"moe": mine, "shared": shared},
                                dict(keys, num_experts=4, router_width=16), held=(first, 4))[0]
        np.testing.assert_allclose(flat(y) + shared_part, held, atol=2e-4)
        assert float(aux) == pytest.approx(whole_aux, rel=1e-6)
    assert rows == 2 * tokens * 4                      # every assignment landed somewhere, once
    np.testing.assert_allclose(total, want, atol=3e-4)
    np.testing.assert_allclose(dx, want_dx, atol=3e-4)
    np.testing.assert_allclose(d_router, want_dp["router_w"], atol=3e-4)


def test_the_whole_range_held_is_todays_layer_bit_for_bit():
    whole, params = expert_params()
    same = DroplessMoE(32, 16, 16, 4, norm_topk_prob=True, held=(0, 16))
    assert same.held is None
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))

    def both(layer):
        (y, aux, stats), grads = layer.apply(params, x), jax.grad(
            lambda p, x: jnp.sum(layer.apply(p, x)[0] ** 2), argnums=(0, 1))(params, x)
        return jax.tree_util.tree_leaves((y, aux, stats, grads))

    for a, b in zip(both(whole), both(same)):
        assert np.array_equal(a, b)
    text = [jax.jit(lambda p, x: layer.apply(p, x)).lower(params, x).as_text() for layer in (whole, same)]
    assert text[0] == text[1]
    with pytest.raises(AssertionError):
        DroplessMoE(32, 16, 16, 4, held=(8, 12))


def test_an_absent_expert_costs_no_row():
    """A router that sends every token to experts 0..3: the range 4..7 receives nothing,
    computes nothing and returns zero with zero gradients; the range 0..3 all of it."""
    _, params = expert_params()
    bias = jnp.where(jnp.arange(16) < 4, 50.0, 0.0)
    params["router_w"] = jnp.zeros((32, 16)).at[0].set(bias)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32)).at[..., 0].set(1.0)
    away = DroplessMoE(32, 16, 16, 4, norm_topk_prob=True, held=(4, 4))
    y, _, stats = away.apply(part_of(params, 4, 4), x)
    assert float(stats["rows_here"]) == 0 and not np.any(y)
    assert float(stats["load_max_over_mean"]) == pytest.approx(4.0)
    here = DroplessMoE(32, 16, 16, 4, norm_topk_prob=True, held=(0, 4))
    y, _, stats = here.apply(part_of(params, 0, 4), x)
    assert float(stats["rows_here"]) == 2 * 24 * 4            # four passes of 48 rows
    want = DroplessMoE(32, 16, 16, 4, norm_topk_prob=True).apply(params, x)[0]
    np.testing.assert_allclose(y, want, atol=1e-4)


# ------------------------------------------------------------------ attention's pieces
@pytest.mark.parametrize("heads, kv_heads", [(8, 2), (4, 1), (4, 4)], ids=["8q-2kv", "4q-1kv", "4q-4kv"])
def test_grouped_query_flash_attention_is_a_repeated_key_value_call(heads, kv_heads):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, heads, 256, 32))
    k, v = (jax.random.normal(key, (2, kv_heads, 256, 32)) for key in ks[1:3])
    cot = jax.random.normal(ks[3], q.shape)
    repeat = lambda a: jnp.repeat(a, heads // kv_heads, axis=1)          # noqa: E731

    def grouped(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, block_q=128, block_k=128) * cot)

    def repeated(q, k, v):
        return jnp.sum(flash_attention(q, repeat(k), repeat(v), True, block_q=128, block_k=128) * cot)

    got = jax.value_and_grad(grouped, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(repeated, argnums=(0, 1, 2))(q, k, v)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-5)


def test_rope_turns_only_the_rotary_width_and_the_old_call_is_unchanged():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 16))
    positions = jnp.arange(8)
    part = rope(x, positions, 1e7, width=4)
    assert np.array_equal(part[..., 4:], x[..., 4:])
    assert np.array_equal(part[..., :4], rope(x[..., :4], positions, 1e7))
    assert np.array_equal(rope(x, positions, 1e4, width=16), rope(x, positions, 1e4))
    want = ref._rope(x.transpose(0, 2, 1, 3), 1e7, 4).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(part, want, atol=1e-6)
    old = jax.jit(lambda x: rope(x, positions, 1e4)).lower(x).as_text()
    assert "concatenate" in old and old.count("concatenate") == 1


def test_the_zero_centred_norm_is_one_plus_the_weight():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8,)) * 0.1
    assert np.array_equal(rms_norm(x, w, 1e-6, zero_centred=True), rms_norm(x, 1.0 + w, 1e-6))
    np.testing.assert_allclose(rms_norm(x, w, 1e-6, zero_centred=True), ref._norm(x, w, 1e-6), atol=1e-6)
    assert np.array_equal(rms_norm(x, jnp.zeros(8), 1e-6, zero_centred=True), rms_norm(x, jnp.ones(8), 1e-6))
