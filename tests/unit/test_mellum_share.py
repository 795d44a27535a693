"""Mellum 2's pieces: the share tied to the model (the four plain held ranges of a 64-expert
layer add up to the uncut reference's layer), both rotary tables against float64 numpy at the
published sizes, and what ``MellumConfig.from_published`` refuses."""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.manifest import Manifest
from benchmarks.reference import mellum_reference as ref
from deepspeed_tpu.models.layers import rope, rope_frequencies
from deepspeed_tpu.models.mellum import MellumConfig, MellumModel
from deepspeed_tpu.parallel.moe import DroplessMoE
from mellum_toy import published

CONFIG = "mellum2-12b-a2.5b-ep4-d4"


@pytest.fixture(scope="module")
def row():
    return Manifest().config(CONFIG)["model"]


# ------------------------------------------------------------------ the share
def test_the_four_held_ranges_add_up_to_the_uncut_layer():
    """A layer of 64 experts, 8 a token, as the model cuts it: the plain held ranges (0, 16),
    (16, 16), (32, 16) and (48, 16) (``stand_in=False``: what the absent experts would add is
    left out) add up to the uncut reference's layer, every assignment lands on exactly one of
    them, and each range's part is the reference's for that range."""
    H, F, E, k = 32, 24, 64, 8
    m = {"num_experts": E, "num_experts_per_tok": k, "moe_intermediate_size": F, "norm_topk_prob": True}
    whole = DroplessMoE(H, F, E, k, norm_topk_prob=True)
    params = whole.init(jax.random.PRNGKey(0), 0.3)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, H), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(x.reshape(-1, H), {"moe": params}, m)[0].reshape(x.shape)
        total, rows = jnp.zeros_like(x), 0.0
        for first in range(0, E, 16):
            held = DroplessMoE(H, F, E, k, norm_topk_prob=True, held=(first, 16))
            mine = dict(params, w_gate_up=params["w_gate_up"][first:first + 16],
                        w_down=params["w_down"][first:first + 16])
            part, _, stats = jax.jit(held.apply)(mine, x)
            want_part = ref.expert_layer(x.reshape(-1, H), {"moe": mine}, dict(m, num_experts=16, router_width=E, first_expert=first))[0]
            np.testing.assert_allclose(part.reshape(-1, H), want_part, atol=3e-5)
            total, rows = total + part, rows + float(stats["rows_here"])
    assert rows == 2 * 24 * k                          # every assignment landed somewhere, once
    np.testing.assert_allclose(total, want, atol=1e-4)


# ------------------------------------------------------------------ the rotary tables
def float64_table(D, theta, how):
    i = np.arange(D // 2, dtype=np.float64)
    inv_freq = theta ** (-2 * i / D)
    if how.get("rope_type", "default") == "default":
        return inv_freq, 1.0, None, None
    c = lambda r: D * math.log(how["original_max_position_embeddings"] / (2 * math.pi * r)) / (2 * math.log(theta))   # noqa: E731
    low, high = math.floor(c(how["beta_fast"])), math.ceil(c(how["beta_slow"]))
    ramp = np.clip((i - low) / (high - low), 0, 1)
    return inv_freq * ((1 - ramp) + ramp / how["factor"]), how["attention_factor"], low, high


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_rotary_tables_against_float64_at_the_published_sizes(row, kind):
    how = row["rope_parameters"][kind]
    want, want_factor, low, high = float64_table(128, 500000, how)
    inv_freq, factor = rope_frequencies(128, how["rope_theta"], how)
    assert inv_freq.dtype == np.float32 and np.max(np.abs(inv_freq / want - 1)) < 1e-6
    theirs, their_factor = ref.rotary_table(row, kind)
    assert np.max(np.abs(theirs / want - 1)) < 1e-12 and their_factor == factor == want_factor
    if kind == "full_attention":
        assert (low, high) == (18, 35) and factor == 1.2772588722239782
        assert factor == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
        # unchanged up to pair 18, sixteen times slower from pair 35 on, a ramp between
        plain = float64_table(128, 500000, {})[0]
        np.testing.assert_allclose(inv_freq[:19], plain[:19], rtol=1e-6)
        np.testing.assert_allclose(inv_freq[35:], plain[35:] / 16, rtol=1e-6)
        assert np.all(np.diff(inv_freq / plain) <= 1e-6)
        # the source's default: without the key the factor is 0.1 ln(factor) + 1
        assert rope_frequencies(128, 500000, {k: v for k, v in how.items() if k != "attention_factor"})[1] == \
            pytest.approx(factor, rel=1e-12)
    else:
        assert factor == 1.0 and np.array_equal(inv_freq, rope_frequencies(128, 500000)[0])


def test_rope_takes_the_table_as_a_value_and_lowers_as_before_without_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 12, 16), jnp.float32)
    positions = jnp.arange(12)
    inv_freq, _ = rope_frequencies(16, 10000.0)
    np.testing.assert_allclose(rope(x, positions, None, inv_freq=inv_freq), rope(x, positions, 10000.0), atol=1e-6)
    np.testing.assert_allclose(rope(x, positions, None, inv_freq=inv_freq, factor=1.5),
                               1.5 * rope(x, positions, 10000.0), atol=1e-6)
    # the reference's rotation is the same one
    got = rope(x, positions, None, inv_freq=inv_freq, factor=1.5)
    want = ref.turned(x.transpose(0, 2, 1, 3), (np.asarray(inv_freq, np.float64), 1.5)).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # today's callers pass neither: the same jaxpr as a call that names the defaults
    before = jax.make_jaxpr(lambda x: rope(x, positions, 10000.0))(x)
    after = jax.make_jaxpr(lambda x: rope(x, positions, 10000.0, None, None, 1.0))(x)
    assert str(before) == str(after) and "mul" in str(before)
    partial = rope(x, positions, 10000.0, width=8)
    np.testing.assert_array_equal(partial[..., 8:], x[..., 8:])


# ------------------------------------------------------------------ what is refused
@pytest.mark.parametrize("change, names", [
    ({"mlp_layer_types": ["sparse", "dense"] * 6}, "mlp_layer_types"),
    ({"max_window_layers": 4}, "max_window_layers"),
    ({"rope_parameters": {"full_attention": {"rope_type": "llama3", "rope_theta": 1e4},
                          "sliding_attention": {"rope_type": "default", "rope_theta": 1e4}}}, "rope_type"),
    ({"layer_types": ["sliding_attention", "chunked_attention"] * 6}, "layer_types"),
    ({"layer_types": ["full_attention"] * 3}, "layer_types"),
    ({"attention_bias": True}, "attention_bias")])
def test_from_published_refuses_what_is_not_built(change, names):
    with pytest.raises(AssertionError, match=names):
        MellumConfig.from_published(published(**change))


def test_from_published_reads_the_catalogs_row(row):
    c = MellumConfig.from_published(row, remat=True)
    assert c.kinds == ("sliding_attention",) * 3 + ("full_attention",) and len(c.layer_types) == 28
    assert [c.window_of(kind) for kind in c.kinds] == [1024, 1024, 1024, None]
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.moe_intermediate_size, c.num_experts_per_tok) == (2304, 32, 4, 128, 896, 8)
    assert (c.num_experts, c.router_width, c.first_expert, c.stand_in) == (16, 64, 0, True)
    model = MellumModel(c)
    assert model.moe.held == (0, 16) and model.moe.stand_in and model.moe.num_experts == 64
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(s.size for s in jax.tree_util.tree_leaves(shapes)) == 595_154_176
    # without the window the sliding layers are full ones
    assert MellumConfig.from_published(dict(row, use_sliding_window=False)).window_of("sliding_attention") is None
    json.dumps(row)
