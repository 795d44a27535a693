"""GLM-4.7-Flash's share tied to the model (the eight plain held ranges of a 64-expert layer, with
the shared expert counted once, add up to the uncut reference's layer), what
``GlmMoeConfig.from_published`` reads of the catalog's row and what it refuses, and the builder's
parameter count."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import flops_mla_moe
from benchmarks.manifest import Manifest
from benchmarks.reference import glm_moe_reference as ref
from deepspeed_tpu.models.glm_moe import GlmMoeConfig, GlmMoeModel
from deepspeed_tpu.parallel.moe import SILU_GATED, DroplessMoE
from glm_toy import published

CONFIG = "glm-4.7-flash-ep8-d5"


@pytest.fixture(scope="module")
def row():
    return Manifest().config(CONFIG)


def test_the_eight_held_ranges_add_up_to_the_uncut_layer():
    """A layer of 64 experts, 4 a token, as the model cuts it: the plain held ranges (0, 8), (8, 8),
    .. (56, 8) (``stand_in=False``: what the absent experts would add is left out) and the shared
    expert ONCE add up to the uncut reference's layer; every assignment lands on exactly one range,
    every range returns the same counts, and each range's part is the reference's for that range."""
    H, F, E, k = 32, 24, 64, 4
    m = {"n_routed_experts": E, "num_experts_per_tok": k, "norm_topk_prob": True, "routed_scaling_factor": 1.8}
    router = ("sigmoid_bias", 1.8)
    whole = DroplessMoE(H, F, E, k, norm_topk_prob=True, router=router, experts=SILU_GATED)
    params = whole.init(jax.random.PRNGKey(0), 0.3)
    params["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (E,))
    shared = {"w_gate_up": jax.random.normal(jax.random.PRNGKey(5), (H, 2 * F)) * 0.3,
              "w_down": jax.random.normal(jax.random.PRNGKey(6), (F, H)) * 0.3}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, H), jnp.float32)
    flat = x.reshape(-1, H)
    with jax.default_matmul_precision("highest"):
        want, chosen, _ = ref.expert_layer(flat, {"moe": params, "shared": shared}, m)
        want_counts = ref.assignments(chosen, E)
        total, rows = ref.gated(flat, shared), 0.0
        for first in range(0, E, 8):
            held = DroplessMoE(H, F, E, k, norm_topk_prob=True, held=(first, 8), router=router, experts=SILU_GATED)
            mine = dict(params, w_gate_up=params["w_gate_up"][first:first + 8],
                        w_down=params["w_down"][first:first + 8])
            part, aux, stats = jax.jit(held.apply)(mine, x)
            theirs = ref.expert_layer(flat, {"moe": mine, "shared": shared},
                                      dict(m, n_routed_experts=8, router_width=E, first_expert=first))[0]
            np.testing.assert_allclose(part.reshape(-1, H) + ref.gated(flat, shared), theirs, atol=3e-5)
            assert float(aux) == 0.0 and np.array_equal(stats["counts"], want_counts)
            total, rows = total + part.reshape(-1, H), rows + float(stats["rows_here"])
    assert rows == 2 * 24 * k                          # every assignment landed somewhere, once
    np.testing.assert_allclose(total, want, atol=1e-4)


@pytest.mark.parametrize("change, names", [
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"n_group": 4, "topk_group": 2}, "n_group"),
    ({"num_nextn_predict_layers": 2}, "num_nextn_predict_layers"),
    ({"attention_bias": True}, "attention_bias"),
    ({"num_key_value_heads": 2}, "key/value heads"),
    ({"tie_word_embeddings": True}, "head"),
    ({"partial_rotary_factor": 0.5}, "rotary")])
def test_from_published_refuses_what_is_not_built(change, names):
    with pytest.raises(AssertionError, match=names):
        GlmMoeConfig.from_published(published(**change))


def test_value_heads_narrower_than_the_keys_are_built_and_match_the_reference():
    """``v_head_dim`` 8 beside keys of 12 + 4 (refused until the flash kernel took two widths, PR 58):
    the toy's latent mixer against the reference's, and the shapes that follow the values' width."""
    keys = published(v_head_dim=8)
    model = GlmMoeModel(GlmMoeConfig.from_published(keys, compute_dtype=jnp.float32, initializer_range=0.1))
    ap = model.init(jax.random.PRNGKey(0))["layers"][0]["attn"]
    assert ap["wkv_b"].shape == (12, 4 * (12 + 8)) and ap["wo"].shape == (4 * 8, 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(jax.jit(model.attention)(x, ap), ref.attention(x, ap, keys), atol=2e-5)


def test_from_published_reads_the_catalogs_row(row):
    c = GlmMoeConfig.from_published(row["model"], remat=True)
    assert (c.hidden_size, c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.intermediate_size, c.moe_intermediate_size,
            c.num_experts_per_tok) == (2048, 20, 768, 512, 192, 64, 256, 10240, 1536, 4)
    assert (c.routed_scaling_factor, c.rope_theta, c.rms_norm_eps, c.first_k_dense_replace) == (1.8, 1000000, 1e-5, 1)
    assert (c.num_hidden_layers, c.n_routed_experts, c.router_width, c.first_expert, c.stand_in) == (5, 8, 64, 0, True)
    assert [c.is_dense(l) for l in range(5)] == [True, False, False, False, False] and c.qk_head_dim == 256
    model = GlmMoeModel(c)
    assert model.moe.held == (0, 8) and model.moe.stand_in and model.moe.num_experts == 64
    assert model.moe.scaling == 1.8 and model.moe.form == SILU_GATED and model.moe.top_k == 4
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert "mlp" in shapes["layers"][0] and all("moe" in lp for lp in shapes["layers"][1:])
    assert shapes["mtp"]["w_eh"].shape == (4096, 2048) and "moe" in shapes["mtp"]["block"]
    # the builder's count, leaf by leaf, is the pricing's and the configuration file's
    count = sum(s.size for s in jax.tree_util.tree_leaves(shapes))
    assert count == flops_mla_moe.param_count(row["model"], row["vocab_size"]) == 706_518_848
    json.dumps(row)
