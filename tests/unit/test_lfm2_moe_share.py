"""LFM2-24B-A2B's share tied to the model (the eight plain held ranges of a 64-expert layer add up
to the uncut reference's layer), what ``Lfm2MoeConfig.from_published`` reads of the catalog's row
and what it refuses, the builder's parameter count, and the renormalisation's epsilon carried with
the router."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import flops_conv_moe
from benchmarks.manifest import Manifest
from benchmarks.reference import lfm2_moe_reference as ref
from deepspeed_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel
from deepspeed_tpu.parallel.moe import SILU_GATED, DroplessMoE
from lfm2_toy import EPS, published

CONFIG = "lfm2-24b-a2b-ep8-d7"


@pytest.fixture(scope="module")
def row():
    return Manifest().config(CONFIG)


def test_the_eight_held_ranges_add_up_to_the_uncut_layer():
    """A layer of 64 experts, 4 a token, as the model cuts it: the plain held ranges (0, 8), (8, 8),
    .. (56, 8) (``stand_in=False``: what the absent experts would add is left out) add up to the
    uncut reference's layer; every assignment lands on exactly one range, every range returns the
    same counts, and each range's part is the reference's for that range."""
    H, F, E, k = 32, 24, 64, 4
    m = {"num_experts": E, "num_experts_per_tok": k, "norm_topk_prob": True, "routed_scaling_factor": 1}
    router = ("sigmoid_bias", 1.0, EPS)
    whole = DroplessMoE(H, F, E, k, norm_topk_prob=True, router=router, experts=SILU_GATED)
    params = whole.init(jax.random.PRNGKey(0), 0.3)
    params["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (E,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, H), jnp.float32)
    flat = x.reshape(-1, H)
    with jax.default_matmul_precision("highest"):
        want, chosen, _ = ref.expert_layer(flat, params, m, EPS)
        want_counts = ref.assignments(chosen, E)
        total, rows = 0.0, 0.0
        for first in range(0, E, 8):
            held = DroplessMoE(H, F, E, k, norm_topk_prob=True, held=(first, 8), router=router, experts=SILU_GATED)
            mine = dict(params, w_gate_up=params["w_gate_up"][first:first + 8],
                        w_down=params["w_down"][first:first + 8])
            part, aux, stats = jax.jit(held.apply)(mine, x)
            theirs = ref.expert_layer(flat, mine, dict(m, num_experts=8, router_width=E, first_expert=first), EPS)[0]
            np.testing.assert_allclose(part.reshape(-1, H), theirs, atol=3e-5)
            assert float(aux) == 0.0 and np.array_equal(stats["counts"], want_counts)
            total, rows = total + part.reshape(-1, H), rows + float(stats["rows_here"])
    assert rows == 2 * 24 * k                          # every assignment landed somewhere, once
    np.testing.assert_allclose(total, want, atol=1e-4)


def test_the_renormalisations_epsilon_goes_with_the_router():
    """``("sigmoid_bias", factor, eps)``: the chosen scores are divided by their sum plus ``eps``;
    without the third entry it is the 1e-20 the two accepted models were built with."""
    H, F, E, k = 32, 24, 8, 2
    assert DroplessMoE(H, F, E, k, router=("sigmoid_bias", 2.5)).eps == 1e-20
    assert DroplessMoE(H, F, E, k).eps == 1e-20 and DroplessMoE(H, F, E, k).scaling is None
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, H), jnp.float32)
    outputs = []
    for eps in (1e-20, 0.5):
        layer = DroplessMoE(H, F, E, k, norm_topk_prob=True, router=("sigmoid_bias", 1.0, eps))
        assert layer.eps == eps
        params = layer.init(jax.random.PRNGKey(0), 0.3)
        with jax.default_matmul_precision("highest"):
            got = jax.jit(layer.apply)(params, x)[0]
            want = ref.expert_layer(x[0], params, {"num_experts": E, "num_experts_per_tok": k, "norm_topk_prob": True,
                                                   "routed_scaling_factor": 1}, eps)[0]
        np.testing.assert_allclose(got[0], want, atol=3e-5)
        outputs.append(got)
    assert np.linalg.norm(outputs[0] - outputs[1]) > 0.1 * np.linalg.norm(outputs[0])


@pytest.mark.parametrize("change, names", [
    ({"conv_bias": True}, "conv_bias"),
    ({"use_expert_bias": False}, "use_expert_bias"),
    ({"rope_parameters": {"rope_type": "yarn", "rope_theta": 1e6, "factor": 4}}, "rope_type"),
    ({"tie_embedding": False}, "untied"),
    ({"layer_types": ["conv", "sliding_attention", "conv"]}, "unknown kinds"),
    ({"layer_types": ["conv", "conv"]}, "names 2 of 3"),
    ({"num_dense_layers": 3}, "every layer dense")])
def test_from_published_refuses_what_is_not_built(change, names):
    with pytest.raises(AssertionError, match=names):
        Lfm2MoeConfig.from_published(published(**change))


def test_from_published_reads_the_catalogs_row(row):
    c = Lfm2MoeConfig.from_published(row["model"], remat=True, router_eps=row["assumed"]["router_eps"][1])
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim, c.intermediate_size,
            c.moe_intermediate_size, c.num_experts_per_tok, c.conv_L_cache) == (2048, 32, 8, 64, 11776, 1536, 4, 3)
    assert (c.routed_scaling_factor, c.rope_theta, c.norm_eps, c.num_dense_layers, c.router_eps) == \
        (1, 1000000, 1e-5, 1, 1e-6)
    assert (c.num_hidden_layers, c.num_experts, c.router_width, c.first_expert, c.stand_in) == (7, 8, 64, 0, True)
    assert c.kinds == ("conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv")
    assert [c.is_dense(l) for l in range(7)] == [True] + [False] * 6
    model = Lfm2MoeModel(c)
    assert model.moe.held == (0, 8) and model.moe.stand_in and model.moe.num_experts == 64
    assert (model.moe.scaling, model.moe.eps, model.moe.form, model.moe.top_k) == (1.0, 1e-6, SILU_GATED, 4)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert "mlp" in shapes["layers"][0] and all("moe" in lp for lp in shapes["layers"][1:])
    assert ["conv" in lp for lp in shapes["layers"]] == [kind == "conv" for kind in c.kinds]
    assert shapes["layers"][0]["conv"]["conv_w"].shape == (3, 2048) and "head" not in shapes
    assert shapes["layers"][1]["attn"]["wkv"].shape == (2048, 1024)
    # the builder's count, leaf by leaf, is the pricing's and the configuration file's
    count = sum(s.size for s in jax.tree_util.tree_leaves(shapes))
    assert count == flops_conv_moe.param_count(row["model"], row["vocab_size"]) == 647_819_904
    json.dumps(row)
