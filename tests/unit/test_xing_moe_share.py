"""Xing4.0's share tied to the model (the eight plain held ranges of a 64-expert layer, with the
shared expert counted once, add up to the uncut reference's layer), what
``XingMoeConfig.from_published`` reads of the catalog's row and what it refuses, and the builder's
parameter count."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import flops_hc_moe
from benchmarks.manifest import Manifest
from benchmarks.reference import xing_moe_reference as ref
from deepspeed_tpu.models.xing_moe import XingMoeConfig, XingMoeModel
from deepspeed_tpu.parallel.moe import SILU_GATED
from xing_toy import published

CONFIG = "xing4.0-29b-a4b-ep8-d5"


@pytest.fixture(scope="module")
def row():
    return Manifest().config(CONFIG)


def test_the_eight_held_ranges_add_up_to_the_uncut_layer():
    """A layer of 64 experts, 4 a token, as the model cuts it: the plain held ranges (0, 8), (8, 8),
    .. (56, 8) (``stand_in`` off: what the absent experts would add is left out) through the MODEL's
    own ``expert_layer``, the shared expert counted ONCE, add up to the uncut reference's layer; every
    assignment lands on exactly one range, and every range returns the same counts."""
    H, F, E, k = 32, 24, 64, 4
    keys = published(hidden_size=H, moe_intermediate_size=F, num_experts_per_tok=k, n_routed_experts=E)
    whole = XingMoeModel(XingMoeConfig.from_published(keys, compute_dtype=jnp.float32))
    mp = whole.moe.init(jax.random.PRNGKey(0), 0.3)
    mp["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (E,))
    shared = {"w_gate_up": jax.random.normal(jax.random.PRNGKey(5), (H, 2 * F)) * 0.3,
              "w_down": jax.random.normal(jax.random.PRNGKey(6), (F, H)) * 0.3}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, H), jnp.float32)
    flat = x.reshape(-1, H)
    with jax.default_matmul_precision("highest"):
        want, chosen, _ = ref.expert_layer(flat, {"moe": mp, "shared": shared}, keys)
        want_counts = ref.assignments(chosen, E)
        once = ref.gated(flat, shared)
        total, rows = once, 0.0
        for first in range(0, E, 8):
            cut = dict(keys, n_routed_experts=8, router_width=E, first_expert=first, stand_in=False)
            model = XingMoeModel(XingMoeConfig.from_published(cut, compute_dtype=jnp.float32))
            assert model.moe.held == (first, 8) and not model.moe.stand_in
            mine = dict(mp, w_gate_up=mp["w_gate_up"][first:first + 8], w_down=mp["w_down"][first:first + 8])
            part, stats = jax.jit(model.expert_layer)(x, {"moe": mine, "shared": shared})
            theirs = ref.expert_layer(flat, {"moe": mine, "shared": shared}, cut)[0]
            np.testing.assert_allclose(part.reshape(-1, H), theirs, atol=3e-5)
            assert np.array_equal(stats["counts"], want_counts)
            # every chip computes the shared expert alike: it is counted once
            total, rows = total + part.reshape(-1, H) - once, rows + float(stats["rows_here"])
    assert rows == 2 * 24 * k                          # every assignment landed somewhere, once
    np.testing.assert_allclose(total, want, atol=1e-4)


@pytest.mark.parametrize("change, names", [
    ({"n_group": 4, "topk_group": 2}, "n_group"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"attention_bias": True}, "attention_bias"),
    ({"num_key_value_heads": 2}, "key/value heads"),
    ({"tie_word_embeddings": True}, "head"),
    ({"scoring_func": "softmax"}, "softmax"),
    ({"rope_scaling": {"type": "linear", "factor": 4}}, "rope_scaling"),
    ({"moe_layer_freq": 2}, "expert block")])
def test_from_published_refuses_what_is_not_built(change, names):
    with pytest.raises(AssertionError, match=names):
        XingMoeConfig.from_published(published(**change))


def test_from_published_reads_the_catalogs_row(row):
    c = XingMoeConfig.from_published(row["model"], remat=True)
    assert (c.hidden_size, c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.intermediate_size, c.moe_intermediate_size,
            c.num_experts_per_tok) == (3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 4)
    assert (c.routed_scaling_factor, c.rope_theta, c.rms_norm_eps, c.first_k_dense_replace) == (2, 10000, 1e-6, 1)
    assert (c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps, c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max) == \
        (4, 20, 1e-6, -30, 30)
    assert (c.num_hidden_layers, c.n_routed_experts, c.router_width, c.first_expert, c.stand_in) == (5, 8, 64, 0, True)
    assert [c.is_dense(l) for l in range(5)] == [True, False, False, False, False] and c.qk_head_dim == 192
    # YaRN: 64 over 4,096; cos and sin unscaled (mscale over mscale_all_dim); m^2 in the scale
    (inv_freq, factor), scale = c.rotary()
    m = 0.1 * np.log(64) + 1
    assert inv_freq.shape == (32,) and factor == 1.0 and scale == pytest.approx(m * m / np.sqrt(192))
    assert inv_freq[0] == 1.0 and inv_freq[-1] == pytest.approx(10000 ** (-62 / 64) / 64, rel=1e-6)
    np.testing.assert_allclose(inv_freq, ref.yarn_inverse_frequencies(64, 10000, row["rope_scaling"]), rtol=1e-6)
    model = XingMoeModel(c)
    assert model.moe.held == (0, 8) and model.moe.stand_in and model.moe.num_experts == 64
    assert model.moe.scaling == 2 and model.moe.form == SILU_GATED and model.moe.top_k == 4
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert "mlp" in shapes["layers"][0] and all("moe" in lp for lp in shapes["layers"][1:])
    assert "mtp" not in shapes and shapes["layers"][0]["hc_attn"]["phi_res"].shape == (14336, 16)
    assert shapes["layers"][4]["attn"]["wkv_b"].shape == (512, 32 * 256) and shapes["layers"][4]["attn"]["wo"].shape == (4096, 3584)
    # the builder's count, leaf by leaf, is the pricing's and the configuration file's
    count = sum(s.size for s in jax.tree_util.tree_leaves(shapes))
    assert count == flops_hc_moe.param_count(row["model"], row["vocab_size"]) == 759_489_806
    assert sum(s.size for s in jax.tree_util.tree_leaves(shapes["layers"][0])) == 128_225_590
    assert sum(s.size for s in jax.tree_util.tree_leaves(shapes["layers"][1])) == 128_455_030
    json.dumps(row)
