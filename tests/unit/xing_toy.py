"""A toy Xing4.0 (``deepspeed_tpu/models/xing_moe.py``) for the unit tests: one dense block and one
expert block inside four residual streams, four heads of 12 + 4 | 8 (values narrower than the keys)
under a YaRN table over 16 original positions."""

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.xing_moe import XingMoeConfig, XingMoeModel

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16, "type": "yarn"}


def published(**more):
    return dict(dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=2, first_k_dense_replace=1,
        num_nextn_predict_layers=0, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=16,
        kv_lora_rank=12, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=8, rope_theta=10000,
        rope_scaling=YARN, intermediate_size=48, moe_intermediate_size=24, moe_layer_freq=1,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
        routed_scaling_factor=2, scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
        rms_norm_eps=1e-6, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
        mhc_h_res_clamp_max=30, hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
        model_type="xing4_0"), **more)


def build(keys=None, bias_spread=0.05, **more):
    """``(keys, model, params)``: the norms' weights, the hyper-connections' biases and gates off
    their initial values and the selection biases off their zero, so that a dropped norm, a bias
    let into the weights or a coefficient set left static shows."""
    keys = keys or published()
    more = dict(dict(compute_dtype=jnp.float32, initializer_range=0.1), **more)
    model = XingMoeModel(XingMoeConfig.from_published(keys, **more))
    params = model.init(jax.random.PRNGKey(3))

    def off(path, p):
        name = jax.tree_util.keystr(path)
        if p.ndim > 1 and not name.endswith("['b_res']"):
            return p
        spread = bias_spread if name.endswith("['router_bias']") else 0.3 if name.endswith("['gates']") else 0.1
        return p + spread * jax.random.normal(jax.random.PRNGKey(p.size + len(path)), p.shape)
    return keys, model, jax.tree_util.tree_map_with_path(off, params)


def batch(seed=1, rows=8, T=40):
    rng = np.random.default_rng(seed)
    stream = rng.integers(0, 96, (rows, T + 1)).astype(np.int32)
    return stream[:, :-1], stream[:, 1:]
