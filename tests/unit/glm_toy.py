"""A toy GLM-4.7-Flash (``deepspeed_tpu/models/glm_moe.py``) for the unit tests: one dense block,
one expert block and the prediction module (an expert block too), four heads of 12 + 4 | 16."""

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.glm_moe import GlmMoeConfig, GlmMoeModel

MTP_WEIGHT = 0.3


def published(**more):
    return dict(dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=2, first_k_dense_replace=1,
        num_nextn_predict_layers=1, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=16,
        kv_lora_rank=12, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, rope_theta=10000,
        rope_scaling=None, partial_rotary_factor=1, intermediate_size=48, moe_intermediate_size=24,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
        routed_scaling_factor=1.8, topk_method="noaux_tc", n_group=1, topk_group=1, rms_norm_eps=1e-5,
        hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
        model_type="glm4_moe_lite"), **more)


def build(keys=None, bias_spread=0.05, **more):
    """``(keys, model, params)``: the norms' weights off their initial one and the selection biases
    off their zero, so that a dropped norm or a bias let into the weights shows."""
    keys = keys or published()
    more = dict(dict(compute_dtype=jnp.float32, initializer_range=0.1, mtp_loss_weight=MTP_WEIGHT), **more)
    model = GlmMoeModel(GlmMoeConfig.from_published(keys, **more))
    params = model.init(jax.random.PRNGKey(3))

    def off(path, p):
        if p.ndim > 1:
            return p
        spread = bias_spread if jax.tree_util.keystr(path).endswith("['router_bias']") else 0.1
        return p + spread * jax.random.normal(jax.random.PRNGKey(p.size + len(path)), p.shape)
    return keys, model, jax.tree_util.tree_map_with_path(off, params)


def batch(seed=1, rows=8, T=40):
    rng = np.random.default_rng(seed)
    stream = rng.integers(0, 96, (rows, T + 1)).astype(np.int32)
    return stream[:, :-1], stream[:, 1:]
