"""A toy Mellum (``deepspeed_tpu/models/mellum.py``) for the unit tests: two periods of
(sliding, sliding, sliding, full), a window of 8, YaRN over an original 16 positions."""

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.mellum import MellumConfig, MellumModel

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
AUX_COEF = 0.01


def published(**more):
    return dict(dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=8, layer_types=PERIOD * 3,
        mlp_layer_types=["sparse"] * 12, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window=8, use_sliding_window=True, max_window_layers=0,
        rope_parameters={
            "full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                               "original_max_position_embeddings": 16, "beta_fast": 2,
                               "beta_slow": 0.5, "attention_factor": 1.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=24, norm_topk_prob=True,
        rms_norm_eps=1e-6, hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
        model_type="mellum"), **more)


def build(keys=None, **more):
    keys = keys or published()
    more = dict(dict(compute_dtype=jnp.float32, initializer_range=0.1, router_aux_loss_coef=AUX_COEF), **more)
    model = MellumModel(MellumConfig.from_published(keys, **more))
    params = model.init(jax.random.PRNGKey(3))
    # the norms' weights off their initial one, so that a dropped one shows
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p,
        params)
    return keys, model, params


def batch(seed=1, rows=8, T=40):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 96, (rows, T)).astype(np.int32),
            rng.integers(0, 96, (rows, T)).astype(np.int32))
