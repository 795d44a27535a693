"""A toy LFM2-MoE (``deepspeed_tpu/models/lfm2_moe.py``) for the unit tests: a dense layer with a
short convolution, then an attention and a convolution layer with experts; 128 wide (the
convolution's kernels want whole registers of 128 lanes), four query over two key/value heads of 32."""

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel

EPS = 1e-6                  # the renormalisation's, as the configuration assumes it
KINDS = ("conv", "full_attention", "conv")


def published(**more):
    return dict(dict(
        vocab_size=96, hidden_size=128, num_hidden_layers=3, layer_types=list(KINDS), num_dense_layers=1,
        conv_L_cache=3, conv_bias=False, num_attention_heads=4, num_key_value_heads=2,
        rope_parameters={"rope_theta": 10000, "rope_type": "default"}, intermediate_size=96,
        moe_intermediate_size=48, num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
        routed_scaling_factor=1, use_expert_bias=True, norm_eps=1e-5, max_position_embeddings=1024,
        model_type="lfm2_moe"), **more)


def build(keys=None, bias_spread=0.05, **more):
    """``(keys, model, params)``: the norms' weights off their initial one and the selection biases
    off their zero, so that a dropped norm or a bias let into the weights shows."""
    keys = keys or published()
    more = dict(dict(compute_dtype=jnp.float32, initializer_range=0.1, router_eps=EPS), **more)
    model = Lfm2MoeModel(Lfm2MoeConfig.from_published(keys, **more))
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
