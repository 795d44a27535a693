"""Xing4.0-29B-A4B (``model_type: xing4_0``): DeepSeek-V3's block (arXiv:2412.19437 section 2.1:
latent attention, leading dense blocks, then expert blocks with a shared expert and a sigmoid
router chosen under a selection bias) INSIDE manifold-constrained hyper-connections (mHC,
arXiv:2512.24880, on hyper-connections, arXiv:2409.19606): the state between blocks is
``hc_mult`` residual streams, and no sub-layer's output is added to its input.

    X_0[i] = E[tokens]  for every stream i               (the embedding copied into every stream)
    block l, sub-layer s in (attention, MLP), on X [n, C] a token (``models/hyper_connections.py``):
        H_pre [n], H_post [n], H_res [n, n] = coefficients(X)        float32, H_res by
                                              ``hc_sinkhorn_iters`` Sinkhorn-Knopp rounds
        u  = sum_i H_pre[i] X[i]                         (the ONE stream the sub-layer reads)
        f  = F_s(rms(u) g_s)                             (F_attention = MLA, F_MLP = dense MLP or experts)
        X'[i] = sum_j H_res[i, j] X[j] + H_post[i] f     (what replaces  x + f)
    x_L = sum_i X_L[i];  logits = (rms(x_L) g_f) W_head;  loss = CE(logits_t, token_{t+1})

    MLA   ``GlmMoeModel.attention`` (shared, not copied) at value heads of ``v_head_dim`` beside
          keys of ``qk_nope + qk_rope`` (128 | 192: the flash kernel takes both widths), the ONE
          rotary key turned by YaRN's inverse frequencies (``layers.rope_frequencies``; cos and sin
          times ``mscale / mscale_all_dim``'s ratio), the softmax's scale ``m^2 / sqrt(nope + rope)``,
          ``m = 0.1 mscale_all_dim ln(factor) + 1``: handed to the kernel as ``sm_scale`` (the
          kernel folds it into ``q``, in float32, before rounding; the reference scales the scores)
    MLP   ``GlmMoeModel.dense_mlp`` in the first ``first_k_dense_replace`` blocks, then
          ``GlmMoeModel.expert_layer`` (``parallel/moe.DroplessMoE``: held range, stand-in, sigmoid
          router with a selection bias, SiLU-gated experts, one shared expert)
    after a step:  b_e <- b_e + u sign(mean(c) - c_e)    (``GlmMoeModel.apply_rule``)

The streams are carried flat, ``[B, T, n C]`` (see ``hyper_connections``): that array is what a
recomputed block keeps of its input, four times a plain model's. Not here (``from_published``
refuses them): ``n_group > 1``, attention biases, a prediction depth (``num_nextn_predict_layers``:
the module lies with a later pipeline stage's chip), a tied head, the latent cache and the
absorbed projections of the served path, dropout. Packed documents are not masked at their
boundaries.

The model follows the repo's convention (``init(rng) -> params``, ``apply(params, tokens[,
labels])``) and goes through ``deepspeed_tpu.initialize`` like the other models.
"""

import functools
import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..runtime.activation_checkpointing.checkpointing import checkpoint_wrapper
from . import hyper_connections as hc
from .glm_moe import GlmMoeModel
from .layers import chunked_cross_entropy, rope_frequencies


@dataclass
class XingMoeConfig:
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    # latent attention
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None      # the published entry (``type: yarn``) or None
    # the dense blocks' MLP
    intermediate_size: int = 9216
    # experts: ``n_routed_experts`` are held here, experts ``first_expert`` onwards of the
    # ``router_width`` the router chooses among (None: all are held)
    n_routed_experts: int = 64
    router_width: Optional[int] = None
    first_expert: int = 0
    stand_in: bool = False
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    bias_update_rate: float = 1e-3           # u of the rule; no published key
    # the residual path
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    remat: bool = False            # whole blocks made again in the backward: a block keeps KEPT_BY_A_LAYER
    compute_dtype: Any = jnp.bfloat16

    @classmethod
    def from_published(cls, keys, **more):
        """From the keys of the model's ``config.json``; keys that say nothing this model
        could do otherwise are checked, not stored."""
        assert keys.get("n_group", 1) == 1 and keys.get("topk_group", 1) == 1, \
            "n_group > 1: the group-limited choice is not built"
        assert keys.get("num_nextn_predict_layers", 0) == 0, \
            f"num_nextn_predict_layers {keys['num_nextn_predict_layers']}: no prediction module is built"
        assert not keys.get("attention_bias", False), "attention_bias: no biases"
        assert not keys.get("tie_word_embeddings", False), "the head is its own table"
        assert keys.get("hidden_act", "silu") == "silu", keys.get("hidden_act")
        assert keys.get("scoring_func", "sigmoid") == "sigmoid", keys.get("scoring_func")
        assert keys.get("topk_method", "noaux_tc") == "noaux_tc", keys.get("topk_method")
        assert keys.get("n_shared_experts", 1) == 1, "one shared expert"
        assert keys.get("moe_layer_freq", 1) == 1, "every block past the dense ones is an expert block"
        heads = keys.get("num_attention_heads", cls.num_attention_heads)
        assert keys.get("num_key_value_heads", heads) == heads, "as many key/value heads as query heads"
        scaling = keys.get("rope_scaling")
        assert scaling is None or scaling.get("type", scaling.get("rope_type")) == "yarn", \
            f"rope_scaling {scaling}: yarn or none"
        stored = {k: v for k, v in keys.items() if k in cls.__dataclass_fields__}
        return cls(**dict(stored, **more))

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def is_dense(self, l):
        return l < self.first_k_dense_replace

    def rotary(self):
        """``((inv_freq, what cos and sin are multiplied by), the softmax's scale)``: YaRN's
        table over the rotary key's features and ``m^2 / sqrt(nope + rope)``; the plain table and
        scale where ``rope_scaling`` is None."""
        s = self.rope_scaling
        if s is None:
            return rope_frequencies(self.qk_rope_head_dim, self.rope_theta), self.qk_head_dim ** -0.5
        scaling = {k: v for k, v in s.items() if k not in ("type", "mscale", "mscale_all_dim")}
        inv_freq, _ = rope_frequencies(self.qk_rope_head_dim, self.rope_theta, dict(scaling, rope_type="yarn"))
        m = lambda scale: 0.1 * scale * math.log(s["factor"]) + 1.0 if s["factor"] > 1 and scale else 1.0   # noqa: E731
        mscale, all_dim = m(s.get("mscale", 1)), m(s.get("mscale_all_dim", 0))
        return (inv_freq, mscale / all_dim), all_dim * all_dim * self.qk_head_dim ** -0.5


# What a recomputed block keeps beside its input (the four streams), by name: ``glm_moe``'s set
# (the flash kernel's output and row sums, the held experts' first grouped product's output) and
# both sub-layers' projections onto the 24 coefficient columns (``hc_proj``: 2 x 24 float32 a token,
# 0.8 MB a block at 4,096 tokens), with which the second forward of the ``jnp`` form runs no n C-deep
# product: 2.5 ms of a 240 ms step on the chip; the three coefficient sets by name bought nothing
# (their chain is made again for its own backward). The hyper-connection's kernels name nothing (on
# the TPU the second forward's ``ds_hc_read`` makes the product again under its tile's transfer).
# Bytes and milliseconds either way: docs/xing4.0-29b-a4b.md.
KEPT_BY_A_LAYER = jax.checkpoint_policies.save_only_these_names(
    "attn_out", "attn_lse", "ds_moe_gate_up", hc.KEPT_NAME)


class XingMoeModel(GlmMoeModel):
    # what ``apply`` returns beside its loss, by name: device scalars the engine keeps of every
    # step, unfetched (``utils/spans.py``): the expert layers' and the hyper-connections'
    device_scalars = ("moe_load_max_over_mean", "moe_rows_here", "moe_bias_abs_max") + hc.READINGS

    def __init__(self, config: XingMoeConfig):
        super().__init__(config)
        self._rotary, self._sm_scale = config.rotary()

    # ------------------------------------------------------------- init
    def _init_block(self, rng, dense):
        c = self.config
        k = jax.random.split(rng, 3)
        connection = lambda key: hc.init(key, c.hc_mult, c.hidden_size, c.initializer_range)    # noqa: E731
        return dict(super()._init_block(k[0], dense), hc_attn=connection(k[1]), hc_mlp=connection(k[2]))

    def init(self, rng):
        """Matrices N(0, ``initializer_range``); norms 1; the selection biases zero; the
        hyper-connections as ``hyper_connections.init``."""
        c = self.config
        H, s = c.hidden_size, c.initializer_range
        keys = jax.random.split(rng, 2 + c.num_hidden_layers)
        return {"embed": jax.random.normal(keys[0], (c.vocab_size, H), jnp.float32) * s,
                "layers": [self._init_block(key, c.is_dense(l)) for l, key in enumerate(keys[2:])],
                "norm_f": jnp.ones((H,), jnp.float32),
                "head": jax.random.normal(keys[1], (c.vocab_size, H), jnp.float32) * s}

    # ------------------------------------------------------------- layers
    def attention(self, x, ap):
        """The latent attention on the normed stream ``x [B, T, H]``: ``GlmMoeModel``'s, under
        YaRN's rotary table and the scale ``m^2 / sqrt(nope + rope)``."""
        return super().attention(x, ap, rotary=self._rotary, sm_scale=self._sm_scale)

    def coefficients(self, x, hp):
        c = self.config
        return hc.coefficients(x, hp, c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps,
                               (c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max), c.rms_norm_eps)

    def connected(self, x, hp, sub_layer):
        """One sub-layer inside its hyper-connection on the flat streams ``x``: ``(X', stats)``,
        ``stats`` what ``sub_layer(u) -> (f, stats)`` said and ``H_res``'s two readings
        (``hyper_connections.connected``: four Pallas kernels on the TPU, the ``jnp`` form elsewhere)."""
        c = self.config
        return hc.connected(x, hp, sub_layer, c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps,
                            (c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max), c.rms_norm_eps)

    def _block(self, x, lp, details=False):
        """One block on the flat streams: ``(X'', stats)``; ``stats`` holds both sub-layers'
        ``H_res`` readings ``[2]``, an expert layer's own, and with ``details`` both normed inputs."""
        kept = {}

        def mixer(u):
            kept["attn_in"] = n1 = self._norm(u, lp["norm_1"])
            return self.attention(n1, lp["attn"]), {}

        def mlp(u):
            kept["mlp_in"] = n2 = self._norm(u, lp["norm_2"])
            if "mlp" in lp:
                return self.dense_mlp(n2, lp["mlp"]), {}
            return self.expert_layer(n2, lp, details)

        with jax.named_scope("ds_attn"):
            x, first = self.connected(x, lp["hc_attn"], mixer)
        # an expert layer is its block's MLP: its ds_moe_* scopes nest under ds_mlp
        with jax.named_scope("ds_mlp"):
            x, stats = self.connected(x, lp["hc_mlp"], mlp)
        for name in hc.READINGS:
            stats[name] = jnp.stack([first[name], stats[name]])
        return x, (dict(stats, **kept) if details else stats)

    def _run(self, x, lp, details):
        block = functools.partial(self._block, details=details)
        if self.config.remat and not details:     # config-aware remat, as ``models/gpt2.py``'s blocks
            block = checkpoint_wrapper(block, policy=KEPT_BY_A_LAYER)
        return block(x, lp)

    def _backbone(self, params, tokens, details=False):
        """The last norm's output and every block's stats, in the blocks' order."""
        c = self.config
        with jax.named_scope("ds_embed"):
            e = params["embed"][tokens].astype(c.compute_dtype)
            x = jnp.concatenate([e] * c.hc_mult, axis=-1)         # every stream starts as the embedding
        stats = []
        for lp in params["layers"]:
            x, s = self._run(x, lp, details)
            stats.append(s)
        with jax.named_scope("ds_loss"):      # the streams' sum, in float32, then the last norm
            x = sum(p.astype(jnp.float32) for p in hc.streams_of(x, c.hc_mult)).astype(c.compute_dtype)
            x = self._norm(x, params["norm_f"])
        return x, stats

    # ------------------------------------------------------------- apply
    def expert_counts(self, params, tokens, labels=None):
        """``[Le, E]`` float32: the assignments to every expert of every expert layer (what
        the rule reads; no head, no loss; ``labels`` as ``GlmMoeModel.expert_counts`` takes them,
        unread: no second depth embeds them)."""
        return self._stacked(self._backbone(params, tokens)[1], "counts")

    def forward_details(self, params, tokens, labels, last):
        """What a comparison with the plain reference reads: the loss, the logits of the ``last``
        positions, every block's two normed inputs ``[L, B, T, H]``, ``H_res``'s readings ``[2 L]``
        (a sub-layer a value), and of the expert layers the choices ``[Le, B, T, k]``, the
        router's logits and the counts ``[Le, E]``."""
        x, stats = self._backbone(params, tokens, details=True)
        with jax.named_scope("ds_loss"):
            loss = chunked_cross_entropy(x, params["head"], labels)
        out = {name: self._stacked(stats, name) for name in (
            "attn_in", "mlp_in", "experts", "router_logits", "counts")}
        out.update({name: self._stacked(stats, name).reshape(-1) for name in hc.READINGS})
        return dict(out, loss=loss, logits=self._logits(params, x[:, -last:]))

    def apply(self, params, tokens, labels=None):
        """Without labels: float32 logits. With labels: ``(loss, stats)`` — the mean token
        cross-entropy, the per-layer device scalars (``device_scalars``: the expert layers' ``[Le]``,
        the hyper-connections' ``[2 L]``, a sub-layer a value) and the step's assignments to every
        expert of every expert layer (``moe_counts`` ``[Le, E]``: what ``apply_rule`` reads)."""
        x, stats = self._backbone(params, tokens)
        if labels is None:
            return self._logits(params, x)
        with jax.named_scope("ds_loss"):
            loss = chunked_cross_entropy(x, params["head"], labels)
        load = self._stacked(stats, "load_max_over_mean")
        # with every expert held (no cut) every assignment lands here
        every = jnp.full_like(load, tokens.size * self.config.num_experts_per_tok)
        rows = self._stacked(stats, "rows_here") if self.moe.held is not None else every
        biases = jnp.stack([jnp.max(jnp.abs(lp["moe"]["router_bias"])) for lp in params["layers"] if "moe" in lp])
        return loss, {"moe_load_max_over_mean": load, "moe_rows_here": rows,
                      "moe_bias_abs_max": jax.lax.stop_gradient(biases).astype(jnp.float32),
                      "moe_counts": self._stacked(stats, "counts"),
                      **{name: self._stacked(stats, name).reshape(-1) for name in hc.READINGS}}
