"""Qwen3-Next (``model_type: qwen3_next``): a pre-norm decoder whose token mixer is a gated
delta-rule linear attention in three layers of four and a gated grouped-query softmax
attention in the fourth, each followed by a top-k mixture of experts with one shared expert;
RMSNorms whose weight is stored as its distance from one, no biases, untied head.

    h = x + mixer_l(norm(x));   y = h + moe(norm(h));   norm(x) = x / rms(x) * (1 + w)

    linear mixer (l + 1 not a multiple of ``full_attention_interval``):
        q, k, v, z = split(Wqkvz x);  b, a = split(Wba x)
        q, k, v = silu(causal depthwise conv of width 4 over [q|k|v])
        beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)            (float32)
        q, k L2-normalised a head, q scaled by Dk^-1/2; a key head serves Hv/Hk value heads
        o = gated_delta_rule(q, k, v, g, beta)            (``ops/delta_rule.py``)
        Wout (rms(o) * w_head * silu(z))                  (a head; plain weight)
    full attention: q, gate = split(Wq x) a head; q, k = norm(q), norm(Wk x) over a head;
        rotary on the first ``partial_rotary_factor`` of a head; causal softmax over
        ``num_key_value_heads`` shared key/value heads; Wo (attn * sigmoid(gate))
    moe: p = softmax(Wr x) over ``router_width`` experts, the top k renormalised;
        sum_e p_e Wdown_e(silu(Wgate_e x) * Wup_e x) over those of the k THIS CHIP HOLDS
        (``parallel/moe.DroplessMoE``'s held-range form), + sigmoid(w_sg x) * shared(x)

Training adds ``router_aux_loss_coef`` x the load-balancing loss over all ``router_width``
experts, averaged over layers. The multi-token-prediction module of the published model is
not here (no key of its ``config.json`` describes it). The column order inside ``w_qkvz``
([q | k | v | z]), ``w_ba`` ([b | a]) and ``wq`` (a head: [q | gate]) is this file's own.

The model follows the repo's convention (``init(rng) -> params``, ``apply(params, tokens[,
labels])``) and goes through ``deepspeed_tpu.initialize`` like GPT-2 and OLMoE.
"""

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .layers import chunked_cross_entropy, rms_norm, rope


@dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # full attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # linear attention
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # experts: ``num_experts`` are held here, experts ``first_expert`` onwards of the
    # ``router_width`` the router chooses among (None: all are held)
    num_experts: int = 512
    router_width: Optional[int] = None
    first_expert: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    router_aux_loss_coef: float = 0.001
    initializer_range: float = 0.02
    compute_dtype: Any = jnp.bfloat16

    @classmethod
    def from_published(cls, keys, **more):
        """From the keys of the model's ``config.json``; keys that say nothing this block
        could do otherwise are checked, not stored."""
        assert keys.get("hidden_act", "silu") == "silu", keys.get("hidden_act")
        assert keys.get("rope_scaling") is None and not keys.get("tie_word_embeddings", False)
        assert not keys.get("attention_bias", False) and not keys.get("use_sliding_window", False)
        assert keys.get("decoder_sparse_step", 1) == 1 and not keys.get("mlp_only_layers"), \
            "every layer is an expert layer in this block"
        return cls(**{k: v for k, v in keys.items() if k in cls.__dataclass_fields__}, **more)

    def is_full_attention(self, layer):
        return (layer + 1) % self.full_attention_interval == 0


def _dot(x, w):
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)


class Qwen3NextModel:
    # what ``apply`` returns beside its loss, by name: per-layer device scalars the engine
    # keeps of every step, unfetched (``utils/spans.py``)
    device_scalars = ("moe_load_max_over_mean", "moe_rows_here")

    def __init__(self, config: Qwen3NextConfig):
        from ..parallel.moe import DroplessMoE
        self.config = c = config
        self.moe = DroplessMoE(c.hidden_size, c.moe_intermediate_size,
                               c.router_width or c.num_experts, c.num_experts_per_tok,
                               norm_topk_prob=c.norm_topk_prob,
                               held=(c.first_expert, c.num_experts))

    # ------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        H, s = c.hidden_size, c.initializer_range
        normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32) * s   # noqa: E731
        zeros = lambda n=H: jnp.zeros((n,), jnp.float32)                                # noqa: E731
        Hk, Hv = c.linear_num_key_heads, c.linear_num_value_heads
        qk, vz = Hk * c.linear_key_head_dim, Hv * c.linear_value_head_dim
        S = c.shared_expert_intermediate_size
        keys = jax.random.split(rng, 2 + c.num_hidden_layers)
        layers = []
        for l, key in enumerate(keys[2:]):
            k = jax.random.split(key, 10)
            if c.is_full_attention(l):
                nq, nkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
                mixer = {"wq": normal(k[0], H, nq * 2 * D), "wkv": normal(k[1], H, 2 * nkv * D),
                         "q_norm": zeros(D), "k_norm": zeros(D), "wo": normal(k[2], nq * D, H)}
            else:
                W = c.linear_conv_kernel_dim
                mixer = {"w_qkvz": normal(k[0], H, 2 * qk + 2 * vz), "w_ba": normal(k[1], H, 2 * Hv),
                         "conv_w": jax.random.uniform(k[2], (W, 2 * qk + vz), jnp.float32,
                                                      -W ** -0.5, W ** -0.5),
                         "A_log": jnp.log(jax.random.uniform(k[3], (Hv,), jnp.float32, 1e-3, 16.0)),
                         "dt_bias": jnp.ones((Hv,), jnp.float32),
                         "o_norm": jnp.ones((c.linear_value_head_dim,), jnp.float32),
                         "w_out": normal(k[4], vz, H)}
            layers.append({
                "norm_1": zeros(), "mixer": mixer, "norm_2": zeros(),
                "moe": self.moe.init(k[5], s),
                "shared": {"w_gate_up": normal(k[6], H, 2 * S), "w_down": normal(k[7], S, H),
                           "w_gate": normal(k[8], H, 1)},
            })
        return {"embed": normal(keys[0], c.vocab_size, H), "layers": layers,
                "norm_f": zeros(), "head": normal(keys[1], c.vocab_size, H)}

    # ------------------------------------------------------------- layers
    def _norm(self, x, w):
        return rms_norm(x, w, self.config.rms_norm_eps, zero_centred=True)

    def _gated_norm(self, o, z, w):
        """``rms(o) * w * silu(z)`` a head in float32; made again in the backward from ``o``
        and ``z`` as they are stored (the compute dtype), so no float32 copy is kept."""
        def gated(o, z, w):
            o = rms_norm(o.astype(jnp.float32), w, self.config.rms_norm_eps)
            return (o * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
        return jax.checkpoint(gated)(o, z, w)

    def linear_mixer(self, x, mp):
        """The gated delta-rule mixer on the normed block input ``x [B, T, H]``."""
        from ..ops.delta_rule import causal_conv, gated_delta_rule
        c = self.config
        B, T, _ = x.shape
        Hk, Hv = c.linear_num_key_heads, c.linear_num_value_heads
        Dk, Dv = c.linear_key_head_dim, c.linear_value_head_dim
        with jax.named_scope("ds_lin_attn"):
            x = checkpoint_name(x, "ds_dot:qkv")     # the remat policies classify dots by tag
            qkvz = _dot(x, mp["w_qkvz"]).astype(x.dtype)
            x = checkpoint_name(x, "ds_dot:qkv")
            b, a = jnp.split(_dot(x, mp["w_ba"]), 2, axis=-1)                 # float32
            z = qkvz[..., 2 * Hk * Dk + Hv * Dv:]
            # the convolution reads q, k and v where the projection leaves them
            mixed = causal_conv(qkvz, mp["conv_w"], True, columns=(0, 2 * Hk * Dk + Hv * Dv))
            q, k, v = jnp.split(mixed, [Hk * Dk, 2 * Hk * Dk], axis=-1)
            beta = jax.nn.sigmoid(b)
            g = -jnp.exp(mp["A_log"]) * jax.nn.softplus(a + mp["dt_bias"])
            o = gated_delta_rule(q.reshape(B, T, Hk, Dk), k.reshape(B, T, Hk, Dk),
                                 v.reshape(B, T, Hv, Dv), g, beta)
            o = self._gated_norm(o, z.reshape(B, T, Hv, Dv), mp["o_norm"])
            o = checkpoint_name(o.reshape(B, T, Hv * Dv), "ds_dot:proj")
            return _dot(o, mp["w_out"]).astype(x.dtype)

    def full_attention(self, x, mp, positions):
        """The gated grouped-query attention on the normed block input ``x [B, T, H]``."""
        from ..ops.pallas.flash_attention import flash_attention_rows
        c = self.config
        B, T, _ = x.shape
        nq, nkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        x = checkpoint_name(x, "ds_dot:qkv")
        q, gate = jnp.split(_dot(x, mp["wq"]).astype(x.dtype).reshape(B, T, nq, 2 * D), 2, axis=-1)
        x = checkpoint_name(x, "ds_dot:qkv")
        k, v = jnp.split(_dot(x, mp["wkv"]).astype(x.dtype).reshape(B, T, 2 * nkv, D), 2, axis=2)
        q, k = self._norm(q, mp["q_norm"]), self._norm(k, mp["k_norm"])
        heads = lambda a: a.transpose(0, 2, 1, 3)      # noqa: E731
        width = int(D * c.partial_rotary_factor)
        q, k = (rope(heads(a), positions, c.rope_theta, width) for a in (q, k))
        # q and k head-major from the rotary pass, v and the output as the projections write and read them
        y = checkpoint_name(flash_attention_rows(q, k, v.reshape(B, T, nkv * D), nq, nkv, True), "attn_out")
        y = y * jax.nn.sigmoid(gate.reshape(B, T, nq * D).astype(jnp.float32)).astype(y.dtype)
        y = checkpoint_name(y, "ds_dot:proj")
        return _dot(y, mp["wo"]).astype(x.dtype)

    def expert_layer(self, x, lp, details=False):
        """The held experts' part plus the shared expert behind its gate: ``(y, aux, stats)``."""
        S = self.config.shared_expert_intermediate_size
        y, aux, stats = self.moe.apply(lp["moe"], x, details)
        with jax.named_scope("ds_moe_shared"):
            sp = lp["shared"]
            gate_up = _dot(x, sp["w_gate_up"])
            hidden = (jax.nn.silu(gate_up[..., :S]) * gate_up[..., S:]).astype(x.dtype)
            shared = _dot(hidden, sp["w_down"]) * jax.nn.sigmoid(_dot(x, sp["w_gate"]))
        return y + shared.astype(x.dtype), aux, stats

    def _block(self, x, lp, full, positions, details=False):
        seen = {}
        with jax.named_scope("ds_attn"):
            n = self._norm(x, lp["norm_1"])
            if details:
                seen["mixer_in"] = n
            x = x + (self.full_attention(n, lp["mixer"], positions) if full
                     else self.linear_mixer(n, lp["mixer"]))
        # the expert layer is this block's MLP: its ds_moe_* scopes nest under ds_mlp
        with jax.named_scope("ds_mlp"):
            n = self._norm(x, lp["norm_2"])
            if details:
                seen["expert_in"] = n
            m, aux, stats = self.expert_layer(n, lp, details)
            return x + m, aux, dict(stats, **seen)

    def _backbone(self, params, tokens, details=False):
        c = self.config
        positions = jnp.arange(tokens.shape[1])
        with jax.named_scope("ds_embed"):
            x = params["embed"][tokens].astype(c.compute_dtype)
        aux, stats = jnp.zeros((), jnp.float32), []
        for l, lp in enumerate(params["layers"]):
            x, a, s = self._block(x, lp, c.is_full_attention(l), positions, details)
            aux = aux + a
            stats.append(s)
        with jax.named_scope("ds_loss"):      # the last norm feeds the head
            x = self._norm(x, params["norm_f"])
        stats = {name: jnp.stack([s[name] for s in stats]) for name in stats[0]}
        return x, aux / len(params["layers"]), stats

    # ------------------------------------------------------------- apply
    def logits(self, params, tokens):
        x, _, _ = self._backbone(params, tokens)
        with jax.named_scope("ds_loss"):
            return jnp.einsum("bth,vh->btv", x, params["head"].astype(x.dtype),
                              preferred_element_type=jnp.float32)

    def forward_details(self, params, tokens, labels, last):
        """What a comparison with the plain reference reads: the loss and its parts, the
        logits of the ``last`` positions, every layer's expert choices, and the normed
        inputs of every layer's mixer and expert layer."""
        x, aux, stats = self._backbone(params, tokens, details=True)
        with jax.named_scope("ds_loss"):
            ce = chunked_cross_entropy(x, params["head"], labels)
            logits = jnp.einsum("bth,vh->btv", x[:, -last:], params["head"].astype(x.dtype),
                                preferred_element_type=jnp.float32)
        return {"loss": ce + self.config.router_aux_loss_coef * aux, "ce": ce, "aux": aux,
                "logits": logits, "experts": stats["experts"],
                "mixer_in": stats["mixer_in"], "expert_in": stats["expert_in"]}

    def apply(self, params, tokens, labels=None):
        """Without labels: float32 logits. With labels: ``(loss, stats)`` — the mean token
        cross-entropy plus ``router_aux_loss_coef`` x the load-balancing loss, and the
        expert layers' per-layer device scalars (``device_scalars``), which the engine
        keeps beside the loss without fetching them."""
        if labels is None:
            return self.logits(params, tokens)
        x, aux, stats = self._backbone(params, tokens)
        with jax.named_scope("ds_loss"):
            ce = chunked_cross_entropy(x, params["head"], labels)
        # with every expert held (no cut) every assignment lands here
        every = jnp.full_like(stats["load_max_over_mean"],
                              tokens.size * self.config.num_experts_per_tok)
        return (ce + self.config.router_aux_loss_coef * aux,
                {"moe_load_max_over_mean": stats["load_max_over_mean"],
                 "moe_rows_here": stats.get("rows_here", every)})
