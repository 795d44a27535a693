"""OLMoE (``model_type: olmoe``): a pre-norm decoder of RMSNorm, rotary attention with
QK-norm, and a top-k mixture of SiLU-gated experts in every layer; no biases, untied head.

    h = x + Wo·attn(rope(qnorm(Wq·n1(x))), rope(knorm(Wk·n1(x))), Wv·n1(x))
    y = h + sum_{e in top-k(p)} p_e · Wdown_e(silu(Wgate_e·n2(h)) * Wup_e·n2(h)),  p = softmax(Wr·n2(h))

``qnorm``/``knorm`` are RMSNorms over the whole projection, before the split into heads.
Training adds ``router_aux_loss_coef`` × the load-balancing loss, averaged over layers.

The model follows the repo's convention (``init(rng) -> params``, ``apply(params, tokens[,
labels])``) and goes through ``deepspeed_tpu.initialize`` like GPT-2. ``engine_shardings``
tells the engine which leaves are experts: they live split over the ``data`` axis, master
copy and Adam moments with them, and the expert layer (``parallel/moe.DroplessMoE``)
finds the same mesh in context and gathers a layer's experts over it for use, as ZeRO-3
gathers a parameter (tokens stay on their chip). Wq, Wk and Wv are stored side by side
(``wqkv``), as are each expert's gate and up matrices: a layer is eight leaves.
"""

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from .layers import chunked_cross_entropy, rms_norm, rope


@dataclass
class OlmoeConfig:
    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 1024          # one expert's width
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = False
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    router_aux_loss_coef: float = 0.01
    initializer_range: float = 0.02
    compute_dtype: Any = jnp.bfloat16

    @classmethod
    def from_published(cls, keys, **more):
        """From the keys of the model's ``config.json``; keys that say nothing about the
        block (``model_type``, ``hidden_act`` ...) are checked, not stored."""
        assert keys.get("hidden_act", "silu") == "silu", keys.get("hidden_act")
        assert not keys.get("attention_bias", False) and keys.get("clip_qkv") is None
        assert keys.get("rope_scaling") is None and not keys.get("tie_word_embeddings", False)
        assert keys.get("num_key_value_heads", keys["num_attention_heads"]) == \
            keys["num_attention_heads"], "grouped heads are not in this block"
        return cls(**{k: v for k, v in keys.items() if k in cls.__dataclass_fields__}, **more)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


class OlmoeModel:
    # what ``apply`` returns beside its loss, by name: the engine keeps these per-layer
    # device scalars of every step, unfetched (``utils/spans.py``)
    device_scalars = ("moe_load_max_over_mean",)

    def __init__(self, config: OlmoeConfig):
        from ..parallel.moe import DroplessMoE
        self.config = config
        self.moe = DroplessMoE(config.hidden_size, config.intermediate_size,
                               config.num_experts, config.num_experts_per_tok,
                               norm_topk_prob=config.norm_topk_prob)

    # ------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        H, s = c.hidden_size, c.initializer_range
        keys = jax.random.split(rng, 2 + c.num_hidden_layers)
        ones = lambda: jnp.ones((H,), jnp.float32)   # noqa: E731
        layers = []
        for key in keys[2:]:
            k = jax.random.split(key, 3)
            layers.append({
                "norm_1": ones(),
                "wqkv": jax.random.normal(k[0], (H, 3 * H), jnp.float32) * s,
                "q_norm": ones(), "k_norm": ones(),
                "wo": jax.random.normal(k[1], (H, H), jnp.float32) * s,
                "norm_2": ones(),
                "moe": self.moe.init(k[2], s),
            })
        return {"embed": jax.random.normal(keys[0], (c.vocab_size, H), jnp.float32) * s,
                "layers": layers, "norm_f": ones(),
                "head": jax.random.normal(keys[1], (c.vocab_size, H), jnp.float32) * s}

    def engine_shardings(self, mesh):
        """Parameter layout for the engine: experts over ``data`` where it divides them,
        everything else whole (the engine's ZeRO layout then claims what is free)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.mesh import DATA_AXIS
        dp = mesh.shape[DATA_AXIS]
        split = dp > 1 and self.config.num_experts % dp == 0
        whole = NamedSharding(mesh, P())
        moe = {name: NamedSharding(mesh, spec if split else P())
               for name, spec in self.moe.expert_specs(DATA_AXIS).items()}
        layer = {"norm_1": whole, "wqkv": whole, "q_norm": whole, "k_norm": whole,
                 "wo": whole, "norm_2": whole, "moe": moe}
        return {"embed": whole, "layers": [layer] * self.config.num_hidden_layers,
                "norm_f": whole, "head": whole}

    # ------------------------------------------------------------- layers
    def _attention(self, x, lp, positions):
        from jax.ad_checkpoint import checkpoint_name
        from ..ops.pallas.flash_attention import flash_attention
        c = self.config
        B, T, H = x.shape
        nh, hd = c.num_attention_heads, c.head_dim
        x = checkpoint_name(x, "ds_dot:qkv")     # the remat policies classify dots by tag
        qkv = jnp.dot(x, lp["wqkv"].astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = rms_norm(q, lp["q_norm"], c.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], c.rms_norm_eps)
        heads = lambda a: a.reshape(B, T, nh, hd).transpose(0, 2, 1, 3)   # noqa: E731
        q, k, v = rope(heads(q), positions, c.rope_theta), \
            rope(heads(k), positions, c.rope_theta), heads(v)
        y = checkpoint_name(flash_attention(q, k, v, True), "attn_out")
        y = y.transpose(0, 2, 1, 3).reshape(B, T, H)
        y = checkpoint_name(y, "ds_dot:proj")
        return jnp.dot(y, lp["wo"].astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)

    def _block(self, x, lp, positions, details=False, keep=False):
        c = self.config
        with jax.named_scope("ds_attn"):
            x = x + self._attention(rms_norm(x, lp["norm_1"], c.rms_norm_eps), lp, positions)
        # the expert layer is this block's MLP: its ds_moe_* scopes nest under ds_mlp
        with jax.named_scope("ds_mlp"):
            m, aux, stats = self.moe.apply(
                lp["moe"], rms_norm(x, lp["norm_2"], c.rms_norm_eps), details, keep)
            return x + m, aux, stats

    def _backbone(self, params, tokens, details=False):
        c = self.config
        positions = jnp.arange(tokens.shape[1])
        with jax.named_scope("ds_embed"):
            x = params["embed"][tokens].astype(c.compute_dtype)
        aux, stats = jnp.zeros((), jnp.float32), []
        # whether a layer's backward finds the experts it fetched still there: as the chip has room
        keep = self.moe.fetches_kept(len(params["layers"]), x)
        for lp in params["layers"]:
            x, a, s = self._block(x, lp, positions, details, keep)
            aux = aux + a
            stats.append(s)
        with jax.named_scope("ds_loss"):      # the last norm feeds the head
            x = rms_norm(x, params["norm_f"], c.rms_norm_eps)
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
        logits of the ``last`` positions, and every layer's expert choices."""
        x, aux, stats = self._backbone(params, tokens, details=True)
        with jax.named_scope("ds_loss"):
            ce = chunked_cross_entropy(x, params["head"], labels)
            logits = jnp.einsum("bth,vh->btv", x[:, -last:], params["head"].astype(x.dtype),
                                preferred_element_type=jnp.float32)
        return {"loss": ce + self.config.router_aux_loss_coef * aux, "ce": ce, "aux": aux,
                "logits": logits, "experts": stats["experts"]}

    def apply(self, params, tokens, labels=None):
        """Without labels: float32 logits. With labels: ``(loss, stats)`` — the mean token
        cross-entropy plus ``router_aux_loss_coef`` × the load-balancing loss, and the
        expert layers' per-layer device scalars (``device_scalars``), which the engine
        keeps beside the loss without fetching them."""
        if labels is None:
            return self.logits(params, tokens)
        x, aux, stats = self._backbone(params, tokens)
        with jax.named_scope("ds_loss"):
            ce = chunked_cross_entropy(x, params["head"], labels)
        return (ce + self.config.router_aux_loss_coef * aux,
                {"moe_load_max_over_mean": stats["load_max_over_mean"]})
