"""Mellum 2 (``model_type: mellum``; Mellum2-12B-A2.5B): a pre-norm decoder whose attention
layers are sliding-window or full by ``layer_types`` (three to one), each kind with a rotary
table of its own, and whose every MLP is an expert layer.

    x0 = E[tokens];   layer l of kind layer_types[l]:
        h = x + Attn_kind(rms(x) * g1);     y = h + MoE(rms(h) * g2)
    logits = (rms(x_L) * g_f) W_head

    Attn  q = u W_q (``num_attention_heads`` of ``head_dim``), k, v = u W_k, u W_v
          (``num_key_value_heads``; query head a reads key/value head a // group), no bias;
          q and k pass an RMSNorm over each head's features with a learned weight, then the
          kind's rotary table (half-split): ``sliding_attention`` the plain ``theta^(-2i/D)``,
          ``full_attention`` YaRN's (``layers.rope_frequencies``), cos and sin times its
          ``attention_factor``; softmax(q k^T / sqrt(head_dim)) over the keys ``j <= i`` and,
          in a sliding layer, ``i - j < sliding_window``; W_o
          (``ops/pallas/flash_attention.py``: one kernel, whose tile schedule is a band there)
    MoE   logits = u W_r in float32 over all ``router_width`` experts, softmax, the k largest,
          renormalised over the chosen (``norm_topk_prob``); expert e is
          W_down_e (silu(W_gate_e u) * W_up_e u); the weighted sum; no shared expert
          (``parallel/moe.DroplessMoE``: the held range, its experts standing in for the absent)
    loss  mean cross-entropy + ``router_aux_loss_coef`` x the layers' mean load-balancing term

The per-head norm of q and k is the Qwen3-MoE family's, whose key set this model's is; the
published keys name none for it. Not here: a dense MLP layer (``mlp_layer_types`` other than
``sparse``: refused), ``max_window_layers`` (refused unless 0), the multi-token-prediction
head the model card mentions (no key describes it), dropout. Packed documents are not masked
at their boundaries: the attention runs across them.

The model follows the repo's convention (``init(rng) -> params``, ``apply(params, tokens[,
labels])``) and goes through ``deepspeed_tpu.initialize`` like the other models.
"""

import functools
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..runtime.activation_checkpointing.checkpointing import checkpoint_wrapper
from .layers import chunked_cross_entropy, rms_norm, rope, rope_frequencies

SLIDING, FULL = "sliding_attention", "full_attention"
# the scope of a layer's whole mixer by kind, INSIDE ``ds_attn``
SCOPE = {SLIDING: "ds_attn_window", FULL: "ds_attn_full"}


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    layer_types: tuple = ()                # a kind a layer; the first num_hidden_layers run
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    use_sliding_window: bool = True
    rope_parameters: dict = field(default_factory=dict)      # by kind: rope_type, rope_theta, ...
    # experts: ``num_experts`` are held here, experts ``first_expert`` onwards of the
    # ``router_width`` the router chooses among (None: all are held)
    num_experts: int = 64
    router_width: Optional[int] = None
    first_expert: int = 0
    # the held experts stand in for the absent ones (``DroplessMoE``'s ``stand_in``)
    stand_in: bool = False
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    router_aux_loss_coef: float = 0.001    # no published key
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    remat: bool = False            # whole layers made again in the backward: a layer keeps KEPT_BY_A_LAYER
    compute_dtype: Any = jnp.bfloat16

    @classmethod
    def from_published(cls, keys, **more):
        """From the keys of the model's ``config.json``; keys that say nothing this model
        could do otherwise are checked, not stored."""
        depth = keys.get("num_hidden_layers", cls.num_hidden_layers)
        kinds = tuple(keys["layer_types"])
        assert len(kinds) >= depth, f"layer_types names {len(kinds)} of {depth} layers"
        unknown = set(kinds) - {SLIDING, FULL}
        assert not unknown, f"layer_types: unknown kinds {sorted(unknown)}"
        dense = set(keys.get("mlp_layer_types", ())) - {"sparse"}
        assert not dense, f"mlp_layer_types: a {sorted(dense)} MLP layer is not built"
        assert keys.get("max_window_layers", 0) == 0, \
            f"max_window_layers {keys['max_window_layers']}: only 0 is built"
        for kind in set(kinds[:depth]):
            how = keys["rope_parameters"][kind].get("rope_type", "default")
            assert how in ("default", "yarn"), f"rope_parameters[{kind}]: rope_type {how!r} is not built"
        assert keys.get("hidden_act", "silu") == "silu", keys.get("hidden_act")
        assert not keys.get("attention_bias", False), "attention_bias: no biases"
        assert not keys.get("tie_word_embeddings", False), "the head is its own table"
        stored = {k: v for k, v in keys.items() if k in cls.__dataclass_fields__}
        return cls(**dict(stored, layer_types=kinds, **more))

    @property
    def kinds(self):
        return tuple(self.layer_types[:self.num_hidden_layers])

    def window_of(self, kind):
        return self.sliding_window if kind == SLIDING and self.use_sliding_window else None


# What a recomputed layer keeps beside its input, by name: the flash kernel's output and row
# sums (named in its forward rule: a layer's backward runs no second forward kernel) and the held
# experts' first grouped product's output (named in ``parallel/moe.py``, whose own checkpoint keeps
# it for the rows' backward: kept here, the second forward gathers no row and runs neither grouped
# product; nothing in a backward reads the second product's output since the router's weights go
# to the rows before ``w_down``, PR 49). The projections' outputs are named too (``attn_q``,
# ``attn_kv``) and NOT kept: 0.28 GB that bought nothing on a v5e.
# Bytes and milliseconds a name: docs/mellum2.md, PERF.md (PR 45, PR 49).
KEPT_BY_A_LAYER = jax.checkpoint_policies.save_only_these_names("attn_out", "attn_lse", "ds_moe_gate_up")


def _dot(x, w):
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)


class MellumModel:
    # what ``apply`` returns beside its loss, by name: per-layer device scalars the engine
    # keeps of every step, unfetched (``utils/spans.py``)
    device_scalars = ("moe_load_max_over_mean", "moe_rows_here")

    def __init__(self, config: MellumConfig):
        from ..parallel.moe import DroplessMoE
        self.config = c = config
        assert c.kinds, "layer_types is empty"
        self.moe = DroplessMoE(c.hidden_size, c.moe_intermediate_size,
                               c.router_width or c.num_experts, c.num_experts_per_tok,
                               norm_topk_prob=c.norm_topk_prob,
                               held=(c.first_expert, c.num_experts), stand_in=c.stand_in)
        # a rotary table a kind: (inv_freq [D / 2], what cos and sin are multiplied by)
        self.tables = {kind: rope_frequencies(c.head_dim, c.rope_parameters[kind]["rope_theta"],
                                              c.rope_parameters[kind])
                       for kind in set(c.kinds)}

    # ------------------------------------------------------------- init
    def init(self, rng):
        """Matrices N(0, ``initializer_range``); norms 1."""
        c = self.config
        H, s = c.hidden_size, c.initializer_range
        nq, nkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32) * s   # noqa: E731
        ones = lambda n=H: jnp.ones((n,), jnp.float32)                                  # noqa: E731
        keys = jax.random.split(rng, 2 + c.num_hidden_layers)
        layers = []
        for key in keys[2:]:
            k = jax.random.split(key, 4)
            layers.append({"norm_1": ones(), "wq": normal(k[0], H, nq * D),
                           "wkv": normal(k[1], H, 2 * nkv * D), "q_norm": ones(D),
                           "k_norm": ones(D), "wo": normal(k[2], nq * D, H),
                           "norm_2": ones(), "moe": self.moe.init(k[3], s)})
        return {"embed": normal(keys[0], c.vocab_size, H), "layers": layers,
                "norm_f": ones(), "head": normal(keys[1], c.vocab_size, H)}

    # ------------------------------------------------------------- layers
    def _norm(self, x, w):
        return rms_norm(x, w, self.config.rms_norm_eps)

    def attention(self, x, lp, kind):
        """The grouped-query attention of a layer of ``kind`` on the normed layer input
        ``x [B, T, H]``: a band of ``sliding_window`` keys under the plain rotary table, or
        the whole triangle under YaRN's."""
        from ..ops.pallas.flash_attention import flash_attention
        c = self.config
        B, T, _ = x.shape
        nq, nkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        heads = lambda a: a.transpose(0, 2, 1, 3)      # noqa: E731
        inv_freq, factor = self.tables[kind]
        turn = lambda a: rope(a, jnp.arange(T), None, inv_freq=inv_freq, factor=factor)   # noqa: E731
        with jax.named_scope(SCOPE[kind]):
            x = checkpoint_name(x, "ds_dot:qkv")      # the remat policies classify dots by tag
            q = checkpoint_name(_dot(x, lp["wq"]).astype(x.dtype), "attn_q").reshape(B, T, nq, D)
            x = checkpoint_name(x, "ds_dot:qkv")
            kv = checkpoint_name(_dot(x, lp["wkv"]).astype(x.dtype), "attn_kv")
            k, v = jnp.split(kv.reshape(B, T, 2 * nkv, D), 2, axis=2)
            q = turn(heads(self._norm(q, lp["q_norm"])))
            k = turn(heads(self._norm(k, lp["k_norm"])))
            y = flash_attention(q, k, heads(v), True, window=c.window_of(kind))
            y = checkpoint_name(heads(y).reshape(B, T, nq * D), "ds_dot:proj")
            return _dot(y, lp["wo"]).astype(x.dtype)

    def expert_layer(self, x, lp, details=False):
        """The expert layer on the normed layer input ``x [B, T, H]``: ``(y, aux, stats)``."""
        return self.moe.apply(lp["moe"], x, details)

    def _layer(self, x, lp, kind, details=False):
        """One layer: ``(y, aux, stats)``; with ``details`` ``stats`` holds both normed inputs."""
        with jax.named_scope("ds_attn"):
            n1 = self._norm(x, lp["norm_1"])
            h = x + self.attention(n1, lp, kind)
        # the expert layer is this layer's MLP: its ds_moe_* scopes nest under ds_mlp
        with jax.named_scope("ds_mlp"):
            n2 = self._norm(h, lp["norm_2"])
            m, aux, stats = self.expert_layer(n2, lp, details)
            if details:
                stats = dict(stats, attn_in=n1, expert_in=n2)
            return h + m, aux, stats

    def _backbone(self, params, tokens, details=False):
        """The last norm's output, the layers' mean load-balancing term, and the expert
        layers' stats stacked."""
        c = self.config
        with jax.named_scope("ds_embed"):
            x = params["embed"][tokens].astype(c.compute_dtype)
        aux, stats = jnp.zeros((), jnp.float32), []
        for kind, lp in zip(c.kinds, params["layers"]):
            layer = functools.partial(self._layer, kind=kind, details=details)
            if c.remat and not details:     # config-aware remat, as ``models/gpt2.py``'s blocks
                layer = checkpoint_wrapper(layer, policy=KEPT_BY_A_LAYER)
            x, a, s = layer(x, lp)
            aux = aux + a
            stats.append(s)
        with jax.named_scope("ds_loss"):      # the last norm feeds the head
            x = self._norm(x, params["norm_f"])
        stats = {name: jnp.stack([s[name] for s in stats]) for name in stats[0]}
        return x, aux / len(params["layers"]), stats

    # ------------------------------------------------------------- apply
    def _logits(self, params, x):
        with jax.named_scope("ds_loss"):
            return jnp.einsum("bth,vh->btv", x, params["head"].astype(x.dtype),
                              preferred_element_type=jnp.float32)

    def logits(self, params, tokens):
        return self._logits(params, self._backbone(params, tokens)[0])

    def forward_details(self, params, tokens, labels, last):
        """What a comparison with the plain reference reads: the loss and its parts, the
        logits of the ``last`` positions, every layer's two normed inputs ``[L, B, T, H]``,
        the experts chosen ``[L, B, T, k]`` and the router's logits ``[L, B, T, E]``."""
        x, aux, stats = self._backbone(params, tokens, details=True)
        with jax.named_scope("ds_loss"):
            ce = chunked_cross_entropy(x, params["head"], labels)
        return {"loss": ce + self.config.router_aux_loss_coef * aux, "ce": ce, "aux": aux,
                "logits": self._logits(params, x[:, -last:]), "attn_in": stats["attn_in"],
                "expert_in": stats["expert_in"], "experts": stats["experts"],
                "router_logits": stats["router_logits"]}

    def apply(self, params, tokens, labels=None):
        """Without labels: float32 logits. With labels: ``(loss, stats)`` — the mean token
        cross-entropy plus ``router_aux_loss_coef`` x the load-balancing term, and the
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
