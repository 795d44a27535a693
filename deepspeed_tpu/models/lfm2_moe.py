"""LFM2-24B-A2B (``model_type: lfm2_moe``): a pre-norm decoder whose layers mix through a GATED
SHORT CONVOLUTION or through grouped-query attention by ``layer_types`` (three to one), whose
first ``num_dense_layers`` layers have a dense gated MLP and the rest an expert layer without a
shared expert, and whose head is its embedding table.

    x0 = E[tokens];   layer l:   h = x + Op_l(rms(x) g_op);   y = h + FF_l(rms(h) g_ff)
    logits = (rms(x_L) g_emb) E^T

    Op, ``conv``             [B | C | z] = a W_in   (``hidden -> 3 x hidden``, split in that order)
          u = B * z;   v_t = sum_{j < L} w[j] u_{t-(L-1)+j}   (depthwise over ``conv_L_cache`` = L
          taps, zeros before the first token, no bias, no activation:
          ``ops/delta_rule.causal_conv``'s two Pallas kernels);   Op = (C * v) W_out
    Op, ``full_attention``   q = a W_q (``num_attention_heads`` of ``hidden / heads``), k, v = a
          W_k, a W_v (``num_key_value_heads``), no bias; q and k pass an RMSNorm over each head's
          features with a learned weight, then the rotary turn at ``rope_theta`` over all the
          head's features (half-split); causal softmax(q k^T / sqrt(head)) v through
          ``ops/pallas/flash_attention.py``;  W_o
    FF, l < num_dense_layers   W_2 (silu(W_1 m) * W_3 m), ``intermediate_size`` wide
    FF, else   s = sigmoid(m W_r) in float32 over all ``router_width`` experts; chosen = top-k of
          (s + b); w_e = ``routed_scaling_factor`` * s_e / (sum over chosen of s + ``router_eps``);
          sum over the chosen e THIS CHIP HOLDS of w_e W_down,e (silu(W_gate,e m) * W_up,e m)
          (``parallel/moe.DroplessMoE``: sigmoid router with a selection bias, gated experts)
    after a step:  b_e <- b_e + u * sign(mean_e'(c_e') - c_e),  c the step's assignments

The selection bias ``b`` is no weight: the model names it to the engine as a leaf updated by a
rule of its own (``rule_updated_leaves``, ``rule_sums``, ``apply_rule``, as
``models/nemotron_h.py`` and ``models/glm_moe.py``). The two gates of the short convolution run
outside its kernel: three passes over ``[tokens, hidden]`` arrays where a fused form would make
one (``ds_short_conv_gate`` is what the benchmark sizes that by). Not here: ``conv_bias`` true,
``use_expert_bias`` false, a scaled rotary table, an untied head (all refused), the
convolution's ``L - 1``-token cache beside a key/value cache on the served path, dropout. Packed
documents are not masked at their boundaries, in the attention or in the convolution.

The model follows the repo's convention (``init(rng) -> params``, ``apply(params, tokens[,
labels])``) and goes through ``deepspeed_tpu.initialize`` like the other models.
"""

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..runtime.activation_checkpointing.checkpointing import checkpoint_wrapper
from .layers import chunked_cross_entropy, rms_norm, rope

CONV, ATTENTION = "conv", "full_attention"
# a conv layer's whole operator, INSIDE ``ds_attn``, and inside that what lies between its two
# products (both gates and the convolution, ``ds_conv`` and its kernels inside)
SCOPE, GATE_SCOPE = "ds_short_conv", "ds_short_conv_gate"
RULE_SCOPE = "ds_moe_bias_update"


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: tuple = ()                # a kind a layer; the first num_hidden_layers run
    num_dense_layers: int = 2
    conv_L_cache: int = 3                  # the short convolution's taps
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1000000.0
    # the dense layers' MLP
    intermediate_size: int = 11776
    # experts: ``num_experts`` are held here, experts ``first_expert`` onwards of the
    # ``router_width`` the router chooses among (None: all are held)
    num_experts: int = 64
    router_width: Optional[int] = None
    first_expert: int = 0
    # the held experts stand in for the absent ones (``DroplessMoE``'s ``stand_in``)
    stand_in: bool = False
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    router_eps: float = 1e-6                 # of the renormalisation; no published key
    bias_update_rate: float = 1e-3           # u of the rule; no published key
    norm_eps: float = 1e-5
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
        unknown = set(kinds) - {CONV, ATTENTION}
        assert not unknown, f"layer_types: unknown kinds {sorted(unknown)}"
        assert not keys.get("conv_bias", False), "conv_bias: the convolution has no bias"
        assert keys.get("use_expert_bias", True), "use_expert_bias false: the router's selection bias is built in"
        turn = keys.get("rope_parameters", {})
        assert turn.get("rope_type", "default") == "default", f"rope_type {turn['rope_type']!r} is not built"
        assert keys.get("tie_embedding", True) and keys.get("tie_word_embeddings", True), \
            "an untied head: the head is the embedding table"
        assert keys.get("num_dense_layers", cls.num_dense_layers) < depth, \
            "every layer dense: no expert layer for the rule to move"
        stored = {k: v for k, v in keys.items() if k in cls.__dataclass_fields__}
        c = cls(**dict(stored, layer_types=kinds, rope_theta=turn.get("rope_theta", cls.rope_theta), **more))
        assert c.hidden_size % c.num_attention_heads == 0, "hidden_size is no whole number of heads"
        return c

    @property
    def kinds(self):
        return tuple(self.layer_types[:self.num_hidden_layers])

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def is_dense(self, l):
        return l < self.num_dense_layers


# What a recomputed layer keeps beside its input, by name: the flash kernel's output and row
# sums (named in its forward rule: a layer's backward runs no second forward kernel), the held
# experts' first grouped product's output (named in ``parallel/moe.py``: kept here, the second
# forward gathers no row and runs neither grouped product; no backward reads the second product's
# output, PR 49) and the short convolution's first product's output (``short_conv_in``, 101 MB a
# conv layer: the second forward runs no ``W_in`` product, 5.4 ms a step of 250 on a v5e, and the
# allocator's peak does not move). Named too and NOT kept: the attention's projections
# (``attn_q``, ``attn_kv``), the dense MLP's first product (``dense_gate_up``).
# Bytes and milliseconds a name: docs/lfm2-24b-a2b.md, PERF.md (PR 52).
KEPT_BY_A_LAYER = jax.checkpoint_policies.save_only_these_names(
    "attn_out", "attn_lse", "ds_moe_gate_up", "short_conv_in")


def _dot(x, w):
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)


class Lfm2MoeModel:
    # what ``apply`` returns beside its loss, by name: per-layer device scalars the engine
    # keeps of every step, unfetched (``utils/spans.py``)
    device_scalars = ("moe_load_max_over_mean", "moe_rows_here", "moe_bias_abs_max")
    # the leaves this model updates by a rule of its own (patterns over leaf paths), and the
    # entries of ``apply``'s dict that the rule reads, summed over a step by the engine
    rule_updated_leaves = (r"moe/router_bias$",)
    rule_sums = ("moe_counts",)

    def __init__(self, config: Lfm2MoeConfig):
        from ..parallel.moe import SILU_GATED, DroplessMoE
        self.config = c = config
        assert len(c.kinds) == c.num_hidden_layers, "layer_types is shorter than the depth"
        self.moe = DroplessMoE(c.hidden_size, c.moe_intermediate_size,
                               c.router_width or c.num_experts, c.num_experts_per_tok,
                               norm_topk_prob=c.norm_topk_prob,
                               held=(c.first_expert, c.num_experts), stand_in=c.stand_in,
                               router=("sigmoid_bias", c.routed_scaling_factor, c.router_eps),
                               experts=SILU_GATED)

    # ------------------------------------------------------------- init
    def init(self, rng):
        """Matrices and taps N(0, ``initializer_range``); norms 1; the selection biases zero."""
        c = self.config
        H, s, D = c.hidden_size, c.initializer_range, c.head_dim
        nq, nkv = c.num_attention_heads, c.num_key_value_heads
        normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32) * s   # noqa: E731
        ones = lambda n=H: jnp.ones((n,), jnp.float32)                                  # noqa: E731
        keys = jax.random.split(rng, 1 + c.num_hidden_layers)
        layers = []
        for l, (kind, key) in enumerate(zip(c.kinds, keys[1:])):
            k = jax.random.split(key, 5)
            layer = {"norm_1": ones(), "norm_2": ones()}
            if kind == CONV:
                layer["conv"] = {"w_in": normal(k[0], H, 3 * H), "conv_w": normal(k[1], c.conv_L_cache, H),
                                 "w_out": normal(k[2], H, H)}
            else:
                layer["attn"] = {"wq": normal(k[0], H, nq * D), "wkv": normal(k[1], H, 2 * nkv * D),
                                 "q_norm": ones(D), "k_norm": ones(D), "wo": normal(k[2], nq * D, H)}
            if c.is_dense(l):
                layer["mlp"] = {"w_gate_up": normal(k[3], H, 2 * c.intermediate_size),
                                "w_down": normal(k[4], c.intermediate_size, H)}
            else:
                layer["moe"] = self.moe.init(k[3], s)
            layers.append(layer)
        return {"embed": normal(keys[0], c.vocab_size, H), "layers": layers, "norm_f": ones()}

    # ------------------------------------------------------------- layers
    def _norm(self, x, w):
        return rms_norm(x, w, self.config.norm_eps)

    def short_conv(self, x, cp):
        """The gated short convolution on the normed layer input ``x [B, T, H]``: the product
        ``B * z`` is a buffer of its own (no window of the projection's output), which the
        kernels read; both gates are plain elementwise passes in the compute dtype."""
        from ..ops.delta_rule import causal_conv
        with jax.named_scope(SCOPE):
            x = checkpoint_name(x, "ds_dot:qkv")      # the remat policies classify dots by tag
            bcz = checkpoint_name(_dot(x, cp["w_in"]).astype(x.dtype), "short_conv_in")
            with jax.named_scope(GATE_SCOPE):
                b, c, z = jnp.split(bcz, 3, axis=-1)
                y = c * causal_conv(b * z, cp["conv_w"])
            y = checkpoint_name(y, "ds_dot:proj")
            return _dot(y, cp["w_out"]).astype(x.dtype)

    def attention(self, x, ap):
        """The grouped-query attention on the normed layer input ``x [B, T, H]``."""
        from ..ops.pallas.flash_attention import flash_attention
        c = self.config
        B, T, _ = x.shape
        nq, nkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        heads = lambda a: a.transpose(0, 2, 1, 3)      # noqa: E731
        turn = lambda a: rope(a, jnp.arange(T), c.rope_theta)      # noqa: E731
        x = checkpoint_name(x, "ds_dot:qkv")
        q = checkpoint_name(_dot(x, ap["wq"]).astype(x.dtype), "attn_q").reshape(B, T, nq, D)
        x = checkpoint_name(x, "ds_dot:qkv")
        kv = checkpoint_name(_dot(x, ap["wkv"]).astype(x.dtype), "attn_kv")
        k, v = jnp.split(kv.reshape(B, T, 2 * nkv, D), 2, axis=2)
        q = turn(heads(self._norm(q, ap["q_norm"])))
        k = turn(heads(self._norm(k, ap["k_norm"])))
        y = flash_attention(q, k, heads(v), True)
        y = checkpoint_name(heads(y).reshape(B, T, nq * D), "ds_dot:proj")
        return _dot(y, ap["wo"]).astype(x.dtype)

    def dense_mlp(self, x, mp):
        """A dense layer's gated MLP on the normed input ``x [B, T, H]``, gate and up side by
        side in ``w_gate_up``; the activation between the products is float32."""
        gate, up = jnp.split(checkpoint_name(_dot(x, mp["w_gate_up"]).astype(x.dtype),
                                             "dense_gate_up").astype(jnp.float32), 2, axis=-1)
        return _dot((jax.nn.silu(gate) * up).astype(x.dtype), mp["w_down"]).astype(x.dtype)

    def expert_layer(self, x, mp, details=False):
        """The held experts' part of the routed result on the normed input ``x [B, T, H]``:
        ``(y, stats)``; no shared expert."""
        y, _, stats = self.moe.apply(mp, x, details)
        stats["bias_abs_max"] = jnp.max(jnp.abs(jax.lax.stop_gradient(
            mp["router_bias"]).astype(jnp.float32)))
        return y, stats

    def _layer(self, x, lp, details=False):
        """One layer: ``(y, stats)``; ``stats`` is empty for a dense layer, and with ``details``
        holds both normed inputs."""
        with jax.named_scope("ds_attn"):
            n1 = self._norm(x, lp["norm_1"])
            h = x + (self.short_conv(n1, lp["conv"]) if "conv" in lp else self.attention(n1, lp["attn"]))
        # an expert layer is its layer's MLP: its ds_moe_* scopes nest under ds_mlp
        with jax.named_scope("ds_mlp"):
            n2 = self._norm(h, lp["norm_2"])
            if "mlp" in lp:
                m, stats = self.dense_mlp(n2, lp["mlp"]), {}
            else:
                m, stats = self.expert_layer(n2, lp["moe"], details)
            return h + m, (dict(stats, op_in=n1, ff_in=n2) if details else stats)

    def _backbone(self, params, tokens, details=False):
        """The last norm's output and every layer's stats, in the layers' order."""
        c = self.config
        with jax.named_scope("ds_embed"):
            x = params["embed"][tokens].astype(c.compute_dtype)
        stats = []
        for lp in params["layers"]:
            layer = functools.partial(self._layer, details=details)
            if c.remat and not details:     # config-aware remat, as ``models/gpt2.py``'s blocks
                layer = checkpoint_wrapper(layer, policy=KEPT_BY_A_LAYER)
            x, s = layer(x, lp)
            stats.append(s)
        with jax.named_scope("ds_loss"):      # the last norm feeds the head
            x = self._norm(x, params["norm_f"])
        return x, stats

    # ------------------------------------------------------------- apply
    def _logits(self, params, x):
        with jax.named_scope("ds_loss"):      # the head is the embedding table
            return jnp.einsum("bth,vh->btv", x, params["embed"].astype(x.dtype),
                              preferred_element_type=jnp.float32)

    def logits(self, params, tokens):
        return self._logits(params, self._backbone(params, tokens)[0])

    def _stacked(self, stats, name):
        return jnp.stack([s[name] for s in stats if name in s])

    def expert_counts(self, params, tokens):
        """``[Le, E]`` float32: the assignments of ``tokens [B, T]`` to every expert of every
        expert layer, in the layers' order (what the rule reads; no head, no loss)."""
        return self._stacked(self._backbone(params, tokens)[1], "counts")

    def forward_details(self, params, tokens, labels, last):
        """What a comparison with the plain reference reads: the loss, the logits of the ``last``
        positions, every layer's two normed inputs ``[L, B, T, H]``, and of the expert layers, in
        their order, the choices ``[Le, B, T, k]``, the router's logits and the counts ``[Le, E]``."""
        x, stats = self._backbone(params, tokens, details=True)
        with jax.named_scope("ds_loss"):
            loss = chunked_cross_entropy(x, params["embed"], labels)
        return {"loss": loss, "logits": self._logits(params, x[:, -last:]),
                "op_in": self._stacked(stats, "op_in"), "ff_in": self._stacked(stats, "ff_in"),
                "experts": self._stacked(stats, "experts"),
                "router_logits": self._stacked(stats, "router_logits"),
                "counts": self._stacked(stats, "counts")}

    def apply(self, params, tokens, labels=None):
        """Without labels: float32 logits. With labels: ``(loss, stats)`` — the mean token
        cross-entropy, the expert layers' per-layer device scalars (``device_scalars``) and the
        step's assignments to every expert of every expert layer (``moe_counts`` ``[Le, E]``:
        what ``apply_rule`` reads, summed over a step by the engine)."""
        x, stats = self._backbone(params, tokens)
        if labels is None:
            return self._logits(params, x)
        with jax.named_scope("ds_loss"):
            loss = chunked_cross_entropy(x, params["embed"], labels)
        load = self._stacked(stats, "load_max_over_mean")
        # with every expert held (no cut) every assignment lands here
        every = jnp.full_like(load, tokens.size * self.config.num_experts_per_tok)
        rows = self._stacked(stats, "rows_here") if self.moe.held is not None else every
        return loss, {"moe_load_max_over_mean": load, "moe_rows_here": rows,
                      "moe_bias_abs_max": self._stacked(stats, "bias_abs_max"),
                      "moe_counts": self._stacked(stats, "counts")}

    # ------------------------------------------------------------- the rule
    def apply_rule(self, leaves, sums):
        """The selection biases after a step: ``leaves`` is the parameter tree with every
        leaf but the named ones None (float32, the master's), ``sums["moe_counts"]``
        ``[Le, E]`` the step's assignments, an expert layer a row in the layers' order:
        ``b_e + u * sign(mean(c) - c_e)``. Returns ``leaves``' tree."""
        u = self.config.bias_update_rate
        with jax.named_scope(RULE_SCOPE):
            biases, treedef = jax.tree_util.tree_flatten(leaves)
            counts = sums["moe_counts"]
            assert counts.shape[0] == len(biases), (counts.shape, len(biases))
            moved = [b + u * jnp.sign(jnp.mean(c) - c).astype(b.dtype)
                     for b, c in zip(biases, counts)]
            return jax.tree_util.tree_unflatten(treedef, moved)
