"""Nemotron-H (``model_type: nemotron_h``; the language model of
Nemotron-Labs-TwoTower-30B-A3B): a pre-norm decoder whose every layer is ONE of a Mamba-2
mixer (``M``), an expert layer (``E``) or a grouped-query attention (``*``), by
``hybrid_override_pattern``; one norm a layer, no biases but the convolution's, untied head.

    x0 = E[tokens];   layer l of kind pattern[l]:   x <- x + f_l(rms(x) * w_l)
    logits = (rms(x_L) * w_f) W_head

    M  [z | xBC | dt] = W_in x                                       (no bias)
       xBC = silu(causal depthwise conv of width ``conv_kernel``, with bias);  [xs | B | C] = xBC
       B, C: ``n_groups`` of ``ssm_state_size``; head h reads group h // (heads / n_groups)
       dt = softplus(dt + dt_bias);  A = -exp(A_log)                 (float32, a head)
       S_t = exp(dt_t A_h) S_{t-1} + dt_t xs_t B_{g(h),t}^T;   y_t = S_t C_{g(h),t} + D_h xs_t
                                                                     (``ops/ssd.py``, chunked)
       W_out (rms_over_each_group's_channels(y * silu(z)) * w_norm)  (gate, THEN norm)
    *  q = W_q x (``num_attention_heads`` of ``head_dim``), k, v = W_k x, W_v x
       (``num_key_value_heads``), no bias and NO positional term; causal
       softmax(q k^T / sqrt(head_dim)) v; W_o
    E  s = sigmoid(W_r x) in float32 over all ``router_width`` experts; chosen = top-k of
       (s + b); p_e = ``routed_scaling_factor`` * s_e / (sum over chosen of s + 1e-20)
       y = sum over the chosen e THIS CHIP HOLDS of p_e W_down_e relu(W_up_e x)^2
           + W_down_s relu(W_up_s x)^2                               (the shared expert, ungated)
       (``parallel/moe.DroplessMoE``: sigmoid router with a selection bias, ``relu2`` experts)
    after a step:  b_e <- b_e + u * sign(mean_e'(c_e') - c_e),  c the step's assignments

The selection bias ``b`` is no weight: no gradient reaches it and no optimizer moves it. The
model names it to the engine as a leaf updated by a rule of its own (``rule_updated_leaves``,
``rule_sums``, ``apply_rule``: ``docs/nemotron_h.md``, ``runtime/engine.py``), which sums the
counts over a step's micro-batches and calls the rule once a step inside the update program.
There is no auxiliary load-balancing loss (the published keys name no coefficient).

This follows the published keys and the family's modelling code. The column order inside
``w_in`` ([z | xBC | dt]) and the convolution's channels ([xs | B | C]) are the published
ones; the fused ``wkv`` ([k | v], heads of k first) is this file's own. Not here: the second
tower (an adaLN denoiser conditioned on this one, bidirectional in-block attention, block
diffusion: no key of the ``config.json`` describes them), a dense MLP layer (``-`` in other
models of the family: refused), ``n_group > 1`` (the group-limited choice), dropout. Packed
documents are not masked at their boundaries: the state and the attention run across them.

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
from .granite_hybrid import inverse_softplus
from .layers import chunked_cross_entropy, rms_norm

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
RULE_SCOPE = "ds_moe_bias_update"


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = ""        # a character a layer; the first num_hidden_layers run
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # mamba
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    # experts: ``n_routed_experts`` are held here, experts ``first_expert`` onwards of the
    # ``router_width`` the router chooses among (None: all are held)
    n_routed_experts: int = 128
    router_width: Optional[int] = None
    first_expert: int = 0
    # the held experts stand in for the absent ones (``DroplessMoE``'s ``stand_in``): every
    # assignment is computed here, the rows a deployment's exchange brings a chip
    stand_in: bool = False
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    bias_update_rate: float = 1e-3           # u of the rule; no published key
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    remat: bool = False            # whole layers made again in the backward: a layer keeps KEPT_BY_A_LAYER
    compute_dtype: Any = jnp.bfloat16

    @classmethod
    def from_published(cls, keys, **more):
        """From the keys of the model's ``config.json``; keys that say nothing this model
        could do otherwise are checked, not stored."""
        assert keys.get("mlp_hidden_act", "relu2") == "relu2", keys.get("mlp_hidden_act")
        assert keys.get("mamba_hidden_act", "silu") == "silu", keys.get("mamba_hidden_act")
        assert not any(keys.get(k, False) for k in ("use_bias", "mlp_bias", "attention_bias",
                                                    "mamba_proj_bias")), "no biases"
        assert keys.get("use_conv_bias", True), "the convolution carries its bias"
        assert not keys.get("tie_word_embeddings", False), "the head is its own table"
        assert keys.get("n_group", 1) == 1 and keys.get("topk_group", 1) == 1, \
            "the group-limited choice is not built"
        assert keys.get("n_shared_experts", 1) == 1, "one shared expert"
        low, high = keys.get("time_step_limit", (0, None))
        assert not low and high is None, "dt is not clamped"
        depth = keys.get("num_hidden_layers", cls.num_hidden_layers)
        pattern = keys.get("hybrid_override_pattern", "")
        unknown = set(pattern) - {MAMBA, EXPERTS, ATTENTION}
        assert not unknown, f"unknown layer kinds {sorted(unknown)} in hybrid_override_pattern"
        assert len(pattern) >= depth, f"a pattern of {len(pattern)} for {depth} layers"
        stored = {k: v for k, v in keys.items() if k in cls.__dataclass_fields__}
        return cls(**dict(stored, **more))

    @property
    def kinds(self):
        return self.hybrid_override_pattern[:self.num_hidden_layers]

    @property
    def mamba_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim


# What a recomputed layer keeps beside its input, by name: the flash kernel's output and row
# sums (named in its forward rule), the Mamba-2 mixers' first product's output as the forward
# leaves it (the compute dtype's copy and the float32 ``dt`` columns), the shared expert's
# first product's output before its activation, and the held experts' first grouped product's
# output where they stand in for the absent ones (named in ``parallel/moe.py``, whose own
# checkpoint keeps it for the rows' backward: kept here, the second forward gathers no row for
# it and runs no ``w_up``; and no ``w_down`` and no gather back either since the router's weights
# go to the rows before ``w_down``, PR 49). Every layer ends ``x + f(norm(x))``: nothing in a
# layer's backward reads its LAST product's output, so the second forward never ran one.
# Bytes and what each buys on a v5e: docs/nemotron-h.md, PERF.md (PR 41, PR 42, PR 49).
KEPT_BY_A_LAYER = jax.checkpoint_policies.save_only_these_names(
    "attn_out", "attn_lse", "ssm_in", "ssm_dt", "shared_up", "ds_moe_gate_up")


def _dot(x, w):
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


class NemotronHModel:
    # what ``apply`` returns beside its loss, by name: per-layer device scalars the engine
    # keeps of every step, unfetched (``utils/spans.py``)
    device_scalars = ("moe_load_max_over_mean", "moe_rows_here", "moe_bias_abs_max")
    # the leaves this model updates by a rule of its own (patterns over leaf paths), and the
    # entries of ``apply``'s dict that the rule reads, summed over a step by the engine
    rule_updated_leaves = (r"moe/router_bias$",)
    rule_sums = ("moe_counts",)

    def __init__(self, config: NemotronHConfig):
        from ..parallel.moe import RELU2, DroplessMoE
        self.config = c = config
        assert EXPERTS in c.kinds, "the layers run hold no expert layer: nothing for the rule to move"
        self.moe = DroplessMoE(c.hidden_size, c.moe_intermediate_size,
                               c.router_width or c.n_routed_experts, c.num_experts_per_tok,
                               norm_topk_prob=c.norm_topk_prob,
                               held=(c.first_expert, c.n_routed_experts), stand_in=c.stand_in,
                               router=("sigmoid_bias", c.routed_scaling_factor), experts=RELU2)

    # ------------------------------------------------------------- init
    def init(self, rng):
        """Matrices N(0, ``initializer_range``); the Mamba-2 family's initialisation of the
        rest (``A_log = log(1..heads)``, ``D = 1``, ``dt_bias`` the inverse softplus of a step
        drawn log-uniform in [0.001, 0.1], the convolution U(-W^-1/2, W^-1/2)); norms 1; the
        selection bias zero."""
        c = self.config
        H, s = c.hidden_size, c.initializer_range
        normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32) * s   # noqa: E731
        ones = lambda n=H: jnp.ones((n,), jnp.float32)                                  # noqa: E731
        heads, inner, W = c.mamba_num_heads, c.mamba_inner, c.conv_kernel
        conv = inner + 2 * c.n_groups * c.ssm_state_size
        nq, nkv, D, S = (c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                         c.moe_shared_expert_intermediate_size)
        keys = jax.random.split(rng, 2 + c.num_hidden_layers)
        layers = []
        for kind, key in zip(c.kinds, keys[2:]):
            k = jax.random.split(key, 5)
            if kind == EXPERTS:
                layers.append({"norm": ones(), "moe": self.moe.init(k[0], s),
                               "shared": {"w_up": normal(k[1], H, S), "w_down": normal(k[2], S, H)}})
                continue
            if kind == ATTENTION:
                mixer = {"wq": normal(k[0], H, nq * D), "wkv": normal(k[1], H, 2 * nkv * D),
                         "wo": normal(k[2], nq * D, H)}
            else:
                step = jnp.exp(jax.random.uniform(k[3], (heads,), jnp.float32,
                                                  jnp.log(1e-3), jnp.log(1e-1)))
                mixer = {"w_in": normal(k[0], H, inner + conv + heads),
                         "conv_w": jax.random.uniform(k[1], (W, conv), jnp.float32,
                                                      -W ** -0.5, W ** -0.5),
                         "conv_b": jax.random.uniform(k[2], (conv,), jnp.float32,
                                                      -W ** -0.5, W ** -0.5),
                         "dt_bias": inverse_softplus(step),
                         "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
                         "D": ones(heads), "norm": ones(inner), "w_out": normal(k[4], inner, H)}
            layers.append({"norm": ones(), "mixer": mixer})
        return {"embed": normal(keys[0], c.vocab_size, H), "layers": layers,
                "norm_f": ones(), "head": normal(keys[1], c.vocab_size, H)}

    # ------------------------------------------------------------- layers
    def _norm(self, x, w):
        return rms_norm(x, w, self.config.layer_norm_epsilon)

    def mamba_inputs(self, x, mp):
        """What the scan of one mixer is given, from the normed layer input ``x [B, T, H]``:
        ``xs [B, T, heads, P]``, ``dt [B, T, heads]`` (float32, after its softplus), ``B``,
        ``C`` ``[B, T, G, N]`` and the gate ``z [B, T, heads * P]``."""
        from ..ops.delta_rule import causal_conv
        c = self.config
        B, T, _ = x.shape
        inner, G, N = c.mamba_inner, c.n_groups, c.ssm_state_size
        x = checkpoint_name(x, "ds_dot:qkv")      # the remat policies classify dots by tag
        proj = _dot(x, mp["w_in"])                                            # float32
        # dt is read off the float32 product; both of the product's readers are named, so a
        # layer that keeps them runs no second product
        dt = jax.nn.softplus(checkpoint_name(proj[..., 2 * inner + 2 * G * N:], "ssm_dt") + mp["dt_bias"])
        # the gate and the convolution's input in the compute dtype, where the projection
        # leaves them: the convolution reads its columns in place
        proj = checkpoint_name(proj.astype(x.dtype), "ssm_in")
        z = proj[..., :inner]
        xBC = causal_conv(proj, mp["conv_w"], True, mp["conv_b"],
                          columns=(inner, 2 * inner + 2 * G * N))
        xs, Bm, Cm = jnp.split(xBC, [inner, inner + G * N], axis=-1)
        return (xs.reshape(B, T, c.mamba_num_heads, c.mamba_head_dim), dt,
                Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N), z)

    def _gated_norm(self, y, z, w):
        """``rms(y * silu(z)) * w`` over each of the ``n_groups`` groups of channels in
        float32 (the gate first, then the norm); made again in the backward from ``y`` and
        ``z`` as they are stored."""
        c = self.config

        def gated(y, z, w):
            g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
            g = g.reshape(g.shape[:-1] + (c.n_groups, -1))
            g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + c.layer_norm_epsilon)
            return (g.reshape(y.shape) * w.astype(jnp.float32)).astype(z.dtype)
        return jax.checkpoint(gated)(y, z, w)

    def mamba_mixer(self, x, mp):
        """The Mamba-2 mixer on the normed layer input ``x [B, T, H]``."""
        from ..ops.ssd import ssd_scan
        c = self.config
        B, T, _ = x.shape
        with jax.named_scope("ds_ssm"):
            xs, dt, Bm, Cm, z = self.mamba_inputs(x, mp)
            y = ssd_scan(xs, dt, -jnp.exp(mp["A_log"].astype(jnp.float32)), Bm, Cm, mp["D"],
                         c.chunk_size)
            y = self._gated_norm(y.reshape(B, T, c.mamba_inner), z, mp["norm"])
            y = checkpoint_name(y, "ds_dot:proj")
            return _dot(y, mp["w_out"]).astype(x.dtype)

    def attention(self, x, mp):
        """The position-free grouped-query attention on the normed layer input ``x [B, T, H]``."""
        from ..ops.pallas.flash_attention import flash_attention_rows
        c = self.config
        nq, nkv = c.num_attention_heads, c.num_key_value_heads
        x = checkpoint_name(x, "ds_dot:qkv")
        q = _dot(x, mp["wq"]).astype(x.dtype)
        x = checkpoint_name(x, "ds_dot:qkv")
        k, v = jnp.split(_dot(x, mp["wkv"]).astype(x.dtype), 2, axis=-1)     # nkv heads each, side by side
        y = checkpoint_name(flash_attention_rows(q, k, v, nq, nkv, True), "ds_dot:proj")
        return _dot(y, mp["wo"]).astype(x.dtype)

    def expert_layer(self, x, lp, details=False):
        """The held experts' part plus the shared expert, ungated: ``(y, stats)``."""
        y, _, stats = self.moe.apply(lp["moe"], x, details)
        with jax.named_scope("ds_moe_shared"):
            sp = lp["shared"]
            hidden = _relu2(checkpoint_name(_dot(x, sp["w_up"]), "shared_up")).astype(x.dtype)
            shared = _dot(hidden, sp["w_down"])
        stats["bias_abs_max"] = jnp.max(jnp.abs(jax.lax.stop_gradient(
            lp["moe"]["router_bias"]).astype(jnp.float32)))
        return y + shared.astype(x.dtype), stats

    def _layer(self, x, lp, kind, details=False):
        """One layer: ``(x + f(norm(x)), stats)``; ``stats`` is empty but for an expert layer,
        and with ``details`` holds the normed input too."""
        # an expert layer is the block's MLP part, a mixer its attention part: the phase x
        # part table of ``utils/spans.py`` still sums
        with jax.named_scope("ds_mlp" if kind == EXPERTS else "ds_attn"):
            n = self._norm(x, lp["norm"])
            if kind == EXPERTS:
                y, stats = self.expert_layer(n, lp, details)
            else:
                mix = self.attention if kind == ATTENTION else self.mamba_mixer
                y, stats = mix(n, lp["mixer"]), {}
            return x + y, (dict(stats, layer_in=n) if details else stats)

    def _backbone(self, params, tokens, details=False):
        """The last norm's output, the expert layers' stats stacked in their order, and with
        ``details`` every layer's normed input ``[L, B, T, H]``."""
        c = self.config
        with jax.named_scope("ds_embed"):
            x = params["embed"][tokens].astype(c.compute_dtype)
        stats, seen = [], []
        for kind, lp in zip(c.kinds, params["layers"]):
            layer = functools.partial(self._layer, kind=kind, details=details)
            if c.remat and not details:     # config-aware remat, as ``models/gpt2.py``'s blocks
                layer = checkpoint_wrapper(layer, policy=KEPT_BY_A_LAYER)
            x, s = layer(x, lp)
            if details:
                seen.append(s.pop("layer_in"))
            if kind == EXPERTS:
                stats.append(s)
        with jax.named_scope("ds_loss"):      # the last norm feeds the head
            x = self._norm(x, params["norm_f"])
        stats = {name: jnp.stack([s[name] for s in stats]) for name in stats[0]}
        return (x, stats, jnp.stack(seen)) if details else (x, stats)

    # ------------------------------------------------------------- apply
    def _logits(self, params, x):
        with jax.named_scope("ds_loss"):
            return jnp.einsum("bth,vh->btv", x, params["head"].astype(x.dtype),
                              preferred_element_type=jnp.float32)

    def expert_counts(self, params, tokens):
        """``[Le, E]`` float32: the assignments of ``tokens [B, T]`` to every expert of every
        expert layer, in the layers' order (what the rule reads; no loss, no head)."""
        return self._backbone(params, tokens)[1]["counts"]

    def forward_details(self, params, tokens, labels, last):
        """What a comparison with the plain reference reads: the loss, the logits of the
        ``last`` positions, every layer's normed input ``[L, B, T, H]``, and of the expert
        layers, in their order, the choices ``[Le, B, T, k]``, the router's logits ``[Le, B, T, E]``
        and the counts ``[Le, E]``."""
        x, stats, seen = self._backbone(params, tokens, details=True)
        with jax.named_scope("ds_loss"):
            loss = chunked_cross_entropy(x, params["head"], labels)
        return {"loss": loss, "logits": self._logits(params, x[:, -last:]), "layer_in": seen,
                "experts": stats.get("experts"), "counts": stats.get("counts"),
                "router_logits": stats.get("router_logits")}

    def apply(self, params, tokens, labels=None):
        """Without labels: float32 logits. With labels: ``(loss, stats)`` — the mean token
        cross-entropy, the expert layers' per-layer device scalars (``device_scalars``) and
        the step's assignments to every expert of every expert layer (``moe_counts``
        ``[Le, E]``: what ``apply_rule`` reads, summed over a step by the engine)."""
        x, stats = self._backbone(params, tokens)
        if labels is None:
            return self._logits(params, x)
        with jax.named_scope("ds_loss"):
            loss = chunked_cross_entropy(x, params["head"], labels)
        # with every expert held (no cut) every assignment lands here
        every = jnp.full_like(stats["load_max_over_mean"],
                              tokens.size * self.config.num_experts_per_tok)
        return loss, {"moe_load_max_over_mean": stats["load_max_over_mean"],
                      "moe_rows_here": stats.get("rows_here", every),
                      "moe_bias_abs_max": stats["bias_abs_max"],
                      "moe_counts": stats["counts"]}

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
