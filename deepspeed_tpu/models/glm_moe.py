"""GLM-4.7-Flash (``model_type: glm4_moe_lite``; 30B-A3B): a pre-norm decoder whose every block
mixes through LATENT attention (DeepSeek-V3's MLA, arXiv:2412.19437 section 2.1), whose first
``first_k_dense_replace`` blocks have a dense gated MLP and the rest an expert layer with a shared
expert, and which is trained with a second prediction depth (section 2.2: one multi-token-
prediction module on the shared embedding and head).

    x0 = E[tokens];   block l:   h = x + Attn(rms(x) g1);   y = h + MLP_l(rms(h) g2)
    logits = (rms(x_L) g_f) W_head;   L_1 = CE(logits_i, t_{i+1})

    Attn  c_q = rms(a W_qa) g_q  (``q_lora_rank`` wide);  q = c_q W_qb, a head [q_nope | q_rope]
          [c_kv | k_r] = a W_kva;  c_kv <- rms(c_kv) g_kv  (``kv_lora_rank`` wide; k_r ONE head of
          ``qk_rope_head_dim`` that all the heads share);  c_kv W_kvb, a head [k_nope | v]
          q_rope, k_r <- rope(., pos) at ``rope_theta`` over all ``qk_rope_head_dim`` features
          o_h = softmax([q_nope_h | q_rope_h] [k_nope_h | k_r]^T / sqrt(nope + rope) + causal) v_h
          (``ops/pallas/flash_attention.py`` at ``num_attention_heads`` query over as many
          key/value heads: k_r is broadcast to every head, its gradient their sum);  W_o
    MLP   l < first_k_dense_replace:  W_d (silu(W_g m) * W_u m), ``intermediate_size`` wide
          else  s = sigmoid(m W_r) in float32 over all ``router_width`` experts; chosen = top-k
          of (s + b); w_e = ``routed_scaling_factor`` * s_e / (sum over chosen of s + 1e-20);
          sum over the chosen e THIS CHIP HOLDS of w_e E_e(m) + E_shared(m), experts SiLU-gated
          (``parallel/moe.DroplessMoE``: sigmoid router with a selection bias, gated experts)
    MTP   h'_i = [rms(E[t_{i+1}]) g_e | rms(h_i) g_h] W_eh  with h the main model's LAST NORM's
          output; one more block of the expert kind on h'; logits' = (rms(.) g_s) W_head on the
          SAME E and W_head;  L_2 = CE(logits'_i, t_{i+2}) over the positions that have one
    loss = L_1 + ``mtp_loss_weight`` L_2
    after a step:  b_e <- b_e + u * sign(mean_e'(c_e') - c_e),  c the step's assignments

``t_{i+1}`` and ``t_{i+2}`` are ``labels`` and ``labels`` moved one position on (the last
position has no ``t_{i+2}`` and is left out of L_2). The selection bias ``b`` is no weight: the
model names it to the engine as a leaf updated by a rule of its own (``rule_updated_leaves``,
``rule_sums``, ``apply_rule``, as ``models/nemotron_h.py``). The rotary turn pairs feature ``i``
with ``i + rope / 2`` (``layers.rope``); the family's code pairs neighbours, which with seeded
weights is a fixed permutation of W_qb's and W_kva's rotary columns. The values may be narrower
than the keys (``v_head_dim`` beside ``qk_nope + qk_rope``: the flash kernel takes both widths
since PR 58), and ``attention`` takes a rotary table and a softmax scale from a caller that has
them (``models/xing_moe.py``: YaRN). Not here, all refused by ``from_published``: a published
``rope_scaling`` (this model builds no table of its own: ``glm4_moe_lite`` publishes null, and a
scaled table needs the family's own reading of ``mscale``), ``n_group > 1`` (the group-limited
choice), more than one prediction depth, attention biases; nor the latent cache and the absorbed
projections of the served path, dropout. Packed documents are not masked at their boundaries.

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

SCOPE, MTP_SCOPE = "ds_attn_latent", "ds_mtp"
RULE_SCOPE = "ds_moe_bias_update"


@dataclass
class GlmMoeConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 1
    # latent attention
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1000000.0
    # the dense blocks' MLP
    intermediate_size: int = 10240
    # experts: ``n_routed_experts`` are held here, experts ``first_expert`` onwards of the
    # ``router_width`` the router chooses among (None: all are held)
    n_routed_experts: int = 64
    router_width: Optional[int] = None
    first_expert: int = 0
    # the held experts stand in for the absent ones (``DroplessMoE``'s ``stand_in``)
    stand_in: bool = False
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    bias_update_rate: float = 1e-3           # u of the rule; no published key
    mtp_loss_weight: float = 0.3             # lambda of the second depth's loss; no published key
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    remat: bool = False            # whole blocks made again in the backward: a block keeps KEPT_BY_A_LAYER
    compute_dtype: Any = jnp.bfloat16

    @classmethod
    def from_published(cls, keys, **more):
        """From the keys of the model's ``config.json``; keys that say nothing this model
        could do otherwise are checked, not stored."""
        assert keys.get("rope_scaling") is None, \
            f"rope_scaling {keys['rope_scaling']}: this model builds no scaled table (hand ``attention`` one)"
        assert keys.get("n_group", 1) == 1 and keys.get("topk_group", 1) == 1, \
            "n_group > 1: the group-limited choice is not built"
        assert keys.get("num_nextn_predict_layers", 1) == 1, \
            f"num_nextn_predict_layers {keys['num_nextn_predict_layers']}: exactly one prediction module is built"
        assert not keys.get("attention_bias", False), "attention_bias: no biases"
        assert not keys.get("tie_word_embeddings", False), "the head is its own table"
        assert keys.get("hidden_act", "silu") == "silu", keys.get("hidden_act")
        assert keys.get("topk_method", "noaux_tc") == "noaux_tc", keys.get("topk_method")
        assert keys.get("partial_rotary_factor", 1) == 1, "the rotary turn covers all of qk_rope_head_dim"
        assert keys.get("n_shared_experts", 1) == 1, "one shared expert"
        heads = keys.get("num_attention_heads", cls.num_attention_heads)
        assert keys.get("num_key_value_heads", heads) == heads, "as many key/value heads as query heads"
        stored = {k: v for k, v in keys.items() if k in cls.__dataclass_fields__}
        return cls(**dict(stored, **more))

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def is_dense(self, l):
        return l < self.first_k_dense_replace


# What a recomputed block keeps beside its input, by name: the flash kernel's output and row
# sums (named in its forward rule: a block's backward runs no second forward kernel) and the held
# experts' first grouped product's output (named in ``parallel/moe.py``: kept here, the second
# forward gathers no row and runs neither grouped product; no backward reads the second product's
# output, PR 49). Named too and NOT kept: the latent projections' outputs (``attn_q``, ``attn_kv``),
# the dense MLP's and the shared expert's first products (``dense_gate_up``, ``shared_gate_up``).
# Bytes and milliseconds a name: docs/glm-4.7-flash.md, PERF.md (PR 48, PR 49).
KEPT_BY_A_LAYER = jax.checkpoint_policies.save_only_these_names("attn_out", "attn_lse", "ds_moe_gate_up")


def _dot(x, w):
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)


def _gated(x, w_gate_up, w_down, name):
    """``W_down (silu(W_gate x) * W_up x)``, gate and up side by side in ``w_gate_up``; the
    first product's output carries ``name`` in the compute dtype, as the routed experts' does,
    and the activation between the products is float32."""
    gate, up = jnp.split(checkpoint_name(_dot(x, w_gate_up).astype(x.dtype), name).astype(jnp.float32),
                         2, axis=-1)
    return _dot((jax.nn.silu(gate) * up).astype(x.dtype), w_down).astype(x.dtype)


class GlmMoeModel:
    # what ``apply`` returns beside its loss, by name: device scalars the engine keeps of
    # every step, unfetched (``utils/spans.py``): both depths' losses and the expert layers'
    device_scalars = ("loss_main", "loss_mtp", "moe_load_max_over_mean", "moe_rows_here")
    # the leaves this model updates by a rule of its own (patterns over leaf paths), and the
    # entries of ``apply``'s dict that the rule reads, summed over a step by the engine
    rule_updated_leaves = (r"moe/router_bias$",)
    rule_sums = ("moe_counts",)

    def __init__(self, config: GlmMoeConfig):
        from ..parallel.moe import SILU_GATED, DroplessMoE
        self.config = c = config
        self.moe = DroplessMoE(c.hidden_size, c.moe_intermediate_size,
                               c.router_width or c.n_routed_experts, c.num_experts_per_tok,
                               norm_topk_prob=c.norm_topk_prob,
                               held=(c.first_expert, c.n_routed_experts), stand_in=c.stand_in,
                               router=("sigmoid_bias", c.routed_scaling_factor), experts=SILU_GATED)

    # ------------------------------------------------------------- init
    def _init_block(self, rng, dense):
        c = self.config
        H, s, n = c.hidden_size, c.initializer_range, c.num_attention_heads
        normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32) * s   # noqa: E731
        ones = lambda width=H: jnp.ones((width,), jnp.float32)                        # noqa: E731
        k = jax.random.split(rng, 8)
        attn = {"wq_a": normal(k[0], H, c.q_lora_rank), "q_norm": ones(c.q_lora_rank),
                "wq_b": normal(k[1], c.q_lora_rank, n * c.qk_head_dim),
                "wkv_a": normal(k[2], H, c.kv_lora_rank + c.qk_rope_head_dim),
                "kv_norm": ones(c.kv_lora_rank),
                "wkv_b": normal(k[3], c.kv_lora_rank, n * (c.qk_nope_head_dim + c.v_head_dim)),
                "wo": normal(k[4], n * c.v_head_dim, H)}
        block = {"norm_1": ones(), "attn": attn, "norm_2": ones()}
        if dense:
            return dict(block, mlp={"w_gate_up": normal(k[5], H, 2 * c.intermediate_size),
                                    "w_down": normal(k[6], c.intermediate_size, H)})
        F = c.moe_intermediate_size
        return dict(block, moe=self.moe.init(k[5], s),
                    shared={"w_gate_up": normal(k[6], H, 2 * F), "w_down": normal(k[7], F, H)})

    def init(self, rng):
        """Matrices N(0, ``initializer_range``); norms 1; the selection biases zero."""
        c = self.config
        H, s = c.hidden_size, c.initializer_range
        keys = jax.random.split(rng, 4 + c.num_hidden_layers)
        params = {"embed": jax.random.normal(keys[0], (c.vocab_size, H), jnp.float32) * s,
                  "layers": [self._init_block(key, c.is_dense(l)) for l, key in enumerate(keys[4:])],
                  "norm_f": jnp.ones((H,), jnp.float32),
                  "head": jax.random.normal(keys[1], (c.vocab_size, H), jnp.float32) * s}
        params["mtp"] = {"norm_e": jnp.ones((H,), jnp.float32), "norm_h": jnp.ones((H,), jnp.float32),
                         "w_eh": jax.random.normal(keys[2], (2 * H, H), jnp.float32) * s,
                         "block": self._init_block(keys[3], dense=False),
                         "norm_s": jnp.ones((H,), jnp.float32)}
        return params

    # ------------------------------------------------------------- layers
    def _norm(self, x, w):
        return rms_norm(x, w, self.config.rms_norm_eps)

    def attention(self, x, ap, rotary=None, sm_scale=None):
        """The latent attention on the normed block input ``x [B, T, H]``; the values are
        ``v_head_dim`` wide beside keys of ``qk_nope + qk_rope`` (the kernel takes two widths).
        ``rotary`` is a table of ``layers.rope_frequencies`` ``(inv_freq, factor)`` in the place of
        ``rope_theta``'s, ``sm_scale`` the softmax's scale in the place of ``1 / sqrt(nope + rope)``
        (``models/xing_moe.py`` hands both: YaRN's frequencies and its ``m^2``)."""
        from ..ops.pallas.flash_attention import flash_attention_rows
        c = self.config
        B, T, _ = x.shape
        n, nope, turned, R = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.kv_lora_rank
        heads = lambda a: a.transpose(0, 2, 1, 3)      # noqa: E731
        if rotary is None:
            turn = lambda a: rope(a, jnp.arange(T), c.rope_theta)      # noqa: E731
        else:
            turn = lambda a: rope(a, jnp.arange(T), c.rope_theta, inv_freq=rotary[0], factor=rotary[1])      # noqa: E731
        with jax.named_scope(SCOPE):
            x = checkpoint_name(x, "ds_dot:qkv")      # the remat policies classify dots by tag
            c_q = self._norm(_dot(x, ap["wq_a"]).astype(x.dtype), ap["q_norm"])
            q = checkpoint_name(_dot(c_q, ap["wq_b"]).astype(x.dtype), "attn_q")
            q_nope, q_rope = jnp.split(heads(q.reshape(B, T, n, nope + turned)), [nope], axis=-1)
            x = checkpoint_name(x, "ds_dot:qkv")
            c_kv, k_rope = jnp.split(_dot(x, ap["wkv_a"]).astype(x.dtype), [R], axis=-1)
            kv = checkpoint_name(_dot(self._norm(c_kv, ap["kv_norm"]), ap["wkv_b"]).astype(x.dtype), "attn_kv")
            k_nope, v = jnp.split(heads(kv.reshape(B, T, n, nope + c.v_head_dim)), [nope], axis=-1)
            # the rotary key is ONE head: every head reads it, and its gradient is their sum
            k_rope = jnp.broadcast_to(turn(k_rope[:, None]), (B, n, T, turned))
            q = jnp.concatenate([q_nope, turn(q_rope)], axis=-1)
            k = jnp.concatenate([k_nope, k_rope], axis=-1)
            # q, k and v are written head-major by the passes that split and turn them (the compiler
            # folds the turn of the axes into them); the output comes out [B, T, n * v] as W_o reads it
            y = flash_attention_rows(q, k, v, n, n, True, sm_scale)          # None: 1 / sqrt(nope + rope)
            y = checkpoint_name(y, "ds_dot:proj")
            return _dot(y, ap["wo"]).astype(x.dtype)

    def dense_mlp(self, x, mp):
        """A dense block's gated MLP on the normed input ``x [B, T, H]``."""
        return _gated(x, mp["w_gate_up"], mp["w_down"], "dense_gate_up")

    def expert_layer(self, x, lp, details=False):
        """The held experts' part plus the shared expert, ungated by any router: ``(y, stats)``."""
        y, _, stats = self.moe.apply(lp["moe"], x, details)
        with jax.named_scope("ds_moe_shared"):
            shared = _gated(x, lp["shared"]["w_gate_up"], lp["shared"]["w_down"], "shared_gate_up")
        return y + shared, stats

    def _block(self, x, lp, details=False):
        """One block: ``(y, stats)``; ``stats`` is empty for a dense block, and with ``details``
        holds both normed inputs."""
        with jax.named_scope("ds_attn"):
            n1 = self._norm(x, lp["norm_1"])
            h = x + self.attention(n1, lp["attn"])
        # an expert layer is its block's MLP: its ds_moe_* scopes nest under ds_mlp
        with jax.named_scope("ds_mlp"):
            n2 = self._norm(h, lp["norm_2"])
            if "mlp" in lp:
                m, stats = self.dense_mlp(n2, lp["mlp"]), {}
            else:
                m, stats = self.expert_layer(n2, lp, details)
            return h + m, (dict(stats, attn_in=n1, mlp_in=n2) if details else stats)

    def _run(self, x, lp, details):
        block = functools.partial(self._block, details=details)
        if self.config.remat and not details:     # config-aware remat, as ``models/gpt2.py``'s blocks
            block = checkpoint_wrapper(block, policy=KEPT_BY_A_LAYER)
        return block(x, lp)

    def _backbone(self, params, tokens, details=False):
        """The last norm's output and every block's stats, in the blocks' order."""
        c = self.config
        with jax.named_scope("ds_embed"):
            x = params["embed"][tokens].astype(c.compute_dtype)
        stats = []
        for lp in params["layers"]:
            x, s = self._run(x, lp, details)
            stats.append(s)
        with jax.named_scope("ds_loss"):      # the last norm feeds the head
            x = self._norm(x, params["norm_f"])
        return x, stats

    def combine(self, e, h, mp):
        """The prediction module's input: ``[rms(e) g_e | rms(h) g_h] W_eh`` from the next
        tokens' embedding rows ``e`` and the main model's last norm's output ``h``, ``[B, T, H]``."""
        both = jnp.concatenate([self._norm(e, mp["norm_e"]), self._norm(h, mp["norm_h"])], axis=-1)
        return _dot(both, mp["w_eh"]).astype(h.dtype)

    def _mtp(self, params, h, labels, details=False):
        """The second depth from the main model's last norm's output ``h``: ``(the module's last
        norm's output, its labels, its block's stats)``. Position ``i`` embeds ``t_{i+1} =
        labels_i`` and is asked for ``t_{i+2} = labels_{i+1}``: the last position has none, nor
        has one whose own label is ignored."""
        c, mp = self.config, params["mtp"]
        after = jnp.concatenate([labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
        after = jnp.where(labels >= 0, after, -1)
        with jax.named_scope("ds_embed"):
            e = params["embed"][jnp.maximum(labels, 0)].astype(c.compute_dtype)
        x = self.combine(e, h, mp)
        y, stats = self._run(x, mp["block"], details)
        with jax.named_scope("ds_loss"):
            y = self._norm(y, mp["norm_s"])
        return y, after, (dict(stats, mtp_in=jnp.concatenate([e, h], axis=-1)) if details else stats)

    # ------------------------------------------------------------- apply
    def _logits(self, params, x):
        with jax.named_scope("ds_loss"):
            return jnp.einsum("bth,vh->btv", x, params["head"].astype(x.dtype),
                              preferred_element_type=jnp.float32)

    def logits(self, params, tokens):
        return self._logits(params, self._backbone(params, tokens)[0])

    def _losses(self, params, tokens, labels, details=False):
        """``(loss, L_1, L_2, both depths' last norms' outputs, the expert layers' stats in
        their order, the module's block's last)``."""
        x, stats = self._backbone(params, tokens, details)
        with jax.named_scope("ds_loss"):
            main = chunked_cross_entropy(x, params["head"], labels)
        with jax.named_scope(MTP_SCOPE):
            y, after, s = self._mtp(params, x, labels, details)
            with jax.named_scope("ds_loss"):
                mtp = chunked_cross_entropy(y, params["head"], after)
        return main + self.config.mtp_loss_weight * mtp, main, mtp, (x, y), stats + [s]

    def _stacked(self, stats, name):
        return jnp.stack([s[name] for s in stats if name in s])

    def expert_counts(self, params, tokens, labels):
        """``[Le, E]`` float32: the assignments to every expert of every expert layer, the
        module's block last (what the rule reads; no head, no loss)."""
        x, stats = self._backbone(params, tokens)
        return self._stacked(stats + [self._mtp(params, x, labels)[2]], "counts")

    def forward_details(self, params, tokens, labels, last):
        """What a comparison with the plain reference reads: the loss and both depths', both
        depths' logits of the ``last`` positions, every block's two normed inputs ``[L + 1, B,
        T, H]`` (the module's block last), the module's two inputs side by side ``[B, T, 2H]``,
        and of the expert layers the choices ``[Le, B, T, k]``, the router's logits and the
        counts ``[Le, E]``."""
        loss, main, mtp, (x, y), stats = self._losses(params, tokens, labels, details=True)
        with jax.named_scope(MTP_SCOPE):
            logits_mtp = self._logits(params, y[:, -last:])
        return {"loss": loss, "loss_main": main, "loss_mtp": mtp,
                "logits": self._logits(params, x[:, -last:]), "logits_mtp": logits_mtp,
                "attn_in": self._stacked(stats, "attn_in"), "mlp_in": self._stacked(stats, "mlp_in"),
                "mtp_in": stats[-1]["mtp_in"], "experts": self._stacked(stats, "experts"),
                "router_logits": self._stacked(stats, "router_logits"),
                "counts": self._stacked(stats, "counts")}

    def apply(self, params, tokens, labels=None):
        """Without labels: float32 logits. With labels: ``(loss, stats)`` — ``L_1 +
        mtp_loss_weight L_2``, both depths' losses and the expert layers' per-layer device
        scalars (``device_scalars``), and the step's assignments to every expert of every
        expert layer (``moe_counts`` ``[Le, E]``: what ``apply_rule`` reads)."""
        if labels is None:
            return self.logits(params, tokens)
        loss, main, mtp, _, stats = self._losses(params, tokens, labels)
        load = self._stacked(stats, "load_max_over_mean")
        # with every expert held (no cut) every assignment lands here
        every = jnp.full_like(load, tokens.size * self.config.num_experts_per_tok)
        rows = self._stacked(stats, "rows_here") if self.moe.held is not None else every
        return loss, {"loss_main": main, "loss_mtp": mtp, "moe_load_max_over_mean": load,
                      "moe_rows_here": rows, "moe_counts": self._stacked(stats, "counts")}

    # ------------------------------------------------------------- the rule
    def apply_rule(self, leaves, sums):
        """The selection biases after a step: ``leaves`` is the parameter tree with every
        leaf but the named ones None (float32, the master's), ``sums["moe_counts"]``
        ``[Le, E]`` the step's assignments, an expert layer a row in the tree's order (the
        blocks', then the module's): ``b_e + u * sign(mean(c) - c_e)``. Returns ``leaves``' tree."""
        u = self.config.bias_update_rate
        with jax.named_scope(RULE_SCOPE):
            biases, treedef = jax.tree_util.tree_flatten(leaves)
            counts = sums["moe_counts"]
            assert counts.shape[0] == len(biases), (counts.shape, len(biases))
            moved = [b + u * jnp.sign(jnp.mean(c) - c).astype(b.dtype)
                     for b, c in zip(biases, counts)]
            return jax.tree_util.tree_unflatten(treedef, moved)
