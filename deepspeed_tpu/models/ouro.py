"""Ouro (``model_type: ouro``, ByteDance's looped language models): a decoder whose stack of
layers runs ``total_ut_steps`` times on ONE set of weights, with the last norm, the head and a
learned exit gate after every pass, trained on a loss that weighs every pass's cross-entropy
by the probability of leaving there.

    x^0 = E[tokens]
    for t = 1..T:                                   # the SAME layers' leaves every pass
        h = x^{t-1}
        for every layer l:
            h = h + n2_l( Wo_l attn(rope(Wq_l n1_l(h)), rope(Wk_l n1_l(h)), Wv_l n1_l(h)) )
            h = h + n4_l( Wdown_l ( silu(Wgate_l n3_l(h)) * Wup_l n3_l(h) ) )
        x^t = norm_f(h)                             # to the head, to the gate AND into pass t+1
        z^t = head x^t                              # the logits of exit t
        lambda^t = sigmoid(w_g . x^t + b_g)         # one gate, shared by the passes, a position
    p^1 = lambda^1;  p^t = lambda^t prod_{j<t}(1 - lambda^j);  p^T = prod_{j<T}(1 - lambda^j)
    l^t_i = -log softmax(z^t_i)[y_i]
    loss = mean over valid i of  sum_t p^t_i l^t_i  -  beta H(p_i),   H(p) = -sum_t p^t log p^t

Attention is causal over heads of ``head_dim`` with no grouping, no bias and no QK-norm, rotary
in the half-split convention, scaled by ``head_dim^-1/2``; the norms are RMSNorms, four a
block (around each branch: "sandwich"); the head is untied. The widths, ``total_ut_steps``
and ``early_exit_threshold`` are the published ``config.json``'s keys; the four norms a
block, ``norm_f`` carried into the next pass, the gate and the training loss are the family's
report (arXiv:2510.25741) and its published modeling file: a configuration lists them as
assumed. ``early_exit_threshold`` 1 takes no exit early: without labels ``apply`` returns the
LAST pass's logits. Left out: early exit at inference, sliding windows (the source turns them
off), dropout. Packed documents are not masked at their boundaries.

In float32 whatever the compute dtype: the gate's logit, the exit distribution (in logs:
``log p^t = log lambda^t + sum_{j<t} log(1 - lambda^j)``), the entropy, every cross-entropy
and the weighted sum. A layer's leaves appear ONCE in ``params``; their gradient is the sum
of the passes' contributions, added in the leaves' dtype as the scan over the passes carries
its cotangents.

The model follows the repo's convention (``init(rng) -> params``, ``apply(params, tokens[,
labels])``) and goes through ``deepspeed_tpu.initialize`` like the others.
"""

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..runtime.activation_checkpointing.checkpointing import checkpoint_wrapper
from .layers import chunked_cross_entropy_a_position, rms_norm, rope


@dataclass
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    head_dim: int = 128
    total_ut_steps: int = 4
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    exit_entropy_coef: float = 0.05     # beta: the entropy of the exit distribution, rewarded
    initializer_range: float = 0.02
    remat: bool = False            # whole blocks made again in the backward: a block pass keeps KEPT_BY_A_BLOCK_PASS
    compute_dtype: Any = jnp.bfloat16

    @classmethod
    def from_published(cls, keys, **more):
        """From the keys of the model's ``config.json``; keys that say nothing this block
        could do otherwise are checked, not stored."""
        assert keys.get("hidden_act", "silu") == "silu", keys.get("hidden_act")
        assert not keys.get("tie_word_embeddings", False), "the head is its own table"
        assert keys.get("rope_scaling") is None, "no scaled rotary embedding is built"
        assert not keys.get("use_sliding_window", False) and keys.get("sliding_window") is None
        assert keys.get("num_key_value_heads", keys["num_attention_heads"]) == \
            keys["num_attention_heads"], "grouped heads are not in this block"
        assert keys.get("early_exit_threshold", 1) >= 1, "no exit is taken early"
        assert keys.get("total_ut_steps", 1) >= 1
        kinds = set(keys.get("layer_types", ())[:keys.get("num_hidden_layers")])
        assert kinds <= {"full_attention"}, f"unknown layer_types {sorted(kinds)}"
        return cls(**{k: v for k, v in keys.items() if k in cls.__dataclass_fields__}, **more)


# What a recomputed block pass keeps beside its input, by name: the flash kernel's output
# and row sums (named in its forward rule) and ``w_down``'s output, which only ``norm_4``'s
# backward reads. A kept [B, T, H] takes a 5632-deep product or the kernel out of the second
# forward, 29-40 ms a GB on a v5e; every other tensor of a block buys 10 (PERF.md, PR 38).
KEPT_BY_A_BLOCK_PASS = jax.checkpoint_policies.save_only_these_names("attn_out", "attn_lse", "mlp_out")


def _dot(x, w):
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32).astype(x.dtype)


def exit_distribution(gate_logits):
    """``(log p [T, ...], p [T, ...])`` from the first ``T - 1`` passes' gate logits
    ``[T - 1, ...]``, in float32: pass ``t`` is left with ``lambda^t`` of what the passes
    before it let through, and the last pass takes what is left. The logs are exact sums; of
    the probabilities the last is ``1 -`` the others' sum and not ``exp`` of its log, so
    that p sums to one to a rounding whatever the chip's ``exp`` and ``log`` are good for (a
    v5e's left the exponentials' sum up to 5e-5 from one: PERF.md, PR 37)."""
    g = gate_logits.astype(jnp.float32)
    zero = jnp.zeros((1,) + g.shape[1:], jnp.float32)
    # log prod_{j<t}(1 - lambda^j): what reaches pass t
    reaches = jnp.concatenate([zero, jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)], axis=0)
    log_p = reaches + jnp.concatenate([jax.nn.log_sigmoid(g), zero], axis=0)
    left = jnp.exp(log_p[:-1])
    last = 1.0 - jnp.sum(left, axis=0, keepdims=True)      # within a rounding of zero where nothing is left
    return log_p, jnp.concatenate([left, last], axis=0)


class OuroModel:
    # what ``apply`` returns beside its loss, by name: the mean p^t and the mean l^t a pass
    # (the engine's one-value-a-layer slot) and the mean entropy, kept unfetched a step
    device_scalars = ("exit_mass", "exit_ce", "exit_entropy")

    def __init__(self, config: OuroConfig):
        self.config = config

    # ------------------------------------------------------------- init
    def init(self, rng):
        """Matrices and the gate's weight N(0, ``initializer_range``); norms 1; the gate's
        bias 0."""
        c = self.config
        H, F, s = c.hidden_size, c.intermediate_size, c.initializer_range
        A = c.num_attention_heads * c.head_dim
        normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32) * s   # noqa: E731
        ones = lambda: jnp.ones((H,), jnp.float32)                                     # noqa: E731
        keys = jax.random.split(rng, 3 + c.num_hidden_layers)
        layers = []
        for key in keys[3:]:
            k = jax.random.split(key, 7)
            layers.append({"norm_1": ones(), "wq": normal(k[0], H, A), "wk": normal(k[1], H, A),
                           "wv": normal(k[2], H, A), "wo": normal(k[3], A, H), "norm_2": ones(),
                           "norm_3": ones(), "w_gate": normal(k[4], H, F), "w_up": normal(k[5], H, F),
                           "w_down": normal(k[6], F, H), "norm_4": ones()})
        return {"embed": normal(keys[0], c.vocab_size, H), "layers": layers, "norm_f": ones(),
                "head": normal(keys[1], c.vocab_size, H),
                "gate": {"w": normal(keys[2], H), "b": jnp.zeros((1,), jnp.float32)}}

    # ------------------------------------------------------------- layers
    def _norm(self, x, w):
        return rms_norm(x, w, self.config.rms_norm_eps)

    def attention(self, x, lp, positions):
        from ..ops.pallas.flash_attention import flash_attention_rows
        c = self.config
        B, T, _ = x.shape
        heads = lambda a: a.reshape(B, T, c.num_attention_heads, c.head_dim).transpose(0, 2, 1, 3)   # noqa: E731
        # q and k are written head-major by the pass that turns them (the compiler folds the turn of the
        # axes into it); v goes in, and the output comes out, where the projections write and read them
        q, k = (rope(heads(_dot(x, lp[name])), positions, c.rope_theta) for name in ("wq", "wk"))
        n = c.num_attention_heads
        return _dot(flash_attention_rows(q, k, _dot(x, lp["wv"]), n, n, True), lp["wo"])

    def mlp(self, x, lp):
        hidden = jax.nn.silu(_dot(x, lp["w_gate"]).astype(jnp.float32)) * _dot(x, lp["w_up"])
        return checkpoint_name(_dot(hidden.astype(x.dtype), lp["w_down"]), "mlp_out")

    def _block(self, x, lp, positions):
        """One layer in a pass: a norm before and a norm after each branch. The passes' scope
        lies inside what is recomputed, so that the second forward carries it too."""
        with jax.named_scope("ds_loop"):
            with jax.named_scope("ds_attn"):
                x = x + self._norm(self.attention(self._norm(x, lp["norm_1"]), lp, positions), lp["norm_2"])
            with jax.named_scope("ds_mlp"):
                return x + self._norm(self.mlp(self._norm(x, lp["norm_3"]), lp), lp["norm_4"])

    def one_pass(self, params, x):
        """``norm_f`` of the layers applied once to ``x [B, T, H]``: the next exit state."""
        c = self.config
        positions = jnp.arange(x.shape[1])
        block = self._block
        if c.remat:
            block = checkpoint_wrapper(block, KEPT_BY_A_BLOCK_PASS)
        for lp in params["layers"]:
            x = block(x, lp, positions)
        with jax.named_scope("ds_loss"):      # the last norm feeds the head, the gate and the next pass
            return self._norm(x, params["norm_f"])

    def exit_states(self, params, tokens):
        """``x^1 .. x^T`` as ``[T, B, S, H]`` in the compute dtype. The passes are ONE
        ``lax.scan`` that closes over the leaves: the step program holds one pass's blocks,
        not ``total_ut_steps`` copies of them (a quarter of the program, of its compile time
        and of its place in a compile cache; 5 % more tokens a second than 24 unrolled block
        passes on a v5e: PERF.md, PR 37), and pass ``t`` is the loop's ``t``-th turn (the
        backward's turns run from the last pass to the first)."""
        c = self.config
        with jax.named_scope("ds_embed"):
            x = params["embed"][tokens].astype(c.compute_dtype)

        def turn(x, _):
            x = self.one_pass(params, x)
            return x, x
        return jax.lax.scan(turn, x, None, length=c.total_ut_steps)[1]

    def gate_logits(self, params, states):
        """``w_g . x^t + b_g`` of the passes that can be left (all but the last; ``states``
        ``[T, B, S, H]``), float32."""
        w = params["gate"]["w"].astype(jnp.float32)
        return jnp.sum(states[:-1].astype(jnp.float32) * w, axis=-1) + params["gate"]["b"].astype(jnp.float32)

    def exit_weights(self, params, states):
        """``(p [T, B, S], entropy [B, S])`` of the exit states, float32."""
        log_p, p = exit_distribution(self.gate_logits(params, states))
        return p, -jnp.sum(p * log_p, axis=0)

    # ------------------------------------------------------------- apply
    def _logits(self, params, x):
        with jax.named_scope("ds_loss"):
            return jnp.einsum("bth,vh->btv", x, params["head"].astype(x.dtype),
                              preferred_element_type=jnp.float32)

    def exits(self, params, tokens, labels):
        """The loss and its parts: the exit distribution ``p [T, B, S]``, its entropy
        ``[B, S]``, the exit states, and the means over the valid positions that the engine
        keeps a step (``stats``: the mass and the cross-entropy a pass, the entropy)."""
        c = self.config
        T, (B, S) = c.total_ut_steps, tokens.shape
        states = self.exit_states(params, tokens)
        with jax.named_scope("ds_loss"):
            # the exits as ONE call against the one table: one product for the table's
            # gradient, summed over the exits in float32 inside it
            ce = chunked_cross_entropy_a_position(
                states.reshape(T * B, S, -1), params["head"], jnp.tile(labels, (T, 1))).reshape(T, B, S)
            with jax.named_scope("ds_exit"):
                p, entropy = self.exit_weights(params, states)
                valid = labels >= 0
                count = jnp.maximum(jnp.sum(valid).astype(jnp.float32), 1.0)
                mean = lambda a: jnp.sum(jnp.where(valid, a, 0.0), axis=(-2, -1)) / count   # noqa: E731
                loss = mean(jnp.sum(p * ce, axis=0) - c.exit_entropy_coef * entropy)
                stats = {"exit_mass": mean(p), "exit_ce": mean(ce), "exit_entropy": mean(entropy)}
        return {"loss": loss, "stats": stats, "p": p, "entropy": entropy, "states": states}

    def forward_details(self, params, tokens, labels, last):
        """What a comparison with the plain reference reads: the loss, every exit's mean
        cross-entropy, the exit distribution and entropy a position, and the logits of the
        ``last`` positions of EVERY exit ``[T, B, last, V]``."""
        out = self.exits(params, tokens, labels)
        return {"loss": out["loss"], "exit_ce": out["stats"]["exit_ce"], "p": out["p"], "entropy": out["entropy"],
                "logits": jnp.stack([self._logits(params, x[:, -last:]) for x in out["states"]])}

    def apply(self, params, tokens, labels=None):
        """Without labels: the last pass's float32 logits. With labels: ``(loss, stats)``,
        the exit-weighted loss and the exit distribution's device scalars
        (``device_scalars``), which the engine keeps beside the loss without fetching them."""
        if labels is None:
            return self._logits(params, self.exit_states(params, tokens)[-1])
        out = self.exits(params, tokens, labels)
        return out["loss"], out["stats"]
