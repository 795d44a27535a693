"""Granite 4.0-H (``model_type: granitemoehybrid`` with ``num_local_experts`` 0): a pre-norm
decoder whose token mixer is a Mamba-2 state-space layer or a position-free grouped-query
softmax attention, a layer by ``layer_types``, each followed by a gated MLP; four scalar
multipliers; a tied head whose logits are divided.

    x0 = embedding_multiplier * E[tokens]
    h  = x + residual_multiplier * mixer_l(rms(x) * w1)
    y  = h + residual_multiplier * Wout(silu(g) * u),   [g | u] = Win (rms(h) * w2)
    logits = (rms(x_L) * wf) E^T / logits_scaling

    mamba:  [z | xBC | dt] = Win x                               (no bias)
        xBC = silu(causal depthwise conv of width ``mamba_d_conv``, with bias)
        [xs | B | C] = xBC;   dt = softplus(dt + dt_bias);   A = -exp(A_log)     (float32, a head)
        a head h (xs_h [P], state S_h [P, N]; one B, C for all heads):
            S_t = exp(dt_t A_h) S_{t-1} + dt_t xs_t B_t^T;   y_t = S_t C_t + D_h xs_t
                                                                 (``ops/ssd.py``, chunked)
        Wout (rms_over_all_channels(y * silu(z)) * w_norm)       (gate, THEN norm)
    attention: q, k, v without bias and WITHOUT a positional embedding
        (``position_embedding_type: nope``); causal softmax of ``attention_multiplier`` q k^T
        (the published 1/64, not D^-1/2) over ``num_key_value_heads`` shared heads; Wo

This follows the ``transformers`` port of the published model. The column order inside the
Mamba ``w_in`` ([z | xBC | dt]), the convolution's channels ([xs | B | C]) and the MLP's
``w_in`` ([g | u]) are the published ones; the fused ``wkv`` ([k | v], heads of k first) is
this file's own (the checkpoint keeps ``k_proj`` and ``v_proj`` apart). Left out, because this
block does not compute them: the routed experts of the family's larger models
(``num_local_experts > 0`` is refused), rotary embeddings (a rotary
``position_embedding_type`` is refused), dropout, and ``mamba_n_groups > 1``. Packed
documents are not masked at their boundaries: the state and the attention run across them.

The model follows the repo's convention (``init(rng) -> params``, ``apply(params, tokens[,
labels])``) and goes through ``deepspeed_tpu.initialize`` like GPT-2, OLMoE and Qwen3-Next.
"""

import functools
from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..runtime.activation_checkpointing.checkpointing import checkpoint_wrapper
from .layers import chunked_cross_entropy, rms_norm

MAMBA, ATTENTION = "mamba", "attention"


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ()          # one entry a layer; empty: every layer mamba
    shared_intermediate_size: int = 8192
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    # mamba
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    remat: bool = False            # whole blocks made again in the backward: a block keeps KEPT_BY_A_BLOCK
    compute_dtype: Any = jnp.bfloat16

    @classmethod
    def from_published(cls, keys, **more):
        """From the keys of the model's ``config.json``; keys that say nothing this block
        could do otherwise are checked, not stored."""
        assert keys.get("num_local_experts", 0) == 0 and keys.get("num_experts_per_tok", 0) == 0, \
            "this block has no routed experts"
        assert keys.get("position_embedding_type", "nope") == "nope", \
            f"no positional embedding is built: {keys.get('position_embedding_type')!r}"
        assert keys.get("hidden_act", "silu") == "silu", keys.get("hidden_act")
        assert keys.get("normalization_function", "rmsnorm") == "rmsnorm"
        assert keys.get("tie_word_embeddings", True), "the head is the embedding"
        assert not keys.get("attention_bias", False) and not keys.get("mamba_proj_bias", False)
        assert keys.get("mamba_conv_bias", True), "the convolution carries its bias"
        assert keys.get("mamba_n_groups", 1) == 1, "one B and C for all heads"
        H = keys.get("hidden_size", cls.hidden_size)
        assert keys.get("mamba_expand", 2) * H == (keys.get("mamba_n_heads", cls.mamba_n_heads)
                                                   * keys.get("mamba_d_head", cls.mamba_d_head))
        kinds = tuple(keys.get("layer_types", ()))[:keys.get("num_hidden_layers")]
        unknown = set(kinds) - {MAMBA, ATTENTION}
        assert not unknown, f"unknown layer_types {sorted(unknown)}"
        assert len(kinds) == keys.get("num_hidden_layers", len(kinds)), "a layer type a layer"
        stored = {k: v for k, v in keys.items() if k in cls.__dataclass_fields__}
        return cls(**dict(stored, layer_types=kinds), **more)

    def kind(self, layer):
        return self.layer_types[layer] if self.layer_types else MAMBA

    @property
    def mamba_inner(self):
        return self.mamba_n_heads * self.mamba_d_head


# What a recomputed block keeps beside its input, by name: the flash kernel's output and row
# sums (named in its forward rule), the mixer's last product's output (``norm_2`` and the MLP
# read ``x + r * mixed`` inside the same block, so that product is NOT dead in the second
# forward, where the MLP's last one is: nothing behind it reads its output), and the Mamba-2
# mixers' first product's output as the forward leaves it: the compute dtype's copy and the
# float32 ``dt`` columns. Bytes and what each buys on a v5e: docs/granite-hybrid.md, PERF.md (PR 41).
KEPT_BY_A_BLOCK = jax.checkpoint_policies.save_only_these_names(
    "attn_out", "attn_lse", "mixer_out", "ssm_in", "ssm_dt")


def _dot(x, w):
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)


def inverse_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


class GraniteHybridModel:
    def __init__(self, config: GraniteHybridConfig):
        self.config = config

    # ------------------------------------------------------------- init
    def init(self, rng):
        """Matrices N(0, ``initializer_range``); the Mamba-2 family's initialisation of the
        rest: ``A_log = log(1..heads)``, ``D = 1``, ``dt_bias`` the inverse softplus of a step
        drawn log-uniform in [0.001, 0.1], the convolution U(-W^-1/2, W^-1/2); norms 1."""
        c = self.config
        H, F, s = c.hidden_size, c.shared_intermediate_size, c.initializer_range
        normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32) * s   # noqa: E731
        ones = lambda n=H: jnp.ones((n,), jnp.float32)                                  # noqa: E731
        heads, inner, N, W = c.mamba_n_heads, c.mamba_inner, c.mamba_d_state, c.mamba_d_conv
        D = H // c.num_attention_heads
        keys = jax.random.split(rng, 1 + c.num_hidden_layers)
        layers = []
        for l, key in enumerate(keys[1:]):
            k = jax.random.split(key, 8)
            if c.kind(l) == ATTENTION:
                mixer = {"wq": normal(k[0], H, c.num_attention_heads * D),
                         "wkv": normal(k[1], H, 2 * c.num_key_value_heads * D),
                         "wo": normal(k[2], c.num_attention_heads * D, H)}
            else:
                step = jnp.exp(jax.random.uniform(k[3], (heads,), jnp.float32,
                                                  jnp.log(1e-3), jnp.log(1e-1)))
                mixer = {"w_in": normal(k[0], H, 2 * inner + 2 * N + heads),
                         "conv_w": jax.random.uniform(k[1], (W, inner + 2 * N), jnp.float32,
                                                      -W ** -0.5, W ** -0.5),
                         "conv_b": jax.random.uniform(k[2], (inner + 2 * N,), jnp.float32,
                                                      -W ** -0.5, W ** -0.5),
                         "dt_bias": inverse_softplus(step),
                         "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
                         "D": ones(heads), "norm": ones(inner),
                         "w_out": normal(k[4], inner, H)}
            layers.append({"norm_1": ones(), "mixer": mixer, "norm_2": ones(),
                           "mlp": {"w_in": normal(k[5], H, 2 * F), "w_out": normal(k[6], F, H)}})
        return {"embed": normal(keys[0], c.vocab_size, H), "layers": layers, "norm_f": ones()}

    # ------------------------------------------------------------- layers
    def _norm(self, x, w):
        return rms_norm(x, w, self.config.rms_norm_eps)

    def mamba_inputs(self, x, mp):
        """What the scan of one mixer is given, from the normed block input ``x [B, T, H]``:
        ``xs [B, T, heads, P]``, ``dt [B, T, heads]`` (float32, after its softplus), ``B``,
        ``C`` ``[B, T, N]`` and the gate ``z [B, T, heads * P]``."""
        from ..ops.delta_rule import causal_conv
        c = self.config
        B, T, _ = x.shape
        inner, N = c.mamba_inner, c.mamba_d_state
        x = checkpoint_name(x, "ds_dot:qkv")      # the remat policies classify dots by tag
        proj = _dot(x, mp["w_in"])                                            # float32
        # dt is read off the float32 product; both of the product's readers are named, so a
        # block that keeps them runs no second product
        dt = jax.nn.softplus(checkpoint_name(proj[..., 2 * inner + 2 * N:], "ssm_dt") + mp["dt_bias"])
        # the gate and the convolution's input in the compute dtype, where the projection
        # leaves them: the convolution reads its columns in place
        proj = checkpoint_name(proj.astype(x.dtype), "ssm_in")
        z = proj[..., :inner]
        xBC = causal_conv(proj, mp["conv_w"], True, mp["conv_b"], columns=(inner, 2 * inner + 2 * N))
        xs, Bm, Cm = jnp.split(xBC, [inner, inner + N], axis=-1)
        return xs.reshape(B, T, c.mamba_n_heads, c.mamba_d_head), dt, Bm, Cm, z

    def _gated_norm(self, y, z, w):
        """``rms(y * silu(z)) * w`` over ALL channels in float32 (the gate first, then the
        norm); made again in the backward from ``y`` and ``z`` as they are stored."""
        def gated(y, z, w):
            g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
            return rms_norm(g, w, self.config.rms_norm_eps).astype(z.dtype)
        return jax.checkpoint(gated)(y, z, w)

    def mamba_mixer(self, x, mp):
        """The Mamba-2 mixer on the normed block input ``x [B, T, H]``."""
        from ..ops.ssd import ssd_scan
        c = self.config
        B, T, _ = x.shape
        with jax.named_scope("ds_ssm"):
            xs, dt, Bm, Cm, z = self.mamba_inputs(x, mp)
            y = ssd_scan(xs, dt, -jnp.exp(mp["A_log"].astype(jnp.float32)), Bm, Cm, mp["D"],
                         c.mamba_chunk_size)
            y = self._gated_norm(y.reshape(B, T, c.mamba_inner), z, mp["norm"])
            y = checkpoint_name(y, "ds_dot:proj")
            return _dot(y, mp["w_out"]).astype(x.dtype)

    def attention(self, x, mp):
        """The position-free grouped-query attention on the normed block input ``x [B, T, H]``."""
        from ..ops.pallas.flash_attention import flash_attention
        c = self.config
        B, T, H = x.shape
        nq, nkv = c.num_attention_heads, c.num_key_value_heads
        D = H // nq
        heads = lambda a: a.transpose(0, 2, 1, 3)      # noqa: E731
        x = checkpoint_name(x, "ds_dot:qkv")
        q = _dot(x, mp["wq"]).astype(x.dtype).reshape(B, T, nq, D)
        x = checkpoint_name(x, "ds_dot:qkv")
        k, v = jnp.split(_dot(x, mp["wkv"]).astype(x.dtype).reshape(B, T, 2 * nkv, D), 2, axis=2)
        y = flash_attention(heads(q), heads(k), heads(v), True, sm_scale=c.attention_multiplier)
        y = checkpoint_name(heads(y).reshape(B, T, nq * D), "ds_dot:proj")
        return _dot(y, mp["wo"]).astype(x.dtype)

    def mlp(self, x, mp):
        F = self.config.shared_intermediate_size
        gate_up = _dot(x, mp["w_in"]).astype(x.dtype)
        hidden = jax.nn.silu(gate_up[..., :F].astype(jnp.float32)) * gate_up[..., F:]
        return _dot(hidden.astype(x.dtype), mp["w_out"]).astype(x.dtype)

    def _block(self, x, lp, kind, details=False):
        r = self.config.residual_multiplier
        with jax.named_scope("ds_attn"):
            n = self._norm(x, lp["norm_1"])
            mixed = self.attention(n, lp["mixer"]) if kind == ATTENTION \
                else self.mamba_mixer(n, lp["mixer"])
            x = x + r * checkpoint_name(mixed, "mixer_out")
        with jax.named_scope("ds_mlp"):
            x = x + r * self.mlp(self._norm(x, lp["norm_2"]), lp["mlp"])
        return (x, n) if details else x

    def _backbone(self, params, tokens, details=False):
        """The last norm's output, scaled for the head (``/ logits_scaling``: the tied table
        then gives the divided logits), and with ``details`` every mixer's normed input."""
        c = self.config
        with jax.named_scope("ds_embed"):
            x = (params["embed"][tokens] * c.embedding_multiplier).astype(c.compute_dtype)
        seen = []
        for l, lp in enumerate(params["layers"]):
            block = functools.partial(self._block, kind=c.kind(l), details=details)
            if c.remat and not details:     # config-aware remat, as ``models/gpt2.py``'s blocks
                block = checkpoint_wrapper(block, policy=KEPT_BY_A_BLOCK)
            x = block(x, lp)
            if details:
                x, n = x
                seen.append(n)
        with jax.named_scope("ds_loss"):      # the last norm feeds the head
            x = self._norm(x, params["norm_f"]) / c.logits_scaling
        return (x, jnp.stack(seen)) if details else x

    # ------------------------------------------------------------- apply
    def _logits(self, params, x):
        with jax.named_scope("ds_loss"):
            return jnp.einsum("bth,vh->btv", x, params["embed"].astype(x.dtype),
                              preferred_element_type=jnp.float32)

    def forward_details(self, params, tokens, labels, last):
        """What a comparison with the plain reference reads: the loss, the logits of the
        ``last`` positions, and every mixer's normed input ``[L, B, T, H]``."""
        x, seen = self._backbone(params, tokens, details=True)
        with jax.named_scope("ds_loss"):
            loss = chunked_cross_entropy(x, params["embed"], labels)
        return {"loss": loss, "logits": self._logits(params, x[:, -last:]), "mixer_in": seen}

    def apply(self, params, tokens, labels=None):
        """Without labels: float32 logits. With labels: the mean token cross-entropy."""
        x = self._backbone(params, tokens)
        if labels is None:
            return self._logits(params, x)
        with jax.named_scope("ds_loss"):
            return chunked_cross_entropy(x, params["embed"], labels)
