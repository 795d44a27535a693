"""OLMoE's forward pass and training loss, written plainly: ``jax.numpy``, float32, matrix
products at ``highest`` precision, every expert applied to every token by a plain loop
and masked, no kernel, no sort, no sharding. It reads the system's parameter tree
(``deepspeed_tpu/models/olmoe.py``) and shares no code with it.

Follows the published description (``model_type: olmoe`` of the source's ``config.json``,
Muennighoff et al. 2024, "OLMoE: Open Mixture-of-Experts Language Models"). Departures:

- Wq|Wk|Wv and each expert's Wgate|Wup arrive side by side in one array (the system's
  storage) and are cut apart here; the products are the published ones.
- The load-balancing loss is E · Σ_e f_e · P_e for each layer, f_e the share of the N·k
  assignments that expert e received and P_e its mean router probability, averaged over
  the layers: the form the model was trained with (the paper's eq. 3, computed by
  megablocks). The ``transformers`` port pools the layers' tokens before the product and
  does not divide by k; it is not followed.
- The router z-loss of the paper's training recipe (coefficient 0.001) is left out: the
  system does not compute it, and the source's ``config.json`` has no key for it.
- No dropout (the source has none), no ``clip_qkv`` (null in the source).
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """``x [B, T, heads, D]``: rotate pairs (i, i + D/2) by ``t * theta^(-2i/D)``."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(x, lp, m, prec=HIGHEST):
    B, T, H = x.shape
    nh = m["num_attention_heads"]
    q, k, v = jnp.split(jnp.dot(x, lp["wqkv"], precision=prec), 3, axis=-1)
    q = _rms(q, lp["q_norm"], m["rms_norm_eps"]).reshape(B, T, nh, H // nh)
    k = _rms(k, lp["k_norm"], m["rms_norm_eps"]).reshape(B, T, nh, H // nh)
    q, k, v = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"]), v.reshape(B, T, nh, H // nh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) / jnp.sqrt(H / nh)
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v, precision=prec)
    return jnp.dot(out.reshape(B, T, H), lp["wo"], precision=prec)


def expert_layer(x, mp, m, prec=HIGHEST, router_dtype=jnp.float32):
    """``(y, chosen [N, k], aux, router logits [N, E])`` of one expert layer on the batch's
    tokens ``x [N, H]``. ``router_dtype`` is float32, as published; ``bfloat16`` (with
    ``prec`` the default) is the second reading a limit on the router has to fail."""
    E, k, F = m["num_experts"], m["num_experts_per_tok"], m["intermediate_size"]
    logits = jnp.dot(x.astype(router_dtype), mp["router_w"].astype(router_dtype),
                     precision=prec).astype(jnp.float32)                           # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    # the weight of expert e for token n: its probability if chosen, else nothing
    weight = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32) * top[..., None], axis=1)

    def one_expert(y, e):
        gate_up, down = mp["w_gate_up"][e], mp["w_down"][e]
        gate = jnp.dot(x, gate_up[:, :F], precision=prec)
        up = jnp.dot(x, gate_up[:, F:], precision=prec)
        out = jnp.dot(jax.nn.silu(gate) * up, down, precision=prec)
        return y + weight[:, e, None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(E))
    share = jnp.mean(jax.nn.one_hot(chosen, E, dtype=jnp.float32), axis=(0, 1))      # sums to 1
    aux = E * jnp.sum(jax.lax.stop_gradient(share) * jnp.mean(probs, axis=0))
    return y, chosen, aux, logits


def forward(params, tokens, labels, m, aux_coef, last=None, dtype=jnp.float32, prec=HIGHEST):
    """A batch ``tokens [B, T]``: ``loss`` (mean cross-entropy + ``aux_coef`` × the
    load-balancing loss over the batch's tokens), its two parts, the logits of the
    ``last`` positions (all if None), the experts chosen, ``[layers, B, T, k]`` sorted
    along k, and what every expert layer was given, ``expert_in [layers, B, T, H]`` (the
    system's expert layer is compared with ``expert_layer`` on these same inputs).
    ``dtype`` and ``prec`` are float32 and ``highest`` for the reference. In bfloat16 at
    the default precision this whole-model reading comes out as correct against the
    whole-model limits (a random model's bf16 activations hide a bf16 router); the limits
    a lower precision fails are those on one expert layer given identical inputs
    (``olmoe_tolerances.json``)."""
    params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
    B, T = tokens.shape
    x = params["embed"][tokens]
    aux, chosen, expert_in = 0.0, [], []
    for lp in params["layers"]:
        x = x + _attention(_rms(x, lp["norm_1"], m["rms_norm_eps"]), lp, m, prec)
        expert_in.append(_rms(x, lp["norm_2"], m["rms_norm_eps"]))
        y, c, a, _ = expert_layer(expert_in[-1].reshape(B * T, -1), lp["moe"], m, prec, dtype)
        x, aux = x + y.reshape(x.shape), aux + a
        chosen.append(jnp.sort(c, axis=-1).reshape(B, T, -1))
    aux = aux / len(params["layers"])
    x = _rms(x, params["norm_f"], m["rms_norm_eps"])
    logits = jnp.dot(x, params["head"].T, precision=prec)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return {"loss": ce + aux_coef * aux, "ce": ce, "aux": aux,
            "logits": logits if last is None else logits[:, -last:], "experts": jnp.stack(chosen),
            "expert_in": jnp.stack(expert_in)}


def loss(params, tokens, labels, m, aux_coef):
    return forward(params, tokens, labels, m, aux_coef, last=1)["loss"]
