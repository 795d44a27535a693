"""Qwen3-Next's forward pass and training loss, written plainly: ``jax.numpy``, float32,
matrix products at ``highest`` precision, the delta rule a token at a time (``lax.scan``),
softmax attention over whole rows of scores (a block of query positions at a time, so that
8,192 positions fit), every held expert applied to every token by a plain loop and masked;
no kernel, no chunked form, no sort, no cache. It reads the system's parameter tree
(``deepspeed_tpu/models/qwen3_next.py``) and shares no code with it.

Follows the published description (``model_type: qwen3_next`` of the source's
``config.json``, and the ``transformers`` port's ``torch_recurrent_gated_delta_rule``).
Departures:

- The multi-token-prediction module is left out: no key of the source's ``config.json``
  describes it, and the system does not compute it.
- The column order inside ``w_qkvz`` ([q | k | v | z]), ``w_ba`` ([b | a]), ``wq`` (a head:
  [q | gate]) and ``wkv`` ([k | v]) is the system's storage, not the checkpoint's (which
  interleaves them a key head); with seeded weights the products are the published ones.
- ONE CHIP'S SHARE of the expert layer (``held = (first, count)``): the router scores all
  ``router_width`` experts and keeps the ``num_experts_per_tok`` largest, renormalised;
  only those of them that lie in ``held`` contribute. What the absent experts would add is
  left out, here as in the system. With ``held`` the whole range this is the uncut layer.
- The load-balancing loss is E * sum_e f_e * P_e for each layer over all ``router_width``
  experts from this chip's tokens (f_e the share of the N k assignments expert e received,
  P_e its mean probability), averaged over the layers, as ``olmoe_reference.py`` has it;
  the ``transformers`` port pools the layers and does not divide by k.
- Sizes the source does not give are the configuration file's ``assumed``:
  ``router_aux_loss_coef``, ``initializer_range``, the convolution's, ``A_log``'s and
  ``dt_bias``'s initial values (weights are the system's seeded ones either way).
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
QUERY_BLOCK = 512         # query positions whose whole score rows exist at once


def _norm(x, w, eps):
    """The block's RMSNorm: the stored weight is the scale's distance from one."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta, width):
    """``x [B, T, heads, D]``: rotate pairs (i, i + width/2) of the first ``width``
    features by ``t * theta^(-2i/width)``; the rest pass."""
    T = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., width:]], axis=-1)


def held_range(m):
    """``(first, count)`` of the experts a configuration holds of its ``router_width``."""
    return m.get("first_expert", 0), m["num_experts"]


def delta_rule_recurrent(q, k, v, g, beta, state_dtype=jnp.float32, prec=HIGHEST):
    """``o [B, T, Hv, Dv]``: a head's state ``S [Dk, Dv]`` from zero, a token at a time:
    ``S <- exp(g_t) S; d = beta_t (v_t - S^T k_t); S <- S + k_t d^T; o_t = S^T q_t``.
    ``q``, ``k`` ``[B, T, Hv, Dk]`` are already of unit length (q scaled). ``state_dtype``
    is float32; bfloat16 is the second reading a limit on the mixer has to fail."""
    B, T, H, Dk = k.shape

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        # exp(g_t) S, as S + expm1(g_t) S: a v5e's exp of a small number is a few 1e-7 off, always
        # to one side, and a head that forgets slowly (A = 0.0016: 430 tokens of memory) takes
        # that factor 430 times over, which put this recurrence 1.7e-4 from the float64 one
        # where the chunked form under test sat 5e-6 (PERF.md, PR 31)
        S = S.astype(jnp.float32)
        S = S + jnp.expm1(g_t)[..., None, None] * S
        d = b_t[..., None] * (v_t - jnp.einsum("bhde,bhd->bhe", S, k_t, precision=prec))
        S = S + k_t[..., :, None] * d[..., None, :]
        return S.astype(state_dtype), jnp.einsum("bhde,bhd->bhe", S, q_t, precision=prec)

    xs = [jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)]
    _, o = jax.lax.scan(step, jnp.zeros((B, H, Dk, v.shape[-1]), state_dtype), xs)
    return jnp.moveaxis(o, 0, 1)


def mixer_inputs(x, mp, m, prec=HIGHEST):
    """What the delta rule of one mixer is given, from the normed block input ``x [B, T, H]``:
    ``q``, ``k`` ``[B, T, Hk, Dk]`` and ``v`` ``[B, T, Hv, Dv]`` as the convolution and its
    SiLU leave them, the log decay ``g`` and the step ``beta`` ``[B, T, Hv]``, and the
    output gate's ``z [B, T, Hv, Dv]``."""
    B, T, _ = x.shape
    Hk, Hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    Dk, Dv, W = m["linear_key_head_dim"], m["linear_value_head_dim"], m["linear_conv_kernel_dim"]
    qkvz = jnp.dot(x, mp["w_qkvz"], precision=prec)
    b, a = jnp.split(jnp.dot(x, mp["w_ba"], precision=prec), 2, axis=-1)
    mixed, z = qkvz[..., :2 * Hk * Dk + Hv * Dv], qkvz[..., 2 * Hk * Dk + Hv * Dv:]
    padded = jnp.pad(mixed, ((0, 0), (W - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j:j + T] * mp["conv_w"][j] for j in range(W)))
    q, k, v = jnp.split(mixed, [Hk * Dk, 2 * Hk * Dk], axis=-1)
    g = -jnp.exp(mp["A_log"]) * jax.nn.softplus(a + mp["dt_bias"])
    return (q.reshape(B, T, Hk, Dk), k.reshape(B, T, Hk, Dk), v.reshape(B, T, Hv, Dv),
            g, jax.nn.sigmoid(b), z.reshape(B, T, Hv, Dv))


def unit_scaled(a, scale):
    """``a`` L2-normalised over a head; a query (``scale``) also times ``Dk^-1/2``."""
    a = a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    return a * a.shape[-1] ** -0.5 if scale else a


def linear_mixer(x, mp, m, state_dtype=jnp.float32, prec=HIGHEST):
    """The gated delta-rule mixer on the normed block input ``x [B, T, H]``."""
    B, T, _ = x.shape
    q, k, v, g, beta, z = mixer_inputs(x, mp, m, prec)
    r = v.shape[2] // k.shape[2]              # value heads a key head serves, side by side
    o = delta_rule_recurrent(jnp.repeat(unit_scaled(q, True), r, axis=2),
                             jnp.repeat(unit_scaled(k, False), r, axis=2), v, g, beta,
                             state_dtype, prec)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + m["rms_norm_eps"]) * mp["o_norm"]
    return jnp.dot((o * jax.nn.silu(z)).reshape(B, T, -1), mp["w_out"], precision=prec)


def full_attention(x, mp, m, prec=HIGHEST):
    """The gated grouped-query attention on the normed block input ``x [B, T, H]``."""
    B, T, _ = x.shape
    nq, nkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q, gate = jnp.split(jnp.dot(x, mp["wq"], precision=prec).reshape(B, T, nq, 2 * D), 2, axis=-1)
    k, v = jnp.split(jnp.dot(x, mp["wkv"], precision=prec).reshape(B, T, 2 * nkv, D), 2, axis=2)
    width = int(D * m["partial_rotary_factor"])
    q = _rope(_norm(q, mp["q_norm"], m["rms_norm_eps"]), m["rope_theta"], width)
    k = _rope(_norm(k, mp["k_norm"], m["rms_norm_eps"]), m["rope_theta"], width)
    k, v = (jnp.repeat(a, nq // nkv, axis=2) for a in (k, v))
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=prec) * D ** -0.5
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=prec)

    out = jax.lax.map(rows, jnp.arange(0, T, block))                 # [T / block, B, block, nq, D]
    out = jnp.moveaxis(out, 0, 1).reshape(B, T, nq * D)
    return jnp.dot(out * jax.nn.sigmoid(gate.reshape(B, T, nq * D)), mp["wo"], precision=prec)


def expert_layer(x, lp, m, held=None, prec=HIGHEST, router_dtype=jnp.float32):
    """``(y, chosen [N, k], aux, router logits [N, E])`` of one expert layer on the tokens
    ``x [N, H]``: the part of the routed result that the experts ``held = (first, count)``
    give (``lp["moe"]``'s arrays hold exactly those), plus the shared expert behind its
    gate. ``router_dtype`` is float32, as published; ``bfloat16`` (with ``prec`` the
    default) is the second reading a limit on the router has to fail."""
    mp, sp = lp["moe"], lp["shared"]
    E = m.get("router_width") or m["num_experts"]
    k, F, S = m["num_experts_per_tok"], m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    first, count = held or held_range(m)
    logits = jnp.dot(x.astype(router_dtype), mp["router_w"].astype(router_dtype),
                     precision=prec).astype(jnp.float32)                           # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    # the weight of expert e for token n: its renormalised probability if chosen, else nothing
    weight = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32) * top[..., None], axis=1)

    def one_expert(y, e):
        gate_up, down = mp["w_gate_up"][e], mp["w_down"][e]
        gate = jnp.dot(x, gate_up[:, :F], precision=prec)
        up = jnp.dot(x, gate_up[:, F:], precision=prec)
        out = jnp.dot(jax.nn.silu(gate) * up, down, precision=prec)
        return y + jax.lax.dynamic_index_in_dim(weight, first + e, 1) * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(count))
    gate_up = jnp.dot(x, sp["w_gate_up"], precision=prec)
    shared = jnp.dot(jax.nn.silu(gate_up[:, :S]) * gate_up[:, S:], sp["w_down"], precision=prec)
    y = y + jax.nn.sigmoid(jnp.dot(x, sp["w_gate"], precision=prec)) * shared
    share = jnp.mean(jax.nn.one_hot(chosen, E, dtype=jnp.float32), axis=(0, 1))      # sums to 1
    aux = E * jnp.sum(jax.lax.stop_gradient(share) * jnp.mean(probs, axis=0))
    return y, chosen, aux, logits


def forward(params, tokens, labels, m, aux_coef, last=None):
    """A batch ``tokens [B, T]``: ``loss`` (mean cross-entropy + ``aux_coef`` x the
    load-balancing loss), its two parts, the logits of the ``last`` positions (all if
    None), the experts chosen ``[layers, B, T, k]`` sorted along k, and what every layer's
    mixer and expert layer was given (``mixer_in``, ``expert_in`` ``[layers, B, T, H]``:
    the system's layers are compared with the functions above on these same inputs)."""
    B, T = tokens.shape
    eps, period = m["rms_norm_eps"], m["full_attention_interval"]
    x = params["embed"][tokens]
    aux, chosen, mixer_in, expert_in = 0.0, [], [], []
    for l, lp in enumerate(params["layers"]):
        mixer_in.append(_norm(x, lp["norm_1"], eps))
        mix = full_attention if (l + 1) % period == 0 else linear_mixer
        x = x + mix(mixer_in[-1], lp["mixer"], m)
        expert_in.append(_norm(x, lp["norm_2"], eps))
        y, c, a, _ = expert_layer(expert_in[-1].reshape(B * T, -1), lp, m)
        x, aux = x + y.reshape(x.shape), aux + a
        chosen.append(jnp.sort(c, axis=-1).reshape(B, T, -1))
    aux = aux / len(params["layers"])
    x = _norm(x, params["norm_f"], eps)
    logits = jnp.dot(x, params["head"].T, precision=HIGHEST)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return {"loss": ce + aux_coef * aux, "ce": ce, "aux": aux,
            "logits": logits if last is None else logits[:, -last:], "experts": jnp.stack(chosen),
            "mixer_in": jnp.stack(mixer_in), "expert_in": jnp.stack(expert_in)}


def loss(params, tokens, labels, m, aux_coef):
    return forward(params, tokens, labels, m, aux_coef, last=1)["loss"]
