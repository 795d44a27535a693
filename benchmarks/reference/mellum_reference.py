"""Mellum 2 (``model_type: mellum``) and its training loss, written plainly: ``jax.numpy``,
float32, matrix products at ``highest`` precision, softmax attention over whole rows of scores
under a boolean ``[block, T]`` mask (a block of query positions at a time), both rotary tables
from their equations, the experts as a loop over the held ones, no kernel, no sort, no cache.
It reads the system's parameter tree (``deepspeed_tpu/models/mellum.py``) and shares no code
with it, nor with ``ops/``, ``models/layers.py`` or ``parallel/moe.py``.

A layer of kind ``layer_types[l]``: ``h = x + Attn_kind(rms(x) g1);  y = h + MoE(rms(h) g2)``.

Follows the published keys and the Qwen3-MoE family's modelling code. Readings and departures:

- The fused ``wkv`` ([k | v], the heads of k first) is the system's storage; the checkpoint
  keeps ``k_proj`` and ``v_proj`` apart.
- q and k pass a per-head RMSNorm with a learned weight before the rotary table (the family's;
  the published keys name none for it).
- Rotary tables, ``i = 0 .. D/2 - 1``: a ``sliding_attention`` layer turns pair ``i`` by
  ``pos * theta^(-2i/D)``. A ``full_attention`` layer (``rope_type: yarn``): with ``c(r) = D
  ln(original / (2 pi r)) / (2 ln theta)``, ``low = floor(c(beta_fast))``, ``high =
  ceil(c(beta_slow))``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``inv_freq_i =
  theta^(-2i/D) ((1 - ramp_i) + ramp_i / factor)``, and cos and sin both times
  ``attention_factor``. Static: the same at any length.
- A ``sliding_attention`` query ``i`` sees the keys ``i - sliding_window < j <= i``.
- The chip holds experts ``first_expert .. first_expert + num_experts - 1`` of the
  ``router_width`` the router chooses among: what the absent ones would add is left out, here
  as in the system. With ``stand_in`` the held experts stand in for the absent ones: expert
  ``e``'s part is computed with held expert ``first + (e - first) % count``'s three matrices.
- The load-balancing term is ``E sum_e f_e P_e`` over all ``router_width`` experts, the
  layers' mean, times the configuration's assumed coefficient.
- No multi-token-prediction head; packed documents are not masked at their boundaries.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512         # query positions whose whole score rows exist at once
SLIDING, FULL = "sliding_attention", "full_attention"


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def kinds(m):
    return m["layer_types"][:m["num_hidden_layers"]]


def held_range(m):
    return m.get("first_expert", 0), m["num_experts"]


# ------------------------------------------------------------------ rotary tables
def rotary_table(m, kind, truncate=True, scaled=True, dtype=np.float64):
    """``(inv_freq [D / 2], factor)`` of a layer of ``kind``, from the equations above, in
    ``dtype`` (float64: the table itself; the model casts it to float32). ``truncate`` False
    (the ramp's ends neither floored nor ceiled) and ``scaled`` False (``attention_factor``
    dropped) are faults a limit has to catch."""
    D = m["head_dim"]
    how = m["rope_parameters"][kind]
    theta = how["rope_theta"]
    i = np.arange(D // 2, dtype=dtype)
    inv_freq = np.asarray(theta, dtype) ** (-2 * i / D)
    if how.get("rope_type", "default") == "default":
        return inv_freq, 1.0
    assert how["rope_type"] == "yarn", how["rope_type"]
    original = how["original_max_position_embeddings"]

    def c(r):
        return D * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))
    low, high = c(how["beta_fast"]), c(how["beta_slow"])
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv_freq = inv_freq * ((1 - ramp) + ramp / how["factor"])
    return inv_freq, (how["attention_factor"] if scaled else 1.0)


def turned(x, table):
    """``x [B, T, heads, D]`` under the rotary ``table`` in the half-split convention: pair
    ``i`` is features ``i`` and ``i + D/2``."""
    inv_freq, factor = table
    T = x.shape[1]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = (f(angle)[None, :, None, :] * factor for f in (jnp.cos, jnp.sin))
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# --------------------------------------------------------------------- attention
def attention(x, lp, m, kind, window="published", table=None, kv_head="grouped",
              softmax_dtype=jnp.float32):
    """The grouped-query attention of a layer of ``kind`` on the normed layer input ``x [B,
    T, H]``. Faults a limit has to catch: ``window`` (None on a sliding layer, a number on a
    full one, one more or less), ``table`` (the other kind's, or ``rotary_table`` at fault),
    ``kv_head`` ``"strided"`` (query head ``a`` reads key/value head ``a mod kv heads``),
    ``softmax_dtype`` bfloat16."""
    B, T, _ = x.shape
    nq, nkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    if window == "published":
        window = m["sliding_window"] if kind == SLIDING and m.get("use_sliding_window", True) else None
    table = table or rotary_table(m, kind)
    q = jnp.dot(x, lp["wq"], precision=HIGHEST).reshape(B, T, nq, D)
    k, v = jnp.split(jnp.dot(x, lp["wkv"], precision=HIGHEST).reshape(B, T, 2 * nkv, D), 2, axis=2)
    q, k = turned(_norm(q, lp["q_norm"], eps), table), turned(_norm(k, lp["k_norm"], eps), table)
    if kv_head == "grouped":
        k, v = (jnp.repeat(a, nq // nkv, axis=2) for a in (k, v))
    else:
        k, v = (jnp.tile(a, (1, 1, nq // nkv, 1)) for a in (k, v))
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) * D ** -0.5
        i, j = (start + jnp.arange(block))[:, None], jnp.arange(T)[None, :]
        seen = j <= i if window is None else (j <= i) & (i - j < window)
        scores = jnp.where(seen, scores, -jnp.inf).astype(softmax_dtype)
        probs = jax.nn.softmax(scores, axis=-1).astype(jnp.float32)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)

    out = jax.lax.map(rows, jnp.arange(0, T, block))                 # [T / block, B, block, nq, D]
    out = jnp.moveaxis(out, 0, 1).reshape(B, T, nq * D)
    return jnp.dot(out, lp["wo"], precision=HIGHEST)


# ------------------------------------------------------------------ expert layer
def router(x, mp, m, router_dtype=jnp.float32, renormalised=None):
    """``(chosen [N, k], weights [N, k], probs [N, E], logits [N, E])`` for the tokens ``x
    [N, H]``: softmax in float32 over all ``router_width`` experts, the ``k`` largest, divided
    by their sum where ``norm_topk_prob``. Faults: ``router_dtype`` bfloat16, ``renormalised``
    False."""
    logits = jnp.dot(x.astype(router_dtype), mp["router_w"].astype(router_dtype),
                     precision=HIGHEST).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m["norm_topk_prob"] if renormalised is None else renormalised:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return chosen, top, probs, logits


def expert_layer(x, lp, m, held=None, stand_in=None, **router_faults):
    """``(y, chosen [N, k], aux, logits [N, E], probs [N, E])`` of one expert layer on the
    tokens ``x [N, H]``: the part of the result that the experts ``held = (first, count)``
    give (``lp["moe"]``'s arrays hold exactly those; with ``stand_in`` each stands in for the
    experts that share its place modulo ``count``), and the load-balancing term over all
    experts."""
    mp = lp["moe"]
    E = m.get("router_width") or m["num_experts"]
    F = m["moe_intermediate_size"]
    first, count = held or held_range(m)
    chosen, top, probs, logits = router(x, mp, m, **router_faults)
    weight = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32) * top[..., None], axis=1)   # [N, E]
    share = jnp.mean(jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32), axis=1), axis=0) \
        / m["num_experts_per_tok"]
    aux = E * jnp.sum(jax.lax.stop_gradient(share) * jnp.mean(probs, axis=0))
    if m.get("stand_in") if stand_in is None else stand_in:
        weight = jnp.roll(weight, -first, axis=1).reshape(-1, E // count, count).sum(axis=1)
        first = 0

    def one_expert(y, e):
        gate, up = jnp.split(jnp.dot(x, mp["w_gate_up"][e], precision=HIGHEST), [F], axis=-1)
        out = jnp.dot(jax.nn.silu(gate) * up, mp["w_down"][e], precision=HIGHEST)
        return y + jax.lax.dynamic_index_in_dim(weight, first + e, 1) * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(count))
    return y, chosen, aux, logits, probs


# ------------------------------------------------------------------------- model
def forward(params, tokens, labels, m, aux_coef, last=None, attention_faults=None,
            expert_faults=None):
    """A batch ``tokens [B, T]``: ``loss`` (mean cross-entropy + ``aux_coef`` x the layers'
    mean load-balancing term) and its parts, the logits of the ``last`` positions (all if
    None), what every layer's attention and expert layer were given after their norms
    (``attn_in``, ``expert_in`` ``[L, B, T, H]``: the system's layers are compared with the
    functions above on these same inputs), the experts chosen ``[L, B, T, k]`` sorted along k
    and the router's probabilities ``[L, B, T, E]``. ``attention_faults`` is ``{kind:
    {keyword: value}}`` and ``expert_faults`` keywords of ``expert_layer``: never the cell."""
    B, T = tokens.shape
    eps = m["rms_norm_eps"]
    E = m.get("router_width") or m["num_experts"]
    x = params["embed"][tokens]
    aux, attn_in, expert_in, chosen, probs = 0.0, [], [], [], []
    for kind, lp in zip(kinds(m), params["layers"]):
        n = _norm(x, lp["norm_1"], eps)
        attn_in.append(n)
        x = x + attention(n, lp, m, kind, **(attention_faults or {}).get(kind, {}))
        n = _norm(x, lp["norm_2"], eps)
        expert_in.append(n)
        y, c, a, _, p = expert_layer(n.reshape(B * T, -1), lp, m, **(expert_faults or {}))
        x, aux = x + y.reshape(x.shape), aux + a
        chosen.append(jnp.sort(c, axis=-1).reshape(B, T, -1))
        probs.append(p.reshape(B, T, E))
    aux = aux / len(params["layers"])
    x = _norm(x, params["norm_f"], eps)
    logits = jnp.dot(x, params["head"].T, precision=HIGHEST)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return {"loss": ce + aux_coef * aux, "ce": ce, "aux": aux,
            "logits": logits if last is None else logits[:, -last:],
            "attn_in": jnp.stack(attn_in), "expert_in": jnp.stack(expert_in),
            "experts": jnp.stack(chosen), "probs": jnp.stack(probs)}


def loss(params, tokens, labels, m, aux_coef):
    return forward(params, tokens, labels, m, aux_coef, last=1)["loss"]


# -------------------------------------------------------------------- edge probe
def edge_probe_values(T, D, kv_heads, dtype):
    """``v [1, kv_heads, T, D]``: position ``j``'s value is the one-hot of ``j mod D``. With
    ``q = k = 0`` every allowed key weighs the same, so a query's output is the share of its
    allowed keys that fall on each lane: exactly ``(window / D) / window`` on every lane for a
    query that sees a whole window that ``D`` divides."""
    v = np.zeros((T, D), np.float32)
    v[np.arange(T), np.arange(T) % D] = 1.0
    return jnp.asarray(np.broadcast_to(v, (1, kv_heads, T, D)), dtype)


def edge_probe_error(out, window, D):
    """The largest distance of ``out [.., T, D]`` from ``1 / D`` over the positions from
    ``window - 1`` on: 0 for a band of exactly ``window`` keys (``D`` divides ``window``); a
    band one key wider or narrower puts ``(window / D ± 1) / (window ± 1)`` on one lane, and
    no band at all puts ``ceil((i + 1) / D) / (i + 1)`` there at position ``i``."""
    assert window % D == 0, (window, D)
    tail = np.asarray(out, np.float64)[..., window - 1:, :]
    return float(np.abs(tail - 1.0 / D).max())
