"""LFM2-24B-A2B (``model_type: lfm2_moe``) and its training loss, written plainly: ``jax.numpy``,
float32, matrix products at ``highest`` precision, the short convolution as ``conv_L_cache``
shifted products, softmax attention over whole rows of scores (a block of query positions at a
time) with each key/value head copied to its query heads by hand, the experts as a loop over the
held ones, no kernel, no sort, no cache. It reads the system's parameter tree
(``deepspeed_tpu/models/lfm2_moe.py``) and shares no code with it, nor with ``ops/``,
``models/layers.py`` or ``parallel/moe.py``.

A layer: ``h = x + Op(rms(x) g1);  y = h + FF(rms(h) g2)``, the operator a gated short convolution
or grouped-query attention by ``layer_types``, the MLP dense in the first ``num_dense_layers``
layers and an expert layer (no shared expert) in the rest; the head is the embedding table.

Follows the published keys. Readings and departures:

- ``w_in``'s columns are ``[B | C | z]``, 2048 each: ``u = B * z`` is convolved, ``C`` gates the
  result; gate and up of an MLP lie side by side in one array (the system's storage), as do a
  layer's key and value projections.
- The convolution is depthwise and causal: ``v_t = sum_j w[j] u_{t-(L-1)+j}``, the last tap on the
  token itself, zeros before the first token; no bias, no activation.
- q and k pass an RMSNorm over each head's 64 features with a learned weight before the rotary
  turn, which pairs feature ``i`` with ``i + 32`` (half-split) at ``rope_theta`` 1e6; the
  family's code pairs the same way.
- The selection bias chooses and never weighs; the chosen scores are renormalised over their sum
  plus ``router_eps`` (assumed, 1e-6) and scaled by ``routed_scaling_factor`` (1).
- The chip holds experts ``first_expert .. first_expert + num_experts - 1`` of the
  ``router_width`` the router chooses among: what the absent ones would add is left out, here
  as in the system. With ``stand_in`` the held experts stand in for the absent ones: expert
  ``e``'s part is computed with held expert ``first + (e - first) % count``'s matrices.
- Packed documents are not masked at their boundaries, in the attention or the convolution.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512         # query positions whose whole score rows exist at once


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _dot(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


def held_range(m):
    return m.get("first_expert", 0), m["num_experts"]


def turned(x, theta, angle_dtype=jnp.float32):
    """``x [B, T, heads, D]`` under the rotary turn at ``theta``, half-split: pair ``i`` is
    features ``i`` and ``i + D/2`` and turns by ``pos * theta^(-2i/D)``."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = (jnp.arange(T, dtype=jnp.float32).astype(angle_dtype)[:, None]
             * inv_freq.astype(angle_dtype)[None, :]).astype(jnp.float32)
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# ------------------------------------------------------------------- the two operators
def short_conv(x, cp, m, taps="causal", gates="B*z,C", activation=None):
    """The gated short convolution on the normed layer input ``x [B, T, H]``. Faults a limit
    has to catch: ``taps`` ``"reversed"`` (the FIRST tap on the token itself) or ``"ahead"`` (the
    window one token on: it sees the next token), ``gates`` ``"B*C,z"`` (the parts taken in another
    order) or ``"B,C"`` (the first gate left out), ``activation`` ``"silu"`` (the delta-rule
    mixers' convolution has one)."""
    T = x.shape[1]
    b, c, z = jnp.split(_dot(x, cp["w_in"]), 3, axis=-1)
    u, gate = {"B*z,C": (b * z, c), "B*C,z": (b * c, z), "B,C": (b, c)}[gates]
    w = cp["conv_w"][::-1] if taps == "reversed" else cp["conv_w"]
    L = w.shape[0]
    ahead = int(taps == "ahead")
    padded = jnp.pad(u, ((0, 0), (L - 1 - ahead, ahead), (0, 0)))
    v = sum(padded[:, j:j + T] * w[j] for j in range(L))
    if activation == "silu":
        v = jax.nn.silu(v)
    return _dot(gate * v, cp["w_out"])


def attention(x, ap, m, head_norms=True, angle_dtype=jnp.float32, softmax_dtype=jnp.float32):
    """The grouped-query attention on the normed layer input ``x [B, T, H]``. Faults:
    ``head_norms`` False (q and k go on unnormed), ``angle_dtype`` bfloat16 (the rotary angles in
    the compute dtype), ``softmax_dtype`` bfloat16."""
    B, T, H = x.shape
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    D, eps, theta = H // nq, m["norm_eps"], m["rope_parameters"]["rope_theta"]
    q = _dot(x, ap["wq"]).reshape(B, T, nq, D)
    k, v = jnp.split(_dot(x, ap["wkv"]).reshape(B, T, 2 * nkv, D), 2, axis=2)
    if head_norms:
        q, k = _norm(q, ap["q_norm"], eps), _norm(k, ap["k_norm"], eps)
    q, k = turned(q, theta, angle_dtype), turned(k, theta, angle_dtype)
    # query head a reads key/value head a // group: each copied to its query heads by hand
    k, v = (jnp.repeat(a, nq // nkv, axis=2) for a in (k, v))
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) * D ** -0.5
        i, j = (start + jnp.arange(block))[:, None], jnp.arange(T)[None, :]
        scores = jnp.where(j <= i, scores, -jnp.inf).astype(softmax_dtype)
        probs = jax.nn.softmax(scores, axis=-1).astype(jnp.float32)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)

    out = jax.lax.map(rows, jnp.arange(0, T, block))                 # [T / block, B, block, nq, D]
    out = jnp.moveaxis(out, 0, 1).reshape(B, T, nq * D)
    return _dot(out, ap["wo"])


# ------------------------------------------------------------------ the two MLPs
def gated(x, mp):
    """``W_down (silu(W_gate x) * W_up x)``, gate and up side by side in ``w_gate_up``."""
    gate, up = jnp.split(_dot(x, mp["w_gate_up"]), 2, axis=-1)
    return _dot(jax.nn.silu(gate) * up, mp["w_down"])


def dense_mlp(x, mp, halves="gate|up"):
    """A dense layer's MLP. Fault: ``halves`` ``"up|gate"`` (the activation on the other half)."""
    if halves == "up|gate":
        mp = dict(mp, w_gate_up=jnp.roll(mp["w_gate_up"], mp["w_gate_up"].shape[-1] // 2, axis=-1))
    return gated(x, mp)


def router(x, mp, m, eps, router_dtype=jnp.float32, renormalised=None):
    """``(chosen [N, k], weights [N, k], scores [N, E])`` for the tokens ``x [N, H]``:
    ``s = sigmoid(x W_r)`` in float32 over all ``router_width`` experts, the ``k`` largest of
    ``s + b`` chosen, each weighted by its own ``s`` over the chosen's sum plus ``eps``, times
    ``routed_scaling_factor``. Faults: ``router_dtype`` bfloat16, ``renormalised`` False (the chosen
    scores weigh as they are)."""
    logits = jnp.dot(x.astype(router_dtype), mp["router_w"].astype(router_dtype),
                     precision=HIGHEST).astype(jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + mp["router_bias"], m["num_experts_per_tok"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if m["norm_topk_prob"] if renormalised is None else renormalised:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + eps)
    return chosen, top * m["routed_scaling_factor"], s


def expert_layer(x, mp, m, eps, held=None, stand_in=None, **router_faults):
    """``(y, chosen [N, k], scores [N, E])`` of one expert layer on the tokens ``x [N, H]``: the
    part of the routed result that the experts ``held = (first, count)`` give (``mp``'s arrays
    hold exactly those; with ``stand_in`` each stands in for the experts that share its place
    modulo ``count``). No shared expert."""
    E = m.get("router_width") or m["num_experts"]
    first, count = held or held_range(m)
    chosen, top, s = router(x, mp, m, eps, **router_faults)
    # the weight of expert e for token n: its scaled share if chosen, else nothing
    weight = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32) * top[..., None], axis=1)
    if m.get("stand_in") if stand_in is None else stand_in:
        weight = jnp.roll(weight, -first, axis=1).reshape(-1, E // count, count).sum(axis=1)
        first = 0

    def one_expert(y, e):
        out = gated(x, {"w_gate_up": mp["w_gate_up"][e], "w_down": mp["w_down"][e]})
        return y + jax.lax.dynamic_index_in_dim(weight, first + e, 1) * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(count))
    return y, chosen, s


def assignments(chosen, E):
    """``c [E]``: how many of the (token, choice) pairs went to each expert."""
    return jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32), axis=tuple(range(chosen.ndim)))


def bias_update(b, counts, rate):
    """The rule that moves a layer's selection bias after a step, from that step's own counts
    ``c [E]``: ``b_e + rate * sign(mean(c) - c_e)``."""
    return b + rate * jnp.sign(jnp.mean(counts) - counts)


# ------------------------------------------------------------------------- model
def _cross_entropy(logits, labels):
    """The mean of ``-log softmax(logits)[label]`` over the positions whose label is not negative."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    valid = labels >= 0
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def forward(params, tokens, labels, m, eps, last=None, conv_faults=None, attention_faults=None,
            expert_faults=None, tied=True):
    """A batch ``tokens [B, T]`` with ``labels_i = t_{i+1}``: the loss, the logits of the ``last``
    positions (all if None), what every layer's operator and MLP were given after their norms
    (``op_in``, ``ff_in`` ``[L, B, T, H]``: the system's layers are compared with the functions
    above on these same inputs), and of the expert layers, in their order, the experts chosen
    ``[Le, B, T, k]`` sorted along k, the router's scores ``[Le, B, T, E]`` and the counts
    ``[Le, E]``. ``eps`` is the renormalisation's (the configuration's ``assumed``). Faults, never
    the cell: ``conv_faults``, ``attention_faults`` and ``expert_faults`` (keywords of
    ``short_conv``, ``attention`` and ``expert_layer``), ``tied`` False (the head a table of its
    own: the embedding rolled one feature round)."""
    B, T = tokens.shape
    norm_eps = m["norm_eps"]
    E = m.get("router_width") or m["num_experts"]
    op_in, ff_in, chosen, scores, counts = [], [], [], [], []
    x = params["embed"][tokens]
    for lp in params["layers"]:
        n = _norm(x, lp["norm_1"], norm_eps)
        op_in.append(n)
        if "conv" in lp:
            x = x + short_conv(n, lp["conv"], m, **(conv_faults or {}))
        else:
            x = x + attention(n, lp["attn"], m, **(attention_faults or {}))
        n = _norm(x, lp["norm_2"], norm_eps)
        ff_in.append(n)
        if "mlp" in lp:
            x = x + dense_mlp(n, lp["mlp"])
            continue
        y, c, s = expert_layer(n.reshape(B * T, -1), lp["moe"], m, eps, **(expert_faults or {}))
        chosen.append(jnp.sort(c, axis=-1).reshape(B, T, -1))
        scores.append(s.reshape(B, T, E))
        counts.append(assignments(c, E))
        x = x + y.reshape(x.shape)
    x = _norm(x, params["norm_f"], norm_eps)
    head = params["embed"] if tied else jnp.roll(params["embed"], 1, axis=1)
    logits = _dot(x, head.T)
    cut = (lambda a: a) if last is None else (lambda a: a[:, -last:])
    return {"loss": _cross_entropy(logits, labels), "logits": cut(logits),
            "op_in": jnp.stack(op_in), "ff_in": jnp.stack(ff_in), "experts": jnp.stack(chosen),
            "scores": jnp.stack(scores), "counts": jnp.stack(counts)}


def loss(params, tokens, labels, m, eps):
    return forward(params, tokens, labels, m, eps, last=1)["loss"]


def updated_biases(params, counts, rate):
    """Every expert layer's selection bias after the step whose counts are ``counts [Le, E]``."""
    expert_layers = [lp for lp in params["layers"] if "moe" in lp]
    return [bias_update(lp["moe"]["router_bias"], c, rate) for lp, c in zip(expert_layers, counts)]
