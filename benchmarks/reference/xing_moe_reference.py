"""Xing4.0-29B-A4B (``model_type: xing4_0``) and its training loss, written plainly: ``jax.numpy``,
float32, matrix products at ``highest`` precision, the residual streams as an explicit ``[n, C]``
axis of ``[B, T, n, C]``, Sinkhorn-Knopp as a Python loop, softmax attention over whole rows of scores
(a block of query positions at a time) with keys of one width and values of another and the one
rotary key copied to every head by hand, the experts as a loop over the held ones, no kernel, no
sort, no cache. It reads the system's parameter tree (``deepspeed_tpu/models/xing_moe.py``) and
shares no code with it, nor with ``models/hyper_connections.py``, ``models/glm_moe.py``, ``ops/``,
``models/layers.py`` or ``parallel/moe.py``.

A block, on X [n, C] a token, for each of its two sub-layers (attention, then MLP):

    x~ = rms(vec(X)) g_hc;  H_pre = sigmoid(a_pre x~ Phi_pre + b_pre);  H_post = 2 sigmoid(a_post x~ Phi_post + b_post)
    M = exp(clip(a_res mat(x~ Phi_res) + B_res, lo, hi));  20 times: M /= rowsum(M) + eps; M /= colsum(M) + eps
    u = sum_i H_pre[i] X[i];  f = F(rms(u) g);  X'[i] = sum_j M[i, j] X[j] + H_post[i] f

Follows the published keys, DeepSeek-V3's description of the block (arXiv:2412.19437 section 2.1)
and mHC's of the residual path (arXiv:2512.24880; hyper-connections, arXiv:2409.19606). Readings and
departures:

- every stream starts as the token's embedding and the last norm reads the streams' sum
  (hyper-connections section 3); ``mat`` is row-major: ``H_res[i, j]`` is column ``i n + j`` of
  ``x~ Phi_res``; ``H_res[i, j]`` weighs stream ``j`` in new stream ``i``, its rows are
  normalised first; ``hc_eps`` sits in both divisions; the flattened norm has a weight.
- ``wkv_b``'s columns are a head's ``[k_nope | v]`` (128 | 128), head after head, and ``wq_b``'s a
  head's ``[q_nope | q_rope]`` (128 | 64); gate and up of an MLP lie side by side in one array.
- The rotary turn pairs feature ``i`` with ``i + 32`` of the 64 rotary features (half-split); the
  family's code pairs neighbours: with seeded weights a fixed permutation of columns. YaRN: pair
  ``i``'s frequency is ``theta^(-2i/64)`` up to the pair that makes ``beta_fast`` turns over the
  original length, that over ``factor`` from the pair that makes ``beta_slow`` turns on, a linear
  ramp between (its ends floored and ceiled); cos and sin times ``m(mscale) / m(mscale_all_dim)``
  (1 here); the scores times ``m(mscale_all_dim)^2 / sqrt(192)``, ``m(s) = 0.1 s ln(factor) + 1``.
- The selection bias chooses and never weighs; ``n_group = topk_group = 1`` is the plain top-k.
- The chip holds experts ``first_expert .. first_expert + n_routed_experts - 1`` of the
  ``router_width`` the router chooses among: what the absent ones would add is left out, here
  as in the system. With ``stand_in`` the held experts stand in for the absent ones: expert
  ``e``'s part is computed with held expert ``first + (e - first) % count``'s matrices.
- No prediction module (``num_nextn_predict_layers`` 0 here: it lies on a further chip); one dense
  block of the published two; packed documents are not masked at their boundaries.
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512         # query positions whose whole score rows exist at once


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _dot(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


def held_range(m):
    return m.get("first_expert", 0), m["n_routed_experts"]


# ------------------------------------------------------------------ the residual path
def coefficients(X, hp, m, iters=None, dtype=jnp.float32, post_factor=2.0):
    """``(H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n])`` from the streams ``X [B, T, n,
    C]``. Faults a limit has to catch: ``iters`` (fewer Sinkhorn-Knopp rounds than the published
    20), ``dtype`` bfloat16 (the coefficients' arithmetic after the projection in half the
    mantissa), ``post_factor`` 1 (``H_post`` a plain sigmoid)."""
    B, T, n, C = X.shape
    eps, lo, hi = m["hc_eps"], m["mhc_h_res_clamp_min"], m["mhc_h_res_clamp_max"]
    normed = _norm(X.reshape(B, T, n * C), hp["norm"], m["rms_norm_eps"])
    a_pre, a_post, a_res = hp["gates"].astype(dtype)
    project = lambda phi: _dot(normed, phi).astype(dtype)           # noqa: E731
    pre = a_pre * project(hp["phi_pre"]) + hp["b_pre"].astype(dtype)
    post = a_post * project(hp["phi_post"]) + hp["b_post"].astype(dtype)
    res = a_res * project(hp["phi_res"]).reshape(B, T, n, n) + hp["b_res"].astype(dtype)
    M = jnp.exp(jnp.clip(res, lo, hi))
    for _ in range(m["hc_sinkhorn_iters"] if iters is None else iters):
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + jnp.asarray(eps, dtype))       # rows
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + jnp.asarray(eps, dtype))       # columns
    f32 = lambda a: a.astype(jnp.float32)                            # noqa: E731
    return f32(jax.nn.sigmoid(pre)), f32(post_factor * jax.nn.sigmoid(post)), f32(M)


def connected(X, hp, m, sub_layer, **faults):
    """One sub-layer inside its hyper-connection: ``(X', u, H_res)`` with ``sub_layer(u) -> f``."""
    H_pre, H_post, H_res = coefficients(X, hp, m, **faults)
    # sums over the stream axis written out (float32, exact): a product of 4 x 4 matrices a token is
    # thousands of tiny matrix products to the chip's compiler
    u = jnp.sum(H_pre[..., None] * X, axis=2)                                   # sum_i H_pre[i] X[i]
    f = sub_layer(u)
    mixed = jnp.sum(H_res[..., None] * X[:, :, None, :, :], axis=3)             # sum_j H_res[i, j] X[j]
    return mixed + H_post[..., None] * f[:, :, None, :], u, H_res


# --------------------------------------------------------------------- attention
def yarn_inverse_frequencies(width, theta, s):
    """The ``width / 2`` inverse frequencies under the published ``rope_scaling`` entry ``s``
    (None: the plain ``theta^(-2i/width)``)."""
    plain = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    if s is None:
        return plain

    def pair_of(turns):         # the pair that makes ``turns`` turns over the original length
        return width * math.log(s["original_max_position_embeddings"] / (2 * math.pi * turns)) / (2 * math.log(theta))
    low = max(math.floor(pair_of(s["beta_fast"])), 0)
    high = min(math.ceil(pair_of(s["beta_slow"])), width - 1)
    ramp = jnp.clip((jnp.arange(width // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / s["factor"] * ramp


def yarn_magnitude(s, key):
    """``m(s[key]) = 0.1 s[key] ln(factor) + 1`` (1 where there is no scaling, or no such key)."""
    if s is None or s["factor"] <= 1 or not s.get(key, 0):
        return 1.0
    return 0.1 * s[key] * math.log(s["factor"]) + 1.0


def turned(x, inv_freq, magnitude):
    """``x [B, T, heads, D]`` under the rotary turn, half-split: pair ``i`` is features ``i`` and
    ``i + D/2`` and turns by ``pos * inv_freq[i]``; cos and sin times ``magnitude``."""
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (f(angle)[None, :, None, :] * magnitude for f in (jnp.cos, jnp.sin))
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x, ap, m, rotary_key="shared", yarn=True, m_squared=True, latent_norm=True):
    """The latent attention on the normed stream ``x [B, T, H]``: keys ``qk_nope + qk_rope`` wide,
    values ``v_head_dim``. Faults a limit has to catch: ``rotary_key`` ``"left_out"`` (the keys'
    rotary part zero) or ``"a_head_its_own"`` (head ``a`` reads the key's features moved ``a``
    places round), ``yarn`` False (the plain frequencies), ``m_squared`` False (the scale without
    YaRN's ``m^2``), ``latent_norm`` False (the key/value latent goes on unnormed)."""
    B, T, _ = x.shape
    n, nope, rot = m["num_attention_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    wide, R, eps = m["v_head_dim"], m["kv_lora_rank"], m["rms_norm_eps"]
    s = m.get("rope_scaling")
    inv_freq = yarn_inverse_frequencies(rot, m["rope_theta"], s if yarn else None)
    all_dim = yarn_magnitude(s, "mscale_all_dim")
    magnitude = yarn_magnitude(s, "mscale") / all_dim
    c_q = _norm(_dot(x, ap["wq_a"]), ap["q_norm"], eps)
    q = _dot(c_q, ap["wq_b"]).reshape(B, T, n, nope + rot)
    q = jnp.concatenate([q[..., :nope], turned(q[..., nope:], inv_freq, magnitude)], axis=-1)
    latent = _dot(x, ap["wkv_a"])
    c_kv, k_r = latent[..., :R], turned(latent[..., None, R:], inv_freq, magnitude)      # [B, T, 1, rot]
    if latent_norm:
        c_kv = _norm(c_kv, ap["kv_norm"], eps)
    kv = _dot(c_kv, ap["wkv_b"]).reshape(B, T, n, nope + wide)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    # the one rotary key, copied to every head by hand
    if rotary_key == "shared":
        k_r = jnp.concatenate([k_r] * n, axis=2)
    elif rotary_key == "left_out":
        k_r = jnp.zeros((B, T, n, rot), x.dtype)
    else:
        assert rotary_key == "a_head_its_own", rotary_key
        k_r = jnp.concatenate([jnp.roll(k_r, a, axis=-1) for a in range(n)], axis=2)
    k = jnp.concatenate([k_nope, k_r], axis=-1)
    scale = (all_dim * all_dim if m_squared else 1.0) * (nope + rot) ** -0.5
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) * scale
        i, j = (start + jnp.arange(block))[:, None], jnp.arange(T)[None, :]
        probs = jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)

    out = jax.lax.map(rows, jnp.arange(0, T, block))                 # [T / block, B, block, n, wide]
    out = jnp.moveaxis(out, 0, 1).reshape(B, T, n * wide)
    return _dot(out, ap["wo"])


# ------------------------------------------------------------------ the two MLPs
def gated(x, mp):
    """``W_down (silu(W_gate x) * W_up x)``, gate and up side by side in ``w_gate_up``."""
    gate, up = jnp.split(_dot(x, mp["w_gate_up"]), 2, axis=-1)
    return _dot(jax.nn.silu(gate) * up, mp["w_down"])


def dense_mlp(x, mp):
    return gated(x, mp)


def router(x, mp, m, router_dtype=jnp.float32, factor=None):
    """``(chosen [N, k], weights [N, k], scores [N, E])`` for the tokens ``x [N, H]``:
    ``s = sigmoid(x W_r)`` in float32 over all ``router_width`` experts, the ``k`` largest of
    ``s + b`` chosen, each weighted by its own ``s`` over the chosen's sum, times
    ``routed_scaling_factor``. Faults: ``router_dtype`` bfloat16, ``factor`` (1.0)."""
    logits = jnp.dot(x.astype(router_dtype), mp["router_w"].astype(router_dtype),
                     precision=HIGHEST).astype(jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + mp["router_bias"], m["num_experts_per_tok"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if m["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return chosen, top * (m["routed_scaling_factor"] if factor is None else factor), s


def expert_layer(x, lp, m, held=None, stand_in=None, **router_faults):
    """``(y, chosen [N, k], scores [N, E])`` of one expert layer on the tokens ``x [N, H]``: the
    part of the routed result that the experts ``held = (first, count)`` give (``lp["moe"]``'s
    arrays hold exactly those; with ``stand_in`` each stands in for the experts that share its
    place modulo ``count``), plus the shared expert, ungated."""
    mp = lp["moe"]
    E = m.get("router_width") or m["n_routed_experts"]
    first, count = held or held_range(m)
    chosen, top, s = router(x, mp, m, **router_faults)
    # the weight of expert e for token n: its scaled share if chosen, else nothing
    weight = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32) * top[..., None], axis=1)
    if m.get("stand_in") if stand_in is None else stand_in:
        weight = jnp.roll(weight, -first, axis=1).reshape(-1, E // count, count).sum(axis=1)
        first = 0

    def one_expert(y, e):
        out = gated(x, {"w_gate_up": mp["w_gate_up"][e], "w_down": mp["w_down"][e]})
        return y + jax.lax.dynamic_index_in_dim(weight, first + e, 1) * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(count))
    return y + gated(x, lp["shared"]), chosen, s


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


def forward(params, tokens, labels, m, last=None, hc_faults=None, attention_faults=None,
            expert_faults=None, streams="copies", keep_inputs=True):
    """A batch ``tokens [B, T]`` with ``labels_i = t_{i+1}``: the loss, the logits of the ``last``
    positions (all if None), what every block's attention and MLP were given after their norms
    (``attn_in``, ``mlp_in`` ``[L, B, T, H]``) and every sub-layer's streams on entry (``hc_in``
    ``[2 L, B, T, n, C]``, a block's attention then its MLP; the system's layers are compared with
    the functions above on these same inputs; left out with ``keep_inputs`` False), ``H_res``'s
    distance from doubly stochastic and its diagonal's mean a sub-layer (``hc_res_err_max``,
    ``hc_res_diag_mean`` ``[2 L]``), and of the expert layers, in their order, the experts chosen
    ``[Le, B, T, k]`` sorted along k, the router's scores ``[Le, B, T, E]`` and the counts ``[Le, E]``.
    Faults, never the cell: ``hc_faults``, ``attention_faults`` and ``expert_faults`` (keywords of
    ``coefficients``, ``attention`` and ``expert_layer``), ``streams`` ``"first_only"`` (the
    embedding in stream 0 and zeros in the others)."""
    B, T = tokens.shape
    eps, n = m["rms_norm_eps"], m["hc_mult"]
    E = m.get("router_width") or m["n_routed_experts"]
    attn_in, mlp_in, hc_in, err, diag, chosen, scores, counts = [], [], [], [], [], [], [], []

    def noted(X, hp, sub_layer):
        hc_in.append(X)
        X, _, H_res = connected(X, hp, m, sub_layer, **(hc_faults or {}))
        err.append(jnp.maximum(jnp.max(jnp.abs(jnp.sum(H_res, axis=-1) - 1.0)),
                               jnp.max(jnp.abs(jnp.sum(H_res, axis=-2) - 1.0))))
        diag.append(jnp.mean(jnp.diagonal(H_res, axis1=-2, axis2=-1)))
        return X

    def block(X, lp):
        def mixer(u):
            attn_in.append(_norm(u, lp["norm_1"], eps))
            return attention(attn_in[-1], lp["attn"], m, **(attention_faults or {}))

        def mlp(u):
            mlp_in.append(_norm(u, lp["norm_2"], eps))
            if "mlp" in lp:
                return dense_mlp(mlp_in[-1], lp["mlp"])
            y, c, s = expert_layer(mlp_in[-1].reshape(B * T, -1), lp, m, **(expert_faults or {}))
            chosen.append(jnp.sort(c, axis=-1).reshape(B, T, -1))
            scores.append(s.reshape(B, T, E))
            counts.append(assignments(c, E))
            return y.reshape(u.shape)
        return noted(noted(X, lp["hc_attn"], mixer), lp["hc_mlp"], mlp)

    e = params["embed"][tokens]
    X = jnp.stack([e] * n if streams == "copies" else [e] + [jnp.zeros_like(e)] * (n - 1), axis=2)
    for lp in params["layers"]:
        X = block(X, lp)
    x = _norm(jnp.sum(X, axis=2), params["norm_f"], eps)
    logits = _dot(x, params["head"].T)
    out = {"loss": _cross_entropy(logits, labels), "logits": logits if last is None else logits[:, -last:],
           "hc_res_err_max": jnp.stack(err), "hc_res_diag_mean": jnp.stack(diag),
           "experts": jnp.stack(chosen), "scores": jnp.stack(scores), "counts": jnp.stack(counts)}
    if keep_inputs:
        out.update(attn_in=jnp.stack(attn_in), mlp_in=jnp.stack(mlp_in), hc_in=jnp.stack(hc_in))
    return out


def loss(params, tokens, labels, m):
    return forward(params, tokens, labels, m, last=1, keep_inputs=False)["loss"]


def expert_blocks(params):
    """The blocks that hold an expert layer, in the order of ``forward``'s ``counts``."""
    return [lp for lp in params["layers"] if "moe" in lp]


def updated_biases(params, counts, rate):
    """Every expert layer's selection bias after the step whose counts are ``counts [Le, E]``."""
    return [bias_update(lp["moe"]["router_bias"], c, rate)
            for lp, c in zip(expert_blocks(params), counts)]
