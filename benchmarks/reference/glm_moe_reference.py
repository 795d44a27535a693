"""GLM-4.7-Flash (``model_type: glm4_moe_lite``) and its training loss at two prediction depths,
written plainly: ``jax.numpy``, float32, matrix products at ``highest`` precision, softmax
attention over whole rows of scores (a block of query positions at a time) with the one rotary
key copied to every head by hand, the experts as a loop over the held ones, no kernel, no sort,
no cache. It reads the system's parameter tree (``deepspeed_tpu/models/glm_moe.py``) and shares
no code with it, nor with ``ops/``, ``models/layers.py`` or ``parallel/moe.py``.

A block: ``h = x + Attn(rms(x) g1);  y = h + MLP(rms(h) g2)``, the MLP dense in the first
``first_k_dense_replace`` blocks and an expert layer with its shared expert in the rest.

Follows the published keys and DeepSeek-V3's description (arXiv:2412.19437, sections 2.1 and
2.2), whose block this is. Readings and departures:

- ``wkv_b``'s columns are a head's ``[k_nope | v]``, head after head, and ``wq_b``'s a head's
  ``[q_nope | q_rope]`` (the family's code views both products so); gate and up of an MLP lie
  side by side in one array (the system's storage).
- The rotary turn pairs feature ``i`` with ``i + 32`` of the 64 rotary features (half-split); the
  family's code pairs neighbours: with seeded weights a fixed permutation of columns.
- The softmax scale is ``1 / sqrt(qk_nope_head_dim + qk_rope_head_dim)``; no ``mscale``
  (``rope_scaling`` is null).
- The selection bias chooses and never weighs; ``n_group = topk_group = 1`` is the plain top-k.
- The chip holds experts ``first_expert .. first_expert + n_routed_experts - 1`` of the
  ``router_width`` the router chooses among: what the absent ones would add is left out, here
  as in the system. With ``stand_in`` the held experts stand in for the absent ones: expert
  ``e``'s part is computed with held expert ``first + (e - first) % count``'s matrices.
- The prediction module takes the main model's hidden state AFTER its last norm and puts the
  next token's embedding FIRST in the concatenation (assumed: ``described_as`` says "MTP 1");
  embedding and head are the main model's; its loss weighs ``mtp_loss_weight`` (assumed).
- The module follows the last block that is run here (the fifth), where the published model's
  follows its forty-seventh; packed documents are not masked at their boundaries.
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
    return m.get("first_expert", 0), m["n_routed_experts"]


def turned(x, theta):
    """``x [B, T, heads, D]`` under the rotary turn at ``theta``, half-split: pair ``i`` is
    features ``i`` and ``i + D/2`` and turns by ``pos * theta^(-2i/D)``."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# --------------------------------------------------------------------- attention
def attention(x, ap, m, rotary_key="shared", scale_width=None, latent_norm=True,
              softmax_dtype=jnp.float32):
    """The latent attention on the normed block input ``x [B, T, H]``. Faults a limit has to
    catch: ``rotary_key`` ``"left_out"`` (the keys' rotary part zero) or ``"a_head_its_own"``
    (head ``a`` reads the key's features moved ``a`` places round), ``scale_width`` (192: the
    scale of the part without position alone), ``latent_norm`` False (the key/value latent goes
    on unnormed), ``softmax_dtype`` bfloat16."""
    B, T, _ = x.shape
    n, nope, rot = m["num_attention_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    wide, R, eps = m["v_head_dim"], m["kv_lora_rank"], m["rms_norm_eps"]
    c_q = _norm(_dot(x, ap["wq_a"]), ap["q_norm"], eps)
    q = _dot(c_q, ap["wq_b"]).reshape(B, T, n, nope + rot)
    q = jnp.concatenate([q[..., :nope], turned(q[..., nope:], m["rope_theta"])], axis=-1)
    latent = _dot(x, ap["wkv_a"])
    c_kv, k_r = latent[..., :R], turned(latent[..., None, R:], m["rope_theta"])      # [B, T, 1, rot]
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
    scale = (scale_width or nope + rot) ** -0.5
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) * scale
        i, j = (start + jnp.arange(block))[:, None], jnp.arange(T)[None, :]
        scores = jnp.where(j <= i, scores, -jnp.inf).astype(softmax_dtype)
        probs = jax.nn.softmax(scores, axis=-1).astype(jnp.float32)
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
def combine(e, h, mp, m):
    """The prediction module's input ``[rms(e) g_e | rms(h) g_h] W_eh``."""
    eps = m["rms_norm_eps"]
    return _dot(jnp.concatenate([_norm(e, mp["norm_e"], eps), _norm(h, mp["norm_h"], eps)], axis=-1),
                mp["w_eh"])


def _cross_entropy(logits, labels):
    """The mean of ``-log softmax(logits)[label]`` over the positions whose label is not negative."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    valid = labels >= 0
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def forward(params, tokens, labels, m, mtp_weight, last=None, attention_faults=None,
            expert_faults=None, mtp_embeds="next", first_block_dense=True):
    """A batch ``tokens [B, T]`` with ``labels_i = t_{i+1}``: ``loss = loss_main + mtp_weight x
    loss_mtp``, both depths' logits of the ``last`` positions (all if None), what every block's
    attention and MLP were given after their norms (``attn_in``, ``mlp_in`` ``[L + 1, B, T, H]``,
    the module's block last: the system's layers are compared with the functions above on these
    same inputs), the module's two inputs side by side (``mtp_in`` ``[B, T, 2H]``: the embedded
    next tokens, then the main model's last norm's output), and of the expert layers, in their
    order, the experts chosen ``[Le, B, T, k]`` sorted along k, the router's scores ``[Le, B, T,
    E]`` and the counts ``[Le, E]``. Faults, never the cell: ``attention_faults`` and
    ``expert_faults`` (keywords of ``attention`` and ``expert_layer``), ``mtp_embeds``
    ``"current"`` (the module is fed ``t_i``), ``first_block_dense`` False (block 0 runs block 1's
    expert layer), and ``mtp_weight`` itself (0: the second loss dropped)."""
    B, T = tokens.shape
    eps = m["rms_norm_eps"]
    E = m.get("router_width") or m["n_routed_experts"]
    attn_in, mlp_in, chosen, scores, counts = [], [], [], [], []

    def block(x, lp):
        n = _norm(x, lp["norm_1"], eps)
        attn_in.append(n)
        x = x + attention(n, lp["attn"], m, **(attention_faults or {}))
        n = _norm(x, lp["norm_2"], eps)
        mlp_in.append(n)
        if "mlp" in lp:
            return x + dense_mlp(n, lp["mlp"])
        y, c, s = expert_layer(n.reshape(B * T, -1), lp, m, **(expert_faults or {}))
        chosen.append(jnp.sort(c, axis=-1).reshape(B, T, -1))
        scores.append(s.reshape(B, T, E))
        counts.append(assignments(c, E))
        return x + y.reshape(x.shape)

    x = params["embed"][tokens]
    for l, lp in enumerate(params["layers"]):
        if l == 0 and not first_block_dense:
            lp = dict({k: v for k, v in lp.items() if k != "mlp"},
                      moe=params["layers"][1]["moe"], shared=params["layers"][1]["shared"])
        x = block(x, lp)
    x = _norm(x, params["norm_f"], eps)
    logits = _dot(x, params["head"].T)
    loss_main = _cross_entropy(logits, labels)

    mp = params["mtp"]
    after = jnp.concatenate([labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
    e = params["embed"][labels if mtp_embeds == "next" else tokens]
    y = _norm(block(combine(e, x, mp, m), mp["block"]), mp["norm_s"], eps)
    logits_mtp = _dot(y, params["head"].T)
    loss_mtp = _cross_entropy(logits_mtp, after)
    cut = (lambda a: a) if last is None else (lambda a: a[:, -last:])
    return {"loss": loss_main + mtp_weight * loss_mtp, "loss_main": loss_main, "loss_mtp": loss_mtp,
            "logits": cut(logits), "logits_mtp": cut(logits_mtp),
            "attn_in": jnp.stack(attn_in), "mlp_in": jnp.stack(mlp_in),
            "mtp_in": jnp.concatenate([e, x], axis=-1), "experts": jnp.stack(chosen),
            "scores": jnp.stack(scores), "counts": jnp.stack(counts)}


def loss(params, tokens, labels, m, mtp_weight):
    return forward(params, tokens, labels, m, mtp_weight, last=1)["loss"]


def expert_blocks(params):
    """The blocks that hold an expert layer, in the order of ``forward``'s ``counts``."""
    return [lp for lp in params["layers"] if "moe" in lp] + [params["mtp"]["block"]]


def updated_biases(params, counts, rate):
    """Every expert layer's selection bias after the step whose counts are ``counts [Le, E]``."""
    return [bias_update(lp["moe"]["router_bias"], c, rate)
            for lp, c in zip(expert_blocks(params), counts)]
