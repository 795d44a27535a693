"""Nemotron-H's language model (``model_type: nemotron_h``: the first tower of
Nemotron-Labs-TwoTower-30B-A3B), its training loss and the rule that moves its routers'
selection bias, written plainly: ``jax.numpy``, float32, matrix products at ``highest``
precision, the state-space layer as its recurrence a token at a time (``lax.scan``; no
chunked form), softmax attention over whole rows of scores (a block of query positions at a
time), the experts as a loop over the held ones, no kernel, no sort, no cache. It reads the
system's parameter tree (``deepspeed_tpu/models/nemotron_h.py``) and shares no code with it,
nor with ``ops/`` or ``parallel/moe.py``.

A layer is ONE of a Mamba-2 mixer (``M``), an expert layer (``E``) or a grouped-query
attention (``*``), by ``hybrid_override_pattern``: ``x <- x + f(rms(x) w)``.

Follows the published keys and the family's modelling code. Departures and readings:

- The fused ``wkv`` ([k | v], the heads of k first) is the system's storage; the checkpoint
  keeps ``k_proj`` and ``v_proj`` apart. Column orders [z | xBC | dt] and [xs | B | C] are the
  published ones; B and C are ``n_groups`` blocks of ``ssm_state_size``, head ``h`` reading
  group ``h // (heads / n_groups)``.
- The state decays as ``S + expm1(dt A) S`` (``granite_hybrid_reference.py`` has why).
- The attention carries no positional term (the family's modelling code applies none;
  ``rope_theta`` and ``partial_rotary_factor`` are read by nothing).
- ``n_group`` 1 and ``topk_group`` 1: the group-limited choice is the plain top-k.
- The chip holds experts ``first_expert .. first_expert + n_routed_experts - 1`` of the
  ``router_width`` the router chooses among: what the absent ones would add is left out,
  here as in the system. With ``stand_in`` the held experts stand in for the absent ones:
  expert ``e``'s part is computed with held expert ``first + (e - first) % count``'s two
  matrices, so every one of a token's six choices adds its part. The shared expert is whole
  and ungated.
- The second tower (the denoiser), its conditioning and block diffusion are not modelled.
- Packed documents are not masked at their boundaries, here as in the system.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512         # query positions whose whole score rows exist at once
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def kinds(m):
    return m["hybrid_override_pattern"][:m["num_hidden_layers"]]


def held_range(m):
    return m.get("first_expert", 0), m["n_routed_experts"]


# ----------------------------------------------------------------------- Mamba-2
def ssm_recurrent(xs, dt, A, Bm, Cm, D, state_dtype=jnp.float32, dt_dtype=jnp.float32,
                  shift_groups=0):
    """``y [B, T, heads, P]``: a head's state ``S [P, N]`` from zero, a token at a time:
    ``S <- exp(dt_t A) S + dt_t x_t B_t^T;  y_t = S C_t + D x_t`` with ``Bm``, ``Cm``
    ``[B, T, G, N]``, head ``h`` reading group ``h // (heads / G)``. ``state_dtype`` and
    ``dt_dtype`` are float32; bfloat16 (the state rounded after every token; the step and
    with it the decay rounded) and ``shift_groups`` (every head reads the B of the group that
    many further on) are faults a limit on the scan has to catch."""
    B, T, H, P = xs.shape
    G = Bm.shape[2]
    dt = dt.astype(dt_dtype).astype(jnp.float32)
    Bh = jnp.repeat(jnp.roll(Bm, -shift_groups, axis=2), H // G, axis=2)        # [B, T, H, N]
    Ch = jnp.repeat(Cm, H // G, axis=2)

    def step(S, at):
        x_t, dt_t, B_t, C_t = at
        S = S.astype(jnp.float32)
        S = S + jnp.expm1(dt_t * A)[..., None, None] * S
        S = S + (dt_t[..., None] * x_t)[..., :, None] * B_t[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", S, C_t, precision=HIGHEST) + D[:, None] * x_t
        return S.astype(state_dtype), y

    at = [jnp.moveaxis(a, 1, 0) for a in (xs, dt, Bh, Ch)]
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, Bm.shape[-1]), state_dtype), at)
    return jnp.moveaxis(y, 0, 1)


def mamba_inputs(x, mp, m):
    """What the scan of one mixer is given, from the normed layer input ``x [B, T, H]``:
    ``(xs [B, T, heads, P], dt [B, T, heads], B, C [B, T, G, N], z [B, T, heads * P])``."""
    B, T, _ = x.shape
    heads, P, N, W, G = (m["mamba_num_heads"], m["mamba_head_dim"], m["ssm_state_size"],
                         m["conv_kernel"], m["n_groups"])
    inner = heads * P
    proj = jnp.dot(x, mp["w_in"], precision=HIGHEST)
    z, xBC, dt = jnp.split(proj, [inner, 2 * inner + 2 * G * N], axis=-1)
    padded = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    xBC = jax.nn.silu(sum(padded[:, j:j + T] * mp["conv_w"][j] for j in range(W)) + mp["conv_b"])
    xs, Bm, Cm = jnp.split(xBC, [inner, inner + G * N], axis=-1)
    return (xs.reshape(B, T, heads, P), jax.nn.softplus(dt + mp["dt_bias"]),
            Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N), z)


def mamba_mixer(x, mp, m, norm_groups=None, **lower):
    """The Mamba-2 mixer on the normed layer input ``x [B, T, H]``: the scan, the gate, THEN
    the norm over each of the ``n_groups`` groups of channels. ``norm_groups`` (another
    number of groups for the norm) and ``lower`` (``ssm_recurrent``) are faults a limit has
    to catch."""
    B, T, _ = x.shape
    xs, dt, Bm, Cm, z = mamba_inputs(x, mp, m)
    y = ssm_recurrent(xs, dt, -jnp.exp(mp["A_log"]), Bm, Cm, mp["D"], **lower).reshape(B, T, -1)
    g = (y * jax.nn.silu(z)).reshape(B, T, norm_groups or m["n_groups"], -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + m["layer_norm_epsilon"])
    return jnp.dot(g.reshape(B, T, -1) * mp["norm"], mp["w_out"], precision=HIGHEST)


# --------------------------------------------------------------------- attention
def attention(x, mp, m, shift_kv_heads=0):
    """The position-free grouped-query attention on the normed layer input ``x [B, T, H]``:
    ``num_attention_heads`` query heads of ``head_dim`` over ``num_key_value_heads``, query head
    ``h`` reading key/value head ``h // (heads / kv heads)``. ``shift_kv_heads`` (every query
    head reads the key/value head that many further on) is a fault a limit has to catch."""
    B, T, _ = x.shape
    nq, nkv, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = jnp.dot(x, mp["wq"], precision=HIGHEST).reshape(B, T, nq, D)
    k, v = jnp.split(jnp.dot(x, mp["wkv"], precision=HIGHEST).reshape(B, T, 2 * nkv, D), 2, axis=2)
    k, v = (jnp.repeat(jnp.roll(a, -shift_kv_heads, axis=2), nq // nkv, axis=2) for a in (k, v))
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) * D ** -0.5
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)

    out = jax.lax.map(rows, jnp.arange(0, T, block))                 # [T / block, B, block, nq, D]
    out = jnp.moveaxis(out, 0, 1).reshape(B, T, nq * D)
    return jnp.dot(out, mp["wo"], precision=HIGHEST)


# ------------------------------------------------------------------ expert layer
def router(x, mp, m, router_dtype=jnp.float32, prec=HIGHEST, bias_in="choice", scaled=True):
    """``(chosen [N, k], weights [N, k], scores [N, E])`` for the tokens ``x [N, H]``:
    ``s = sigmoid(W x)`` in float32 over all ``router_width`` experts, the ``k`` largest of
    ``s + b`` chosen, each weighted by ITS ``s`` over the chosen ones' sum, times
    ``routed_scaling_factor``. Faults a limit has to catch: ``router_dtype`` bfloat16;
    ``bias_in`` ``"none"`` (the bias left out of the choice) or ``"weight"`` (let into the
    weights too); ``scaled`` False (the factor dropped)."""
    logits = jnp.dot(x.astype(router_dtype), mp["router_w"].astype(router_dtype),
                     precision=prec).astype(jnp.float32)
    s = jax.nn.sigmoid(logits)
    b = mp["router_bias"].astype(jnp.float32)
    _, chosen = jax.lax.top_k(s if bias_in == "none" else s + b, m["num_experts_per_tok"])
    top = jnp.take_along_axis(s + b if bias_in == "weight" else s, chosen, axis=-1)
    if m["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return chosen, top * (m["routed_scaling_factor"] if scaled else 1.0), s


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def expert_layer(x, lp, m, held=None, act=relu2, **router_faults):
    """``(y, chosen [N, k], scores [N, E])`` of one expert layer on the tokens ``x [N, H]``:
    the part of the routed result that the experts ``held = (first, count)`` give
    (``lp["moe"]``'s arrays hold exactly those), plus the shared expert, ungated. ``act``
    (``jax.nn.relu`` for the squared one) and ``router_faults`` (``router``) are faults a
    limit has to catch."""
    mp, sp = lp["moe"], lp["shared"]
    E = m.get("router_width") or m["n_routed_experts"]
    first, count = held or held_range(m)
    chosen, top, s = router(x, mp, m, **router_faults)
    # the weight of expert e for token n: its scaled share if chosen, else nothing
    weight = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32) * top[..., None], axis=1)

    if m.get("stand_in"):
        # held expert first + j stands in for every expert e with (e - first) % count == j:
        # its weight for a token is the sum of theirs
        weight = jnp.roll(weight, -first, axis=1).reshape(-1, E // count, count).sum(axis=1)
        first = 0

    def one_expert(y, e):
        hidden = act(jnp.dot(x, mp["w_up"][e], precision=HIGHEST))
        out = jnp.dot(hidden, mp["w_down"][e], precision=HIGHEST)
        return y + jax.lax.dynamic_index_in_dim(weight, first + e, 1) * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(count))
    shared = jnp.dot(act(jnp.dot(x, sp["w_up"], precision=HIGHEST)), sp["w_down"], precision=HIGHEST)
    return y + shared, chosen, s


def assignments(chosen, E):
    """``c [E]``: how many of the (token, choice) pairs went to each expert."""
    return jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32), axis=tuple(range(chosen.ndim)))


def bias_update(b, counts, rate):
    """The rule that moves a layer's selection bias after a step, from that step's own
    counts ``c [E]``: ``b_e + rate * sign(mean(c) - c_e)`` (an expert that got less than its
    share is made likelier, one that got more less likely)."""
    return b + rate * jnp.sign(jnp.mean(counts) - counts)


# ------------------------------------------------------------------------- model
def forward(params, tokens, labels, m, last=None, **expert_faults):
    """A batch ``tokens [B, T]``: the mean cross-entropy ``loss``, the logits of the ``last``
    positions (all if None), what every layer was given after its norm (``layer_in``
    ``[layers, B, T, H]``: the system's layers are compared with the functions above on these
    same inputs), and of the expert layers, in their order, the experts chosen ``[Le, B, T,
    k]`` sorted along k, the router's scores ``[Le, B, T, E]`` and the counts ``[Le, E]``.
    ``expert_faults`` go to every expert layer (``expert_layer``): never the cell."""
    B, T = tokens.shape
    eps = m["layer_norm_epsilon"]
    E = m.get("router_width") or m["n_routed_experts"]
    x = params["embed"][tokens]
    layer_in, chosen, scores, counts = [], [], [], []
    for kind, lp in zip(kinds(m), params["layers"]):
        n = _norm(x, lp["norm"], eps)
        layer_in.append(n)
        if kind == EXPERTS:
            y, c, s = expert_layer(n.reshape(B * T, -1), lp, m, **expert_faults)
            x = x + y.reshape(x.shape)
            chosen.append(jnp.sort(c, axis=-1).reshape(B, T, -1))
            scores.append(s.reshape(B, T, E))
            counts.append(assignments(c, E))
        else:
            x = x + (attention if kind == ATTENTION else mamba_mixer)(n, lp["mixer"], m)
    x = _norm(x, params["norm_f"], eps)
    logits = jnp.dot(x, params["head"].T, precision=HIGHEST)
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return {"loss": loss, "logits": logits if last is None else logits[:, -last:],
            "layer_in": jnp.stack(layer_in), "experts": jnp.stack(chosen),
            "scores": jnp.stack(scores), "counts": jnp.stack(counts)}


def loss(params, tokens, labels, m):
    return forward(params, tokens, labels, m, last=1)["loss"]


def updated_biases(params, counts, m, rate):
    """Every expert layer's selection bias after the step whose counts are ``counts [Le, E]``,
    in the layers' order."""
    layers = [lp for kind, lp in zip(kinds(m), params["layers"]) if kind == EXPERTS]
    return [bias_update(lp["moe"]["router_bias"], c, rate) for lp, c in zip(layers, counts)]
