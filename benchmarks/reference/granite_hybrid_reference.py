"""Granite 4.0-H's forward pass and training loss, written plainly: ``jax.numpy``, float32,
matrix products at ``highest`` precision, the state-space layer as its recurrence a token at
a time (``lax.scan``; no chunked form), softmax attention over whole rows of scores (a block
of query positions at a time, so that 8,192 positions fit), no kernel, no cache. It reads the
system's parameter tree (``deepspeed_tpu/models/granite_hybrid.py``) and shares no code with
it.

Follows the published description (``model_type: granitemoehybrid`` of the source's
``config.json`` with ``num_local_experts`` 0, and the ``transformers`` port's
``torch_forward`` of its Mamba-2 layer). Departures:

- The fused ``wkv`` ([k | v], the heads of k first) is the system's storage; the checkpoint
  keeps ``k_proj`` and ``v_proj`` apart. With seeded weights the products are the published
  ones. Every other column order ([z | xBC | dt], [xs | B | C], [g | u]) is the published one.
- The state decays as ``S + expm1(dt A) S``, not ``exp(dt A) S``: on a TPU v5e ``exp`` of a
  small number is 1.16e-6 low, always, and a head that forgets slowly multiplies its state
  by that error hundreds of times over (PERF.md, PR 31). The same number in exact arithmetic.
- Routed experts (``num_local_experts > 0``), rotary embeddings and dropout are left out: the
  source's keys turn each off for this model.
- Packed documents are not masked at their boundaries, here as in the system.
- Sizes the source does not give are the configuration file's ``assumed`` (weights are the
  system's seeded ones either way).
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512         # query positions whose whole score rows exist at once


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def ssm_recurrent(xs, dt, A, Bm, Cm, D, state_dtype=jnp.float32, dt_dtype=jnp.float32):
    """``y [B, T, heads, P]``: a head's state ``S [P, N]`` from zero, a token at a time:
    ``S <- exp(dt_t A) S + dt_t x_t B_t^T;  y_t = S C_t + D x_t``. ``state_dtype`` and
    ``dt_dtype`` are float32; bfloat16 (the state rounded after every token; the step and
    with it the decay rounded) are the second readings a limit on the scan has to fail."""
    B, T, H, P = xs.shape
    dt = dt.astype(dt_dtype).astype(jnp.float32)

    def step(S, at):
        x_t, dt_t, B_t, C_t = at
        S = S.astype(jnp.float32)
        S = S + jnp.expm1(dt_t * A)[..., None, None] * S
        S = S + (dt_t[..., None] * x_t)[..., :, None] * B_t[:, None, None, :]
        y = jnp.einsum("bhpn,bn->bhp", S, C_t, precision=HIGHEST) + D[:, None] * x_t
        return S.astype(state_dtype), y

    at = [jnp.moveaxis(a, 1, 0) for a in (xs, dt, Bm, Cm)]
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, Bm.shape[-1]), state_dtype), at)
    return jnp.moveaxis(y, 0, 1)


def mamba_inputs(x, mp, m):
    """What the scan of one mixer is given, from the normed block input ``x [B, T, H]``:
    ``(xs [B, T, heads, P], dt [B, T, heads], B, C [B, T, N], z [B, T, heads * P])``."""
    B, T, _ = x.shape
    heads, P, N, W = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"], m["mamba_d_conv"]
    inner = heads * P
    proj = jnp.dot(x, mp["w_in"], precision=HIGHEST)
    z, xBC, dt = jnp.split(proj, [inner, 2 * inner + 2 * N], axis=-1)
    padded = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    xBC = jax.nn.silu(sum(padded[:, j:j + T] * mp["conv_w"][j] for j in range(W)) + mp["conv_b"])
    xs, Bm, Cm = jnp.split(xBC, [inner, inner + N], axis=-1)
    return xs.reshape(B, T, heads, P), jax.nn.softplus(dt + mp["dt_bias"]), Bm, Cm, z


def mamba_mixer(x, mp, m, gate_first=True, **lower):
    """The Mamba-2 mixer on the normed block input ``x [B, T, H]``. ``gate_first`` is the
    published order (gate, then the norm over all channels); False is a fault a limit has to
    catch, as are ``lower``'s dtypes (``ssm_recurrent``)."""
    B, T, _ = x.shape
    xs, dt, Bm, Cm, z = mamba_inputs(x, mp, m)
    y = ssm_recurrent(xs, dt, -jnp.exp(mp["A_log"]), Bm, Cm, mp["D"], **lower).reshape(B, T, -1)
    if gate_first:
        y = _norm(y * jax.nn.silu(z), mp["norm"], m["rms_norm_eps"])
    else:
        y = _norm(y, mp["norm"], m["rms_norm_eps"]) * jax.nn.silu(z)
    return jnp.dot(y, mp["w_out"], precision=HIGHEST)


def attention(x, mp, m):
    """The position-free grouped-query attention on the normed block input ``x [B, T, H]``."""
    B, T, H = x.shape
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    D = H // nq
    q = jnp.dot(x, mp["wq"], precision=HIGHEST).reshape(B, T, nq, D)
    k, v = jnp.split(jnp.dot(x, mp["wkv"], precision=HIGHEST).reshape(B, T, 2 * nkv, D), 2, axis=2)
    k, v = (jnp.repeat(a, nq // nkv, axis=2) for a in (k, v))
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) * m["attention_multiplier"]
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)

    out = jax.lax.map(rows, jnp.arange(0, T, block))                 # [T / block, B, block, nq, D]
    out = jnp.moveaxis(out, 0, 1).reshape(B, T, nq * D)
    return jnp.dot(out, mp["wo"], precision=HIGHEST)


def mlp(x, mp, m):
    g, u = jnp.split(jnp.dot(x, mp["w_in"], precision=HIGHEST), 2, axis=-1)
    return jnp.dot(jax.nn.silu(g) * u, mp["w_out"], precision=HIGHEST)


def forward(params, tokens, labels, m, last=None):
    """A batch ``tokens [B, T]``: the mean cross-entropy ``loss``, the logits of the ``last``
    positions (all if None), and what every layer's mixer was given (``mixer_in``
    ``[layers, B, T, H]``: the system's layers are compared with the functions above on
    these same inputs)."""
    eps, r = m["rms_norm_eps"], m["residual_multiplier"]
    x = params["embed"][tokens] * m["embedding_multiplier"]
    mixer_in = []
    for kind, lp in zip(m["layer_types"], params["layers"]):
        mixer_in.append(_norm(x, lp["norm_1"], eps))
        mix = attention if kind == "attention" else mamba_mixer
        x = x + r * mix(mixer_in[-1], lp["mixer"], m)
        x = x + r * mlp(_norm(x, lp["norm_2"], eps), lp["mlp"], m)
    x = _norm(x, params["norm_f"], eps)
    logits = jnp.dot(x, params["embed"].T, precision=HIGHEST) / m["logits_scaling"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return {"loss": loss, "logits": logits if last is None else logits[:, -last:],
            "mixer_in": jnp.stack(mixer_in)}


def loss(params, tokens, labels, m):
    return forward(params, tokens, labels, m, last=1)["loss"]
