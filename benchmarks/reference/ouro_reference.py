"""Ouro's forward pass and training loss, written plainly: ``jax.numpy``, float32, matrix
products at ``highest`` precision, softmax attention over whole rows of scores (a block of
query positions at a time, so that 4,096 positions fit), the whole logits of every exit (a
block of positions at a time), no kernel, no recomputation, no chunked head. It reads the
system's parameter tree (``deepspeed_tpu/models/ouro.py``) and shares no code with it.

Follows the published description: the source's ``config.json`` (``model_type: ouro``) for
the widths, ``total_ut_steps`` and ``early_exit_threshold``; the family's report ("Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741) and its published modeling
file, as the configuration's ``assumed`` lists them, for the four norms a block, ``norm_f``
carried into the next pass, the exit gate and the training loss. Departures:

- The exit distribution is made of products of ``1 - lambda`` as the report writes it; the
  system makes it of sums of logs. The same numbers in exact arithmetic.
- The LAST pass's gate is never asked: its exit takes what the earlier passes let through
  (``p^T = prod_{j<T}(1 - lambda^j)``), so that p sums to one whatever the gate says.
- ``early_exit_threshold`` 1: no exit is taken early, and nothing here takes one.
- Sliding windows, dropout and a key/value cache are left out: the source's keys turn the
  first off, the others are not training's.
- Packed documents are not masked at their boundaries, here as in the system.
- Sizes the source does not give are the configuration file's ``assumed`` (weights are the
  system's seeded ones either way).

``lower`` precisions (``exit_dtype``, ``ce_dtype``, ``sum_dtype``) are float32; bfloat16 there
is the second reading a limit has to fail (``tests/perf/ouro_precision_probe.py``).
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512         # query positions whose whole score rows exist at once
LOGITS_BLOCK = 1024       # positions whose whole logits exist at once


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Half-split rotary embedding on ``x [B, T, heads, D]``: feature ``i`` of the first half
    and of the second turn together by ``position * theta^(-2i/D)``."""
    T, D = x.shape[1], x.shape[-1]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x, lp, m):
    """Causal softmax attention on the normed block input ``x [B, T, H]``."""
    B, T, _ = x.shape
    n, D = m["num_attention_heads"], m["head_dim"]
    q, k, v = (jnp.dot(x, lp[name], precision=HIGHEST).reshape(B, T, n, D) for name in ("wq", "wk", "wv"))
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) * D ** -0.5
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)

    out = jax.lax.map(rows, jnp.arange(0, T, block))                 # [T / block, B, block, n, D]
    return jnp.dot(jnp.moveaxis(out, 0, 1).reshape(B, T, n * D), lp["wo"], precision=HIGHEST)


def mlp(x, lp):
    gate, up = (jnp.dot(x, lp[name], precision=HIGHEST) for name in ("w_gate", "w_up"))
    return jnp.dot(jax.nn.silu(gate) * up, lp["w_down"], precision=HIGHEST)


def block(h, lp, m, sandwich=True):
    """One layer: a norm before AND after each branch. ``sandwich`` False (the norms after
    left out) is a fault a limit has to catch."""
    eps = m["rms_norm_eps"]
    after = (lambda y, w: _norm(y, w, eps)) if sandwich else (lambda y, w: y)
    h = h + after(attention(_norm(h, lp["norm_1"], eps), lp, m), lp["norm_2"])
    return h + after(mlp(_norm(h, lp["norm_3"], eps), lp), lp["norm_4"])


def one_pass(params, x, m, **fault):
    """``norm_f`` of the layers applied once to ``x``."""
    for lp in params["layers"]:
        x = block(x, lp, m, **fault)
    return _norm(x, params["norm_f"], m["rms_norm_eps"])


def exits(states, gate, exit_dtype=jnp.float32):
    """``(p [T, B, S], entropy [B, S])`` of the exit states ``[T, B, S, H]``: the gate asks every
    pass but the last. ``exit_dtype`` bfloat16 rounds the gate's logit and makes the
    distribution and its entropy in that dtype: a fault a limit has to catch."""
    logit = jnp.einsum("tbsh,h->tbs", states[:-1], gate["w"], precision=HIGHEST) + gate["b"]
    p = exit_distribution(jax.nn.sigmoid(logit.astype(exit_dtype)))
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return p.astype(jnp.float32), entropy.astype(jnp.float32)


def exit_distribution(lam):
    """``p [T, ...]`` from the first ``T - 1`` passes' ``lambda [T - 1, ...]``: ``p^t`` is
    ``lambda^t`` of what the passes before let through, the last pass takes the rest."""
    p, through = [], jnp.ones(lam.shape[1:], lam.dtype)
    for l in lam:
        p.append(l * through)
        through = through * (1 - l)
    return jnp.stack(p + [through])


def cross_entropy(x, head, labels, ce_dtype=jnp.float32):
    """``(l [B, T], logits [B, T, V])``: ``-log softmax(head x)[label]``, 0 where the label is
    negative, the whole logits of ``LOGITS_BLOCK`` positions at a time. ``ce_dtype``
    bfloat16 rounds the logits before the softmax and the loss after it."""
    B, T, _ = x.shape
    size = min(LOGITS_BLOCK, T)
    assert T % size == 0, (T, size)

    def rows(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)
        lb = jax.lax.dynamic_slice_in_dim(labels, start, size, axis=1)
        logits = jnp.dot(xb, head.T, precision=HIGHEST)
        logp = jax.nn.log_softmax(logits.astype(ce_dtype), axis=-1)
        gold = jnp.take_along_axis(logp, jnp.maximum(lb, 0)[..., None], axis=-1)[..., 0]
        return jnp.where(lb >= 0, -gold, 0).astype(jnp.float32), logits

    ce, logits = jax.lax.map(rows, jnp.arange(0, T, size))
    return (jnp.moveaxis(ce, 0, 1).reshape(B, T), jnp.moveaxis(logits, 0, 1).reshape(B, T, -1))


def forward(params, tokens, labels, m, beta, last=None, untied=None, exit_dtype=jnp.float32,
            ce_dtype=jnp.float32, carry_norm=True, **fault):
    """A batch ``tokens [B, S]``: the ``loss``; every exit's loss a position ``ce [T, B, S]``
    and their means ``exit_ce [T]``; the exit distribution ``p [T, B, S]`` and its ``entropy``
    ``[B, S]``; the logits of the ``last`` positions of every exit ``[T, B, last, V]`` (all if
    None); the exit states ``states [T, B, S, H]`` (``x^1 .. x^T``).

    ``untied`` is one list of layers a PASS in the shared leaves' place (``T`` copies whose
    gradients, summed, are a shared leaf's). ``exit_dtype`` and ``ce_dtype`` bfloat16 (``exits``,
    ``cross_entropy``) and ``carry_norm`` False, which hands the next pass the un-normed stream,
    are faults a limit has to catch."""
    T = m["total_ut_steps"]
    x = params["embed"][tokens]
    states, ces, logits = [], [], []
    for t in range(T):
        h = x
        for lp in params["layers"] if untied is None else untied[t]:
            h = block(h, lp, m, **fault)
        state = _norm(h, params["norm_f"], m["rms_norm_eps"])
        x = state if carry_norm else h
        states.append(state)
        ce, z = cross_entropy(state, params["head"], labels, ce_dtype)
        ces.append(ce)
        logits.append(z if last is None else z[:, -last:])
    states, ce = jnp.stack(states), jnp.stack(ces)
    p, entropy = exits(states, params["gate"], exit_dtype)
    valid = labels >= 0
    count = jnp.maximum(jnp.sum(valid), 1)
    a_position = jnp.sum(p * ce, axis=0) - beta * entropy
    return {"loss": jnp.sum(jnp.where(valid, a_position, 0.0)) / count,
            "ce": ce, "exit_ce": jnp.sum(jnp.where(valid, ce, 0.0), axis=(1, 2)) / count,
            "p": p, "entropy": entropy, "logits": jnp.stack(logits), "states": states}


def loss(params, tokens, labels, m, beta, **how):
    return forward(params, tokens, labels, m, beta, last=1, **how)["loss"]


def shared_gradient_by_pass(params, tokens, labels, m, beta, layer, names):
    """The gradients of the loss by leaves ``names`` of layer ``layer`` of ``total_ut_steps``
    UNTIED copies of the layers, a copy a pass: one dict a pass. Summed, a shared leaf's."""
    T = m["total_ut_steps"]

    def by_pass(copies):
        untied = [[dict(lp, **copies[t]) if l == layer else lp for l, lp in enumerate(params["layers"])]
                  for t in range(T)]
        return loss(params, tokens, labels, m, beta, untied=untied)

    return jax.grad(by_pass)([{name: params["layers"][layer][name] for name in names} for _ in range(T)])


def sum_over_passes(by_pass, sum_dtype=jnp.float32, without=None):
    """A shared leaf's gradient from its passes' contributions. ``sum_dtype`` bfloat16 rounds
    every contribution and adds them in that dtype; ``without`` leaves one pass's out: faults a
    limit on a shared leaf's gradient has to catch."""
    kept = [g for t, g in enumerate(by_pass) if t != without]
    total = {name: g.astype(sum_dtype) for name, g in kept[0].items()}
    for g in kept[1:]:
        total = {name: total[name] + g[name].astype(sum_dtype) for name in total}
    return {name: v.astype(jnp.float32) for name, v in total.items()}


def shared_gradient(params, tokens, labels, m, beta, layer, names, **fault):
    return sum_over_passes(shared_gradient_by_pass(params, tokens, labels, m, beta, layer, names), **fault)


def head_gradients(x, head, labels, cot, kept_dtype=jnp.float32):
    """The gradients of ``sum(cross_entropy(x, head, labels) * cot)`` by ``x`` and ``head``,
    written out: ``g = softmax - onehot`` a position, ``dx = cot g head``, ``dhead = g^T cot x``.
    ``kept_dtype`` rounds ``g`` as a head that keeps it between forward and backward does:
    float32 is ``jax.grad``'s result, bfloat16 what the system keeps, float8 the precision below
    it, which a limit on the head's gradients has to catch."""
    logits = jnp.dot(x, head.T, precision=HIGHEST)
    g = jax.nn.softmax(logits, axis=-1) - jax.nn.one_hot(labels, head.shape[0], dtype=jnp.float32)
    kept = jnp.finfo(kept_dtype)       # reduce_precision: a convert there and back is optimised away
    g = jax.lax.reduce_precision(jnp.where((labels >= 0)[..., None], g, 0.0), kept.nexp, kept.nmant)
    dx = cot[..., None] * jnp.dot(g, head, precision=HIGHEST)
    return dx, jnp.einsum("bsv,bsh->vh", g, cot[..., None] * x, precision=HIGHEST)
