"""GPT-2 as published (Radford et al. 2019; openai/gpt-2 ``model.py``), in plain
``jax.numpy`` and float32: no kernel, no cache, no batching tricks, no chunked loss.

Pre-layer-norm blocks, learned position embeddings, fused qkv projection, causal
softmax attention scaled by 1/sqrt(head size), GELU in its tanh form ("gelu_new"),
output head tied to the token embedding. It reads the program's parameter tree
(``wte``, ``wpe``, ``blocks[i].{ln_1, attn, ln_2, mlp}``, ``ln_f``; weights laid out
[in, out]) and nothing else of the program. Departures from the source: none in the
mathematics; dropout is off, as in the configurations.

On a TPU a float32 matrix multiplication runs in lower precision unless
``jax.default_matmul_precision("highest")`` is set, so every entry point sets it.
"""

import math

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, p, n_head):
    B, T, E = x.shape
    hd = E // n_head
    qkv = x @ p["c_attn_w"] + p["c_attn_b"]
    q, k, v = (t.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3) for t in jnp.split(qkv, 3, -1))
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    y = (probs @ v).transpose(0, 2, 1, 3).reshape(B, T, E)
    return y @ p["c_proj_w"] + p["c_proj_b"]


def _mlp(x, p):
    return _gelu_new(x @ p["c_fc_w"] + p["c_fc_b"]) @ p["c_proj_w"] + p["c_proj_b"]


def logits(params, tokens, *, n_head, eps=1e-5):
    """[B, T] tokens -> [B, T, V] float32 logits."""
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        T = tokens.shape[1]
        x = p["wte"][tokens] + p["wpe"][jnp.arange(T)]
        for bp in p["blocks"]:
            x = x + _attention(_layer_norm(x, bp["ln_1"], eps), bp["attn"], n_head)
            x = x + _mlp(_layer_norm(x, bp["ln_2"], eps), bp["mlp"])
        return _layer_norm(x, p["ln_f"], eps) @ p["wte"].T


def loss(params, tokens, labels, *, n_head, eps=1e-5):
    """Mean next-token cross-entropy over all positions."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(logits(params, tokens, n_head=n_head, eps=eps), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
