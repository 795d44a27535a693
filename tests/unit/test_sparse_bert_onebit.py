"""Combined workload: long-sequence sparse-attention BERT encoder trained with 1-bit
Adam through the engine — BASELINE.json's "Long-seq sparse-attention BERT + 1-bit Adam
compressed allreduce over ICI" config, exercised end to end on the 8-device mesh
(warmup AND compressed phases)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.sparse_attention import BertSparseSelfAttention, FixedSparsityConfig
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.runtime.engine import DeepSpeedEngine

VOCAB, SEQ, HID, HEADS, LAYERS = 64, 64, 32, 4, 2


class SparseBertEcho:
    """Tiny sparse-attention encoder + tied head; loss = CE reconstructing the input
    tokens (learnable fast, exercises the sparse kernels + engine end to end)."""

    def __init__(self):
        cfg = FixedSparsityConfig(num_heads=HEADS, block=16, num_local_blocks=2,
                                  num_global_blocks=1, attention="bidirectional")
        self.attn = [BertSparseSelfAttention(HID, HEADS, cfg) for _ in range(LAYERS)]

    def init(self, rng):
        ks = jax.random.split(rng, 3 * LAYERS + 1)
        params = {"embed": jax.random.normal(ks[0], (VOCAB, HID), jnp.float32) * 0.1,
                  "layers": []}
        for i in range(LAYERS):
            # every weight live from step 1: 1-bit Adam freezes the variance estimate at
            # freeze_step, so parameters whose gradients only wake up later would divide
            # a full-size compressed momentum by a near-zero frozen sqrt(v)
            params["layers"].append({
                "attn": self.attn[i].init(ks[1 + 3 * i]),
                "ln": {"scale": jnp.ones((HID,), jnp.float32),
                       "bias": jnp.zeros((HID,), jnp.float32)},
                "ffn": {"w1": jax.random.normal(ks[2 + 3 * i], (HID, 2 * HID),
                                                jnp.float32) * 0.1,
                        "b1": jnp.zeros((2 * HID,), jnp.float32),
                        "w2": jax.random.normal(ks[3 + 3 * i], (2 * HID, HID),
                                                jnp.float32) * 0.1,
                        "b2": jnp.zeros((HID,), jnp.float32)},
            })
        return params

    @staticmethod
    def _ln(x, p):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]

    def apply(self, params, tokens):
        x = params["embed"][tokens]
        for i, lp in enumerate(params["layers"]):
            x = x + self.attn[i].apply(lp["attn"], x)
            h = jax.nn.gelu(x @ lp["ffn"]["w1"] + lp["ffn"]["b1"])
            x = self._ln(x + h @ lp["ffn"]["w2"] + lp["ffn"]["b2"], lp["ln"])
        logits = jnp.dot(x, params["embed"].T, preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0])


def test_sparse_bert_with_onebit_adam_trains(eight_devices):
    model = SparseBertEcho()
    params = model.init(jax.random.PRNGKey(0))
    FREEZE = 8
    engine = DeepSpeedEngine(
        model=model, model_parameters=params,
        mesh=build_mesh(data=8, model=1, pipe=1),
        config_params={"train_batch_size": 8, "steps_per_print": 100,
                       "optimizer": {"type": "OneBitAdam",
                                     "params": {"lr": 1e-3, "freeze_step": FREEZE}}})
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(FREEZE + 6):   # warmup (exact allreduce) + 6 compressed steps
        toks = rng.integers(0, VOCAB, (8, SEQ)).astype(np.int32)  # varied batches
        loss = engine(toks)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"
    # compressed phase must keep converging, not just the warmup
    assert losses[-1] < losses[FREEZE], f"no progress after freeze_step: {losses}"
