#!/usr/bin/env python
"""OLMoE pretraining example: the published block (RMSNorm, RoPE, QK-norm, top-8 of 64
SiLU-gated experts) through ``deepspeed_tpu.initialize``, on synthetic tokens.

    python examples/train_olmoe.py --steps 20                  # toy widths, anywhere
    python examples/train_olmoe.py --published --layers 4      # OLMoE-1B-7B widths: a four-chip host

On a mesh with several devices the engine splits the batch over the ``data`` axis and
stores each layer's experts split over it too (a layer gathers them for use); nothing
selects that but the mesh (``docs/olmoe.md``). Off the TPU the flash kernel is interpreted:
keep ``--seq`` short there.
"""

import argparse
import json
import os

import numpy as np

PUBLISHED = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                         "benchmarks", "configs", "olmoe-1b-7b-d4.json")
TOY = dict(hidden_size=128, intermediate_size=64, num_attention_heads=4, num_key_value_heads=4,
           num_experts=8, num_experts_per_tok=2, vocab_size=4096, max_position_embeddings=256,
           rms_norm_eps=1e-5, rope_theta=10000, norm_topk_prob=False)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--rows", type=int, default=None, help="sequences a step (default: one a device)")
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--published", action="store_true",
                   help="the published widths of allenai/OLMoE-1B-7B-0125-Instruct")
    args = p.parse_args()

    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.olmoe import OlmoeConfig, OlmoeModel

    keys = dict(TOY)
    if args.published:
        with open(PUBLISHED) as f:
            keys = json.load(f)["model"]
    keys["num_hidden_layers"] = args.layers
    seq = args.seq or keys["max_position_embeddings"]
    rows = args.rows or jax.device_count()
    model = OlmoeModel(OlmoeConfig.from_published(keys))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": rows, "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2}, "steps_per_print": 10 ** 9})
    del params
    rng = np.random.default_rng(0)
    for step in range(args.steps):
        tokens = rng.zipf(1.3, size=(rows, seq + 1)).astype(np.int64) % keys["vocab_size"]
        tokens = tokens.astype(np.int32)
        loss = engine(tokens[:, :-1], tokens[:, 1:])
        engine.backward(loss)
        engine.step()
        print(f"step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
