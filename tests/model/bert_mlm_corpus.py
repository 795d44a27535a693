#!/usr/bin/env python
"""BERT masked-LM pretraining on the REAL natural-text corpus — the MLM half of
the real-data convergence gate (the reference's analog workload is
the BingBertSquad/Megatron real-data suites, tests/model/BingBertSquad).

Byte-level MLM over tests/model/data/corpus.txt: 15% of byte positions are
replaced by a [MASK] id (vocab 256 bytes + 1 mask token) and the model predicts
the original byte; labels are -100 elsewhere. Prints the same parseable
``step: N loss: X lr: Y`` lines as gpt2_pretrain.py.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from workload_env import setup  # noqa: E402  (must precede jax backend init)

jax = setup()

import argparse  # noqa: E402

import numpy as np  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.models.bert import BertConfig, BertForMaskedLM  # noqa: E402

MASK_ID = 256


def get_args():
    p = argparse.ArgumentParser(description="byte-level BERT MLM on real text")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seed", type=int, default=31)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--corpus", type=str, required=True)
    p = deepspeed_tpu.add_config_arguments(p)
    return p.parse_args()


def build_dataset(args, steps, batch):
    with open(args.corpus, "rb") as f:
        data = np.frombuffer(f.read(), np.uint8).astype(np.int32)
    rng = np.random.default_rng(args.seed)
    starts = rng.integers(0, len(data) - args.seq, size=(steps, batch))
    ids = data[starts[..., None] + np.arange(args.seq)]
    labels = np.full_like(ids, -100)
    masked = rng.random(ids.shape) < 0.15
    labels[masked] = ids[masked]
    ids = np.where(masked, MASK_ID, ids)
    return ids, labels


def main():
    args = get_args()
    cfg = BertConfig(vocab_size=MASK_ID + 1, hidden_size=args.hidden,
                     num_hidden_layers=args.layers, num_attention_heads=args.heads,
                     max_position_embeddings=args.seq,
                     intermediate_size=4 * args.hidden,
                     hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    model = BertForMaskedLM(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    engine, _, _, _ = deepspeed_tpu.initialize(args=args, model=model,
                                               model_parameters=params)
    gas = engine.gradient_accumulation_steps()
    assert gas == 1, "this driver keeps gas=1"
    ids, labels = build_dataset(args, args.steps, engine.train_batch_size())

    for step in range(args.steps):
        loss = engine(ids[step], labels[step])
        engine.backward(loss)
        engine.step()
        lr = engine.get_lr()
        print(f"step: {step + 1} loss: {float(jax.device_get(loss)):.6f} "
              f"lr: {lr[0] if lr else 0.0:.8f}", flush=True)

    print("training_complete", flush=True)


if __name__ == "__main__":
    main()
