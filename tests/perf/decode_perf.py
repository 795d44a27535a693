"""KV-cache decode + prefill measurements on the real TPU (VERDICT r4 #3).

Times `GPT2Model.generate` (greedy) and `beam_search` (beam-4) for GPT-2 420M and
1.5B at batch 1 and 8: decode tokens/s (isolated from prefill by differencing a
long and a 1-token generation) and prefill TFLOP/s over a 1024-token prompt.

Every measurement ends in ``block_until_ready`` on the output tokens; min-of-reps
is reported.

    python tests/perf/decode_perf.py [--small-only]
"""

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

T0 = 1024        # prompt length
NEW = 128        # generated tokens for the decode-rate measurement
REPS = 3

MODELS = {
    "420M": dict(vocab_size=50304, n_positions=T0 + NEW + 8, n_embd=1024,
                 n_layer=24, n_head=16, use_flash_attention=True),
    "1.5B": dict(vocab_size=50304, n_positions=T0 + NEW + 8, n_embd=1600,
                 n_layer=48, n_head=25, use_flash_attention=True),
}


def fence(x):
    # a pytree: beam_search returns (sequences, scores)
    return jax.block_until_ready(x)


def time_call(fn, reps=REPS):
    fence(fn())  # compile + warm
    fence(fn())  # donation/layout recompile settles
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        fence(fn())
        best = min(best, time.time() - t0)
    return best


def bench_model(name, cfg_kwargs, batches=(1, 8), do_beam=True):
    cfg = GPT2Config(**cfg_kwargs)
    model = GPT2Model(cfg)
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16) if p.ndim >= 2 else p,
        model.init(jax.random.PRNGKey(0)))
    n_params = model.param_count(params)
    rows = []
    for B in batches:
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, size=(B, T0)),
            jnp.int32)

        t1 = time_call(lambda: model.generate(params, prompt, 1))
        t_long = time_call(lambda: model.generate(params, prompt, NEW))
        greedy_tps = (NEW - 1) * B / max(t_long - t1, 1e-9)
        # prefill: fwd-only flops over the prompt, ~2*N per token (+ attention)
        prefill_tf = 2.0 * n_params * B * T0 / t1 / 1e12
        row = {"model": name, "batch": B, "prefill_s": round(t1, 3),
               "prefill_tf_s": round(prefill_tf, 1),
               "greedy_tok_s": round(greedy_tps, 1)}
        if do_beam:
            tb1 = time_call(lambda: model.beam_search(params, prompt, 1, num_beams=4))
            tbl = time_call(lambda: model.beam_search(params, prompt, NEW, num_beams=4))
            row["beam4_tok_s"] = round((NEW - 1) * B / max(tbl - tb1, 1e-9), 1)
        rows.append(row)
        print(row, flush=True)
    del params
    return rows


def main():
    print("devices:", jax.devices())
    names = ["420M"] if "--small-only" in sys.argv else ["420M", "1.5B"]
    for name in names:
        bench_model(name, MODELS[name], batches=(1, 8))


if __name__ == "__main__":
    main()
