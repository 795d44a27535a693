"""Model-level long-sequence bench (VERDICT r4 #7): sparse vs dense GPT-2 at
T=8192 END TO END through DeepSpeedEngine — tokens/s and MFU, the model-level
counterpart of the 4.58x kernel number (the reference's long-seq claims are
model-level: "10x longer sequences, up to 6x faster", reference README.md:17,35).

Config: GPT-2 (12L, 1024E, 16H) at T=8192, batch 1, ZeRO-2 engine, bf16.
Sparse = BigBird-family sliding-window band at block 256 (the round-4 gap
decomposition's best TPU-shaped layout, PERF.md block-sparse section); dense =
the flash kernel's chunked long-context path.

    python tests/perf/long_seq_model_perf.py
"""

import time

import numpy as np

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.ops.sparse_attention.sparsity_config import VariableSparsityConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.parallel.mesh import build_mesh

T, B, LAYERS, EMBD, HEADS = 8192, 1, 12, 1024, 16
PEAK_TFLOPS = 197.0  # v5e bf16


def fence(x):
    return np.asarray(jax.block_until_ready(x))


def run_engine(sparse):
    common = dict(vocab_size=50304, n_positions=T, n_embd=EMBD, n_layer=LAYERS,
                  n_head=HEADS, remat=True, remat_policy="dots", loss_chunk=512)
    if sparse:
        # sliding-window band, block 256: the layout the round-4 kernel probe
        # pinned at 4.58x over dense flash at T=8192 (~9% density)
        sc = VariableSparsityConfig(num_heads=HEADS, block=256,
                                    num_random_blocks=0,
                                    local_window_blocks=[3],
                                    global_block_indices=[0],
                                    attention="unidirectional")
        cfg = GPT2Config(sparse_attention=sc, **common)
    else:
        cfg = GPT2Config(use_flash_attention=True, **common)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = model.param_count(params)
    engine = DeepSpeedEngine(
        model=model, model_parameters=params, mesh=build_mesh(model=1, pipe=1),
        config_params={"train_batch_size": B, "steps_per_print": 1000,
                       "bf16": {"enabled": True},
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                       "zero_optimization": {"stage": 2}})
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(B, T)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)

    def step():
        loss = engine(tokens, labels)
        engine.backward(loss)
        engine.step()
        return loss

    step()
    fence(step())  # donated-layout recompile settles
    # median-of-3 windows + recorded spread (same policy as the bench.py
    # headline rows: a best-of draw biases the long-seq claim high)
    steps, dts = 3, []
    for _ in range(3):
        t0 = time.time()
        for _ in range(steps):
            loss = step()
        fence(loss)
        dts.append(time.time() - t0)
    dts.sort()
    dt = dts[1]
    spread = (dts[-1] - dts[0]) / dt
    tps = B * T * steps / dt
    mfu = tps * 6.0 * n_params / 1e12 / PEAK_TFLOPS
    name = "sparse-band256" if sparse else "dense-flash"
    print(f"{name}: {tps:,.1f} tok/s  param-MFU {mfu:.4f}  "
          f"({dt/steps:.3f} s/step median-of-3, spread {spread:.1%}, "
          f"{n_params/1e6:.0f}M params)", flush=True)
    del engine, params
    import gc
    gc.collect()
    return tps, mfu


def main():
    print("devices:", jax.devices())
    d_tps, d_mfu = run_engine(sparse=False)
    s_tps, s_mfu = run_engine(sparse=True)
    print(f"model-level speedup sparse/dense at T={T}: {s_tps / d_tps:.2f}x")


if __name__ == "__main__":
    main()
