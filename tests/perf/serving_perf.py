"""Continuous-batching serving measurements on the real TPU.

Replays seeded mixed greedy/beam traces (deepspeed_tpu.serve.sim.synth_trace)
through the InferenceEngine at GPT-2 420M and 1.5B bf16, sweeping slot count
and the XLA-gather vs Pallas paged-decode attention path. Reports per config:
decode tok/s, goodput tok/s, mean TTFT, mean slot occupancy, preemptions, and
the compile-watchdog recompile count (must be 0 after warmup — the same gate
``ds-tpu serve-sim`` enforces on the CPU mesh).

Timing: the engine loop fetches every logits row to the host each iteration
(sampling is host-side), so every step ends with the device idle and the host
clock around the run is the served wall.

    python tests/perf/serving_perf.py [--small-only] [--requests N]

Deliberately NOT named test_*.py: this is a minutes-long benchmark driver,
excluded from tier-1 collection (tests/unit/test_tier1_collection.py pins
that).
"""

import argparse
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serve.engine import InferenceEngine
from deepspeed_tpu.serve.sim import synth_trace
from deepspeed_tpu.utils.monitor import SummaryMonitor
from deepspeed_tpu.utils.telemetry import TelemetrySession

ML = 1024            # serving context budget (tokens)

MODELS = {
    "420M": dict(vocab_size=50304, n_positions=ML, n_embd=1024,
                 n_layer=24, n_head=16, use_flash_attention=True),
    "1.5B": dict(vocab_size=50304, n_positions=ML, n_embd=1600,
                 n_layer=48, n_head=25, use_flash_attention=True),
}


def _require_tpu():
    if jax.devices()[0].platform == "cpu":
        print("serving_perf: needs a real TPU (use `ds-tpu serve-sim` for "
              "the CPU-mesh correctness replay)", file=sys.stderr)
        sys.exit(2)


def bench_config(name, cfg_kwargs, *, num_slots, use_pallas, n_requests,
                 seed=11):
    cfg = GPT2Config(**cfg_kwargs)
    model = GPT2Model(cfg)
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16) if p.ndim >= 2 else p,
        model.init(jax.random.PRNGKey(0)))
    session = TelemetrySession(monitor=SummaryMonitor(enabled=False))
    eng = InferenceEngine(model, params, num_slots=num_slots, block_size=16,
                          num_blocks=num_slots * (ML // 16) // 2 + 1,
                          max_model_len=ML, prefill_chunk=128,
                          use_pallas=use_pallas, telemetry=session)
    reqs = synth_trace(n_requests, vocab_size=cfg.vocab_size,
                       max_model_len=ML, seed=seed)
    t0 = time.time()
    outs, logs = eng.run(reqs)
    wall = max(time.time() - t0, 1e-9)
    fin = [o for o in outs if o.status == "finished"]
    new_tokens = sum(len(o.tokens) for o in fin)
    occ = float(np.mean([len(log["decode"]) / num_slots for log in logs]))
    recompiles = sum(session.watchdog.recompiles(n)
                     for n in session.watchdog.records
                     if n.startswith("serve:"))
    path = "pallas" if use_pallas else "xla-gather"
    print(f"{name:5s} slots={num_slots:3d} {path:10s} "
          f"tok/s={eng._tokens_sampled / wall:8.1f} "
          f"goodput={new_tokens / wall:8.1f} "
          f"ttft_ms={np.mean([o.ttft_ms for o in fin]):8.1f} "
          f"occ={occ:.3f} preempt={sum(o.preemptions for o in fin):3d} "
          f"recompiles={recompiles}", flush=True)
    assert recompiles == 0, "serving decode program recompiled after warmup"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small-only", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    args = ap.parse_args()
    _require_tpu()
    names = ["420M"] if args.small_only else ["420M", "1.5B"]
    for name in names:
        for num_slots in (8, 32):
            for use_pallas in (False, True):
                bench_config(name, MODELS[name], num_slots=num_slots,
                             use_pallas=use_pallas,
                             n_requests=args.requests)


if __name__ == "__main__":
    main()
