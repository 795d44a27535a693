"""Which remat policy avoids replaying the flash fwd kernel in backward?

Compiles value_and_grad of a 2-layer rematted GPT-2 on the TPU and counts
pallas custom-calls in the HLO, classified by kernel (fwd vs the one-pass
bwd). A policy that saves the kernel's (out, lse) should show ONE fwd
kernel per layer; dots shows TWO (one fwd + one backward replay).

Usage: python tests/perf/remat_flash_probe.py [policy ...]
"""

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model


def count_kernels(policy):
    cfg = GPT2Config(vocab_size=2048, n_positions=512, n_embd=256, n_layer=2,
                     n_head=4, remat=True, remat_policy=policy,
                     use_flash_attention=True)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = jnp.zeros((2, 512), jnp.int32)
    lab = jnp.zeros((2, 512), jnp.int32)

    f = jax.jit(jax.value_and_grad(lambda p: model.apply(p, tok, lab)))
    txt = f.lower(params).compile().as_text()
    calls = [c for c in re.findall(r'.*custom-call[^\n]*', txt)
             if "tpu_custom_call" in c]
    # classify by output signature: fwd = (bf16 out, f32 lse) pair; bwd = the (dq, dk,
    # dv) bf16 triple. A fwd call inside a rematted_computation is the
    # backward-pass REPLAY the policy is supposed to eliminate.
    def sig(c):
        m = re.search(r"= (\(.*?\)|\S+) custom-call", c)
        return tuple(re.findall(r"(bf16|f32)\[", m.group(1))) if m else ()
    fwd = [c for c in calls if sig(c) == ("bf16", "f32")]
    bwd = [c for c in calls if sig(c) == ("bf16", "bf16", "bf16")]
    replay = [c for c in fwd if "remat" in c]
    return {"fwd_total": len(fwd), "fwd_replayed": len(replay), "bwd": len(bwd),
            "unclassified": len(calls) - len(fwd) - len(bwd)}


if __name__ == "__main__":
    policies = sys.argv[1:] or ["dots", "attn", "dots+attn"]
    print("devices:", jax.devices())
    for p in policies:
        print(p, "->", count_kernels(p))
