"""Flash-attention block-size sweep on the real TPU (slope-timed; see devtime.py).

    python tests/perf/flash_sweep.py [--bwd]
"""

import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from devtime import timeit_slope  # noqa: E402
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402


def main():
    do_bwd = "--bwd" in sys.argv
    B, H, D = 1, 16, 64
    rng = np.random.default_rng(0)
    for T, causal in ((4096, False), (4096, True), (8192, False), (8192, True)):
        q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
        flops = 4.0 * B * H * T * T * D * (0.5 if causal else 1.0)
        for bq, bk in ((None, None), (256, 512), (512, 1024), (1024, 1024)):
            label = "auto" if bq is None else f"bq={bq} bk={bk}"
            try:
                dt = timeit_slope(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk), q, k, v,
                    n1=20, n2=100)
                print(f"T={T} causal={int(causal)} {label}: {dt*1e3:7.3f} ms "
                      f"{flops/dt/1e12:6.1f} TF/s")
                if do_bwd:
                    g = lambda q, k, v, bq=bq, bk=bk: jax.grad(
                        lambda q: jnp.sum(flash_attention(
                            q, k, v, causal=causal, block_q=bq,
                            block_k=bk).astype(jnp.float32)))(q)
                    dt = timeit_slope(g, q, k, v, n1=5, n2=30)
                    print(f"T={T} causal={int(causal)} {label} +bwd: {dt*1e3:7.3f} ms "
                          f"{3.5*flops/dt/1e12:6.1f} TF/s")
            except Exception as e:
                print(f"T={T} causal={int(causal)} {label}: {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
