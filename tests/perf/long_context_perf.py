"""Single-chip long-context flash (chunked tile path) with and without in-kernel
attention dropout, slope-timed (PERF.md long-context rows; VERDICT r3 #4 asked for
the dropout-on re-measurement once global-coordinate dropout landed), plus the
masked-vs-zigzag causal ring sweep at T=8192 over an 8-device mesh (PR 2
tentpole: the zigzag schedule removes the masked ring's ~2x dead-compute tax).

    python tests/perf/long_context_perf.py             # chunked flash sweep (1 chip)
    python tests/perf/long_context_perf.py --ring      # ring sweep (needs 8 devices)
    python tests/perf/long_context_perf.py --ring-cpu  # ring sweep on 8 virtual CPU devices
"""

import os
import sys

# --ring-cpu must claim the virtual CPU platform BEFORE jax initializes
# (see tests/conftest.py)
if "--ring-cpu" in sys.argv:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

import jax
import jax.numpy as jnp

if "--ring-cpu" in sys.argv:
    jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, ".")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from devtime import timeit_slope_stats  # noqa: E402
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402


def tf(t, T, B, H, D, causal, bwd):
    flops = 4.0 * B * H * T * T * D * (0.5 if causal else 1.0) * (2.5 if bwd else 1.0)
    return flops / t / 1e12


def ring_sweep(T=8192, B=1, H=2, D=64, reps=3):
    """Causal ring attention fwd+bwd, masked vs zigzag schedule, same mesh and
    shapes — the PR 2 tentpole's headline measurement. Times the shard_map'ped
    LOCAL ring (the sharded wrapper's one-off layout gather is not part of the
    per-step cost) and prints the per-rotation work-balance table alongside, so
    the measured ratio can be read against the analytic 31/17 at n=8."""
    import functools
    import time

    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.parallel.ring_attention import (ring_attention,
                                                       ring_work_schedule)

    n = 8
    assert len(jax.devices()) >= n, (
        f"ring sweep needs {n} devices (got {len(jax.devices())}); on a "
        f"single-chip rig run with --ring-cpu for the 8-virtual-device mesh")
    mesh = build_mesh(data=n, model=1, pipe=1)
    on_tpu = jax.devices()[0].platform == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)), dtype) for _ in range(3))
    spec = P(None, None, "data", None)
    print(f"ring sweep: T={T} B={B} H={H} D={D} n={n} "
          f"({'tpu' if on_tpu else 'cpu interpret'})", flush=True)

    results = {}
    for schedule in ("masked", "zigzag"):
        local = jax.shard_map(
            functools.partial(ring_attention, axis_name="data", causal=True,
                              interpret=not on_tpu, schedule=schedule),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        step = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(local(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))
        jax.block_until_ready(step(q, k, v))  # compile + warm
        dts = []
        for _ in range(reps):
            t0 = time.time()
            jax.block_until_ready(step(q, k, v))
            dts.append(time.time() - t0)
        dts.sort()
        dt, spread = dts[len(dts) // 2], (dts[-1] - dts[0]) / dts[len(dts) // 2]
        results[schedule] = dt
        print(f"  {schedule:>6}: {dt:8.3f} s/step fwd+bwd (median-of-{reps}, "
              f"spread {spread:.1%})", flush=True)

    print(f"  zigzag speedup over masked: "
          f"{results['masked'] / results['zigzag']:.2f}x", flush=True)
    print(f"\n  per-rotation work balance (C x C block units per rank, "
          f"C = T/2n = {T // (2 * n)}):")
    print(f"  {'r':>3} {'masked comp':>12} {'masked useful':>14} "
          f"{'zigzag comp':>12} {'zigzag useful':>14}")
    mk = ring_work_schedule(n, "masked")["rotations"]
    zz = ring_work_schedule(n, "zigzag")["rotations"]
    for m, z in zip(mk, zz):
        mu = (f"{m['useful_min']:.0f}" if m["useful_min"] == m["useful_max"]
              else f"{m['useful_min']:.0f}..{m['useful_max']:.0f}")
        print(f"  {m['r']:>3} {m['computed_per_rank']:>12.0f} {mu:>14} "
              f"{z['computed_per_rank']:>12.0f} {z['useful_min']:>14.0f}")
    tm = ring_work_schedule(n, "masked")["total_computed"]
    tz = ring_work_schedule(n, "zigzag")["total_computed"]
    print(f"  total computed: masked {tm:.0f} vs zigzag {tz:.0f} "
          f"(analytic ratio {tm / tz:.2f}x)")
    return results


def main():
    if "--ring" in sys.argv or "--ring-cpu" in sys.argv:
        ring_sweep()
        return
    B, H, D = 1, 8, 64
    rng = np.random.default_rng(0)
    for T, causal in ((16384, False), (16384, True), (32768, True)):
        q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
        for rate in (0.0, 0.1):
            kw = {} if rate == 0 else {"dropout_rate": rate, "dropout_seed": 7}

            def fwd_bwd(q, k, v):
                return jax.grad(lambda q: jnp.sum(flash_attention(
                    q, k, v, causal=causal, **kw).astype(jnp.float32)))(q)

            dt, sp, sc = timeit_slope_stats(fwd_bwd, q, k, v, n1=3, n2=12, reps=3,
                                            max_scale=4)
            print(f"T={T} causal={causal} dropout={rate}: {dt*1e3:7.2f} ms ±{sp:.1%} "
                  f"(x{sc}) fwd+bwd -> {tf(dt, T, B, H, D, causal, True):.0f} TF/s")


if __name__ == "__main__":
    main()
