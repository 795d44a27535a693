"""The state-space scan alone on the chip: device milliseconds a call, forward and forward
plus backward, read from a profiler trace, beside what the recurrence requires.

    python tests/perf/ssd_scan_sweep.py [--dtype bf16,f32] [--tiles 64,128,256] [--heads 8,16] [--check]
                                        [--out chiprun_out/ssd_scan_sweep.jsonl]

Run it from the root of a checkout; from the root of another checkout (a parent unpacked
beside this one) it measures that tree's ``ssd_scan`` on the same inputs (give ``--out`` an
absolute path there):

    (cd _parent && python ../tests/perf/ssd_scan_sweep.py --out /root/repo/chiprun_out/parent.jsonl)

The shape is the benchmark's cell's, ``[1, 8192, 64, 64]`` with a state of 128: x, B and C
hold bfloat16 values as the convolution leaves them, dt is float32 and log-uniform in
[0.001, 0.1], ``A`` = -1 .. -64 as the family initialises it. ``--tiles`` and ``--heads`` set
the tokens and the heads of a grid step (``ops/ssd.TILE``, ``HEADS``) for the run, every
pair of them in turn; a tree whose scan has no such thing (the plain ``lax`` form) is
measured once. ``ms`` is every device operation of the call (the kernels and what lays their
operands out); ``kernels`` the ``ds_ssd_scan_*`` kernels among them, by name. The required
operations and bytes are ``benchmarks/flops_ssm.ssd_scan_required`` for ONE layer, the
roofline share the larger of operations over 197 TF/s and bytes over 819 GB/s, over the time.
``--check`` adds the relative error of y (all 8,192 tokens) and of the six gradients (the last
1,024 tokens) against the float32 recurrence of ``benchmarks/reference/granite_hybrid_reference.py``,
on float32 arrays that hold the same values (what the cell's set-up compares).
"""

import argparse
import itertools
import json
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from benchmarks import flops_ssm  # noqa: E402
from benchmarks.reference import granite_hybrid_reference as ref  # noqa: E402
from deepspeed_tpu.ops import ssd  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from delta_rule_sweep import device_ms, rel  # noqa: E402  (the trace's reader, shared)

PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9      # TPU v5e: bf16, HBM
T, H, P, N = 8192, 64, 64, 128
MODEL = dict(num_hidden_layers=1, layer_types=["mamba"], mamba_n_heads=H, mamba_d_head=P,
             mamba_d_state=N, mamba_n_groups=1)
ARGNUMS = tuple(range(6))


def inputs(dtype, seed=0):
    rng = np.random.default_rng(seed)
    silu = lambda x: x / (1.0 + np.exp(-x))      # noqa: E731  (what the convolution leaves)
    low = lambda *shape: jnp.asarray(silu(rng.normal(size=shape)), jnp.bfloat16).astype(dtype)   # noqa: E731
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, T, H)))
    args = (low(1, T, H, P), jnp.asarray(dt, jnp.float32), -jnp.arange(1, H + 1, dtype=jnp.float32),
            low(1, T, N), low(1, T, N), jnp.ones((H,), jnp.float32))
    return args, low(1, T, H, P)


def check(args, cot):
    """The scan on float32 arrays of these values against the recurrence: y's relative
    error, and each gradient's on the last 1,024 tokens."""
    args = tuple(a.astype(jnp.float32) for a in args)
    scan = lambda *a: ssd.ssd_scan(*a)      # noqa: E731  (a new function a setting: jit traces anew)
    out = rel(jax.jit(scan)(*args), jax.jit(ref.ssm_recurrent)(*args))
    tail = tuple(a[:, -1024:] if a.ndim > 1 else a for a in args)
    cot = cot[:, -1024:].astype(jnp.float32)
    grads = [jax.jit(jax.grad(lambda *a, fn=fn: jnp.sum(fn(*a) * cot), argnums=ARGNUMS))(*tail)
             for fn in (scan, ref.ssm_recurrent)]
    return out, [rel(g, w) for g, w in zip(*grads)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bf16")
    ap.add_argument("--tiles", default="")
    ap.add_argument("--heads", default="")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--out", default="chiprun_out/ssd_scan_sweep.jsonl")
    opts = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("ssd_scan_sweep.py measures the compiled scan: it needs a TPU")
    os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
    tiled = hasattr(ssd, "TILE")
    tiles = [int(t) for t in opts.tiles.split(",") if t] if tiled else []
    heads = [int(h) for h in opts.heads.split(",") if h] if tiled else []
    with open(opts.out, "w") as f:
        for name in opts.dtype.split(","):
            args, cot = inputs(dict(bf16=jnp.bfloat16, f32=jnp.float32)[name])
            for tile, group in itertools.product(tiles or [None], heads or [None]):
                if tile:
                    ssd.TILE = tile
                if group:
                    ssd.HEADS = group
                line = dict(shape=[1, T, H, P, N], dtype=name, device=jax.devices()[0].device_kind,
                            tile=getattr(ssd, "TILE", None), heads=getattr(ssd, "HEADS", None))
                scan = lambda *a: ssd.ssd_scan(*a)      # noqa: E731  (a new function a setting: jit traces anew)
                passes = {"fwd": scan,
                          "fwd_bwd": jax.grad(lambda *a: jnp.sum((scan(*a) * cot).astype(jnp.float32)),
                                              argnums=ARGNUMS)}
                try:
                    for which, fn in passes.items():
                        flops, bytes_ = flops_ssm.ssd_scan_required(MODEL, T, training=which == "fwd_bwd")
                        ms, kernels = device_ms(fn, args, kernels_named="ds_ssd_scan_")
                        least = max(flops / PEAK_FLOPS, bytes_ / PEAK_BYTES)
                        line[which] = dict(ms=ms, kernels=kernels, required_flops=flops, required_bytes=bytes_,
                                           roofline=100 * least / (ms * 1e-3))
                    if opts.check:
                        line["scan_rel"], line["grad_rel"] = check(args, cot)
                except Exception as e:  # a tile the compiler refuses is a line of the table too
                    line["error"] = repr(e)[:400]
                f.write(json.dumps(line) + "\n")
                f.flush()
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
