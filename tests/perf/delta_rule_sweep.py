"""The gated delta rule alone on the chip: device milliseconds a call, forward and forward
plus backward, read from a profiler trace, beside what the recurrence requires.

    python tests/perf/delta_rule_sweep.py [--T 8192,1024] [--dtype bf16,f32] [--check] [--out chiprun_out/delta_rule_sweep.jsonl]

Run it from the root of a checkout; from the root of another checkout (a parent unpacked
beside this one) it measures that tree's ``gated_delta_rule`` on the same inputs:

    (cd _parent && python ../tests/perf/delta_rule_sweep.py --out ../chiprun_out/parent.jsonl)

A shape is the benchmark's cell's, ``[1, T, 16 | 32, 128]``: q and k of 16 key heads, v of
32 value heads, g and beta float32, one head of the 32 forgetting slowly (0.0015 a token).
``ms`` is every device operation of the call (the kernels and what lays their operands
out); ``kernels`` the ``ds_delta_rule_*`` kernels among them, by name (none in a tree that
has none). The required operations and bytes are ``benchmarks/flops_hybrid.delta_rule_required``
for ONE layer, the roofline share the larger of operations over 197 TF/s and bytes over
819 GB/s, over the time. ``--check`` adds the relative error of o (the whole T) and of the
worst gradient (the last 1,024 tokens) against the float32 recurrence of
``benchmarks/reference/qwen3_next_reference.py`` on the same values.
"""

import argparse
import collections
import glob
import json
import os
import re
import shutil
import sys
import tempfile

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from benchmarks import flops_hybrid  # noqa: E402
from benchmarks.reference import qwen3_next_reference as ref  # noqa: E402
from deepspeed_tpu.ops.delta_rule import gated_delta_rule  # noqa: E402

PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9      # TPU v5e: bf16, HBM
MODEL = dict(num_hidden_layers=1, full_attention_interval=4, linear_num_key_heads=16,
             linear_num_value_heads=32, linear_key_head_dim=128, linear_value_head_dim=128)


def inputs(T, dtype, seed=0):
    rng = np.random.default_rng(seed)
    Hk, Hv, D = MODEL["linear_num_key_heads"], MODEL["linear_num_value_heads"], 128
    rates = rng.uniform(0.001, 16.0, Hv)
    rates[0] = 0.0015
    g = -rates * np.logaddexp(0.0, rng.normal(size=(1, T, Hv)) + 1.0)
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=(1, T, Hv))))
    silu = lambda x: x / (1.0 + np.exp(-x))      # noqa: E731  (what the convolution leaves)
    q, k = (jnp.asarray(silu(rng.normal(size=(1, T, Hk, D))), jnp.bfloat16).astype(dtype) for _ in range(2))
    v, do = (jnp.asarray(silu(rng.normal(size=(1, T, Hv, D))), jnp.bfloat16).astype(dtype) for _ in range(2))
    return (q, k, v, jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32)), do


def device_ms(fn, args, calls=4, kernels_named="ds_delta_rule_"):
    """``(ms a call of every device operation, {kernel name: ms a call})`` of ``fn(*args)``,
    the kernels those whose name starts with ``kernels_named``."""
    from jax.profiler import ProfileData
    f = jax.jit(fn)
    jax.block_until_ready(f(*args))
    trace_dir = tempfile.mkdtemp(prefix="delta_rule_sweep_")
    try:
        jax.profiler.start_trace(trace_dir)
        out = None
        for _ in range(calls):
            out = f(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        busy, kernels = [], collections.Counter()
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    busy.append((e.start_ns, e.start_ns + e.duration_ns))
                    m = re.search(kernels_named + r"\w+?(?=\.\d+|$|[^\w])", e.name)
                    if m:
                        kernels[m.group(0)] += e.duration_ns * 1e-6
        # the union of the operations' intervals: a loop's own event spans its body's
        total, end = 0.0, 0
        for lo, hi in sorted(busy):
            total += max(0, hi - max(lo, end)) * 1e-6
            end = max(end, hi)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return total / calls, {name: ms / calls for name, ms in kernels.items()}


def recurrence(q, k, v, g, beta):
    r = v.shape[2] // k.shape[2]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    return ref.delta_rule_recurrent(jnp.repeat(ref.unit_scaled(q, True), r, axis=2),
                                    jnp.repeat(ref.unit_scaled(k, False), r, axis=2), v, g, beta)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(args, do):
    """The rule in float32 on these values against the recurrence: o's relative error, and
    the worst gradient's on the last 1,024 tokens."""
    args = tuple(a.astype(jnp.float32) for a in args)
    out = rel(jax.jit(gated_delta_rule)(*args), jax.jit(recurrence)(*args))
    tail = tuple(a[:, -1024:] for a in args)
    cot = do[:, -1024:].astype(jnp.float32)
    grads = [jax.jit(jax.grad(lambda *a, fn=fn: jnp.sum(fn(*a) * cot), argnums=(0, 1, 2, 3, 4)))(*tail)
             for fn in (gated_delta_rule, recurrence)]
    return out, [rel(g, w) for g, w in zip(*grads)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--T", default="8192,1024")
    ap.add_argument("--dtype", default="bf16")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--out", default="chiprun_out/delta_rule_sweep.jsonl")
    opts = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("delta_rule_sweep.py measures the compiled kernels: it needs a TPU")
    os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
    with open(opts.out, "w") as f:
        for T in (int(t) for t in opts.T.split(",")):
            for name in opts.dtype.split(","):
                args, do = inputs(T, dict(bf16=jnp.bfloat16, f32=jnp.float32)[name])
                line = dict(shape=[1, T, 16, 32, 128], dtype=name, device=jax.devices()[0].device_kind)
                passes = {"fwd": gated_delta_rule,
                          "fwd_bwd": jax.grad(lambda *a: jnp.sum((gated_delta_rule(*a) * do).astype(jnp.float32)),
                                              argnums=(0, 1, 2, 3, 4))}
                for which, fn in passes.items():
                    flops, bytes_ = flops_hybrid.delta_rule_required(MODEL, T, training=which == "fwd_bwd")
                    ms, kernels = device_ms(fn, args)
                    least = max(flops / PEAK_FLOPS, bytes_ / PEAK_BYTES)
                    line[which] = dict(ms=ms, kernels=kernels, required_flops=flops, required_bytes=bytes_,
                                       roofline=100 * least / (ms * 1e-3))
                if opts.check:
                    line["delta_rule_rel"], line["grad_rel"] = check(args, do)
                f.write(json.dumps(line) + "\n")
                f.flush()
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
