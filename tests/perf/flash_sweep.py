"""Flash-attention tile sweep on the chip: device milliseconds a call of each kernel,
forward and backward apart, read from a profiler trace by the kernels' own names.

    python tests/perf/flash_sweep.py [--rows cell,long,other,band,mla,latent] [--picked] [--out chiprun_out/flash_sweep.jsonl]

Run it from the root of a checkout; from the root of another checkout (a parent
unpacked beside this one) it measures that tree's kernels with the same rows:

    (cd _parent && python ../tests/perf/flash_sweep.py --out ../chiprun_out/parent.jsonl)

A row is a shape [B, H, T, D] in bf16, causal or not, and a list of (block_q, block_k);
``None`` is what ``_resolve`` picks. The share of the roofline is the required
operations (4.B.H.T^2.D a forward, half of it causal; twice that a backward, as
``benchmarks/flops.py`` counts) over 197 TF/s over the measured time. A row may add
``(key/value heads, window, value width)``: grouped heads, a sliding window, whose required
operations are the pairs inside the band (``band_pairs``'s ``needed``), and values of another
width than the keys' (the required operations are then 2.B.H.T^2.(D + Dv) a forward).
"""

import argparse
import collections
import glob
import importlib
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
fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

PEAK_FLOPS = 197e12     # TPU v5e, bf16
SQUARE = [(128, 128), (256, 256), (512, 512)]
ROWS = {
    # the benchmark's cell: GPT-2 XL heads, 4 x 1024 tokens a step
    "cell": [((4, 25, 1024, 64), True,
              [None] + SQUARE + [(1024, 1024), (256, 512), (512, 256)])],
    # the long sequences the tiles were first chosen at (and the chunks of T > 8192)
    "long": [((1, 16, T, 64), causal, [None, (256, 512), (512, 512), (512, 1024), (1024, 1024)])
             for T in (4096, 8192) for causal in (True, False)],
    # BERT-large (non-causal, every tile full), GPT-class at T = 2048, head width 128
    "other": [((8, 16, 512, 64), False, [None] + SQUARE[1:] + [(256, 512)]),
              ((4, 16, 2048, 64), True, [None] + SQUARE[1:] + [(1024, 1024), (256, 512)]),
              ((2, 8, 2048, 128), True, [None] + SQUARE[1:] + [(1024, 1024)])],
    # a sliding-window layer and a full one of mellum2_ep4_d4_train_1chip: 32 query heads over
    # 4 key/value heads of 128 at 8192 positions, a window of 1024 and none
    "band": [((1, 32, 8192, 128), True, [None] + SQUARE[1:] + [(1024, 1024)] + more, 4, window)
             for window, more in ((1024, [(512, 256), (256, 512), (1024, 512)]), (None, []))],
    # a latent-attention block of glm47flash_ep8_d5_train_1chip: 20 query over 20 key/value heads
    # of 192 + 64 | 256 at 8192 positions, six calls a step
    "mla": [((1, 20, 8192, 256), True, [None] + SQUARE[1:] + [(1024, 1024), (512, 1024), (1024, 512)])],
    # a latent-attention block of xing4_ep8_d5_train_1chip: 32 heads, keys of 128 + 64 beside values
    # of 128, at 4096 positions, five calls a step; and the same call with the values filled up to 192
    "latent": [((1, 32, 4096, 192), True, [None] + SQUARE[1:] + [(1024, 1024)], None, None, 128),
               ((1, 32, 4096, 192), True, [None], None, None, None)],
}


def kernel_ms(fn, args, calls=8):
    """{kernel name: device ms a call} for the Pallas kernels ``fn(*args)`` runs."""
    from jax.profiler import ProfileData
    f = jax.jit(fn)
    jax.block_until_ready(f(*args))
    trace_dir = tempfile.mkdtemp(prefix="flash_sweep_")
    try:
        jax.profiler.start_trace(trace_dir)
        out = None
        for _ in range(calls):
            out = f(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        seconds, calls_seen, others = (collections.Counter() for _ in range(3))
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    m = re.search(r"ds_flash_\w+?(?=\.\d+|$|[^\w])", e.name)
                    if m:
                        seconds[m.group(0)] += e.duration_ns * 1e-9
                        calls_seen[m.group(0)] += 1
                    else:
                        others[e.name] += 1
        if not seconds or any(n % calls for n in calls_seen.values()):
            raise RuntimeError(
                f"the trace names no ds_flash_* kernel {calls} times over: {dict(calls_seen)}; "
                f"it holds {others.most_common(6)}")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {name: 1e3 * s / calls for name, s in seconds.items()}


def sweep_row(shape, causal, tiles, emit, tag="", passes=("fwd", "bwd"), kv_heads=None,
              window=None, v_width=None):
    B, H, T, D = shape
    Dv = v_width or D
    rng = np.random.default_rng(0)
    kv_shape = (B, kv_heads or H, T, D)
    q, k = (jnp.asarray(rng.normal(size=s), jnp.bfloat16) for s in (shape, kv_shape))
    v, do = (jnp.asarray(rng.normal(size=s[:-1] + (Dv,)), jnp.bfloat16) for s in (kv_shape, shape))
    need = 2.0 * B * H * T * T * (D + Dv) * (0.5 if causal else 1.0)
    if window is not None:
        need = 4.0 * B * H * D * fa.band_pairs(T, T, T, window)[1]
    for tile in tiles:
        sm_scale, bq, bk, _ = fa._resolve(q, None, *(tile or (None, None)), causal, False, window)
        common = dict(shape=list(shape), causal=causal, block_q=bq, block_k=bk, v_width=Dv,
                      picked=tile is None, tag=tag, kv_heads=kv_shape[1], window=window)
        try:
            fwd = lambda q, k, v: fa._flash_fwd(q, k, v, None, None, sm_scale, causal, 0.0,
                                                bq, bk, False, window)
            if "fwd" in passes:
                ms = kernel_ms(fwd, (q, k, v))
                total = sum(ms.values())
                emit(dict(common, pass_="fwd", ms=total, kernels=ms,
                          roofline=100 * need / PEAK_FLOPS / (total * 1e-3)))
            if "bwd" not in passes:
                continue
            out, lse = jax.jit(fwd)(q, k, v)
            delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
            bwd = lambda q, k, v, do, lse, delta: fa._flash_bwd_local(
                q, k, v, do, lse, delta, None, None, sm_scale=sm_scale, causal=causal,
                rate=0.0, block_q=bq, block_k=bk, interpret=False, window=window)
            ms = kernel_ms(bwd, (q, k, v, do, lse, delta))
            total = sum(ms.values())
            emit(dict(common, pass_="bwd", ms=total, kernels=ms,
                      roofline=100 * 2 * need / PEAK_FLOPS / (total * 1e-3)))
        except Exception as e:  # a tile the compiler refuses is a row of the table too
            emit(dict(common, pass_="error", error=f"{type(e).__name__}: {str(e)[:300]}"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="cell,long,other")
    ap.add_argument("--out", default="chiprun_out/flash_sweep.jsonl")
    ap.add_argument("--picked", action="store_true", help="only the tiles _resolve picks")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("flash_sweep.py measures the compiled kernels: it needs a TPU")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        def emit(rec):
            f.write(json.dumps(rec) + "\n")
            f.flush()
            if rec["pass_"] == "error":
                print(f"{rec['shape']} causal={int(rec['causal'])} bq={rec['block_q']} "
                      f"bk={rec['block_k']}: {rec['error']}", flush=True)
                return
            print(f"{rec['shape']} | {rec['v_width']} kv={rec['kv_heads']} w={rec['window']} causal={int(rec['causal'])} {rec['pass_']} "
                  f"bq={rec['block_q']:5d} bk={rec['block_k']:5d}{' *' if rec['picked'] else '  '} "
                  f"{rec['ms']:8.4f} ms {rec['roofline']:6.2f} % {rec['tag']} "
                  + " ".join(f"{n}={t:.4f}" for n, t in sorted(rec["kernels"].items())),
                  flush=True)
        for name in args.rows.split(","):
            for shape, causal, tiles, *more in ROWS[name]:
                sweep_row(shape, causal, [None] if args.picked else tiles, emit,
                          **dict(zip(("kv_heads", "window", "v_width"), more)))


if __name__ == "__main__":
    main()
