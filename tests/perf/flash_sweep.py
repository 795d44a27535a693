"""Flash-attention tile sweep on the chip: device milliseconds a call of each kernel,
forward and backward apart, read from a profiler trace by the kernels' own names.

    python tests/perf/flash_sweep.py [--rows cell,long,other,band,mla,latent,layout] [--picked] [--out chiprun_out/flash_sweep.jsonl]

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

``--rows layout`` is another table: at the ten cells' attention shapes, one attention layer with
nothing between its projections and the kernel (``x W_q``, ``x W_k``, ``x W_v``, the kernel,
``W_o``; value and every gradient), the operands turned head-major round ``flash_attention`` as
the models did before PR 60 against ``flash_attention_rows`` on them as they lie: device ms a
call of the WHOLE program, of its ``ds_flash_*`` kernels by name, and of the rest (the four
projections and their gradients, the same products both ways, and the copies). On a tree without
``flash_attention_rows`` the second line is absent.
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


# the ten cells' attention calls: (cells, B, T, query heads, key/value heads, D, Dv, window)
LAYOUT_ROWS = [("glm47flash", 1, 8192, 20, 20, 256, 256, None), ("qwen3next", 1, 8192, 16, 2, 256, 256, None),
               ("mellum2 window", 1, 8192, 32, 4, 128, 128, 1024), ("mellum2 full", 1, 8192, 32, 4, 128, 128, None),
               ("nemotronh", 1, 8192, 32, 2, 128, 128, None), ("ouro olmoe", 2, 4096, 16, 16, 128, 128, None),
               ("xl_d20", 4, 1024, 25, 25, 64, 64, None), ("lfm2 granite4h", 1, 8192, 32, 8, 64, 64, None),
               ("xing4", 1, 4096, 32, 32, 192, 128, None)]


def device_events(fn, args, calls):
    """``[(name, ns)]`` of every operation the device runs in ``calls`` calls of ``jit(fn)(*args)``
    after a first, from a profiler trace."""
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
        return [(e.name, e.duration_ns) for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/device:TPU:")
                for line in plane.lines if line.name == "XLA Ops" for e in line.events]
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


KERNEL = re.compile(r"ds_flash_\w+?(?=\.\d+|$|[^\w])")


def program_ms(fn, args, calls=6):
    """``(whole, {kernel name: ms})``: device ms a call of everything ``fn(*args)`` runs, and of its
    ``ds_flash_*`` kernels."""
    whole, kernels = 0.0, collections.Counter()
    for name, ns in device_events(fn, args, calls):
        whole += ns
        m = KERNEL.search(name)
        if m:
            kernels[m.group(0)] += ns
    per = 1e-6 / calls
    return whole * per, {name: ns * per for name, ns in kernels.items()}


def sweep_layout(emit, hidden=1024):
    """One attention layer at each of ``LAYOUT_ROWS``, head-major with its transposes and row-major."""
    rng = np.random.default_rng(0)
    for cells, B, T, H, Hkv, D, Dv, window in LAYOUT_ROWS:
        def layer(rows):
            def attend(x, wq, wk, wv, wo):
                q, k, v = (jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype) for w in (wq, wk, wv))
                if rows:
                    y = fa.flash_attention_rows(q, k, v, H, Hkv, True, window=window)
                else:
                    heads = lambda a, n: a.reshape(B, T, n, -1).transpose(0, 2, 1, 3)      # noqa: E731
                    y = fa.flash_attention(heads(q, H), heads(k, Hkv), heads(v, Hkv), True, window=window)
                    y = y.transpose(0, 2, 1, 3).reshape(B, T, H * Dv)
                return jnp.sum(jnp.dot(y, wo, preferred_element_type=jnp.float32) ** 2)
            return jax.grad(attend, argnums=(0, 1, 2, 3, 4))
        widths = ((hidden, H * D), (hidden, Hkv * D), (hidden, Hkv * Dv), (H * Dv, hidden))
        args = [jnp.asarray(rng.normal(size=(B, T, hidden)), jnp.bfloat16)] + [
            jnp.asarray(rng.normal(size=w) * 0.03, jnp.bfloat16) for w in widths]
        ways = [("heads_major", False)]
        if hasattr(fa, "flash_attention_rows"):
            ways.append((fa.layout_of(D, Dv, window), True))
        for way, rows in ways:
            common = dict(cells=cells, shape=[B, T, H, Hkv, D, Dv], window=window, way=way, rows=rows)
            try:
                whole, kernels = program_ms(layer(rows), args)
                emit(dict(common, pass_="layout", ms=whole, kernels=kernels, rest_ms=whole - sum(kernels.values())))
            except Exception as e:
                emit(dict(common, pass_="layout_error", error=f"{type(e).__name__}: {str(e)[:300]}"))


def kernel_ms(fn, args, calls=8):
    """{kernel name: device ms a call} for the Pallas kernels ``fn(*args)`` runs."""
    seconds, calls_seen, others = (collections.Counter() for _ in range(3))
    for name, ns in device_events(fn, args, calls):
        m = KERNEL.search(name)
        if m:
            seconds[m.group(0)] += ns * 1e-9
            calls_seen[m.group(0)] += 1
        else:
            others[name] += 1
    if not seconds or any(n % calls for n in calls_seen.values()):
        raise RuntimeError(
            f"the trace names no ds_flash_* kernel {calls} times over: {dict(calls_seen)}; "
            f"it holds {others.most_common(6)}")
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
            if rec["pass_"].startswith("layout"):
                print(f"{rec['cells']:16s} {rec['shape']} w={rec['window']} {rec['way']:11s} "
                      + (rec.get("error") or f"{rec['ms']:8.4f} ms a call, outside the kernels {rec['rest_ms']:7.4f} "
                         + " ".join(f"{n}={t:.4f}" for n, t in sorted(rec["kernels"].items()))), flush=True)
                return
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
            if name == "layout":
                sweep_layout(emit)
                continue
            for shape, causal, tiles, *more in ROWS[name]:
                sweep_row(shape, causal, [None] if args.picked else tiles, emit,
                          **dict(zip(("kv_heads", "window", "v_width"), more)))


if __name__ == "__main__":
    main()
