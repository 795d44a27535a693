"""The hyper-connection's kernels alone on the chip (``deepspeed_tpu/ops/pallas/hyper_connection.py``):
device milliseconds a call of each kernel over the tokens a tile, beside the floor of its bytes at
HBM's rate, and a whole connection round the identity (forward, and forward + backward) as XLA's
``jnp`` form and as the kernels, every device operation of the program counted.

    python tests/perf/hc_sweep.py [--tiles 128,256] [--tokens 4096] [--check] [--out chiprun_out/hc_sweep.jsonl]

The shapes are ``xing4_ep8_d5_train_1chip``'s: four streams of 3,584 in bf16, 24 columns, 20 rounds.
Run from the root of a checkout. ``--check`` also prints how far the kernels' outputs and gradients
lie from the ``jnp`` form's on the chip (relative L2, by leaf).
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

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from deepspeed_tpu.models import hyper_connections as hc  # noqa: E402
from deepspeed_tpu.ops.pallas import hyper_connection as kernels  # noqa: E402

HBM_BYTES_PER_S = 819e9     # TPU v5e
N, C, ITERS, EPS, CLAMP, NORM_EPS = 4, 3584, 20, 1e-6, (-30.0, 30.0), 1e-6
ARGS = (N, ITERS, EPS, CLAMP, NORM_EPS)
KW = dict(n=N, iters=ITERS, eps=EPS, clamp=CLAMP)


def device_ms(fn, args, calls=8):
    """``(device ms a call over every operation, {operation: ms a call})`` of ``fn(*args)``; a
    ``ds_hc_*`` kernel under its own name."""
    from jax.profiler import ProfileData
    f = jax.jit(fn)
    jax.block_until_ready(f(*args))
    trace_dir = tempfile.mkdtemp(prefix="hc_sweep_")
    try:
        jax.profiler.start_trace(trace_dir)
        out = None
        for _ in range(calls):
            out = f(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        total, by_kernel = 0.0, collections.Counter()
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    total += e.duration_ns * 1e-6
                    m = re.search(r"ds_hc_\w+?(?=\.\d+|$|[^\w])", e.name)
                    by_kernel[m.group(0) if m else e.name] += e.duration_ns * 1e-6
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return total / calls, {name: ms / calls for name, ms in by_kernel.items()}


def operands(tokens, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    hp = hc.init(k[0], N, C, 0.02)
    hp = dict(hp, norm=hp["norm"] + 0.1 * jax.random.normal(k[1], hp["norm"].shape),
              b_pre=0.3 * jax.random.normal(k[2], (N,)), b_post=0.3 * jax.random.normal(k[3], (N,)),
              b_res=hp["b_res"] + 0.5 * jax.random.normal(k[4], (N, N)), gates=jnp.asarray([0.4, -0.3, 0.5]))
    bf16 = lambda key, shape: jax.random.normal(key, shape).astype(jnp.bfloat16)     # noqa: E731
    return hp, bf16(k[5], (tokens, N * C)), bf16(k[6], (tokens, N * C)), bf16(k[7], (tokens, C))


def moved(kind, tokens):
    """The bytes a kernel's call has to move: its streams in and out, in bf16."""
    stream, one = tokens * N * C * 2, tokens * C * 2
    return {"ds_hc_read": stream + one, "ds_hc_write": 2 * stream + one,
            "ds_hc_write_bwd": 3 * stream + 2 * one, "ds_hc_read_bwd": 3 * stream + one}[kind]


def kernels_alone(tokens, tm, emit):
    hp, x, dy, f = operands(tokens)
    phi, gate_bias = hc._packed(hp, N)
    g, cols = hc._operands(x, hp["norm"], gate_bias)
    phi_c = phi.astype(x.dtype)
    read = lambda *a: kernels.read(*a, tm=tm, norm_eps=NORM_EPS, **KW)     # noqa: E731
    u, co, proj = jax.jit(read)(x, g, phi_c, cols)
    dco = jnp.zeros_like(co)
    calls = {
        "ds_hc_read": (read, (x, g, phi_c, cols)),
        "ds_hc_write": (lambda *a: kernels.write(*a, n=N, tm=tm), (x, f, co)),
        "ds_hc_write_bwd": (lambda *a: kernels.write_bwd(*a, n=N, tm=tm), (dy, x, f, co)),
        "ds_hc_read_bwd": (lambda *a: kernels.read_bwd(*a, tm=tm, **KW), (x, f, dy, dco, proj, g, phi_c.T, cols)),
    }
    for name, (fn, args) in calls.items():
        try:
            _, by_kernel = device_ms(fn, args)
            floor = 1e3 * moved(name, tokens) / HBM_BYTES_PER_S
            emit(dict(row="kernel", kernel=name, tokens=tokens, tm=tm, ms=by_kernel[name], floor_ms=floor,
                      share=100 * floor / by_kernel[name], picked=tm == kernels.tile(tokens, N, C, 2)))
        except Exception as e:      # a tile the compiler refuses is a row of the table too
            emit(dict(row="error", kernel=name, tokens=tokens, tm=tm, error=f"{type(e).__name__}: {str(e)[:300]}"))


def jnp_form(x, hp, sub_layer):
    h_pre, h_post, h_res = hc.coefficients(x, hp, *ARGS)
    return hc.write(x, sub_layer(hc.read(x, h_pre))[0], h_post, h_res)


def whole(tokens, tm, emit, check):
    """A connection round the identity: forward, and forward + backward by the streams and the
    parameters, as XLA's passes and as the kernels; every device operation of the call."""
    hp, x, cot, _ = operands(tokens, seed=1)
    x, cot = x[None], cot[None]         # the ``jnp`` form lays its coefficients out [n, B, T]
    identity = lambda u: (u, {})     # noqa: E731
    forms = {"jnp": lambda x, hp: jnp_form(x, hp, identity),
             "kernels": lambda x, hp: hc.connected_by_kernels(x, hp, identity, *ARGS, tm=tm)[0]}
    grads = {}
    for name, form in forms.items():
        loss = lambda x, hp, form=form: jnp.sum(form(x, hp).astype(jnp.float32) * cot.astype(jnp.float32))   # noqa: E731
        fwd_ms, fwd_kernels = device_ms(form, (x, hp))
        both_ms, both_kernels = device_ms(jax.grad(loss, argnums=(0, 1)), (x, hp))
        top = lambda ops: dict(sorted(ops.items(), key=lambda kv: -kv[1])[:12])     # noqa: E731
        emit(dict(row="whole", form=name, tokens=tokens, tm=tm, forward_ms=fwd_ms, forward_backward_ms=both_ms,
                  forward_ops=top(fwd_kernels), forward_backward_ops=top(both_kernels)))
        if check:
            grads[name] = (jax.jit(form)(x, hp), jax.jit(jax.grad(loss, argnums=(0, 1)))(x, hp))
    if check:
        rel = lambda a, b: float(jnp.linalg.norm((a.astype(jnp.float32) - b.astype(jnp.float32)).ravel())    # noqa: E731
                                 / jnp.linalg.norm(b.astype(jnp.float32).ravel()))
        apart = jax.tree_util.tree_map(rel, grads["kernels"], grads["jnp"])
        emit(dict(row="check", tokens=tokens, tm=tm, out=apart[0], by_streams=apart[1][0], by_leaf=apart[1][1]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="128,256,512")
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--out", default="chiprun_out/hc_sweep.jsonl")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("hc_sweep.py measures the compiled kernels: it needs a TPU")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        def emit(rec):
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print(json.dumps(rec), flush=True)
        picked = kernels.tile(args.tokens, N, C, 2)
        for tm in (int(t) for t in args.tiles.split(",")):
            kernels_alone(args.tokens, tm, emit)
        whole(args.tokens, picked, emit, args.check)


if __name__ == "__main__":
    main()
