"""The mixers' short causal convolution alone on the chip: device milliseconds a call, forward
and backward, read from a profiler trace, the kernels tile by tile beside the plain form.

    python tests/perf/causal_conv_sweep.py [--rows 256,512,1024,2048] [--lanes 128,256,512] [--chunks 32,64]
                                           [--out chiprun_out/causal_conv_sweep.jsonl]

Run it from the root of a checkout. The two shapes are the benchmark's cells', bfloat16, each
as the projection leaves it: Granite 4.0-H's 4,352 channels with a bias, columns 4,096-8,447 of
``in_proj``'s ``[1, 8192, 8512]``, and Qwen3-Next's 8,192 without, columns 0-8,191 of ``w_qkvz``'s
``[1, 8192, 12288]``. ``--rows``, ``--lanes`` and ``--chunks`` set the tokens and channels of a
grid step and the rows of a turn of the kernels' inner loop (``ops/pallas/causal_conv.ROWS``,
``LANES``, ``CHUNK``) for a run, every combination in turn; the first line of a shape is the
plain form (``plain_causal_conv`` on the materialised window). ``fwd`` is every device operation
of one call, ``bwd`` of one pull-back of a cotangent (the forward's result unused: the plain form
makes its sum again inside, as it does in a step; the kernels' backward is one call), ``kernels``
the ``ds_causal_conv_*`` kernels among them by name; ``floor`` is x read and y written once
(forward), x and dy read and dx written once (backward), at 819 GB/s. ``--check`` adds the
relative distance of the kernels' results from the plain form's.
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
from deepspeed_tpu.ops import delta_rule  # noqa: E402
from deepspeed_tpu.ops.pallas import causal_conv as kernels  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from delta_rule_sweep import device_ms, rel  # noqa: E402  (the trace's reader, shared)

PEAK_BYTES = 819e9      # TPU v5e: HBM
T, W = 8192, 4
SHAPES = {"granite": dict(wide=8512, columns=(4096, 8448), bias=True),
          "qwen3next": dict(wide=12288, columns=(0, 8192), bias=False)}


def inputs(wide, columns, bias, seed=0):
    rng = np.random.default_rng(seed)
    C = columns[1] - columns[0]
    low = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)      # noqa: E731
    uniform = lambda *shape: jnp.asarray(rng.uniform(-0.5, 0.5, shape), jnp.bfloat16)   # noqa: E731
    return (low(1, T, wide), uniform(W, C), uniform(C) if bias else None), low(1, T, C)


def passes(conv):
    """``(forward, pull-back)`` of ``conv(x, w, bias)``, each a function of the operands (and the
    cotangent) alone."""
    return conv, lambda x, w, b, cot: jax.vjp(conv, x, w, b)[1](cot)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="256,512,1024,2048")
    ap.add_argument("--lanes", default="128,256,512")
    ap.add_argument("--chunks", default="64")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--out", default="chiprun_out/causal_conv_sweep.jsonl")
    opts = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("causal_conv_sweep.py measures the compiled kernels: it needs a TPU")
    os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
    numbers = lambda text: [int(n) for n in text.split(",") if n]      # noqa: E731
    with open(opts.out, "w") as f:
        for name, shape in SHAPES.items():
            (x, w, b), cot = inputs(**shape)
            columns = shape["columns"]
            C = columns[1] - columns[0]
            floor = dict(fwd=1e3 * 2 * T * C * 2 / PEAK_BYTES, bwd=1e3 * 3 * T * C * 2 / PEAK_BYTES)
            plain = lambda x, w, b: delta_rule.plain_causal_conv(x[..., columns[0]:columns[1]], w, True, b)  # noqa: E731
            want = None
            settings = [None] + list(itertools.product(numbers(opts.rows), numbers(opts.lanes), numbers(opts.chunks)))
            for setting in settings:
                line = dict(shape=name, x=[1, T, shape["wide"]], columns=columns, bias=shape["bias"],
                            device=jax.devices()[0].device_kind, floor_ms=floor)
                if setting is None:
                    conv = plain
                    line["form"] = "plain"
                else:
                    kernels.ROWS, kernels.LANES, kernels.CHUNK = setting
                    jax.clear_caches()       # the kernels' entry points are jitted: a body is kept a shape
                    conv = lambda x, w, b: delta_rule.causal_conv(x, w, True, b, columns)   # noqa: E731
                    line.update(form="kernels", rows=setting[0], chunk=setting[2],
                                lanes=kernels.sizes(T, C, columns[0])[1])
                fwd, bwd = passes(conv)
                try:
                    for which, fn, args in (("fwd", fwd, (x, w, b)), ("bwd", bwd, (x, w, b, cot))):
                        ms, named = device_ms(fn, args, kernels_named="ds_causal_conv_")
                        line[which] = dict(ms=ms, kernels=named, of_floor=floor[which] / ms)
                    if opts.check:
                        got = jax.jit(fwd)(x, w, b), jax.jit(bwd)(x, w, b, cot)
                        if setting is None:
                            want = got
                        else:
                            line["fwd_rel"] = rel(got[0], want[0])
                            line["bwd_rel"] = [rel(g, p) for g, p in zip(got[1], want[1]) if g is not None]
                except Exception as e:  # a tile the compiler refuses is a line of the table too
                    line["error"] = repr(e)[:400]
                f.write(json.dumps(line) + "\n")
                f.flush()
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
