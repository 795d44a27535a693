"""What every runner shares: the clock, the quiet host, the profiler window, the
per-step record, and the weights from the seed."""

import contextlib
import gc
import json
import os
import shutil
import time

import numpy as np

from . import trace_reduce

clock = time.perf_counter


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def clipped_lognormal(rng, spec, n):
    """``n`` whole lengths, log-normal around ``spec["median"]``, clipped to its range."""
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def step_profile(return_intervals_ms, ahead=2, span=6):
    """What the intervals between successive returns of ``engine.step()`` say about
    the steps, with no fence added: (median step ms, longest stall ms).

    The host runs one to two steps ahead of the device, so single intervals alternate
    between some 20 ms and two steps' time (my chip runs, PR 23). The median step is the
    median span of two successive intervals, halved. A return can be late by a step's
    time when the host had fallen back, but never early: the earliest returns of any
    ``span`` successive steps follow the device. So the stall is the largest rise, over
    ``span`` steps, of the running minimum of (return time - steps x median step): near
    zero when every step took the same time, the lost time when one step was long.
    The first ``ahead`` intervals of a window, in which the queue fills, are left out."""
    v = np.asarray(return_intervals_ms, np.float64)[ahead:]
    if v.size < 2 * span:
        return None, None
    c = np.cumsum(v)
    padded = np.concatenate([[0.0], c])
    median = float(np.median((padded[2:] - padded[:-2]) / 2))
    lead = c - np.arange(1, v.size + 1) * median
    floor = np.array([lead[j:j + span].min() for j in range(v.size - span + 1)])
    return median, float(max(0.0, (floor[span:] - floor[:-span]).max()))


def seed_key(seed):
    """A PRNG key from any whole number, also one that 32 bits do not hold."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def quiet_host():
    """Collect what set-up left and take it out of the collector's sight, so that no
    collection of old objects runs inside the window."""
    gc.collect()
    gc.freeze()


class Tracing:
    """The profiler around one window. Off, its spans cost a ``nullcontext``; on, the
    harness's spans go into the profiler's own trace as ``TraceAnnotation``s and
    ``reduced`` holds the trace reduced over the ``bench_window`` span."""

    def __init__(self, on, trace_dir, keep=False):
        self.on, self.dir, self.keep, self.reduced = bool(on), trace_dir, keep, None

    def span(self, name):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        if not self.on:
            yield
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0        # the harness's spans, not every Python call
        jax.profiler.start_trace(self.dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(self.dir)
        if path is not None:
            self.reduced = trace_reduce.Reduced(trace_reduce.load_xplane(path))
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)


def write_record(out_dir, cell, seed, record):
    """``<out>/<cell>.<seed>.steps.json``: what a run's steps or iterations took."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{cell}.{seed}.steps.json")
    with open(path, "w") as f:
        json.dump(record, f)
    return path


def summary_line(kind, values_ms, **more):
    v = np.asarray(values_ms, np.float64)
    line = {"record": kind, "count": int(v.size), "median_ms": float(np.median(v)),
            "max_ms": float(v.max()), "min_ms": float(v.min())}
    line.update(more)
    print(json.dumps(line), flush=True)


def memory_peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def build_gpt2(config):
    """The program's GPT-2 at the configuration's sizes (vocabulary padded as the
    file states)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    m = config["model"]
    return GPT2Model(GPT2Config(
        vocab_size=config.get("padded_vocab_size", m["vocab_size"]),
        n_positions=m["n_positions"], n_embd=m["n_embd"], n_layer=m["n_layer"],
        n_head=m["n_head"], layer_norm_epsilon=m["layer_norm_epsilon"],
        initializer_range=m["initializer_range"],
        use_flash_attention=config["use_flash_attention"],
        loss_chunk=config.get("assumed", {}).get("loss_chunk", 128),
        compute_dtype=getattr(jnp, config["compute_dtype"])))


def init_params(model, seed, dtype=None):
    """The weights, made on the device in one jitted call from the seed; matrices in
    ``dtype`` where one is given (the type they are served in)."""
    import jax

    def make(key):
        params = model.init(key)
        if dtype is None:
            return params
        return jax.tree_util.tree_map(lambda p: p.astype(dtype) if p.ndim >= 2 else p, params)

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))
