"""A training cell: the engine's own ``forward``/``backward``/``step`` on packed
documents, measured over a whole number of steps to one final fence."""

import functools

import numpy as np

from benchmarks import harness
from benchmarks.harness import clock


def _check_reference(ctx, model, params, batch):
    """The model's loss on one seeded sequence against the plain float32 reference on
    the same parameters, before the engine takes the memory."""
    import jax
    config, m = ctx["config"], ctx["config"]["model"]
    ref = ctx["manifest"].reference(config["reference"]["module"])
    tokens, labels = batch[0][:1], batch[1][:1]
    got = float(jax.jit(model.apply)(params, tokens, labels))
    want = float(jax.jit(functools.partial(
        ref.loss, n_head=m["n_head"], eps=m["layer_norm_epsilon"]))(params, tokens, labels))
    tol = ctx["manifest"].tolerance(config["reference"]["tolerance"])
    rel = abs(got - want) / abs(want)
    return {"system_loss": got, "reference_loss": want, "rel_diff": rel, "tolerance": tol,
            "ok": bool(np.isfinite(got) and rel <= tol)}


def _build_engine(ctx, model, params, batch_size):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    config = dict(ctx["config"]["engine"], train_batch_size=batch_size)
    devices = ctx["devices"]
    if len(devices) == jax.device_count():
        # the normal entry point, which spans every device of the host
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config_params=config)
    else:
        # a one-chip cell on a host with more chips: the same engine class on a mesh
        # over the cell's devices
        mesh = build_mesh(data=len(devices), model=1, pipe=1, devices=devices)
        engine = DeepSpeedEngine(model=model, model_parameters=params,
                                 config_params=config, mesh=mesh)
    return engine


def run(ctx):
    import jax
    cell, config, traffic, log = ctx["cell"], ctx["config"], ctx["traffic"], ctx["log"]
    tr, chips = ctx["tracing"], cell["chips"]
    batch_size = cell["micro_batch_per_chip"] * chips
    seq_len = traffic["seq_len"]
    setup = {}

    t = clock()
    generate = ctx["manifest"].generator(traffic["generator"])
    model = harness.build_gpt2(config)
    batches, _ = generate(traffic, ctx["seed"], vocab=model.config.vocab_size,
                          batch=batch_size, n_batches=traffic["batches_ahead"])
    setup["data_s"] = clock() - t

    t = clock()
    params = harness.init_params(model, ctx["seed"])
    setup["weights_s"] = clock() - t
    t = clock()
    reference = _check_reference(ctx, model, params, batches[-1])
    setup["reference_s"] = clock() - t
    t = clock()
    engine = _build_engine(ctx, model, params, batch_size)
    del params
    setup["engine_s"] = clock() - t

    def step(i):
        tokens, labels = batches[i % len(batches)]
        loss = engine(tokens, labels)
        engine.backward(loss)
        engine.step()
        return loss

    # warm-up: until ``warm_steps`` steps in a row have compiled nothing
    t = clock()
    warm_losses, quiet, n = [], 0, 0
    while quiet < cell["warm_steps"]:
        mark = log.mark()
        loss = step(n)
        jax.block_until_ready(engine.params)
        warm_losses.append(loss)
        quiet = quiet + 1 if log.since(mark)["compiles"] == 0 else 0
        n += 1
        if n > cell["warm_steps"] + 20:
            raise RuntimeError("the step keeps compiling: no steady state to measure")
    first_loss = float(warm_losses[0])
    warm_count = n
    setup["warm_s"] = clock() - t
    setup["compile_s"] = log.counts["compile_s"]
    setup["compiles"] = log.counts["compiles"]
    setup["cache_hits"] = log.counts["cache_hits"]

    seconds = min(ctx["seconds"], cell["trace_seconds"]) if tr.on else ctx["seconds"]
    harness.quiet_host()
    mark = log.mark()
    losses, dispatch_s, returns = [], [], []
    with tr.window():
        t0 = clock()
        while True:
            with tr.span("dispatch"):
                ta = clock()
                losses.append(step(n))
                tb = clock()
            dispatch_s.append(tb - ta)
            returns.append(tb)
            n += 1
            if tb - t0 >= seconds:
                break
        with tr.span("fence"):
            jax.block_until_ready((engine.params, losses[-1]))
        t1 = clock()
    window_compiles = log.since(mark)["compiles"]
    losses = [float(x) for x in jax.device_get(losses)]

    steps = len(losses)
    window_s = t1 - t0
    tokens_per_step = batch_size * seq_len
    rate_chip = steps * tokens_per_step / window_s / chips
    intervals_ms = (np.diff([t0] + returns) * 1e3).tolist()
    bad = sum(not np.isfinite(x) for x in losses) + int(engine.skipped_steps)
    fell = float(np.mean(losses[-10:])) < first_loss
    correct = bool(bad == 0 and fell and window_compiles == 0 and reference["ok"])

    harness.write_record(ctx["out_dir"], cell["name"], ctx["seed"], {
        "cell": cell["name"], "seed": ctx["seed"], "traced": tr.on, "window_s": window_s,
        "fence_ms": (t1 - returns[-1]) * 1e3, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s], "losses": losses,
        "warm_losses": [float(x) for x in jax.device_get(warm_losses)],
        "setup": setup, "reference": reference})
    step_ms, stall_ms = harness.step_profile(intervals_ms)
    harness.summary_line("step_return_interval", intervals_ms, step_ms_median=step_ms,
                         longest_stall_ms=stall_ms, fence_ms=(t1 - returns[-1]) * 1e3,
                         first_losses=[float(x) for x in jax.device_get(warm_losses[:5])],
                         window_last_loss=losses[-1], reference=reference, setup=setup,
                         warm_steps=warm_count, window_compiles=window_compiles)

    return {
        "correct": correct, "attempted": steps, "failed": bad,
        "t_window_start": t0, "window_s": window_s, "setup": setup,
        "end_to_end": {"train_tokens_per_s_chip": rate_chip},
        "memory_peak_bytes": harness.memory_peak_bytes(ctx["devices"]),
        # what the per-layer readers take their numbers from
        "kind": "train", "chips": chips, "steps": steps, "tokens_per_step": tokens_per_step,
        "batch_per_chip": cell["micro_batch_per_chip"], "seq_len": seq_len,
        "tokens_per_s_chip": rate_chip, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s],
        "model": config["model"], "vocab": model.config.vocab_size,
    }
