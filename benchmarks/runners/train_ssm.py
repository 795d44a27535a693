"""A training cell of a hybrid state-space model (``deepspeed_tpu/models/granite_hybrid.py``)
whose training state fills the chip: the engine's own ``forward``/``backward``/``step`` on
packed documents with whole blocks recomputed, as ``runners/train_hybrid.py`` measures
Qwen3-Next. In set-up one seeded sequence goes through the system and through the
configuration's plain reference on the same parameters: the whole model (loss, last logits),
and each new kind of layer ALONE on the reference's own inputs (the Mamba-2 mixer, the scan
itself in float32 on bf16 values, the position-free grouped attention; outputs over the
sequence, gradients on its last positions). Everything the comparison held is dropped before
the engine builds its state. The process's first step then runs the ENGINE's own compiled
programs, recomputation and all, on that sequence (``check_step``): its loss against the
reference's, and what it took off every leaf of the master against Adam's first step, the
"before" copy of the master on the host."""

import numpy as np

from benchmarks import harness
from benchmarks.harness import clock
from benchmarks.runners.train import _build_engine
from benchmarks.runners.train_hybrid import Alone, _limits, _rel_l2, _worst_leaf


def build_model(config):
    """The program's Granite 4.0-H from the configuration's keys."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig, GraniteHybridModel
    return GraniteHybridModel(GraniteHybridConfig.from_published(
        config["model"], initializer_range=config["assumed"]["initializer_range"][1],
        remat=config["remat"], compute_dtype=getattr(jnp, config["compute_dtype"])))


class ScanAlone:
    """The state-space scan ALONE: the system's chunked ``ops/ssd.ssd_scan`` against the
    reference's token-at-a-time recurrence on the xs, dt, B and C that the reference's mixer
    makes of its input (xs, B, C rounded to the compute dtype's values, as the system's are,
    and held in float32, so that the scan returns float32: what is compared is its
    arithmetic, not the rounding of its output). ``lower`` puts the reference's own recurrence
    at a lower precision (``state_dtype``, ``dt_dtype``) in the system's place: the second
    reading of a limit (``tests/perf``'s probe; never the cell)."""

    def __init__(self, ref, m, dtype, chunk, **lower):
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.ops.ssd import ssd_scan

        def inputs(mp, x):
            xs, dt, B, C, _ = ref.mamba_inputs(x.astype(jnp.float32), mp, m)
            rounded = lambda a: a.astype(dtype).astype(jnp.float32)       # noqa: E731
            return (rounded(xs), dt, -jnp.exp(mp["A_log"]), rounded(B), rounded(C), mp["D"])

        def grad_of(fn):
            return jax.jit(jax.grad(lambda cot, *a: jnp.sum(fn(*a) * cot), argnums=tuple(range(1, 7))))

        system = (lambda *a: ssd_scan(*a, chunk)) if not lower else (
            lambda *a: ref.ssm_recurrent(*a, **lower))
        self.inputs = jax.jit(inputs)
        self.fns = jax.jit(system), jax.jit(ref.ssm_recurrent)
        self.grads = grad_of(system), grad_of(ref.ssm_recurrent)

    def read(self, mp, x, rows, seed):
        """``(output's relative error over the sequence, the worst gradient's on the last
        ``rows`` positions or None where ``rows`` is 0)``."""
        import jax
        import jax.numpy as jnp
        args = self.inputs(mp, x[None])
        out = _rel_l2(jax.device_get(self.fns[0](*args)), jax.device_get(self.fns[1](*args)))
        if not rows:
            return out, None
        tail = tuple(a[:, -rows:] if a.ndim > 1 else a for a in args)
        cot = jnp.asarray(np.random.default_rng(seed).standard_normal(tail[0].shape), jnp.float32)
        got, want = (jax.device_get(g(cot, *tail)) for g in self.grads)
        return out, _worst_leaf(got, want)


def compare_layers(model, ref, m, params, mixer_in, rows, seed):
    """Every new kind of layer alone on the reference's own inputs (``mixer_in [L, 1, T, H]``):
    the worst layer's reading of each output, and the gradients of the first layer of each
    kind."""
    import jax.numpy as jnp
    c = model.config
    kinds = {"mixer": Alone(lambda p, x: model.mamba_mixer(x, p), lambda p, x: ref.mamba_mixer(x, p, m)),
             "attention": Alone(lambda p, x: model.attention(x, p), lambda p, x: ref.attention(x, p, m))}
    scan = ScanAlone(ref, m, c.compute_dtype, c.mamba_chunk_size)
    out = {"scan_rel": 0.0}
    for l, lp in enumerate(params["layers"]):
        x = jnp.asarray(mixer_in[l, 0]).astype(c.compute_dtype)
        kind = "attention" if c.kind(l) == "attention" else "mixer"
        out[kind + "_rel"] = max(out.get(kind + "_rel", 0.0), kinds[kind].output(lp["mixer"], x))
        if kind + "_grad_rel" not in out:
            out[kind + "_grad_rel"] = kinds[kind].gradients(lp["mixer"], x, rows, seed)
        if kind == "mixer":
            y, g = scan.read(lp["mixer"], x, 0 if "scan_grad_rel" in out else rows, seed)
            out["scan_rel"] = max(out["scan_rel"], y)
            out.setdefault("scan_grad_rel", g)
    return out


def check_reference(ctx, model, params, tokens, labels):
    """One seeded sequence through the system and through the plain float32 reference on the
    same parameters: the whole model (the loss, the logits of the last positions), then every
    new kind of layer alone (``compare_layers``), which is where a lower precision shows.
    Returns the readings and the reference's own inputs of every mixer (``[L, 1, T, H]``, on
    the device: the cell drops them at once)."""
    import jax
    config = ctx["config"]
    spec, m = config["reference"], config["model"]
    ref = ctx["manifest"].reference(spec["module"])
    tol = _limits(ctx, of_the_step=False)
    last = min(spec["last_positions"], tokens.shape[0])
    rows = min(spec["grad_positions"], tokens.shape[0])
    got = jax.device_get(jax.jit(lambda p, t, l: model.forward_details(p, t[None], l[None], last))(
        params, tokens, labels))
    want = jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], m, last))(params, tokens, labels)
    mixer_in = want["mixer_in"]
    readings = compare_layers(model, ref, m, params, mixer_in, rows, ctx["seed"])
    want = jax.device_get({k: want[k] for k in ("loss", "logits")})
    readings["train_loss_rel"] = abs(float(got["loss"]) - float(want["loss"])) / abs(float(want["loss"]))
    readings["last_logits_rel"] = float(np.abs(got["logits"][0] - want["logits"][0]).max()
                                        / np.abs(want["logits"][0]).max())
    ok = bool(np.isfinite(float(got["loss"])) and set(readings) == set(tol)
              and all(readings[k] <= tol[k] for k in tol))
    return dict(readings, system_loss=float(got["loss"]), reference_loss=float(want["loss"]),
                rel_diff=readings["train_loss_rel"], tolerances=tol, ok=ok), mixer_in


def check_step(ctx, engine, tokens, labels, batch_size, reference_loss):
    """One step of the ENGINE's own compiled programs (the gradient program with its blocks
    recomputed, the ZeRO-2 gradient path, the update program) on the sequence the reference
    saw, as ``train_hybrid.check_step`` reads Qwen3-Next's: ``step_loss_rel`` is the step's
    loss against the reference's, ``step_update_shortfall`` what the step took off each leaf
    of the float32 master against Adam's first step at the engine's rate,
    ``| ||after - before|| / (rate x sqrt(elements)) - 1 |``, the worst leaf's. Here EVERY
    element has a gradient, the table's rows of tokens the sequence lacks too: the table is
    also the head. The master's "before" copy lies on the host; no second one on the device."""
    import jax
    tol = _limits(ctx, of_the_step=True)
    rate, = engine.get_lr()
    before = jax.device_get(engine.master_params)
    loss = engine(*(np.broadcast_to(a, (batch_size,) + a.shape) for a in (tokens, labels)))
    engine.backward(loss)
    engine.step()
    after = jax.device_get(engine.master_params)
    by_leaf = {}
    for (path, b), a in zip(jax.tree_util.tree_flatten_with_path(before)[0],
                            jax.tree_util.tree_leaves(after)):
        by_leaf[jax.tree_util.keystr(path)] = abs(float(
            np.linalg.norm((a - b).astype(np.float64)) / (rate * np.sqrt(b.size))) - 1.0)
    worst = max(by_leaf, key=by_leaf.get)
    readings = {"step_loss_rel": abs(float(loss) - reference_loss) / abs(reference_loss),
                "step_update_shortfall": by_leaf[worst]}
    ok = bool(set(readings) == set(tol) and all(readings[k] <= tol[k] for k in tol))
    return dict(readings, worst_leaf=worst, rate=rate, tolerances=tol, ok=ok), loss


def run(ctx):
    import jax
    cell, config, traffic, log = ctx["cell"], ctx["config"], ctx["traffic"], ctx["log"]
    tr, chips = ctx["tracing"], cell["chips"]
    batch_size = cell["micro_batch_per_chip"] * chips
    seq_len = traffic["seq_len"]
    m = config["model"]
    setup = {}

    t = clock()
    generate = ctx["manifest"].generator(traffic["generator"])
    model = build_model(config)
    batches, _ = generate(traffic, ctx["seed"], vocab=m["vocab_size"],
                          batch=batch_size, n_batches=traffic["batches_ahead"])
    setup["data_s"] = clock() - t

    t = clock()
    params = harness.init_params(model, ctx["seed"])
    setup["weights_s"] = clock() - t
    t = clock()
    sequence = batches[-1][0][0], batches[-1][1][0]
    reference, _ = check_reference(ctx, model, params, *sequence)
    jax.clear_caches()           # the comparison's programs, and the constants they hold
    setup["reference_s"] = clock() - t
    t = clock()
    engine = _build_engine(ctx, model, params, batch_size)
    del params
    setup["engine_s"] = clock() - t
    t = clock()
    reference["step"], first_loss = check_step(ctx, engine, *sequence, batch_size,
                                                 reference["reference_loss"])
    setup["step_check_s"] = clock() - t

    def step(i):
        tokens, labels = batches[i % len(batches)]
        loss = engine(tokens, labels)
        engine.backward(loss)
        engine.step()
        return loss

    # warm-up: until ``warm_steps`` steps in a row have compiled nothing
    t = clock()
    warm_losses, quiet, n = [first_loss], 0, 0
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
    correct = bool(bad == 0 and fell and window_compiles == 0 and reference["ok"]
                   and reference["step"]["ok"])

    peak = harness.memory_peak_bytes(ctx["devices"])
    memory = {k: v for k, v in (ctx["devices"][0].memory_stats() or {}).items()
              if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_alloc_size")}
    harness.write_record(ctx["out_dir"], cell["name"], ctx["seed"], {
        "cell": cell["name"], "seed": ctx["seed"], "traced": tr.on, "window_s": window_s,
        "fence_ms": (t1 - returns[-1]) * 1e3, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s], "losses": losses,
        "warm_losses": [float(x) for x in jax.device_get(warm_losses)],
        "setup": setup, "reference": reference, "memory": memory, "memory_peak_bytes": peak,
        "tokens_per_s_chip": rate_chip})
    step_ms, stall_ms = harness.step_profile(intervals_ms)
    harness.summary_line("step_return_interval", intervals_ms, step_ms_median=step_ms,
                         longest_stall_ms=stall_ms, fence_ms=(t1 - returns[-1]) * 1e3,
                         first_losses=[float(x) for x in jax.device_get(warm_losses[:5])],
                         window_last_loss=losses[-1], reference=reference, setup=setup,
                         warm_steps=len(warm_losses), window_compiles=window_compiles,
                         memory=memory, memory_peak_bytes=peak)

    return {
        "correct": correct, "attempted": steps, "failed": bad,
        "t_window_start": t0, "window_s": window_s, "setup": setup,
        "end_to_end": {"train_tokens_per_s_chip": rate_chip},
        "memory_peak_bytes": peak,
        # what the per-layer readers take their numbers from. ``model`` is what the flash
        # readers that exist know a model by (``flops.flash_required``): exactly the
        # attention layers, ``hidden_size`` wide over ``num_attention_heads``
        "kind": "train", "chips": chips, "steps": steps, "tokens_per_step": tokens_per_step,
        "batch_per_chip": cell["micro_batch_per_chip"], "seq_len": seq_len,
        "tokens_per_s_chip": rate_chip, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s],
        "model": flash_sizes(m), "ssm_model": m, "vocab": m["vocab_size"],
    }


def flash_sizes(m):
    """The three GPT-2 names ``flops.flash_required`` reads, such that it counts exactly
    this model's softmax attention: its attention layers, ``hidden_size`` wide in all."""
    kinds = m["layer_types"][:m["num_hidden_layers"]]
    return {"n_embd": m["hidden_size"], "n_layer": sum(k == "attention" for k in kinds),
            "n_head": m["num_attention_heads"]}
