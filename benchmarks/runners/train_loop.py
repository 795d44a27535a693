"""A training cell of a looped model (``deepspeed_tpu/models/ouro.py``): the engine's own
``forward``/``backward``/``step`` on packed documents with whole blocks recomputed, as
``runners/train_ssm.py`` measures Granite. In set-up one seeded sequence goes through the
system and through the configuration's plain reference on the same parameters:

(a) the whole model: the loss, each exit's mean cross-entropy, the exit distribution a
    position, the last logits of EVERY exit;
(b) one pass ALONE: the reference's own ``x^1`` rounded to the compute dtype through the
    system's layers and ``norm_f`` (output over the sequence, gradients on its last positions);
(c) a SHARED leaf's gradient: the whole loss by layer 0's ``wq`` and ``w_down`` on the
    sequence's first positions, the system's parameters in the compute dtype as a step's are
    (so that the passes' contributions are added as a step adds them), against the reference's
    contributions of untied copies, a copy a pass: against their sum, and pass by pass (the
    weight each contribution has in the system's gradient, which is one);
(d) the head a position ALONE: the losses and their gradients by the input and by the table
    under a seeded cotangent a position;
(e) the gate and the exit distribution ALONE on the reference's exit states: a position's
    probabilities and entropy, which are float32 whatever the compute dtype.

Everything the comparison held is dropped before the engine builds its state. The process's
first step then runs the ENGINE's own compiled programs on that sequence
(``train_hybrid.check_step``: its loss against the reference's, and what it took off every
leaf of the master against Adam's first step; the embedding's rows of absent tokens have no
gradient, the untied head's all have). After the window the exit distribution's device
scalars of every step are fetched: they sum to one."""

import numpy as np

from benchmarks import harness
from benchmarks.harness import clock
from benchmarks.runners.train import _build_engine
from benchmarks.runners.train_hybrid import Alone, _limits, _rel_l2, _worst_leaf, check_step

SHARED = ("wq", "w_down")        # the leaves of layer 0 whose summed gradient (c) reads
EXIT_SUM_TOLERANCE = 1e-5


def build_model(config):
    """The program's Ouro from the configuration's keys."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.ouro import OuroConfig, OuroModel
    return OuroModel(OuroConfig.from_published(
        config["model"], exit_entropy_coef=config["exit_entropy_coef"],
        initializer_range=config["assumed"]["initializer_range"][1],
        remat=config["remat"], compute_dtype=getattr(jnp, config["compute_dtype"])))


def read_whole(got, want):
    """(a): two forwards' ``loss``, ``exit_ce [T]``, ``p [T, 1, S]`` and ``logits [T, 1, last, V]``."""
    got, want = ({k: np.asarray(v[k], np.float64) for k in ("loss", "exit_ce", "p", "logits")}
                 for v in (got, want))
    return {"train_loss_rel": float(abs(got["loss"] - want["loss"]) / abs(want["loss"])),
            "exit_ce_rel": float(np.max(np.abs(got["exit_ce"] - want["exit_ce"]) / np.abs(want["exit_ce"]))),
            "exit_p_abs": float(np.abs(got["p"] - want["p"]).max()),
            "last_logits_rel": float(max(np.abs(g - w).max() / np.abs(w).max()
                                         for g, w in zip(got["logits"], want["logits"])))}


class HeadAlone:
    """The head a position ALONE: ``fn(x [1, S, H], head [V, H], labels [1, S]) -> l [1, S]``,
    the system's against the reference's on the reference's own last exit state rounded to
    the compute dtype; the gradients of ``sum(l * c)`` (``c`` seeded, a position) by the input
    and by the table."""

    def __init__(self, system_fn, reference_fn):
        import jax
        import jax.numpy as jnp

        def grad_of(fn):
            return jax.jit(jax.grad(lambda x, head, labels, cot: jnp.sum(fn(x, head, labels) * cot),
                                    argnums=(0, 1)))
        self.fns = jax.jit(system_fn), jax.jit(reference_fn)
        self.grads = grad_of(system_fn), grad_of(reference_fn)

    def read(self, x, head, labels, seed):
        import jax
        import jax.numpy as jnp
        cot = jnp.asarray(np.random.default_rng(seed).standard_normal(labels.shape), jnp.float32)
        sides = (x, x.astype(jnp.float32))
        losses = [jax.device_get(fn(xs, head, labels)) for fn, xs in zip(self.fns, sides)]
        grads = [jax.device_get(g(xs, head, labels, cot)) for g, xs in zip(self.grads, sides)]
        return {"head_ce_rel": _rel_l2(*losses), "head_grad_rel": _worst_leaf(*grads)}


def read_shared(got, by_pass):
    """(c): a shared leaf's gradient ``got {name: g}`` against the reference's contributions a
    pass ``by_pass [{name: G_t}]``. ``shared_grad_rel`` is its distance from their sum. At
    initialisation the first pass's contribution is nearly all of that sum (a later pass's may be
    a hundredth of it), so a later pass lost or carried wrongly hides inside the bf16 products'
    own error there. ``shared_pass_weight_abs`` reads it: ``g`` fitted by least squares as
    ``sum_t a_t G_t``, the largest ``|a_t - 1|`` of any pass and leaf. The contributions are
    nearly orthogonal, so a pass left out reads 1 and one added twice reads 1 however small it
    is, while an error that is not along a contribution moves no weight."""
    rel, weight = 0.0, 0.0
    for name, g in got.items():
        G = np.stack([np.asarray(one[name], np.float64).ravel() for one in by_pass])
        g = np.asarray(g, np.float64).ravel()
        total = G.sum(axis=0)
        rel = max(rel, float(np.linalg.norm(g - total) / np.linalg.norm(total)))
        weights = np.linalg.lstsq(G @ G.T, G @ g, rcond=None)[0]
        weight = max(weight, float(np.abs(weights - 1.0).max()))
    return {"shared_grad_rel": rel, "shared_pass_weight_abs": weight}


def system_shared_gradient(model, params, tokens, labels):
    """(c), the system's side: the gradient of the loss by layer 0's ``SHARED`` leaves, every
    parameter in the compute dtype as a step's are."""
    import jax
    step_params = jax.tree_util.tree_map(lambda p: p.astype(model.config.compute_dtype), params)

    def by_leaves(leaves, p, t, l):
        layers = [dict(p["layers"][0], **leaves)] + list(p["layers"][1:])
        return model.apply(dict(p, layers=layers), t[None], l[None])[0]

    leaves = {name: step_params["layers"][0][name] for name in SHARED}
    return jax.device_get(jax.jit(jax.grad(by_leaves))(leaves, step_params, tokens, labels))


def exits_alone(system_fn, reference_fn, states, gate):
    """(e): the gate and the exit distribution ALONE, ``fn(states [T, 1, S, H], gate) ->
    (p [T, 1, S], entropy [1, S])`` on the reference's own exit states rounded to the compute
    dtype's values: the largest error of a position's probability and of its entropy."""
    import jax
    got, want = (jax.device_get(jax.jit(fn)(states, gate)) for fn in (system_fn, reference_fn))
    return {"exit_alone_abs": float(max(np.abs(np.asarray(g, np.float64) - w).max() for g, w in zip(got, want)))}


def head_alone(ref):
    from deepspeed_tpu.models.layers import chunked_cross_entropy_a_position
    return HeadAlone(chunked_cross_entropy_a_position,
                     lambda x, head, labels: ref.cross_entropy(x, head, labels)[0])


def pass_alone(model, ref, m):
    return Alone(model.one_pass, lambda p, x: ref.one_pass(p, x, m))


def worst_gradient(alone, params, x, rows, seed):
    """``Alone.gradients``' reading with the name of the leaf that gave it."""
    import jax
    import jax.numpy as jnp
    tail = x[None, -rows:]
    cot = jnp.asarray(np.random.default_rng(seed).standard_normal(tail.shape), jnp.float32)
    got, want = alone.grads[0](params, tail, cot), alone.grads[1](params, tail.astype(jnp.float32), cot)
    by_leaf = {jax.tree_util.keystr(path): _rel_l2(jax.device_get(g), jax.device_get(w))
               for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                       jax.tree_util.tree_leaves(want))}
    worst = max(by_leaf, key=by_leaf.get)
    return by_leaf[worst], worst


def check_reference(ctx, model, params, tokens, labels):
    """One seeded sequence through the system and through the plain float32 reference on the
    same parameters, (a) to (e) of this file's head. Returns the readings and what the probe
    reads its faults on (the reference's forward, on the device: the cell drops it at once)."""
    import jax
    import jax.numpy as jnp
    config = ctx["config"]
    spec, m, beta = config["reference"], config["model"], config["exit_entropy_coef"]
    ref = ctx["manifest"].reference(spec["module"])
    tol = _limits(ctx, of_the_step=False)
    last = min(spec["last_positions"], tokens.shape[0])
    rows = min(spec["grad_positions"], tokens.shape[0])
    dtype = model.config.compute_dtype
    got = jax.device_get(jax.jit(lambda p, t, l: model.forward_details(p, t[None], l[None], last))(
        params, tokens, labels))
    want = jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], m, beta, last))(params, tokens, labels)
    readings = read_whole(got, jax.device_get({k: want[k] for k in ("loss", "exit_ce", "p", "logits")}))
    # (b) the second pass: what it is handed is the first pass's exit state
    blocks = {"layers": params["layers"], "norm_f": params["norm_f"]}
    alone = pass_alone(model, ref, m)
    x = want["states"][0, 0].astype(dtype)
    readings["pass_rel"] = alone.output(blocks, x)
    readings["pass_grad_rel"], worst = worst_gradient(alone, blocks, x, rows, ctx["seed"])
    # (c) a shared leaf's gradient, a sum over the passes
    by_pass = jax.device_get(jax.jit(
        lambda p, t, l: ref.shared_gradient_by_pass(p, t[None], l[None], m, beta, 0, SHARED))(
        params, tokens[:rows], labels[:rows]))
    readings.update(read_shared(system_shared_gradient(model, params, tokens[:rows], labels[:rows]), by_pass))
    # (d) the head a position on the last exit's state
    readings.update(head_alone(ref).read(
        want["states"][-1].astype(dtype), params["head"], jnp.asarray(labels)[None], ctx["seed"]))
    # (e) the gate and the exit distribution on the exit states, the compute dtype's values
    rounded = want["states"].astype(dtype).astype(jnp.float32)
    readings.update(exits_alone(lambda states, gate: model.exit_weights({"gate": gate}, states), ref.exits,
                                rounded, params["gate"]))
    loss = float(got["loss"])
    ok = bool(np.isfinite(loss) and set(readings) == set(tol) and all(readings[k] <= tol[k] for k in tol))
    return dict(readings, system_loss=loss, reference_loss=float(want["loss"]),
                rel_diff=readings["train_loss_rel"], exit_ce=[float(v) for v in got["exit_ce"]],
                exit_mass=[float(v) for v in np.mean(got["p"], axis=(1, 2))],
                pass_grad_worst_leaf=worst, tolerances=tol, ok=ok), want


def exits_of(kept):
    """The window's exit scalars from the fetched device scalars of its steps: the mean
    share of positions' mass a pass, the mean cross-entropy a pass, the mean entropy, and how
    far the worst step's masses lie from summing to one."""
    if not kept:
        return {"steps_counted": 0, "mass_by_pass": None, "ce_by_pass": None, "entropy": None,
                "mass_sum_error_max": None}
    mass = np.stack([s["exit_mass"] for s in kept]).astype(np.float64)
    return {"steps_counted": len(kept), "mass_by_pass": mass.mean(axis=0).tolist(),
            "ce_by_pass": np.stack([s["exit_ce"] for s in kept]).mean(axis=0).tolist(),
            "entropy": float(np.mean([s["exit_entropy"] for s in kept])),
            "mass_sum_error_max": float(np.abs(mass.sum(axis=1) - 1.0).max())}


def run(ctx):
    import jax
    from deepspeed_tpu.utils import spans
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
    reference = check_reference(ctx, model, params, *sequence)[0]      # the reference's forward dies here
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
    first_step = engine.global_steps
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
    # the exit distribution's device scalars of every step the recorder still holds: fetched
    # here, after the window
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    exits = exits_of([s for step_no, s in kept if step_no >= first_step])

    steps = len(losses)
    window_s = t1 - t0
    tokens_per_step = batch_size * seq_len
    rate_chip = steps * tokens_per_step / window_s / chips
    intervals_ms = (np.diff([t0] + returns) * 1e3).tolist()
    bad = sum(not np.isfinite(x) for x in losses) + int(engine.skipped_steps)
    fell = float(np.mean(losses[-10:])) < first_loss
    correct = bool(bad == 0 and fell and window_compiles == 0 and reference["ok"]
                   and reference["step"]["ok"] and exits["steps_counted"] == steps
                   and exits["mass_sum_error_max"] <= EXIT_SUM_TOLERANCE)

    peak = harness.memory_peak_bytes(ctx["devices"])
    memory = {k: v for k, v in (ctx["devices"][0].memory_stats() or {}).items()
              if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_alloc_size")}
    harness.write_record(ctx["out_dir"], cell["name"], ctx["seed"], {
        "cell": cell["name"], "seed": ctx["seed"], "traced": tr.on, "window_s": window_s,
        "fence_ms": (t1 - returns[-1]) * 1e3, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s], "losses": losses,
        "warm_losses": [float(x) for x in jax.device_get(warm_losses)],
        "setup": setup, "reference": reference, "exits": exits, "memory": memory,
        "memory_peak_bytes": peak, "tokens_per_s_chip": rate_chip})
    step_ms, stall_ms = harness.step_profile(intervals_ms)
    harness.summary_line("step_return_interval", intervals_ms, step_ms_median=step_ms,
                         longest_stall_ms=stall_ms, fence_ms=(t1 - returns[-1]) * 1e3,
                         first_losses=[float(x) for x in jax.device_get(warm_losses[:5])],
                         window_last_loss=losses[-1], reference=reference, setup=setup,
                         warm_steps=len(warm_losses), window_compiles=window_compiles,
                         exits=exits, memory=memory, memory_peak_bytes=peak)

    return {
        "correct": correct, "attempted": steps, "failed": bad,
        "t_window_start": t0, "window_s": window_s, "setup": setup,
        "end_to_end": {"train_tokens_per_s_chip": rate_chip},
        "memory_peak_bytes": peak,
        # what the per-layer readers take their numbers from. ``model`` is what the flash
        # readers that exist know a model by (``flops.flash_required``): the attention calls
        # of a step, a layer a pass
        "kind": "train", "chips": chips, "steps": steps, "tokens_per_step": tokens_per_step,
        "batch_per_chip": cell["micro_batch_per_chip"], "seq_len": seq_len,
        "tokens_per_s_chip": rate_chip, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s],
        "model": flash_sizes(m), "loop_model": m, "vocab": m["vocab_size"], "exits": exits,
    }


def flash_sizes(m):
    """The three GPT-2 names ``flops.flash_required`` reads, such that it counts exactly this
    model's attention calls of a step: every layer once a pass."""
    return {"n_embd": m["num_attention_heads"] * m["head_dim"],
            "n_layer": m["num_hidden_layers"] * m["total_ut_steps"], "n_head": m["num_attention_heads"]}
