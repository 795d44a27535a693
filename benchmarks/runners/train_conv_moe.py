"""A training cell of a model of gated short-convolution and grouped-query attention layers, a
leading dense layer and expert layers (``deepspeed_tpu/models/lfm2_moe.py``) as one chip's share of
a wider deployment: the engine's own ``forward``/``backward``/``step`` on packed documents with
whole layers recomputed, as ``runners/train_mla_moe.py`` measures GLM-4.7-Flash and
``train_ssm_moe.py`` Nemotron-H. In set-up one seeded sequence goes through the system and through
the configuration's plain reference on the same parameters, the selection biases SEEDED
(``train_mla_moe.seeded_biases``): the whole model (the loss, the last logits, the experts chosen),
and each kind ALONE on the reference's own inputs (the gated short convolution, the attention, the
dense MLP, the expert layer and its router; outputs over the sequence, gradients on its last
positions). Everything the comparison held is dropped before the engine builds its state. The
process's first step then runs the ENGINE's own compiled programs on that sequence
(``check_step``): its loss against the reference's, what it took off every Adam leaf against Adam's
first step, and every selection bias against the reference's rule on the reference's own counts.
After the window the device scalars are fetched.

Expert choices. A (token, layer) pair counts as a WRONG choice only where the reference's gap
between its fourth and fifth ``s + b`` is wider than a margin and the choices still differ
(``train_ssm_moe.wide_gaps``): ``tie_margin`` for a router ALONE on the reference's own input,
``tie_margin_whole_model`` inside the whole model, where the system's rows are bf16."""

import numpy as np

from benchmarks import flops_conv_moe, harness
from benchmarks.harness import clock
from benchmarks.runners.train import _build_engine
from benchmarks.runners.train_hybrid import Alone, _limits, _rel_l2, _worst_leaf
from benchmarks.runners.train_mla_moe import AT_LEAST, BIAS, biases_of, seeded_biases
from benchmarks.runners.train_ssm_moe import choice_readings, counts_of, moe_record, wide_gaps


def build_model(config):
    """The program's LFM2-MoE from the configuration's keys (published, and the share)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel
    assumed = config["assumed"]
    return Lfm2MoeModel(Lfm2MoeConfig.from_published(
        config["model"], initializer_range=assumed["initializer_range"][1],
        bias_update_rate=assumed["bias_update_rate"][1], router_eps=assumed["router_eps"][1],
        remat=config["remat"], compute_dtype=getattr(jnp, config["compute_dtype"])))


def expert_gradients(alone, mp, x, rows, seed):
    """An expert layer's gradients on the last ``rows`` positions, three readings: the worst
    relative error over the input and the held experts' arrays (``expert_layer_grad_rel``); the
    router's matrix apart (``router_grad_rel``: its gradient is the DIFFERENCE of the chosen
    experts' pulls on a token's weights, far smaller than the others', while the bf16 noise in the
    expert outputs it is made of stays: PERF.md section 6, PR 48); and the largest |gradient| that
    reaches the selection bias, in system and reference alike, which has to be exactly zero."""
    import jax
    import jax.numpy as jnp
    tail = x[None, -rows:]
    cot = jnp.asarray(np.random.default_rng(seed).standard_normal(tail.shape), jnp.float32)
    got = jax.device_get(alone.grads[0](mp, tail, cot))
    want = jax.device_get(alone.grads[1](mp, tail.astype(jnp.float32), cot))
    bias = max(np.abs(np.asarray(g[0].pop("router_bias"), np.float64)).max() for g in (got, want))
    router = _rel_l2(got[0].pop("router_w"), want[0].pop("router_w"))
    return {"expert_layer_grad_rel": _worst_leaf(got, want), "router_grad_rel": router,
            "router_bias_grad_abs_max": float(bias)}


def compare_layers(model, ref, m, eps, params, want, rows, seed, margin):
    """Every kind alone on the reference's own inputs (``want["op_in"]``, ``want["ff_in"]``
    ``[L, 1, T, H]``): the worst layer's reading of each output, and the gradients of the first
    layer of each kind. The attention's gradients are read on the FIRST layer's input whatever its
    depth (a deeper layer's rows share a direction that a softmax's gradient cancels: PERF.md
    section 6, PR 45)."""
    import jax
    import jax.numpy as jnp
    c = model.config
    dt, k = c.compute_dtype, c.num_experts_per_tok
    alone = {
        "short_conv": Alone(lambda p, x: model.short_conv(x, p), lambda p, x: ref.short_conv(x, p, m)),
        "attention": Alone(lambda p, x: model.attention(x, p), lambda p, x: ref.attention(x, p, m)),
        "dense_mlp": Alone(lambda p, x: model.dense_mlp(x, p), lambda p, x: ref.dense_mlp(x, p)),
        "expert_layer": Alone(lambda p, x: model.expert_layer(x, p)[0],
                              lambda p, x: ref.expert_layer(x[0], p, m, eps)[0][None]),
    }
    routed = jax.jit(lambda p, x: model.moe.apply(p, x, details=True)[2])
    routed_ref = jax.jit(lambda p, x: ref.router(x, p, m, eps)[::2])
    out = {"router_scores_rel": 0.0, "router_choice_agreement": 1.0, "router_wrong_choice_share": 0.0}

    def read(name, lp, x, grad_x=None):
        out[name + "_rel"] = max(out.get(name + "_rel", 0.0), alone[name].output(lp, x))
        if name + "_grad_rel" in out:
            return
        if name == "expert_layer":
            out.update(expert_gradients(alone[name], lp, x, rows, seed))
        else:
            out[name + "_grad_rel"] = alone[name].gradients(lp, x if grad_x is None else grad_x, rows, seed)

    first_input = jnp.asarray(want["op_in"][0, 0]).astype(dt)
    for l, lp in enumerate(params["layers"]):
        x = jnp.asarray(want["op_in"][l, 0]).astype(dt)
        if "conv" in lp:
            read("short_conv", lp["conv"], x)
        else:
            read("attention", lp["attn"], x, first_input)
        x = jnp.asarray(want["ff_in"][l, 0]).astype(dt)
        if "mlp" in lp:
            read("dense_mlp", lp["mlp"], x)
            continue
        read("expert_layer", lp["moe"], x)
        stats = jax.device_get(routed(lp["moe"], x[None]))
        chosen, scores = jax.device_get(routed_ref(lp["moe"], x.astype(jnp.float32)))
        got = jax.nn.sigmoid(stats["router_logits"][0])
        out["router_scores_rel"] = max(out["router_scores_rel"], float(
            np.abs(got - scores).max() / np.abs(scores).max()))
        agree, wrong = choice_readings(stats["experts"][0], np.sort(chosen, axis=-1), wide_gaps(
            scores, jax.device_get(lp["moe"]["router_bias"]), k, margin))
        out["router_choice_agreement"] = min(out["router_choice_agreement"], agree)
        out["router_wrong_choice_share"] = max(out["router_wrong_choice_share"], wrong)
    return out


def whole_model_readings(got, want, biases, k, margin):
    """The whole model's readings from the system's (or a reference at fault's) ``got`` and the
    reference's ``want``: the loss, the last logits, the experts chosen apart from the near-ties."""
    wide = wide_gaps(want["scores"], biases[:, None, None, :], k, margin)
    agree, wrong = choice_readings(got["experts"], want["experts"], wide)
    return {"train_loss_rel": abs(float(got["loss"]) - float(want["loss"])) / abs(float(want["loss"])),
            "last_logits_rel": _rel_l2(got["logits"][0], want["logits"][0]),
            "expert_agreement": agree, "expert_wrong_choice_share": wrong}, wide


def reference_forward(ctx, seq_len):
    """The reference's whole model on one sequence as ONE jitted program ``(params, tokens, labels)
    -> forward's dict``, the logits of the configuration's last positions: the comparison and the
    step's counts both call it, on different biases, and it compiles once."""
    import jax
    config = ctx["config"]
    ref = ctx["manifest"].reference(config["reference"]["module"])
    eps = config["assumed"]["router_eps"][1]
    last = min(config["reference"]["last_positions"], seq_len)
    return jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], config["model"], eps, last))


def check_reference(ctx, model, params, tokens, labels, forward=None):
    """One seeded sequence through the system and through the plain float32 reference on the same
    parameters, the selection biases seeded: the whole model (``whole_model_readings``), then
    every kind alone (``compare_layers``), which is where a lower precision shows."""
    import jax
    config = ctx["config"]
    spec, m = config["reference"], config["model"]
    ref = ctx["manifest"].reference(spec["module"])
    tol = _limits(ctx, of_the_step=False)
    last = min(spec["last_positions"], tokens.shape[0])
    rows = min(spec["grad_positions"], tokens.shape[0])
    params = seeded_biases(params, ctx["seed"])
    got = jax.device_get(jax.jit(lambda p, t, l: model.forward_details(p, t[None], l[None], last))(
        params, tokens, labels))
    want = (forward or reference_forward(ctx, tokens.shape[0]))(params, tokens, labels)
    readings = compare_layers(model, ref, m, config["assumed"]["router_eps"][1], params, want, rows,
                              ctx["seed"], spec["tie_margin"])
    want = jax.device_get({k: want[k] for k in ("loss", "logits", "experts", "scores", "counts")})
    whole, wide = whole_model_readings(got, want, biases_of(params), m["num_experts_per_tok"],
                                       spec["tie_margin_whole_model"])
    readings.update(whole)
    scores_apart = np.abs(1 / (1 + np.exp(-got["router_logits"].astype(np.float64))) - want["scores"])
    ok = bool(np.isfinite(float(got["loss"])) and set(readings) == set(tol) and all(
        readings[k] >= tol[k] if k in AT_LEAST else readings[k] <= tol[k] for k in tol))
    return dict(readings, system_loss=float(got["loss"]), reference_loss=float(want["loss"]),
                rel_diff=readings["train_loss_rel"], wide_gap_share=float(np.mean(wide)),
                scores_apart=[float(np.percentile(scores_apart, q)) for q in (50, 99, 100)],
                counts_apart_max=float(np.abs(got["counts"] - want["counts"]).max()),
                tolerances=tol, ok=ok)


def reference_counts(model, params, tokens, labels, forward):
    """For the step's check, on the parameters the engine is built from (the biases' initial
    zero): the reference's own counts ``[Le, E]``, the system's, how far apart they lie at most,
    and the reference's loss."""
    import jax
    out = forward(params, tokens, labels)
    want = jax.device_get({k: out[k] for k in ("counts", "loss")})
    del out
    got = jax.device_get(counts_of(model)(params, tokens[None]))
    return {"reference": want["counts"], "system": got, "loss": float(want["loss"]),
            "apart": float(np.abs(got - want["counts"]).max()),
            "load_max_over_mean": (want["counts"].max(axis=1) / want["counts"].mean(axis=1)).tolist()}


def check_step(ctx, engine, tokens, labels, batch_size, counts):
    """One step of the ENGINE's own compiled programs (the gradient program with its layers
    recomputed, the ZeRO-2 gradient path, the update program with the model's rule inside) on the
    sequence the reference saw; ``train_ssm_moe.check_step``'s readings. ``step_loss_rel``: the
    step's loss against the reference's. ``step_update_shortfall``: what the step took off each
    ADAM leaf of the float32 master against Adam's first step at the engine's rate, the worst
    leaf's; EVERY row of the embedding has a gradient, since the table is the head too.
    ``step_bias_abs_err``: every selection bias against the reference's ``b + u sign(mean(c) -
    c)`` on the REFERENCE's own counts; an expert whose count lies within the system's distance of
    the mean has to have moved by exactly ``+u``, ``-u`` or nothing.
    ``step_bias_moment_abs_max``: Adam's moments of the biases: zero."""
    import jax
    tol = _limits(ctx, of_the_step=True)
    rate_u = ctx["config"]["assumed"]["bias_update_rate"][1]
    ref = ctx["manifest"].reference(ctx["config"]["reference"]["module"])
    rate, = engine.get_lr()
    before = jax.device_get(engine.master_params)
    loss = engine(*(np.broadcast_to(a, (batch_size,) + a.shape) for a in (tokens, labels)))
    engine.backward(loss)
    engine.step()
    after = jax.device_get(engine.master_params)
    by_leaf = {}
    for (path, b), a in zip(jax.tree_util.tree_flatten_with_path(before)[0],
                            jax.tree_util.tree_leaves(after)):
        name = jax.tree_util.keystr(path)
        if not name.endswith(BIAS):
            by_leaf[name] = abs(float(np.linalg.norm((a - b).astype(np.float64))
                                      / (rate * np.sqrt(b.size))) - 1.0)
    worst = max(by_leaf, key=by_leaf.get)
    want = np.stack(jax.device_get(ref.updated_biases(before, counts["reference"], rate_u)))
    was, got = biases_of(before), biases_of(after)
    c = np.asarray(counts["reference"], np.float64)
    sure = np.abs(c - c.mean(axis=1, keepdims=True)) > counts["apart"]
    moved = np.abs(np.abs(got.astype(np.float64) - was) - rate_u * (got != was))
    err = np.where(sure, np.abs(got.astype(np.float64) - want), moved)
    moments = [np.abs(biases_of(field)).max() for field in jax.device_get(engine.opt_state)
               if isinstance(field, dict)]
    reference_loss = counts["loss"]
    readings = {"step_loss_rel": abs(float(loss) - reference_loss) / abs(reference_loss),
                "step_update_shortfall": by_leaf[worst],
                "step_bias_abs_err": float(err.max()),
                "step_bias_moment_abs_max": float(max(moments)) if moments else float("nan")}
    ok = bool(set(readings) == set(tol) and all(readings[k] <= tol[k] for k in tol))
    return dict(readings, worst_leaf=worst, rate=rate,
                shortfall_by_leaf={name: round(v, 4) for name, v in sorted(by_leaf.items()) if v > 0.05},
                biases_sure=int(sure.sum()), biases_near_the_mean=int((~sure).sum()),
                biases_moved=int((got != was).sum()), counts_apart_max=counts["apart"],
                load_max_over_mean_at_start=counts["load_max_over_mean"],
                reference_loss=reference_loss, tolerances=tol, ok=ok), loss


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
    forward = reference_forward(ctx, seq_len)
    reference = check_reference(ctx, model, params, *sequence, forward)
    # the engine starts from the biases' initial zero, and the step's check reads the rule on
    # the reference's own counts under those
    counts = reference_counts(model, params, *sequence, forward)
    del forward
    jax.clear_caches()           # the comparison's programs, and the constants they hold
    setup["reference_s"] = clock() - t
    t = clock()
    engine = _build_engine(ctx, model, params, batch_size)
    del params
    setup["engine_s"] = clock() - t
    t = clock()
    reference["step"], first_loss = check_step(ctx, engine, *sequence, batch_size, counts)
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
    # the expert layers' device scalars of every step the recorder still holds: fetched here,
    # after the window
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    in_window = [s for step_no, s in kept if step_no >= first_step]

    steps = len(losses)
    window_s = t1 - t0
    tokens_per_step = batch_size * seq_len
    rate_chip = steps * tokens_per_step / window_s / chips
    intervals_ms = (np.diff([t0] + returns) * 1e3).tolist()
    bad = sum(not np.isfinite(x) for x in losses) + int(engine.skipped_steps)
    fell = float(np.mean(losses[-10:])) < first_loss
    correct = bool(bad == 0 and fell and window_compiles == 0 and reference["ok"]
                   and reference["step"]["ok"] and len(in_window) > 0)

    moe = moe_record(in_window, tokens_per_step, chips, m["num_experts_per_tok"])
    peak = harness.memory_peak_bytes(ctx["devices"])
    memory = {k: v for k, v in (ctx["devices"][0].memory_stats() or {}).items()
              if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_alloc_size")}
    harness.write_record(ctx["out_dir"], cell["name"], ctx["seed"], {
        "cell": cell["name"], "seed": ctx["seed"], "traced": tr.on, "window_s": window_s,
        "fence_ms": (t1 - returns[-1]) * 1e3, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s], "losses": losses,
        "warm_losses": [float(x) for x in jax.device_get(warm_losses)],
        "setup": setup, "reference": reference, "moe": moe, "memory": memory,
        "memory_peak_bytes": peak, "tokens_per_s_chip": rate_chip})
    step_ms, stall_ms = harness.step_profile(intervals_ms)
    harness.summary_line("step_return_interval", intervals_ms, step_ms_median=step_ms,
                         longest_stall_ms=stall_ms, fence_ms=(t1 - returns[-1]) * 1e3,
                         first_losses=[float(x) for x in jax.device_get(warm_losses[:5])],
                         window_last_loss=losses[-1], reference=reference, setup=setup,
                         warm_steps=len(warm_losses), window_compiles=window_compiles, moe=moe,
                         memory=memory, memory_peak_bytes=peak)

    return {
        "correct": correct, "attempted": steps, "failed": bad,
        "t_window_start": t0, "window_s": window_s, "setup": setup,
        "end_to_end": {"train_tokens_per_s_chip": rate_chip},
        "memory_peak_bytes": peak,
        # what the per-layer readers take their numbers from: ``model`` is what the flash readers
        # know a model by (``flops.flash_required``: two whole triangles at 32 heads of 64),
        # ``conv_moe_model`` the configuration's own keys (``flops_conv_moe``), ``recomputed``
        # whether a step runs a second forward (the gated convolution's bytes count it)
        "kind": "train", "chips": chips, "steps": steps, "tokens_per_step": tokens_per_step,
        "batch_per_chip": cell["micro_batch_per_chip"], "seq_len": seq_len,
        "tokens_per_s_chip": rate_chip, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s],
        "model": flops_conv_moe.flash_sizes(m), "conv_moe_model": m, "recomputed": config["remat"],
        "vocab": m["vocab_size"], "moe": moe,
    }
