"""A training cell of a model of latent-attention blocks INSIDE hyper-connections (four residual
streams mixed round every sub-layer; a leading dense block, then expert blocks;
``deepspeed_tpu/models/xing_moe.py``) as one chip's share of a wider deployment: the engine's own
``forward``/``backward``/``step`` on packed documents with whole blocks recomputed, as
``runners/train_mla_moe.py`` measures GLM-4.7-Flash, whose expert-layer and bias readings this
runner takes as they stand. In set-up one seeded sequence goes through the system and through the
configuration's plain reference on the same parameters, the selection biases SEEDED: the whole
model (the loss, the last logits, the experts chosen), and each kind ALONE on the reference's own
inputs (a sub-layer's hyper-connection round the identity and how far from doubly stochastic its 20
rounds leave ``H_res``, the latent mixer, the dense MLP, the expert layer with its
shared expert and router; outputs over the sequence, gradients on its last positions). Everything
the comparison held is dropped before the engine builds its state. The process's first step then
runs the ENGINE's own compiled programs on that sequence (``check_step``): its loss against the
reference's, what it took off every Adam leaf against Adam's first step, and every selection bias
against the reference's rule on the reference's own counts. After the window the device scalars
are fetched: the expert layers', and ``H_res``'s two readings for all ten sub-layers."""

import numpy as np

from benchmarks import harness
from benchmarks.harness import clock
from benchmarks.runners.train import _build_engine
from benchmarks.runners.train_hybrid import Alone, _limits, _rel_l2
from benchmarks.runners.train_mla_moe import (AT_LEAST, BIAS, biases_of, expert_gradients,
                                              reference_counts, seeded_biases)
from benchmarks.runners.train_ssm_moe import choice_readings, wide_gaps
from benchmarks.runners.train_swa_moe import moe_record

HC_SCALARS = ("hc_res_err_max", "hc_res_diag_mean")
# seeded cotangents under which a hyper-connection's gradients are read (``gradients_by_leaf``)
GRADIENT_DRAWS = 16


def build_model(config):
    """The program's Xing4.0 from the configuration's keys (published, and the share)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.xing_moe import XingMoeConfig, XingMoeModel
    assumed = config["assumed"]
    return XingMoeModel(XingMoeConfig.from_published(
        config["model"], initializer_range=assumed["initializer_range"][1],
        bias_update_rate=assumed["bias_update_rate"][1], remat=config["remat"],
        compute_dtype=getattr(jnp, config["compute_dtype"])))


def no_gradient_by_structure(name, blocks):
    """Whether the leaf ``name`` has no gradient whatever the data. The FIRST sub-layer reads
    streams that are all the embedding, so its ``H_pre`` only scales what an RMSNorm reads
    (``phi_pre``, ``b_pre``); the LAST one's streams are summed, and ``H_res``'s columns sum to one
    (``phi_res``, ``b_res``). What rounding leaves there lies under Adam's epsilon."""
    first = [f"['layers'][0]['hc_attn']['{leaf}']" for leaf in ("phi_pre", "b_pre")]
    last = [f"['layers'][{blocks - 1}]['hc_mlp']['{leaf}']" for leaf in ("phi_res", "b_res")]
    return name in first + last


def gradients_by_leaf(alone, params, x, rows, draws):
    """``Alone.gradients``' reading of every leaf under several seeded cotangents TOGETHER (``draws``:
    a generator's seed each): ``sqrt(sum_d |g_sys - g_ref|^2 / sum_d |g_ref|^2)``, the relative
    Frobenius error of the leaf's gradients stacked, by the leaf's name. Why not one cotangent: a
    hyper-connection has leaves of THREE numbers (the gates) and four (``b_pre``, ``b_post``), each
    the sum over the positions of terms whose signs follow the cotangent. Under one draw such a sum
    lands near zero now and then while the bf16 rounding it carries does not, and the ratio of the
    two has a heavy tail: of nineteen seeds on the chip two read 0.018 (``b_pre``) and 0.036 (the
    gates) where every leaf reads 0.003 under sixteen draws (PERF.md section 6, PR 58), and the
    driver's check drew the second. Sixteen draws put 48 terms under the smallest leaf's ratio; the
    large leaves read what they read under one."""
    import jax
    import jax.numpy as jnp
    tail = x[None, -rows:]
    apart = jax.jit(lambda got, want: jax.tree_util.tree_map(
        lambda g, w: jnp.stack([jnp.sum(jnp.square(g.astype(jnp.float32) - w)), jnp.sum(jnp.square(w))]), got, want))
    sums = None
    for draw in draws:
        cot = jnp.asarray(np.random.default_rng(draw).standard_normal(tail.shape), jnp.float32)
        one = jax.device_get(apart(alone.grads[0](params, tail, cot),
                                   alone.grads[1](params, tail.astype(jnp.float32), cot)))
        one = {jax.tree_util.keystr(path): np.asarray(v, np.float64)
               for path, v in jax.tree_util.tree_flatten_with_path(one)[0]}
        sums = one if sums is None else {name: sums[name] + v for name, v in one.items()}
    return {name: float(np.sqrt(err / norm)) for name, (err, norm) in sums.items()}


def compare_layers(model, ref, m, params, want, rows, seed, margin):
    """Every kind alone on the reference's own inputs (``want["hc_in"]`` ``[2 L, 1, T, n, C]``,
    ``want["attn_in"]``, ``want["mlp_in"]`` ``[L, 1, T, H]``): the worst sub-layer's or block's
    reading of each output; ``hc_res_err_rel``, the worst sub-layer's ``| the system's largest |row
    or column sum - 1| of H_res over the tokens - the reference's | / the reference's`` (Sinkhorn-
    Knopp's own distance from its fixed point after the published rounds: fewer rounds, or
    coefficients in half the mantissa, leave another); and the gradients of one of each kind: the hyper-connection of block
    1's attention (block 0's first reads four copies of one stream) under ``GRADIENT_DRAWS``
    cotangents together (``gradients_by_leaf``; ``out["by_leaf"]`` keeps every leaf's), the mixer on the FIRST
    block's input whatever its depth (PERF.md section 6, PR 45), block 0's dense MLP, block 1's
    expert layer."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import hyper_connections as hc
    c = model.config
    dt, k, n = c.compute_dtype, c.num_experts_per_tok, c.hc_mult
    streams = lambda x: x.reshape(x.shape[:-1] + (n, -1))       # noqa: E731
    alone = {
        # a sub-layer's hyper-connection round the identity: H_res X + H_post (sum_i H_pre[i] X[i])
        "hyper_connection": Alone(lambda p, x: model.connected(x, p, lambda u: (u, {}))[0],
                                  lambda p, x: ref.connected(streams(x), p, m, lambda u: u)[0].reshape(x.shape)),
        "latent_attention": Alone(lambda p, x: model.attention(x, p), lambda p, x: ref.attention(x, p, m)),
        "dense_mlp": Alone(lambda p, x: model.dense_mlp(x, p), lambda p, x: ref.dense_mlp(x, p)),
        "expert_layer": Alone(lambda p, x: model.expert_layer(x, p)[0],
                              lambda p, x: ref.expert_layer(x[0], p, m)[0][None]),
    }
    # how far from doubly stochastic the 20 rounds leave H_res, on the same streams: the system's
    # own device scalar against the reference's matrix
    left = jax.jit(lambda p, x: hc.readings(model.coefficients(x, p)[2])["hc_res_err_max"])
    left_ref = jax.jit(lambda p, x: jnp.maximum(*(jnp.max(jnp.abs(jnp.sum(
        ref.coefficients(streams(x.astype(jnp.float32))[None], p, m)[2], axis=axis) - 1.0)) for axis in (-1, -2))))
    routed = jax.jit(lambda p, x: model.moe.apply(p, x, details=True)[2])
    routed_ref = jax.jit(lambda p, x: ref.router(x, p, m)[::2])
    out = {"router_scores_rel": 0.0, "router_choice_agreement": 1.0, "router_wrong_choice_share": 0.0,
           "hc_res_err_rel": 0.0}

    def read(name, lp, x, gradients=True, grad_x=None):
        out[name + "_rel"] = max(out.get(name + "_rel", 0.0), alone[name].output(lp, x))
        if not gradients or name + "_grad_rel" in out:
            return
        if name == "expert_layer":
            out.update(expert_gradients(alone[name], lp, x, rows, seed))
        elif name == "hyper_connection":
            out["by_leaf"] = gradients_by_leaf(alone[name], lp, x, rows, [(seed, d) for d in range(GRADIENT_DRAWS)])
            out[name + "_grad_rel"] = max(out["by_leaf"].values())
        else:
            out[name + "_grad_rel"] = alone[name].gradients(lp, x if grad_x is None else grad_x, rows, seed)

    flat = lambda s: jnp.asarray(want["hc_in"][s, 0]).reshape(want["hc_in"].shape[2], -1).astype(dt)   # noqa: E731
    first_input = jnp.asarray(want["attn_in"][0, 0]).astype(dt)
    for l, lp in enumerate(params["layers"]):
        read("hyper_connection", lp["hc_attn"], flat(2 * l), gradients=l == 1)
        read("hyper_connection", lp["hc_mlp"], flat(2 * l + 1), gradients=False)
        for s, hp in ((2 * l, lp["hc_attn"]), (2 * l + 1, lp["hc_mlp"])):
            got, theirs = float(left(hp, flat(s)[None])), float(left_ref(hp, flat(s)))
            out["hc_res_err_rel"] = max(out["hc_res_err_rel"], abs(got - theirs) / theirs)
        read("latent_attention", lp["attn"], jnp.asarray(want["attn_in"][l, 0]).astype(dt), grad_x=first_input)
        x = jnp.asarray(want["mlp_in"][l, 0]).astype(dt)
        if "mlp" in lp:
            read("dense_mlp", lp["mlp"], x)
            continue
        read("expert_layer", {"moe": lp["moe"], "shared": lp["shared"]}, x)
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
    last = min(config["reference"]["last_positions"], seq_len)
    return jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], config["model"], last))


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
    readings = compare_layers(model, ref, m, params, want, rows, ctx["seed"], spec["tie_margin"])
    by_leaf = readings.pop("by_leaf")
    want = jax.device_get({k: want[k] for k in ("loss", "logits", "experts", "scores", "counts") + HC_SCALARS})
    whole, wide = whole_model_readings(got, want, biases_of(params), m["num_experts_per_tok"],
                                       spec["tie_margin_whole_model"])
    readings.update(whole)
    scores_apart = np.abs(1 / (1 + np.exp(-got["router_logits"].astype(np.float64))) - want["scores"])
    ok = bool(np.isfinite(float(got["loss"])) and set(readings) == set(tol) and all(
        readings[k] >= tol[k] if k in AT_LEAST else readings[k] <= tol[k] for k in tol))
    return dict(readings, system_loss=float(got["loss"]), reference_loss=float(want["loss"]),
                rel_diff=readings["train_loss_rel"], wide_gap_share=float(np.mean(wide)),
                hyper_connection_grad_by_leaf=by_leaf,
                hc={"system": {k: np.asarray(got[k]).reshape(-1).tolist() for k in HC_SCALARS},
                    "reference": {k: np.asarray(want[k]).reshape(-1).tolist() for k in HC_SCALARS}},
                scores_apart=[float(np.percentile(scores_apart, q)) for q in (50, 99, 100)],
                counts_apart_max=float(np.abs(got["counts"] - want["counts"]).max()),
                tolerances=tol, ok=ok)


def check_step(ctx, engine, tokens, labels, batch_size, counts):
    """One step of the ENGINE's own compiled programs (the gradient program with its blocks
    recomputed, the ZeRO-2 gradient path, the update program with the model's rule inside) on the
    sequence the reference saw; ``train_mla_moe.check_step``'s four readings and one more.
    ``step_loss_rel``: the step's loss against the reference's. ``step_update_shortfall``: what the
    step took off each ADAM leaf of the float32 master OUTSIDE the hyper-connections against Adam's
    first step at the engine's rate, the worst leaf's; the embedding's rows that have a gradient are
    those of the sequence's tokens. ``step_hc_moved_share`` (a LOWER bound): the share of the
    hyper-connections' elements that the step changed at all, the leaves that have no gradient by
    structure left out: behind gates of 0.01 and Sinkhorn-Knopp's invariance to a row's or a
    column's scale their gradients lie round Adam's epsilon, where a first step is no whole rate
    (their values are compared in ``hyper_connection_grad_rel``). ``step_bias_abs_err``: every selection
    bias against the reference's ``b + u sign(mean(c) - c)`` on the REFERENCE's own counts; an
    expert whose count lies within the system's distance of the mean has to have moved by exactly
    ``+u``, ``-u`` or nothing. ``step_bias_moment_abs_max``: Adam's moments of the biases: zero."""
    import jax
    tol = _limits(ctx, of_the_step=True)
    rate_u = ctx["config"]["assumed"]["bias_update_rate"][1]
    ref = ctx["manifest"].reference(ctx["config"]["reference"]["module"])
    blocks = ctx["config"]["model"]["num_hidden_layers"]
    rate, = engine.get_lr()
    before = jax.device_get(engine.master_params)
    loss = engine(*(np.broadcast_to(a, (batch_size,) + a.shape) for a in (tokens, labels)))
    engine.backward(loss)
    engine.step()
    after = jax.device_get(engine.master_params)
    seen = len(np.unique(np.asarray(tokens)))
    by_leaf, hc_moved, hc_size = {}, 0, 0
    for (path, b), a in zip(jax.tree_util.tree_flatten_with_path(before)[0],
                            jax.tree_util.tree_leaves(after)):
        name = jax.tree_util.keystr(path)
        if name.endswith(BIAS) or no_gradient_by_structure(name, blocks):
            continue
        if "['hc_" in name:
            hc_moved, hc_size = hc_moved + int(np.sum(a != b)), hc_size + b.size
            continue
        moved = seen * b.shape[1] if name == "['embed']" else b.size
        by_leaf[name] = abs(float(np.linalg.norm((a - b).astype(np.float64))
                                  / (rate * np.sqrt(moved))) - 1.0)
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
                "step_hc_moved_share": hc_moved / hc_size,
                "step_bias_abs_err": float(err.max()),
                "step_bias_moment_abs_max": float(max(moments)) if moments else float("nan")}
    ok = bool(set(readings) == set(tol) and all(
        readings[k] >= tol[k] if k == "step_hc_moved_share" else readings[k] <= tol[k] for k in tol))
    return dict(readings, worst_leaf=worst, rate=rate, tokens_seen=seen,
                biases_sure=int(sure.sum()), biases_near_the_mean=int((~sure).sum()),
                biases_moved=int((got != was).sum()), counts_apart_max=counts["apart"],
                load_max_over_mean_at_start=counts["load_max_over_mean"],
                reference_loss=reference_loss, tolerances=tol, ok=ok), loss


def hc_record(in_window, sub_layers):
    """What the hyper-connections' device scalars of the window's steps say, a sub-layer a value:
    the largest ``hc_res_err_max`` and the mean ``hc_res_diag_mean`` over the steps; ``complete``
    where every step reported every sub-layer."""
    if not in_window:
        return {"res_err_max_by_sub_layer": None, "res_diag_mean_by_sub_layer": None, "complete": False}
    err = np.stack([np.asarray(s["hc_res_err_max"]) for s in in_window])          # [steps, 2 L]
    diag = np.stack([np.asarray(s["hc_res_diag_mean"]) for s in in_window])
    return {"res_err_max_by_sub_layer": err.max(axis=0).tolist(),
            "res_diag_mean_by_sub_layer": diag.mean(axis=0).tolist(),
            "complete": bool(err.shape[1] == diag.shape[1] == sub_layers and np.all(np.isfinite(err)))}


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
    # the device scalars of every step the recorder still holds (the expert layers', the
    # hyper-connections'): fetched here, after the window
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    in_window = [s for step_no, s in kept if step_no >= first_step]

    steps = len(losses)
    window_s = t1 - t0
    tokens_per_step = batch_size * seq_len
    rate_chip = steps * tokens_per_step / window_s / chips
    intervals_ms = (np.diff([t0] + returns) * 1e3).tolist()
    bad = sum(not np.isfinite(x) for x in losses) + int(engine.skipped_steps)
    fell = float(np.mean(losses[-10:])) < first_loss
    moe = moe_record(in_window, tokens_per_step, chips, m["num_experts_per_tok"])
    hc = hc_record(in_window, 2 * m["num_hidden_layers"])
    correct = bool(bad == 0 and fell and window_compiles == 0 and reference["ok"]
                   and reference["step"]["ok"] and hc["complete"])

    peak = harness.memory_peak_bytes(ctx["devices"])
    memory = {k: v for k, v in (ctx["devices"][0].memory_stats() or {}).items()
              if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_alloc_size")}
    harness.write_record(ctx["out_dir"], cell["name"], ctx["seed"], {
        "cell": cell["name"], "seed": ctx["seed"], "traced": tr.on, "window_s": window_s,
        "fence_ms": (t1 - returns[-1]) * 1e3, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s], "losses": losses,
        "warm_losses": [float(x) for x in jax.device_get(warm_losses)],
        "setup": setup, "reference": reference, "moe": moe, "hc": hc, "memory": memory,
        "memory_peak_bytes": peak, "tokens_per_s_chip": rate_chip})
    step_ms, stall_ms = harness.step_profile(intervals_ms)
    harness.summary_line("step_return_interval", intervals_ms, step_ms_median=step_ms,
                         longest_stall_ms=stall_ms, fence_ms=(t1 - returns[-1]) * 1e3,
                         first_losses=[float(x) for x in jax.device_get(warm_losses[:5])],
                         window_last_loss=losses[-1], reference=reference, setup=setup,
                         warm_steps=len(warm_losses), window_compiles=window_compiles, moe=moe, hc=hc,
                         memory=memory, memory_peak_bytes=peak)

    return {
        "correct": correct, "attempted": steps, "failed": bad,
        "t_window_start": t0, "window_s": window_s, "setup": setup,
        "end_to_end": {"train_tokens_per_s_chip": rate_chip},
        "memory_peak_bytes": peak,
        # what the per-layer readers take their numbers from: ``hc_moe_model`` is the
        # configuration's own keys (``flops_hc_moe``); no reader of ``flops.flash_required`` is
        # joined (one width), so no ``model`` is handed
        "kind": "train", "chips": chips, "steps": steps, "tokens_per_step": tokens_per_step,
        "batch_per_chip": cell["micro_batch_per_chip"], "seq_len": seq_len,
        "tokens_per_s_chip": rate_chip, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s],
        "hc_moe_model": m, "recomputed": config["remat"], "vocab": m["vocab_size"], "moe": moe, "hc": hc,
    }
