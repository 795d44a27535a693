"""A training cell of a hybrid state-space expert model (``deepspeed_tpu/models/nemotron_h.py``)
as one chip's share of a wider deployment: the engine's own ``forward``/``backward``/``step``
on packed documents with whole layers recomputed, as ``runners/train_ssm.py`` measures
Granite and ``runners/train_hybrid.py`` Qwen3-Next. In set-up one seeded sequence goes
through the system and through the configuration's plain reference on the same parameters:
the whole model (loss, last logits, the experts chosen), and each kind of layer ALONE on the
reference's own inputs (the Mamba-2 mixer, the grouped scan itself on float32 and on bfloat16
operands, the position-free attention, the expert layer and its router; outputs over the
sequence, gradients on its last positions, the selection bias's exactly zero). Everything
the comparison held is dropped before the engine builds its state. The process's first step
then runs the ENGINE's own compiled programs on that sequence (``check_step``): its loss
against the reference's, what it took off every Adam leaf of the master against Adam's first
step, and every selection bias against the reference's own rule on the reference's own
counts, its moments untouched. After the window the expert layers' device scalars are
fetched.

Expert choices. A router that scores 128 experts leaves many a token's sixth and seventh
close together, and the system's bf16 rows move a score by more than that gap: such a pair
disagrees without a fault. A (token, layer) pair counts as a WRONG choice only where the
reference's gap between its sixth and seventh ``s + b`` is wider than a margin and the choices
still differ: ``tie_margin`` for a router ALONE on the reference's own input (float32 on both
sides), ``tie_margin_whole_model`` inside the whole model, where the system's rows are bf16
(both in the configuration's ``reference`` block)."""

import numpy as np

from benchmarks import harness
from benchmarks.harness import clock
from benchmarks.runners.train import _build_engine
from benchmarks.runners.train_hybrid import Alone, _limits, _rel_l2, _worst_leaf
from benchmarks.runners.train_ssm import ScanAlone

AT_LEAST = ("expert_agreement", "router_choice_agreement")
BIAS_SPREAD = 0.05     # the spread of the seeded selection biases the comparisons are made under


def seeded_biases(params, seed):
    """``params`` with every selection bias drawn N(0, ``BIAS_SPREAD``) from the seed in place
    of its initial zero, for the comparisons (system and reference read the same tree): a bias
    left out of the choice, or let into the weights, has to show. Never the engine's."""
    import jax
    layers = []
    for l, lp in enumerate(params["layers"]):
        if "moe" in lp:
            key = jax.random.fold_in(harness.seed_key(seed), l)
            bias = BIAS_SPREAD * jax.random.normal(key, lp["moe"]["router_bias"].shape)
            lp = dict(lp, moe=dict(lp["moe"], router_bias=bias))
        layers.append(lp)
    return dict(params, layers=layers)


def counts_of(model):
    """``(float32 params, tokens [B, T]) -> [Le, E]``: the system's own counts, the parameters
    as a step reads them: the weights cast to the compute dtype, the selection biases as the
    master holds them (the engine's compute copy keeps a rule-updated leaf exact)."""
    import jax
    dtype = model.config.compute_dtype

    def as_a_step_reads(path, a):
        return a if jax.tree_util.keystr(path).endswith("['router_bias']") else a.astype(dtype)
    return jax.jit(lambda p, t: model.expert_counts(
        jax.tree_util.tree_map_with_path(as_a_step_reads, p), t))


def build_model(config):
    """The program's Nemotron-H from the configuration's keys (published, and the share)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel
    assumed = config["assumed"]
    return NemotronHModel(NemotronHConfig.from_published(
        config["model"], initializer_range=assumed["initializer_range"][1],
        bias_update_rate=assumed["bias_update_rate"][1], remat=config["remat"],
        compute_dtype=getattr(jnp, config["compute_dtype"])))


def ssm_keys(m):
    """The model under the key names ``flops_ssm.ssd_scan_required`` reads (Granite's)."""
    kinds = ["mamba" if k == "M" else "other" for k in m["hybrid_override_pattern"]]
    return {"mamba_n_heads": m["mamba_num_heads"], "mamba_d_head": m["mamba_head_dim"],
            "mamba_d_state": m["ssm_state_size"], "mamba_n_groups": m["n_groups"],
            "layer_types": kinds, "num_hidden_layers": m["num_hidden_layers"]}


def flash_sizes(m):
    """The three GPT-2 names ``flops.flash_required`` reads, such that it counts exactly
    this model's softmax attention: its attention layers, ``heads x head_dim`` wide."""
    kinds = m["hybrid_override_pattern"][:m["num_hidden_layers"]]
    return {"n_embd": m["num_attention_heads"] * m["head_dim"], "n_layer": kinds.count("*"),
            "n_head": m["num_attention_heads"]}


def wide_gaps(scores, bias, k, margin):
    """``[..]`` bool: where the gap between the k-th and the (k + 1)-th largest of
    ``scores + bias`` ``[.., E]`` is wider than ``margin``: a choice that rounding cannot flip."""
    ranked = np.sort(np.asarray(scores, np.float64) + np.asarray(bias, np.float64), axis=-1)
    return ranked[..., -k] - ranked[..., -k - 1] > margin


def choice_readings(got, want, wide):
    """``(agreement, wrong)``: the share of tokens whose sorted choices ``[.., k]`` are the
    same, and the share that differ although the reference's gap was ``wide``."""
    same = np.all(np.asarray(got) == np.asarray(want), axis=-1)
    return float(np.mean(same)), float(np.mean(~same & wide))


def scan_bf16_rel(scan, mp, x):
    """The grouped scan on bfloat16 OPERANDS (what a step hands it) against the reference's
    recurrence on the same values in float32: the output's relative error."""
    import jax
    import jax.numpy as jnp
    args = scan.inputs(mp, x[None])
    narrow = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a for i, a in enumerate(args))
    return _rel_l2(jax.device_get(scan.fns[0](*narrow)).astype(np.float32),
                   jax.device_get(scan.fns[1](*args)))


def compare_layers(model, ref, m, params, want, rows, seed, margin):
    """Every kind of layer alone on the reference's own inputs (``want["layer_in"]``
    ``[L, 1, T, H]``): the worst layer's reading of each output, and the gradients of the
    first layer of each kind."""
    import jax
    import jax.numpy as jnp
    c = model.config
    dt, k = c.compute_dtype, c.num_experts_per_tok
    kinds = {
        "mixer": Alone(lambda p, x: model.mamba_mixer(x, p), lambda p, x: ref.mamba_mixer(x, p, m)),
        "attention": Alone(lambda p, x: model.attention(x, p), lambda p, x: ref.attention(x, p, m)),
        "expert_layer": Alone(lambda p, x: model.expert_layer(x, p)[0],
                              lambda p, x: ref.expert_layer(x[0], p, m)[0][None]),
    }
    scan = ScanAlone(ref, m, dt, c.chunk_size)
    routed = jax.jit(lambda p, x: model.moe.apply(p, x, details=True)[2])
    routed_ref = jax.jit(lambda p, x: ref.router(x, p, m)[::2])
    out = {"scan_rel": 0.0, "scan_bf16_rel": 0.0, "router_scores_rel": 0.0,
           "router_choice_agreement": 1.0, "router_wrong_choice_share": 0.0}
    names = {"M": "mixer", "*": "attention", "E": "expert_layer"}
    for kind, lp, x in zip(c.kinds, params["layers"], want["layer_in"]):
        x = jnp.asarray(x[0]).astype(dt)
        name = names[kind]
        lp = {"moe": lp["moe"], "shared": lp["shared"]} if kind == "E" else lp["mixer"]
        out[name + "_rel"] = max(out.get(name + "_rel", 0.0), kinds[name].output(lp, x))
        if name + "_grad_rel" not in out:
            out[name + "_grad_rel"] = gradients_alone(kinds[name], lp, x, rows, seed, out)
        if kind == "M":
            y, g = scan.read(lp, x, 0 if "scan_grad_rel" in out else rows, seed)
            out["scan_rel"] = max(out["scan_rel"], y)
            out["scan_bf16_rel"] = max(out["scan_bf16_rel"], scan_bf16_rel(scan, lp, x))
            out.setdefault("scan_grad_rel", g)
        if kind == "E":
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


def gradients_alone(alone, params, x, rows, seed, out):
    """``Alone.gradients`` for a layer that may hold a selection bias: no gradient reaches
    it, in system and reference alike, so that leaf is read on its own
    (``router_bias_grad_abs_max``, which has to be exactly zero) and left out of the
    relative errors."""
    import jax
    import jax.numpy as jnp
    tail = x[None, -rows:]
    cot = jnp.asarray(np.random.default_rng(seed).standard_normal(tail.shape), jnp.float32)
    got = jax.device_get(alone.grads[0](params, tail, cot))
    want = jax.device_get(alone.grads[1](params, tail.astype(jnp.float32), cot))
    if "moe" in got[0]:
        bias = [np.abs(np.asarray(g[0]["moe"].pop("router_bias"), np.float64)).max() for g in (got, want)]
        out["router_bias_grad_abs_max"] = float(max(bias))
    return _worst_leaf(got, want)


def check_reference(ctx, model, params, tokens, labels):
    """One seeded sequence through the system and through the plain float32 reference on the
    same parameters, the selection biases seeded (``seeded_biases``): the whole model (the
    loss, the logits of the last positions, the experts chosen a layer: agreement, and the
    wrong choices apart from the near-ties), then every kind of layer alone
    (``compare_layers``), which is where a lower precision shows. Returns the readings."""
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
    want = jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], m, last))(params, tokens, labels)
    readings = compare_layers(model, ref, m, params, want, rows, ctx["seed"], spec["tie_margin"])
    want = jax.device_get({k: want[k] for k in ("loss", "logits", "experts", "scores", "counts")})
    biases = np.stack([jax.device_get(lp["moe"]["router_bias"]) for lp in params["layers"] if "moe" in lp])
    wide = wide_gaps(want["scores"], biases[:, None, None, :], m["num_experts_per_tok"],
                     spec["tie_margin_whole_model"])
    scores_apart = np.abs(1 / (1 + np.exp(-got["router_logits"].astype(np.float64))) - want["scores"])
    agree, wrong = choice_readings(got["experts"], want["experts"], wide)
    readings.update(
        train_loss_rel=abs(float(got["loss"]) - float(want["loss"])) / abs(float(want["loss"])),
        last_logits_rel=_rel_l2(got["logits"][0], want["logits"][0]),
        expert_agreement=agree, expert_wrong_choice_share=wrong)
    ok = bool(np.isfinite(float(got["loss"])) and set(readings) == set(tol) and all(
        readings[k] >= tol[k] if k in AT_LEAST else readings[k] <= tol[k] for k in tol))
    return dict(readings, system_loss=float(got["loss"]), reference_loss=float(want["loss"]),
                rel_diff=readings["train_loss_rel"], wide_gap_share=float(np.mean(wide)),
                scores_apart=[float(np.percentile(scores_apart, q)) for q in (50, 99, 100)],
                counts_apart_max=float(np.abs(got["counts"] - want["counts"]).max()),
                tolerances=tol, ok=ok)


def reference_counts(ctx, model, params, tokens, labels):
    """For the step's check, on the parameters the engine is built from: the reference's own
    counts ``[Le, E]``, the system's, how far apart they lie at most, and the reference's loss."""
    import jax
    m = ctx["config"]["model"]
    ref = ctx["manifest"].reference(ctx["config"]["reference"]["module"])
    want = jax.device_get(jax.jit(lambda p, t, l: {
        k: v for k, v in ref.forward(p, t[None], l[None], m, 1).items() if k in ("counts", "loss")})(
        params, tokens, labels))
    got = jax.device_get(counts_of(model)(params, tokens[None]))
    return {"reference": want["counts"], "system": got, "loss": float(want["loss"]),
            "apart": float(np.abs(got - want["counts"]).max()),
            "load_max_over_mean": (want["counts"].max(axis=1) / want["counts"].mean(axis=1)).tolist()}


def check_step(ctx, engine, tokens, labels, batch_size, counts):
    """One step of the ENGINE's own compiled programs (the gradient program with its layers
    recomputed, the ZeRO-2 gradient path, the update program with the model's rule inside) on
    the sequence the reference saw. ``step_loss_rel``: the step's loss against the
    reference's. ``step_update_shortfall``: what the step took off each ADAM leaf of the
    float32 master against Adam's first step at the engine's rate (``train_hybrid.check_step``
    has the form), the worst leaf's. ``step_bias_abs_err``: every selection bias against the
    reference's ``b + u sign(mean(c) - c)`` from the REFERENCE's own counts; an expert whose
    reference count lies within the system's distance of the mean (``counts["apart"]``: the
    sign is then not the reference's to give) has to have moved by exactly ``+u``, ``-u`` or
    nothing instead. ``step_bias_moment_abs_max``: Adam's moments of the biases, which stay
    what they were: zero."""
    import jax
    tol = _limits(ctx, of_the_step=True)
    m, rate_u = ctx["config"]["model"], ctx["config"]["assumed"]["bias_update_rate"][1]
    ref = ctx["manifest"].reference(ctx["config"]["reference"]["module"])
    rate, = engine.get_lr()
    before = jax.device_get(engine.master_params)
    loss = engine(*(np.broadcast_to(a, (batch_size,) + a.shape) for a in (tokens, labels)))
    engine.backward(loss)
    engine.step()
    after = jax.device_get(engine.master_params)
    seen = len(np.unique(np.asarray(tokens)))
    by_leaf = {}
    for (path, b), a in zip(jax.tree_util.tree_flatten_with_path(before)[0],
                            jax.tree_util.tree_leaves(after)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            continue
        moved = b.size if name != "['embed']" else seen * b.shape[1]
        by_leaf[name] = abs(float(np.linalg.norm((a - b).astype(np.float64))
                                  / (rate * np.sqrt(moved))) - 1.0)
    worst = max(by_leaf, key=by_leaf.get)
    # the rule, by the reference, on the reference's counts (this step is on one sequence's
    # copies: the counts of ``batch_size`` copies are the one's times ``batch_size``)
    want = np.stack(jax.device_get(ref.updated_biases(before, counts["reference"], m, rate_u)))
    was, got = (np.stack([lp["moe"]["router_bias"] for lp in tree["layers"] if "moe" in lp])
                for tree in (before, after))
    c = np.asarray(counts["reference"], np.float64)
    sure = np.abs(c - c.mean(axis=1, keepdims=True)) > counts["apart"]
    moved = np.abs(np.abs(got.astype(np.float64) - was) - rate_u * (got != was))
    err = np.where(sure, np.abs(got.astype(np.float64) - want), moved)
    moments = [np.abs(np.asarray(lp["moe"]["router_bias"])).max()
               for field in jax.device_get(engine.opt_state) if isinstance(field, dict)
               for lp in field["layers"] if "moe" in lp]
    reference_loss = counts["loss"]
    readings = {"step_loss_rel": abs(float(loss) - reference_loss) / abs(reference_loss),
                "step_update_shortfall": by_leaf[worst],
                "step_bias_abs_err": float(err.max()),
                "step_bias_moment_abs_max": float(max(moments)) if moments else float("nan")}
    ok = bool(set(readings) == set(tol) and all(readings[k] <= tol[k] for k in tol))
    return dict(readings, worst_leaf=worst, rate=rate, tokens_seen=seen,
                biases_sure=int(sure.sum()), biases_near_the_mean=int((~sure).sum()),
                biases_moved=int((got != was).sum()), counts_apart_max=counts["apart"],
                load_max_over_mean_at_start=counts["load_max_over_mean"],
                bias_abs_max_at_start=float(np.abs(was).max()), reference_loss=reference_loss,
                tolerances=tol, ok=ok), loss


def moe_record(in_window, tokens_per_step, chips, k):
    """What the expert layers' device scalars of the window's steps say: the means over the
    window, and the first and the last five steps' apart (the router learns, and the rule
    moves the bias: how far the rows on held experts drift inside a window)."""
    moe = {"steps_counted": len(in_window), "load_max_over_mean_by_layer": None,
           "load_max_over_mean": None, "rows_here_by_layer": None, "rows_here_per_token": None,
           "rows_here_share": None, "bias_abs_max": None, "at_start": None, "at_end": None}
    if not in_window:
        return moe
    load = np.stack([s["moe_load_max_over_mean"] for s in in_window])          # [steps, layers]
    rows = np.stack([s["moe_rows_here"] for s in in_window])
    bias = np.stack([s["moe_bias_abs_max"] for s in in_window])
    share = lambda r: float(r.mean() / (tokens_per_step / chips * k))           # noqa: E731
    ends = {name: {"rows_here_share": share(rows[part]),
                   "load_max_over_mean": float(load[part].max(axis=1).mean()),
                   "bias_abs_max": float(bias[part].max())}
            for name, part in (("at_start", slice(0, 5)), ("at_end", slice(-5, None)))}
    by_layer = rows.mean(axis=0)
    moe.update(load_max_over_mean_by_layer=load.mean(axis=0).tolist(),
               load_max_over_mean=float(load.max(axis=1).mean()),
               rows_here_by_layer=by_layer.tolist(),
               rows_here_per_token=float(by_layer.mean() / tokens_per_step * chips),
               rows_here_share=share(by_layer), bias_abs_max=float(bias.max()), **ends)
    return moe


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
    reference = check_reference(ctx, model, params, *sequence)
    jax.clear_caches()           # the comparison's programs, and the constants they hold
    # the engine starts from the biases' initial zero, and the step's check reads the rule on
    # the reference's own counts under those
    counts = reference_counts(ctx, model, params, *sequence)
    jax.clear_caches()
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
    # the expert layers' device scalars of every step the recorder still holds: fetched
    # here, after the window
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
        # what the per-layer readers take their numbers from. ``model`` is what the flash
        # readers know a model by, ``ssm_model`` what the scan's roofline does (Granite's
        # key names), ``ssm_moe_model`` the configuration's own keys
        "kind": "train", "chips": chips, "steps": steps, "tokens_per_step": tokens_per_step,
        "batch_per_chip": cell["micro_batch_per_chip"], "seq_len": seq_len,
        "tokens_per_s_chip": rate_chip, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s],
        "model": flash_sizes(m), "ssm_model": ssm_keys(m), "ssm_moe_model": m,
        "vocab": m["vocab_size"], "moe": moe,
    }
