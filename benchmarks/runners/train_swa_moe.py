"""A training cell of a model of sliding-window and full attention layers with an expert layer
each (``deepspeed_tpu/models/mellum.py``) as one chip's share of a wider deployment: the
engine's own ``forward``/``backward``/``step`` on packed documents with whole layers
recomputed, as ``runners/train_ssm_moe.py`` measures Nemotron-H. In set-up one seeded sequence
goes through the system and through the configuration's plain reference on the same
parameters: the whole model (loss, last logits, the experts chosen), each kind of layer ALONE
on the reference's own inputs (a sliding layer's attention, a full layer's, the expert layer
and its router; outputs over the sequence, gradients on its last positions), and an EDGE PROBE
of the flash kernel at the timed shape (``edge_probe``: the band is exactly the window wide).
Everything the comparison held is dropped before the engine builds its state. The process's
first step then runs the ENGINE's own compiled programs on that sequence
(``train_hybrid.check_step``). After the window the expert layers' device scalars are fetched.

Expert choices. A (token, layer) pair counts as a WRONG choice only where the reference's gap
between its eighth and ninth probability is wider than a margin and the choices still differ
(``train_ssm_moe.wide_gaps``): ``tie_margin`` for a router ALONE on the reference's own input
(float32 on both sides), ``tie_margin_whole_model`` inside the whole model, where the system's
rows are bf16 (both in the configuration's ``reference`` block)."""

import numpy as np

from benchmarks import harness
from benchmarks.harness import clock
from benchmarks.runners.train import _build_engine
from benchmarks.runners.train_hybrid import Alone, _limits, _rel_l2, check_step
from benchmarks.runners.train_ssm_moe import choice_readings, wide_gaps

AT_LEAST = ("expert_agreement", "router_choice_agreement")
KINDS = {"sliding_attention": "window_attention", "full_attention": "full_attention"}


def build_model(config):
    """The program's Mellum from the configuration's keys (published, and the share)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.mellum import MellumConfig, MellumModel
    assumed = config["assumed"]
    return MellumModel(MellumConfig.from_published(
        config["model"], initializer_range=assumed["initializer_range"][1],
        router_aux_loss_coef=assumed["router_aux_loss_coef"][1], remat=config["remat"],
        compute_dtype=getattr(jnp, config["compute_dtype"])))


def band_record(model, seq_len):
    """The tiles the flash kernel resolves for each kind of layer at this length, and the
    query-key pairs its schedule visits against the pairs the masks allow, over the model's
    calls (``flash_attention.band_pairs``: plain integers, nothing traced)."""
    import importlib
    import jax
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    c = model.config
    q = jax.ShapeDtypeStruct((1, 1, seq_len, c.head_dim), c.compute_dtype)      # its sizes are all that is read
    out = {"visited": 0, "needed": 0, "tiles": {}}
    for kind in c.kinds:
        window = c.window_of(kind)
        _, bq, bk, _ = fa._resolve(q, None, None, None, True, False, window)
        visited, needed = fa.band_pairs(seq_len, bq, bk, window)
        out["visited"] += visited
        out["needed"] += needed
        out["tiles"][kind] = {"block_q": bq, "block_k": bk, "window": window,
                              "visited": visited, "needed": needed}
    return out


def edge_probe(model, ref, seq_len, window="published"):
    """The flash kernel at the timed shape on ``q = k = 0`` and one-hot values: the largest
    distance from ``1 / head_dim`` over the positions that see a whole window (0 for a band
    exactly ``sliding_window`` wide; ``reference.edge_probe_error`` has what a band a key wider
    or narrower, or none, reads). ``window`` puts another one (None: none) in the kernel's place:
    a fault the limit has to catch (the rehearsal and the probe; never the cell)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    c = model.config
    D, published = c.head_dim, c.sliding_window
    q = jnp.zeros((1, c.num_attention_heads, seq_len, D), c.compute_dtype)
    k = jnp.zeros((1, c.num_key_value_heads, seq_len, D), c.compute_dtype)
    v = ref.edge_probe_values(seq_len, D, c.num_key_value_heads, c.compute_dtype)
    window = published if window == "published" else window
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, window=window))(q, k, v)
    return ref.edge_probe_error(jax.device_get(out).astype(np.float32), published, D)


def table_distance(model, ref, m, **fault):
    """The system's rotary tables against the reference's own (float64), the worst kind's:
    the largest relative distance of a frequency, or of what cos and sin are multiplied by.
    ``fault`` puts ``reference.rotary_table`` at fault in the system's place (the probe)."""
    worst = 0.0
    for kind, (inv_freq, factor) in sorted(model.tables.items()):
        want, want_factor = ref.rotary_table(m, kind)
        if fault:
            inv_freq, factor = ref.rotary_table(m, kind, **fault)
        worst = max(worst, float(np.max(np.abs(np.asarray(inv_freq, np.float64) / want - 1.0))),
                    abs(factor / want_factor - 1.0))
    return worst


def compare_layers(model, ref, m, params, want, rows, seed, margin):
    """Every kind of layer alone on the reference's own inputs (``want["attn_in"]``,
    ``want["expert_in"]`` ``[L, 1, T, H]``): the worst layer's reading of each output, and the
    gradients of the first layer of each kind. An attention layer's gradients are read on the
    input of the model's FIRST layer (``grad_input``), whatever its depth: a deeper layer's rows
    share a common direction, which a softmax's gradient cancels, and what is left of the
    query-side gradients there is a twelfth as large and a fifth of it bf16 rounding, in the
    system and in the reference's own bf16 softmax alike (PERF.md section 6, PR 45)."""
    import jax
    import jax.numpy as jnp
    c = model.config
    dt, k = c.compute_dtype, c.num_experts_per_tok
    mixer = ("wq", "wkv", "q_norm", "k_norm", "wo")
    alone = {name: Alone(lambda p, x, kind=kind: model.attention(x, p, kind),
                         lambda p, x, kind=kind: ref.attention(x, p, m, kind))
             for kind, name in KINDS.items() if kind in c.kinds}
    alone["expert_layer"] = Alone(lambda p, x: model.expert_layer(x, p)[0],
                                  lambda p, x: ref.expert_layer(x[0], p, m)[0][None])
    routed = jax.jit(lambda p, x: model.moe.apply(p, x, details=True)[2])
    routed_ref = jax.jit(lambda p, x: ref.router(x, p, m)[::2])
    out = {"router_probs_rel": 0.0, "router_choice_agreement": 1.0, "router_wrong_choice_share": 0.0}

    def read(name, lp, x, grad_x=None):
        out[name + "_rel"] = max(out.get(name + "_rel", 0.0), alone[name].output(lp, x))
        if name + "_grad_rel" not in out:
            out[name + "_grad_rel"] = alone[name].gradients(lp, x if grad_x is None else grad_x, rows, seed)

    grad_input = jnp.asarray(want["attn_in"][0, 0]).astype(dt)
    for l, (kind, lp) in enumerate(zip(c.kinds, params["layers"])):
        read(KINDS[kind], {name: lp[name] for name in mixer}, jnp.asarray(want["attn_in"][l, 0]).astype(dt),
             grad_input)
        x = jnp.asarray(want["expert_in"][l, 0]).astype(dt)
        read("expert_layer", {"moe": lp["moe"]}, x)
        stats = jax.device_get(routed(lp["moe"], x[None]))
        chosen, probs = jax.device_get(routed_ref(lp["moe"], x.astype(jnp.float32)))
        got = jax.nn.softmax(stats["router_logits"][0], axis=-1)
        out["router_probs_rel"] = max(out["router_probs_rel"], float(
            np.abs(got - probs).max() / np.abs(probs).max()))
        agree, wrong = choice_readings(stats["experts"][0], np.sort(chosen, axis=-1),
                                       wide_gaps(probs, 0.0, k, margin))
        out["router_choice_agreement"] = min(out["router_choice_agreement"], agree)
        out["router_wrong_choice_share"] = max(out["router_wrong_choice_share"], wrong)
    return out


def check_reference(ctx, model, params, tokens, labels):
    """One seeded sequence through the system and through the plain float32 reference on the
    same parameters: the whole model (the loss, the logits of the last positions, the experts
    chosen a layer: agreement, and the wrong choices apart from the near-ties), every kind of
    layer alone (``compare_layers``), and the kernel's edge probe. Returns the readings."""
    import jax
    config = ctx["config"]
    spec, m = config["reference"], config["model"]
    coef = config["assumed"]["router_aux_loss_coef"][1]
    ref = ctx["manifest"].reference(spec["module"])
    tol = _limits(ctx, of_the_step=False)
    last = min(spec["last_positions"], tokens.shape[0])
    rows = min(spec["grad_positions"], tokens.shape[0])
    got = jax.device_get(jax.jit(lambda p, t, l: model.forward_details(p, t[None], l[None], last))(
        params, tokens, labels))
    want = jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], m, coef, last))(params, tokens, labels)
    readings = compare_layers(model, ref, m, params, want, rows, ctx["seed"], spec["tie_margin"])
    want = jax.device_get({k: want[k] for k in ("loss", "logits", "experts", "probs")})
    wide = wide_gaps(want["probs"], 0.0, m["num_experts_per_tok"], spec["tie_margin_whole_model"])
    logits = got["router_logits"].astype(np.float64)
    probs = np.exp(logits - logits.max(axis=-1, keepdims=True))
    probs_apart = np.abs(probs / probs.sum(axis=-1, keepdims=True) - want["probs"])
    agree, wrong = choice_readings(got["experts"], want["experts"], wide)
    readings.update(
        train_loss_rel=abs(float(got["loss"]) - float(want["loss"])) / abs(float(want["loss"])),
        last_logits_rel=_rel_l2(got["logits"][0], want["logits"][0]),
        expert_agreement=agree, expert_wrong_choice_share=wrong,
        edge_probe_abs_err=edge_probe(model, ref, tokens.shape[0]),
        rotary_table_rel=table_distance(model, ref, m))
    ok = bool(np.isfinite(float(got["loss"])) and set(readings) == set(tol) and all(
        readings[k] >= tol[k] if k in AT_LEAST else readings[k] <= tol[k] for k in tol))
    return dict(readings, system_loss=float(got["loss"]), reference_loss=float(want["loss"]),
                rel_diff=readings["train_loss_rel"], wide_gap_share=float(np.mean(wide)),
                probs_apart=[float(np.percentile(probs_apart, q)) for q in (50, 99, 100)],
                tolerances=tol, ok=ok)


def moe_record(in_window, tokens_per_step, chips, k):
    """What the expert layers' device scalars of the window's steps say: the means over the
    window (the keys the ``moe_*`` readers ask for)."""
    moe = {"steps_counted": len(in_window), "load_max_over_mean_by_layer": None,
           "load_max_over_mean": None, "rows_here_by_layer": None, "rows_here_per_token": None,
           "rows_here_share": None}
    if not in_window:
        return moe
    load = np.stack([s["moe_load_max_over_mean"] for s in in_window])          # [steps, layers]
    by_layer = np.stack([s["moe_rows_here"] for s in in_window]).mean(axis=0)
    moe.update(load_max_over_mean_by_layer=load.mean(axis=0).tolist(),
               load_max_over_mean=float(load.max(axis=1).mean()),
               rows_here_by_layer=by_layer.tolist(),
               rows_here_per_token=float(by_layer.mean() / tokens_per_step * chips),
               rows_here_share=float(by_layer.mean() / (tokens_per_step / chips * k)))
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
    band = band_record(model, seq_len)
    peak = harness.memory_peak_bytes(ctx["devices"])
    memory = {k: v for k, v in (ctx["devices"][0].memory_stats() or {}).items()
              if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_alloc_size")}
    harness.write_record(ctx["out_dir"], cell["name"], ctx["seed"], {
        "cell": cell["name"], "seed": ctx["seed"], "traced": tr.on, "window_s": window_s,
        "fence_ms": (t1 - returns[-1]) * 1e3, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s], "losses": losses,
        "warm_losses": [float(x) for x in jax.device_get(warm_losses)],
        "setup": setup, "reference": reference, "moe": moe, "band": band, "memory": memory,
        "memory_peak_bytes": peak, "tokens_per_s_chip": rate_chip})
    step_ms, stall_ms = harness.step_profile(intervals_ms)
    harness.summary_line("step_return_interval", intervals_ms, step_ms_median=step_ms,
                         longest_stall_ms=stall_ms, fence_ms=(t1 - returns[-1]) * 1e3,
                         first_losses=[float(x) for x in jax.device_get(warm_losses[:5])],
                         window_last_loss=losses[-1], reference=reference, setup=setup,
                         warm_steps=len(warm_losses), window_compiles=window_compiles, moe=moe,
                         band=band, memory=memory, memory_peak_bytes=peak)

    return {
        "correct": correct, "attempted": steps, "failed": bad,
        "t_window_start": t0, "window_s": window_s, "setup": setup,
        "end_to_end": {"train_tokens_per_s_chip": rate_chip},
        "memory_peak_bytes": peak,
        # what the per-layer readers take their numbers from: ``swa_moe_model`` is the
        # configuration's own keys (``flops_swa_moe``), ``band`` the schedule's counter
        "kind": "train", "chips": chips, "steps": steps, "tokens_per_step": tokens_per_step,
        "batch_per_chip": cell["micro_batch_per_chip"], "seq_len": seq_len,
        "tokens_per_s_chip": rate_chip, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s],
        "swa_moe_model": m, "vocab": m["vocab_size"], "moe": moe, "band": band,
    }
