"""A training cell of an expert model (``deepspeed_tpu/models/olmoe.py``): the engine's own
``forward``/``backward``/``step`` on packed documents, as ``runners/train.py`` measures
GPT-2, with the weights made in the engine's layout (the experts split over the chips),
the comparison with the configuration's plain reference on one seeded sequence, and the
expert layers' device scalars fetched after the window."""

import json
import os

import numpy as np

from benchmarks import harness
from benchmarks.harness import clock
from benchmarks.runners.train import _build_engine


def build_model(config):
    """The program's OLMoE from the configuration's published keys."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.olmoe import OlmoeConfig, OlmoeModel
    return OlmoeModel(OlmoeConfig.from_published(
        config["model"], router_aux_loss_coef=config["router_aux_loss_coef"],
        initializer_range=config["assumed"]["initializer_range"][1],
        compute_dtype=getattr(jnp, config["compute_dtype"])))


def _mesh(devices):
    from deepspeed_tpu.parallel.mesh import build_mesh
    return build_mesh(data=len(devices), model=1, pipe=1, devices=devices)


def init_params(model, seed, mesh):
    """The float32 weights from the seed, made in the layout the engine will hold them in
    (1.9 B parameters are 7.5 GB: more than one chip should be handed at once)."""
    import jax
    make = jax.jit(model.init, out_shardings=model.engine_shardings(mesh))
    return jax.block_until_ready(make(harness.seed_key(seed)))


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


GRAD_ROWS = 1024      # the positions whose expert-layer gradients are compared (layer 0)


def compare_expert_layers(system_layer, reference_layer, system_moe, reference_moe, expert_in, seed):
    """Every expert layer ALONE, the system's against the reference's, on identical
    inputs: the reference's own float32 ``expert_in [L, T, H]`` rounded to the compute
    dtype (what the system's layer is handed in a step), so that nothing but the layer's
    own arithmetic differs. ``system_layer(mp, x, cot)`` and ``reference_layer(mp, x, cot)``
    take a layer's expert parameters (``system_moe[l]``, ``reference_moe[l]``) and return
    ``{"y", "router_logits", "experts"}`` and, given a cotangent ``cot`` of ``y``,
    ``"grads"`` of ``sum(y * cot)`` by ``x``, ``router_w``, ``w_gate_up``, ``w_down``.
    The worst layer's reading of each quantity, and layer 0's gradients on the last
    ``GRAD_ROWS`` positions."""
    out = {"router_logits_rel": 0.0, "router_choice_agreement": 1.0, "expert_layer_rel": 0.0}
    rng = np.random.default_rng(seed)
    for l, (sys_mp, ref_mp) in enumerate(zip(system_moe, reference_moe)):
        x = expert_in[l]
        cot = None
        if l == 0:
            x = x[-GRAD_ROWS:]
            cot = rng.standard_normal(x.shape).astype(np.float32)
        got, want = system_layer(sys_mp, x, cot), reference_layer(ref_mp, x, cot)
        out["router_logits_rel"] = max(out["router_logits_rel"], float(
            np.abs(got["router_logits"] - want["router_logits"]).max()
            / np.abs(want["router_logits"]).max()))
        out["router_choice_agreement"] = min(out["router_choice_agreement"], float(
            np.mean(np.all(got["experts"] == want["experts"], axis=-1))))
        out["expert_layer_rel"] = max(out["expert_layer_rel"], _rel_l2(got["y"], want["y"]))
        if cot is not None:
            out["expert_layer_grad_rel"] = max(
                _rel_l2(g, w) for g, w in zip(got["grads"], want["grads"]))
    return out


WEIGHTS = ("router_w", "w_gate_up", "w_down")


def system_layer_fn(model, mesh):
    """``system_layer`` for ``compare_expert_layers``: the system's ``DroplessMoE`` under
    the mesh, a copy of the sequence a chip, so that the experts cross the chips as in a
    step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    rows = len(mesh.devices.flat)
    tile = lambda a: jax.device_put(np.tile(np.asarray(a)[None], (rows, 1, 1)),    # noqa: E731
                                    NamedSharding(mesh, P("data")))

    def fwd(mp, x):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            y, _, stats = model.moe.apply(mp, x, details=True)
        return y, stats

    def grads(mp, x, cot):
        def scalar(x, *w):
            return jnp.sum(fwd(dict(zip(WEIGHTS, w)), x)[0].astype(jnp.float32) * cot)
        return jax.grad(scalar, argnums=(0, 1, 2, 3))(x, *(mp[n] for n in WEIGHTS))

    fwd_jit, grads_jit = jax.jit(fwd), jax.jit(grads)

    def system_layer(mp, x, cot):
        xs = tile(np.asarray(x, np.float32).astype(model.config.compute_dtype))
        y, stats = jax.device_get(fwd_jit(mp, xs))
        out = {"y": y[0], "router_logits": stats["router_logits"][0], "experts": stats["experts"][0]}
        if cot is not None:     # every chip has the same rows: a weight's gradient is `rows` times one's
            g = jax.device_get(grads_jit(mp, xs, tile(cot)))
            out["grads"] = [np.asarray(g[0], np.float32)[0]] + [
                np.asarray(a, np.float32) / rows for a in g[1:]]
        return out

    return system_layer


def reference_layer_fn(ref, m, dtype, round_weights=None, **how):
    """``reference_layer`` for ``compare_expert_layers``: ``ref.expert_layer`` in float32
    on a layer's float32 parameters (on one chip), its inputs rounded to ``dtype`` as the
    system's are. ``round_weights`` (applied to the expert arrays) and ``how``
    (``router_dtype``, ``prec``) make the second readings of a lower precision
    (``tests/perf/olmoe_precision_probe.py``); the cell passes neither."""
    import jax
    import jax.numpy as jnp

    def fwd(mp, x):
        if round_weights is not None:
            mp = dict(mp, w_gate_up=round_weights(mp["w_gate_up"]), w_down=round_weights(mp["w_down"]))
        y, chosen, _, logits = ref.expert_layer(x, mp, m, **how)
        return y, {"experts": jnp.sort(chosen, axis=-1), "router_logits": logits}

    def grads(mp, x, cot):
        return jax.grad(lambda x, *w: jnp.sum(fwd(dict(zip(WEIGHTS, w)), x)[0] * cot),
                        argnums=(0, 1, 2, 3))(x, *(mp[n] for n in WEIGHTS))

    fwd_jit, grads_jit = jax.jit(fwd), jax.jit(grads)

    def reference_layer(mp, x, cot):
        x = jnp.asarray(np.asarray(x, np.float32).astype(dtype), jnp.float32)    # the same rounded inputs
        y, stats = jax.device_get(fwd_jit(mp, x))
        out = dict(stats, y=y)
        if cot is not None:
            out["grads"] = jax.device_get(grads_jit(mp, x, jnp.asarray(cot)))
        return out

    return reference_layer


def check_reference(ctx, model, params, mesh, tokens, labels):
    """One seeded sequence through the system (a row a chip, so that the experts cross
    the chips as in a step) and through the plain float32 reference on the last chip, on
    the same parameters. The whole model: the loss, the logits of the last positions, the
    share of (token, layer) pairs whose experts are the same. Every expert layer alone, on
    the reference's own inputs (``compare_expert_layers``): router logits, expert choices,
    the layer's output and layer 0's gradients, which is where a lower precision shows.
    Returns the readings and those inputs, ``expert_in [L, T, H]``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    config = ctx["config"]
    spec = config["reference"]
    ref = ctx["manifest"].reference(spec["module"])
    with open(os.path.join(ctx["manifest"].bench_dir, "reference", spec["tolerances"] + ".json")) as f:
        tol = {k: v["value"] for k, v in json.load(f).items()}
    last = min(spec["last_positions"], tokens.shape[0])
    rows = len(mesh.devices.flat)
    put = lambda a: jax.device_put(np.tile(a[None], (rows, 1)),          # noqa: E731
                                   NamedSharding(mesh, P("data")))

    def system(p, t, l):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return model.forward_details(p, t, l, last)

    got = jax.device_get(jax.jit(system)(params, put(tokens), put(labels)))
    # the reference's 7.5 GB of float32 weights go to the LAST chip: ``memory_peak_bytes``
    # is read from the others, whose peak is the training step's alone
    one = jax.device_put(params, mesh.devices.flat[-1])
    want = jax.device_get(jax.jit(
        lambda p, t, l: ref.forward(p, t[None], l[None], config["model"],
                                    config["router_aux_loss_coef"], last))(one, tokens, labels))
    layers = compare_expert_layers(
        system_layer_fn(model, mesh),
        reference_layer_fn(ref, config["model"], model.config.compute_dtype),
        [lp["moe"] for lp in params["layers"]], [lp["moe"] for lp in one["layers"]],
        want["expert_in"][:, 0], ctx["seed"])
    del one
    loss_rel = abs(float(got["loss"]) - float(want["loss"])) / abs(float(want["loss"]))
    logits_rel = float(np.abs(got["logits"][0] - want["logits"][0]).max()
                       / np.abs(want["logits"][0]).max())
    agree = float(np.mean(np.all(got["experts"][:, 0] == want["experts"][:, 0], axis=-1)))
    readings = dict(layers, train_loss_rel=loss_rel, last_logits_rel=logits_rel,
                    expert_agreement=agree)
    at_least = ("expert_agreement", "router_choice_agreement")
    ok = bool(np.isfinite(float(got["loss"])) and set(readings) == set(tol) and all(
        readings[k] >= tol[k] if k in at_least else readings[k] <= tol[k] for k in tol))
    return dict(readings, system_loss=float(got["loss"]), reference_loss=float(want["loss"]),
                rel_diff=loss_rel, aux=[float(got["aux"]), float(want["aux"])],
                tolerances=tol, ok=ok), want["expert_in"][:, 0]


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
    mesh = _mesh(ctx["devices"])
    params = init_params(model, ctx["seed"], mesh)
    setup["weights_s"] = clock() - t
    t = clock()
    reference, _ = check_reference(ctx, model, params, mesh, batches[-1][0][0], batches[-1][1][0])
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
    load = np.stack([s["moe_load_max_over_mean"] for s in in_window]) if in_window else None

    steps = len(losses)
    window_s = t1 - t0
    tokens_per_step = batch_size * seq_len
    rate_chip = steps * tokens_per_step / window_s / chips
    intervals_ms = (np.diff([t0] + returns) * 1e3).tolist()
    bad = sum(not np.isfinite(x) for x in losses) + int(engine.skipped_steps)
    fell = float(np.mean(losses[-10:])) < first_loss
    correct = bool(bad == 0 and fell and window_compiles == 0 and reference["ok"]
                   and len(in_window) > 0)
    # the reference's weights sat on the last chip: the others' peak is the step's own
    peaks = [harness.memory_peak_bytes([d]) for d in ctx["devices"]]

    moe = {"steps_counted": len(in_window),
           "load_max_over_mean_by_layer": None if load is None else load.mean(axis=0).tolist(),
           "load_max_over_mean": None if load is None else float(load.max(axis=1).mean())}
    harness.write_record(ctx["out_dir"], cell["name"], ctx["seed"], {
        "cell": cell["name"], "seed": ctx["seed"], "traced": tr.on, "window_s": window_s,
        "fence_ms": (t1 - returns[-1]) * 1e3, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s], "losses": losses,
        "warm_losses": [float(x) for x in jax.device_get(warm_losses)],
        "setup": setup, "reference": reference, "moe": moe, "memory_peak_bytes_by_chip": peaks})
    step_ms, stall_ms = harness.step_profile(intervals_ms)
    harness.summary_line("step_return_interval", intervals_ms, step_ms_median=step_ms,
                         longest_stall_ms=stall_ms, fence_ms=(t1 - returns[-1]) * 1e3,
                         first_losses=[float(x) for x in jax.device_get(warm_losses[:5])],
                         window_last_loss=losses[-1], reference=reference, setup=setup,
                         warm_steps=len(warm_losses), window_compiles=window_compiles, moe=moe,
                         memory_peak_bytes_by_chip=peaks)

    return {
        "correct": correct, "attempted": steps, "failed": bad,
        "t_window_start": t0, "window_s": window_s, "setup": setup,
        "end_to_end": {"train_tokens_per_s_chip": rate_chip},
        "memory_peak_bytes": harness.memory_peak_bytes(ctx["devices"][:-1] or ctx["devices"]),
        # what the per-layer readers take their numbers from; the three GPT-2 names are
        # what the readers that exist know a model by
        "kind": "train", "chips": chips, "steps": steps, "tokens_per_step": tokens_per_step,
        "batch_per_chip": cell["micro_batch_per_chip"], "seq_len": seq_len,
        "tokens_per_s_chip": rate_chip, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s],
        "model": dict(m, n_embd=m["hidden_size"], n_layer=m["num_hidden_layers"],
                      n_head=m["num_attention_heads"]),
        "vocab": m["vocab_size"], "moe": moe,
    }
