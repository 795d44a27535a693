"""A training cell of a hybrid linear-attention expert model
(``deepspeed_tpu/models/qwen3_next.py``) as one chip's share of a wider deployment: the
engine's own ``forward``/``backward``/``step`` on packed documents, as ``runners/train.py``
measures GPT-2 and ``runners/train_moe.py`` OLMoE. In set-up one seeded sequence goes
through the system and through the configuration's plain reference on the same parameters:
the whole model (loss, last logits, expert choices), and each new kind of layer ALONE on the
reference's own inputs (the delta-rule mixer and the delta rule itself, the held-range
expert layer, the gated attention; outputs over the sequence, gradients on its last
positions). The process's first step then runs the ENGINE's own compiled programs on that
sequence (``check_step``): its loss against the reference's, and what it took off every leaf
of the master against Adam's first step. After the window the expert layers' device scalars
are fetched."""

import json
import os

import numpy as np

from benchmarks import harness
from benchmarks.harness import clock
from benchmarks.runners.train import _build_engine


def build_model(config):
    """The program's Qwen3-Next from the configuration's keys (published, and the share)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextModel
    return Qwen3NextModel(Qwen3NextConfig.from_published(
        config["model"], router_aux_loss_coef=config["router_aux_loss_coef"],
        initializer_range=config["assumed"]["initializer_range"][1],
        compute_dtype=getattr(jnp, config["compute_dtype"])))


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _worst_leaf(got, want):
    import jax
    return max(_rel_l2(g, w) for g, w in zip(jax.tree_util.tree_leaves(got),
                                             jax.tree_util.tree_leaves(want)))


class Alone:
    """One kind of layer ALONE, the system's against the reference's, on identical inputs:
    the reference's own float32 ``x [T, H]`` rounded to the compute dtype (what the
    system's layer is handed in a step). ``fn(params, x [1, t, H]) -> y [1, t, H]``; each
    function compiles once and serves every layer of its kind."""

    def __init__(self, system_fn, reference_fn):
        import jax
        import jax.numpy as jnp

        def grad_of(fn):
            return jax.jit(jax.grad(lambda p, x, cot: jnp.sum(fn(p, x).astype(jnp.float32) * cot),
                                    argnums=(0, 1)))
        self.fns = jax.jit(system_fn), jax.jit(reference_fn)
        self.grads = grad_of(system_fn), grad_of(reference_fn)

    def output(self, params, x):
        """The relative error of the output over the whole sequence."""
        import jax
        import jax.numpy as jnp
        y_sys, y_ref = self.fns[0](params, x[None]), self.fns[1](params, x[None].astype(jnp.float32))
        return _rel_l2(jax.device_get(y_sys), jax.device_get(y_ref))

    def gradients(self, params, x, rows, seed):
        """The relative error of the gradients of ``sum(y * c)`` (``c`` seeded) by every
        parameter and the input on the last ``rows`` positions: the worst leaf's."""
        import jax
        import jax.numpy as jnp
        tail = x[None, -rows:]
        cot = jnp.asarray(np.random.default_rng(seed).standard_normal(tail.shape), jnp.float32)
        g_sys = self.grads[0](params, tail, cot)
        g_ref = self.grads[1](params, tail.astype(jnp.float32), cot)
        return _worst_leaf(jax.device_get(g_sys), jax.device_get(g_ref))


def compare_layers(model, ref, m, params, want, rows, seed):
    """Every new kind of layer alone on the reference's own inputs (``want["mixer_in"]``,
    ``want["expert_in"]`` ``[L, 1, T, H]``): the worst layer's reading of each output, and
    the gradients of the first layer of each kind."""
    import jax
    import jax.numpy as jnp
    c = model.config
    dt = c.compute_dtype
    kinds = {
        "mixer": Alone(lambda p, x: model.linear_mixer(x, p), lambda p, x: ref.linear_mixer(x, p, m)),
        "attention": Alone(lambda p, x: model.full_attention(x, p, jnp.arange(x.shape[1])),
                           lambda p, x: ref.full_attention(x, p, m)),
        "expert_layer": Alone(lambda p, x: model.expert_layer(x, p)[0],
                              lambda p, x: ref.expert_layer(x[0], p, m)[0][None]),
    }
    delta_rule = DeltaRuleAlone(ref, m, dt)
    routed = jax.jit(lambda p, x: model.moe.apply(p, x, details=True)[2])
    routed_ref = jax.jit(lambda p, x: ref.expert_layer(x, p, m)[1::2])
    out = {"delta_rule_rel": 0.0, "router_logits_rel": 0.0, "router_choice_agreement": 1.0}

    def read(kind, lp, x):
        out[kind + "_rel"] = max(out.get(kind + "_rel", 0.0), kinds[kind].output(lp, x))
        if kind + "_grad_rel" not in out:
            out[kind + "_grad_rel"] = kinds[kind].gradients(lp, x, rows, seed)

    for l, lp in enumerate(params["layers"]):
        x = jnp.asarray(want["mixer_in"][l, 0]).astype(dt)
        read("attention" if c.is_full_attention(l) else "mixer", lp["mixer"], x)
        if not c.is_full_attention(l):
            y, g = delta_rule.read(lp["mixer"], x, 0 if "delta_rule_grad_rel" in out else rows, seed)
            out["delta_rule_rel"] = max(out["delta_rule_rel"], y)
            out.setdefault("delta_rule_grad_rel", g)
        x = jnp.asarray(want["expert_in"][l, 0]).astype(dt)
        read("expert_layer", {"moe": lp["moe"], "shared": lp["shared"]}, x)
        stats = jax.device_get(routed(lp["moe"], x[None]))
        chosen, logits = jax.device_get(routed_ref(lp, x.astype(jnp.float32)))
        out["router_logits_rel"] = max(out["router_logits_rel"], float(
            np.abs(stats["router_logits"][0] - logits).max() / np.abs(logits).max()))
        out["router_choice_agreement"] = min(out["router_choice_agreement"], float(
            np.mean(np.all(stats["experts"][0] == np.sort(chosen, axis=-1), axis=-1))))
    return out


class DeltaRuleAlone:
    """The delta rule ALONE: the system's chunked ``gated_delta_rule`` against the
    reference's token-at-a-time recurrence on the q, k, v, g and beta that the reference's
    mixer makes of its input (q, k, v rounded to the compute dtype's values, as the system's are).
    ``state_dtype`` puts the reference's own recurrence with such a state in the system's
    place: the second reading of a lower precision (``tests/perf``'s probe; never the cell)."""

    def __init__(self, ref, m, dtype, state_dtype=None):
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.ops.delta_rule import gated_delta_rule
        r = m["linear_num_value_heads"] // m["linear_num_key_heads"]

        def reference(q, k, v, g, beta, state=jnp.float32):
            q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
            return ref.delta_rule_recurrent(jnp.repeat(ref.unit_scaled(q, True), r, axis=2),
                                            jnp.repeat(ref.unit_scaled(k, False), r, axis=2),
                                            v, g, beta, state).astype(jnp.float32)

        def inputs(mp, x):
            q, k, v, g, beta, _ = ref.mixer_inputs(x.astype(jnp.float32), mp, m)
            # the compute dtype's values in float32, so that the rule returns float32: what
            # is compared is its arithmetic, not the rounding of its output
            rounded = lambda a: a.astype(dtype).astype(jnp.float32)       # noqa: E731
            return rounded(q), rounded(k), rounded(v), g, beta

        def grad_of(fn):
            return jax.jit(jax.grad(lambda cot, *a: jnp.sum(fn(*a) * cot), argnums=(1, 2, 3, 4, 5)))

        system = gated_delta_rule if state_dtype is None else (
            lambda *a: reference(*a, state=state_dtype))
        self.inputs = jax.jit(inputs)
        self.fns = jax.jit(system), jax.jit(reference)
        self.grads = grad_of(system), grad_of(reference)

    def read(self, mp, x, rows, seed):
        """``(output's relative error over the sequence, the worst gradient's on the last
        ``rows`` positions or None where ``rows`` is 0)``."""
        import jax
        import jax.numpy as jnp
        args = self.inputs(mp, x[None])
        out = _rel_l2(jax.device_get(self.fns[0](*args)), jax.device_get(self.fns[1](*args)))
        if not rows:
            return out, None
        tail = tuple(a[:, -rows:] for a in args)
        cot = jnp.asarray(np.random.default_rng(seed).standard_normal(tail[2].shape), jnp.float32)
        got, want = (jax.device_get(g(cot, *tail)) for g in self.grads)
        return out, _worst_leaf([np.asarray(a, np.float32) for a in got], want)


def _limits(ctx, of_the_step):
    """The configuration's limits by name: those of the engine's own step (``step_*``,
    ``check_step``) or the others (``check_reference``)."""
    name = ctx["config"]["reference"]["tolerances"]
    with open(os.path.join(ctx["manifest"].bench_dir, "reference", name + ".json")) as f:
        return {k: v["value"] for k, v in json.load(f).items() if k.startswith("step_") == of_the_step}


def check_reference(ctx, model, params, tokens, labels):
    """One seeded sequence through the system and through the plain float32 reference on
    the same parameters: the whole model (the loss, the logits of the last positions, the
    share of (token, layer) pairs whose experts are the same), then every new kind of layer
    alone (``compare_layers``), which is where a lower precision shows. Returns the readings
    and the reference's own inputs of every layer (``mixer_in``, ``expert_in``)."""
    import jax
    config = ctx["config"]
    spec, m = config["reference"], config["model"]
    ref = ctx["manifest"].reference(spec["module"])
    tol = _limits(ctx, of_the_step=False)
    last = min(spec["last_positions"], tokens.shape[0])
    rows = min(spec["grad_positions"], tokens.shape[0])
    got = jax.device_get(jax.jit(lambda p, t, l: model.forward_details(p, t[None], l[None], last))(
        params, tokens, labels))
    want = jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], m,
                                               config["router_aux_loss_coef"], last))(
        params, tokens, labels)
    layers = compare_layers(model, ref, m, params, want, rows, ctx["seed"])
    inputs = {k: want[k] for k in ("mixer_in", "expert_in")}
    want = jax.device_get({k: want[k] for k in ("loss", "aux", "logits", "experts")})
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
                tolerances=tol, ok=ok), inputs


def check_step(ctx, engine, tokens, labels, batch_size, reference_loss):
    """One step of the ENGINE's own compiled programs (the gradient program under its remat
    policy, the ZeRO-2 gradient path, the update program) on the sequence the reference saw
    (a batch of its copies, whose loss is the one's).
    ``step_loss_rel``: the step's loss against the reference's. ``step_update_shortfall``:
    what the step took off each leaf of the float32 master against Adam's first step at the
    engine's rate, which moves every element that has a gradient by the rate (``m / sqrt(v)``
    is the gradient's sign then, less where the gradient is not far above Adam's epsilon):
    ``| ||after - before|| / (rate x sqrt(elements with a gradient)) - 1 |``, the worst
    leaf's. Every element has a gradient but the embedding's rows of tokens the sequence
    lacks. A leaf whose gradient was lost on the way reads 1, a rate applied twice 1, Adam
    without its bias correction 2.2. Returns the readings and the step's loss."""
    import jax
    tol = _limits(ctx, of_the_step=True)
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
        moved = b.size if name != "['embed']" else seen * b.shape[1]
        by_leaf[name] = abs(float(np.linalg.norm((a - b).astype(np.float64))
                                  / (rate * np.sqrt(moved))) - 1.0)
    worst = max(by_leaf, key=by_leaf.get)
    readings = {"step_loss_rel": abs(float(loss) - reference_loss) / abs(reference_loss),
                "step_update_shortfall": by_leaf[worst]}
    ok = bool(set(readings) == set(tol) and all(readings[k] <= tol[k] for k in tol))
    return dict(readings, worst_leaf=worst, rate=rate, tokens_seen=seen, tolerances=tol,
                ok=ok), loss


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
    reference, _ = check_reference(ctx, model, params, *sequence)
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

    moe = {"steps_counted": len(in_window), "load_max_over_mean_by_layer": None,
           "load_max_over_mean": None, "rows_here_by_layer": None, "rows_here_per_token": None,
           "rows_here_share": None}
    if in_window:
        load = np.stack([s["moe_load_max_over_mean"] for s in in_window])
        rows = np.stack([s["moe_rows_here"] for s in in_window]).mean(axis=0)     # [layers]
        moe.update(load_max_over_mean_by_layer=load.mean(axis=0).tolist(),
                   load_max_over_mean=float(load.max(axis=1).mean()),
                   rows_here_by_layer=rows.tolist(),
                   rows_here_per_token=float(rows.mean() / tokens_per_step * chips),
                   rows_here_share=float(rows.mean() / (tokens_per_step / chips
                                                        * m["num_experts_per_tok"])))
    peak = harness.memory_peak_bytes(ctx["devices"])
    harness.write_record(ctx["out_dir"], cell["name"], ctx["seed"], {
        "cell": cell["name"], "seed": ctx["seed"], "traced": tr.on, "window_s": window_s,
        "fence_ms": (t1 - returns[-1]) * 1e3, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s], "losses": losses,
        "warm_losses": [float(x) for x in jax.device_get(warm_losses)],
        "setup": setup, "reference": reference, "moe": moe, "memory_peak_bytes": peak,
        "tokens_per_s_chip": rate_chip})
    step_ms, stall_ms = harness.step_profile(intervals_ms)
    harness.summary_line("step_return_interval", intervals_ms, step_ms_median=step_ms,
                         longest_stall_ms=stall_ms, fence_ms=(t1 - returns[-1]) * 1e3,
                         first_losses=[float(x) for x in jax.device_get(warm_losses[:5])],
                         window_last_loss=losses[-1], reference=reference, setup=setup,
                         warm_steps=len(warm_losses), window_compiles=window_compiles, moe=moe,
                         memory_peak_bytes=peak)

    return {
        "correct": correct, "attempted": steps, "failed": bad,
        "t_window_start": t0, "window_s": window_s, "setup": setup,
        "end_to_end": {"train_tokens_per_s_chip": rate_chip},
        "memory_peak_bytes": peak,
        # what the per-layer readers take their numbers from. ``model`` is what the flash
        # readers that exist know a model by (``flops.flash_required``): exactly the
        # full-attention layers, their query heads' width in all
        "kind": "train", "chips": chips, "steps": steps, "tokens_per_step": tokens_per_step,
        "batch_per_chip": cell["micro_batch_per_chip"], "seq_len": seq_len,
        "tokens_per_s_chip": rate_chip, "step_interval_ms": intervals_ms,
        "dispatch_ms": [d * 1e3 for d in dispatch_s],
        "model": flash_sizes(m), "hybrid_model": m, "vocab": m["vocab_size"], "moe": moe,
    }


def flash_sizes(m):
    """The three GPT-2 names ``flops.flash_required`` reads, such that it counts exactly
    this model's softmax attention: its full-attention layers, ``heads x head_dim`` wide."""
    full = sum((l + 1) % m["full_attention_interval"] == 0 for l in range(m["num_hidden_layers"]))
    return {"n_embd": m["num_attention_heads"] * m["head_dim"], "n_layer": full,
            "n_head": m["num_attention_heads"]}
