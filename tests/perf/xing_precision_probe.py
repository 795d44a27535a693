"""Both readings behind the limits of ``benchmarks/reference/xing_moe_tolerances.json``, at
``xing4.0-29b-a4b-ep8-d5``'s full widths on one chip:

    chiprun --timeout 2400 -- python tests/perf/xing_precision_probe.py [--seeds 11,12,...]

For every seed: the system's readings (``benchmarks/runners/train_hc_moe.check_reference``, as the
cell's set-up takes them), and the same comparisons with the plain reference itself at fault in the
system's place. Each kind alone on the reference's own inputs: a sub-layer's hyper-connection with its
coefficients in bfloat16, with 19 and with 10 Sinkhorn-Knopp rounds and with ``H_post`` a plain
sigmoid; the latent mixer under the plain rotary frequencies, under the scale without YaRN's ``m^2``,
with its rotary key left out; the expert layer with a bfloat16 router and with factor 1.0. The whole
model (unless ``--alone-only``): the hyper-connections' faults in every sub-layer, the embedding in
the first stream alone, the scale without ``m^2``; last, what Adam's first step would take off each
leaf from the gradients' sizes (``adam_first_step``: what ``step_update_shortfall`` and
``step_hc_moved_share`` are read against). A limit has to lie above the system's largest reading and
below the fault's smallest. One JSON line a seed on stdout and in
``chiprun_out/xing_precision_probe.jsonl``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

HC_FAULTS = {"bf16_coefficients": {"dtype": "bfloat16"}, "rounds_19": {"iters": 19}, "rounds_10": {"iters": 10},
             "h_post_plain_sigmoid": {"post_factor": 1.0}}
ATTENTION_FAULTS = {"plain_frequencies": {"yarn": False}, "scale_without_m2": {"m_squared": False},
                    "rotary_key_left_out": {"rotary_key": "left_out"}}
EXPERT_FAULTS = {"bf16_router": {"router_dtype": "bfloat16"}, "factor_1": {"factor": 1.0}}
# keywords of ``reference.forward``
MODEL_FAULTS = {"embedding_in_the_first_stream_alone": {"streams": "first_only"}}


def probe(manifest, config_name, traffic_name, seeds, whole_model=True, adam=True):
    """One dict a seed: ``system`` and, under its name, each fault's readings (``whole_model``: the
    faults inside the whole model too, a compile of the reference each; ``adam``: what Adam's first
    step would take off each leaf, a gradient program of the system)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import harness
    runner = manifest._module("runners", "train_hc_moe")

    config, traffic = manifest.config(config_name), manifest.traffic(traffic_name)
    m, spec = config["model"], config["reference"]
    ref = manifest.reference(spec["module"])
    generate = manifest.generator(traffic["generator"])
    model = runner.build_model(config)
    dtype, k, n = model.config.compute_dtype, m["num_experts_per_tok"], m["hc_mult"]
    rows, last = spec["grad_positions"], spec["last_positions"]
    f32 = lambda x: x.astype(jnp.float32)        # the system's place is handed the compute dtype's rows   # noqa: E731
    named = lambda f: {a: getattr(jnp, b) if a.endswith("dtype") else b for a, b in f.items()}   # noqa: E731
    streams = lambda x: x.reshape(x.shape[:-1] + (n, -1))       # noqa: E731
    plain_hc = lambda p, x: ref.connected(streams(x), p, m, lambda u: u)[0].reshape(x.shape)     # noqa: E731
    system_hc = runner.Alone(lambda p, x: model.connected(x, p, lambda u: (u, {}))[0], plain_hc)
    wrong_hc = {name: runner.Alone(
        lambda p, x, f=named(f): ref.connected(streams(f32(x)), p, m, lambda u: u, **f)[0].reshape(x.shape), plain_hc)
        for name, f in HC_FAULTS.items()}
    wrong_attention = {name: runner.Alone(lambda p, x, f=f: ref.attention(f32(x), p, m, **f),
                                          lambda p, x: ref.attention(x, p, m))
                       for name, f in ATTENTION_FAULTS.items()}
    plain_experts = lambda p, x: ref.expert_layer(x[0], p, m)[0][None]        # noqa: E731
    wrong_experts = {name: runner.Alone(
        lambda p, x, f=named(f): ref.expert_layer(f32(x[0]), p, m, **f)[0][None], plain_experts)
        for name, f in EXPERT_FAULTS.items()}
    kept = ("loss", "logits", "experts", "scores") + runner.HC_SCALARS

    def one_seed(seed):
        params = harness.init_params(model, seed)
        # the cell's own sequence: its last batch of as many as its set-up makes
        batches, _ = generate(traffic, seed, vocab=m["vocab_size"], batch=1, n_batches=traffic["batches_ahead"])
        tokens, labels = batches[-1][0][0], batches[-1][1][0]
        del batches
        ctx = {"config": config, "manifest": manifest, "seed": seed}
        system = runner.check_reference(ctx, model, params, tokens, labels)
        line = {"seed": seed, "device": jax.devices()[0].device_kind, "system": system}
        params = runner.seeded_biases(params, seed)           # as check_reference compares
        want = jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], m, last))(params, tokens, labels)
        # block 1's attention's hyper-connection: the first whose streams differ
        hp = params["layers"][1]["hc_attn"]
        x = jnp.asarray(want["hc_in"][2, 0]).reshape(tokens.shape[0], -1).astype(dtype)
        left = lambda **f: float(jnp.max(jnp.abs(jnp.sum(    # noqa: E731
            jax.jit(lambda x: ref.coefficients(streams(f32(x))[None], hp, m, **f)[2])(x), axis=-1) - 1.0)))
        draws = [(seed, d) for d in range(runner.GRADIENT_DRAWS)]       # as the cell's set-up reads them
        # the system's leaves under ONE cotangent (Alone.gradients' draw) beside the set-up's sixteen
        line["hyper_connection_grad_by_leaf_one_cotangent"] = runner.gradients_by_leaf(system_hc, hp, x, rows, [seed])
        for name, alone in wrong_hc.items():
            line[name] = {"hyper_connection_rel": alone.output(hp, x),
                          "hyper_connection_grad_rel": max(runner.gradients_by_leaf(alone, hp, x, rows, draws).values()),
                          "hc_res_err_rel": abs(left(**named(HC_FAULTS[name])) - left()) / left()}
        first_input = jnp.asarray(want["attn_in"][0, 0]).astype(dtype)
        ap, x = params["layers"][1]["attn"], jnp.asarray(want["attn_in"][1, 0]).astype(dtype)
        for name, alone in wrong_attention.items():
            line[name] = {"latent_attention_rel": alone.output(ap, x),
                          "latent_attention_grad_rel": alone.gradients(ap, first_input, rows, seed)}
        lp = {name: params["layers"][1][name] for name in ("moe", "shared")}
        x = jnp.asarray(want["mlp_in"][1, 0]).astype(dtype)
        chosen, scores = jax.device_get(jax.jit(lambda p, x: ref.router(x, p, m)[::2])(lp["moe"], f32(x)))
        wide = runner.wide_gaps(scores, jax.device_get(lp["moe"]["router_bias"]), k, spec["tie_margin"])
        for name, alone in wrong_experts.items():
            line[name] = dict(runner.expert_gradients(alone, lp, x, rows, seed),
                              expert_layer_rel=alone.output(lp, x))
            got, _, s = jax.device_get(jax.jit(
                lambda mp, x, f=named(EXPERT_FAULTS[name]): ref.router(x, mp, m, **f))(lp["moe"], f32(x)))
            agree, wrong = runner.choice_readings(np.sort(got, -1), np.sort(chosen, -1), wide)
            line[name].update(router_scores_rel=float(np.abs(s - scores).max() / np.abs(scores).max()),
                              router_choice_agreement=agree, router_wrong_choice_share=wrong)
        # the whole model at fault: what the whole-model limits read
        want = jax.device_get({key: want[key] for key in kept})
        biases = runner.biases_of(params)

        def whole(**faults):
            got = jax.device_get(jax.jit(lambda p, t, l: {
                key: v for key, v in ref.forward(p, t[None], l[None], m, last, keep_inputs=False, **faults).items()
                if key in kept})(params, tokens, labels))
            return runner.whole_model_readings(got, want, biases, k, spec["tie_margin_whole_model"])[0]

        for name, f in MODEL_FAULTS.items() if whole_model else ():
            line[name] = whole(**f)
        for name, f in HC_FAULTS.items() if whole_model else ():
            line[name].update(whole(hc_faults=named(f)))
        if whole_model:
            line["scale_without_m2"].update(whole(attention_faults=ATTENTION_FAULTS["scale_without_m2"]))
        if not adam:
            return line
        # Adam's first step moves an element by rate x g / (|g| + eps): a leaf whose gradients sit
        # near eps (1e-8) moves by less than the rate, which is what step_update_shortfall reads
        step_params = jax.tree_util.tree_map_with_path(
            lambda path, p: p if jax.tree_util.keystr(path).endswith(runner.BIAS) else p.astype(dtype),
            harness.init_params(model, seed))
        grads = jax.jit(jax.grad(lambda *a: model.apply(*a)[0]))(step_params, tokens[None], labels[None])
        moved = {jax.tree_util.keystr(path): (
            float(jnp.sqrt(jnp.mean(jnp.square(f32(g) / (jnp.abs(f32(g)) + 1e-8))))),
            float(jnp.sqrt(jnp.mean(jnp.square(f32(g))))))
            for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]
            if not jax.tree_util.keystr(path).endswith(runner.BIAS)}
        outside = [name for name in moved if "['hc_" not in name]
        least = min(outside, key=lambda name: moved[name][0])
        line["adam_first_step"] = {"least_moved_leaf": least, "moved_over_rate": moved[least][0],
                                   "rms_gradient": moved[least][1], "predicted_shortfall": 1.0 - moved[least][0],
                                   "leaves_under_0.95": {name: [round(moved[name][0], 4), moved[name][1]]
                                                         for name in sorted(moved) if moved[name][0] < 0.95}}
        return line

    for seed in seeds:
        yield one_seed(seed)       # a seed's 3 GB of parameters die with its frame


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="2147484101,2147484102")
    parser.add_argument("--alone-only", action="store_true",
                        help="skip the faults inside the whole model (a compile of the reference each) and "
                             "Adam's first step (a gradient program of the system)")
    args = parser.parse_args()
    from benchmarks.manifest import Manifest
    os.makedirs("chiprun_out", exist_ok=True)
    for line in probe(Manifest(), "xing4.0-29b-a4b-ep8-d5", "packed_docs_4k_v16384",
                      [int(s) for s in args.seeds.split(",")], whole_model=not args.alone_only,
                      adam=not args.alone_only):
        text = json.dumps(line)
        print(text, flush=True)
        with open("chiprun_out/xing_precision_probe.jsonl", "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
