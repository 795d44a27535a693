"""Both readings behind the limits of ``benchmarks/reference/glm_moe_tolerances.json``, at
``glm-4.7-flash-ep8-d5``'s full widths on one chip:

    chiprun --timeout 2400 -- python tests/perf/glm_precision_probe.py [--seeds 11,12,...]

For every seed: the system's readings (``benchmarks/runners/train_mla_moe.check_reference``, as the
cell's set-up takes them), and the same comparisons with the plain reference itself at fault in the
system's place. Each kind alone on the reference's own inputs: the latent mixer with its rotary key
left out, with a key of its own a head, under the scale of the 192 features without position, with
the key/value latent unnormed, with a bfloat16 softmax; the expert layer with a bfloat16 router and
with factor 1.0. The whole model: the module fed ``t_i``, ``L_2`` dropped or weighted 1, block 0 run
as an expert layer, and (unless ``--alone-only``) every fault above inside the whole model; last, what
Adam's first step would take off each leaf from the gradients' sizes (``adam_first_step``: what
``step_update_shortfall`` is read against). A limit has to lie above the system's largest reading and
below the fault's smallest. One JSON line a seed on
stdout and in ``chiprun_out/glm_precision_probe.jsonl``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

ATTENTION_FAULTS = {"rotary_key_left_out": {"rotary_key": "left_out"},
                    "rotary_key_a_head_its_own": {"rotary_key": "a_head_its_own"},
                    "latent_norm_skipped": {"latent_norm": False},
                    "bf16_softmax": {"softmax_dtype": "bfloat16"}}
EXPERT_FAULTS = {"bf16_router": {"router_dtype": "bfloat16"}, "factor_1": {"factor": 1.0}}
# keywords of ``reference.forward``; ``mtp_weight`` takes the configuration's place
MODEL_FAULTS = {"module_fed_t_i": {"mtp_embeds": "current"}, "l2_dropped": {"mtp_weight": 0.0},
                "l2_weighted_1": {"mtp_weight": 1.0}, "block_0_as_experts": {"first_block_dense": False}}


def probe(manifest, config_name, traffic_name, seeds, whole_model=True, adam=True):
    """One dict a seed: ``system`` and, under its name, each fault's readings (``whole_model``: the
    layers' faults inside the whole model too, a compile of the reference each; ``adam``: what Adam's
    first step would take off each leaf, a gradient program of the system)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import harness
    runner = manifest._module("runners", "train_mla_moe")

    config, traffic = manifest.config(config_name), manifest.traffic(traffic_name)
    m, spec = config["model"], config["reference"]
    weight = config["assumed"]["mtp_loss_weight"][1]
    ref = manifest.reference(spec["module"])
    generate = manifest.generator(traffic["generator"])
    model = runner.build_model(config)
    dtype, k = model.config.compute_dtype, m["num_experts_per_tok"]
    rows, last = spec["grad_positions"], spec["last_positions"]
    f32 = lambda x: x.astype(jnp.float32)        # the system's place is handed the compute dtype's rows   # noqa: E731
    named = lambda f: {a: getattr(jnp, b) if a.endswith("dtype") else b for a, b in f.items()}   # noqa: E731
    # the scale of the features without position alone: 1 / sqrt(192) at the published widths
    attention_faults = dict(ATTENTION_FAULTS, scale_of_the_nope_width={"scale_width": m["qk_nope_head_dim"]})
    wrong_attention = {name: runner.Alone(lambda p, x, f=named(f): ref.attention(f32(x), p, m, **f),
                                          lambda p, x: ref.attention(x, p, m))
                       for name, f in attention_faults.items()}
    plain_experts = lambda p, x: ref.expert_layer(x[0], p, m)[0][None]        # noqa: E731
    wrong_experts = {name: runner.Alone(
        lambda p, x, f=named(f): ref.expert_layer(f32(x[0]), p, m, **f)[0][None], plain_experts)
        for name, f in EXPERT_FAULTS.items()}
    system_experts = runner.Alone(lambda p, x: model.expert_layer(x, p)[0], plain_experts)
    kept = ("loss", "loss_main", "loss_mtp", "logits", "logits_mtp", "experts", "scores")

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
        want = jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], m, weight, last))(params, tokens, labels)
        first_input = jnp.asarray(want["attn_in"][0, 0]).astype(dtype)
        # the second block: the first whose input has been through a mixer and an MLP
        ap, x = params["layers"][1]["attn"], jnp.asarray(want["attn_in"][1, 0]).astype(dtype)
        for name, alone in wrong_attention.items():
            line[name] = {"latent_attention_rel": alone.output(ap, x),
                          "latent_attention_grad_rel": alone.gradients(ap, first_input, rows, seed)}
        lp = {name: params["layers"][1][name] for name in ("moe", "shared")}
        x = jnp.asarray(want["mlp_in"][1, 0]).astype(dtype)
        # the system's expert layer's gradients LEAF BY LEAF: error and size (the router's matrix has
        # a reading of its own, ``router_grad_rel``)
        tail = x[None, -rows:]
        cot = jnp.asarray(np.random.default_rng(seed).standard_normal(tail.shape), jnp.float32)
        got, theirs = (jax.device_get(g) for g in (system_experts.grads[0](lp, tail, cot),
                                                   system_experts.grads[1](lp, f32(tail), cot)))
        line["expert_layer_grad_by_leaf"] = {
            jax.tree_util.keystr(path): [runner._rel_l2(g, w), float(np.linalg.norm(np.asarray(w, np.float64)))]
            for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(theirs))
            if not jax.tree_util.keystr(path).endswith(runner.BIAS)}
        chosen, scores = jax.device_get(jax.jit(lambda p, x: ref.router(x, p, m)[::2])(lp["moe"], f32(x)))
        bias = jax.device_get(lp["moe"]["router_bias"])
        wide = runner.wide_gaps(scores, bias, k, spec["tie_margin"])
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

        def whole(mtp_weight=weight, **faults):
            got = jax.device_get(jax.jit(lambda p, t, l: {
                key: v for key, v in ref.forward(p, t[None], l[None], m, mtp_weight, last, **faults).items()
                if key in kept})(params, tokens, labels))
            got["experts"] = got["experts"][-len(want["experts"]):]      # block 0 as experts chooses too
            return runner.whole_model_readings(got, want, biases, k, spec["tie_margin_whole_model"])[0]

        for name, f in MODEL_FAULTS.items():
            line[name] = whole(**f)
        for name, f in attention_faults.items() if whole_model else ():
            line[name].update(whole(attention_faults=named(f)))
        for name, f in EXPERT_FAULTS.items() if whole_model else ():
            line[name].update(whole(expert_faults=named(f)))
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
        least = min(moved, key=lambda name: moved[name][0])
        line["adam_first_step"] = {"least_moved_leaf": least, "moved_over_rate": moved[least][0],
                                   "rms_gradient": moved[least][1], "predicted_shortfall": 1.0 - moved[least][0],
                                   "leaves_under_0.95": {name: round(moved[name][0], 4) for name in sorted(moved)
                                                         if moved[name][0] < 0.95}}
        return line

    for seed in seeds:
        yield one_seed(seed)       # a seed's 2.8 GB of parameters die with its frame


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="2147484101,2147484102")
    parser.add_argument("--alone-only", action="store_true",
                        help="skip the layers' faults inside the whole model (a compile of the reference each)")
    args = parser.parse_args()
    from benchmarks.manifest import Manifest
    os.makedirs("chiprun_out", exist_ok=True)
    for line in probe(Manifest(), "glm-4.7-flash-ep8-d5", "packed_docs_8k_v19360",
                      [int(s) for s in args.seeds.split(",")], whole_model=not args.alone_only):
        text = json.dumps(line)
        print(text, flush=True)
        with open("chiprun_out/glm_precision_probe.jsonl", "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
