"""Both readings behind the limits of ``benchmarks/reference/lfm2_moe_tolerances.json``, at
``lfm2-24b-a2b-ep8-d7``'s full widths on one chip:

    chiprun --timeout 2400 -- python tests/perf/lfm2_precision_probe.py [--seeds 11,12,...]

For every seed: the system's readings (``benchmarks/runners/train_conv_moe.check_reference``, as the
cell's set-up takes them), and the same comparisons with the plain reference itself at fault in the
system's place. Each kind alone on the reference's own inputs: the short convolution with its taps
reversed, its window a token ahead, its parts taken in another order, its first gate left out, a SiLU
after it; the attention with q and k unnormed, with bfloat16 rotary angles (the nearest precision
below the float32 the configuration states for them), with a bfloat16 softmax; the expert layer with
a bfloat16 router (the nearest precision below its float32) and with its chosen scores not
renormalised; the dense MLP with the activation on the other half. The whole model: an untied head, and
(unless ``--alone-only``) every fault above inside the whole model. A limit has to lie above the
system's largest reading and below the fault's smallest. One JSON line a seed on stdout and in
``chiprun_out/lfm2_precision_probe.jsonl``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

CONV_FAULTS = {"taps_reversed": {"taps": "reversed"}, "window_a_token_ahead": {"taps": "ahead"},
               "parts_in_another_order": {"gates": "B*C,z"}, "first_gate_left_out": {"gates": "B,C"},
               "silu_after_the_taps": {"activation": "silu"}}
ATTENTION_FAULTS = {"head_norms_skipped": {"head_norms": False}, "bf16_rotary_angles": {"angle_dtype": "bfloat16"},
                    "bf16_softmax": {"softmax_dtype": "bfloat16"}}
EXPERT_FAULTS = {"bf16_router": {"router_dtype": "bfloat16"}, "weights_not_renormalised": {"renormalised": False}}
MODEL_FAULTS = {"head_untied": {"tied": False}}        # keywords of ``reference.forward``


def probe(manifest, config_name, traffic_name, seeds, whole_model=True):
    """One dict a seed: ``system`` and, under its name, each fault's readings (``whole_model``: the
    layers' faults inside the whole model too, a compile of the reference each)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import harness
    runner = manifest._module("runners", "train_conv_moe")

    config, traffic = manifest.config(config_name), manifest.traffic(traffic_name)
    m, spec = config["model"], config["reference"]
    eps = config["assumed"]["router_eps"][1]
    ref = manifest.reference(spec["module"])
    generate = manifest.generator(traffic["generator"])
    model = runner.build_model(config)
    dtype, k = model.config.compute_dtype, m["num_experts_per_tok"]
    rows, last = spec["grad_positions"], spec["last_positions"]
    f32 = lambda x: x.astype(jnp.float32)        # the system's place is handed the compute dtype's rows   # noqa: E731
    named = lambda f: {a: getattr(jnp, b) if a.endswith("dtype") else b for a, b in f.items()}   # noqa: E731
    wrong_conv = {name: runner.Alone(lambda p, x, f=f: ref.short_conv(f32(x), p, m, **f),
                                     lambda p, x: ref.short_conv(x, p, m)) for name, f in CONV_FAULTS.items()}
    wrong_attention = {name: runner.Alone(lambda p, x, f=named(f): ref.attention(f32(x), p, m, **f),
                                          lambda p, x: ref.attention(x, p, m)) for name, f in ATTENTION_FAULTS.items()}
    plain_experts = lambda p, x: ref.expert_layer(x[0], p, m, eps)[0][None]        # noqa: E731
    wrong_experts = {name: runner.Alone(
        lambda p, x, f=named(f): ref.expert_layer(f32(x[0]), p, m, eps, **f)[0][None], plain_experts)
        for name, f in EXPERT_FAULTS.items()}
    wrong_dense = runner.Alone(lambda p, x: ref.dense_mlp(f32(x), p, halves="up|gate"), lambda p, x: ref.dense_mlp(x, p))
    kept = ("loss", "logits", "experts", "scores")

    def one_seed(seed):
        params = harness.init_params(model, seed)
        # the cell's own sequence: its last batch of as many as its set-up makes
        batches, _ = generate(traffic, seed, vocab=m["vocab_size"], batch=1, n_batches=traffic["batches_ahead"])
        tokens, labels = batches[-1][0][0], batches[-1][1][0]
        del batches
        ctx = {"config": config, "manifest": manifest, "seed": seed}
        line = {"seed": seed, "device": jax.devices()[0].device_kind,
                "system": runner.check_reference(ctx, model, params, tokens, labels)}
        params = runner.seeded_biases(params, seed)           # as check_reference compares
        want = jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], m, eps, last))(params, tokens, labels)
        first_input = jnp.asarray(want["op_in"][0, 0]).astype(dtype)
        layers = params["layers"]
        conv_at = [l for l, lp in enumerate(layers) if "conv" in lp]
        attention_at = [l for l, lp in enumerate(layers) if "attn" in lp]
        experts_at = [l for l, lp in enumerate(layers) if "moe" in lp]
        # the LAST layer of a kind: its input has been through the layers before it
        cp, x = layers[conv_at[-1]]["conv"], jnp.asarray(want["op_in"][conv_at[-1], 0]).astype(dtype)
        for name, alone in wrong_conv.items():
            line[name] = {"short_conv_rel": alone.output(cp, x),
                          "short_conv_grad_rel": alone.gradients(layers[conv_at[0]]["conv"], first_input, rows, seed)}
        ap, x = layers[attention_at[-1]]["attn"], jnp.asarray(want["op_in"][attention_at[-1], 0]).astype(dtype)
        for name, alone in wrong_attention.items():
            line[name] = {"attention_rel": alone.output(ap, x),
                          "attention_grad_rel": alone.gradients(layers[attention_at[0]]["attn"], first_input, rows, seed)}
        dp, x = layers[0]["mlp"], jnp.asarray(want["ff_in"][0, 0]).astype(dtype)
        line["activation_on_the_other_half"] = {"dense_mlp_rel": wrong_dense.output(dp, x),
                                                "dense_mlp_grad_rel": wrong_dense.gradients(dp, x, rows, seed)}
        mp, x = layers[experts_at[0]]["moe"], jnp.asarray(want["ff_in"][experts_at[0], 0]).astype(dtype)
        chosen, scores = jax.device_get(jax.jit(lambda p, x: ref.router(x, p, m, eps)[::2])(mp, f32(x)))
        wide = runner.wide_gaps(scores, jax.device_get(mp["router_bias"]), k, spec["tie_margin"])
        for name, alone in wrong_experts.items():
            line[name] = dict(runner.expert_gradients(alone, mp, x, rows, seed), expert_layer_rel=alone.output(mp, x))
            got, _, s = jax.device_get(jax.jit(
                lambda p, x, f=named(EXPERT_FAULTS[name]): ref.router(x, p, m, eps, **f))(mp, f32(x)))
            agree, wrong = runner.choice_readings(np.sort(got, -1), np.sort(chosen, -1), wide)
            line[name].update(router_scores_rel=float(np.abs(s - scores).max() / np.abs(scores).max()),
                              router_choice_agreement=agree, router_wrong_choice_share=wrong)
        # the whole model at fault: what the whole-model limits read
        want = jax.device_get({key: want[key] for key in kept})
        biases = runner.biases_of(params)

        def whole(**faults):
            got = jax.device_get(jax.jit(lambda p, t, l: {
                key: v for key, v in ref.forward(p, t[None], l[None], m, eps, last, **faults).items()
                if key in kept})(params, tokens, labels))
            return runner.whole_model_readings(got, want, biases, k, spec["tie_margin_whole_model"])[0]

        for name, f in MODEL_FAULTS.items():
            line[name] = whole(**f)
        if whole_model:
            for name, f in CONV_FAULTS.items():
                line[name].update(whole(conv_faults=f))
            for name, f in ATTENTION_FAULTS.items():
                line[name].update(whole(attention_faults=named(f)))
            for name, f in EXPERT_FAULTS.items():
                line[name].update(whole(expert_faults=named(f)))
        return line

    for seed in seeds:
        yield one_seed(seed)       # a seed's 2.6 GB of parameters die with its frame


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="2147484201,2147484202")
    parser.add_argument("--alone-only", action="store_true",
                        help="skip the layers' faults inside the whole model (a compile of the reference each)")
    args = parser.parse_args()
    from benchmarks.manifest import Manifest
    os.makedirs("chiprun_out", exist_ok=True)
    for line in probe(Manifest(), "lfm2-24b-a2b-ep8-d7", "packed_docs_8k_v8192",
                      [int(s) for s in args.seeds.split(",")], whole_model=not args.alone_only):
        text = json.dumps(line)
        print(text, flush=True)
        with open("chiprun_out/lfm2_precision_probe.jsonl", "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
