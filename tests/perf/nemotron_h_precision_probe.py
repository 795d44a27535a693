"""Both readings behind the limits of ``benchmarks/reference/nemotron_h_tolerances.json``, at
``nemotron-twotower-30b-a3b-ep16-d9``'s full widths on one chip:

    chiprun --timeout 2400 -- python tests/perf/nemotron_h_precision_probe.py [--seeds 11,12,...]

For every seed: the system's readings (``benchmarks/runners/train_ssm_moe.check_reference``, as
the cell's set-up takes them), and the same comparisons with the plain reference itself at
fault in the system's place, each layer alone on the reference's own inputs: the scan with its
state rounded to bfloat16 after every token, with its step (and the decay) rounded to
bfloat16, with every head reading the NEXT group's B; the mixer normed over all channels at
once; the attention with every query head reading the OTHER key/value head; the expert layer (under a seeded selection bias, as the cell compares it) with ``relu``
for ``relu^2``, the bias left out of the choice, the bias let into the weights, the factor 2.5
dropped, and the router's scores made in bfloat16. A limit has to lie above the system's
largest reading and below the fault's smallest. One JSON line a seed on stdout and in
``chiprun_out/nemotron_h_precision_probe.jsonl``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))


def probe(manifest, config_name, traffic_name, seeds):
    """One dict a seed: ``system`` and, under its name, each fault's readings."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import harness
    runner = manifest._module("runners", "train_ssm_moe")

    config, traffic = manifest.config(config_name), manifest.traffic(traffic_name)
    m, spec = config["model"], config["reference"]
    ref = manifest.reference(spec["module"])
    generate = manifest.generator(traffic["generator"])
    model = runner.build_model(config)
    dtype, chunk, k = model.config.compute_dtype, model.config.chunk_size, m["num_experts_per_tok"]
    rows, last = spec["grad_positions"], spec["last_positions"]
    plain_mixer = lambda p, x: ref.mamba_mixer(x, p, m)                       # noqa: E731
    plain_experts = lambda p, x: ref.expert_layer(x[0], p, m)[0][None]        # noqa: E731
    f32 = lambda x: x.astype(jnp.float32)        # the system's place is handed the compute dtype's rows   # noqa: E731
    wrong_mixers = {
        "norm_over_all_channels": runner.Alone(lambda p, x: ref.mamba_mixer(f32(x), p, m, norm_groups=1), plain_mixer),
        "next_groups_B_mixer": runner.Alone(lambda p, x: ref.mamba_mixer(f32(x), p, m, shift_groups=1), plain_mixer),
    }
    wrong_attention = runner.Alone(lambda p, x: ref.attention(f32(x), p, m, shift_kv_heads=1),
                                   lambda p, x: ref.attention(x, p, m))
    wrong_scans = {"bf16_state": dict(state_dtype=jnp.bfloat16), "bf16_dt": dict(dt_dtype=jnp.bfloat16),
                   "next_groups_B": dict(shift_groups=1)}
    wrong_scans = {name: runner.ScanAlone(ref, m, dtype, chunk, **lower) for name, lower in wrong_scans.items()}
    router_faults = {"relu_for_relu2": dict(act=jax.nn.relu), "bias_left_out_of_the_choice": dict(bias_in="none"),
                     "bias_let_into_the_weights": dict(bias_in="weight"), "factor_dropped": dict(scaled=False),
                     "bf16_router": dict(router_dtype=jnp.bfloat16, prec=None)}
    wrong_experts = {name: runner.Alone(lambda p, x, f=fault: ref.expert_layer(f32(x[0]), p, m, **f)[0][None], plain_experts)
                     for name, fault in router_faults.items()}

    def one_seed(seed):
        params = runner.seeded_biases(harness.init_params(model, seed), seed)    # as the cell compares
        batches, _ = generate(traffic, seed, vocab=m["vocab_size"], batch=1, n_batches=1)
        tokens, labels = batches[0][0][0], batches[0][1][0]
        ctx = {"config": config, "manifest": manifest, "seed": seed}
        system = runner.check_reference(ctx, model, params, tokens, labels)
        line = {"seed": seed, "device": jax.devices()[0].device_kind, "system": system}
        layer_in = jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], m, last)["layer_in"])(
            params, tokens, labels)
        kinds = m["hybrid_override_pattern"][:m["num_hidden_layers"]]
        at = {kind: kinds.index(kind) for kind in "ME*"}
        lp, x = params["layers"][at["*"]]["mixer"], jnp.asarray(layer_in[at["*"], 0]).astype(dtype)
        line["other_key_value_head"] = {"attention_rel": wrong_attention.output(lp, x),
                                        "attention_grad_rel": wrong_attention.gradients(lp, x, rows, seed)}
        lp, x = params["layers"][at["M"]]["mixer"], jnp.asarray(layer_in[at["M"], 0]).astype(dtype)
        for name, alone in wrong_mixers.items():
            line[name] = {"mixer_rel": alone.output(lp, x), "mixer_grad_rel": alone.gradients(lp, x, rows, seed)}
        for name, scan in wrong_scans.items():
            y, g = scan.read(lp, x, rows, seed)
            line[name] = {"scan_rel": y, "scan_grad_rel": g}
        lp, x = params["layers"][at["E"]], jnp.asarray(layer_in[at["E"], 0]).astype(dtype)
        lp, bias = {"moe": lp["moe"], "shared": lp["shared"]}, lp["moe"]["router_bias"]
        chosen, scores = jax.device_get(jax.jit(lambda p, x: ref.router(x, p, m)[::2])(lp["moe"], x.astype(jnp.float32)))
        wide = runner.wide_gaps(scores, jax.device_get(bias), k, spec["tie_margin"])
        for name, alone in wrong_experts.items():
            out = {}
            line[name] = {"expert_layer_rel": alone.output(lp, x),
                          "expert_layer_grad_rel": runner.gradients_alone(alone, lp, x, rows, seed, out)}
            got, _, s = jax.device_get(jax.jit(
                lambda p, x, f=router_faults[name]: ref.router(x, p, m, **{a: b for a, b in f.items() if a != "act"}))(
                lp["moe"], x.astype(jnp.float32)))
            agree, wrong = runner.choice_readings(np.sort(got, -1), np.sort(chosen, -1), wide)
            line[name].update(router_scores_rel=float(np.abs(s - scores).max() / np.abs(scores).max()),
                              router_choice_agreement=agree, router_wrong_choice_share=wrong)
        del layer_in
        # the whole model with every expert layer at fault: what the whole-model limits read
        whole = jax.jit(lambda p, t, l, **f: {k: v for k, v in ref.forward(p, t[None], l[None], m, last, **f).items()
                                              if k in ("loss", "logits", "experts", "scores")},
                        static_argnames=("act", "bias_in", "scaled", "router_dtype", "prec"))
        want = jax.device_get(whole(params, tokens, labels))
        biases = np.stack([jax.device_get(l["moe"]["router_bias"]) for l in params["layers"] if "moe" in l])
        wide = runner.wide_gaps(want["scores"], biases[:, None, None, :], k, spec["tie_margin_whole_model"])
        for name, fault in router_faults.items():
            got = jax.device_get(whole(params, tokens, labels, **fault))
            agree, wrong = runner.choice_readings(got["experts"], want["experts"], wide)
            line[name].update(
                train_loss_rel=abs(float(got["loss"]) - float(want["loss"])) / abs(float(want["loss"])),
                last_logits_rel=runner._rel_l2(got["logits"][0], want["logits"][0]),
                expert_agreement=agree, expert_wrong_choice_share=wrong)
        # Adam's first step moves an element by rate x g / (|g| + eps): a leaf whose gradients sit
        # near eps (1e-8) moves by less than the rate, which is what step_update_shortfall reads
        step_params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
        grads = jax.jit(jax.grad(lambda *a: model.apply(*a)[0]))(step_params, tokens[None], labels[None])
        moved = {jax.tree_util.keystr(path): (
            float(jnp.sqrt(jnp.mean(jnp.square(g.astype(jnp.float32) / (jnp.abs(g.astype(jnp.float32)) + 1e-8))))),
            float(jnp.sqrt(jnp.mean(jnp.square(g.astype(jnp.float32))))))
            for path, g in jax.tree_util.tree_flatten_with_path(grads)[0] if "router_bias" not in jax.tree_util.keystr(path)}
        least = min(moved, key=lambda name: moved[name][0])
        line["adam_first_step"] = {"least_moved_leaf": least, "moved_over_rate": moved[least][0],
                                   "rms_gradient": moved[least][1], "predicted_shortfall": 1.0 - moved[least][0],
                                   "leaves_under_0.95": sorted(name for name in moved if moved[name][0] < 0.95)}
        return line

    for seed in seeds:
        yield one_seed(seed)       # a seed's 2.7 GB of parameters die with its frame


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="2147484001,2147484002")
    args = parser.parse_args()
    from benchmarks.manifest import Manifest
    os.makedirs("chiprun_out", exist_ok=True)
    for line in probe(Manifest(), "nemotron-twotower-30b-a3b-ep16-d9", "packed_docs_8k_v16384",
                      [int(s) for s in args.seeds.split(",")]):
        text = json.dumps(line)
        print(text, flush=True)
        with open("chiprun_out/nemotron_h_precision_probe.jsonl", "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
