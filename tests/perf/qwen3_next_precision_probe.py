"""Both readings behind the limits of ``benchmarks/reference/qwen3_next_tolerances.json``
that a lower precision has to fail, at ``qwen3-next-80b-a3b-ep16-d4``'s full widths on one
chip:

    chiprun -- python tests/perf/qwen3_next_precision_probe.py [--seeds 11,12,...]

For every seed: the system's readings (``benchmarks/runners/train_hybrid.check_reference``,
as the cell's set-up takes them), and the same comparisons with the plain reference itself
computed one precision down in place of the system, each layer alone on the reference's own
inputs: the delta rule's state rounded to bfloat16 after every token (the rule alone, and
the whole mixer), the router in bfloat16. A limit has to lie above the system's largest
reading and below the lower precision's smallest. One JSON line a seed on stdout and in
``chiprun_out/qwen3_next_precision_probe.jsonl``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))


def probe(manifest, config_name, traffic_name, seeds):
    """One dict a seed: ``system`` and, under its name, each lower precision's readings."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import harness
    runner = manifest._module("runners", "train_hybrid")

    config, traffic = manifest.config(config_name), manifest.traffic(traffic_name)
    m = config["model"]
    ref = manifest.reference(config["reference"]["module"])
    generate = manifest.generator(traffic["generator"])
    model = runner.build_model(config)
    dtype = model.config.compute_dtype
    rows = config["reference"]["grad_positions"]
    plain_mixer = lambda p, x: ref.linear_mixer(x, p, m)                       # noqa: E731
    plain_experts = lambda p, x: ref.expert_layer(x[0], p, m)[0][None]          # noqa: E731
    low_router = dict(router_dtype=jnp.bfloat16, prec=jax.lax.Precision.DEFAULT)
    lower = {
        "mixer": runner.Alone(lambda p, x: ref.linear_mixer(x, p, m, state_dtype=jnp.bfloat16), plain_mixer),
        # on the TPU the default precision of a float32 product is one bfloat16 pass, so
        # this is also what a router without ``precision=HIGHEST`` reads
        "expert_layer": runner.Alone(
            lambda p, x: ref.expert_layer(x[0].astype(jnp.float32), p, m, **low_router)[0][None], plain_experts),
    }
    delta_rule = runner.DeltaRuleAlone(ref, m, dtype, state_dtype=jnp.bfloat16)
    routed = [jax.jit(lambda p, x, how=how: ref.expert_layer(x, p, m, **how)[1::2])
              for how in (low_router, {})]

    def one_seed(seed):
        params = harness.init_params(model, seed)
        batches, _ = generate(traffic, seed, vocab=m["vocab_size"], batch=1, n_batches=1)
        ctx = {"config": config, "manifest": manifest, "seed": seed}
        system, inputs = runner.check_reference(ctx, model, params, batches[0][0][0], batches[0][1][0])
        line = {"seed": seed, "device": jax.devices()[0].device_kind, "system": system}
        lp = params["layers"][0]
        x = jnp.asarray(inputs["mixer_in"][0, 0]).astype(dtype)
        y, g = delta_rule.read(lp["mixer"], x, rows, seed)
        line["bf16_state"] = {"delta_rule_rel": y, "delta_rule_grad_rel": g,
                              "mixer_rel": lower["mixer"].output(lp["mixer"], x),
                              "mixer_grad_rel": lower["mixer"].gradients(lp["mixer"], x, rows, seed)}
        x = jnp.asarray(inputs["expert_in"][0, 0]).astype(dtype)
        part = {"moe": lp["moe"], "shared": lp["shared"]}
        (chosen, logits), (want_chosen, want_logits) = (
            jax.device_get(fn(lp, x.astype(jnp.float32))) for fn in routed)
        line["bf16_router"] = {
            "router_logits_rel": float(np.abs(logits - want_logits).max() / np.abs(want_logits).max()),
            "router_choice_agreement": float(np.mean(np.all(
                np.sort(chosen, -1) == np.sort(want_chosen, -1), axis=-1))),
            "expert_layer_rel": lower["expert_layer"].output(part, x),
            "expert_layer_grad_rel": lower["expert_layer"].gradients(part, x, rows, seed)}
        return line

    for seed in seeds:
        yield one_seed(seed)       # a seed's 2.5 GB of parameters die with its frame


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="2147484001,2147484002,2147484003")
    args = parser.parse_args()
    from benchmarks.manifest import Manifest
    os.makedirs("chiprun_out", exist_ok=True)
    for line in probe(Manifest(), "qwen3-next-80b-a3b-ep16-d4", "packed_docs_8k",
                      [int(s) for s in args.seeds.split(",")]):
        text = json.dumps(line)
        print(text, flush=True)
        with open("chiprun_out/qwen3_next_precision_probe.jsonl", "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
