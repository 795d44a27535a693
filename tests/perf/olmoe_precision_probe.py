"""Both readings behind every limit of ``benchmarks/reference/olmoe_tolerances.json``, at
``olmoe-1b-7b-d4``'s full widths and depth on ONE chip (the experts are not split there;
gathering them moves bits and changes none):

    chiprun -- python tests/perf/olmoe_precision_probe.py [--seeds 11,12,...]

For every seed: the system's readings (``benchmarks/runners/train_moe.check_reference``,
as the cell's set-up takes them), and the same comparison with the plain reference itself
computed one precision down in place of the system, every expert layer alone on the
reference's own inputs: the router in bfloat16, the expert weights rounded through
float8_e4m3fn. A limit has to lie above the system's largest reading and below the lower
precision's smallest. One JSON line a seed on stdout and in ``chiprun_out/olmoe_precision_probe.jsonl``.
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
    from benchmarks.runners import train_moe

    config, traffic = manifest.config(config_name), manifest.traffic(traffic_name)
    m = config["model"]
    ref = manifest.reference(config["reference"]["module"])
    generate = manifest.generator(traffic["generator"])
    mesh = train_moe._mesh(jax.devices()[:1])
    model = train_moe.build_model(config)
    dtype = model.config.compute_dtype

    def through(low, top):
        """A tensor scaled so that its largest magnitude is ``top``, rounded to ``low`` and
        back; gradients pass straight through. The barrier keeps the pair of converts: the
        TPU compiler drops a narrowing round trip that it can see whole
        (``xla_allow_excess_precision``)."""
        def rounded(w):
            scale = top / jnp.max(jnp.abs(w))
            low_w = jax.lax.optimization_barrier((w * scale).astype(low)).astype(w.dtype) / scale
            return w + jax.lax.stop_gradient(low_w - w)
        return rounded

    plain = train_moe.reference_layer_fn(ref, m, dtype)
    lower = {
        # on the TPU the default precision of a float32 product is one bfloat16 pass, so
        # this is also what a router without ``precision=HIGHEST`` reads
        "bf16_router": dict(router_dtype=jnp.bfloat16, prec=jax.lax.Precision.DEFAULT),
        "fp8_expert_weights": dict(round_weights=through(jnp.float8_e4m3fn, 448.0)),
        # the configuration's own precision, for scale: this one has to pass
        "bf16_expert_weights": dict(round_weights=through(jnp.bfloat16, 1.0)),
    }
    lower = {name: train_moe.reference_layer_fn(ref, m, dtype, **how) for name, how in lower.items()}

    def one_seed(seed):
        params = train_moe.init_params(model, seed, mesh)
        batches, _ = generate(traffic, seed, vocab=m["vocab_size"], batch=1, n_batches=1)
        ctx = {"config": config, "manifest": manifest, "seed": seed}
        system, expert_in = train_moe.check_reference(
            ctx, model, params, mesh, batches[0][0][0], batches[0][1][0])
        line = {"seed": seed, "device": jax.devices()[0].device_kind, "system": system}
        moe = [lp["moe"] for lp in params["layers"]]
        for name, layer in lower.items():
            line[name] = train_moe.compare_expert_layers(layer, plain, moe, moe, expert_in, seed)
        return line

    for seed in seeds:
        yield one_seed(seed)       # a seed's 7.5 GB of parameters die with its frame


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="2147484001,2147484002,2147484003,2147484004")
    args = parser.parse_args()
    from benchmarks.manifest import Manifest
    os.makedirs("chiprun_out", exist_ok=True)
    for line in probe(Manifest(), "olmoe-1b-7b-d4", "packed_docs_4k",
                      [int(s) for s in args.seeds.split(",")]):
        text = json.dumps(line)
        print(text, flush=True)
        with open("chiprun_out/olmoe_precision_probe.jsonl", "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
