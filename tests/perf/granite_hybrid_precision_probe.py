"""Both readings behind the limits of ``benchmarks/reference/granite_hybrid_tolerances.json``,
at ``granite-4.0-h-micro-d10``'s full widths on one chip:

    chiprun -- python tests/perf/granite_hybrid_precision_probe.py [--seeds 11,12,...]

For every seed: the system's readings (``benchmarks/runners/train_ssm.check_reference``, as
the cell's set-up takes them), and the same comparisons with the plain reference itself at
fault in the system's place, each layer alone on the reference's own inputs: the scan's state
rounded to bfloat16 after every token, its step (and with it the decay) rounded to bfloat16,
the mixer normed before it is gated, the mixer without its ``D`` skip, the attention scaled
by ``D^-1/2`` (1/8) in place of the published 1/64; and the whole model with each of the four
multipliers left at 1. A limit has to lie above the system's largest reading and below the
fault's smallest. One JSON line a seed on stdout and in
``chiprun_out/granite_hybrid_precision_probe.jsonl``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

MULTIPLIERS = ("embedding_multiplier", "residual_multiplier", "attention_multiplier", "logits_scaling")


def probe(manifest, config_name, traffic_name, seeds):
    """One dict a seed: ``system`` and, under its name, each fault's readings."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import harness
    runner = manifest._module("runners", "train_ssm")

    config, traffic = manifest.config(config_name), manifest.traffic(traffic_name)
    m = config["model"]
    ref = manifest.reference(config["reference"]["module"])
    generate = manifest.generator(traffic["generator"])
    model = runner.build_model(config)
    dtype, chunk = model.config.compute_dtype, model.config.mamba_chunk_size
    rows, last = config["reference"]["grad_positions"], config["reference"]["last_positions"]
    plain_mixer = lambda p, x: ref.mamba_mixer(x, p, m)                       # noqa: E731
    head_scale = (m["hidden_size"] // m["num_attention_heads"]) ** -0.5
    wrong_layers = {
        "norm_before_gate": ("mixer", runner.Alone(
            lambda p, x: ref.mamba_mixer(x, p, m, gate_first=False), plain_mixer)),
        "no_D_skip": ("mixer", runner.Alone(
            lambda p, x: ref.mamba_mixer(x, dict(p, D=jnp.zeros_like(p["D"])), m), plain_mixer)),
        "bf16_state_mixer": ("mixer", runner.Alone(
            lambda p, x: ref.mamba_mixer(x, p, m, state_dtype=jnp.bfloat16), plain_mixer)),
        "head_width_scale": ("attention", runner.Alone(
            lambda p, x: ref.attention(x, p, dict(m, attention_multiplier=head_scale)),
            lambda p, x: ref.attention(x, p, m))),
    }
    wrong_scans = {"bf16_state": runner.ScanAlone(ref, m, dtype, chunk, state_dtype=jnp.bfloat16),
                   "bf16_dt": runner.ScanAlone(ref, m, dtype, chunk, dt_dtype=jnp.bfloat16)}

    def ref_forward(params, tokens, labels, mult):
        return jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], dict(m, **mult), last))(
            params, tokens, labels)

    def one_seed(seed):
        params = harness.init_params(model, seed)
        batches, _ = generate(traffic, seed, vocab=m["vocab_size"], batch=1, n_batches=1)
        tokens, labels = batches[0][0][0], batches[0][1][0]
        ctx = {"config": config, "manifest": manifest, "seed": seed}
        system, mixer_in = runner.check_reference(ctx, model, params, tokens, labels)
        line = {"seed": seed, "device": jax.devices()[0].device_kind, "system": system}
        kinds = m["layer_types"][:m["num_hidden_layers"]]
        first = {"mixer": kinds.index("mamba"), "attention": kinds.index("attention")}
        for name, (kind, alone) in wrong_layers.items():
            lp, x = params["layers"][first[kind]], jnp.asarray(mixer_in[first[kind], 0]).astype(dtype)
            line[name] = {kind + "_rel": alone.output(lp["mixer"], x),
                          kind + "_grad_rel": alone.gradients(lp["mixer"], x, rows, seed)}
        lp, x = params["layers"][first["mixer"]], jnp.asarray(mixer_in[first["mixer"], 0]).astype(dtype)
        for name, scan in wrong_scans.items():
            y, g = scan.read(lp["mixer"], x, rows, seed)
            line[name] = {"scan_rel": y, "scan_grad_rel": g}
        del mixer_in
        want = jax.device_get({k: v for k, v in ref_forward(params, tokens, labels, {}).items()
                               if k != "mixer_in"})
        for name in MULTIPLIERS:
            got = jax.device_get({k: v for k, v in ref_forward(params, tokens, labels, {name: 1.0}).items()
                                  if k != "mixer_in"})
            line["no_" + name] = {
                "train_loss_rel": abs(float(got["loss"]) - float(want["loss"])) / abs(float(want["loss"])),
                "last_logits_rel": float(np.abs(got["logits"][0] - want["logits"][0]).max()
                                         / np.abs(want["logits"][0]).max())}
        # Adam's first step moves an element by rate x g / (|g| + eps): a leaf whose gradients
        # sit near eps (1e-8) moves by less than the rate, which is what step_update_shortfall
        # reads of it. The system's own gradients on the sequence, blocks recomputed, in bf16
        step_params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
        grads = jax.jit(jax.grad(model.apply))(step_params, tokens[None], labels[None])
        moved = {jax.tree_util.keystr(path): (
            float(jnp.sqrt(jnp.mean(jnp.square(g.astype(jnp.float32) / (jnp.abs(g.astype(jnp.float32)) + 1e-8))))),
            float(jnp.sqrt(jnp.mean(jnp.square(g.astype(jnp.float32))))))
            for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
        least = min(moved, key=lambda k: moved[k][0])
        line["adam_first_step"] = {"least_moved_leaf": least, "moved_over_rate": moved[least][0],
                                   "rms_gradient": moved[least][1],
                                   "predicted_shortfall": 1.0 - moved[least][0],
                                   "leaves_under_0.95": sorted(k for k in moved if moved[k][0] < 0.95)}
        return line

    for seed in seeds:
        yield one_seed(seed)       # a seed's 3 GB of parameters die with its frame


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="2147484001,2147484002")
    args = parser.parse_args()
    from benchmarks.manifest import Manifest
    os.makedirs("chiprun_out", exist_ok=True)
    for line in probe(Manifest(), "granite-4.0-h-micro-d10", "packed_docs_8k_v12544",
                      [int(s) for s in args.seeds.split(",")]):
        text = json.dumps(line)
        print(text, flush=True)
        with open("chiprun_out/granite_hybrid_precision_probe.jsonl", "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
