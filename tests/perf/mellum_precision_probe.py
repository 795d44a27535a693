"""Both readings behind the limits of ``benchmarks/reference/mellum_tolerances.json``, at
``mellum2-12b-a2.5b-ep4-d4``'s full widths on one chip:

    chiprun --timeout 2400 -- python tests/perf/mellum_precision_probe.py [--seeds 11,12,...]

For every seed: the system's readings (``benchmarks/runners/train_swa_moe.check_reference``, as
the cell's set-up takes them, and its gradients leaf by leaf), and the same comparisons with the
plain reference itself at fault in the system's place, each layer alone on the reference's own
inputs: a sliding layer without its window, a full layer with one, each kind under the other's
rotary table, ``attention_factor`` dropped, YaRN's ramp without truncation, query head ``a``
reading key/value head ``a mod 4``, a bfloat16 softmax; the expert layer with its top-8 weights
not renormalised and with a bfloat16 router; the kernel's edge probe under windows of 1,023 and
1,025 and under none; then the whole model with every layer of a kind at fault. A limit has to
lie above the system's largest reading and below the fault's smallest. One JSON line a seed on
stdout and in ``chiprun_out/mellum_precision_probe.jsonl``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

SLIDING, FULL = "sliding_attention", "full_attention"


def attention_faults(ref, m):
    """``{name: (kind, keywords of ref.attention)}``: the reference at fault."""
    import jax.numpy as jnp
    table = {kind: ref.rotary_table(m, kind) for kind in (SLIDING, FULL)}
    return {
        "no_window_on_a_sliding_layer": (SLIDING, {"window": None}),
        "a_window_on_the_full_layer": (FULL, {"window": m["sliding_window"]}),
        "the_plain_table_on_the_full_layer": (FULL, {"table": table[SLIDING]}),
        "yarn_on_a_sliding_layer": (SLIDING, {"table": table[FULL]}),
        "attention_factor_dropped": (FULL, {"table": ref.rotary_table(m, FULL, scaled=False)}),
        "ramp_without_truncation": (FULL, {"table": ref.rotary_table(m, FULL, truncate=False)}),
        "key_value_head_a_mod_4": (SLIDING, {"kv_head": "strided"}),
        "bf16_softmax": (FULL, {"softmax_dtype": jnp.bfloat16}),
        "bf16_softmax_on_a_sliding_layer": (SLIDING, {"softmax_dtype": jnp.bfloat16}),
    }


EXPERT_FAULTS = {"weights_not_renormalised": {"renormalised": False},
                 "bf16_router": {"router_dtype": "bfloat16"}}


def probe(manifest, config_name, traffic_name, seeds, by_leaf=True, whole_model=True):
    """One dict a seed: ``system`` and, under its name, each fault's readings (``by_leaf``: the
    system's gradients leaf by leaf too; ``whole_model``: every fault inside the whole model too,
    a compile each)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import harness
    runner = manifest._module("runners", "train_swa_moe")

    config, traffic = manifest.config(config_name), manifest.traffic(traffic_name)
    m, spec = config["model"], config["reference"]
    coef = config["assumed"]["router_aux_loss_coef"][1]
    ref = manifest.reference(spec["module"])
    generate = manifest.generator(traffic["generator"])
    model = runner.build_model(config)
    c = model.config
    dtype, k = c.compute_dtype, m["num_experts_per_tok"]
    rows, last = spec["grad_positions"], spec["last_positions"]
    mixer = ("wq", "wkv", "q_norm", "k_norm", "wo")
    f32 = lambda x: x.astype(jnp.float32)        # the system's place is handed the compute dtype's rows   # noqa: E731
    named = lambda f: {a: getattr(jnp, b) if a.endswith("dtype") else b for a, b in f.items()}   # noqa: E731
    wrong_attention = {
        name: (kind, runner.Alone(lambda p, x, kind=kind, f=f: ref.attention(f32(x), p, m, kind, **f),
                                  lambda p, x, kind=kind: ref.attention(x, p, m, kind)))
        for name, (kind, f) in attention_faults(ref, m).items() if kind in c.kinds}
    plain_experts = lambda p, x: ref.expert_layer(x[0], p, m)[0][None]        # noqa: E731
    wrong_experts = {name: runner.Alone(
        lambda p, x, f=named(f): ref.expert_layer(f32(x[0]), p, m, **f)[0][None], plain_experts)
        for name, f in EXPERT_FAULTS.items()}
    system_attention = {kind: runner.Alone(lambda p, x, kind=kind: model.attention(x, p, kind),
                                           lambda p, x, kind=kind: ref.attention(x, p, m, kind))
                        for kind in set(c.kinds)}

    def leaf_by_leaf(alone, lp, x, seed):
        tail = x[None, -rows:]
        cot = jnp.asarray(np.random.default_rng(seed).standard_normal(tail.shape), jnp.float32)
        got = jax.device_get(alone.grads[0](lp, tail, cot))
        want = jax.device_get(alone.grads[1](lp, tail.astype(jnp.float32), cot))
        return {jax.tree_util.keystr(path): runner._rel_l2(g, w) for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want))}

    def one_seed(seed):
        params = harness.init_params(model, seed)
        batches, _ = generate(traffic, seed, vocab=m["vocab_size"], batch=1, n_batches=1)
        tokens, labels = batches[0][0][0], batches[0][1][0]
        ctx = {"config": config, "manifest": manifest, "seed": seed}
        system = runner.check_reference(ctx, model, params, tokens, labels)
        line = {"seed": seed, "device": jax.devices()[0].device_kind, "system": system}
        want = jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], m, coef, last))(params, tokens, labels)
        at = {kind: c.kinds.index(kind) for kind in set(c.kinds)}
        inputs = {kind: jnp.asarray(want["attn_in"][l, 0]).astype(dtype) for kind, l in at.items()}
        grad_input = jnp.asarray(want["attn_in"][0, 0]).astype(dtype)      # as compare_layers reads them
        layers = {kind: {name: params["layers"][l][name] for name in mixer} for kind, l in at.items()}
        if by_leaf:
            line["system_grad_by_leaf"] = {kind: leaf_by_leaf(system_attention[kind], layers[kind], grad_input, seed)
                                           for kind in at}
            line["system_grad_by_leaf_on_the_layers_own_input"] = {
                kind: leaf_by_leaf(system_attention[kind], layers[kind], inputs[kind], seed) for kind in at}
        for name, (kind, alone) in wrong_attention.items():
            key = runner.KINDS[kind]
            line[name] = {key + "_rel": alone.output(layers[kind], inputs[kind]),
                          key + "_grad_rel": alone.gradients(layers[kind], grad_input, rows, seed)}
        for name, window in (("edge_window_1023", c.sliding_window - 1), ("edge_window_1025", c.sliding_window + 1),
                             ("edge_no_window", None)):
            line[name] = {"edge_probe_abs_err": runner.edge_probe(model, ref, tokens.shape[0], window)}
        line["ramp_without_truncation"]["rotary_table_rel"] = runner.table_distance(
            model, ref, m, truncate=False)
        line["attention_factor_dropped"]["rotary_table_rel"] = runner.table_distance(
            model, ref, m, scaled=False)
        lp = {"moe": params["layers"][0]["moe"]}
        x = jnp.asarray(want["expert_in"][0, 0]).astype(dtype)
        chosen, probs = jax.device_get(jax.jit(lambda p, x: ref.router(x, p, m)[::2])(lp["moe"], f32(x)))
        wide = runner.wide_gaps(probs, 0.0, k, spec["tie_margin"])
        for name, alone in wrong_experts.items():
            line[name] = {"expert_layer_rel": alone.output(lp, x),
                          "expert_layer_grad_rel": alone.gradients(lp, x, rows, seed)}
            got, _, p, _ = jax.device_get(jax.jit(
                lambda mp, x, f=named(EXPERT_FAULTS[name]): ref.router(x, mp, m, **f))(lp["moe"], f32(x)))
            agree, wrong = runner.choice_readings(np.sort(got, -1), np.sort(chosen, -1), wide)
            line[name].update(router_probs_rel=float(np.abs(p - probs).max() / np.abs(probs).max()),
                              router_choice_agreement=agree, router_wrong_choice_share=wrong)
        # the whole model with every layer of a kind at fault: what the whole-model limits read
        want = jax.device_get({key: want[key] for key in ("loss", "logits", "experts", "probs")})
        wide = runner.wide_gaps(want["probs"], 0.0, k, spec["tie_margin_whole_model"])

        def whole(**faults):
            got = jax.device_get(jax.jit(lambda p, t, l: {
                key: v for key, v in ref.forward(p, t[None], l[None], m, coef, last, **faults).items()
                if key in ("loss", "logits", "experts")})(params, tokens, labels))
            agree, wrong = runner.choice_readings(got["experts"], want["experts"], wide)
            return dict(train_loss_rel=abs(float(got["loss"]) - float(want["loss"])) / abs(float(want["loss"])),
                        last_logits_rel=runner._rel_l2(got["logits"][0], want["logits"][0]),
                        expert_agreement=agree, expert_wrong_choice_share=wrong)

        for name, (kind, f) in attention_faults(ref, m).items() if whole_model else ():
            if kind in c.kinds:
                line[name].update(whole(attention_faults={kind: f}))
        for name, f in EXPERT_FAULTS.items() if whole_model else ():
            line[name].update(whole(expert_faults=named(f)))
        # Adam's first step moves an element by rate x g / (|g| + eps): a leaf whose gradients sit
        # near eps (1e-8) moves by less than the rate, which is what step_update_shortfall reads
        step_params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
        grads = jax.jit(jax.grad(lambda *a: model.apply(*a)[0]))(step_params, tokens[None], labels[None])
        moved = {jax.tree_util.keystr(path): (
            float(jnp.sqrt(jnp.mean(jnp.square(f32(g) / (jnp.abs(f32(g)) + 1e-8))))),
            float(jnp.sqrt(jnp.mean(jnp.square(f32(g))))))
            for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
        least = min(moved, key=lambda name: moved[name][0])
        line["adam_first_step"] = {"least_moved_leaf": least, "moved_over_rate": moved[least][0],
                                   "rms_gradient": moved[least][1], "predicted_shortfall": 1.0 - moved[least][0],
                                   "leaves_under_0.95": sorted(name for name in moved if moved[name][0] < 0.95)}
        return line

    for seed in seeds:
        yield one_seed(seed)       # a seed's 2.4 GB of parameters die with its frame


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="2147484001,2147484002")
    parser.add_argument("--alone-only", action="store_true",
                        help="skip every fault inside the whole model (a compile of the reference each)")
    args = parser.parse_args()
    from benchmarks.manifest import Manifest
    os.makedirs("chiprun_out", exist_ok=True)
    for line in probe(Manifest(), "mellum2-12b-a2.5b-ep4-d4", "packed_docs_8k_v24576",
                      [int(s) for s in args.seeds.split(",")], whole_model=not args.alone_only):
        text = json.dumps(line)
        print(text, flush=True)
        with open("chiprun_out/mellum_precision_probe.jsonl", "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
