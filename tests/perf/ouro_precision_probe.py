"""Both readings behind the limits of ``benchmarks/reference/ouro_tolerances.json``, at
``ouro-2.6b-d6``'s full widths on one chip:

    chiprun --timeout 2400 -- python tests/perf/ouro_precision_probe.py [--seeds 11,12,...] [--only shared,head]

For every seed: the system's readings (``benchmarks/runners/train_loop.check_reference``, as
the cell's set-up takes them), and the same comparisons with the plain reference itself at
fault in the system's place: the gate's logit rounded to bfloat16 and the exit distribution
and its entropy made in bfloat16; the logits rounded to bfloat16 before the softmax and the
loss after it (the whole model, and the head alone); a shared leaf's four contributions
rounded to bfloat16 and added in bfloat16, each pass's contribution lost in turn and the last
pass's halved; the head's
kept ``softmax - onehot`` in bfloat16 (what the system keeps) and in float8; the loss without
its entropy term; a block without
its norms AFTER the branches; the un-normed stream carried into the next pass. Then what Adam's first step moves of every leaf
(``rms(g / (|g| + eps))`` of the system's own gradients), which is what
``step_update_shortfall`` reads. A limit has to lie above the system's largest reading and
below the fault's smallest. One JSON line a seed on stdout and in
``chiprun_out/ouro_precision_probe.jsonl``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

WHOLE = ("loss", "exit_ce", "p", "logits")


def probe(manifest, config_name, traffic_name, seeds, only=None):
    """One dict a seed: ``system`` and, under its name, each fault's readings; ``only`` names the
    groups of faults to read (``whole``, ``shared``, ``head``, ``adam``; all where None)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import harness
    runner = manifest._module("runners", "train_loop")

    config, traffic = manifest.config(config_name), manifest.traffic(traffic_name)
    m, beta = config["model"], config["exit_entropy_coef"]
    T = m["total_ut_steps"]
    ref = manifest.reference(config["reference"]["module"])
    generate = manifest.generator(traffic["generator"])
    model = runner.build_model(config)
    dtype = model.config.compute_dtype
    rows, last = config["reference"]["grad_positions"], config["reference"]["last_positions"]

    def ref_forward(params, tokens, labels, beta=beta, **fault):
        out = jax.jit(lambda p, t, l: ref.forward(p, t[None], l[None], m, beta, last, **fault))(
            params, tokens, labels)
        return jax.device_get({k: out[k] for k in WHOLE})

    plain_pass = lambda p, x: ref.one_pass(p, x, m)                                      # noqa: E731
    bare_pass = runner.Alone(lambda p, x: ref.one_pass(p, x, m, sandwich=False), plain_pass)
    bf16_head = runner.HeadAlone(lambda x, head, labels: ref.cross_entropy(x, head, labels, jnp.bfloat16)[0],
                                 lambda x, head, labels: ref.cross_entropy(x, head, labels)[0])

    def whole(line, params, tokens, labels, want, seed):
        """The whole model, one pass, the exits and the head's losses, the reference at fault."""
        first, final = want["states"][0, 0].astype(dtype), want["states"][-1].astype(dtype)
        rounded = want["states"].astype(dtype).astype(jnp.float32)
        exact = jax.device_get({k: want[k] for k in WHOLE})
        for name, fault in (("bf16_exit", {"exit_dtype": jnp.bfloat16}),
                            ("bf16_cross_entropy", {"ce_dtype": jnp.bfloat16}),
                            ("no_entropy_term", {"beta": 0.0}),
                            ("no_norm_after", {"sandwich": False}),
                            ("no_norm_carried", {"carry_norm": False})):
            line[name] = runner.read_whole(ref_forward(params, tokens, labels, **fault), exact)
        line["bf16_exit"].update(runner.exits_alone(
            lambda states, gate: ref.exits(states, gate, jnp.bfloat16), ref.exits, rounded, params["gate"]))
        line["bf16_cross_entropy"].update(bf16_head.read(final, params["head"], jnp.asarray(labels)[None], seed))
        blocks = {"layers": params["layers"], "norm_f": params["norm_f"]}
        line["no_norm_after"].update(pass_rel=bare_pass.output(blocks, first),
                                     pass_grad_rel=bare_pass.gradients(blocks, first, rows, seed))

    def shared(line, params, tokens, labels, want, seed):
        """A shared leaf's gradient from the reference's own four contributions, at fault, through
        the cell's own comparison (``train_loop.read_shared``)."""
        by_pass = jax.device_get(jax.jit(lambda p, t, l: ref.shared_gradient_by_pass(
            p, t[None], l[None], m, beta, 0, runner.SHARED))(params, tokens[:rows], labels[:rows]))
        exact = ref.sum_over_passes(by_pass)
        faults = {"bf16_pass_sum": ref.sum_over_passes(by_pass, sum_dtype=jnp.bfloat16),
                  # a cotangent carried wrongly from the last pass to the one before: half of it arrives
                  "last_pass_halved": {k: exact[k] - 0.5 * by_pass[-1][k] for k in exact}}
        faults.update({f"pass{t}_lost": ref.sum_over_passes(by_pass, without=t) for t in range(T)})
        for name, got in faults.items():
            line[name] = runner.read_shared(jax.device_get(got), by_pass)
        total = {k: np.linalg.norm(np.asarray(v, np.float64)) for k, v in exact.items()}
        line["pass_share_of_sum"] = {k: [float(np.linalg.norm(np.asarray(one[k], np.float64)) / total[k])
                                         for one in by_pass] for k in exact}

    def head(line, params, tokens, labels, want, seed):
        """The head's gradients with the kept softmax - onehot at the system's precision and below."""
        cot = jnp.asarray(np.random.default_rng(seed).standard_normal(labels.shape), jnp.float32)[None]
        args = (want["states"][-1].astype(dtype).astype(jnp.float32), params["head"], jnp.asarray(labels)[None], cot)
        kept = {d: jax.device_get(jax.jit(lambda *a, d=d: ref.head_gradients(*a, kept_dtype=d))(*args))
                for d in (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn)}
        for name, d in (("bf16_kept_head_gradient", jnp.bfloat16), ("float8_kept_head_gradient", jnp.float8_e4m3fn)):
            line[name] = {"head_grad_rel": runner._worst_leaf(kept[d], kept[jnp.float32])}

    def adam(line, params, tokens, labels, want, seed):
        """Adam's first step moves an element by rate x g / (|g| + eps): a leaf whose gradients sit
        near eps (1e-8) moves by less than the rate, which is what step_update_shortfall reads of
        it. The system's own gradients on the sequence, blocks recomputed, in bf16."""
        step_params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
        grads = jax.jit(jax.grad(lambda p, t, l: model.apply(p, t, l)[0]))(step_params, tokens[None], labels[None])
        moved = {}
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
            g = g.astype(jnp.float32)
            if jax.tree_util.keystr(path) == "['embed']":        # the rows of the tokens it saw
                g = g[jnp.unique(jnp.asarray(tokens))]
            moved[jax.tree_util.keystr(path)] = (float(jnp.sqrt(jnp.mean(jnp.square(g / (jnp.abs(g) + 1e-8))))),
                                                 float(jnp.sqrt(jnp.mean(jnp.square(g)))))
        least = min(moved, key=lambda k: moved[k][0])
        line["adam_first_step"] = {"least_moved_leaf": least, "moved_over_rate": moved[least][0],
                                   "rms_gradient": moved[least][1],
                                   "predicted_shortfall": 1.0 - moved[least][0],
                                   "gate": {k: moved[k] for k in moved if "['gate']" in k},
                                   "leaves_under_0.95": sorted(k for k in moved if moved[k][0] < 0.95)}

    groups = {"whole": whole, "shared": shared, "head": head, "adam": adam}

    def one_seed(seed):
        params = harness.init_params(model, seed)
        batches, _ = generate(traffic, seed, vocab=m["vocab_size"], batch=1, n_batches=1)
        tokens, labels = batches[0][0][0], batches[0][1][0]
        ctx = {"config": config, "manifest": manifest, "seed": seed}
        system, want = runner.check_reference(ctx, model, params, tokens, labels)
        line = {"seed": seed, "device": jax.devices()[0].device_kind, "system": system}
        for name in only or groups:
            groups[name](line, params, tokens, labels, want, seed)
        return line

    for seed in seeds:
        yield one_seed(seed)       # a seed's 2 GB of parameters die with its frame


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="2147484001,2147484002")
    parser.add_argument("--only", default="", help="groups of faults, of whole,shared,head,adam (all where empty)")
    args = parser.parse_args()
    from benchmarks.manifest import Manifest
    os.makedirs("chiprun_out", exist_ok=True)
    for line in probe(Manifest(), "ouro-2.6b-d6", "packed_docs_4k_v49152",
                      [int(s) for s in args.seeds.split(",")], [g for g in args.only.split(",") if g]):
        text = json.dumps(line)
        print(text, flush=True)
        with open("chiprun_out/ouro_precision_probe.jsonl", "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
