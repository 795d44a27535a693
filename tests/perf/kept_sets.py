"""One hybrid cell with another kept set than the model's, for the choice of what a recomputed
layer keeps (``granite_hybrid.KEPT_BY_A_BLOCK``, ``nemotron_h.KEPT_BY_A_LAYER``,
``mellum.KEPT_BY_A_LAYER``, ``glm_moe.KEPT_BY_A_LAYER``, ``lfm2_moe.KEPT_BY_A_LAYER``,
``xing_moe.KEPT_BY_A_LAYER``; PERF.md, PR 41, PR 45, PR 48, PR 52, PR 58).

    chiprun --timeout 1800 -- python tests/perf/kept_sets.py --workload granite4h_d10_train_1chip \
        --keep attn_out,attn_lse,mixer_out --seed 4100000101 --seconds 40 --trace 0

The cell runs through ``benchmarks/run.py``'s own ``run_cell`` and its result line is printed as
the command prints it. ``--keep`` is ``model`` (the constant as the tree has it), ``none`` (only
a layer's input: ``policy=None``) or the names a layer keeps, comma-separated. The constant is
replaced in this process alone: the program has no option for it.
"""

import argparse
import json
import sys

sys.path.insert(0, ".")

CONSTANTS = {"granite4h_d10_train_1chip": ("deepspeed_tpu.models.granite_hybrid", "KEPT_BY_A_BLOCK"),
             "nemotronh_ep16_d9_train_1chip": ("deepspeed_tpu.models.nemotron_h", "KEPT_BY_A_LAYER"),
             "mellum2_ep4_d4_train_1chip": ("deepspeed_tpu.models.mellum", "KEPT_BY_A_LAYER"),
             "glm47flash_ep8_d5_train_1chip": ("deepspeed_tpu.models.glm_moe", "KEPT_BY_A_LAYER"),
             "lfm2_ep8_d7_train_1chip": ("deepspeed_tpu.models.lfm2_moe", "KEPT_BY_A_LAYER"),
             "xing4_ep8_d5_train_1chip": ("deepspeed_tpu.models.xing_moe", "KEPT_BY_A_LAYER")}


def main():
    from benchmarks import run
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True, choices=sorted(CONSTANTS))
    parser.add_argument("--keep", default="model")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    if args.keep != "model":
        import importlib
        import jax
        module, constant = CONSTANTS[args.workload]
        policy = None if args.keep == "none" else \
            jax.checkpoint_policies.save_only_these_names(*args.keep.split(","))
        setattr(importlib.import_module(module), constant, policy)
    print(json.dumps(run.run_cell(args.workload, args.seed, args.seconds, bool(args.trace))), flush=True)


if __name__ == "__main__":
    main()
