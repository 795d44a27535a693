"""Registry of representative test-scale engine programs for ``ds-tpu lint``.

Each entry builds a real engine on the 8-virtual-device CPU mesh (the same
mesh the tier-1 HLO tests pin collectives on) and captures every program on
its active step path via ``engine.lint_programs`` — the engines themselves
declare the expected-collective manifests. Entries cover the step-path matrix
the bespoke tests grew one file at a time: standard two-jit ZeRO-2, the comm
modes, ZeRO-Offload's host-tier split, and the instruction-executor
pipeline's per-stage programs.

The lint model computes in the engine's compute dtype (params enter already
cast; inputs are cast once at the boundary) — unlike the test-suite
SimpleModel, which casts params to ``x.dtype`` and therefore runs f32 dots
that would (correctly!) trip the dtype-promotion pass. The seeded-violation
fixtures use exactly that trick.
"""

import jax
import jax.numpy as jnp
import numpy as np

from .program_passes import ProgramArtifact

HIDDEN = 32
BATCH = 8


class LintModel:
    """Two-layer MLP that computes in the dtype the engine handed it params
    in, with only the loss in f32 — the clean low-precision reference shape."""

    def __init__(self, hidden_dim=HIDDEN):
        self.hidden_dim = hidden_dim

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        h = self.hidden_dim
        return {"w1": jax.random.normal(k1, (h, h), jnp.float32) * 0.1,
                "b1": jnp.zeros((h,), jnp.float32),
                "w2": jax.random.normal(k2, (h, h), jnp.float32) * 0.1,
                "b2": jnp.zeros((h,), jnp.float32)}

    def apply(self, params, x, y):
        dt = params["w1"].dtype
        h = jnp.tanh(x.astype(dt) @ params["w1"] + params["b1"])
        out = h @ params["w2"] + params["b2"]
        return jnp.mean(jnp.square(out.astype(jnp.float32) - y))


def _config(batch=BATCH, **overrides):
    cfg = {"train_batch_size": batch, "steps_per_print": 1000,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}}
    cfg.update(overrides)
    return cfg


def _sample_batch(rng_seed=0, batch=BATCH, hidden=HIDDEN):
    rng = np.random.default_rng(rng_seed)
    x = rng.normal(size=(batch, hidden)).astype(np.float32)
    return x, np.tanh(x)


def _build_standard():
    import deepspeed_tpu
    model = LintModel()
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=_config(zero_optimization={"stage": 2}))
    return eng, _sample_batch()


def _build_comm_hierarchical():
    # two-level ICI+DCN grad exchange (uncompressed): reduce-scatter/all-gather
    # ride inside the 2x4 slice factorization, one fp32 psum crosses slices
    import deepspeed_tpu
    model = LintModel()
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=_config(
            zero_optimization={"stage": 2},
            comm={"mode": "hierarchical", "dcn_slices": 2}))
    return eng, _sample_batch()


def _build_comm_compressed():
    # error-feedback 1-bit cross-slice exchange: the DCN phases ship packed u8
    # signs (all-to-all + all-gather) and fp32 per-segment scales
    import deepspeed_tpu
    model = LintModel()
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=_config(
            zero_optimization={"stage": 2},
            comm={"mode": "hierarchical_compressed", "dcn_slices": 2}))
    return eng, _sample_batch()


def _build_comm_overlap():
    # bucketed overlapped exchange over the two-level topology: 0.004 MB
    # buckets split the LintModel into three EQUAL padded buckets
    # ((b1, b2) / (w1) / (w2), 1024 elements each), so the backward issues
    # three independent reduce-scatter/psum/all-gather chains and every
    # bucket's ICI phases fit under the other buckets' in-flight DCN wire
    # (docs/overlap.md)
    import deepspeed_tpu
    model = LintModel()
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=_config(
            zero_optimization={"stage": 2},
            comm={"mode": "hierarchical", "dcn_slices": 2,
                  "overlap": {"mode": "bucketed", "bucket_mb": 0.004}}))
    if len(eng._overlap_plan) != 3:
        raise RuntimeError("lint registry: comm_overlap entry expects the "
                           f"equal 3-bucket plan, got {eng._overlap_plan}")
    return eng, _sample_batch()


def _build_comm_overlap_compressed():
    # bucketed compressed exchange: per-bucket 1-bit DCN phases with the
    # bucketed error-feedback layout — bucket k's all-to-all can overlap
    # bucket k+1's ICI reduce-scatter
    import deepspeed_tpu
    model = LintModel()
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=_config(
            zero_optimization={"stage": 2},
            comm={"mode": "hierarchical_compressed", "dcn_slices": 2,
                  "overlap": {"mode": "bucketed", "bucket_mb": 0.004}}))
    return eng, _sample_batch()


def _build_zero_offload():
    import deepspeed_tpu
    model = LintModel()
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=_config(zero_optimization={"stage": 2,
                                                 "cpu_offload": True}))
    return eng, _sample_batch()


def _build_pipeline():
    # instruction executor, not SPMD: differentiating through the SPMD
    # executor's shard_map needs jax >= 0.5 (tests/unit/oldjax.py), and the
    # registry must capture the same programs on every supported jax. The
    # per-stage local jits are the instruction path's real step programs.
    import deepspeed_tpu
    from ..parallel.pipe import LayerSpec, PipelineModule

    class Dense:
        def __init__(self, dim):
            self.dim = dim

        def init(self, rng, x):
            return {"w": jax.random.normal(rng, (x.shape[-1], self.dim),
                                           jnp.float32) * 0.3}

        def apply(self, p, x):
            return jnp.tanh(x.astype(p["w"].dtype) @ p["w"])

    def mse(out, tgt):
        return jnp.mean(jnp.square(out.astype(jnp.float32)
                                   - tgt.astype(jnp.float32)))

    module = PipelineModule(layers=[LayerSpec(Dense, HIDDEN) for _ in range(4)],
                            num_stages=4, loss_fn=mse)
    params = module.init_params(jax.random.PRNGKey(0),
                                jnp.zeros((4, HIDDEN), jnp.float32))
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=module, model_parameters=params,
        config_params={"train_batch_size": 64, "gradient_accumulation_steps": 2,
                       "steps_per_print": 1000,
                       "pipeline": {"spmd": False},
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
    if eng._spmd:
        raise RuntimeError("lint registry: pipeline entry must stay on the "
                           "instruction executor")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, HIDDEN)).astype(np.float32)  # one micro-batch
    return eng, (x, np.tanh(x))


def _tiny_gpt2():
    from ..models.gpt2 import GPT2Config, GPT2Model
    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=16, n_layer=2,
                     n_head=2, compute_dtype=jnp.float32, loss_chunk=0)
    model = GPT2Model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


class _DecodeLintAdapter:
    """Engine-shaped wrapper so the gpt2 decode programs (prefill + greedy +
    beam, models/gpt2.py decode_lint_programs) ride the same capture path."""

    def __init__(self, model, params):
        self.model, self.params = model, params

    def lint_programs(self, sample_batch=None):
        return self.model.decode_lint_programs(self.params)

    def memory_manifest(self):
        # params are the only persistent device residents on the dense
        # decode path (caches are per-call arguments, not engine state)
        leaves = jax.tree_util.tree_leaves(self.params)
        psi = sum(int(np.prod(l.shape)) if l.shape else 1 for l in leaves)
        itemsize = int(jnp.dtype(leaves[0].dtype).itemsize) if leaves else 4
        return {"classes": {"params": self.params},
                "geometry": {"kind": "decode", "psi": psi,
                             "param_itemsize": itemsize}}


def _build_gpt2_decode():
    return _DecodeLintAdapter(*_tiny_gpt2()), None


def _build_serving():
    # fixed-shape paged serving programs: decode step, prefill chunk, CoW
    # page copy — the zero-recompile contract ds-tpu serve-sim replays
    from ..serve.engine import InferenceEngine
    model, params = _tiny_gpt2()
    eng = InferenceEngine(model, params, num_slots=4, block_size=4,
                          num_blocks=17, max_model_len=32, prefill_chunk=8)
    return eng, None


def _build_serving_speculative():
    # speculative decoding: the target-side spec_verify program (K+1-wide
    # chunked-prefill-shaped verification over the paged pool) plus the
    # draft-side decode/prefill programs over the draft's own small pool.
    # Self-draft (same model+params) keeps the builder cheap; the programs
    # are shape-identical to a real small-draft deployment. Only the spec
    # programs are captured here — the engine's base decode/prefill/copy
    # programs are geometry-identical to the ``serving`` entry's and already
    # linted there; re-lowering them would double the entry's cost for zero
    # extra coverage
    from ..serve.engine import InferenceEngine
    model, params = _tiny_gpt2()
    eng = InferenceEngine(model, params, num_slots=4, block_size=4,
                          num_blocks=17, max_model_len=32, prefill_chunk=8,
                          speculation={"enabled": True, "draft_model": model,
                                       "draft_params": params,
                                       "max_draft_tokens": 2})

    class _SpecPrograms:
        def lint_programs(self, sample_batch=None):
            return [e for e in eng.lint_programs(sample_batch)
                    if "spec" in e[0]]

        def memory_manifest(self):
            # the wrapped engine's full resident set: the entry captures only
            # the spec programs, so target-only classes report as unobserved
            # in the hbm sweep (resident, but outside this program subset)
            return eng.memory_manifest()

    return _SpecPrograms(), None


def _build_serving_sharded():
    # model-axis sharded serving: same programs lowered over a 2-way head
    # shard. The manifests tighten to a collective BUDGET — decode/prefill
    # must contain exactly n_layer f32 all-reduces (the per-layer proj psum)
    # and nothing else; copy_blocks must stay collective-free (the block axis
    # is unsharded, so GSPMD has nothing to exchange)
    from ..serve.engine import InferenceEngine
    model, params = _tiny_gpt2()
    eng = InferenceEngine(model, params, num_slots=4, block_size=4,
                          num_blocks=17, max_model_len=32, prefill_chunk=8,
                          sharding={"model": 2})
    return eng, None


BUILDERS = {
    "standard": _build_standard,
    "comm_hierarchical": _build_comm_hierarchical,
    "comm_compressed": _build_comm_compressed,
    "comm_overlap": _build_comm_overlap,
    "comm_overlap_compressed": _build_comm_overlap_compressed,
    "zero_offload": _build_zero_offload,
    "pipeline": _build_pipeline,
    "gpt2_decode": _build_gpt2_decode,
    "serving": _build_serving,
    "serving_speculative": _build_serving_speculative,
    "serving_sharded": _build_serving_sharded,
}


def capture_entry(entry):
    """[ProgramArtifact] for one registry entry, program names prefixed
    ``entry:program``."""
    engine, batch = BUILDERS[entry]()
    artifacts = []
    for name, jitted, args, manifest in engine.lint_programs(batch):
        artifacts.append(ProgramArtifact.capture(f"{entry}:{name}", jitted,
                                                 args, manifest))
    return artifacts


def capture_registry(entries=None):
    """Artifacts for the requested entries (default: all, in name order)."""
    names = sorted(BUILDERS) if not entries else list(entries)
    out = []
    for entry in names:
        out.extend(capture_entry(entry))
    return out
