"""Benchmark: the BASELINE.json metrics on one TPU chip, in one process.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device", "extra": {...}}.
There is no CPU mode: without a TPU the script exits non-zero before it measures
anything, and a phase that raises fails the run. Everything runs in this process (a
chip belongs to one process at a time). Timed windows end in ``block_until_ready``.
The peak FLOP/s comes from utils/roofline.py's table by ``device_kind``.

Headline metric = BASELINE.json's "tokens/sec/chip at 1.5B (ZeRO-2)": a GPT-2 1.5B
(1600x48, 25 heads) training step on one v5e chip — fwd+bwd over the full 1.5B bf16
parameters plus the 1/32 fp32 optimizer-shard update a single v5e-32 ZeRO-2 rank
performs (collectives excluded: they need the other 31 chips). vs_baseline =
measured MFU / 0.40.

extra:
- gpt2_420m_*: the round-1 flagship config (real DeepSpeedEngine, ZeRO-2, dp=1).
- max_trainable_params_per_chip_zero_offload: largest GPT-2 (1600 wide, deepening
  n_layer) whose ZeRO-Offload HBM footprint — bf16 params + bf16 grads + remat
  activations; master/moments live in host DRAM — completes fwd+bwd on the chip
  (binary search over n_layer; running out of device memory is the probe's signal).

Set DS_BENCH_FAST=1 to run only the 420M flagship (quick iteration).
"""

import gc
import json
import os
import sys
import time

import numpy as np

def peak_tflops():
    """Dense bf16 peak of the local chip, from utils/roofline.py's table by
    ``device_kind`` (a device that is not in the table raises)."""
    from deepspeed_tpu.utils.roofline import resolve_spec
    return resolve_spec().peak_tflops


def _fence(x):
    import jax
    return float(jax.block_until_ready(x))


# the metrics a round is compared on: headline + the per-block numbers that may
# regress while the headline holds
REGRESSION_KEYS = (
    "value",
    "extra.gpt2_420m_tokens_per_sec_per_chip",
    "extra.gpt2_1p5b_engine_tokens_per_sec",
    "extra.decode_420m.greedy_tok_s",
    "extra.serving_420m.tok_s",
    "extra.serving_420m.goodput_tok_s",
    # serving latency ledger: TTFT percentiles regress independently of tok/s
    # (e.g. a scheduler change that favors decode over prefill admission) —
    # note lower-is-better keys flag on RISES via the inverted delta below
    "extra.serving_420m.ttft_ms_p50",
    "extra.serving_420m.ttft_ms_p95",
    # prefix-cache efficacy + sharded-decode throughput
    "extra.serving_420m_prefix_cache.prefix_cache_hit_rate",
    "extra.serving_420m_prefix_cache.ttft_ms_p50",
    "extra.serving_420m_sharded.tok_s",
    # speculative decoding (docs/serving.md): how often the draft is right,
    # and how many target program executions each emitted token costs —
    # target_steps_per_token is lower-is-better (PERF.md defines the metric)
    "extra.serving_speculative.spec_acceptance_rate",
    "extra.serving_speculative.target_steps_per_token",
    "extra.serving_1p5b_spec.spec_acceptance_rate",
    "extra.serving_1p5b_spec.target_steps_per_token",
    # fleet router (docs/serving.md): merged tail latency across replicas,
    # shed share under the seeded burst, and the merged goodput fraction
    # after the scripted warm failover — p99/shed lower-is-better
    "extra.serving_fleet.fleet_p99_ttft_ms",
    "extra.serving_fleet.shed_rate",
    "extra.serving_fleet.shed_rate_2x_saturation",
    "extra.serving_fleet.goodput_fleet_fraction",
    # HBM observatory (docs/hbm.md): the smoke engine's per-class resident
    # bytes (engine.memory_manifest -> utils/hbm) and the compile-reported
    # temp peak — a RISE is a memory regression (all lower-is-better)
    "extra.hbm.peak_by_class.params",
    "extra.hbm.peak_by_class.grads",
    "extra.hbm.peak_by_class.master",
    "extra.hbm.peak_by_class.optimizer",
    "extra.hbm.peak_by_class.compiled_temp_peak",
    # measured-time profile observatory (docs/profile.md): per-step exposed
    # collective time and host gap from the smoke trace window (all
    # lower-is-better — a RISE means overlap regressed), plus the measured
    # window MFU beside the rolling estimate
    "extra.profile.exposed_ici_ms",
    "extra.profile.exposed_dcn_ms",
    "extra.profile.host_gap_ms",
    "extra.profile.measured_mfu",
    # resilience ledger: caller-thread checkpoint stall and the warm/cold
    # restart TTFT ratio (docs/resilience.md) — both lower-is-better
    "extra.resilience.checkpoint_stall_ms",
    "extra.resilience.restore_warm_vs_cold_ttft",
    # run-lifecycle goodput (docs/goodput.md): productive share of run wall,
    # and the checkpoint-fence share of it (lower-is-better)
    "extra.goodput.goodput_fraction",
    "extra.goodput.badput_checkpoint_pct",
)

# Every regression key maps to its declared metric in the MetricCatalog
# (deepspeed_tpu/utils/metrics.py) — the catalog's direction decides which
# way is worse, so bench keeps NO private lower-is-better list. A key whose
# metric resolves neutral (or not at all) is a declaration bug:
# tests/unit/test_metrics_catalog.py pins full coverage.
REGRESSION_KEY_METRICS = {
    "value": "Telemetry/Samples/samples_per_sec",
    "extra.gpt2_420m_tokens_per_sec_per_chip":
        "Telemetry/Samples/samples_per_sec",
    "extra.gpt2_1p5b_engine_tokens_per_sec":
        "Telemetry/Samples/samples_per_sec",
    "extra.decode_420m.greedy_tok_s": "Serving/tok_s",
    "extra.serving_420m.tok_s": "Serving/tok_s",
    "extra.serving_420m.goodput_tok_s": "Serving/goodput_tok_s",
    "extra.serving_420m.ttft_ms_p50": "Serving/Latency/ttft_ms_p50",
    "extra.serving_420m.ttft_ms_p95": "Serving/Latency/ttft_ms_p95",
    "extra.serving_420m_prefix_cache.prefix_cache_hit_rate":
        "Serving/PrefixCache/hit_rate",
    "extra.serving_420m_prefix_cache.ttft_ms_p50":
        "Serving/Latency/ttft_ms_p50",
    "extra.serving_420m_sharded.tok_s": "Serving/tok_s",
    "extra.serving_speculative.spec_acceptance_rate":
        "Serving/Spec/acceptance_rate",
    "extra.serving_speculative.target_steps_per_token":
        "Serving/Spec/target_steps_per_token",
    "extra.serving_1p5b_spec.spec_acceptance_rate":
        "Serving/Spec/acceptance_rate",
    "extra.serving_1p5b_spec.target_steps_per_token":
        "Serving/Spec/target_steps_per_token",
    "extra.serving_fleet.fleet_p99_ttft_ms":
        "Serving/Fleet/Latency/ttft_ms_p99",
    "extra.serving_fleet.shed_rate": "Serving/Fleet/shed",
    "extra.serving_fleet.shed_rate_2x_saturation": "Serving/Fleet/shed",
    "extra.serving_fleet.goodput_fleet_fraction":
        "Serving/Fleet/Goodput/fraction",
    "extra.hbm.peak_by_class.params": "Memory/params_bytes",
    "extra.hbm.peak_by_class.grads": "Memory/grads_bytes",
    "extra.hbm.peak_by_class.master": "Memory/master_bytes",
    "extra.hbm.peak_by_class.optimizer": "Memory/optimizer_bytes",
    "extra.hbm.peak_by_class.compiled_temp_peak":
        "Memory/compiled_temp_peak_bytes",
    "extra.profile.exposed_ici_ms": "Profile/exposed_ici_ms",
    "extra.profile.exposed_dcn_ms": "Profile/exposed_dcn_ms",
    "extra.profile.host_gap_ms": "Profile/host_gap_ms",
    "extra.profile.measured_mfu": "Profile/mfu",
    "extra.resilience.checkpoint_stall_ms":
        "Run/Goodput/checkpoint_stall_seconds",
    "extra.resilience.restore_warm_vs_cold_ttft": "Serving/ttft_ms",
    "extra.goodput.goodput_fraction": "Run/Goodput/goodput_fraction",
    "extra.goodput.badput_checkpoint_pct":
        "Run/Goodput/checkpoint_stall_seconds",
}


def lower_is_better_keys():
    """Regression keys whose metric the catalog declares lower-is-better —
    their delta sign is inverted before the flag check (a regression is a
    RISE). Lazy import: the catalog costs nothing but bench's module import
    must stay dependency-light."""
    from deepspeed_tpu.utils.metrics import default_catalog
    catalog = default_catalog()
    return frozenset(k for k, metric in REGRESSION_KEY_METRICS.items()
                     if catalog.direction(metric) == "lower_is_better")


def bench_420m():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.parallel.mesh import build_mesh

    # GPT-2-family ~420M flagship (tied LM head) shaped for one v5e chip: 1536-wide
    # matmuls keep the MXU fed; remat OFF — flash attention + seq-chunked fused CE keep
    # residuals small enough that batch 16 of full activations fits next to fp32 Adam.
    cfg = GPT2Config(vocab_size=50304, n_positions=1024, n_embd=1536, n_layer=12,
                     n_head=12, remat=False, use_flash_attention=True)
    batch, seq, steps = 16, 1024, 20
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = model.param_count(params)
    mesh = build_mesh(model=1, pipe=1)
    engine = DeepSpeedEngine(model=model, model_parameters=params, mesh=mesh,
                             config_params={
                                 "train_batch_size": batch,
                                 "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                                 "zero_optimization": {"stage": 2},
                             })
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)

    def step():
        loss = engine(tokens, labels)
        engine.backward(loss)
        engine.step()
        return loss

    # two warm-up steps: the step compiles twice (ROADMAP A7)
    step()
    _fence(step())
    # median-of-3 windows with the spread recorded: a best-of draw biases the
    # number high
    dts = []
    for _ in range(3):
        t0 = time.time()
        for _ in range(steps):
            loss = step()
        _fence(loss)
        dts.append(time.time() - t0)
    dts.sort()
    dt = dts[1]
    tps = batch * seq * steps / dt
    mfu = tps * 6.0 * n_params / 1e12 / peak_tflops()
    del engine, params
    gc.collect()
    out = {"gpt2_420m_tokens_per_sec_per_chip": round(tps, 1),
           "gpt2_420m_mfu": round(mfu, 4),
           "gpt2_420m_window_spread": round((dts[-1] - dts[0]) / dt, 4),
           "gpt2_420m_selection": f"median-of-3 {steps}-step windows"}
    out["gpt2_420m_telemetry"] = _telemetry_probe_420m(
        model, cfg, mesh, batch, tokens, labels)
    return out


def _telemetry_probe_420m(model, cfg, mesh, batch, tokens, labels, steps=8):
    """Separate short instrumented run for the telemetry block. The timed windows
    above run untelemetered on purpose: telemetry fetches the loss every step, which
    drains the dispatch queue the timed median depends on."""
    import gc
    import tempfile

    import jax
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    tel_dir = tempfile.mkdtemp(prefix="ds_bench_telemetry_")
    probe = DeepSpeedEngine(model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
                            mesh=mesh,
                            config_params={
                                "train_batch_size": batch,
                                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                                "zero_optimization": {"stage": 2},
                                "telemetry": {"enabled": True,
                                              "peak_tflops": peak_tflops(),
                                              "mfu_window": steps,
                                              "output_path": tel_dir,
                                              # one traced 2-step window mid-probe;
                                              # the profile observatory ingests it and
                                              # summary()["profile"] carries the
                                              # measured decomposition next to
                                              # anatomy's prediction (docs/profile.md)
                                              "trace_steps": [4, 6],
                                              "trace_dir": os.path.join(
                                                  tel_dir, "trace"),
                                              "profile": {"enabled": True},
                                              # chip auto-detected from device_kind;
                                              # summary()["anatomy"] then carries the
                                              # roofline floor + MFU ceiling beside
                                              # the measured MFU (docs/anatomy.md)
                                              "anatomy": {"enabled": True}},
                                "numerics": {"enabled": True,
                                             "audit_interval": 4},
                            })
    for _ in range(steps):
        loss = probe(tokens, labels)
        probe.backward(loss)
        probe.step()
    summary = probe.telemetry.summary()
    summary["note"] = (f"separate {steps}-step instrumented run; the timed windows "
                       "above stay untelemetered")
    if probe._numerics is not None:
        num = probe._numerics.summary()
        step_ms = summary.get("step_time_ms")
        try:
            total_s = float(step_ms) * steps / 1000.0
            num["audit_overhead_pct"] = round(100.0 * num["audit_seconds"] / total_s, 3) \
                if total_s > 0 else None
        except (TypeError, ValueError):
            num["audit_overhead_pct"] = None
        summary["numerics"] = num
    probe.telemetry.close()
    del probe
    gc.collect()
    return summary


def _shard_optimizer(dp):
    """Client (init, apply) pair for DeepSpeedEngine doing exactly one v5e-32 ZeRO-2
    rank's optimizer work: Adam over a 1/dp fp32 shard of the gradient stream. The
    apply is marked ``external_master``: the fp32 master shard it owns lives in
    opt_state, so the engine holds NO dp=1 full fp32 master at all (zero HBM — a
    real 1/32 rank never holds it) and skips the full-params re-cast
    (a real rank refreshes params from the 32-way all-gather, which needs the other
    31 chips and is excluded here like every cross-chip collective)."""
    import jax
    import jax.numpy as jnp

    def shard_of(tree):
        leaves = jax.tree_util.tree_leaves(tree)
        n = sum(l.size for l in leaves) // dp
        flat = jnp.concatenate(
            [l.reshape(-1)[: max(l.size // dp, 1)].astype(jnp.bfloat16) for l in leaves])
        if flat.shape[0] < n:
            flat = jnp.pad(flat, (0, n - flat.shape[0]))
        return flat[:n].astype(jnp.float32), n

    def init(master):
        n = sum(l.size for l in jax.tree_util.tree_leaves(master)) // dp
        return {"shard": jnp.zeros((n,), jnp.float32),
                "m1": jnp.zeros((n,), jnp.float32),
                "m2": jnp.zeros((n,), jnp.float32)}

    def apply(grads, state, master, step, hyper):
        gs, _ = shard_of(grads)
        m1 = hyper["beta1"] * state["m1"] + (1.0 - hyper["beta1"]) * gs
        m2 = hyper["beta2"] * state["m2"] + (1.0 - hyper["beta2"]) * gs * gs
        shard = state["shard"] - hyper["lr"] * m1 / (jnp.sqrt(m2) + hyper["eps"])
        return master, {"shard": shard, "m1": m1, "m2": m2}

    apply.external_master = True
    return init, apply


def bench_1p5b_engine(remat_policy="dots", batch=8, loss_chunk=128):
    """The 1.5B metric measured THROUGH DeepSpeedEngine: the real jitted
    value_and_grad, grad adoption, apply_update with donated buffers,
    monitor/report path — with the per-rank optimizer work supplied as an
    external-master client pair: the fp32 shard lives in opt_state, the engine
    holds NO dp=1 master at all, and at gas==1 the engine's fused single-jit step
    keeps the grad tree internal to the program — matching a real 1/32 rank's HBM
    footprint. The only remaining difference vs a real v5e-32 rank: cross-chip
    collectives are excluded (they need the other 31 chips)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.parallel.mesh import build_mesh

    cfg = GPT2Config(vocab_size=50304, n_positions=1024, n_embd=1600, n_layer=48,
                     n_head=25, remat=remat_policy != "none",
                     remat_policy=None if remat_policy in ("full", "none") else remat_policy,
                     use_flash_attention=True, loss_chunk=loss_chunk)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = model.param_count(params)
    engine = DeepSpeedEngine(
        model=model, model_parameters=params, mesh=build_mesh(model=1, pipe=1),
        optimizer=_shard_optimizer(32),
        config_params={"train_batch_size": batch, "steps_per_print": 1000,
                       "bf16": {"enabled": True},
                       "zero_optimization": {"stage": 2},
                       # the external-master shard pair is a client optimizer
                       "zero_allow_untested_optimizer": True})
    del params
    gc.collect()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(batch, 1024)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)

    def step():
        loss = engine(tokens, labels)
        engine.backward(loss)
        engine.step()
        return loss

    step()
    _fence(step())  # second warm-up: the step compiles twice (ROADMAP A7)
    # median of three 15-step windows, each sample kept: a best-of draw biases
    # the number high
    steps, samples = 15, []
    for _ in range(3):
        t0 = time.time()
        for _ in range(steps):
            loss = step()
        _fence(loss)
        tps = batch * 1024 * steps / (time.time() - t0)
        samples.append((tps, tps * 6.0 * n_params / 1e12 / peak_tflops()))
    del engine
    gc.collect()
    ranked = sorted(samples, key=lambda s: s[1])
    tps, mfu = ranked[1]
    return {"tps": tps, "mfu": mfu,
            "mfu_spread": round((ranked[-1][1] - ranked[0][1]) / mfu, 4),
            "config": f"remat={remat_policy},batch={batch},chunk={loss_chunk}",
            "selection": "median-of-3 15-step windows in one process",
            "samples": [[round(t, 1), round(m, 4)] for t, m in samples]}


# Round-5 sweep winner (PERF.md "Round-5 1.5B remat/batch sweep", measured before
# PR 1): no library remat at batch 3 with unchunked CE. The one config whose number
# may become ``gpt2_1p5b_engine_mfu``; if it fails, the run fails.
# Triple = (remat_policy, batch, loss_chunk).
PINNED_ENGINE_CONFIG = ("none", 3, 1024)


def _offload_step_once(n_embd, n_layer, vocab=8192):
    """One REAL ZeRO-Offload engine step at the given size; returns the
    DeepSpeedCPUAdam.last_step_timing breakdown plus derived rates."""
    import jax
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.parallel.mesh import build_mesh

    cfg = GPT2Config(vocab_size=vocab, n_positions=512, n_embd=n_embd,
                     n_layer=n_layer, n_head=8, remat=True, use_flash_attention=True)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = model.param_count(params)
    engine = DeepSpeedEngine(
        model=model, model_parameters=params, mesh=build_mesh(model=1, pipe=1),
        config_params={"train_batch_size": 4, "steps_per_print": 1000,
                       "bf16": {"enabled": True},
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                       "zero_optimization": {"stage": 2, "cpu_offload": True}})
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(4, 512)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)
    # TWO steps: the first pipelined step autotunes the region-element cap (it
    # takes effect at the next grad fetch), the second is the measured one
    for _ in range(2):
        loss = engine(tokens, labels)
        engine.backward(loss)
        engine.step()
        _fence(loss)
    t = dict(engine.offload_step_timing)
    numel = int(engine._offload.numel)
    # lane-busy seconds are the honest overlap denominator: fetch_wait is only
    # the stall the Adam loop actually SAW, so a well-overlapped step has tiny
    # fetch_wait while fetch_busy stays ~= the serial fetch time
    lanes = {"fetch": t.get("fetch_busy", t["fetch_wait"]),
             "adam": t["host_adam"], "push": t.get("push_busy", t["push"])}
    regions = t.get("regions", [])
    top = sorted(regions, key=lambda r: -(r["fetch"] + r["adam"] + r["push"]))[:5]
    out = {"params": int(n_params), "numel_local": numel,
           "fetch_wait_s": round(t["fetch_wait"], 3),
           "fetch_busy_s": round(lanes["fetch"], 3),
           "host_adam_s": round(t["host_adam"], 3),
           "push_s": round(t["push"], 3),
           "push_busy_s": round(lanes["push"], 3),
           "total_s": round(t["total"], 3),
           "pipeline_depth": t.get("pipeline_depth"),
           "region_cap_elements": t.get("region_cap"),
           "n_regions": len(regions), "n_work_items": t.get("n_work_items"),
           "elements_per_s": round(numel / max(t["total"], 1e-9)),
           # ideal overlapped pipeline -> total ~= max(lane busy) -> efficiency -> 1
           "overlap_efficiency": round(
               max(lanes.values()) / max(t["total"], 1e-9), 3),
           "regions_top": [
               {"leaf": r["leaf"], "size": r["size"], "chunks": r["chunks"],
                "fetch_wait_s": round(r["fetch_wait"], 3),
                "fetch_s": round(r["fetch"], 3), "adam_s": round(r["adam"], 3),
                "push_s": round(r["push"], 3)} for r in top]}
    del engine, params
    gc.collect()
    return out


def bench_offload_step_timing():
    """ZeRO-Offload step breakdown at three sizes + a modeled step at the
    advertised 4B max-params config: the fetch/adam/push overlap structure,
    elements/s against size (the region pipeline has no super-linear term), and
    the 4B row extrapolated linearly from the largest measured size's rates."""
    sizes = [
        (512, 8),     # ~30 M local elements (the round-4 measurement point)
        (1024, 10),   # ~130 M
        (1280, 20),   # ~400 M
    ]
    rows = [_offload_step_once(n_embd, n_layer) for n_embd, n_layer in sizes]

    big = rows[-1]
    max_numel = 4_016_950_400  # max_trainable_params_per_chip probe result
    scale = max_numel / big["numel_local"]
    modeled = {
        "numel_local": max_numel,
        "fetch_wait_s": round(big["fetch_wait_s"] * scale, 1),
        "host_adam_s": round(big["host_adam_s"] * scale, 1),
        "push_s": round(big["push_s"] * scale, 1),
        "total_s": round(big["total_s"] * scale, 1),
        "basis": f"linear scaling from the {big['numel_local']:,}-element measured row "
                 f"(elements/s {big['elements_per_s']:,})",
    }
    return {"sizes": rows, "modeled_step_at_max_params": modeled}


def bench_decode_420m():
    """KV-cache greedy decode tokens/s, GPT-2 420M batch 8 (the generation stack
    is beyond the v0.3.0 reference, so it carries its own number). Decode rate
    isolated from prefill by differencing a 128-token and a 1-token generation."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    T0, NEW, B = 1024, 128, 8
    cfg = GPT2Config(vocab_size=50304, n_positions=T0 + NEW + 8, n_embd=1024,
                     n_layer=24, n_head=16, use_flash_attention=True)
    model = GPT2Model(cfg)
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16) if p.ndim >= 2 else p,
        model.init(jax.random.PRNGKey(0)))
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(B, T0)), jnp.int32)

    def timed(fn):
        jax.block_until_ready(fn())  # compiles
        best = float("inf")
        for _ in range(2):
            t0 = time.time()
            jax.block_until_ready(fn())
            best = min(best, time.time() - t0)
        return best

    t1 = timed(lambda: model.generate(params, prompt, 1))
    t_long = timed(lambda: model.generate(params, prompt, NEW))
    out = {"greedy_tok_s": round((NEW - 1) * B / max(t_long - t1, 1e-9), 1),
           "prefill_s": round(t1, 3), "batch": B, "prompt": T0}
    del params
    gc.collect()
    return out


def bench_serving_summary(cfg_kwargs, *, n_requests, num_slots, block_size,
                          num_blocks, max_model_len, prefill_chunk,
                          param_dtype=None, seed=11, prefix_cache=False,
                          sharding=1, shared_prefix=0, speculate=0,
                          draft_cfg_kwargs=None):
    """Continuous-batching serving summary (docs/serving.md): replay a seeded
    mixed greedy/beam trace through the InferenceEngine and report tok/s,
    TTFT/TPOT latency percentiles (request-trace ledger), preemption-waste
    fraction, mean slot occupancy, and goodput — plus the compile-watchdog
    recompile count, which must be 0 after warmup (the fixed-shape contract
    ds-tpu serve-sim gates on). Runs OUTSIDE the headline measurement windows
    (PERF.md): the ledger is host-side bookkeeping, but the headline numbers
    stay untraced on principle."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.serve.sim import synth_trace
    from deepspeed_tpu.utils.monitor import SummaryMonitor
    from deepspeed_tpu.utils.telemetry import TelemetrySession

    cfg = GPT2Config(**cfg_kwargs)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if param_dtype is not None:
        params = jax.tree_util.tree_map(
            lambda p: p.astype(param_dtype) if p.ndim >= 2 else p, params)
    # speculation: self-draft (same model+params, acceptance ~1) unless a
    # separate draft config is given — then the real small-drafts-big shape
    draft_model = draft_params = None
    if speculate:
        if draft_cfg_kwargs is None:
            draft_model, draft_params = model, params
        else:
            draft_model = GPT2Model(GPT2Config(**draft_cfg_kwargs))
            draft_params = draft_model.init(jax.random.PRNGKey(1))
            if param_dtype is not None:
                draft_params = jax.tree_util.tree_map(
                    lambda p: p.astype(param_dtype) if p.ndim >= 2 else p,
                    draft_params)
    # disabled monitor: the watchdog is wanted, the scalar files are not
    session = TelemetrySession(monitor=SummaryMonitor(enabled=False))
    import deepspeed_tpu
    eng = deepspeed_tpu.init_inference(
        model=model, model_parameters=params, telemetry=session,
        draft_model=draft_model, draft_parameters=draft_params,
        config_params={"serving": {
            "enabled": True, "max_seqs": num_slots, "block_size": block_size,
            "num_blocks": num_blocks, "max_model_len": max_model_len,
            "prefill_chunk": prefill_chunk,
            "prefix_cache": {"enabled": prefix_cache},
            "sharding": {"model": sharding},
            "speculation": {"enabled": bool(speculate),
                            "max_draft_tokens": max(int(speculate), 1)},
            "request_trace": {"enabled": True,
                              "capacity": max(n_requests + 1, 256)}}})
    reqs = synth_trace(n_requests, vocab_size=cfg.vocab_size,
                       max_model_len=max_model_len, seed=seed,
                       shared_prefix_len=shared_prefix)
    t0 = time.time()
    outs, logs = eng.run(reqs)
    wall = max(time.time() - t0, 1e-9)
    fin = [o for o in outs if o.status == "finished"]
    new_tokens = sum(len(o.tokens) for o in fin)
    occ = [len(log["decode"]) / num_slots for log in logs]
    recompiles = sum(session.watchdog.recompiles(n)
                     for n in session.watchdog.records
                     if n.startswith("serve:"))
    spec_extra = {}
    if speculate:
        ss = eng.spec_summary()
        spec_extra = {
            "spec_k": int(speculate),
            "spec_acceptance_rate": round(ss["spec_acceptance_rate"], 4),
            "target_steps_per_token": round(ss["target_steps_per_token"], 4),
            "drafted_tokens": ss["drafted_tokens"],
            "accepted_draft_tokens": ss["accepted_tokens"],
            "wasted_draft_tokens": ss["wasted_draft_tokens"]}
    cache_extra = {}
    if eng.prefix_cache is not None:
        cs = eng.prefix_cache.stats()
        cache_extra = {
            "prefix_cache_hit_rate": round(cs["hit_rate"], 4),
            "cached_token_fraction": round(cs["cached_token_fraction"], 4),
            "cached_prefix_tokens": cs["hit_tokens"],
            "prefix_cache_evictions": cs["evictions"]}
    return {"requests": len(reqs), "finished": len(fin),
            "iterations": len(logs), "wall_s": round(wall, 2),
            **({"sharding_model_ways": sharding} if sharding > 1 else {}),
            **cache_extra, **spec_extra,
            # tok_s counts every sampled token (all beam lanes, preempted
            # work included); goodput only tokens of finished requests
            "tok_s": round(eng._tokens_sampled / wall, 1),
            "goodput_tok_s": round(new_tokens / wall, 1),
            "ttft_ms_mean": round(float(np.mean([o.ttft_ms for o in fin])), 2),
            "ttft_iters_mean": round(float(np.mean([o.ttft_iters
                                                    for o in fin])), 2),
            **{f"{m}_{p}": round(v, 2)
               for m in ("ttft_ms", "tpot_ms")
               for p, v in eng.tracer.percentiles(m, ps=(50, 95, 99)).items()
               if v is not None},
            "waste_fraction": round(
                eng.tracer.waste_summary()["waste_fraction"], 4),
            "occupancy_mean": round(float(np.mean(occ)) if occ else 0.0, 3),
            "preemptions": sum(o.preemptions for o in fin),
            "decode_recompiles_after_warmup": recompiles}


def bench_serving_fleet_summary(cfg_kwargs, *, replicas, n_requests, num_slots,
                                block_size, num_blocks, max_model_len,
                                prefill_chunk, param_dtype=None, seed=11,
                                shared_prefix=0, max_queue_depth=0, kills=()):
    """Fleet-router serving summary (docs/serving.md): N replicas sharing one
    model/params object behind the prefix-affinity FleetRouter, a seeded
    shared-prefix trace routed through it, and a scripted warm failover —
    reports the fleet-MERGED TTFT/TPOT percentiles (exact sketch fold), the
    shed rate under the queue-depth bound, and the merged goodput_fleet
    fraction after the kills bill their restart_replay badput. Runs OUTSIDE
    the headline windows like the single-replica serving smokes."""
    import shutil
    import tempfile

    import jax
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.serve.engine import InferenceEngine
    from deepspeed_tpu.serve.router import FleetRouter
    from deepspeed_tpu.serve.sim import synth_trace
    from deepspeed_tpu.utils.monitor import SummaryMonitor
    from deepspeed_tpu.utils.telemetry import TelemetrySession

    cfg = GPT2Config(**cfg_kwargs)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if param_dtype is not None:
        params = jax.tree_util.tree_map(
            lambda p: p.astype(param_dtype) if p.ndim >= 2 else p, params)
    # disabled monitor: the recompile watchdog is wanted, scalar files are not
    session = TelemetrySession(monitor=SummaryMonitor(enabled=False))

    def build(slot, telemetry=None):
        return InferenceEngine(
            model, params, num_slots=num_slots, block_size=block_size,
            num_blocks=num_blocks, max_model_len=max_model_len,
            prefill_chunk=prefill_chunk, prefix_cache=True,
            telemetry=telemetry,
            request_trace={"enabled": True,
                           "capacity": max(n_requests + 1, 256),
                           "host_id": slot})

    engines = [build(s, session if s == 0 else None) for s in range(replicas)]
    snap = tempfile.mkdtemp(prefix="ds_bench_fleet_") if kills else None
    router = FleetRouter(
        engines, max_queue_depth=max_queue_depth,
        kill_schedule=list(kills), snapshot_dir=snap,
        build_replacement=(lambda slot: build(slot)) if kills else None,
        telemetry=session, run_id=f"bench_fleet{replicas}")
    reqs = synth_trace(n_requests, vocab_size=cfg.vocab_size,
                       max_model_len=max_model_len, seed=seed,
                       shared_prefix_len=shared_prefix)
    t0 = time.time()
    outs, _ = router.run(reqs)
    wall = max(time.time() - t0, 1e-9)
    if snap:
        shutil.rmtree(snap, ignore_errors=True)
    summary = router.fleet_summary()
    lat = summary["latency"]
    fin = [o for o in outs if o.status == "finished"]
    recompiles = sum(session.watchdog.recompiles(n)
                     for n in session.watchdog.records
                     if n.startswith("serve:"))
    return {"replicas": replicas, "requests": len(reqs),
            "finished": len(fin), "shed": summary["shed"],
            "kills": summary["kills"], "wall_s": round(wall, 2),
            "goodput_tok_s": round(sum(len(o.tokens) for o in fin) / wall, 1),
            **{f"fleet_{k}": round(v, 2) for k, v in lat.items()},
            "fleet_p99_ttft_ms": round(lat.get("ttft_ms_p99", 0.0), 2),
            "shed_rate": round(summary["shed"] / max(len(reqs), 1), 4),
            "goodput_fleet_fraction": round(
                summary["goodput_fleet"]["goodput_fraction"], 4),
            "prefill_chunks": summary["prefill_chunks"],
            "total_prefill_chunks": summary["total_prefill_chunks"],
            "decode_recompiles_after_warmup": recompiles}


def bench_goodput_smoke():
    """Run-lifecycle goodput smoke (docs/goodput.md): a short engine run with
    the badput ledger on and periodic async saves, reporting the goodput
    fraction and the checkpoint-fence share of run wall — the two
    run-efficiency numbers the round ledger tracks (the checkpoint share is
    lower-is-better). Runs OUTSIDE the headline window."""
    import shutil
    import tempfile

    from deepspeed_tpu.resilience.crash_sim import (_goodput_trainer,
                                                    _train_batches)

    workdir = tempfile.mkdtemp(prefix="ds_bench_goodput_")
    try:
        engine = _goodput_trainer(0, os.path.join(workdir, "led"),
                                  {"enabled": True,
                                   "save_dir": os.path.join(workdir, "ckpt"),
                                   "save_interval": 3})
        for x, y in _train_batches(9, 0):
            loss = engine(x, y)
            engine.backward(loss)
            engine.step()
        engine._resilience.wait()
        summary = engine._goodput.finalize()
        wall = summary["wall_s"] or 1.0
        cs = summary["class_seconds"]
        return {"goodput_fraction": round(summary["goodput_fraction"], 4),
                "badput_checkpoint_pct":
                    round(100.0 * cs["checkpoint_stall"] / wall, 3),
                "badput_init_pct": round(100.0 * cs["init"] / wall, 3),
                "badput_compile_pct": round(100.0 * cs["compile"] / wall, 3),
                "steps": int(summary["steps"]),
                "checkpoint_stalls": int(summary["checkpoint_stalls"])}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def bench_serving_420m():
    """TPU serving path: GPT-2 420M bf16, 32-request mixed trace."""
    import jax.numpy as jnp
    out = bench_serving_summary(
        dict(vocab_size=50304, n_positions=1024, n_embd=1024, n_layer=24,
             n_head=16, use_flash_attention=True),
        n_requests=32, num_slots=8, block_size=16, num_blocks=513,
        max_model_len=1024, prefill_chunk=128, param_dtype=jnp.bfloat16)
    gc.collect()
    return out


def bench_serving_420m_prefix_cache():
    """420M shared-system-prompt trace with the prefix cache on: the TTFT
    delta vs ``serving_420m`` prices what cross-request reuse buys at size."""
    import jax.numpy as jnp
    out = bench_serving_summary(
        dict(vocab_size=50304, n_positions=1024, n_embd=1024, n_layer=24,
             n_head=16, use_flash_attention=True),
        n_requests=32, num_slots=8, block_size=16, num_blocks=513,
        max_model_len=1024, prefill_chunk=128, param_dtype=jnp.bfloat16,
        prefix_cache=True, shared_prefix=256)
    gc.collect()
    return out


def bench_serving_420m_sharded():
    """420M decode sharded 2 ways over the model axis by attention head."""
    import jax.numpy as jnp
    out = bench_serving_summary(
        dict(vocab_size=50304, n_positions=1024, n_embd=1024, n_layer=24,
             n_head=16, use_flash_attention=True),
        n_requests=32, num_slots=8, block_size=16, num_blocks=513,
        max_model_len=1024, prefill_chunk=128, param_dtype=jnp.bfloat16,
        sharding=2)
    gc.collect()
    return out


def bench_serving_1p5b_spec():
    """GPT-2 420M drafts for a 1.5B target (both bf16) — the real-deployment
    shape of speculative decoding. Acceptance rate prices how often the small
    model predicts the big one's greedy choice; target_steps_per_token is what
    the K+1-wide verify amortization actually buys at size."""
    import jax.numpy as jnp
    out = bench_serving_summary(
        dict(vocab_size=50304, n_positions=1024, n_embd=1600, n_layer=48,
             n_head=25, use_flash_attention=True),
        n_requests=32, num_slots=8, block_size=16, num_blocks=513,
        max_model_len=1024, prefill_chunk=128, param_dtype=jnp.bfloat16,
        shared_prefix=256, speculate=4,
        draft_cfg_kwargs=dict(vocab_size=50304, n_positions=1024, n_embd=1024,
                              n_layer=24, n_head=16, use_flash_attention=True))
    gc.collect()
    return out


def bench_serving_420m_fleet():
    """420M bf16 fleet: 3 replicas behind the prefix-affinity router, a
    shared-system-prompt trace, and one scripted warm failover — the fleet
    tail-latency / shed-rate / goodput_fleet row of the regression ledger."""
    import jax.numpy as jnp
    out = bench_serving_fleet_summary(
        dict(vocab_size=50304, n_positions=1024, n_embd=1024, n_layer=24,
             n_head=16, use_flash_attention=True),
        replicas=3, n_requests=32, num_slots=8, block_size=16, num_blocks=513,
        max_model_len=1024, prefill_chunk=128, param_dtype=jnp.bfloat16,
        shared_prefix=256, max_queue_depth=16, kills=((8, 0),))
    gc.collect()
    return out


def _zero2_step_fn(model, dp_shard):
    """jitted fwd+bwd + the 1/dp fp32 Adam-shard update of one ZeRO-2 rank."""
    import jax
    import jax.numpy as jnp

    def step(params, master, m1, m2, tokens, labels):
        loss, grads = jax.value_and_grad(lambda p: model.apply(p, tokens, labels))(params)
        # bf16 grads (the reference keeps fp16 grads under ZeRO-2); this rank's
        # 1/dp partition updates in fp32, exactly the per-chip ZeRO-2 optimizer work.
        # Per-leaf floor(size/dp) slices can sum short of total//dp when leaf sizes
        # aren't dp-divisible — pad to the master shard length.
        gflat = jnp.concatenate(
            [g.astype(jnp.bfloat16).reshape(-1)[: max(g.size // dp_shard, 1)]
             for g in jax.tree_util.tree_leaves(grads)])
        short = master.shape[0] - gflat.shape[0]
        if short > 0:
            gflat = jnp.pad(gflat, (0, short))
        gs = gflat[: master.shape[0]].astype(jnp.float32)
        m1n = 0.9 * m1 + 0.1 * gs
        m2n = 0.999 * m2 + 0.001 * gs * gs
        mastern = master - 1e-4 * m1n / (jnp.sqrt(m2n) + 1e-8)
        return loss, mastern, m1n, m2n

    return jax.jit(step, donate_argnums=(1, 2, 3))


def bench_1p5b():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    DP = 32  # the target platform: v5e-32, ZeRO-2 shards the optimizer 32 ways
    # remat_policy="dots" (save matmul outputs, replay only elementwise ops in
    # backward): measured 0.46 MFU vs 0.39 under full recompute — the saved dots fit
    # HBM at batch 8 next to bf16 params+grads
    cfg = GPT2Config(vocab_size=50304, n_positions=1024, n_embd=1600, n_layer=48,
                     n_head=25, remat=True, remat_policy="dots",
                     use_flash_attention=True)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = model.param_count(params)
    params = jax.device_put(
        jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), params))
    shard_n = sum(l.size for l in jax.tree_util.tree_leaves(params)) // DP
    master = jnp.zeros((shard_n,), jnp.float32)
    m1 = jnp.zeros((shard_n,), jnp.float32)
    m2 = jnp.zeros((shard_n,), jnp.float32)
    jstep = _zero2_step_fn(model, DP)

    rng = np.random.default_rng(0)
    B, T, steps = 8, 1024, 15
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, T)), jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    loss, master, m1, m2 = jstep(params, master, m1, m2, tokens, labels)
    loss_v = _fence(loss)
    loss, master, m1, m2 = jstep(params, master, m1, m2, tokens, labels)
    _fence(loss)
    dt = float("inf")
    for _ in range(2):
        t0 = time.time()
        for _ in range(steps):
            loss, master, m1, m2 = jstep(params, master, m1, m2, tokens, labels)
        _fence(loss)
        dt = min(dt, time.time() - t0)
    tps = B * T * steps / dt
    mfu = tps * 6.0 * n_params / 1e12 / peak_tflops()
    del params, master, m1, m2
    gc.collect()
    return tps, mfu, n_params, loss_v


def probe_offload_footprint(n_layer):
    """Does a GPT-2(1600-wide, n_layer) ZeRO-Offload HBM footprint fit on this chip?
    bf16 params + bf16 grads + remat activations (master/moments are host-resident)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    cfg = GPT2Config(vocab_size=50304, n_positions=1024, n_embd=1600, n_layer=n_layer,
                     n_head=25, remat=True, use_flash_attention=True)
    model = GPT2Model(cfg)
    try:
        # allocate bf16 directly from abstract shapes: a real fp32 init would
        # transiently DOUBLE the param footprint and mask the true capacity
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        n_params = int(sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)))
        params = jax.jit(lambda: jax.tree_util.tree_map(
            lambda s: jnp.full(s.shape, 0.01, jnp.bfloat16), shapes))()

        @jax.jit
        def fwd_bwd(p, tokens, labels):
            loss, grads = jax.value_and_grad(lambda pp: model.apply(pp, tokens, labels))(p)
            # bf16 grads, exactly what the offload engine materializes in HBM (the
            # host tier upcasts to fp32 in its landing buffer)
            return loss, jax.tree_util.tree_map(lambda g: g.astype(jnp.bfloat16), grads)

        tokens = jnp.zeros((4, 1024), jnp.int32)
        loss, grads = fwd_bwd(params, tokens, tokens)
        ok = bool(np.isfinite(_fence(loss)))
        del params, grads, loss
        gc.collect()
        return ok, n_params
    except jax.errors.JaxRuntimeError as e:
        # running out of device memory is this probe's signal; anything else is a fault
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        gc.collect()
        sys.stderr.write(f"[bench] offload probe n_layer={n_layer}: does not fit\n")
        return False, 0


def max_params_offload():
    """Binary-search the deepest 1600-wide GPT-2 whose offload footprint fits.

    Seeded at the boundary measured before PR 1 (128 layers fit, 132 did not) so
    the steady-state cost is two probes; falls back to the full search if the
    boundary moved. Probes run in this process, one after another."""
    ok128, n128 = probe_offload_footprint(128)
    if ok128:
        ok132, n132 = probe_offload_footprint(132)
        if not ok132:
            return n128
        lo, best = 132, n132
    else:
        lo = 48
        ok, best = probe_offload_footprint(lo)
        if not ok:
            return 0
    hi = 160  # analytic ceiling ~ (16GB - act) / (4 B/param * 30.7M/layer)
    ok_hi, hi_params = probe_offload_footprint(hi)
    if ok_hi:
        return hi_params
    while hi - lo > 8:  # invariant: lo fits, hi does not
        mid = (lo + hi) // 2 // 4 * 4
        if mid <= lo:
            break
        ok, n = probe_offload_footprint(mid)
        if ok:
            lo, best = mid, n
        else:
            hi = mid
    return best


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)) or ".")
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"bench.py measures a TPU; JAX found {device.platform} devices")
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    device_info = {"platform": device.platform, "kind": device.device_kind,
                   "count": jax.device_count()}

    extra = bench_420m()
    if os.environ.get("DS_BENCH_FAST", "0") == "1":
        print(json.dumps({"metric": "gpt2_420m_tokens_per_sec_per_chip",
                          "value": extra["gpt2_420m_tokens_per_sec_per_chip"],
                          "unit": "tokens/s",
                          "vs_baseline": round(extra["gpt2_420m_mfu"] / 0.40, 4),
                          "device": device_info, "extra": extra}))
        return

    tps, mfu, n_params, loss_v = bench_1p5b()
    extra.update({"gpt2_1p5b_mfu": round(mfu, 4),
                  "gpt2_1p5b_params": int(n_params),
                  "gpt2_1p5b_first_loss": round(loss_v, 3),
                  "gpt2_1p5b_note": ("fwd+bwd on full 1.5B bf16 params + 1/32 fp32 "
                                     "optimizer-shard update (one v5e-32 ZeRO-2 rank's "
                                     "per-chip work; cross-chip collectives excluded)")})
    # the same metric measured THROUGH DeepSpeedEngine (jitted engine paths +
    # donated-buffer update; the external-master shard optimizer keeps the dp=1
    # fp32 master off-HBM, matching a real rank's 1/32 footprint)
    policy, batch, chunk = PINNED_ENGINE_CONFIG
    e = bench_1p5b_engine(remat_policy=policy, batch=batch, loss_chunk=chunk)
    extra.update({"gpt2_1p5b_engine_tokens_per_sec": round(e["tps"], 1),
                  "gpt2_1p5b_engine_mfu": round(e["mfu"], 4),
                  "gpt2_1p5b_engine_mfu_spread": e["mfu_spread"],
                  "gpt2_1p5b_engine_config": e["config"],
                  "gpt2_1p5b_engine_selection": e["selection"],
                  "gpt2_1p5b_engine_samples": e["samples"]})
    extra["offload_step_timing"] = bench_offload_step_timing()
    extra["decode_420m"] = bench_decode_420m()
    # the serving summaries ride after the headline windows, never inside them
    extra["serving_420m"] = bench_serving_420m()
    extra["serving_420m_prefix_cache"] = bench_serving_420m_prefix_cache()
    if jax.device_count() >= 2:  # head-sharded decode needs a second chip
        extra["serving_420m_sharded"] = bench_serving_420m_sharded()
    extra["serving_1p5b_spec"] = bench_serving_1p5b_spec()
    extra["serving_fleet"] = bench_serving_420m_fleet()
    extra["goodput"] = bench_goodput_smoke()
    extra["max_trainable_params_per_chip_zero_offload"] = int(max_params_offload())
    print(json.dumps({"metric": "gpt2_1p5b_zero2_tokens_per_sec_per_chip",
                      "value": round(tps, 1), "unit": "tokens/s",
                      "vs_baseline": round(mfu / 0.40, 4),
                      "device": device_info, "extra": extra}))


if __name__ == "__main__":
    main()
