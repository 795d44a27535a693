"""HBM memory observatory (docs/hbm.md): attribute every HBM byte three ways
and reconcile them.

**measured** — the backend watermarks the compile watchdog already captures
(``memory_analysis()`` arg/out/temp per compiled program, ``memory_stats()``
in-use/peak per device, read through :func:`device_memory_stats`).

**parsed** — per-buffer attribution from the optimized program's entry layout
and donation tables (``utils/hlo.entry_buffer_table``). Each entry buffer is
classified into params / grads / optimizer state / comm error-feedback /
paged KV pool by matching its (dtype, per-device shape) against the multiset
of leaf signatures the engine declares via ``memory_manifest()`` — the memory
analogue of ``lint_programs()``. Classification is greedy in a fixed class
priority order; when two classes hold identical signatures (e.g. master and
Adam moments at ZeRO-2, all fp32 leaves scattered the same way) any
assignment swap moves identical byte counts, so per-class totals are
assignment-order independent.

**modeled** — a closed-form ZeRO-style predictor (PAPER.md's 2Ψ/2Ψ/12Ψ
accounting) parameterized by the manifest's geometry: (Ψ, dp, ZeRO stage,
sharded fraction, external-master shard, accumulation, remat policy, CE
chunking, serving pool geometry). Auxiliary buffers whose sizes are config
shapes rather than ZeRO formulas (comm EF buckets, KV pools) are modeled
from the declared shapes — still pre-compile configuration, so parsing the
compiled HLO against them remains a real cross-check.

The registry sweep (``ds-tpu hbm``) runs all three over every lint-registry
entry and gates parsed-vs-modeled within a pinned tolerance; ``--forecast``
is the pure-host feasibility predicate that re-derives the round-5 OOM
frontier (PERF.md) without executing anything.
"""

import argparse
import json
import sys
from collections import Counter
from typing import Any, Dict, List, Optional

HBM_REPORT_VERSION = 1
HBM_REPORT_KIND = "hbm_registry_sweep"

# parsed-vs-modeled reconciliation gate: relative slack for real divergence
# (layout padding, scalar optimizer fields), absolute slack so tiny classes
# aren't gated at sub-buffer granularity
HBM_REL_TOL = 0.02
HBM_ABS_TOL = 1024

# classification priority: persistent state first (params most recognizable),
# transient/auxiliary last. Order only matters when class signatures collide,
# and colliding assignments are byte-neutral (see module docstring).
CLASS_PRIORITY = ("params", "master", "optimizer", "grads", "comm_ef",
                  "kv_pool", "draft_params", "draft_pool")

# jnp dtype name -> HLO element type (mirrors lint/program_passes._HLO_DTYPE;
# kept local so utils does not import the lint package at module scope)
_HLO_DTYPE = {"float32": "f32", "float16": "f16", "bfloat16": "bf16",
              "float64": "f64", "int32": "s32", "int64": "s64", "int16": "s16",
              "int8": "s8", "uint32": "u32", "uint64": "u64", "uint16": "u16",
              "uint8": "u8", "bool": "pred"}
_DTYPE_ITEMSIZE = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                   "f16": 2, "bf16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                   "pred": 1}

GIB = 2 ** 30


# --------------------------------------------------------------- measured
def device_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """``memory_stats()`` of one device (default: local device 0), or None
    where the backend doesn't report them. Contract: CPU returns None; TPU and
    GPU report at least ``bytes_in_use`` / ``peak_bytes_in_use``. This is THE
    memory_stats read for the whole package — runtime/utils.see_memory_usage,
    utils/timer.memory_usage, telemetry.hbm_stats and the cluster heartbeat
    row all delegate here, so the None-on-CPU behavior is pinned once."""
    try:
        import jax
        if device is None:
            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:
        return None
    return dict(stats) if stats else None


# What a gradient program needs beside what it KEEPS beyond need (``room_beside_state``), set from
# ``olmoe_d4_train_4chip`` on four v5e chips (PERF.md, PR 54; ``tests/perf/kept_fetches_probe.py``):
# the device's limit 16.909 GB, the engine's state 7.006 GB a chip and 0.942 GB a set of gradients;
# the gradient program's temporaries 6.366 GB with nothing kept (the margin, rounded up) and
# 7.980 GB with the 2.416 GB a chip fetches for four expert layers kept: +1.61, since a layer's
# second fetch was a temporary too. 0.98 GB then stay free while the program runs alone.
TEMPORARIES_MARGIN = 6_400_000_000


def room_beside_state(limit, class_bytes):
    """Bytes of a chip that a gradient program may spend on what it keeps beyond need (the
    experts an expert layer fetched: ``parallel/moe.fetches_kept``): the device's ``limit``
    less the engine's state (``class_bytes``: a chip's bytes by ``memory_manifest`` class), ONE
    set of gradients, the program's own output, and ``TEMPORARIES_MARGIN``; never negative.
    One set: a program that keeps more no longer fits beside the update program that holds the
    set before, so the runtime starts it when that has ended (one step in flight where the
    parent has two: the price, measured at 8.8 against 10.3 GB in use)."""
    state = sum(b for cls, b in class_bytes.items() if cls != "grads")
    return max(0, limit - state - class_bytes.get("grads", 0) - TEMPORARIES_MARGIN)


# ----------------------------------------------------------------- parsed
def leaf_signature(leaf):
    """(hlo_dtype, per-device shape, per-device bytes) of one manifest leaf.

    Entry parameters of a jitted SPMD program carry post-partitioning
    per-device shapes, so a sharded leaf must be signed by its shard shape
    (``sharding.shard_shape``), not its global shape."""
    import numpy as np
    dtype = np.dtype(leaf.dtype)
    shape = tuple(int(d) for d in leaf.shape)
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        try:
            shape = tuple(int(d) for d in sharding.shard_shape(shape))
        except Exception:
            pass
    n = 1
    for d in shape:
        n *= d
    hdt = _HLO_DTYPE.get(dtype.name, dtype.name)
    return (hdt, shape, n * _DTYPE_ITEMSIZE.get(hdt, dtype.itemsize))


def manifest_signatures(manifest):
    """(signatures, class_bytes) of a ``memory_manifest()`` dict:
    ``signatures[cls]`` is the Counter of (dtype, per-device shape) leaf
    signatures, ``class_bytes[cls]`` the class's total per-device bytes."""
    import jax
    signatures, class_bytes = {}, {}
    for cls, tree in (manifest.get("classes") or {}).items():
        counter = Counter()
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            dt, shape, b = leaf_signature(leaf)
            counter[(dt, shape)] += 1
            total += b
        signatures[cls] = counter
        class_bytes[cls] = total
    return signatures, class_bytes


def classify_program(hlo_text, signatures):
    """Attribute one optimized program's entry buffers against the manifest.

    Returns ``{"by_class": {cls: bytes}, "other_bytes", "parameter_bytes",
    "unaliased_result_bytes", "temp_estimate_bytes"}``. Each program gets a
    fresh copy of every class's signature multiset — the same resident buffer
    (params, pools) legitimately appears in several programs."""
    from . import hlo
    table = hlo.entry_buffer_table(hlo_text)
    remaining = {cls: Counter(c) for cls, c in signatures.items()}
    by_class = {cls: 0 for cls in signatures}
    other = 0
    for p in table["parameters"]:
        for dt, dims, b in p["leaves"]:
            key = (dt, tuple(dims))
            for cls in CLASS_PRIORITY:
                if remaining.get(cls, Counter()).get(key, 0) > 0:
                    remaining[cls][key] -= 1
                    by_class[cls] += b
                    break
            else:
                for cls in remaining:   # manifest classes outside the priority
                    if cls not in CLASS_PRIORITY and remaining[cls].get(key, 0) > 0:
                        remaining[cls][key] -= 1
                        by_class[cls] += b
                        break
                else:
                    other += b
    return {
        "by_class": {c: int(b) for c, b in by_class.items()},
        "other_bytes": int(other),
        "parameter_bytes": int(table["parameter_bytes"]),
        "unaliased_result_bytes": int(table["unaliased_result_bytes"]),
        "temp_estimate_bytes": int(hlo.temp_allocation_estimate(hlo_text)),
    }


def attribute_programs(program_reports):
    """Entry-level parsed attribution: per-class MAX over the entry's
    programs. The classes are resident state threaded through every program
    that touches it, so the live footprint of a class is the largest single
    appearance, not the sum."""
    parsed = {}
    for rep in program_reports:
        for cls, b in rep["by_class"].items():
            parsed[cls] = max(parsed.get(cls, 0), b)
    return parsed


# ---------------------------------------------------------------- modeled
def modeled_classes(geometry) -> Dict[str, int]:
    """Closed-form per-device byte prediction per class from a manifest's
    geometry dict — the ZeRO accounting (params Ψ·bytes, grads Ψ·bytes/dp at
    stage ≥ 2, master 4Ψ/dp + moments 8Ψ/dp at stage ≥ 1, i.e. the paper's
    2Ψ/2Ψ/12Ψ split) with the engine's measured sharded-coverage fraction in
    place of the ideal 1/dp, plus shape-derived sizes for auxiliary buffers
    (comm error-feedback, paged KV pools)."""
    kind = geometry.get("kind", "training")
    out: Dict[str, int] = {}
    if kind == "serving":
        psi = int(geometry["psi"])
        ib = int(geometry["param_itemsize"])
        pf = float(geometry.get("param_per_device_fraction", 1.0))
        out["params"] = int(round(psi * ib * pf))
        g = geometry.get("pool")
        if g:
            pool = (2 * g["n_layer"] * g["num_blocks"] * g["block_size"]
                    * g["n_head"] * g["head_dim"] * g["itemsize"])
            out["kv_pool"] = int(pool // max(int(g.get("shard_factor", 1)), 1))
        d = geometry.get("draft")
        if d:
            out["draft_params"] = int(d["psi"] * d["param_itemsize"])
            dp_ = d["pool"]
            out["draft_pool"] = int(2 * dp_["n_layer"] * dp_["num_blocks"]
                                    * dp_["block_size"] * dp_["n_head"]
                                    * dp_["head_dim"] * dp_["itemsize"])
        return out
    if kind == "decode":
        out["params"] = int(geometry["psi"]) * int(geometry["param_itemsize"])
        return out
    if kind == "pipeline_local":
        # instruction-executor pipeline: per-stage LOCAL programs — the live
        # param working set of any one program is the largest stage subtree
        out["params"] = int(geometry["stage_param_bytes_max"])
        return out

    psi = int(geometry["psi"])
    dp = max(int(geometry.get("dp", 1)), 1)
    stage = int(geometry.get("zero_stage", 0))
    zsf = geometry.get("zero_sharded_fraction")
    zsf = 1.0 if zsf is None else float(zsf)

    def frac(threshold):
        # sharded coverage zsf of the bytes scale 1/dp, the rest replicate
        if stage >= threshold and dp > 1:
            return 1.0 - zsf + zsf / dp
        return 1.0

    out["params"] = int(round(psi * int(geometry["param_itemsize"]) * frac(3)))
    if not geometry.get("fused", False) or geometry.get("offload", False):
        # two-jit / accumulation / offload paths hand grads between programs
        # as a resident buffer; the fused step keeps the grad tree internal so
        # XLA frees each leaf as the optimizer consumes it (PERF.md round 5)
        out["grads"] = int(round(psi * int(geometry["grad_itemsize"])
                                 * frac(2)))
    if not geometry.get("offload", False):
        # else master + moments live in host DRAM: zero device bytes
        out["master"] = int(round(4 * psi * frac(1)))
        out["optimizer"] = int(round(8 * psi * frac(1)))
    ef = int(geometry.get("comm_ef_bytes", 0))
    if ef:
        out["comm_ef"] = ef
    return out


def reconcile(parsed, modeled, class_bytes=None, rel_tol=HBM_REL_TOL,
              abs_tol=HBM_ABS_TOL):
    """Per-class reconciliation verdicts. A class is gated when the parsed
    attribution observed it (parsed > 0); a modeled-but-never-parsed class is
    ``unobserved`` (resident state outside the captured program set — e.g.
    the target pools of a spec-programs-only registry entry), which is not
    drift. Returns ``(classes, ok)``."""
    classes = {}
    ok = True
    for cls in sorted(set(parsed) | set(modeled)):
        p = int(parsed.get(cls, 0))
        m = int(modeled.get(cls, 0))
        row = {"parsed_bytes": p, "modeled_bytes": m}
        if class_bytes is not None:
            row["manifest_bytes"] = int(class_bytes.get(cls, 0))
        if p == 0 and m > 0:
            row["status"] = "unobserved"
        elif abs(p - m) <= max(abs_tol, rel_tol * max(p, m)):
            row["status"] = "ok"
        else:
            row["status"] = "drift"
            ok = False
        classes[cls] = row
    return classes, ok


# --------------------------------------------------------------- forecast
# Calibrated activation residency per remat policy, in units of
# n_embd-equivalents per token-layer (bf16). 'dots' = 8 is physically exact
# for the GPT-2 block: saved qkv (3E) + attention proj input (E) + mlp fc
# output (4E); policies saving more residuals sit above it, and XLA's own
# scheduler under 'none'/'flash' holds ~3E live. Calibrated against — and
# verified to binary-classify — every cell of the round-5 sweep (PERF.md).
REMAT_ACT_UNITS = {"none": 3, "flash": 3, "attn": 4, "dots": 8,
                   "dots+attn": 10, "dots+attn-lean": 12}

# fixed XLA workspace + fragmentation allowance at the 1.5B scale
FORECAST_WORKSPACE_BYTES = 1 * GIB


def gpt2_param_count(n_embd, n_layer, vocab_size, n_positions):
    """Exact GPT-2 Ψ: wte + wpe + per-block (12E² + 13E) + final LN (2E)."""
    e = int(n_embd)
    return (int(vocab_size) * e + int(n_positions) * e
            + int(n_layer) * (12 * e * e + 13 * e) + 2 * e)


def forecast(config) -> Dict[str, Any]:
    """Feasibility predicate for one training config — per-chip peak HBM
    prediction and fit/OOM verdict, without compiling or executing anything.

    ``config`` keys: ``model`` {n_embd, n_layer, vocab_size, n_positions,
    psi?}, ``remat`` (REMAT_ACT_UNITS key), ``batch_per_device``, ``seq_len``,
    ``ce_chunk`` (0 = unchunked), ``external_master_shards`` (0 = internal
    12Ψ/dp master+opt with ``dp``), ``dp``, ``budget_gib``.

    The prediction is BINARY by design: margins near the cliff are not
    comparable to XLA's real peak (scheduling is non-monotonic there —
    round 5 measured a policy that frees more yet peaks higher), but the
    fit/OOM frontier itself reproduces the round-5 sweep exactly."""
    m = config["model"]
    e, layers = int(m["n_embd"]), int(m["n_layer"])
    vocab, positions = int(m["vocab_size"]), int(m["n_positions"])
    psi = int(m.get("psi") or gpt2_param_count(e, layers, vocab, positions))
    remat = str(config.get("remat", "none"))
    if remat not in REMAT_ACT_UNITS:
        raise ValueError(f"unknown remat policy {remat!r}; expected one of "
                         f"{sorted(REMAT_ACT_UNITS)}")
    batch = int(config["batch_per_device"])
    seq = int(config.get("seq_len", positions))
    chunk = int(config.get("ce_chunk", 0)) or seq
    shards = int(config.get("external_master_shards", 0))
    dp = max(int(config.get("dp", 1)), 1)
    budget = int(round(float(config.get("budget_gib", 15.75)) * GIB))

    params_b = 2 * psi                                   # bf16 compute params
    opt_frac = (1.0 / shards) if shards else (1.0 / dp)
    master_opt_b = int(round(12 * psi * opt_frac))       # fp32 master + Adam
    acts_b = REMAT_ACT_UNITS[remat] * batch * seq * layers * e * 2
    logits_b = batch * chunk * vocab * 4                 # f32 CE chunk
    total = (params_b + master_opt_b + acts_b + logits_b
             + FORECAST_WORKSPACE_BYTES)
    return {
        "psi": psi,
        "classes": {"params": params_b, "master_opt": master_opt_b,
                    "activations": acts_b, "logits": logits_b,
                    "workspace": FORECAST_WORKSPACE_BYTES},
        "predicted_peak_bytes": int(total),
        "budget_bytes": budget,
        "fits": total <= budget,
        "headroom_bytes": int(budget - total),
    }


def smallest_fitting_delta(config) -> List[Dict[str, Any]]:
    """Single-knob config deltas predicted to fit, for an OOMed config —
    ordered cheapest-change first (chunk the CE loss, then a leaner remat
    policy, then smaller batch). Empty when the config already fits or no
    single knob rescues it."""
    base = forecast(config)
    if base["fits"]:
        return []
    out = []
    m = config["model"]
    seq = int(config.get("seq_len", int(m["n_positions"])))
    chunk = int(config.get("ce_chunk", 0)) or seq
    for cand in (256, 128, 64):
        if cand < chunk:
            trial = dict(config, ce_chunk=cand)
            f = forecast(trial)
            if f["fits"]:
                out.append({"change": "ce_chunk", "value": cand,
                            "predicted_peak_bytes": f["predicted_peak_bytes"]})
                break
    units = REMAT_ACT_UNITS[str(config.get("remat", "none"))]
    leaner = sorted(((u, p) for p, u in REMAT_ACT_UNITS.items() if u < units),
                    reverse=True)
    for _u, policy in leaner:
        f = forecast(dict(config, remat=policy))
        if f["fits"]:
            out.append({"change": "remat", "value": policy,
                        "predicted_peak_bytes": f["predicted_peak_bytes"]})
            break
    for b in range(int(config["batch_per_device"]) - 1, 0, -1):
        f = forecast(dict(config, batch_per_device=b))
        if f["fits"]:
            out.append({"change": "batch_per_device", "value": b,
                        "predicted_peak_bytes": f["predicted_peak_bytes"]})
            break
    return out


# The round-5 manual sweep (PERF.md): GPT-2 1.5B, T=1024, one 15.75 GiB v5e
# chip, external-master 1/32 fp32 shard, fused step. (remat, batch, ce_chunk,
# oomed). --forecast round5 re-derives this frontier offline and exits 1 on
# any misclassification — the acceptance gate for the predictor.
ROUND5_MODEL = {"n_embd": 1600, "n_layer": 48, "vocab_size": 50304,
                "n_positions": 1024}
ROUND5_BUDGET_GIB = 15.75
ROUND5_SHARDS = 32
ROUND5_WINNER = ("none", 3, 1024)
ROUND5_SWEEP = [
    ("dots", 8, 128, False),
    ("dots+attn", 8, 128, True),
    ("dots+attn", 8, 256, True),
    ("dots+attn", 8, 64, True),
    ("dots+attn-lean", 8, 128, True),
    ("flash", 8, 64, False),
    ("attn", 8, 128, False),
    ("none", 8, 128, False),
    ("none", 6, 128, False),
    ("none", 4, 128, False),
    ("none", 8, 1024, False),
    ("none", 6, 1024, False),
    ("none", 4, 256, False),
    ("none", 4, 512, False),
    ("none", 4, 1024, False),
    ("dots+attn", 4, 1024, False),
    ("none", 2, 1024, False),
    ("none", 3, 1024, False),
]


def forecast_round5() -> Dict[str, Any]:
    """Run the predictor over every round-5 sweep cell and diff the verdicts
    against the measured outcomes. ``ok`` iff every OOMed config is predicted
    infeasible AND every config that ran (the winner included) is predicted
    feasible — the frontier re-derived offline."""
    cells = []
    mismatches = []
    for remat, batch, chunk, oomed in ROUND5_SWEEP:
        cfg = {"model": dict(ROUND5_MODEL), "remat": remat,
               "batch_per_device": batch, "seq_len": 1024,
               "ce_chunk": 0 if chunk >= 1024 else chunk,
               "external_master_shards": ROUND5_SHARDS,
               "budget_gib": ROUND5_BUDGET_GIB}
        f = forecast(cfg)
        agree = f["fits"] == (not oomed)
        cells.append({"remat": remat, "batch": batch, "ce_chunk": chunk,
                      "measured_oom": oomed, "predicted_fits": f["fits"],
                      "predicted_peak_bytes": f["predicted_peak_bytes"],
                      "agree": agree})
        if not agree:
            mismatches.append(f"{remat}@{batch},c{chunk}: measured "
                              f"{'OOM' if oomed else 'fit'} but predicted "
                              f"{'fit' if f['fits'] else 'OOM'}")
    winner = next(c for c in cells
                  if (c["remat"], c["batch"], c["ce_chunk"]) == ROUND5_WINNER)
    return {
        "version": HBM_REPORT_VERSION,
        "kind": "hbm_forecast_round5",
        "budget_gib": ROUND5_BUDGET_GIB,
        "cells": cells,
        "winner": {"config": list(ROUND5_WINNER),
                   "predicted_fits": winner["predicted_fits"]},
        "mismatches": mismatches,
        "ok": not mismatches,
    }


# ----------------------------------------------------------- OOM forensics
def oom_forensics(snapshot) -> Dict[str, Any]:
    """Flight-recorder memory block: the per-class resident bytes largest
    first, the device watermarks, and — when the engine registered a
    forecastable config — the smallest single-knob deltas predicted to fit.
    Pure host dict-shuffling over an already-captured snapshot."""
    classes = dict(snapshot.get("classes") or {})
    out = {
        "classes": {c: int(b) for c, b in classes.items()},
        "largest_classes": [
            {"class": c, "bytes": int(b)}
            for c, b in sorted(classes.items(), key=lambda kv: (-kv[1], kv[0]))
        ],
    }
    measured = snapshot.get("measured")
    if measured:
        out["measured"] = {k: int(v) for k, v in measured.items()
                           if isinstance(v, (int, float))}
    if snapshot.get("temp_peak_bytes"):
        out["compiled_temp_bytes_peak"] = int(snapshot["temp_peak_bytes"])
    cfg = snapshot.get("forecast_config")
    if cfg:
        try:
            f = forecast(cfg)
            out["forecast"] = {"predicted_peak_bytes": f["predicted_peak_bytes"],
                               "budget_bytes": f["budget_bytes"],
                               "fits": f["fits"]}
            if not f["fits"]:
                out["fitting_deltas"] = smallest_fitting_delta(cfg)
        except Exception as e:           # forensics must never mask the crash
            out["forecast_error"] = repr(e)
    return out


# ------------------------------------------------------------ registry sweep
def sweep_entry(entry, builders=None, rel_tol=HBM_REL_TOL,
                abs_tol=HBM_ABS_TOL) -> Dict[str, Any]:
    """Measured + parsed + modeled attribution for one lint-registry entry.

    Builds the entry's engine, captures its step programs AOT (the same
    ``ProgramArtifact.capture`` path lint uses, so ``memory_analysis``
    watermarks ride along), classifies every program's entry buffers against
    the engine's ``memory_manifest()``, and reconciles the per-class maxima
    against the closed-form model."""
    from ..lint.program_passes import ProgramArtifact
    if builders is None:
        from ..lint.registry import BUILDERS as builders
    engine, batch = builders[entry]()
    manifest_fn = getattr(engine, "memory_manifest", None)
    manifest = manifest_fn() if manifest_fn is not None else {"classes": {},
                                                              "geometry": {}}
    signatures, class_bytes = manifest_signatures(manifest)
    programs = {}
    for name, jitted, args, man in engine.lint_programs(batch):
        artifact = ProgramArtifact.capture(f"{entry}:{name}", jitted, args,
                                           man)
        rep = classify_program(artifact.hlo_text, signatures)
        rep["measured"] = {k: int(v) for k, v in artifact.memory_stats.items()}
        programs[name] = rep
    parsed = attribute_programs(programs.values())
    geometry = dict(manifest.get("geometry") or {})
    modeled = modeled_classes(geometry) if geometry else {}
    classes, ok = reconcile(parsed, modeled, class_bytes,
                            rel_tol=rel_tol, abs_tol=abs_tol)
    return {
        "geometry": geometry,
        "classes": classes,
        "programs": programs,
        "activations": {
            "temp_estimate_bytes_max": max(
                (p["temp_estimate_bytes"] for p in programs.values()),
                default=0),
            "measured_temp_bytes_max": max(
                (p["measured"].get("temp_size_in_bytes", 0)
                 for p in programs.values()), default=0),
        },
        "reconciled": ok,
    }


def sweep_registry(entries=None, rel_tol=HBM_REL_TOL,
                   abs_tol=HBM_ABS_TOL) -> Dict[str, Any]:
    """The full sweep report over the lint registry (default: every entry)."""
    from ..lint.registry import BUILDERS
    names = sorted(BUILDERS) if not entries else list(entries)
    out_entries = {}
    errors = []
    for entry in names:
        try:
            out_entries[entry] = sweep_entry(entry, rel_tol=rel_tol,
                                             abs_tol=abs_tol)
        except Exception as e:
            errors.append(f"{entry}: sweep failed: {e}")
    drift = sorted(e for e, rep in out_entries.items()
                   if not rep["reconciled"])
    return {
        "version": HBM_REPORT_VERSION,
        "kind": HBM_REPORT_KIND,
        "tolerance": {"rel": rel_tol, "abs": abs_tol},
        "entries": out_entries,
        "drift_entries": drift,
        "errors": sorted(errors),
        "ok": not errors and not drift,
    }


def stable_projection(report) -> Dict[str, Any]:
    """The golden-pinnable slice of a sweep report: parsed/modeled per-class
    bytes, reconciliation verdicts, and entry-layout byte totals — all pure
    functions of the abstract manifests and the entry computation layout on
    the pinned 8-device CPU mesh. Measured watermarks and the temp-liveness
    estimate are excluded (they move with the XLA scheduler)."""
    entries = {}
    for entry, rep in report["entries"].items():
        entries[entry] = {
            "classes": rep["classes"],
            "reconciled": rep["reconciled"],
            "programs": {
                name: {"by_class": p["by_class"],
                       "other_bytes": p["other_bytes"],
                       "parameter_bytes": p["parameter_bytes"]}
                for name, p in rep["programs"].items()
            },
        }
    return {
        "version": report["version"],
        "kind": report["kind"] + "_golden",
        "tolerance": report["tolerance"],
        "entries": entries,
        "drift_entries": report["drift_entries"],
        "ok": report["ok"],
    }


def diff_reports(old, new, rel_tol=HBM_REL_TOL,
                 abs_tol=HBM_ABS_TOL) -> Dict[str, Any]:
    """Cross-run regression gate over two sweep reports (full or golden
    projection): any class whose parsed bytes GREW beyond tolerance, any
    entry that newly drifted, and any entry/class that disappeared."""
    regressions = []
    o_entries = old.get("entries", {})
    n_entries = new.get("entries", {})
    for entry in sorted(o_entries):
        if entry not in n_entries:
            regressions.append(f"{entry}: entry disappeared")
            continue
        o_rep, n_rep = o_entries[entry], n_entries[entry]
        if o_rep.get("reconciled", True) and not n_rep.get("reconciled", True):
            regressions.append(f"{entry}: newly drifted "
                               "(parsed vs modeled out of tolerance)")
        o_cls = o_rep.get("classes", {})
        n_cls = n_rep.get("classes", {})
        for cls in sorted(o_cls):
            ob = int(o_cls[cls].get("parsed_bytes", 0))
            nb = int(n_cls.get(cls, {}).get("parsed_bytes", 0))
            if nb > ob + max(abs_tol, rel_tol * ob):
                regressions.append(
                    f"{entry}/{cls}: parsed bytes grew {ob} -> {nb} "
                    f"(+{nb - ob})")
    return {"version": HBM_REPORT_VERSION, "kind": "hbm_diff",
            "regressions": regressions, "ok": not regressions}


# ------------------------------------------------------------------- CLI
def _load_json(path):
    with open(path) as f:
        return json.load(f)


def hbm_main(argv=None):
    """``ds-tpu hbm`` — the memory observatory CLI. Default: the registry
    sweep (per-program attribution + reconciliation gate, exit 1 on drift).
    ``--forecast round5|CONFIG.json`` and ``--diff A B`` are pure-host modes
    that never build an engine."""
    parser = argparse.ArgumentParser(
        prog="ds-tpu hbm",
        description="HBM attribution: measured vs parsed vs modeled over the "
                    "lint registry; offline OOM feasibility forecasts")
    parser.add_argument("--json", action="store_true",
                        help="emit the full report as JSON on stdout")
    parser.add_argument("--out", metavar="PATH",
                        help="also write the JSON report to PATH")
    parser.add_argument("--golden-out", metavar="PATH",
                        help="write the stable (golden-pinnable) projection "
                             "of the sweep to PATH")
    parser.add_argument("--entry", action="append", metavar="NAME",
                        help="limit the sweep to a lint-registry entry "
                             "(repeatable; default: every entry)")
    parser.add_argument("--tolerance", type=float, default=HBM_REL_TOL,
                        help="parsed-vs-modeled relative tolerance "
                             "(default: %(default)s)")
    parser.add_argument("--forecast", metavar="CONFIG",
                        help="feasibility forecast: 'round5' re-derives the "
                             "round-5 OOM frontier, else a JSON config path")
    parser.add_argument("--budget-gib", type=float, default=0.0,
                        help="override the forecast config's HBM budget")
    parser.add_argument("--diff", nargs=2, metavar=("OLD", "NEW"),
                        help="compare two sweep reports; exit 1 on parsed-"
                             "byte growth beyond tolerance")
    args = parser.parse_args(argv)

    # stdout belongs to the report (same contract as ds-tpu lint)
    import logging
    for h in logging.getLogger("DeepSpeedTPU").handlers:
        if isinstance(h, logging.StreamHandler) and h.stream is sys.stdout:
            h.stream = sys.stderr

    if args.diff:
        report = diff_reports(_load_json(args.diff[0]),
                              _load_json(args.diff[1]),
                              rel_tol=args.tolerance)
    elif args.forecast == "round5":
        report = forecast_round5()
    elif args.forecast:
        cfg = _load_json(args.forecast)
        if args.budget_gib:
            cfg["budget_gib"] = args.budget_gib
        report = forecast(cfg)
        report.update({"version": HBM_REPORT_VERSION, "kind": "hbm_forecast",
                       "ok": True})
        if not report["fits"]:
            report["fitting_deltas"] = smallest_fitting_delta(cfg)
    else:
        report = sweep_registry(args.entry, rel_tol=args.tolerance)

    text = json.dumps(report, indent=2, sort_keys=True) + "\n"
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    if args.golden_out and report.get("kind") == HBM_REPORT_KIND:
        with open(args.golden_out, "w") as f:
            f.write(json.dumps(stable_projection(report), indent=2,
                               sort_keys=True) + "\n")
    if args.json:
        sys.stdout.write(text)
    else:
        _print_report(report)
    return 0 if report.get("ok", True) else 1


def _print_report(report):
    kind = report.get("kind")
    if kind == HBM_REPORT_KIND:
        for entry in sorted(report["entries"]):
            rep = report["entries"][entry]
            verdict = "ok" if rep["reconciled"] else "DRIFT"
            print(f"{entry}: [{verdict}]")
            for cls, row in sorted(rep["classes"].items()):
                print(f"  {cls:<14} parsed {row['parsed_bytes']:>12,} B  "
                      f"modeled {row['modeled_bytes']:>12,} B  "
                      f"[{row['status']}]")
            act = rep["activations"]
            print(f"  {'activations':<14} temp est "
                  f"{act['temp_estimate_bytes_max']:>9,} B  measured temp "
                  f"{act['measured_temp_bytes_max']:>9,} B")
        for e in report["errors"]:
            print(f"ERROR {e}")
        print(f"{len(report['entries'])} entr(ies), "
              f"{len(report['drift_entries'])} drifted, "
              f"{len(report['errors'])} error(s)")
    elif kind == "hbm_forecast_round5":
        for c in report["cells"]:
            mark = "ok" if c["agree"] else "MISMATCH"
            print(f"{c['remat']}@{c['batch']},c{c['ce_chunk']}: predicted "
                  f"{'fit' if c['predicted_fits'] else 'OOM'} "
                  f"({c['predicted_peak_bytes'] / GIB:.2f} GiB), measured "
                  f"{'OOM' if c['measured_oom'] else 'fit'} [{mark}]")
        print(f"winner {report['winner']['config']}: predicted "
              f"{'fit' if report['winner']['predicted_fits'] else 'OOM'}; "
              f"{len(report['mismatches'])} mismatch(es)")
    elif kind == "hbm_forecast":
        for cls, b in sorted(report["classes"].items()):
            print(f"  {cls:<12} {b / GIB:>8.3f} GiB")
        print(f"predicted peak {report['predicted_peak_bytes'] / GIB:.3f} GiB "
              f"vs budget {report['budget_bytes'] / GIB:.2f} GiB -> "
              f"{'FITS' if report['fits'] else 'OOM'}")
        for d in report.get("fitting_deltas", []):
            print(f"  delta: {d['change']} -> {d['value']} "
                  f"({d['predicted_peak_bytes'] / GIB:.3f} GiB)")
    elif kind == "hbm_diff":
        for r in report["regressions"]:
            print(f"REGRESSION {r}")
        print(f"{len(report['regressions'])} regression(s)")


if __name__ == "__main__":
    sys.exit(hbm_main())
