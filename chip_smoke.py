"""On-chip smoke test: the engine's main paths on one TPU chip, at published widths.

    python chip_smoke.py            # one chip: phase A (train), flash parity, the experts in
                                    # pieces, phase B (serve)
    python chip_smoke.py --chips 4  # one four-chip host: the data-parallel phase only

One process, normal entry points, random weights from ``SEED``. Every phase prints
one JSON line of observations; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``. Any
failed phase, non-finite loss or broken comparison raises, so the run exits
non-zero and that line is never printed. There is no CPU mode: ``main`` refuses
anything but a TPU before it builds a model. The phases are functions of a size so
that tests/unit/test_chip_smoke.py can rehearse them at a tiny one.
"""

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
BF16_EPS = 2.0 ** -8
# relative to the largest reference magnitude, as tests/tpu_parity.py measures it
PARITY_TOL = 3e-2
LOGITS_TOL = 2e-2

XL_WIDTHS = dict(vocab_size=50304, n_positions=1024, n_embd=1600, n_head=25)
XL_LAYERS = 48
# Full XL holds 16 bytes a parameter (bf16 params and grads, fp32 master and two
# Adam moments): ~25 GB, more than one chip's 16. Depth is the only cut. Compiled
# for a described v5e at batch 4, the grad program plus the 12 bytes a parameter
# that stay resident beside it come to 13.7 GiB at 20 layers, 15.0 at 22 and 16.2
# at 24 (the analysis sees one program, not the allocator): 20 leaves 2 GiB spare.
TRAIN_LAYERS = 20
TRAIN = dict(model=dict(XL_WIDTHS, n_layer=TRAIN_LAYERS), batch=4, seq=1024)
TRAIN_FULL = dict(model=dict(XL_WIDTHS, n_layer=XL_LAYERS), batch=4, seq=1024)
PARITY_SHAPE = (3, 25, 1024, 64)
# OLMoE's first expert product as one chip of four runs it: 8 x 8,192 assignments
EXPERTS = dict(rows=65536, hidden=2048, columns=2048, experts=64, pieces=4)
SERVE = dict(
    model=dict(vocab_size=50304, n_positions=1024, n_embd=1024, n_layer=24, n_head=16),
    serving=dict(max_seqs=8, block_size=16, num_blocks=513, max_model_len=1024,
                 prefill_chunk=128),
    # eight requests over four prompt lengths: each length costs the dense
    # reference one compile of its prefill and decode programs
    prompt_lens=(64, 160, 320, 512, 64, 160, 320, 512), new_tokens=32)


def emit(record):
    print(json.dumps(record), flush=True)


class CompileLog:
    """XLA compile requests and persistent-cache hits, counted from jax.monitoring."""

    def __init__(self):
        import jax
        self.counts = {"compiles": 0, "compile_s": 0.0, "cache_requests": 0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1
            self.counts["compile_s"] += seconds

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.counts["cache_requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    def mark(self):
        return dict(self.counts)

    def since(self, mark):
        d = {k: self.counts[k] - mark[k] for k in mark}
        return {"compiles": d["compiles"], "compile_s": round(d["compile_s"], 2),
                "cache_hits": d["cache_hits"],
                "cache_misses": d["cache_requests"] - d["cache_hits"]}


def cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def peak_bytes(device):
    """``peak_bytes_in_use`` as the backend reports it (the CPU backend reports none)."""
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def compiled_texts(engine, sample_batch):
    """Compiled HLO text of every program on the training engine's active step
    path, through the engine's own ``lint_programs`` hook."""
    return {name: jitted.lower(*args).compile().as_text()
            for name, jitted, args, _ in engine.lint_programs(sample_batch)}


def require_kernel(text, program):
    if "tpu_custom_call" not in text:
        raise AssertionError(f"no tpu_custom_call in the compiled {program}: the "
                             "Pallas kernel was interpreted or replaced")


def reduced_depth(n_layer):
    return {} if n_layer == XL_LAYERS else {"n_layer": [XL_LAYERS, n_layer]}


# --------------------------------------------------------------------- train
def build_trainer(size, zero_stage, mesh):
    """GPT-2 at ``size`` under ZeRO ``zero_stage`` with the repo's Adam: through
    ``deepspeed_tpu.initialize`` (which spans every device) when ``mesh`` is None,
    else the same engine class on the explicit mesh."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    model = GPT2Model(GPT2Config(**size["model"], use_flash_attention=True))
    params = model.init(jax.random.PRNGKey(SEED))
    # lr: Adam's first steps move every weight by lr whatever the gradient's size;
    # with no warm-up, 1e-4 overshoots by the third step at 48 layers (PERF.md)
    config = {"train_batch_size": size["batch"], "steps_per_print": 10 ** 9,
              "bf16": {"enabled": True},
              "optimizer": {"type": "Adam", "params": {"lr": 1e-5}},
              "zero_optimization": {"stage": zero_stage}}
    if mesh is None:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config_params=config)
    else:
        engine = DeepSpeedEngine(model=model, model_parameters=params,
                                 config_params=config, mesh=mesh)
    return engine, model.param_count(params)


def seeded_batch(size):
    rng = np.random.default_rng(SEED)
    tokens = rng.integers(0, size["model"]["vocab_size"],
                          size=(size["batch"], size["seq"])).astype(np.int32)
    return tokens, np.roll(tokens, -1, axis=1)


def sharded_state_report(engine, dp):
    """What "sharded over data=dp" means, checked on the live arrays: every master
    and optimizer leaf with a ZeRO layout has one shard on each of ``dp`` distinct
    devices, each holding 1/dp of its elements."""
    import jax
    leaves = (jax.tree_util.tree_leaves(engine.master_params)
              + jax.tree_util.tree_leaves(engine.opt_state))
    total = sharded = per_device = 0
    for leaf in leaves:
        total += leaf.size
        if leaf.sharding.is_fully_replicated:
            per_device += leaf.nbytes
            continue
        per_device += leaf.nbytes // dp
        shards = leaf.addressable_shards
        devices = {s.device for s in shards}
        if len(devices) != dp or any(s.data.size * dp != leaf.size for s in shards):
            raise AssertionError(
                f"leaf {leaf.shape} is laid out over {len(devices)} devices with shard "
                f"sizes {[s.data.size for s in shards]}, expected {dp} x {leaf.size // dp}")
        sharded += leaf.size
    fraction = sharded / total
    if fraction < 0.9:
        raise AssertionError(f"only {fraction:.1%} of master and optimizer elements are "
                             f"sharded over data={dp}")
    return {"sharded_fraction": round(fraction, 4), "state_leaves": len(leaves),
            "state_bytes_per_device": per_device}


def run_train(size, log, *, zero_stage, mesh=None, steps=5, on_chip=True, sharded_dp=0):
    """Build the trainer, take ``steps`` steps on one seeded batch and return what
    was observed. Raises unless every loss is finite, the last is below the first
    and nothing compiles after the second step. ``sharded_dp`` > 1 also holds the
    engine to a ZeRO layout over that many devices."""
    import jax
    from deepspeed_tpu.parallel.mesh import DATA_AXIS
    from deepspeed_tpu.utils.hlo import collective_counts

    start = log.mark()
    engine, n_params = build_trainer(size, zero_stage, mesh)
    tokens, labels = seeded_batch(size)
    losses, step_ms, compiles = [], [], []
    for _ in range(steps):
        mark, t0 = log.mark(), time.perf_counter()
        loss = engine(tokens, labels)
        engine.backward(loss)
        engine.step()
        jax.block_until_ready(engine.params)
        step_ms.append(round((time.perf_counter() - t0) * 1e3, 1))
        compiles.append(log.since(mark)["compiles"])
        losses.append(float(loss))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    if any(compiles[2:]):
        raise AssertionError(f"compiles after the second step: {compiles}")

    dp = engine.mesh.shape[DATA_AXIS]
    out = {"n_layer": size["model"]["n_layer"], "params": int(n_params), "dp": dp,
           "zero_stage": zero_stage, "batch": size["batch"], "seq": size["seq"],
           "losses": [round(l, 4) for l in losses], "step_ms": step_ms,
           "compiles_per_step": compiles}
    texts = compiled_texts(engine, (tokens, labels))
    if on_chip:
        require_kernel(texts["loss_and_grad"], "loss_and_grad")
    if sharded_dp > 1:
        if dp != sharded_dp:
            raise AssertionError(f"default mesh came out as data={dp}, not {sharded_dp}")
        out.update(sharded_state_report(engine, dp))
        counts = {name: dict(collective_counts(text)) for name, text in texts.items()}
        out["collectives"] = counts
        # the gradient reduction shows as all-reduce (the compilers here slice it
        # to the ZeRO layout themselves) and the parameter gather as all-gather:
        # in the grad program under stage 3, in the update below it
        grads = counts["loss_and_grad"]
        reduced = grads.get("reduce-scatter", 0) + grads.get("all-reduce", 0)
        gather_in = "loss_and_grad" if zero_stage >= 3 else "apply_update"
        if not reduced or not counts[gather_in].get("all-gather", 0):
            raise AssertionError(f"ZeRO-{zero_stage} step lacks its gradient reduction "
                                 f"or parameter all-gather: {counts}")
    peaks = [peak_bytes(d) for d in engine.mesh.devices.flat]
    if on_chip and None in peaks:
        raise AssertionError(f"a device reports no peak_bytes_in_use: {peaks}")
    if sharded_dp > 1 and None not in peaks and min(peaks) < out["state_bytes_per_device"]:
        raise AssertionError(f"a device peaked below its {out['state_bytes_per_device']} "
                             f"bytes of master and optimizer shards: {peaks}")
    out["peak_bytes_per_device"] = peaks
    out.update(log.since(start))
    del engine   # closures keep an engine in reference cycles: free its state now
    gc.collect()
    return out


def hbm_forecast(size):
    """The repo's own offline predictor (``ds-tpu hbm --forecast``) for this size."""
    from deepspeed_tpu.utils.hbm import forecast
    f = forecast({"model": size["model"], "remat": "none", "batch_per_device": size["batch"],
                  "seq_len": size["seq"], "ce_chunk": 128, "dp": 1})
    return {"predicted_peak_bytes": f["predicted_peak_bytes"], "fits": f["fits"]}


def phase_train(size, log, *, mesh=None, on_chip=True):
    """Phase A: five ZeRO-2 steps through ``deepspeed_tpu.initialize``."""
    out = run_train(size, log, zero_stage=2, mesh=mesh, on_chip=on_chip)
    out["hbm_forecast"] = hbm_forecast(size)
    return out


def phase_flash_parity(shape, *, on_chip=True):
    """The compiled flash kernel, forward and backward, against the dense
    reference: the check tests/tpu_parity.py holds, at this shape."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(HERE, "tests"))
    import tpu_parity
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    if on_chip:
        q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        text = jax.jit(lambda q, k, v: flash_attention(q, k, v, True)).lower(
            q, q, q).compile().as_text()
        require_kernel(text, "flash_attention")
    errs = tpu_parity.flash_parity(shape, jnp.bfloat16, True, PARITY_TOL, seed=SEED)
    if tpu_parity.FAILURES:
        raise AssertionError(f"flash parity failed: {tpu_parity.FAILURES}")
    return {"shape": list(shape), "tol": PARITY_TOL,
            "rel_err": {k: float(f"{v:.3e}") for k, v in errs.items()}}


def expert_group_sizes(size, layout):
    """Rows a group, summing to ``size['rows']``, no size a multiple of a row tile, so that
    tiles span groups and pieces. ``spread``: every fifth group empty, the others uneven.
    ``collapsed``: eight groups hold all but a few rows, as the router of
    ``olmoe_d4_train_4chip`` leaves them (``moe_load_max_over_mean`` near 8)."""
    rng = np.random.default_rng(SEED)
    share = rng.random(size["experts"]) + 0.05
    if layout == "collapsed":
        share[rng.permutation(size["experts"])[8:]] *= 1e-3
    share[::5] = 0.0
    sizes = np.floor(share / share.sum() * size["rows"]).astype(np.int32)
    sizes[np.flatnonzero(share)[-1]] += size["rows"] - sizes.sum()
    return sizes


def phase_experts_in_pieces(size, *, on_chip=True):
    """``parallel/moe.experts_matmul`` given the experts in pieces out of order, as a layer
    under a mesh gets them from the other chips (one kernel call a piece, chained through
    one buffer that nothing zeroed), against one call over all of them: output, and the
    cotangents of the rows and of every expert. Each row is the same product either way,
    so the two agree bit for bit; ``PARITY_TOL`` is the limit, ``identical`` the reading."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.parallel.moe import GMM_TILES, experts_matmul

    per = size["experts"] // size["pieces"]
    order = [(1 - s) % size["pieces"] for s in range(size["pieces"])]      # chip 1's arrivals
    keys = jax.random.split(jax.random.PRNGKey(SEED), 3)
    lhs = jax.random.normal(keys[0], (size["rows"], size["hidden"]), jnp.bfloat16)
    rhs = jax.random.normal(keys[1], (size["experts"], size["hidden"], size["columns"]),
                            jnp.bfloat16) * size["hidden"] ** -0.5
    cot = jax.random.normal(keys[2], (size["rows"], size["columns"]), jnp.bfloat16)

    def both_ways(lhs, rhs, cot, sizes, firsts):
        whole, back = jax.vjp(lambda x, w: experts_matmul(x, (w,), (None,), sizes), lhs, rhs)
        d_lhs, d_rhs = back(cot)
        parts = tuple(rhs[o * per:(o + 1) * per] for o in order)
        pieced, back = jax.vjp(lambda x, ws: experts_matmul(x, ws, tuple(firsts), sizes),
                               lhs, parts)
        p_lhs, d_parts = back(cot)
        p_rhs = jnp.concatenate([d_parts[order.index(o)] for o in range(size["pieces"])])
        return [(whole, pieced), (d_lhs, p_lhs), (d_rhs, p_rhs)]

    firsts = jnp.asarray([o * per for o in order], jnp.int32)        # traced, as a chip's index is
    program = jax.jit(both_ways)
    if on_chip:
        text = program.lower(lhs, rhs, cot, jnp.zeros(size["experts"], jnp.int32),
                             firsts).compile().as_text()
        require_kernel(text, "experts_matmul")
    out = {"rows": size["rows"], "experts": size["experts"], "pieces": size["pieces"],
           "tol": PARITY_TOL, "layouts": {}}
    for layout in ("spread", "collapsed"):
        sizes = expert_group_sizes(size, layout)
        ends = np.cumsum(sizes)[per - 1::per][:-1]
        pairs = jax.device_get(program(lhs, rhs, cot, jnp.asarray(sizes), firsts))
        rel, same = {}, True
        for name, (a, b) in zip(("out", "d_rows", "d_experts"), pairs):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            if not (np.isfinite(a).all() and np.isfinite(b).all()):
                raise AssertionError(f"experts in pieces, {layout}: {name} is not finite")
            rel[name] = float(np.abs(a - b).max() / np.abs(a).max())
            same &= bool(np.array_equal(a, b))
        if max(rel.values()) > PARITY_TOL:
            raise AssertionError(f"experts in pieces, {layout}: {rel} against one call")
        out["layouts"][layout] = {
            "empty_groups": int((sizes == 0).sum()), "largest_group": int(sizes.max()),
            "piece_ends_inside_a_tile": [int(e % GMM_TILES[0]) for e in ends],
            "rel_err": {k: float(f"{v:.3e}") for k, v in rel.items()}, "identical": same}
    return out


# --------------------------------------------------------------------- serve
def serving_programs(engine):
    """The serving engine's jitted programs by name, from its ``lint_programs`` hook."""
    return {name: (jitted, args) for name, jitted, args, _ in engine.lint_programs()}


def paged_next_logits(engine, programs, prefix):
    """Next-token logits after ``prefix``, replayed through the serving engine's own
    paged programs as the engine drives them: chunked prefill, then one decode step."""
    import jax.numpy as jnp
    c = engine.model.config
    prefill, decode = programs["serve_prefill_chunk"][0], programs["serve_decode_step"][0]
    pool_shape = (c.n_layer, engine.num_blocks, engine.block_size, c.n_head, c.head_dim)
    k_pool = jnp.zeros(pool_shape, c.compute_dtype)
    v_pool = jnp.zeros(pool_shape, c.compute_dtype)
    body, chunk = prefix[:-1], engine.prefill_chunk
    table = np.zeros(engine.max_blocks, np.int32)   # 0 is the null page
    n_blocks = -(-len(prefix) // engine.block_size)
    table[:n_blocks] = 1 + np.arange(n_blocks)
    for pos in range(0, len(body), chunk):
        piece = body[pos:pos + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(piece)] = piece
        _, k_pool, v_pool = prefill(
            engine.params, jnp.asarray(toks), jnp.int32(pos), jnp.int32(len(piece)),
            jnp.asarray(table), k_pool, v_pool)
    slots = engine.num_slots
    toks, pos = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
    tables = np.zeros((slots, engine.max_blocks), np.int32)
    active = np.zeros(slots, bool)
    toks[0], pos[0], tables[0], active[0] = prefix[-1], len(body), table, True
    logits, _, _ = decode(
        engine.params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(tables),
        jnp.asarray(active), k_pool, v_pool)
    return np.asarray(logits[0], np.float32)


def dense_next_logits(model, params, prefix):
    """Next-token logits after ``prefix`` from the dense-cache forward ``generate`` runs."""
    import jax
    import jax.numpy as jnp
    c = model.config
    cache_shape = (c.n_layer, 1, c.n_head, len(prefix), c.head_dim)
    logits, _, _ = jax.jit(model._build_cached_forward(len(prefix)))(
        params, jnp.asarray([prefix], jnp.int32), 0,
        jnp.zeros(cache_shape, c.compute_dtype), jnp.zeros(cache_shape, c.compute_dtype))
    return np.asarray(logits[0], np.float32)


def near_tie(paged, dense, served_tok, ref_tok, tol=LOGITS_TOL):
    """Whether two argmax choices may differ: both paths' logits agree within ``tol``
    of the largest reference logit, and on each path the two chosen tokens lie
    closer together than that."""
    bound = tol * float(np.max(np.abs(dense)))
    agree = float(np.max(np.abs(paged - dense)))
    gaps = [abs(float(l[served_tok] - l[ref_tok])) for l in (paged, dense)]
    return {"ok": agree <= bound and max(gaps) <= bound, "bound": round(bound, 5),
            "logits_max_abs_diff": round(agree, 5), "token_gaps": [round(g, 5) for g in gaps]}


def compare_streams(engine, programs, model, params, prompts, served, reference):
    """Served tokens against the reference, request by request. A stream may leave
    the reference only at a near-tie; returns how many did."""
    diverged = []
    for i, (prompt, got, want) in enumerate(zip(prompts, served, reference)):
        if got == want:
            continue
        if len(got) != len(want):
            raise AssertionError(f"request {i}: {len(got)} tokens served, {len(want)} expected")
        t = next(j for j, (a, b) in enumerate(zip(got, want)) if a != b)
        prefix = list(prompt) + want[:t]
        verdict = near_tie(paged_next_logits(engine, programs, prefix),
                           dense_next_logits(model, params, prefix), got[t], want[t])
        verdict.update(request=i, position=t, served=got[t], reference=want[t])
        if not verdict.pop("ok"):
            raise AssertionError(f"served tokens diverge from generate(): {verdict}")
        diverged.append(verdict)
    return diverged


def phase_serve(size, log, *, on_chip=True):
    """Phase B: the requests through ``deepspeed_tpu.init_inference`` (continuous
    batching over the paged cache), on the default gather path and on the Pallas
    decode kernel, each stream compared with the model's dense-cache ``generate``."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.serve.scheduler import Request

    start = log.mark()
    model = GPT2Model(GPT2Config(**size["model"], use_flash_attention=True))
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16) if p.ndim >= 2 else p,
        model.init(jax.random.PRNGKey(SEED)))
    rng = np.random.default_rng(SEED)
    vocab, new = size["model"]["vocab_size"], size["new_tokens"]
    prompts = [rng.integers(0, vocab, size=n).astype(np.int32).tolist()
               for n in size["prompt_lens"]]

    t0 = time.perf_counter()
    reference = [np.asarray(model.generate(params, jnp.asarray([p], jnp.int32), new))
                 [0, len(p):].tolist() for p in prompts]
    out = {"n_layer": size["model"]["n_layer"], "n_embd": size["model"]["n_embd"],
           "requests": len(prompts), "prompt_lens": list(size["prompt_lens"]),
           "new_tokens": new, "reference_s": round(time.perf_counter() - t0, 2)}

    for name, use_pallas in (("gather", False), ("pallas", True)):
        engine = deepspeed_tpu.init_inference(
            model=model, model_parameters=params,
            config_params={"serving": dict(size["serving"], enabled=True,
                                           use_pallas_decode=use_pallas)})
        t0 = time.perf_counter()
        outputs, logs = engine.run([Request(f"r{i}", p, new) for i, p in enumerate(prompts)])
        wall = time.perf_counter() - t0
        unfinished = [o.req_id for o in outputs if o.status != "finished"]
        if unfinished:
            raise AssertionError(f"{name}: requests not finished: {unfinished}")
        served = [o.tokens for o in outputs]
        programs = serving_programs(engine)
        decode, decode_args = programs["serve_decode_step"]
        kernel = "tpu_custom_call" in decode.lower(*decode_args).compile().as_text()
        if on_chip and kernel != use_pallas:
            raise AssertionError(f"{name}: decode program "
                                 f"{'has' if kernel else 'lacks'} a tpu_custom_call")
        diverged = compare_streams(engine, programs, model, params, prompts, served,
                                   reference)
        out[name] = {"tokens": sum(len(s) for s in served), "iterations": len(logs),
                     "wall_s": round(wall, 2), "kernel_in_decode": kernel,
                     "identical_streams": len(prompts) - len(diverged),
                     "near_tie_divergences": diverged}
        del engine, programs, decode_args
        gc.collect()
    out["peak_bytes"] = peak_bytes(jax.devices()[0])
    out.update(log.since(start))
    return out


# ----------------------------------------------------------------- multichip
def phase_multichip(size, full_size, log, *, on_chip=True):
    """``--chips 4``: the phase-A model on one explicit device, then through
    ``deepspeed_tpu.initialize`` over every device under ZeRO 2 and 3 (the loss
    sequences must agree), then ``full_size`` under ZeRO 3. Yields one record a run."""
    import jax
    from deepspeed_tpu.parallel.mesh import single_device_mesh

    dp = jax.device_count()
    ref = run_train(size, log, zero_stage=2, mesh=single_device_mesh(jax.devices()[0]),
                    steps=3, on_chip=on_chip)
    yield dict(ref, run="reference dp=1")
    for stage in (2, 3):
        got = run_train(size, log, zero_stage=stage, steps=3, on_chip=on_chip, sharded_dp=dp)
        diffs = [abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])]
        if max(diffs) > BF16_EPS:
            raise AssertionError(f"ZeRO-{stage} dp={dp} losses {got['losses']} leave the "
                                 f"dp=1 reference {ref['losses']} by {max(diffs):.2e}")
        yield dict(got, run=f"zero{stage} dp={dp}", max_rel_loss_diff=float(f"{max(diffs):.2e}"))
    full = run_train(full_size, log, zero_stage=3, steps=3, on_chip=on_chip, sharded_dp=dp)
    yield dict(full, run=f"full depth zero3 dp={dp}")


# ---------------------------------------------------------------------- main
def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs the data-parallel phase on a four-chip host, and no other")
    args = parser.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke needs a TPU; JAX found {devices[0].platform} devices")
    if args.chips == 4 and len(devices) != 4:
        sys.exit(f"--chips 4 needs four chips; JAX found {len(devices)}")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}

    from deepspeed_tpu.parallel.mesh import single_device_mesh
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache
    from deepspeed_tpu.utils.roofline import detect_chip

    cache_dir = configure_compile_cache()
    emit({"device": device, "chip_spec": detect_chip(), "jax": jax.__version__,
          "compile_cache_dir": cache_dir, "cache_entries_before": cache_entries(cache_dir),
          "cache_max_bytes": jax.config.jax_compilation_cache_max_size})
    log = CompileLog()

    if args.chips == 4:
        for record in phase_multichip(TRAIN, TRAIN_FULL, log):
            emit({"phase": "multichip", "reduced": reduced_depth(record["n_layer"]), **record})
    else:
        mesh = None
        if len(devices) > 1:
            mesh = single_device_mesh(devices[0])
            emit({"note": f"{len(devices)} devices present: initialize() would span them "
                          "all, so the one-chip phases build the same engine class on "
                          f"single_device_mesh({devices[0]})"})
        emit({"phase": "A train", "reduced": reduced_depth(TRAIN_LAYERS),
              **phase_train(TRAIN, log, mesh=mesh)})
        emit({"phase": "A flash parity", **phase_flash_parity(PARITY_SHAPE)})
        emit({"phase": "A experts in pieces", **phase_experts_in_pieces(EXPERTS)})
        emit({"phase": "B serve", "reduced": {}, **phase_serve(SERVE, log)})

    emit({"cache_entries_after": cache_entries(cache_dir)})
    emit({"ok": True, "device": device})


if __name__ == "__main__":
    main()
