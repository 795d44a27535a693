"""A serving cell: ``deepspeed_tpu.init_inference`` driven in a closed loop, every
request and token stamped on the benchmark's own clock from what
``InferenceEngine.step()`` returns."""

import functools

import numpy as np

from benchmarks import harness
from benchmarks.harness import clock


def _paged_next_logits(engine, prefix):
    """Next-token logits after ``prefix`` through the engine's own paged programs, as
    the engine drives them (chunked prefill, then one decode step), on the engine's
    own pools while no request is live."""
    import jax.numpy as jnp
    chunk, slots = engine.prefill_chunk, engine.num_slots
    k_pool, v_pool = engine.k_pool, engine.v_pool
    body = prefix[:-1]
    table = np.zeros(engine.max_blocks, np.int32)            # 0 is the null page
    n_blocks = -(-len(prefix) // engine.block_size)
    table[:n_blocks] = 1 + np.arange(n_blocks)
    for pos in range(0, len(body), chunk):
        piece = body[pos:pos + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(piece)] = piece
        _, k_pool, v_pool = engine._prefill(
            engine.params, jnp.asarray(toks), jnp.int32(pos), jnp.int32(len(piece)),
            jnp.asarray(table), k_pool, v_pool)
    toks, pos = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
    tables = np.zeros((slots, engine.max_blocks), np.int32)
    active = np.zeros(slots, bool)
    toks[0], pos[0], tables[0], active[0] = prefix[-1], len(body), table, True
    logits, k_pool, v_pool = engine._decode(
        engine.params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(tables),
        jnp.asarray(active), k_pool, v_pool)
    engine.k_pool, engine.v_pool = k_pool, v_pool
    return np.asarray(logits[0], np.float32)


def _check_reference(ctx, engine, vocab):
    """For a seeded sample of prefixes, the paged path's next-token logits against the
    plain reference's full forward pass."""
    import jax
    config, m, traffic = ctx["config"], ctx["config"]["model"], ctx["traffic"]
    ref = ctx["manifest"].reference(config["reference"]["module"])
    tol = ctx["manifest"].tolerance(config["reference"]["tolerance"])
    full = jax.jit(functools.partial(ref.logits, n_head=m["n_head"], eps=m["layer_norm_epsilon"]))
    rng = np.random.default_rng([ctx["seed"], 0x726566])
    pad_to = engine.max_model_len
    worst, rows = 0.0, []
    for n in traffic["correctness_sample"]:
        n = min(n, pad_to - 1)
        prefix = rng.integers(0, vocab, size=n).astype(np.int32)
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :n] = prefix
        want = np.asarray(full(engine.params, padded)[0, n - 1], np.float32)
        got = _paged_next_logits(engine, prefix.tolist())
        rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        rows.append({"prefix_len": int(n), "rel_diff": rel})
        worst = max(worst, rel)
    return {"samples": rows, "worst_rel_diff": worst, "tolerance": tol,
            "ok": bool(np.isfinite(worst) and worst <= tol)}


class _Live:
    """A request in flight, on the benchmark's clock."""
    __slots__ = ("submitted", "want", "last_token", "prefill_started")

    def __init__(self, submitted, want):
        self.submitted, self.want = submitted, want
        self.last_token = self.prefill_started = None


class ClosedLoop:
    """As many clients as slots; each sends its next request when its last finished."""

    def __init__(self, engine, requests, tracing):
        from deepspeed_tpu.serve.scheduler import Request
        self.Request, self.engine, self.requests, self.tr = Request, engine, requests, tracing
        self.n = 0
        self.live = {}            # req_id -> _Live
        self.finished = self.failed = self.preempted = 0
        # one row an event, stamped with the return of the iteration that produced it
        self.ttft, self.gaps, self.prefill_wait, self.iterations = [], [], [], []

    def submit(self):
        with self.tr.span("data"):
            prompt, want = next(self.requests)
        req_id = f"r{self.n}"
        self.n += 1
        now = clock()
        refused = self.engine.submit(self.Request(req_id, prompt, want))
        if refused is not None:
            self.failed += 1
            self.engine.outputs.pop(req_id, None)
            return
        self.live[req_id] = _Live(now, want)

    def iterate(self):
        ta = clock()
        with self.tr.span("dispatch"):
            log = self.engine.step()
        tb = clock()
        with self.tr.span("schedule"):
            tokens = 0
            pf = log["prefill"]
            if pf is not None:
                req_id, _, _, prompt_done = pf
                state = self.live[req_id]
                if state.prefill_started is None:
                    state.prefill_started = ta
                    self.prefill_wait.append((tb, (ta - state.submitted) * 1e3))
                if prompt_done:                      # its first token was sampled
                    state.last_token = tb
                    self.ttft.append((tb, (tb - state.submitted) * 1e3))
                    tokens += 1
            for req_id, _, _ in log["decode"]:
                state = self.live[req_id]
                self.gaps.append((tb, (tb - state.last_token) * 1e3))
                state.last_token = tb
                tokens += 1
            self.preempted += len(log["preempted"])
            for req_id in log["finished"]:
                want = self.live.pop(req_id).want
                out = self.engine.outputs.pop(req_id)
                if out.status == "finished" and len(out.tokens) == want:
                    self.finished += 1
                else:
                    self.failed += 1
                self.submit()
            self.iterations.append((tb, (tb - ta) * 1e3, len(log["decode"]), tokens))
        return tb


def run(ctx):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    cell, config, traffic, log = ctx["cell"], ctx["config"], ctx["traffic"], ctx["log"]
    tr = ctx["tracing"]
    setup = {}

    t = clock()
    model = harness.build_gpt2(config)
    params = harness.init_params(model, ctx["seed"], dtype=getattr(jnp, config["weights_dtype"]))
    setup["weights_s"] = clock() - t
    t = clock()
    engine = deepspeed_tpu.init_inference(
        model=model, model_parameters=params,
        config_params={"serving": dict(config["serving"], enabled=True)})
    setup["engine_s"] = clock() - t
    t = clock()
    vocab = config["model"]["vocab_size"]          # ids the tokenizer can produce
    reference = _check_reference(ctx, engine, vocab)
    setup["reference_s"] = clock() - t

    # fill: run the loop until every slot has turned over once
    t = clock()
    requests, _ = ctx["manifest"].generator(traffic["generator"])(traffic, ctx["seed"], vocab=vocab)
    loop = ClosedLoop(engine, requests, tr)
    slots = engine.num_slots
    for _ in range(slots):
        loop.submit()
    first = set(loop.live)
    while first & set(loop.live):
        loop.iterate()
    setup["fill_s"] = clock() - t
    setup["fill_iterations"] = len(loop.iterations)
    setup["compile_s"] = log.counts["compile_s"]
    setup["compiles"] = log.counts["compiles"]
    setup["cache_hits"] = log.counts["cache_hits"]

    seconds = min(ctx["seconds"], cell["trace_seconds"]) if tr.on else ctx["seconds"]
    harness.quiet_host()
    mark = log.mark()
    done_before, failed_before = loop.finished, loop.failed
    with tr.window():
        t0 = t1 = clock()
        while t1 - t0 < seconds:
            t1 = loop.iterate()
    window_compiles = log.since(mark)["compiles"]
    window_s = t1 - t0

    # every row is stamped with the return of the iteration that produced it
    its = [r for r in loop.iterations if r[0] > t0]
    iteration_ms, lanes = [r[1] for r in its], [r[2] for r in its]
    tokens = sum(r[3] for r in its)
    ttft, gaps, waits = ([ms for t, ms in rows if t > t0]
                         for rows in (loop.ttft, loop.gaps, loop.prefill_wait))
    finished = loop.finished - done_before
    failed = loop.failed - failed_before + loop.preempted
    correct = bool(failed == 0 and loop.failed == 0 and finished > 0 and window_compiles == 0
                   and reference["ok"])

    harness.write_record(ctx["out_dir"], cell["name"], ctx["seed"], {
        "cell": cell["name"], "seed": ctx["seed"], "traced": tr.on, "window_s": window_s,
        "iteration_ms": iteration_ms, "decode_lanes": lanes,
        "ttft_ms": ttft, "setup": setup, "reference": reference})
    harness.summary_line("iteration", iteration_ms, requests_finished=finished,
                         tokens=tokens, ttft_samples=len(ttft), gap_samples=len(gaps),
                         reference=reference, setup=setup, window_compiles=window_compiles,
                         preempted=loop.preempted)

    return {
        "correct": correct, "attempted": finished + failed, "failed": failed,
        "t_window_start": t0, "window_s": window_s, "setup": setup,
        "end_to_end": {"serve_tokens_per_s": tokens / window_s,
                       "ttft_ms_p95": harness.percentile(ttft, 95) if ttft else None,
                       "token_gap_ms_p95": harness.percentile(gaps, 95) if gaps else None},
        "memory_peak_bytes": harness.memory_peak_bytes(ctx["devices"]),
        "kind": "serve", "chips": cell["chips"], "slots": slots,
        "iteration_ms": iteration_ms, "decode_lanes": lanes, "prefill_wait_ms": waits,
    }
