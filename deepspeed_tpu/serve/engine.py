"""InferenceEngine: continuous-batching serving over the paged KV cache.

The engine owns the device state (params + one paged KV pool, aliased through
every program by donation) and executes the scheduler's host decisions in a
fixed per-iteration order:

    admit -> ensure write blocks (CoW page copies) -> one prefill chunk
          -> one decode step for every live lane -> sampling heads

Every device program has one fixed abstract signature (serve/paged.py), so the
whole serving loop compiles each program exactly once — ``ds-tpu serve-sim``
asserts this through the compile watchdog. Sampling is host-side for the
single-lane path — exact greedy (np.argmax over the fetched f32 logits row,
same first-max tie-break as the in-graph jnp.argmax) when ``temperature <= 0``,
else temperature/top-k/top-p sampling with a counter-based RNG keyed on
``(request seed, token position)`` so replays and preempt-restarts regenerate
identical tokens — and a tiny fixed-shape device program per beam step.

``mirror=True`` runs the dense-cache oracle (serve/oracle.py) in lockstep and
asserts the paged logits are **bitwise identical** to the dense ones every
prefill chunk and every decode step — the standing proof that paging is a
memory-layout change, not a numerics change.
"""

import time

import numpy as np

import jax.numpy as jnp

from .block_allocator import NULL_BLOCK
from .paged import build_paged_programs
from .request_trace import RequestTracer
from .scheduler import RequestOutput, Scheduler

_MAX_IDLE_SKIP = 1 << 30


class InferenceEngine:
    def __init__(self, model, params, *, num_slots=8, block_size=16,
                 num_blocks=257, max_model_len=256, prefill_chunk=32,
                 use_pallas=False, telemetry=None, mirror=False,
                 request_trace=None, prefix_cache=False, sharding=None,
                 speculation=None):
        c = model.config
        spec_cfg = speculation if (speculation or {}).get("enabled") else None
        if max_model_len % block_size != 0:
            raise ValueError(f"max_model_len {max_model_len} not a multiple "
                             f"of block_size {block_size}")
        if max_model_len > c.n_positions:
            raise ValueError(f"max_model_len {max_model_len} exceeds the "
                             f"model's n_positions {c.n_positions}")
        if getattr(c, "moe_experts", 0):
            raise ValueError("serving supports dense models only (no MoE)")
        if getattr(c, "sparse_attention", None):
            raise ValueError("serving supports dense attention only")
        if isinstance(sharding, dict):
            tp = int(sharding.get("model", 1))
        else:
            tp = int(sharding or 1)
        if tp < 1:
            raise ValueError(f"serving.sharding.model must be >= 1, got {tp}")
        if tp > 1:
            import jax
            if c.n_head % tp:
                raise ValueError(f"n_head {c.n_head} not divisible by "
                                 f"serving.sharding.model {tp}")
            if tp > len(jax.devices()):
                raise ValueError(f"serving.sharding.model {tp} exceeds "
                                 f"{len(jax.devices())} devices")
            if mirror:
                raise ValueError(
                    "mirror asserts bitwise identity against the dense "
                    "oracle; the sharded proj psum reorders each reduction "
                    "(token-identical, not bitwise) — run the mirror on an "
                    "unsharded engine")
        if mirror and prefix_cache:
            raise ValueError(
                "mirror cannot run with prefix_cache: a warm start skips "
                "prefill chunks whose KV the dense per-slot oracle no longer "
                "holds (its cache is overwritten on slot reuse) — prove "
                "bitwise identity on a cache-off engine instead")
        if spec_cfg is not None and mirror:
            raise ValueError(
                "mirror asserts bitwise identity against the dense oracle; "
                "the K+1-wide spec_verify program fuses the batch differently "
                "than the 1-wide decode_step (token-identical, not bitwise — "
                "the sharded-psum precedent) and commits multiple tokens per "
                "step the per-step oracle cannot follow — run the mirror on a "
                "speculation-off engine, and pin speculative token identity "
                "with `ds-tpu serve-sim --compare-speculate` instead")
        if spec_cfg is not None and tp > 1:
            raise ValueError(
                "speculation + serving.sharding.model > 1 is not supported: "
                "the spec_verify program is single-chip only (shard the "
                "target OR speculate, not both)")
        self.tp = tp
        self.model = model
        self.params = params
        self.num_slots = int(num_slots)
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.max_model_len = int(max_model_len)
        self.max_blocks = self.max_model_len // self.block_size
        self.prefill_chunk = int(prefill_chunk)
        self.telemetry = telemetry
        # the non-perturbing gate: with serving.request_trace disabled the
        # tracer is None — no attribute exists for compiled code to close
        # over, and every hook below is a `is not None` host branch
        # (tests/unit/test_request_trace.py pins HLO-identity on/off)
        rt = request_trace or {}
        self.tracer = None
        if rt.get("enabled"):
            self.tracer = RequestTracer(
                capacity=rt.get("capacity", 256),
                iteration_capacity=rt.get("iteration_capacity", 4096),
                dump_dir=rt.get("dump_dir") or None,
                slo=rt.get("slo"),
                host_id=rt.get("host_id", 0))

        self._mesh = None
        if tp > 1:
            import jax
            from ..comm.topology import CommTopology
            from ..parallel.mesh import build_mesh
            self._mesh = build_mesh(data=1, model=tp, pipe=1,
                                    devices=jax.devices()[:tp])
            if self.telemetry is not None:
                # classify the decode/prefill psums' links for the wire-byte
                # ledger: the model axis of one serving replica rides a
                # single slice, so its collectives are all-ICI wire
                topo = CommTopology(tp, 1)
                self.telemetry.set_comm_topology(
                    topo.slice_device_sets(self._mesh))
        self._spec = None
        self._verify = None
        self.spec_k = 0
        if spec_cfg is not None:
            from .speculative import SpeculativeDecoder
            draft_model = spec_cfg.get("draft_model")
            draft_params = spec_cfg.get("draft_params")
            if draft_model is None or draft_params is None:
                raise ValueError(
                    "serving.speculation.enabled needs a live draft model: "
                    "pass draft_model= and draft_parameters= to "
                    "deepspeed.init_inference (the config's draft_model key "
                    "is a label, not a loader)")
            self.spec_k = int(spec_cfg.get("max_draft_tokens", 4))
            self._spec = SpeculativeDecoder(
                draft_model, draft_params, num_slots=self.num_slots,
                block_size=self.block_size, max_blocks=self.max_blocks,
                prefill_chunk=self.prefill_chunk,
                draft_pool_blocks=(int(spec_cfg.get("draft_pool_blocks") or 0)
                                   or self.num_blocks),
                max_draft_tokens=self.spec_k, target_config=c,
                watch=self._watch)
        self._raw = build_paged_programs(
            model, num_slots=self.num_slots, block_size=self.block_size,
            max_blocks=self.max_blocks, prefill_chunk=self.prefill_chunk,
            use_pallas=use_pallas, mesh=self._mesh,
            verify_width=self.spec_k + 1 if self._spec is not None else 0)
        if self._spec is not None:
            self._verify = self._watch("serve:spec_verify",
                                       self._raw["spec_verify"])
        self._decode = self._watch("serve:decode_step", self._raw["decode_step"])
        self._prefill = self._watch("serve:prefill_chunk",
                                    self._raw["prefill_chunk"])
        self._copy = self._watch("serve:copy_blocks", self._raw["copy_blocks"])
        self._beam_watched = {}
        self.copy_width = self._raw["copy_width"]

        pool_shape = (c.n_layer, self.num_blocks, self.block_size,
                      c.n_head, c.head_dim)
        self.k_pool = jnp.zeros(pool_shape, c.compute_dtype)
        self.v_pool = jnp.zeros(pool_shape, c.compute_dtype)
        if self._mesh is not None:
            import jax
            self.k_pool = jax.device_put(self.k_pool,
                                         self._raw["pool_sharding"])
            self.v_pool = jax.device_put(self.v_pool,
                                         self._raw["pool_sharding"])

        self.scheduler = Scheduler(
            num_slots=self.num_slots, num_blocks=self.num_blocks,
            block_size=self.block_size, max_model_len=self.max_model_len,
            prefill_chunk=self.prefill_chunk, prefix_cache=prefix_cache)
        self.prefix_cache = self.scheduler.prefix_cache

        self._mirror = None
        self.mirror_checks = 0
        if mirror:
            from .oracle import build_oracle_programs
            self._mirror = build_oracle_programs(
                model, num_slots=self.num_slots, max_len=self.max_model_len,
                prefill_chunk=self.prefill_chunk)
            self._okcs, self._ovcs = self._mirror["fresh_caches"]()

        self._it = 0
        self._order = []                    # req_id submission order
        self.outputs = {}                   # req_id -> RequestOutput
        self._submit_ms = {}
        self._start_wall = None
        self._tokens_sampled = 0            # every appended token
        self._tokens_finished = 0           # tokens of finished requests only
        # target-model step accounting (speculation's headline number):
        # _target_steps counts target program executions (prefill chunks,
        # decode steps, spec verifies); _advance_steps counts per-GROUP
        # participations in a token-advancing step, so advance/token reads
        # ~1.0 for plain greedy and ~1/(1+E[accepted]) with speculation
        self._target_steps = 0
        self._advance_steps = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_rounds = 0

    # ------------------------------------------------------------- plumbing
    def _watch(self, name, fn):
        return self.telemetry.watch(name, fn) if self.telemetry else fn

    def _beam_head(self, kind, g):
        K, eos = g.lanes, g.req.eos_token_id
        key = (kind, K, eos)
        if key not in self._beam_watched:
            fn = self._raw[f"beam_{kind}"](K, eos)
            self._beam_watched[key] = self._watch(
                f"serve:beam_{kind}_k{K}_e{eos}", fn)
        return self._beam_watched[key]

    def _scalar(self, name, value):
        if self.telemetry is not None:
            self.telemetry.monitor.add_scalar(f"Serving/{name}",
                                              float(value), self._it)

    # ----------------------------------------------------------- submission
    def submit(self, req):
        """Queue a request; infeasible ones are refused (a RequestOutput with
        status "refused"), never crash the engine."""
        self._order.append(req.req_id)
        self._submit_ms[req.req_id] = time.perf_counter()
        if self.tracer is not None:
            self.tracer.on_submit(req)
        reason = self.scheduler.submit(req)
        if reason is not None:
            if self.tracer is not None:
                self.tracer.on_refused(req, reason)
            out = RequestOutput(req.req_id, "refused", refusal=reason)
            self.outputs[req.req_id] = out
            return out
        return None

    # ---------------------------------------------------------- the big loop
    def step(self):
        """One serving iteration. Returns the schedule-log dict — pure host
        decisions only, so a trace replay is byte-identical (json.dumps)."""
        if self._start_wall is None:
            self._start_wall = time.perf_counter()
        sched, it, tr = self.scheduler, self._it, self.tracer
        log = {"it": it}
        if tr is not None:
            tr.begin_iteration(it)

        admitted = sched.admit(it)
        preempted, copies = sched.ensure_decode_room()
        log["admitted"] = [g.req.req_id for g in admitted]
        log["preempted"] = [g.req.req_id for g in preempted]
        log["copies"] = [list(c) for c in copies]
        if tr is not None:
            for g in admitted:
                tr.on_admit(g, it)
            for g in preempted:
                tr.on_preempt(g, it, g.evicted_blocks)
        self._run_copies(copies)

        log["prefill"] = self._prefill_one(it)
        spec_res = self._speculate_all(it) if self._spec is not None else None
        if spec_res is not None:
            log["spec"], log["decode"], log["finished"] = spec_res
        else:
            if self._spec is not None:
                log["spec"] = []
            log["decode"], log["finished"] = self._decode_all(it)

        self._scalar("occupancy", sched.occupancy())
        self._scalar("waiting", len(sched.waiting))
        self._scalar("free_blocks", sched.allocator.num_free)
        if self.prefix_cache is not None:
            pc = self.prefix_cache.stats()
            self._scalar("PrefixCache/hit_rate", pc["hit_rate"])
            self._scalar("PrefixCache/hit_tokens", pc["hit_tokens"])
            self._scalar("PrefixCache/parked_blocks", pc["parked_blocks"])
            self._scalar("PrefixCache/evictions", pc["evictions"])
        if self._spec is not None:
            s = self.spec_summary()
            self._scalar("Spec/acceptance_rate", s["spec_acceptance_rate"])
            self._scalar("Spec/drafted_tokens", s["drafted_tokens"])
            self._scalar("Spec/accepted_tokens", s["accepted_tokens"])
            self._scalar("Spec/wasted_draft_tokens",
                         s["wasted_draft_tokens"])
            self._scalar("Spec/target_steps_per_token",
                         s["target_steps_per_token"])
        elapsed = max(time.perf_counter() - self._start_wall, 1e-9)
        self._scalar("tok_s", self._tokens_sampled / elapsed)
        self._scalar("goodput_tok_s", self._tokens_finished / elapsed)
        if tr is not None:
            itrec = tr.end_iteration(len(sched.waiting), len(sched.running),
                                     sched.pool_stats())
            ws = tr.waste_summary()
            self._scalar("Waste/replayed_tokens", ws["replayed_tokens"])
            self._scalar("Waste/fraction", ws["waste_fraction"])
            self._scalar("Pool/fragmentation", itrec["pool"]["frag"])
            if self.telemetry is not None:
                self.telemetry.end_step(it, 1, serving=tr.latency_summary())

        self._it += 1
        return log

    def run(self, requests):
        """Submit everything, drive steps until drained. Returns (outputs in
        submission order, per-iteration schedule log)."""
        for r in requests:
            self.submit(r)
        logs = []
        guard = 0
        while not self.scheduler.idle:
            if not self.scheduler.running:
                na = self.scheduler.next_arrival()
                if na is not None and na > self._it:
                    self._it = na           # fast-forward idle iterations
            logs.append(self.step())
            guard += 1
            if guard > 200000:
                raise RuntimeError("serving loop failed to drain (bug)")
        return [self.outputs[rid] for rid in self._order], logs

    # -------------------------------------------------------------- internals
    def _run_copies(self, copies):
        P = self.copy_width
        for i in range(0, len(copies), P):
            batch = copies[i:i + P]
            src = np.zeros(P, np.int32)     # pads: null 0 -> 0 self-copy
            dst = np.zeros(P, np.int32)
            for j, (s, d) in enumerate(batch):
                src[j], dst[j] = s, d
            self.k_pool, self.v_pool = self._copy(
                self.k_pool, self.v_pool, jnp.asarray(src), jnp.asarray(dst))

    def _pad_table(self, table):
        out = np.full(self.max_blocks, NULL_BLOCK, np.int32)
        out[:len(table)] = table
        return out

    def _prefill_one(self, it):
        pf = self.scheduler.next_prefill(it)
        if pf is None:
            return None
        g, pos, n, chunk = pf
        toks = jnp.asarray([chunk], jnp.int32)
        table = jnp.asarray(self._pad_table(g.tables[0]))
        logits, self.k_pool, self.v_pool = self._prefill(
            self.params, toks, jnp.int32(pos), jnp.int32(n), table,
            self.k_pool, self.v_pool)
        self._target_steps += 1
        if self._mirror is not None:
            ol, self._okcs, self._ovcs = self._mirror["prefill_chunk"](
                self.params, toks, jnp.int32(pos), jnp.int32(n),
                jnp.int32(g.slots[0]), self._okcs, self._ovcs)
            self._assert_bitwise(logits, ol, f"prefill it={it} "
                                 f"req={g.req.req_id} pos={pos}")
        if self.tracer is not None:
            self.tracer.on_prefill(g, it, pos, n, g.prefill_replay_tokens(pos, n))
        done = self.scheduler.finish_prefill_chunk(g, n, it)
        if done:
            self._first_tokens(g, logits, it)
        return [g.req.req_id, pos, n, bool(done)]

    def _first_tokens(self, g, logits, it):
        if g.lanes == 1:
            tok = self._sample_token(g, np.asarray(logits[0]), 0)
            self.scheduler.begin_decode(g, [tok], it)
        else:
            scores, tok0, live = self._beam_head("init", g)(logits)
            self.scheduler.begin_decode(
                g, [int(t) for t in np.asarray(tok0)], it,
                scores=np.asarray(scores), live=np.asarray(live))
            if self._mirror is not None and g.lanes > 1:
                perm = np.arange(self.num_slots, dtype=np.int32)
                perm[np.asarray(g.slots[1:], np.int32)] = g.slots[0]
                self._okcs, self._ovcs = self._mirror["reorder"](
                    self._okcs, self._ovcs, jnp.asarray(perm))
        self._tokens_sampled += g.lanes
        if self.tracer is not None:
            # single-source TTFT: the ledger record feeds the Group field,
            # the Serving/* scalars AND the RequestOutput fields (they read
            # the same numbers, so they cannot drift)
            self.tracer.on_fork(g, it)
            ttft_ms, ttft_iters = self.tracer.on_first_token(g, it)
        else:
            ttft_ms = (time.perf_counter()
                       - self._submit_ms[g.req.req_id]) * 1000.0
            ttft_iters = it - g.req.arrival
        g.first_token_ms = ttft_ms
        self._scalar("ttft_ms", ttft_ms)
        self._scalar("ttft_iters", ttft_iters)

    # -------------------------------------------------------- speculation
    def _extend_target_table(self, g, m, copies):
        """Cover write positions ``next_pos .. next_pos+m`` in the group's
        target block table before a verify step: fresh pages past the end,
        ``ensure_exclusive`` (CoW) for existing shared ones — the same
        discipline as Scheduler._ensure_group_blocks, widened to the verify
        window. On pool exhaustion the appended pages go back and the table
        shrinks to its original length (the group plain-decodes this
        iteration); CoW swaps that already happened keep their device copy,
        the pages are genuinely exclusive now (the scheduler precedent)."""
        from .block_allocator import AllocationError
        alloc = self.scheduler.allocator
        BS = self.block_size
        table = g.tables[0]
        orig_len = len(table)
        p0 = g.next_pos(0)
        try:
            for bi in range(p0 // BS, (p0 + m) // BS + 1):
                if bi == len(table):
                    table.append(alloc.allocate(1)[0])
                else:
                    blk, copy = alloc.ensure_exclusive(table[bi])
                    if copy is not None:
                        table[bi] = blk
                        copies.append(copy)
        except AllocationError:
            if len(table) > orig_len:
                alloc.free(table[orig_len:])
                del table[orig_len:]
            return False
        return True

    def _speculate_all(self, it):
        """One speculative decode round, replacing ``_decode_all`` for the
        whole iteration: eligible single-lane greedy groups get up to K draft
        proposals verified at K+1 positions, and EVERY other decode lane
        (beam lanes, sampled lanes, groups that lost a draft-page race) rides
        the same ``spec_verify`` execution as a plain ``n_valid=1`` row — so
        a speculative iteration still executes exactly ONE target
        decode-domain program, and "strictly fewer target steps" holds at
        the program-execution level, not just per token.

        Accepted prefixes (plus the target's own next token) commit; the
        first rejection truncates the block table to the accepted frontier
        and refcount-releases the tail (free rollback — the kept partial
        page's garbage tail is never attended and is overwritten next
        round). Returns ``(spec_log, decode_log, finished)``, or None when
        no group can draft this iteration (the caller falls back to the
        cheaper 1-wide ``decode_step``)."""
        spec, sched, alloc = self._spec, self.scheduler, self.scheduler.allocator
        spec.sync(sched.running)
        lanes = [(g, lane, slot) for g, lane, slot in
                 sched.decode_lanes() if g.entered_decode_it != it]
        plan, copies = [], []
        for g, lane, slot in lanes:
            if lane != 0 or g.lanes != 1 or g.req.temperature > 0.0:
                continue
            # never draft past the request budget: m proposals commit at most
            # m+1 tokens, and the final token must come from a verify row so
            # the emitted stream matches plain decode's finish check exactly
            m = min(self.spec_k,
                    g.req.max_new_tokens - len(g.generated[0]) - 1)
            if m < 1:
                continue
            if not spec.prepare(g, m):
                continue
            if not self._extend_target_table(g, m, copies):
                continue
            plan.append((g, m))
        self._run_copies(copies)
        if not plan:
            return None

        drafts = spec.propose(plan)
        plan_groups = {id(g) for g, _ in plan}
        plain = [(g, lane, slot) for g, lane, slot in lanes
                 if id(g) not in plan_groups]
        decode_log = [[g.req.req_id, lane, slot] for g, lane, slot in plain]
        if self.tracer is not None:
            traced = set()
            for g, _, _ in plain:
                if id(g) in traced:
                    continue
                traced.add(id(g))
                self.tracer.on_decode(
                    g, it, g.lanes, g.lanes if g.decode_is_replay() else 0)

        S, D = self.num_slots, self.spec_k + 1
        toks = np.zeros((S, D), np.int32)
        pos0 = np.zeros(S, np.int32)
        n_valid = np.zeros(S, np.int32)
        tables = np.full((S, self.max_blocks), NULL_BLOCK, np.int32)
        active = np.zeros(S, bool)
        for g, m in plan:
            slot = g.slots[0]
            toks[slot, 0] = g.generated[0][-1]
            toks[slot, 1:1 + m] = drafts[spec._key(g)]
            pos0[slot] = g.next_pos(0)
            n_valid[slot] = m + 1
            tables[slot] = self._pad_table(g.tables[0])
            active[slot] = True
        for g, lane, slot in plain:
            toks[slot, 0] = g.generated[lane][-1]
            pos0[slot] = g.next_pos(lane)
            n_valid[slot] = 1
            tables[slot] = self._pad_table(g.tables[lane])
            active[slot] = True
        logits, self.k_pool, self.v_pool = self._verify(
            self.params, jnp.asarray(toks), jnp.asarray(pos0),
            jnp.asarray(n_valid), jnp.asarray(tables), jnp.asarray(active),
            self.k_pool, self.v_pool)
        self._target_steps += 1
        self._advance_steps += len(plan) + len({id(g) for g, _, _ in plain})
        self._spec_rounds += 1
        logits_np = np.asarray(logits)

        spec_log, finished = [], []
        for g, m in plan:
            slot = g.slots[0]
            p0 = g.next_pos(0)
            ds = drafts[spec._key(g)]
            len_before = len(g.generated[0])
            eos, L = g.req.eos_token_id, g.req.max_new_tokens
            committed, a, fin = [], 0, False
            for i in range(m + 1):
                t = int(np.argmax(logits_np[slot, i]))
                committed.append(t)
                matched = i < m and ds[i] == t
                if matched:
                    a += 1
                # the exact _sample_greedy finish check, applied per token
                if (len_before + len(committed) >= L
                        or (eos >= 0 and t == eos)):
                    fin = True
                    break
                if not matched:
                    break
            g.generated[0].extend(committed)
            self._tokens_sampled += len(committed)
            self._spec_drafted += m
            self._spec_accepted += a
            r = min(max(g.replay_decode_hwm - len_before, 0), len(committed))
            if self.tracer is not None:
                self.tracer.on_spec(g, it, drafted=m, accepted=a,
                                    committed=len(committed), replayed=r)
            spec_log.append([g.req.req_id, m, a, len(committed)])
            if fin:
                self._finish(g, g.generated[0], None, finished, it)
                continue
            # rollback: the table only needs to cover the committed frontier
            # (positions <= p0 + a hold valid KV)
            keep = alloc.blocks_for_tokens(p0 + a + 1)
            table = g.tables[0]
            if keep < len(table):
                alloc.free(table[keep:])
                del table[keep:]
            spec.observe(g, p0, a, m)

        # the ride-along lanes sample from verify row 0 — greedy argmax is
        # token-identical to decode_step's row (the --compare-speculate
        # contract); beam heads consume the device row like the sharded
        # engine's psum'd logits (token-identical precedent)
        logits0_np = logits_np[:, 0]
        logits0 = None
        for g in list(sched.running):
            if (g.phase != "decode" or g.entered_decode_it == it
                    or id(g) in plan_groups):
                continue
            if g.lanes == 1:
                self._sample_greedy(g, logits0_np, finished, it)
            else:
                if logits0 is None:
                    logits0 = logits[:, 0]
                self._sample_beam(g, logits0, finished, it)
        return spec_log, decode_log, finished

    def _decode_all(self, it):
        # a group that completed prefill THIS iteration sits out one decode:
        # its first write block is ensured at the NEXT iteration's start
        lanes = [(g, lane, slot) for g, lane, slot in
                 self.scheduler.decode_lanes() if g.entered_decode_it != it]
        decode_log = [[g.req.req_id, lane, slot] for g, lane, slot in lanes]
        if not lanes:
            return decode_log, []
        if self.tracer is not None:
            # classify BEFORE sampling appends: a step whose pre-append token
            # count sits below the group's replay high-water mark regenerates
            # work a preempted attempt already did (all K lanes of it)
            traced = set()
            for g, _, _ in lanes:
                if id(g) in traced:
                    continue
                traced.add(id(g))
                self.tracer.on_decode(
                    g, it, g.lanes, g.lanes if g.decode_is_replay() else 0)
        S = self.num_slots
        toks = np.zeros(S, np.int32)
        pos = np.zeros(S, np.int32)
        tables = np.full((S, self.max_blocks), NULL_BLOCK, np.int32)
        active = np.zeros(S, bool)
        for g, lane, slot in lanes:
            toks[slot] = g.generated[lane][-1]
            pos[slot] = g.next_pos(lane)
            tables[slot] = self._pad_table(g.tables[lane])
            active[slot] = True
        logits, self.k_pool, self.v_pool = self._decode(
            self.params, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(tables), jnp.asarray(active),
            self.k_pool, self.v_pool)
        self._target_steps += 1
        self._advance_steps += len({id(g) for g, _, _ in lanes})
        if self._mirror is not None:
            ol, self._okcs, self._ovcs = self._mirror["decode_step"](
                self.params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(active), self._okcs, self._ovcs)
            self._assert_bitwise(logits, ol, f"decode it={it}", rows=active)
        logits_np = np.asarray(logits)

        finished = []
        for g in list(self.scheduler.running):
            if g.phase != "decode" or g.entered_decode_it == it:
                continue                    # groups that just prefilled wait
            if g.lanes == 1:
                self._sample_greedy(g, logits_np, finished, it)
            else:
                self._sample_beam(g, logits, finished, it)
        return decode_log, finished

    def _sample_token(self, g, logits_row, position):
        """Next token for a single-lane group from its f32 logits row.

        ``temperature <= 0`` is the exact historical greedy path. Otherwise:
        scale by temperature, apply top-k then nucleus truncation, softmax in
        f64 (host math — bit-stable across platforms), and invert the CDF at a
        uniform drawn from ``default_rng([seed, position])``. The counter-based
        keying makes every draw a pure function of (request, position): replays
        and preempt-restarts (bit-identical logits) resample identical tokens,
        and no RNG state needs checkpointing or preemption care."""
        req = g.req
        if req.temperature <= 0.0:
            return int(np.argmax(logits_row))
        logits = np.asarray(logits_row, np.float64) / req.temperature
        if 0 < req.top_k < logits.size:
            kth = np.partition(logits, -req.top_k)[-req.top_k]
            logits = np.where(logits < kth, -np.inf, logits)
        probs = np.exp(logits - np.max(logits))
        probs /= probs.sum()
        if req.top_p < 1.0:
            order = np.argsort(-logits, kind="stable")
            csum = np.cumsum(probs[order])
            # smallest prefix reaching top_p, always keeping the crossing token
            cut = int(np.searchsorted(csum, req.top_p, side="left")) + 1
            mask = np.zeros(probs.size, bool)
            mask[order[:cut]] = True
            probs = np.where(mask, probs, 0.0)
            probs /= probs.sum()
        u = np.random.default_rng([req.seed, position]).random()
        tok = int(np.searchsorted(np.cumsum(probs), u, side="right"))
        tok = min(tok, probs.size - 1)
        while tok > 0 and probs[tok] == 0.0:   # float-edge guard: never emit a
            tok -= 1                           # truncated (zero-mass) token
        return tok

    def _sample_greedy(self, g, logits_np, finished, it):
        tok = self._sample_token(g, logits_np[g.slots[0]], len(g.generated[0]))
        g.generated[0].append(tok)
        self._tokens_sampled += 1
        eos = g.req.eos_token_id
        if (len(g.generated[0]) >= g.req.max_new_tokens
                or (eos >= 0 and tok == eos)):
            self._finish(g, g.generated[0], None, finished, it)

    def _sample_beam(self, g, logits, finished, it):
        scores, parents, toks, live = self._beam_head("select", g)(
            logits, jnp.asarray(g.slots, jnp.int32),
            jnp.asarray(g.scores, jnp.float32), jnp.asarray(g.live, bool))
        parents = [int(p) for p in np.asarray(parents)]
        old_slot_of = [g.slots[p] for p in parents]
        self.scheduler.reorder_beams(g, parents)
        if self._mirror is not None:
            perm = np.arange(self.num_slots, dtype=np.int32)
            perm[np.asarray(g.slots, np.int32)] = old_slot_of
            self._okcs, self._ovcs = self._mirror["reorder"](
                self._okcs, self._ovcs, jnp.asarray(perm))
        for k, t in enumerate(np.asarray(toks)):
            g.generated[k].append(int(t))
        self._tokens_sampled += g.lanes
        g.scores = np.asarray(scores)
        g.live = np.asarray(live)
        if len(g.generated[0]) >= g.req.max_new_tokens:
            best, score = self._rank_beams(g)
            self._finish(g, best, score, finished, it)

    def _rank_beams(self, g):
        """Host replay of beam_search's GNMT final ranking: finished beams
        count tokens through EOS (clamped to L), unfinished count exactly L.
        Bitwise-identical to the dense path for length_penalty == 1.0."""
        L = float(g.req.max_new_tokens)
        eos = g.req.eos_token_id
        scores = np.asarray(g.scores, np.float32)
        if eos >= 0:
            lengths = []
            for toks in g.generated:
                n = 0
                for t in toks:
                    if t == eos:
                        break
                    n += 1
                lengths.append(min(n + 1.0, L))
        else:
            lengths = [L] * g.lanes
        lengths = np.asarray(lengths, np.float32)
        final = scores / np.power(lengths, np.float32(g.req.length_penalty))
        best = int(np.argmax(final))
        return g.generated[best], float(final[best])

    def _finish(self, g, tokens, score, finished, it):
        if self._spec is not None:
            self._spec.release(g)   # draft pages die with the request
        self.scheduler.finish_group(g)
        n = len(tokens)
        self._tokens_finished += n
        rec = (self.tracer.on_finish(g, it, n)
               if self.tracer is not None else None)
        if rec is not None:
            # ledger-derived bookkeeping (same record the timeline exports)
            out = RequestOutput(
                g.req.req_id, "finished", tokens=list(tokens), score=score,
                ttft_iters=rec.get("ttft_iters"), ttft_ms=rec.get("ttft_ms"),
                finished_it=rec["finished_it"],
                preemptions=rec["preemptions"])
        else:
            out = RequestOutput(
                g.req.req_id, "finished", tokens=list(tokens), score=score,
                ttft_iters=(g.first_token_it - g.req.arrival),
                ttft_ms=g.first_token_ms, finished_it=it,
                preemptions=getattr(g.req, "_preemptions_carry",
                                    g.preemptions))
        self.outputs[g.req.req_id] = out
        finished.append(g.req.req_id)

    def _assert_bitwise(self, paged, dense, what, rows=None):
        a, b = np.asarray(paged), np.asarray(dense)
        if rows is not None:
            a, b = a[rows], b[rows]
        if not np.array_equal(a, b):
            bad = int(np.sum(a != b))
            raise AssertionError(
                f"paged/dense logits diverged ({what}): {bad} of {a.size} "
                f"entries differ; max abs diff "
                f"{float(np.max(np.abs(a - b)))!r}")
        self.mirror_checks += 1

    # ------------------------------------------------------------- metrics
    @property
    def target_steps(self):
        """Target-model program executions so far (prefill chunks + decode
        steps + spec verifies) — speculation's strict-improvement number."""
        return self._target_steps

    def spec_summary(self):
        """Speculation efficiency counters (PERF.md 'target steps per
        token'): ``target_steps_per_token`` divides per-group participations
        in token-advancing steps by tokens sampled, so plain greedy reads
        ~1.0 and speculation ~1/(1 + E[accepted]) — the number the serve-sim
        ``--spec-steps-budget`` gate thresholds."""
        drafted, accepted = self._spec_drafted, self._spec_accepted
        return {
            "drafted_tokens": drafted,
            "accepted_tokens": accepted,
            "wasted_draft_tokens": drafted - accepted,
            "spec_rounds": self._spec_rounds,
            "spec_acceptance_rate": accepted / max(drafted, 1),
            "target_steps": self._target_steps,
            "advance_steps": self._advance_steps,
            "target_steps_per_token":
                self._advance_steps / max(self._tokens_sampled, 1),
        }

    # ------------------------------------------------------- fleet hooks
    def prefix_peek(self, prompt):
        """Read-only fleet-router probe: ``(hit_blocks, hit_tokens)`` this
        replica's prefix cache would serve for ``prompt`` — no stats are
        touched, no blocks are revived, so peeking every replica per arrival
        is free. ``(0, 0)`` when the cache is disabled."""
        if self.prefix_cache is None:
            return (0, 0)
        blocks, hit_tokens = self.prefix_cache.peek(prompt)
        return (len(blocks), hit_tokens)

    def load_view(self) -> dict:
        """Host-side load snapshot for fleet admission/balance decisions:
        queue depth, lane usage, and pool headroom, all exact counters the
        scheduler already maintains (no device sync)."""
        return {"waiting": len(self.scheduler.waiting),
                "running": len(self.scheduler.running),
                "free_slots": len(self.scheduler.free_slots),
                "free_blocks": self.scheduler.allocator.num_free,
                "num_blocks": self.num_blocks,
                "it": self._it}

    def fast_forward(self, it: int):
        """Advance the iteration clock without stepping — the fleet router
        keeps all replicas on one timebase, so a cold replacement joining at
        router iteration ``it`` must not restart from 0 (its arrivals and
        latency iteration-counts would otherwise be skewed)."""
        self._it = max(self._it, int(it))

    # ------------------------------------------------------- warm restart
    _OUT_FIELDS = ("req_id", "status", "tokens", "score", "refusal",
                   "ttft_iters", "ttft_ms", "finished_it", "preemptions")

    def geometry(self) -> dict:
        """Everything the paged programs' shapes (and therefore the KV pool
        bytes) depend on — a warm restart into a different geometry would
        read pages laid out for another engine, so restore validates this."""
        c = self.model.config
        return {"num_slots": self.num_slots, "block_size": self.block_size,
                "num_blocks": self.num_blocks,
                "max_model_len": self.max_model_len,
                "prefill_chunk": self.prefill_chunk, "tp": self.tp,
                "n_layer": int(c.n_layer), "n_head": int(c.n_head),
                "head_dim": int(c.head_dim),
                "compute_dtype": str(jnp.dtype(c.compute_dtype).name)}

    def state_dict(self) -> dict:
        """Warm-restart snapshot: quiesces the scheduler (preempting every
        running group so its prefill frontier parks in the prefix cache),
        then captures the KV pools, the allocator/cache/scheduler ledgers,
        and the request bookkeeping as host data. The restored replica remaps
        parked prompt pages through the prefix machinery instead of
        re-prefilling (docs/resilience.md)."""
        from .scheduler import pack_request  # noqa: F401  (re-export site)
        self.scheduler.quiesce()
        if self._spec is not None:
            # draft state is best-effort: the restored replica re-drafts from
            # each request's committed context (token-identity is unaffected)
            self._spec.drop_all()
        return {
            "geometry": self.geometry(),
            "scheduler": self.scheduler.state_dict(),
            "it": self._it,
            "order": list(self._order),
            "outputs": [{k: getattr(o, k) for k in self._OUT_FIELDS}
                        for o in self.outputs.values()],
            "tokens_sampled": self._tokens_sampled,
            "tokens_finished": self._tokens_finished,
            "k_pool": np.asarray(self.k_pool),
            "v_pool": np.asarray(self.v_pool),
        }

    def load_state_dict(self, state: dict) -> None:
        """Rejoin warm from a ``state_dict`` snapshot. Refuses (ValueError) a
        snapshot whose geometry does not match this engine — page indices and
        pool bytes are only meaningful under the exact same layout."""
        mine, theirs = self.geometry(), state["geometry"]
        if mine != theirs:
            diff = {k: (theirs.get(k), mine.get(k))
                    for k in sorted(set(mine) | set(theirs))
                    if theirs.get(k) != mine.get(k)}
            raise ValueError(f"serving warm restart refused: checkpoint "
                             f"geometry does not match this engine "
                             f"(checkpoint vs live): {diff}")
        self.scheduler.load_state_dict(state["scheduler"])
        self._it = int(state["it"])
        self._order = list(state["order"])
        self.outputs = {d["req_id"]: RequestOutput(**d)
                        for d in state["outputs"]}
        self._tokens_sampled = int(state["tokens_sampled"])
        self._tokens_finished = int(state["tokens_finished"])
        c = self.model.config
        self.k_pool = jnp.asarray(state["k_pool"], c.compute_dtype)
        self.v_pool = jnp.asarray(state["v_pool"], c.compute_dtype)
        if self._mesh is not None:
            import jax
            self.k_pool = jax.device_put(self.k_pool,
                                         self._raw["pool_sharding"])
            self.v_pool = jax.device_put(self.v_pool,
                                         self._raw["pool_sharding"])
        # wall-clock bookkeeping restarts: TTFT-ms of still-pending requests
        # is measured from the rejoin (iteration-time TTFT is exact)
        now = time.perf_counter()
        self._submit_ms = {r.req_id: now
                           for r, _ in self.scheduler.waiting}
        if self.tracer is not None:
            # requeued requests re-enter this replica's ledger fresh — their
            # pre-kill history died with the old process, and TTFT after a
            # warm restart is TTFT as experienced from the rejoin
            for r, _ in self.scheduler.waiting:
                self.tracer.on_submit(r)
        self._start_wall = None

    # ------------------------------------------------------------------ lint
    def lint_programs(self, sample_batch=None):
        """(name, jitted, example_args, manifest) for the lint registry —
        same contract as runtime engine.lint_programs. Fresh example pools so
        capture never lowers against donated-dead buffers."""
        c = self.model.config
        compute = {"bfloat16": "bf16", "float16": "f16"}.get(
            jnp.dtype(c.compute_dtype).name, "f32")
        manifest = {
            "compute_dtype": compute,
            "donation": {"check_unusable": True, "min_undonated_bytes": 1024},
            "strict": True,
            "any_reduction": {"max": 0},
        }
        copy_manifest = manifest
        if self.tp > 1:
            # head-sharded programs: exactly one f32 proj psum per layer and
            # nothing else on the wire — threshold 0 so even a tiny stray
            # resharding collective fails the budget, not just a large one
            manifest = {
                "compute_dtype": compute,
                "donation": {"check_unusable": True,
                             "min_undonated_bytes": 1024},
                "strict": True,
                "small_element_threshold": 0,
                "collectives": {"all-reduce": {"min": c.n_layer,
                                               "max": c.n_layer,
                                               "dtypes": ["f32"]}},
            }
            copy_manifest = {
                "compute_dtype": compute,
                "donation": {"check_unusable": True,
                             "min_undonated_bytes": 1024},
                "strict": True,
                "small_element_threshold": 0,
                "any_reduction": {"max": 0},
            }
        S, MB, C, P = (self.num_slots, self.max_blocks, self.prefill_chunk,
                       self.copy_width)
        pool_shape = (c.n_layer, self.num_blocks, self.block_size,
                      c.n_head, c.head_dim)
        kp = jnp.zeros(pool_shape, c.compute_dtype)
        vp = jnp.zeros(pool_shape, c.compute_dtype)
        zs = jnp.zeros(S, jnp.int32)
        entries = [
            ("serve_decode_step", self._raw["decode_step"],
             (self.params, zs, zs, jnp.zeros((S, MB), jnp.int32),
              jnp.zeros(S, bool), kp, vp), manifest),
            ("serve_prefill_chunk", self._raw["prefill_chunk"],
             (self.params, jnp.zeros((1, C), jnp.int32), jnp.int32(0),
              jnp.int32(1), jnp.zeros(MB, jnp.int32), kp, vp), manifest),
            ("serve_copy_blocks", self._raw["copy_blocks"],
             (kp, vp, jnp.zeros(P, jnp.int32), jnp.zeros(P, jnp.int32)),
             copy_manifest),
        ]
        if self._spec is not None:
            D = self.spec_k + 1
            entries.append(
                ("serve_spec_verify", self._raw["spec_verify"],
                 (self.params, jnp.zeros((S, D), jnp.int32), zs, zs,
                  jnp.zeros((S, MB), jnp.int32), jnp.zeros(S, bool),
                  kp, vp), manifest))
            entries.extend(self._spec.lint_programs(manifest))
        return entries

    def memory_manifest(self):
        """The memory analogue of ``lint_programs`` (utils/hbm, docs/hbm.md):
        the serving engine's persistent device residents — compute-dtype
        params (head-sharded under tp) and the paged KV pools, plus the draft
        model's own params/pool when speculation is live. Geometry carries the
        closed-form pool arithmetic (2 x L x blocks x block_size x H x Hd x
        itemsize, head-sharded over tp) the modeled view predicts from."""
        import jax
        from ..utils.hbm import leaf_signature
        c = self.model.config
        itemsize = int(jnp.dtype(c.compute_dtype).itemsize)
        leaves = jax.tree_util.tree_leaves(self.params)
        psi = sum(int(np.prod(l.shape)) if l.shape else 1 for l in leaves)
        per_device = sum(leaf_signature(l)[2] for l in leaves)
        classes = {"params": self.params,
                   "kv_pool": [self.k_pool, self.v_pool]}
        geometry = {
            "kind": "serving",
            "psi": psi,
            "param_itemsize": itemsize,
            "tp": int(self.tp),
            "param_per_device_fraction": per_device / max(psi * itemsize, 1),
            "pool": {"n_layer": int(c.n_layer),
                     "num_blocks": int(self.num_blocks),
                     "block_size": int(self.block_size),
                     "n_head": int(c.n_head), "head_dim": int(c.head_dim),
                     "itemsize": itemsize,
                     "shard_factor": int(self.tp) if self.tp > 1 else 1},
        }
        if self._spec is not None:
            dc = self._spec.model.config
            d_item = int(jnp.dtype(dc.compute_dtype).itemsize)
            d_leaves = jax.tree_util.tree_leaves(self._spec.params)
            classes["draft_params"] = self._spec.params
            classes["draft_pool"] = [self._spec.k_pool, self._spec.v_pool]
            geometry["draft"] = {
                "psi": sum(int(np.prod(l.shape)) if l.shape else 1
                           for l in d_leaves),
                "param_itemsize": d_item,
                "pool": {"n_layer": int(dc.n_layer),
                         "num_blocks": int(self._spec.k_pool.shape[1]),
                         "block_size": int(self._spec.block_size),
                         "n_head": int(dc.n_head),
                         "head_dim": int(dc.head_dim),
                         "itemsize": d_item},
            }
        return {"classes": classes, "geometry": geometry}
