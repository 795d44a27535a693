"""Fixed-shape paged KV-cache programs over models/gpt2.py.

Every program here has ONE abstract signature for the engine's lifetime — slot
count, chunk length, block table width and pool geometry are baked in at build
time, and per-iteration variation (which sequences are live, where they write)
rides in as array *values* (positions, tables, active masks). That is the whole
recompile story: ``ds-tpu serve-sim`` asserts zero decode-program recompiles
after warmup via the compile watchdog.

The pool is ``[n_layer, num_blocks, block_size, n_head, head_dim]`` per k/v in
the model's compute dtype; block 0 is the reserved null page (block_allocator).
The paged attention gathers each slot's pages by table and reshapes them into
the same ``[slots, n_head, max_blocks * block_size, head_dim]`` dense view the
model's cached forward contracts over, so with ``max_blocks * block_size ==
max_len`` the paged programs are **bitwise** the dense cached-forward math:
identical dot shapes, identical mask (``-1e9`` scores underflow to exact-zero
softmax weights, so garbage in never-written or masked page slots contributes
exact zeros), identical reduction orders. tests/unit/test_paged_attention.py
pins this against ``_build_cached_forward`` directly; serve/oracle.py carries
the per-slot-position dense mirror for mixed traces.

All cache/pool arguments are donated (the lesson of the dense decode path,
models/gpt2.py ``_cached_jit``): XLA aliases one pool buffer through every program, so serving
HBM is params + pool + activations — never 2x pool.

**Model-axis sharding** (``mesh=`` a Mesh carrying a ``model`` axis of size
``tp``): the KV pool is sharded by attention head — each chip holds
``[n_layer, num_blocks, block_size, n_head/tp, head_dim]`` — and decode /
prefill lower as one pjit program over that axis via ``shard_map``. Per
shard: slice the local head columns of ``c_attn_w`` (rows of ``c_proj_w``)
by ``axis_index``, run attention against the *local* pool shard (the block
table is replicated, pages are local — the same table steers every shard's
gather, including the Pallas kernel's BlockSpec index maps, which are
shape-generic over the head count), then one f32 ``psum`` per layer rebuilds
the proj contraction. Everything outside attention (LN, MLP, residual,
logits) is replicated compute on replicated activations, so all shards hold
bit-identical activations; the psum splits each proj dot's reduction into
``tp`` ordered partials, which moves float rounding by ulps — the sharded
engine is **token-identical** to the single-chip one (asserted by ``ds-tpu
serve-sim --sharding``), while the *bitwise* dense-mirror contract stays on
the unsharded path. Per-iteration variation still rides as array values and
the collective set is static (``n_layer`` all-reduces per program — the lint
registry's collective-budget manifest pins exactly that), so the
zero-recompile contract is unchanged.
"""

import math
import weakref

import jax
import jax.numpy as jnp

from .block_allocator import NULL_BLOCK

# One program set per (model instance, build geometry), shared by every engine
# built over it. The jitted programs close over only the model's pure config
# math and the baked geometry — params and pools arrive as call arguments — so
# two engines with the same model and geometry would lower byte-identical HLO;
# rebuilding per engine just recompiles it. Sharing makes engine construction
# (warm restarts, test fleets, the lint registry's capture engines) pay XLA
# once per process instead of once per engine. Weak-keyed so a model's
# programs die with it. Telemetry compile accounting is unaffected: the
# session's _WatchedJit AOT-compiles per (session, signature) on top of the
# raw jit, so watched engines still observe their own compiles.
_BUILD_CACHE = weakref.WeakKeyDictionary()


def _mesh_cache_key(mesh):
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


def build_paged_programs(model, *, num_slots, block_size, max_blocks,
                         prefill_chunk, copy_width=None, use_pallas=False,
                         mesh=None, verify_width=0):
    """Jitted program dict for one engine: ``decode_step``, ``prefill_chunk``,
    ``copy_blocks`` plus ``beam_init(K, eos)`` / ``beam_select(K, eos)``
    factories (per-(K, eos) program caches — K is a shape, eos a baked
    constant, so each variant is its own fixed-signature program). With
    ``mesh`` (carrying a ``model`` axis), the pool-touching programs lower
    as head-sharded pjit programs instead; the dict also carries the
    ``pool_sharding`` / ``replicated_sharding`` placements the engine puts
    its buffers with.

    ``verify_width = D > 0`` additionally builds ``spec_verify`` — the
    speculative-decoding verification program: a batched, D-token-wide
    generalization of ``decode_step`` (one chunked-prefill-shaped pass per
    slot, per-position logits out) that scores a drafted continuation for
    every slot in ONE target-model execution. Single-chip only: the engine
    refuses speculation + sharding, so the sharded build never asks for it."""
    cache_key = (int(num_slots), int(block_size), int(max_blocks),
                 int(prefill_chunk), int(copy_width or num_slots),
                 bool(use_pallas), _mesh_cache_key(mesh), int(verify_width))
    try:
        per_model = _BUILD_CACHE.setdefault(model, {})
    except TypeError:               # model not weak-referenceable: no sharing
        per_model = None
    if per_model is not None and cache_key in per_model:
        return per_model[cache_key]
    out = _build_paged_programs(
        model, num_slots=num_slots, block_size=block_size,
        max_blocks=max_blocks, prefill_chunk=prefill_chunk,
        copy_width=copy_width, use_pallas=use_pallas, mesh=mesh,
        verify_width=verify_width)
    if per_model is not None:
        per_model[cache_key] = out
    return out


def _build_paged_programs(model, *, num_slots, block_size, max_blocks,
                          prefill_chunk, copy_width=None, use_pallas=False,
                          mesh=None, verify_width=0):
    c = model.config
    nh, hd = c.n_head, c.head_dim
    S, BS, MB, C = int(num_slots), int(block_size), int(max_blocks), int(prefill_chunk)
    ML = MB * BS                      # the dense view length the gather rebuilds
    P = int(copy_width or num_slots)  # CoW copies per batched copy_blocks call
    cd = c.compute_dtype
    eps = c.layer_norm_epsilon
    V = c.vocab_size

    if use_pallas:
        from ..ops.pallas.paged_attention import paged_decode_attention
    else:
        paged_decode_attention = None

    def _qkv(x, bp):
        # verbatim models/gpt2.py attn_cached projection — bit-for-bit
        B_, Tn, _ = x.shape
        qkv = jnp.dot(x, bp["c_attn_w"].astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype) \
            + bp["c_attn_b"].astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B_, Tn, nh, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B_, Tn, nh, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B_, Tn, nh, hd).transpose(0, 2, 1, 3)
        return q, k, v

    def _proj(y, bp, x_dtype):
        return (jnp.dot(y, bp["c_proj_w"].astype(x_dtype),
                        preferred_element_type=jnp.float32).astype(x_dtype)
                + bp["c_proj_b"].astype(x_dtype))

    def _gather(pool, li, tables):
        """[S_, heads, ML, hd] dense view of one layer's pages by table — the
        exact layout ``kcs[li]`` has in the model's cached forward. Shape-
        generic over the pool's head dim, so a shard_map-local pool shard
        gathers its local heads with the same code."""
        g = pool[li][tables]                       # [S_, MB, BS, heads, hd]
        S_ = tables.shape[0]
        return g.reshape(S_, ML, pool.shape[3], hd).transpose(0, 2, 1, 3)

    def _attend(q, kg, vg, mask, x_dtype):
        # verbatim attn_cached score/softmax/value path
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kg,
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        s = jnp.where(mask, s, jnp.float32(-1e9))
        p = jax.nn.softmax(s, axis=-1).astype(x_dtype)
        y = jnp.einsum("bhqk,bhkd->bhqd", p, vg,
                       preferred_element_type=jnp.float32).astype(x_dtype)
        B_, heads, Tn, _ = y.shape
        return y.transpose(0, 2, 1, 3).reshape(B_, Tn, heads * hd)

    def _blocks_forward(p, x, attn_fn):
        for li, bp in enumerate(p["blocks"]):
            a = attn_fn(model._layer_norm(x, bp["ln_1"], eps), bp["attn"], li)
            x = x + a
            h = model._layer_norm(x, bp["ln_2"], eps)
            x = x + model._mlp(h, bp["mlp"])
        return model._layer_norm(x, p["ln_f"], eps)

    def _logits(row, p):
        # row [B_, H] — same einsum the cached forward applies to x[:, -1]
        return jnp.einsum("bh,vh->bv", row, p["wte"].astype(row.dtype),
                          preferred_element_type=jnp.float32)

    # ---------------------------------------------------------------- decode
    def decode_step(p, toks, pos, tables, active, k_pool, v_pool):
        """One token for every slot: toks/pos/tables/active are [S]-shaped
        ([S, MB] for tables); inactive lanes compute garbage and write to the
        null page. Returns (logits [S, V] f32, k_pool, v_pool)."""
        pools = {"k": k_pool, "v": v_pool}
        x = p["wte"][toks[:, None]].astype(cd) \
            + p["wpe"][pos[:, None]].astype(cd)             # [S, 1, H]
        wblk = jnp.where(active, tables[jnp.arange(S), pos // BS],
                         NULL_BLOCK)
        off = pos % BS

        def attn(xin, bp, li):
            q, k, v = _qkv(xin, bp)
            pools["k"] = pools["k"].at[li, wblk, off].set(
                k[:, :, 0, :].astype(pools["k"].dtype))
            pools["v"] = pools["v"].at[li, wblk, off].set(
                v[:, :, 0, :].astype(pools["v"].dtype))
            if paged_decode_attention is not None:
                y = paged_decode_attention(q, pools["k"], pools["v"], li,
                                           tables, pos + 1, block_size=BS)
                return _proj(y.transpose(0, 2, 1, 3).reshape(S, 1, nh * hd),
                             bp, xin.dtype)
            kg = _gather(pools["k"], li, tables)
            vg = _gather(pools["v"], li, tables)
            mask = (jnp.arange(ML)[None, :] <= pos[:, None])[:, None, None, :]
            return _proj(_attend(q, kg, vg, mask, xin.dtype), bp, xin.dtype)

        x = _blocks_forward(p, x, attn)
        return _logits(x[:, -1], p), pools["k"], pools["v"]

    # --------------------------------------------------------------- prefill
    def prefill_chunk_fn(p, toks, pos, n_valid, table, k_pool, v_pool):
        """One chunk of ONE sequence's prompt: toks [1, C] padded past
        ``n_valid``; writes positions [pos, pos + n_valid) through ``table``
        (pads go to the null page) and returns the logits of the last valid
        row — only meaningful on the chunk that completes the prompt."""
        pools = {"k": k_pool, "v": v_pool}
        wpe_cap = p["wpe"].shape[0] - 1
        tp = pos + jnp.arange(C)                              # [C] positions
        positions = jnp.minimum(tp, wpe_cap)  # pads only; valid rows untouched
        x = p["wte"][toks].astype(cd) \
            + p["wpe"][positions][None].astype(cd)            # [1, C, H]
        valid = jnp.arange(C) < n_valid
        wblk = jnp.where(valid, table[jnp.minimum(tp // BS, MB - 1)],
                         NULL_BLOCK)
        off = tp % BS
        tbl1 = table[None]                                    # [1, MB]

        def attn(xin, bp, li):
            q, k, v = _qkv(xin, bp)                           # [1, nh, C, hd]
            pools["k"] = pools["k"].at[li, wblk, off].set(
                k[0].transpose(1, 0, 2).astype(pools["k"].dtype))
            pools["v"] = pools["v"].at[li, wblk, off].set(
                v[0].transpose(1, 0, 2).astype(pools["v"].dtype))
            kg = _gather(pools["k"], li, tbl1)
            vg = _gather(pools["v"], li, tbl1)
            # same [Tn, ML] causal frontier the cached forward masks with
            mask = jnp.arange(ML)[None, :] <= tp[:, None]     # [C, ML]
            return _proj(_attend(q, kg, vg, mask, xin.dtype), bp, xin.dtype)

        x = _blocks_forward(p, x, attn)
        last = jax.lax.dynamic_slice(x, (0, n_valid - 1, 0),
                                     (1, 1, x.shape[-1]))[:, 0]
        return _logits(last, p), pools["k"], pools["v"]

    # ---------------------------------------------------- speculative verify
    D = int(verify_width)

    def spec_verify(p, toks, pos0, n_valid, tables, active, k_pool, v_pool):
        """Score a drafted continuation for every slot in one step: ``toks``
        is [S, D] — row 0 each slot's last committed token, rows 1.. the
        draft's proposals — at positions ``pos0 + [0, D)``; rows past
        ``n_valid[s]`` (and all rows of inactive slots) write to the null
        page and produce garbage logits the host ignores. Returns
        (logits [S, D, V] f32, k_pool, v_pool): row i's logits are the
        target's next-token distribution AFTER consuming toks[:, :i+1] —
        exactly what ``decode_step`` would have produced i steps later, so
        greedy acceptance against these rows is token-identical to plain
        decode. Rejected rows leave garbage KV past the accepted frontier;
        the causal mask (keys <= query position) means it is never attended,
        and the next round's writes cover the same extent — rollback is a
        host-side table truncation, no device work."""
        pools = {"k": k_pool, "v": v_pool}
        wpe_cap = p["wpe"].shape[0] - 1
        tp = pos0[:, None] + jnp.arange(D)[None, :]           # [S, D] positions
        positions = jnp.minimum(tp, wpe_cap)  # pads only; valid rows untouched
        x = p["wte"][toks].astype(cd) + p["wpe"][positions].astype(cd)
        valid = (jnp.arange(D)[None, :] < n_valid[:, None]) & active[:, None]
        wblk = jnp.where(
            valid,
            tables[jnp.arange(S)[:, None], jnp.minimum(tp // BS, MB - 1)],
            NULL_BLOCK)
        off = tp % BS

        def attn(xin, bp, li):
            q, k, v = _qkv(xin, bp)                           # [S, nh, D, hd]
            pools["k"] = pools["k"].at[li, wblk, off].set(
                k.transpose(0, 2, 1, 3).astype(pools["k"].dtype))
            pools["v"] = pools["v"].at[li, wblk, off].set(
                v.transpose(0, 2, 1, 3).astype(pools["v"].dtype))
            kg = _gather(pools["k"], li, tables)
            vg = _gather(pools["v"], li, tables)
            # per-row causal frontier: row i attends keys <= pos0 + i — the
            # same mask decode_step applies one position at a time
            mask = (jnp.arange(ML)[None, None, :]
                    <= tp[:, :, None])[:, None, :, :]
            return _proj(_attend(q, kg, vg, mask, xin.dtype), bp, xin.dtype)

        x = _blocks_forward(p, x, attn)
        logits = _logits(x.reshape(S * D, -1), p)
        return logits.reshape(S, D, V), pools["k"], pools["v"]

    # ------------------------------------------------------------ block copy
    def copy_blocks(k_pool, v_pool, src, dst):
        """Copy-on-write page copies, batched to a fixed width ``P`` (pads are
        0 -> 0 null self-copies). Gathers before scattering, so overlapping
        pairs are safe; the engine never generates them anyway."""
        k_pool = k_pool.at[:, dst].set(k_pool[:, src])
        v_pool = v_pool.at[:, dst].set(v_pool[:, src])
        return k_pool, v_pool

    # ----------------------------------------------------------- beam heads
    NEG = jnp.float32(-1e9)
    beam_cache = {}

    def beam_init(K, eos):
        """(prefill logits [1, V]) -> (scores, tok0, live) [K each] — the
        top-K first-token expansion from the chunk that completed the prompt.
        Verbatim beam_search init math."""
        key = ("init", K, eos)
        if key not in beam_cache:
            def f(logits):
                logp0 = jax.nn.log_softmax(logits, axis=-1)
                scores, tok0 = jax.lax.top_k(logp0, K)        # [1, K]
                live = (tok0 != eos) if eos >= 0 else jnp.ones((1, K), bool)
                return scores[0], tok0[0].astype(jnp.int32), live[0]
            beam_cache[key] = jax.jit(f)
        return beam_cache[key]

    def beam_select(K, eos):
        """(logits [S, V], slot_idx [K], scores [K], live [K]) ->
        (scores, parent, tok, live) [K each] — one beam step, verbatim
        beam_search step_scores + top-K reorder math at B=1."""
        key = ("select", K, eos)
        if key not in beam_cache:
            def f(logits, slot_idx, scores, live):
                logp = jax.nn.log_softmax(
                    logits[slot_idx].reshape(1, K, V), axis=-1)
                cand = scores[None, :, None] + logp
                if eos >= 0:
                    frozen = jnp.full((1, K, V), NEG).at[:, :, eos].set(
                        scores[None])
                    cand = jnp.where(live[None, :, None], cand, frozen)
                flat = cand.reshape(1, K * V)
                new_scores, idx = jax.lax.top_k(flat, K)      # [1, K]
                parent = idx // V
                tok = (idx % V).astype(jnp.int32)
                new_live = jnp.take_along_axis(live[None], parent, axis=1)
                if eos >= 0:
                    new_live = new_live & (tok != eos)
                return (new_scores[0], parent[0].astype(jnp.int32), tok[0],
                        new_live[0])
            beam_cache[key] = jax.jit(f)
        return beam_cache[key]

    if mesh is None:
        out = {
            "decode_step": jax.jit(decode_step, donate_argnums=(5, 6)),
            "prefill_chunk": jax.jit(prefill_chunk_fn, donate_argnums=(5, 6)),
            "copy_blocks": jax.jit(copy_blocks, donate_argnums=(0, 1)),
            "beam_init": beam_init,
            "beam_select": beam_select,
            "copy_width": P,
        }
        if D > 0:
            out["spec_verify"] = jax.jit(spec_verify, donate_argnums=(6, 7))
        return out

    if D > 0:
        raise ValueError("speculative verify is single-chip only (the engine "
                         "refuses speculation + sharding)")

    # ------------------------------------------------- model-axis sharding
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from ..parallel.mesh import MODEL_AXIS

    tp = mesh.shape[MODEL_AXIS]
    if nh % tp:
        raise ValueError(f"n_head {nh} not divisible by model-axis size {tp}")
    nh_l = nh // tp
    H = nh * hd
    POOL = PS(None, None, None, MODEL_AXIS, None)   # pool sharded by head
    REP = PS()                                      # everything else replicated
    pool_sharding = NamedSharding(mesh, POOL)
    rep_sharding = NamedSharding(mesh, REP)

    def _qkv_local(x, bp):
        """Local-head slice of the attn projection: column block
        ``[part*H + h0, +nh_l*hd)`` of ``c_attn_w`` for part in (q, k, v).
        Same dot/bias/reshape structure as ``_qkv``, nh_l heads wide."""
        B_, Tn, _ = x.shape
        h0 = jax.lax.axis_index(MODEL_AXIS) * (nh_l * hd)
        w = bp["c_attn_w"].astype(x.dtype)
        b = bp["c_attn_b"].astype(x.dtype)

        def part(i):
            wc = jax.lax.dynamic_slice_in_dim(w, i * H + h0, nh_l * hd, 1)
            bc = jax.lax.dynamic_slice_in_dim(b, i * H + h0, nh_l * hd, 0)
            out = jnp.dot(x, wc,
                          preferred_element_type=jnp.float32).astype(x.dtype) \
                + bc
            return out.reshape(B_, Tn, nh_l, hd).transpose(0, 2, 1, 3)

        return part(0), part(1), part(2)

    def _proj_local(y, bp, x_dtype):
        """Row block of ``c_proj_w`` for the local heads; the f32 ``psum``
        over the model axis rebuilds the full contraction (the ONE collective
        per layer the budget manifest admits), bias added once after."""
        h0 = jax.lax.axis_index(MODEL_AXIS) * (nh_l * hd)
        wr = jax.lax.dynamic_slice_in_dim(
            bp["c_proj_w"].astype(x_dtype), h0, nh_l * hd, 0)
        part = jnp.dot(y, wr, preferred_element_type=jnp.float32)
        return (jax.lax.psum(part, MODEL_AXIS).astype(x_dtype)
                + bp["c_proj_b"].astype(x_dtype))

    def sharded_decode_step(p, toks, pos, tables, active, k_pool, v_pool):
        def body(p, toks, pos, tables, active, k_pool, v_pool):
            pools = {"k": k_pool, "v": v_pool}
            x = p["wte"][toks[:, None]].astype(cd) \
                + p["wpe"][pos[:, None]].astype(cd)
            wblk = jnp.where(active, tables[jnp.arange(S), pos // BS],
                             NULL_BLOCK)
            off = pos % BS

            def attn(xin, bp, li):
                q, k, v = _qkv_local(xin, bp)        # [S, nh_l, 1, hd]
                pools["k"] = pools["k"].at[li, wblk, off].set(
                    k[:, :, 0, :].astype(pools["k"].dtype))
                pools["v"] = pools["v"].at[li, wblk, off].set(
                    v[:, :, 0, :].astype(pools["v"].dtype))
                if paged_decode_attention is not None:
                    y = paged_decode_attention(q, pools["k"], pools["v"], li,
                                               tables, pos + 1, block_size=BS)
                    y = y.transpose(0, 2, 1, 3).reshape(S, 1, nh_l * hd)
                else:
                    kg = _gather(pools["k"], li, tables)
                    vg = _gather(pools["v"], li, tables)
                    mask = (jnp.arange(ML)[None, :]
                            <= pos[:, None])[:, None, None, :]
                    y = _attend(q, kg, vg, mask, xin.dtype)
                return _proj_local(y, bp, xin.dtype)

            x = _blocks_forward(p, x, attn)
            return _logits(x[:, -1], p), pools["k"], pools["v"]

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(REP, REP, REP, REP, REP, POOL, POOL),
                             out_specs=(REP, POOL, POOL), check_vma=False)(
            p, toks, pos, tables, active, k_pool, v_pool)

    def sharded_prefill_chunk(p, toks, pos, n_valid, table, k_pool, v_pool):
        def body(p, toks, pos, n_valid, table, k_pool, v_pool):
            pools = {"k": k_pool, "v": v_pool}
            wpe_cap = p["wpe"].shape[0] - 1
            tp_ = pos + jnp.arange(C)
            positions = jnp.minimum(tp_, wpe_cap)
            x = p["wte"][toks].astype(cd) \
                + p["wpe"][positions][None].astype(cd)
            valid = jnp.arange(C) < n_valid
            wblk = jnp.where(valid, table[jnp.minimum(tp_ // BS, MB - 1)],
                             NULL_BLOCK)
            off = tp_ % BS
            tbl1 = table[None]

            def attn(xin, bp, li):
                q, k, v = _qkv_local(xin, bp)        # [1, nh_l, C, hd]
                pools["k"] = pools["k"].at[li, wblk, off].set(
                    k[0].transpose(1, 0, 2).astype(pools["k"].dtype))
                pools["v"] = pools["v"].at[li, wblk, off].set(
                    v[0].transpose(1, 0, 2).astype(pools["v"].dtype))
                kg = _gather(pools["k"], li, tbl1)
                vg = _gather(pools["v"], li, tbl1)
                mask = jnp.arange(ML)[None, :] <= tp_[:, None]
                return _proj_local(_attend(q, kg, vg, mask, xin.dtype),
                                   bp, xin.dtype)

            x = _blocks_forward(p, x, attn)
            last = jax.lax.dynamic_slice(x, (0, n_valid - 1, 0),
                                         (1, 1, x.shape[-1]))[:, 0]
            return _logits(last, p), pools["k"], pools["v"]

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(REP, REP, REP, REP, REP, POOL, POOL),
                             out_specs=(REP, POOL, POOL), check_vma=False)(
            p, toks, pos, n_valid, table, k_pool, v_pool)

    # copy_blocks scatters along the (unsharded) block axis only — GSPMD
    # partitions it per shard with zero collectives; no shard_map needed
    return {
        "decode_step": jax.jit(
            sharded_decode_step, donate_argnums=(5, 6),
            in_shardings=(rep_sharding,) * 5 + (pool_sharding,) * 2,
            out_shardings=(rep_sharding, pool_sharding, pool_sharding)),
        "prefill_chunk": jax.jit(
            sharded_prefill_chunk, donate_argnums=(5, 6),
            in_shardings=(rep_sharding,) * 5 + (pool_sharding,) * 2,
            out_shardings=(rep_sharding, pool_sharding, pool_sharding)),
        "copy_blocks": jax.jit(
            copy_blocks, donate_argnums=(0, 1),
            in_shardings=(pool_sharding, pool_sharding,
                          rep_sharding, rep_sharding),
            out_shardings=(pool_sharding, pool_sharding)),
        "beam_init": beam_init,
        "beam_select": beam_select,
        "copy_width": P,
        "pool_sharding": pool_sharding,
        "replicated_sharding": rep_sharding,
        "model_parallel": tp,
    }
