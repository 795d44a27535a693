"""GPT-2 family model, TPU-first.

Flagship decoder LM for the framework benchmarks (BASELINE.json: GPT-2 1.5B ZeRO-2). The
reference trains GPT-2 through external Megatron-LM (tests/model/Megatron_GPT2); here the
model is in-tree, a pure-function pytree model:

- bf16-friendly: all matmuls carry ``preferred_element_type=float32`` accumulation;
- static shapes, layer loop unrolled (or remat-scanned) for XLA;
- attention dispatches to the Pallas flash-attention kernel on TPU when enabled, with a
  dense fallback (ops/pallas/flash_attention.py);
- weights laid out [in, out] so the ``model``-axis TP sharding (attention heads / MLP
  columns) is a pure PartitionSpec choice.
"""

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .layers import chunked_cross_entropy


@dataclass
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0          # dropout is applied via stateless PRNG when > 0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    use_flash_attention: bool = False
    remat: bool = False            # activation checkpointing over blocks
    remat_policy: Any = None       # None=full recompute; "dots"=save matmul outputs
    # 0 = materialize the full [B, T, vocab] logits; any other value = do not (the fused
    # head + cross-entropy of layers.chunked_cross_entropy, which picks its own tile from
    # the shapes: the number sets nothing)
    loss_chunk: int = 128
    compute_dtype: Any = jnp.bfloat16
    # Mixture-of-Experts (parallel/moe.py): 0 = dense FFN everywhere. When > 0,
    # every ``moe_every``-th block replaces its MLP with a switch-style MoE FFN;
    # the training loss gains ``moe_aux_weight`` x the Switch load-balancing term.
    # Expert parallelism comes from param_shardings(mesh): expert weights shard
    # their leading E axis over the ``model`` mesh axis and GSPMD partitions the
    # batched expert einsums across it.
    moe_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Block-sparse attention (ops/sparse_attention + the Pallas kernel): a
    # SparsityConfig instance (BigBird/Fixed/Variable/BSLongformer...) replaces
    # dense/flash attention in every block — causal training over the layout's
    # block pattern (the kernel's causal mask composes with the layout, so
    # bidirectional layouts are safely clipped to the lower triangle). The
    # layout is built once per sequence length and cached on the model.
    # Constraints: no attention dropout (the sparse kernel has no in-kernel
    # PRNG), not composable with ring sequence parallelism; decode
    # (generate/beam_search) stays dense-incremental.
    sparse_attention: Any = None

    # named sizes for convenience
    @property
    def head_dim(self):
        return self.n_embd // self.n_head


def _dense_init(rng, shape, scale):
    return jax.random.normal(rng, shape, jnp.float32) * scale


def qkv_tp_permutation(n_embd: int, tp: int) -> "np.ndarray":
    """Column permutation turning the ``[q | k | v]`` fused-qkv layout into rank-grouped
    ``[q_0 k_0 v_0 | q_1 k_1 v_1 | ...]`` so a contiguous model-axis shard of width
    3*n_embd/tp is a valid local (q, k, v) triple for manual (shard_map) TP. GSPMD TP
    needs no permutation — it keeps global semantics through the qkv split."""
    import numpy as np
    per = n_embd // tp
    cols = []
    for r in range(tp):
        for third in range(3):
            start = third * n_embd + r * per
            cols.append(np.arange(start, start + per))
    return np.concatenate(cols)


class GPT2Model:
    """Pure-function GPT-2: ``init(rng) -> params``, ``apply(params, tokens[, labels])``.

    Tensor parallelism comes in two flavors (SURVEY §2.3: TP is first-class here where
    the reference delegated to Megatron's mpu):
    - GSPMD: pass ``param_shardings(mesh)`` to the engine; XLA inserts the collectives
      from the Megatron-style weight layouts (requires ``use_flash_attention=False`` —
      a Pallas call cannot be auto-partitioned over the model axis).
    - Manual (inside ``shard_map``, e.g. the SPMD pipeline): ``with_tp(axis, size)``
      returns a model whose attention/MLP consume model-axis weight shards and psum the
      row-parallel projections, the Megatron forward exactly.
    """

    def __init__(self, config: GPT2Config):
        self.config = config
        self.tp_axis = None   # set via with_tp() for manual-collective (shard_map) TP
        self.tp_size = 1
        self.seq_axis = None  # set via with_sequence_parallel() for ring attention
        self.seq_schedule = "zigzag"  # causal ring schedule ("zigzag" | "masked")
        self._sparse_layouts = {}  # seq_len -> block layout (host numpy), built once
        if config.sparse_attention is not None:
            assert config.dropout == 0.0, \
                "sparse_attention has no in-kernel dropout; set dropout=0"
        self._moe = None
        if config.moe_experts > 0:
            assert config.moe_every >= 1, \
                f"moe_every must be >= 1 (got {config.moe_every})"
            from ..parallel.moe import MoELayer
            # single-program dense dispatch, routed PER SEQUENCE ROW (the GShard
            # group convention — ungrouped dispatch is O((B*T)^2) memory); expert
            # PARALLELISM comes from param_shardings' leading-E layouts (GSPMD
            # partitions the batched expert einsums over the model axis)
            self._moe = MoELayer(config.n_embd, 4 * config.n_embd,
                                 config.moe_experts,
                                 capacity_factor=config.moe_capacity_factor,
                                 group_size=config.n_positions)

    def with_tp(self, axis: str, size: int) -> "GPT2Model":
        """A copy configured for manual tensor parallelism over mesh axis ``axis``."""
        assert self.config.n_head % size == 0, \
            f"n_head={self.config.n_head} must divide by tp size {size}"
        assert (4 * self.config.n_embd) % size == 0
        assert self.config.moe_experts == 0, \
            "MoE blocks do not compose with manual TP (use GSPMD expert sharding)"
        assert self.config.sparse_attention is None, \
            "sparse_attention does not compose with manual TP (per-rank head "\
            "layouts are not split)"
        m = GPT2Model(self.config)
        m.tp_axis = axis
        m.tp_size = size
        return m

    def with_sequence_parallel(self, axis: str, schedule: str = "zigzag") -> "GPT2Model":
        """A copy configured for ring-attention sequence parallelism over mesh axis
        ``axis``: call inside shard_map with tokens/activations sharded over the
        SEQUENCE dim (see ``sequence_parallel_loss_fn`` for the packaged wrapper).
        ``schedule`` picks the causal ring: ``"zigzag"`` (default — balanced
        early+late chunk layout, no masked-compute tax; tokens must arrive in the
        ``zigzag_shard`` order and positions follow the interleave) or
        ``"masked"`` (contiguous chunks, the original oracle). Position
        embeddings map local positions to global; attention runs the ppermute
        ring (parallel/ring_attention.py). Long-context path past the
        single-chip flash kernel's whole-K/V VMEM cap."""
        from ..parallel.ring_attention import SCHEDULES
        assert schedule in SCHEDULES, \
            f"schedule must be one of {SCHEDULES}, got {schedule!r}"
        assert self.tp_axis is None, \
            "sequence parallelism does not compose with manual TP yet"
        assert self.config.sparse_attention is None, \
            "sparse_attention does not compose with ring sequence parallelism " \
            "(the ring path would silently ignore the layout)"
        # MoE composes: the dense dispatch routes each rank's LOCAL sequence chunk
        # (per-chunk capacity; experts replicated inside the shard_map) and the aux
        # term is pmean'd unweighted alongside the count-weighted CE
        m = GPT2Model(self.config)
        m.seq_axis = axis
        m.seq_schedule = schedule
        return m

    def sequence_parallel_loss_fn(self, mesh, axis: str, schedule: str = "zigzag"):
        """``model_fn(params, tokens, labels, rng=None) -> loss`` for the engine:
        shard_map over ``axis`` with the sequence dim of tokens/labels sharded and
        ring attention inside. ``labels`` must be globally next-token-shifted
        BEFORE sharding (the shift crosses chunk boundaries). Pass ``rng`` to
        enable dropout (config.dropout > 0): attention dropout runs in-ring with
        global-coordinate masks; hidden dropout decorrelates per rank.

        Under the default ``schedule="zigzag"`` the wrapper reorders tokens AND
        labels into the zigzag layout (one static gather each) before sharding,
        so callers keep passing natural-order sequences; the scalar loss needs no
        inverse. The per-token CE is weighted by global valid counts, which is
        permutation-invariant, so the loss equals the masked schedule's exactly
        (up to flash-merge rounding)."""
        from jax.sharding import PartitionSpec as P
        sp = self.with_sequence_parallel(axis, schedule=schedule)
        n_ranks = mesh.shape[axis]
        tok_spec = P(None, axis)

        def model_fn(params, tokens, labels, rng=None):
            if schedule == "zigzag":
                from ..parallel.ring_attention import zigzag_shard
                tokens = zigzag_shard(tokens, n_ranks, axis=1)
                labels = zigzag_shard(labels, n_ranks, axis=1)
            def local(params, tokens, labels, *r):
                # sum-of-losses / sum-of-counts across ranks: with ignore labels
                # (-100) the per-rank VALID counts differ, so a pmean of per-rank
                # means would over-weight ranks holding masked positions (and a
                # fully-masked chunk would scale the loss by (sp-1)/sp). The MoE
                # aux term is a per-chunk load-balancing mean, NOT a per-token
                # loss — it stays a plain pmean so label masking can't reweight
                # (or, for a fully-masked rank, drop) its contribution.
                ce_mean, aux = sp.apply_parts(params, tokens, labels,
                                              rng=(r[0] if r else None))
                n_valid = jnp.sum((labels >= 0).astype(jnp.float32))
                total = jax.lax.psum(ce_mean * n_valid, axis)
                count = jax.lax.psum(n_valid, axis)
                return total / jnp.maximum(count, 1.0) + jax.lax.pmean(aux, axis)

            args = (params, tokens, labels) + (() if rng is None else (rng,))
            in_specs = (P(), tok_spec, tok_spec) + (() if rng is None else (P(),))
            return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(), check_vma=False)(*args)

        return model_fn

    def param_shardings(self, mesh):
        """Megatron-style TP layouts over the mesh's ``model`` axis for the GSPMD path:
        column-parallel c_attn/c_fc (output dim sharded), row-parallel c_proj (input dim
        sharded), vocab-sharded embedding; norms/biases-of-row-parallel replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.mesh import MODEL_AXIS

        def ns(*spec):
            return NamedSharding(mesh, P(*spec))

        repl = ns()
        ln = {"scale": repl, "bias": repl}
        block = {
            "ln_1": ln,
            "attn": {"c_attn_w": ns(None, MODEL_AXIS), "c_attn_b": ns(MODEL_AXIS),
                     "c_proj_w": ns(MODEL_AXIS, None), "c_proj_b": repl},
            "ln_2": ln,
            "mlp": {"c_fc_w": ns(None, MODEL_AXIS), "c_fc_b": ns(MODEL_AXIS),
                    "c_proj_w": ns(MODEL_AXIS, None), "c_proj_b": repl},
        }
        if self._moe is not None:
            moe_block = {k: v for k, v in block.items() if k != "mlp"}
            moe_block["moe"] = self._moe.param_shardings(mesh, MODEL_AXIS)
            blocks = [moe_block if self._is_moe_block(i) else block
                      for i in range(self.config.n_layer)]
        else:
            blocks = [block for _ in range(self.config.n_layer)]
        return {"wte": ns(MODEL_AXIS, None), "wpe": repl, "ln_f": dict(ln),
                "blocks": blocks}

    def _is_moe_block(self, i: int) -> bool:
        return (self._moe is not None
                and i % self.config.moe_every == self.config.moe_every - 1)

    # ------------------------------------------------------------- init
    def init(self, rng) -> Dict:
        c = self.config
        keys = jax.random.split(rng, 4 + c.n_layer)
        params = {
            "wte": _dense_init(keys[0], (c.vocab_size, c.n_embd), c.initializer_range),
            "wpe": _dense_init(keys[1], (c.n_positions, c.n_embd), c.initializer_range),
            "ln_f": {"scale": jnp.ones((c.n_embd,), jnp.float32),
                     "bias": jnp.zeros((c.n_embd,), jnp.float32)},
            "blocks": [],
        }
        # residual-scaled init for output projections (GPT-2 paper)
        proj_scale = c.initializer_range / math.sqrt(2 * c.n_layer)
        for i in range(c.n_layer):
            k = jax.random.split(keys[4 + i], 4)
            block = {
                "ln_1": {"scale": jnp.ones((c.n_embd,), jnp.float32),
                         "bias": jnp.zeros((c.n_embd,), jnp.float32)},
                "attn": {
                    "c_attn_w": _dense_init(k[0], (c.n_embd, 3 * c.n_embd), c.initializer_range),
                    "c_attn_b": jnp.zeros((3 * c.n_embd,), jnp.float32),
                    "c_proj_w": _dense_init(k[1], (c.n_embd, c.n_embd), proj_scale),
                    "c_proj_b": jnp.zeros((c.n_embd,), jnp.float32),
                },
                "ln_2": {"scale": jnp.ones((c.n_embd,), jnp.float32),
                         "bias": jnp.zeros((c.n_embd,), jnp.float32)},
            }
            if self._is_moe_block(i):
                block["moe"] = self._moe.init(k[2])
            else:
                block["mlp"] = {
                    "c_fc_w": _dense_init(k[2], (c.n_embd, 4 * c.n_embd), c.initializer_range),
                    "c_fc_b": jnp.zeros((4 * c.n_embd,), jnp.float32),
                    "c_proj_w": _dense_init(k[3], (4 * c.n_embd, c.n_embd), proj_scale),
                    "c_proj_b": jnp.zeros((c.n_embd,), jnp.float32),
                }
            params["blocks"].append(block)
        return params

    # ------------------------------------------------------------- layers
    def _layer_norm(self, x, p, eps):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mean) * jax.lax.rsqrt(var + eps)
        return (out * p["scale"] + p["bias"]).astype(x.dtype)

    def _dropout(self, x, rng):
        """Stateless inverted dropout (rate = config.dropout). The PRNG key is threaded
        explicitly, so recompute-under-remat reproduces identical masks — the TPU analog
        of the reference's CUDA RNG state tracker (checkpointing.py:147-262)."""
        keep = 1.0 - self.config.dropout
        if self.seq_axis is not None:
            # sequence-parallel: each rank sees only its LOCAL chunk shape, so an
            # unfolded (replicated) key would repeat the same mask on every chunk —
            # fold the rank in to decorrelate
            rng = jax.random.fold_in(rng, jax.lax.axis_index(self.seq_axis))
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / jnp.asarray(keep, x.dtype), jnp.zeros((), x.dtype))

    def _attention(self, x, p, dropout_rng=None):
        from jax.ad_checkpoint import checkpoint_name
        c = self.config
        B, T, E = x.shape
        nh = c.n_head // self.tp_size  # local heads under manual TP (all heads otherwise)
        # announce the fused-qkv dot to the flash remat policies: tagging the dot
        # input turns the policy's width-signature guess into an exact match
        x = checkpoint_name(x, "ds_dot:qkv")
        qkv = jnp.dot(x, p["c_attn_w"].astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype) + p["c_attn_b"].astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, nh, c.head_dim).transpose(0, 2, 1, 3)
        k = k.reshape(B, T, nh, c.head_dim).transpose(0, 2, 1, 3)
        v = v.reshape(B, T, nh, c.head_dim).transpose(0, 2, 1, 3)

        # in-kernel attention dropout: the seed is a traced operand so remat replays
        # identical masks. Under sequence parallelism every rank derives the SAME
        # seed from the replicated rng — the ring hashes GLOBAL coordinates, so the
        # sampled mask is exactly the single-chip kernel's for that seed.
        rate, seed = 0.0, None
        if dropout_rng is not None and c.dropout > 0:
            seed = jax.random.randint(dropout_rng, (), 0,
                                      jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
            rate = float(c.dropout)
        if self.seq_axis is not None:
            # sequence-parallel ring: T here is the LOCAL chunk; global causality
            # is handled by the schedule's layout + in-kernel global-coordinate
            # masks (zigzag) or chunk ordering + the diagonal mask (masked)
            from ..parallel.ring_attention import ring_attention
            y = ring_attention(q, k, v, axis_name=self.seq_axis, causal=True,
                               dropout_rate=rate, dropout_seed=seed,
                               schedule=self.seq_schedule)
        elif c.sparse_attention is not None:
            from ..ops.pallas.block_sparse_attention import block_sparse_attention
            sc = c.sparse_attention
            if T not in self._sparse_layouts:
                layout = sc.make_layout(T)
                assert layout.shape[0] == nh, \
                    (f"sparse_attention config built for {layout.shape[0]} heads; "
                     f"model runs {nh} — construct it with num_heads={c.n_head}")
                self._sparse_layouts[T] = layout
            y = block_sparse_attention(q, k, v, self._sparse_layouts[T], sc.block,
                                       causal=True)
        elif c.use_flash_attention:
            from ..ops.pallas.flash_attention import flash_attention
            if seed is not None and self.tp_axis is not None:
                # the kernel hashes the LOCAL head index; decorrelate the
                # model-parallel ranks (which see the same program_ids) by
                # folding the tp rank into the seed (int32 wraparound is fine)
                seed = seed + (jax.lax.axis_index(self.tp_axis) + 1) \
                    * jnp.int32(-1640531527)  # 2654435761 as int32
            y = flash_attention(q, k, v, True, dropout_rate=rate, dropout_seed=seed)
        else:
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                                preferred_element_type=jnp.float32) / math.sqrt(c.head_dim)
            mask = jnp.tril(jnp.ones((T, T), jnp.bool_))
            scores = jnp.where(mask, scores, jnp.float32(-1e9))
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            if dropout_rng is not None and c.dropout > 0:
                # attention-probability dropout; under manual TP fold the rank in —
                # a replicated key would give different GLOBAL heads (same local
                # slot on different ranks) byte-identical masks
                if self.tp_axis is not None:
                    dropout_rng = jax.random.fold_in(
                        dropout_rng, jax.lax.axis_index(self.tp_axis))
                probs = self._dropout(probs, dropout_rng)
            y = jnp.einsum("bhqk,bhkd->bhqd", probs, v,
                           preferred_element_type=jnp.float32).astype(x.dtype)
        # tag for the "attn" remat policy: saving this tensor lets backward skip
        # replaying the attention kernel (the priciest recompute under full remat)
        y = checkpoint_name(y, "attn_out")
        y = y.transpose(0, 2, 1, 3).reshape(B, T, nh * c.head_dim)
        # announce the square output projection (the 'dots+attn-lean' exclusion)
        y = checkpoint_name(y, "ds_dot:proj")
        y = jnp.dot(y, p["c_proj_w"].astype(x.dtype), preferred_element_type=jnp.float32)
        if self.tp_axis is not None:
            # row-parallel projection: partial sums over the model axis (Megatron fwd)
            y = jax.lax.psum(y, self.tp_axis)
        return y.astype(x.dtype) + p["c_proj_b"].astype(x.dtype)

    def _mlp(self, x, p):
        h = jnp.dot(x, p["c_fc_w"].astype(x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype) + p["c_fc_b"].astype(x.dtype)
        h = jax.nn.gelu(h, approximate=True)
        out = jnp.dot(h, p["c_proj_w"].astype(x.dtype), preferred_element_type=jnp.float32)
        if self.tp_axis is not None:
            out = jax.lax.psum(out, self.tp_axis)
        return out.astype(x.dtype) + p["c_proj_b"].astype(x.dtype)

    def _block(self, x, bp, rng=None):
        c = self.config
        k_attn = k_res1 = k_res2 = None
        if rng is not None and c.dropout > 0:
            k_attn, k_res1, k_res2 = jax.random.split(rng, 3)
        # ds_attn, ds_mlp (and ds_embed, ds_loss below) are metadata on the compiled
        # program, no instruction: the device table reads a step's parts from them.
        # A layer norm falls under the part it feeds.
        with jax.named_scope("ds_attn"):
            a = self._attention(
                self._layer_norm(x, bp["ln_1"], c.layer_norm_epsilon),
                bp["attn"], dropout_rng=k_attn)
            if k_res1 is not None:
                a = self._dropout(a, k_res1)
            x = x + a
        with jax.named_scope("ds_mlp"):
            h = self._layer_norm(x, bp["ln_2"], c.layer_norm_epsilon)
            if "moe" in bp:
                m, aux = self._moe.apply(bp["moe"], h)
            else:
                m, aux = self._mlp(h, bp["mlp"]), jnp.zeros((), jnp.float32)
            if k_res2 is not None:
                m = self._dropout(m, k_res2)
            return x + m, aux

    # ------------------------------------------------------------- apply
    def _backbone(self, params, tokens, rng=None):
        """Embeddings → transformer blocks → final layernorm: (B, T, H) hidden states.
        ``rng`` enables stateless dropout (config.dropout) — omit it for eval."""
        c = self.config
        B, T = tokens.shape
        pos = jnp.arange(T)
        if self.seq_axis is not None:
            rank = jax.lax.axis_index(self.seq_axis)
            if self.seq_schedule == "zigzag":
                # zigzag layout: this rank holds global chunks (rank, 2n-1-rank)
                # of size T/2 — positions follow the interleave
                n = jax.lax.axis_size(self.seq_axis)
                assert T % 2 == 0, f"zigzag needs an even local seq, got {T}"
                C = T // 2
                pos = jnp.concatenate([rank * C + jnp.arange(C),
                                       (2 * n - 1 - rank) * C + jnp.arange(C)])
            else:
                # contiguous: this rank holds global positions [r*T, (r+1)*T)
                pos = pos + rank * T
        use_dropout = rng is not None and c.dropout > 0
        with jax.named_scope("ds_embed"):
            x = (params["wte"][tokens].astype(c.compute_dtype)
                 + params["wpe"][pos].astype(c.compute_dtype))
            if use_dropout:
                rng, k_embd = jax.random.split(rng)
                x = self._dropout(x, k_embd)

        block_fn = self._block
        if c.remat:
            # config-aware remat: honors partition_activations / cpu_checkpointing
            from ..runtime.activation_checkpointing.checkpointing import checkpoint_wrapper
            block_fn = checkpoint_wrapper(block_fn, policy=c.remat_policy)
        aux_total = jnp.zeros((), jnp.float32)
        for bp in params["blocks"]:
            if use_dropout:
                rng, kb = jax.random.split(rng)
                x, aux = block_fn(x, bp, kb)
            else:
                x, aux = block_fn(x, bp)
            aux_total = aux_total + aux
        with jax.named_scope("ds_loss"):      # the last layer norm feeds the head
            return self._layer_norm(x, params["ln_f"], c.layer_norm_epsilon), aux_total

    def logits(self, params, tokens, rng=None):
        x, _ = self._backbone(params, tokens, rng=rng)
        # tied LM head: logits = x @ wte.T, contracted without materializing the
        # transposed table (153 MB HBM at 1.5B — see layers.chunked_cross_entropy)
        with jax.named_scope("ds_loss"):
            return jnp.einsum("bth,vh->btv", x, params["wte"].astype(x.dtype),
                              preferred_element_type=jnp.float32)

    def apply_parts(self, params, tokens, labels, rng=None):
        """``(ce_mean, weighted_aux)`` — the two training-loss components kept
        separate. ``apply`` returns their sum; the sequence-parallel wrapper
        needs them apart (CE is psum-weighted across ranks by valid-label
        count, while the MoE load-balancing aux — already a per-chunk mean —
        is pmean'd unweighted so masked labels don't reweight it)."""
        c = self.config
        x, aux = self._backbone(params, tokens, rng=rng)
        aux = (c.moe_aux_weight * aux if self._moe is not None
               else jnp.zeros((), jnp.float32))
        with jax.named_scope("ds_loss"):
            if c.loss_chunk:
                return chunked_cross_entropy(x, params["wte"], labels), aux
            logits = jnp.einsum("bth,vh->btv", x, params["wte"].astype(x.dtype),
                                preferred_element_type=jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            valid = (labels >= 0).astype(jnp.float32)  # < 0 = ignored (BERT's -100)
            ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                                     axis=-1)[..., 0]
            return -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1.0), aux

    def apply(self, params, tokens, labels=None, rng=None):
        """With labels: mean token cross-entropy loss (the training objective);
        negative labels (the -100 convention) are ignored — mask padding or the
        roll-wrapped last position with them. Without labels: fp32 logits.
        ``rng`` enables stateless dropout when config.dropout > 0."""
        if labels is None:
            return self.logits(params, tokens, rng=rng)
        ce, aux = self.apply_parts(params, tokens, labels, rng=rng)
        return ce + aux

    # ------------------------------------------------------------- generation
    def _cached_jit(self, key, fn, donate_argnums=()):
        """Per-model decode-program cache: generate and beam_search share it (the
        shape-keyed ``("prefill", ...)`` entries are deliberately common so any
        decode variant reuses the expensive prompt program).

        ``donate_argnums`` is forwarded to ``jax.jit``: the decode-path programs
        donate their KV-cache arguments so XLA aliases one buffer through
        input -> scan carry -> output instead of double-buffering the caches.
        Without the donation the caller's cache stays live across the call —
        at 1.5B batch-8 decode that is an extra 2x [L, B, nh, max_len, hd]
        (~5.7 GB) held through the prompt-forward activation peak, which put
        1.5B batch-8 decode over the 16 GB HBM cliff at execution time.

        The serving stack applies the same discipline to its paged pools:
        serve/paged.py donates the target KV pool through decode/prefill/
        verify, and the speculative DRAFT model's pool rides the identical
        builds at the draft's shapes (serve/speculative.py) — a second
        un-donated pool copy per drafting turn would price the draft model
        right back out of its speedup. The lint registry's
        ``serving_speculative`` entry pins all of it (check_unusable +
        min_undonated_bytes on every spec program)."""
        cache = getattr(self, "_gen_jit_cache", None)
        if cache is None:
            cache = self._gen_jit_cache = {}
        if key not in cache:
            cache[key] = jax.jit(fn, donate_argnums=donate_argnums)
        return cache[key]

    def _build_cached_forward(self, max_len: int):
        """Incremental forward over per-layer KV caches, shared by ``generate``
        and ``beam_search``: ``forward(p, toks [B, Tn], pos, kcs, vcs) ->
        (last-position logits [B, vocab] fp32, new_kcs, new_vcs)`` where
        kcs/vcs are ``[n_layer, B, nh, max_len, hd]`` and ``pos`` counts the
        tokens already cached."""
        c = self.config
        nh, hd = c.n_head, c.head_dim
        if c.sparse_attention is not None and not getattr(
                self, "_warned_sparse_decode", False):
            self._warned_sparse_decode = True
            from ..utils.logging import logger
            logger.warning(
                "[deepspeed_tpu] decode runs DENSE causal attention over the KV "
                "cache — the sparse_attention layout applies to training "
                "forwards only, so generated text reflects full attention")

        def attn_cached(x, bp, kcs, vcs, li, pos):
            B_, Tn, _ = x.shape
            qkv = jnp.dot(x, bp["c_attn_w"].astype(x.dtype),
                          preferred_element_type=jnp.float32).astype(x.dtype) \
                + bp["c_attn_b"].astype(x.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B_, Tn, nh, hd).transpose(0, 2, 1, 3)
            k = k.reshape(B_, Tn, nh, hd).transpose(0, 2, 1, 3)
            v = v.reshape(B_, Tn, nh, hd).transpose(0, 2, 1, 3)
            # write THROUGH the stacked [L, B, nh, max_len, hd] carry arrays:
            # per-layer slice-out + end-of-step jnp.stack kept L transient copies
            # of the whole cache live (measured: 1.5B batch-8 decode demanded
            # 37.1 G HBM and OOM'd); in-place dynamic_update_slice on the carry
            # lets XLA alias one buffer through the layer loop
            kcs = jax.lax.dynamic_update_slice(
                kcs, k.astype(kcs.dtype)[None], (li, 0, 0, pos, 0))
            vcs = jax.lax.dynamic_update_slice(
                vcs, v.astype(vcs.dtype)[None], (li, 0, 0, pos, 0))
            s = jnp.einsum("bhqd,bhkd->bhqk", q, kcs[li],
                           preferred_element_type=jnp.float32) / math.sqrt(hd)
            j = jnp.arange(max_len)[None, :]
            i = pos + jnp.arange(Tn)[:, None]
            s = jnp.where(j <= i, s, jnp.float32(-1e9))  # causal + not-yet-written mask
            p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            y = jnp.einsum("bhqk,bhkd->bhqd", p, vcs[li],
                           preferred_element_type=jnp.float32).astype(x.dtype)
            y = y.transpose(0, 2, 1, 3).reshape(B_, Tn, nh * hd)
            return (jnp.dot(y, bp["c_proj_w"].astype(x.dtype),
                            preferred_element_type=jnp.float32).astype(x.dtype)
                    + bp["c_proj_b"].astype(x.dtype)), kcs, vcs

        def forward(p, toks, pos, kcs, vcs):
            Tn = toks.shape[1]
            positions = pos + jnp.arange(Tn)
            x = p["wte"][toks].astype(c.compute_dtype) \
                + p["wpe"][positions].astype(c.compute_dtype)
            for li, bp in enumerate(p["blocks"]):
                a, kcs, vcs = attn_cached(
                    self._layer_norm(x, bp["ln_1"], c.layer_norm_epsilon),
                    bp["attn"], kcs, vcs, li, pos)
                x = x + a
                h = self._layer_norm(x, bp["ln_2"], c.layer_norm_epsilon)
                m = (self._moe.apply(bp["moe"], h)[0] if "moe" in bp
                     else self._mlp(h, bp["mlp"]))
                x = x + m
            x = self._layer_norm(x, p["ln_f"], c.layer_norm_epsilon)
            logits = jnp.einsum("bh,vh->bv", x[:, -1], p["wte"].astype(x.dtype),
                                preferred_element_type=jnp.float32)
            return logits, kcs, vcs

        return forward

    def beam_search(self, params, tokens, max_new_tokens: int, num_beams: int = 4,
                    *, eos_token_id=None, length_penalty: float = 1.0):
        """KV-cached beam search: prefill once, expand to ``num_beams`` beams per
        batch row, then a ``lax.scan`` of single-token steps that keeps the K
        highest-scoring hypotheses (summed token log-probs). With
        ``eos_token_id`` a finished beam is frozen (only the EOS continuation at
        zero cost survives) and padded with EOS; scores are length-normalized by
        ``len**length_penalty`` (GNMT convention) for the final ranking.
        Returns ``(sequences [B, T0 + max_new_tokens], scores [B])`` — the best
        beam per row. Same caching/compile discipline as ``generate``."""
        assert self.tp_axis is None and self.seq_axis is None, \
            "beam_search() supports the plain (non-shard_map) model"
        assert max_new_tokens >= 1 and num_beams >= 1
        assert num_beams <= self.config.vocab_size, \
            f"num_beams {num_beams} exceeds vocab_size {self.config.vocab_size}"
        assert eos_token_id is None or 0 <= eos_token_id < self.config.vocab_size, \
            f"eos_token_id {eos_token_id} outside vocab [0, {self.config.vocab_size})"
        c = self.config
        B, T0 = tokens.shape
        K = int(num_beams)
        L = int(max_new_tokens)
        max_len = T0 + L
        assert max_len <= c.n_positions, \
            f"prompt {T0} + {L} new tokens exceeds n_positions {c.n_positions}"
        forward = self._build_cached_forward(max_len)
        V = c.vocab_size
        NEG = jnp.float32(-1e9)
        eos = -1 if eos_token_id is None else int(eos_token_id)

        def step_scores(logits, scores, live):
            """Per-beam next-token scores [B, K, V]: log-probs added to the beam
            score; a finished beam admits only the EOS continuation, at no cost."""
            logp = jax.nn.log_softmax(logits.reshape(B, K, V), axis=-1)
            cand = scores[:, :, None] + logp
            if eos >= 0:
                frozen = jnp.full((B, K, V), NEG).at[:, :, eos].set(scores)
                cand = jnp.where(live[:, :, None], cand, frozen)
            return cand

        def decode(p, first_logits, kcs, vcs):
            # beam init: top-K first tokens per row from the prefill logits.
            # kcs/vcs arrive ALREADY replicated per beam ([nl, B*K, ...]) and
            # donated — the expansion happens eagerly outside this program so
            # the donated input aliases the scan carry and the returned caches
            # (an in-jit repeat would leave the [nl, B, ...] input un-aliasable)
            logp0 = jax.nn.log_softmax(first_logits, axis=-1)      # [B, V]
            scores, tok0 = jax.lax.top_k(logp0, K)                  # [B, K]
            live = (tok0 != eos) if eos >= 0 else jnp.ones((B, K), bool)
            seqs = jnp.full((B, K, L), eos if eos >= 0 else 0, jnp.int32)
            seqs = seqs.at[:, :, 0].set(tok0)

            def step(carry, t):
                seqs, scores, live, kcs, vcs = carry
                # each beam's newest token is seqs[:, :, t] (written last round)
                prev = jax.lax.dynamic_slice_in_dim(seqs, t, 1, axis=2)
                logits, kcs, vcs = forward(p, prev.reshape(B * K, 1),
                                           T0 + t, kcs, vcs)
                cand = step_scores(logits, scores, live)            # [B, K, V]
                flat = cand.reshape(B, K * V)
                scores, idx = jax.lax.top_k(flat, K)                # [B, K]
                parent = idx // V                                   # [B, K]
                tok = (idx % V).astype(jnp.int32)
                # reorder: sequences + caches follow their parent beam
                seqs = jnp.take_along_axis(seqs, parent[:, :, None], axis=1)
                seqs = jax.lax.dynamic_update_slice_in_dim(
                    seqs, tok[:, :, None], t + 1, axis=2)
                flatp = (jnp.arange(B)[:, None] * K + parent).reshape(-1)
                kcs = kcs[:, flatp]
                vcs = vcs[:, flatp]
                live = jnp.take_along_axis(live, parent, axis=1)
                if eos >= 0:
                    live = live & (tok != eos)
                return (seqs, scores, live, kcs, vcs), ()

            (seqs, scores, live, kcs, vcs), _ = jax.lax.scan(
                step, (seqs, scores, live, kcs, vcs), jnp.arange(L - 1))
            # GNMT length normalization: finished beams count tokens up to and
            # including EOS; an unfinished beam counts exactly L (clamped — the
            # +1 for EOS must not credit beams that never emitted one)
            if eos >= 0:
                lengths = jnp.minimum(jnp.sum(jnp.cumprod(
                    (seqs != eos).astype(jnp.float32), axis=2), axis=2) + 1.0,
                    float(L))
            else:
                lengths = jnp.full((B, K), float(L))
            final = scores / jnp.power(lengths, jnp.float32(length_penalty))
            best = jnp.argmax(final, axis=1)                        # [B]
            # returning the caches lets XLA alias donated input -> carry -> output
            return (jnp.take_along_axis(seqs, best[:, None, None], axis=1)[:, 0],
                    jnp.take_along_axis(final, best[:, None], axis=1)[:, 0],
                    kcs, vcs)

        # the prefill program depends only on shapes — key it separately so
        # varying num_beams/eos/length_penalty reuses the expensive prompt jit
        jit_forward = self._cached_jit(("prefill", B, T0, max_len), forward,
                                       donate_argnums=(3, 4))
        jit_decode = self._cached_jit(
            ("beam", B, T0, L, K, eos, float(length_penalty)), decode,
            donate_argnums=(2, 3))

        cache_shape = (c.n_layer, B, c.n_head, max_len, c.head_dim)
        kcs = jnp.zeros(cache_shape, c.compute_dtype)
        vcs = jnp.zeros(cache_shape, c.compute_dtype)
        first_logits, kcs, vcs = jit_forward(params, tokens, 0, kcs, vcs)
        # per-beam cache expansion [nl, B, ...] -> [nl, B*K, ...] happens here,
        # outside the jit, so the decode program's donated inputs already have
        # the carry/output shape and XLA keeps ONE cache buffer end to end
        kcs, vcs = (jnp.repeat(t, K, axis=1) for t in (kcs, vcs))
        gen, scores, _, _ = jit_decode(params, first_logits, kcs, vcs)
        return jnp.concatenate([tokens, gen.astype(tokens.dtype)], axis=1), scores

    def generate(self, params, tokens, max_new_tokens: int,
                 temperature: float = 0.0, rng=None, *, top_k: int = 0,
                 top_p: float = 1.0):
        """Autoregressive decode with per-layer KV caches: one jitted prefill over
        the prompt, then a ``lax.scan`` of single-token steps that append to
        static-length caches (no recompilation per step, no O(T²) re-forward).
        ``temperature == 0`` is greedy; otherwise categorical sampling with ``rng``,
        optionally truncated to the ``top_k`` highest-probability tokens and/or the
        nucleus of smallest-count tokens whose cumulative probability reaches
        ``top_p`` (both filters compose; at least the argmax token always survives).
        Eval semantics (no dropout). Dense configs decode EXACTLY as the full
        re-forward would; MoE configs route each decode step's B tokens with a
        per-step capacity, so outputs match the full forward only while capacity
        does not bind (raise moe_capacity_factor for decode if exactness matters).
        Not for manual-TP / sequence-parallel model copies. The jitted prefill and
        decode programs are cached on the model per (shape, temperature, top_k,
        top_p) signature."""
        assert self.tp_axis is None and self.seq_axis is None, \
            "generate() supports the plain (non-shard_map) model"
        assert max_new_tokens >= 1, f"max_new_tokens must be >= 1 (got {max_new_tokens})"
        c = self.config
        B, T0 = tokens.shape
        max_len = T0 + int(max_new_tokens)
        assert max_len <= c.n_positions, \
            f"prompt {T0} + {max_new_tokens} new tokens exceeds n_positions {c.n_positions}"
        nh, hd = c.n_head, c.head_dim
        if temperature > 0:
            assert rng is not None, "temperature > 0 requires an rng key"
        assert top_k >= 0, f"top_k must be >= 0 (got {top_k})"
        assert 0.0 < top_p <= 1.0, f"top_p must be in (0, 1] (got {top_p})"
        forward = self._build_cached_forward(max_len)
        out_dtype = tokens.dtype

        def sample(logits, key):
            if temperature == 0:
                return jnp.argmax(logits, axis=-1).astype(out_dtype)
            logits = logits / jnp.float32(temperature)
            if top_k > 0 and top_k < c.vocab_size:
                kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
                logits = jnp.where(logits < kth, jnp.float32(-jnp.inf), logits)
            if top_p < 1.0:
                order = jnp.argsort(logits, axis=-1)[..., ::-1]
                sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
                probs = jax.nn.softmax(sorted_logits, axis=-1)
                # exclusive cumulative mass BEFORE each token: a token stays while
                # the mass ahead of it is under top_p, so the kept set is the
                # smallest prefix reaching top_p (the argmax always stays). The
                # keep mask is scattered back by SORT POSITION, not logit value,
                # so tokens tying the cutoff logit don't expand the nucleus.
                mass_before = jnp.cumsum(probs, axis=-1) - probs
                kept_sorted = mass_before < top_p
                inv = jnp.argsort(order, axis=-1)
                kept = jnp.take_along_axis(kept_sorted, inv, axis=-1)
                logits = jnp.where(kept, logits, jnp.float32(-jnp.inf))
            return jax.random.categorical(key, logits, axis=-1).astype(out_dtype)

        def decode(p, first, kcs, vcs, keys):
            def step(carry, key):
                tok, pos, kcs, vcs = carry
                logits, kcs, vcs = forward(p, tok[:, None], pos, kcs, vcs)
                nxt = sample(logits, key)
                return (nxt, pos + 1, kcs, vcs), tok

            (last, _, kcs, vcs), outs = jax.lax.scan(
                step, (first, jnp.asarray(T0, jnp.int32), kcs, vcs), keys)
            # outs collects each step's INPUT token; the final sample is `last`.
            # The caches ride out so the donated inputs alias carry and output
            return jnp.concatenate([outs.T, last[:, None]], axis=1), kcs, vcs

        # one compile per signature, reused across calls — params are explicit
        # jit arguments, not closure captures. The prefill depends only on
        # shapes (same key beam_search uses), so sampling-parameter variants
        # share the expensive prompt program.
        jit_forward = self._cached_jit(("prefill", B, T0, max_len), forward,
                                       donate_argnums=(3, 4))
        jit_decode = self._cached_jit(
            (B, T0, int(max_new_tokens), float(temperature), int(top_k),
             float(top_p), str(out_dtype)), decode, donate_argnums=(2, 3))

        cache_shape = (c.n_layer, B, nh, max_len, hd)
        kcs = jnp.zeros(cache_shape, c.compute_dtype)
        vcs = jnp.zeros(cache_shape, c.compute_dtype)
        logits, kcs, vcs = jit_forward(params, tokens, 0, kcs, vcs)
        keys = jax.random.split(rng if rng is not None else jax.random.PRNGKey(0),
                                max_new_tokens)
        first = sample(logits, keys[0])
        if max_new_tokens == 1:
            return jnp.concatenate([tokens, first[:, None]], axis=1)
        gen, _, _ = jit_decode(params, first, kcs, vcs, keys[1:])
        return jnp.concatenate([tokens, gen], axis=1)

    def decode_lint_programs(self, params, *, batch=2, prompt_len=4,
                             max_new_tokens=4, num_beams=2):
        """``(name, jitted, example_args, manifest)`` for the decode-path
        programs, in the shape ``ds-tpu lint`` consumes (lint/registry.py).

        Runs a tiny ``generate`` (greedy) and ``beam_search`` to populate the
        per-model program cache, then hands the cached jitted functions back
        with FRESH example arguments — the lint capture only lowers/compiles,
        nothing executes, but the arrays the tiny runs donated are dead. The
        manifests pin the invariant an undonated cache violates: every
        declared cache donation must actually alias (check_unusable), no
        cache-sized input may ride un-donated (min_undonated_bytes), and the
        single-host decode programs carry zero large collectives."""
        import numpy as np

        c = self.config
        B, T0, L, K = int(batch), int(prompt_len), int(max_new_tokens), int(num_beams)
        max_len = T0 + L
        tokens = jnp.asarray(np.arange(B * T0).reshape(B, T0) % c.vocab_size,
                             jnp.int32)
        self.generate(params, tokens, L)
        self.beam_search(params, tokens, L, num_beams=K)

        dt = jnp.dtype(c.compute_dtype).name
        compute = {"bfloat16": "bf16", "float16": "f16"}.get(dt, "f32")
        manifest = {"compute_dtype": compute,
                    "donation": {"check_unusable": True,
                                 "min_undonated_bytes": 1024},
                    "strict": True, "any_reduction": {"max": 0}}

        cache_shape = (c.n_layer, B, c.n_head, max_len, c.head_dim)

        def caches(beams=1):
            s = (cache_shape[0], B * beams) + cache_shape[2:]
            return jnp.zeros(s, c.compute_dtype), jnp.zeros(s, c.compute_dtype)

        cache = self._gen_jit_cache
        kcs, vcs = caches()
        keys = jax.random.split(jax.random.PRNGKey(0), L)
        first = jnp.zeros((B,), jnp.int32)
        first_logits = jnp.zeros((B, c.vocab_size), jnp.float32)
        bk, bv = caches(beams=K)
        return [
            ("gpt2_prefill", cache[("prefill", B, T0, max_len)],
             (params, tokens, 0) + caches(), manifest),
            ("gpt2_decode_greedy",
             cache[(B, T0, L, 0.0, 0, 1.0, str(tokens.dtype))],
             (params, first, kcs, vcs, keys[1:]), manifest),
            ("gpt2_decode_beam", cache[("beam", B, T0, L, K, -1, 1.0)],
             (params, first_logits, bk, bv), manifest),
        ]

    def param_count(self, params) -> int:
        from ..runtime.utils import param_count
        return param_count(params)
