"""Mixture-of-Experts with expert parallelism over a mesh axis.

Beyond the reference's feature set (DeepSpeed v0.3.0 has no MoE; DeepSpeed-MoE
arrived later) — included because expert parallelism is the 5th parallelism
dimension a complete TPU framework needs next to dp/tp/pp/sp. The design is the
GShard/Switch-Transformer recipe expressed TPU-first:

- **Static shapes everywhere**: top-1 (switch) or top-2 (GShard) routing with a
  fixed per-expert capacity ``C = ceil(top_k * tokens/E * capacity_factor)``
  (GShard scales capacity with k, else second choices mostly drop); slot
  assignment is one-hot + cumsum queueing (no dynamic shapes), tokens over
  capacity are DROPPED and ride the residual connection (standard switch
  semantics). The ``[E, C, H]`` dispatch buffer is built either by the dense
  one-hot ``[N,E,C]×[N,H]`` einsums (``dispatch="einsum"``, the default —
  N·E·C·H MXU flops) or by a row scatter-add on flat slot ids with a
  gather-based combine (``"scatter"`` — O(N·H) HBM traffic); both produce
  identical outputs and gradients, and on TPU the einsum measures FASTER
  (see the dispatch comment in ``__init__``).
- **Expert parallelism**: experts shard over a mesh axis. Inside ``shard_map``
  each rank holds ``E / ep`` experts; the ``[E, C, H]`` dispatch buffer is
  exchanged with ONE ``lax.all_to_all`` (rank r keeps the slices for its local
  experts from every peer — the NCCL AllToAll of every MoE system, riding ICI),
  experts run as one batched einsum over their leading axis (MXU-friendly), and
  a second all_to_all returns expert outputs to the token owners.
- **Load-balancing loss** (Switch eq. 4): ``E * sum_e f_e * p_e`` where ``f_e``
  is the fraction of tokens routed to expert e and ``p_e`` the mean router
  probability — computed over the GLOBAL batch via a psum so every rank adds the
  same auxiliary term.

``MoELayer`` follows the repo's pure-function module convention (init/apply) so
it slots into ``PipelineModule`` stacks and the engine unchanged.
"""

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.pallas import grouped_matmul as grouped, rows_sum
from ..utils import spans
from .mesh import MODEL_AXIS


class MoELayer:
    """Switch-style top-1 MoE FFN: ``[.., H] -> [.., H]`` with E expert MLPs.

    Args:
      hidden: model width H.
      ffn_dim: expert MLP inner width.
      num_experts: E (must divide by the expert-parallel degree when sharded).
      capacity_factor: per-expert capacity multiplier (1.0 = perfectly balanced).
      expert_axis: mesh axis name experts shard over when applied inside
        shard_map (None = single-program dense dispatch, still capacity-based).
      group_size: route tokens in fixed-size groups (the GShard convention, e.g.
        one sequence row per group). The dense dispatch/combine tensors are
        [N, E, C] with C ∝ N·cf/E — UNGROUPED that is O(N²·cf) elements and
        exhausts HBM at real batch·seq sizes; grouping bounds it at
        O(N·group_size·cf). None = one group (fine for small N / unit tests).
    """

    def __init__(self, hidden: int, ffn_dim: int, num_experts: int,
                 capacity_factor: float = 1.25,
                 expert_axis: Optional[str] = None,
                 group_size: Optional[int] = None,
                 top_k: int = 1,
                 dispatch: str = "einsum"):
        assert top_k in (1, 2), "top_k must be 1 (switch) or 2 (GShard)"
        assert dispatch in ("scatter", "einsum"), dispatch
        self.hidden = hidden
        self.ffn_dim = ffn_dim
        self.num_experts = num_experts
        self.capacity_factor = float(capacity_factor)
        self.expert_axis = expert_axis
        self.group_size = group_size
        self.top_k = top_k
        # "einsum" (default): the dense one-hot [N,E,C]x[N,H] contractions —
        # N*E*C*H MXU flops. "scatter": each kept token owns exactly one slot per
        # routed expert, so dispatch is a row scatter-add into the [E*C, H]
        # buffer and combine a row gather — O(N*H) HBM traffic, asymptotically
        # cheaper, but on the v5e chip XLA's row scatter/gather lowering LOSES
        # to the MXU einsum end-to-end (1.62 vs 1.28 ms/layer at the PERF.md
        # config, slope-timed) — wasted flops on a systolic array beat serialized
        # memory ops. Both modes are output- and gradient-identical.
        self.dispatch = dispatch

    # ------------------------------------------------------------------ params
    def init(self, rng, x=None):
        kg, k1, k2 = jax.random.split(rng, 3)
        H, F, E = self.hidden, self.ffn_dim, self.num_experts
        scale = 1.0 / math.sqrt(H)
        return {
            "gate_w": jax.random.normal(kg, (H, E), jnp.float32) * scale,
            # experts stacked on a leading E axis — the dim that shards over
            # the expert-parallel mesh axis
            "w_in": jax.random.normal(k1, (E, H, F), jnp.float32) * scale,
            "b_in": jnp.zeros((E, F), jnp.float32),
            "w_out": jax.random.normal(k2, (E, F, H), jnp.float32) / math.sqrt(F),
            "b_out": jnp.zeros((E, H), jnp.float32),
        }

    def param_shardings(self, mesh: Mesh, axis: Optional[str] = None):
        """Expert-sharded layouts (leading E axis over ``axis``); gate replicated."""
        axis = axis or self.expert_axis or MODEL_AXIS
        ex = NamedSharding(mesh, P(axis))
        return {"gate_w": NamedSharding(mesh, P()),
                "w_in": ex, "b_in": ex, "w_out": ex, "b_out": ex}

    # ---------------------------------------------------------------- routing
    def _route_plan(self, x2, gate_w, capacity):
        """ONE source of truth for the slot assignment (both dispatch encodings
        decode from this): top-1 (switch) or top-2 (GShard — second choices
        queue after every KEPT first choice per expert; a saturated router's
        phantom second pick is masked; gate weights normalized by p1+p2 even
        when the second pick drops, so the first is not re-normalized to 1).

        Returns (picks, (f, p)) where picks is a list of ``top_k`` tuples
        ``(expert [N] int32, pos [N] int32, keep [N] bool, weight [N] fp32)``
        — weight is the gate coefficient for the combine, NOT yet keep-masked —
        plus the Switch load-balancing statistics (callers under shard_map
        pmean (f, p) so the aux term is global)."""
        E, C = self.num_experts, capacity
        logits = jnp.dot(x2.astype(jnp.float32), gate_w.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)                     # [N, E]
        expert1 = jnp.argmax(probs, axis=-1)                        # [N]
        onehot1 = jax.nn.one_hot(expert1, E, dtype=jnp.float32)     # [N, E]
        pos1 = jnp.sum(jnp.cumsum(onehot1, axis=0) * onehot1 - onehot1, axis=-1)
        keep1 = pos1 < C
        p1 = jnp.sum(probs * onehot1, axis=-1)                      # [N]
        f = jnp.mean(onehot1, axis=0)                               # [E]
        p = jnp.mean(probs, axis=0)                                 # [E]
        e1 = expert1.astype(jnp.int32)
        pos1 = pos1.astype(jnp.int32)
        if self.top_k == 1:
            return [(e1, pos1, keep1, p1)], (f, p)
        probs2 = probs * (1.0 - onehot1)                            # mask the winner
        expert2 = jnp.argmax(probs2, axis=-1)
        onehot2 = jax.nn.one_hot(expert2, E, dtype=jnp.float32)
        onehot2 = onehot2 * (jnp.max(probs2, axis=-1) > 0)[:, None]
        first_counts = jnp.sum(onehot1 * keep1[:, None], axis=0)    # [E]
        pos2 = jnp.sum(jnp.cumsum(onehot2, axis=0) * onehot2 - onehot2
                       + first_counts[None, :] * onehot2, axis=-1)
        valid2 = jnp.sum(onehot2, axis=-1) > 0
        keep2 = (pos2 < C) & valid2
        p2 = jnp.sum(probs * onehot2, axis=-1)
        denom = jnp.maximum(p1 + p2, 1e-9)
        return [(e1, pos1, keep1, p1 / denom),
                (expert2.astype(jnp.int32), pos2.astype(jnp.int32), keep2,
                 p2 / denom)], (f, p)

    def _route(self, x2, gate_w, capacity):
        """Dense one-hot encoding of the plan: (dispatch [N, E, C] slot one-hot,
        combine [N, E, C] gate-weighted, (f, p))."""
        E, C = self.num_experts, capacity
        picks, fp = self._route_plan(x2, gate_w, capacity)
        dispatch = combine = 0.0
        for e, pos, keep, w in picks:
            d = (jax.nn.one_hot(e, E, dtype=jnp.float32)[:, :, None]
                 * jax.nn.one_hot(pos, C, dtype=jnp.float32)[:, None, :]
                 * keep[:, None, None])
            dispatch = dispatch + d
            combine = combine + d * w[:, None, None]
        return dispatch, combine, fp

    def _route_indexed(self, x2, gate_w, capacity):
        """Flat-slot encoding of the plan: each pick gets slot id
        ``expert * C + pos`` in ``[0, E*C)`` with ``E*C`` as the dropped/absent
        sentinel. Returns (slots [N, k] int32, weights [N, k] fp32 — zeroed on
        drop — and (f, p))."""
        E, C = self.num_experts, capacity
        picks, fp = self._route_plan(x2, gate_w, capacity)
        slots = [jnp.where(keep, e * C + pos, E * C) for e, pos, keep, _ in picks]
        weights = [(w * keep).astype(jnp.float32) for e, pos, keep, w in picks]
        return jnp.stack(slots, axis=1), jnp.stack(weights, axis=1), fp

    @staticmethod
    def _scatter_buf(x2, slots, n_slots):
        """Row scatter-add of tokens into their flat slots: [n_slots, H] buffer
        (one extra trash row swallows the drop sentinel)."""
        buf = jnp.zeros((n_slots + 1, x2.shape[-1]), x2.dtype)
        for i in range(slots.shape[1]):
            buf = buf.at[slots[:, i]].add(x2)
        return buf[:n_slots]

    @staticmethod
    def _gather_combine(out_flat, slots, weights, dtype):
        """Row gather of expert outputs back to token order, gate-weighted."""
        last = out_flat.shape[0] - 1
        y = None
        for i in range(slots.shape[1]):
            rows = out_flat[jnp.minimum(slots[:, i], last)]
            term = rows * weights[:, i][:, None].astype(out_flat.dtype)
            y = term if y is None else y + term
        return y.astype(dtype)

    @staticmethod
    def _expert_ffn(w_in, b_in, w_out, b_out, buf):
        """Batched expert MLP: ``buf [E_local, C*, H] -> [E_local, C*, H]``."""
        h = jnp.einsum("ech,ehf->ecf", buf, w_in.astype(buf.dtype),
                       preferred_element_type=jnp.float32)
        h = jax.nn.gelu(h + b_in.astype(jnp.float32)[:, None, :])
        y = jnp.einsum("ecf,efh->ech", h.astype(buf.dtype),
                       w_out.astype(buf.dtype),
                       preferred_element_type=jnp.float32)
        return (y + b_out.astype(jnp.float32)[:, None, :]).astype(buf.dtype)

    # ------------------------------------------------------------------ apply
    def apply(self, params, x):
        """``x [.., H] -> (y [.., H], aux_loss)``; call inside shard_map when
        ``expert_axis`` is set (tokens sharded over any OTHER axis or replicated;
        expert params sharded over ``expert_axis``)."""
        orig_shape = x.shape
        H, E = self.hidden, self.num_experts
        x2 = x.reshape(-1, H)
        N = x2.shape[0]

        if self.expert_axis is None:
            g = self.group_size if (self.group_size and N % self.group_size == 0
                                    and N > self.group_size) else N
            G = N // g
            capacity = max(1, int(math.ceil(
                g / E * self.capacity_factor * self.top_k)))
            xg = x2.reshape(G, g, H)

            if self.dispatch == "scatter":
                def route_group(xr):
                    slots, w, (f, p) = self._route_indexed(xr, params["gate_w"],
                                                           capacity)
                    buf = self._scatter_buf(xr, slots, E * capacity)
                    return buf.reshape(E, capacity, H), (slots, w), f, p

                def combine_groups(out, plans):  # out [G, E, C, H]
                    slots, ws = plans
                    return jax.vmap(lambda o, s, w: self._gather_combine(
                        o.reshape(E * capacity, H), s, w, x2.dtype))(out, slots, ws)
            else:
                def route_group(xr):
                    dispatch, combine, (f, p) = self._route(xr, params["gate_w"],
                                                            capacity)
                    buf = jnp.einsum("nec,nh->ech", dispatch.astype(xr.dtype), xr)
                    return buf, combine, f, p

                def combine_groups(out, combines):
                    return jnp.einsum("gnec,gech->gnh", combines.astype(out.dtype),
                                      out)

            bufs, plans, fs, ps = jax.vmap(route_group)(xg)  # [G, E, C, H], ...
            stacked = bufs.transpose(1, 0, 2, 3).reshape(E, G * capacity, H)
            out = self._expert_ffn(params["w_in"], params["b_in"],
                                   params["w_out"], params["b_out"], stacked)
            out = out.reshape(E, G, capacity, H).transpose(1, 0, 2, 3)
            y = combine_groups(out, plans)
            # mean over groups of the per-group balancing term (Switch eq. 4
            # computed per routing group, the same convention a sharded run uses)
            aux = E * jnp.mean(jnp.sum(fs * ps, axis=-1))
            return y.reshape(orig_shape), aux

        axis = self.expert_axis
        ep = jax.lax.axis_size(axis)
        assert E % ep == 0, \
            f"num_experts {E} must be divisible by the expert-parallel degree {ep}"
        e_local = E // ep
        # per-RANK per-expert capacity (GShard convention): each rank may send up
        # to C of its local tokens to any expert; an expert processes ep*C slots
        # total (= the global capacity). Local overflow drops even if other ranks
        # underuse their slots — the standard static-shape trade.
        capacity = max(1, int(math.ceil(N / E * self.capacity_factor * self.top_k)))
        # shard_map hands the expert-sharded leaves as [E_local, ...] slices
        gate_w = params["gate_w"]
        if self.dispatch == "scatter":
            slots, weights, (f, p) = self._route_indexed(x2, gate_w, capacity)
            buf = self._scatter_buf(x2, slots, E * capacity).reshape(E, capacity, H)
        else:
            dispatch, combine, (f, p) = self._route(x2, gate_w, capacity)
            # local [E, C, H] buffer -> all_to_all so rank r receives its local
            # experts' slices from EVERY rank: [ep, e_local, C, H] with a peer axis
            buf = jnp.einsum("nec,nh->ech", dispatch.astype(x2.dtype), x2)
        buf = buf.reshape(ep, e_local, capacity, H)
        recv = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=False)                 # [ep, e_local, C, H]
        stacked = recv.transpose(1, 0, 2, 3).reshape(e_local, ep * capacity, H)
        out = self._expert_ffn(params["w_in"], params["b_in"],
                               params["w_out"], params["b_out"], stacked)
        out = out.reshape(e_local, ep, capacity, H).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                                  tiled=False)                 # [ep, e_local, C, H]
        back = back.reshape(E, capacity, H)
        if self.dispatch == "scatter":
            y = self._gather_combine(back.reshape(E * capacity, H), slots,
                                     weights, x2.dtype)
        else:
            y = jnp.einsum("nec,ech->nh", combine.astype(back.dtype), back)
        # global load-balance statistics (mean over the full token batch)
        f = jax.lax.pmean(f, axis)
        p = jax.lax.pmean(p, axis)
        aux = E * jnp.sum(f * p)
        return y.reshape(orig_shape), aux


def moe_apply_sharded(layer: MoELayer, mesh: Mesh, params, x,
                      tokens_axis: Optional[str] = None):
    """Convenience wrapper: run an expert-sharded MoELayer over ``mesh`` from
    global arrays. ``tokens_axis`` optionally shards the flat token batch's
    leading dim (data parallelism composes with expert parallelism)."""
    axis = layer.expert_axis
    assert axis is not None, "layer must be constructed with expert_axis"
    # ONE source of truth for the layout: derive the shard_map specs from
    # param_shardings (a new param added there is automatically honored here)
    shardings = layer.param_shardings(mesh, axis)
    pspecs = {k: s.spec for k, s in shardings.items()}
    x_spec = P(*([tokens_axis] + [None] * (x.ndim - 1))) if tokens_axis else P()

    def local(params, x):
        y, aux = layer.apply(params, x)
        if tokens_axis:
            aux = jax.lax.pmean(aux, tokens_axis)
        return y, aux

    fn = jax.shard_map(local, mesh=mesh, in_specs=(pspecs, x_spec),
                       out_specs=(x_spec, P()), check_vma=False)
    return fn(jax.device_put(params, shardings), x)


# ===================================================================== dropless top-k
# The layer OLMoE (and the expert models queued behind it) trains with: softmax routing
# in float32, top-k without capacity, assignments sorted by expert, one grouped matmul
# over the experts. Nothing above this line is called from here.

@jax.custom_vjp
def _sort_rows(sent_to, slots, weights):
    """The ``n * k`` assignments sorted by expert, stably: ``(by_expert, order, inverse, w_sorted)``.
    ``order`` ``[n * k]`` is each sorted row's flat ``n * k + j``, ``inverse`` ``[n, k]`` the row that
    holds token n's j-th assignment, ``w_sorted`` the router's ``weights`` ``[n, k]`` in the rows'
    order: a further operand of the sort that orders the rows, where ``weights.reshape(-1)[order]``
    is a gather of scalars of its own (0.56 ms at 65,536: PERF.md, PR 46). Their cotangent is the
    sort run the other way: an operand of the sort by ``order`` that makes ``inverse``."""
    by_expert, order, w_sorted = jax.lax.sort((sent_to, slots, weights.reshape(-1)), num_keys=1, is_stable=True)
    inverse = jax.lax.sort((order, slots), num_keys=1)[1].reshape(weights.shape)
    return by_expert, order, inverse, w_sorted


def _sort_rows_fwd(sent_to, slots, weights):
    out = _sort_rows(sent_to, slots, weights)
    return out, out[1:3]


def _sort_rows_bwd(res, grads):
    order, inverse = res
    # ``order`` is a permutation of the slots: sorted by it, ``d_w_sorted`` IS ``d_w_sorted[inverse]``
    # (no two keys tie: a stable sort would carry an iota for nothing)
    return None, None, jax.lax.sort((order, grads[3]), num_keys=1, is_stable=False)[1].reshape(inverse.shape)


_sort_rows.defvjp(_sort_rows_fwd, _sort_rows_bwd)


def _chosen(values, experts):
    """``values[n, experts[n, j]]`` ``[n, k]`` out of the router's ``[n, E]``, read as a compare, a
    select and a sum over ``E`` that the compiler fuses, slot by slot (never ``[n, k, E]``): a sum
    of one value and zeros is that value, and its cotangent is the same select the other way,
    where ``take_along_axis`` and its scatter back move ``n k`` scalars at 8 ns each (0.3-0.7 ms
    a layer either way: PERF.md, PR 51). A ``where``, never a product with a one-hot."""
    lanes = jnp.arange(values.shape[1], dtype=experts.dtype)
    return jnp.concatenate([jnp.sum(jnp.where(slot == lanes, values, 0), axis=1, keepdims=True)
                            for slot in jnp.split(experts, experts.shape[1], axis=1)], axis=1)


def _rows_of_the_tokens(rows, inverse):
    """``[k, n, H]``: the sorted row of every token's j-th assignment, slot by slot. The gather's
    ``[k n, H]`` is that as it lies whatever ``k`` (``n`` fills the tiles), where ``[n, k, H]``
    is a relayout of all the rows at a ``k`` the sublane tile of eight does not divide. This gather
    FROM the ``n k`` sorted rows is bound by issuing rows, not by bytes: less the write of its
    output at HBM's rate it costs 29-36 ns a ROW whatever the row's width (2.09 ms at 49,152 x
    2,688, 2.45 at 65,536 x 2,304, 2.24 at 65,536 x 2,048, 1.10 at 32,768 x 2,048), where the
    gather from the ``n`` tokens' rows, a source the compiler holds in fast memory, is its output's
    write at 650 GB/s and nothing more (0.40, 0.47, 0.41, 0.21 ms: PERF.md, PR 53,
    ``tests/perf/rows_sum_probe.py``)."""
    n, k = inverse.shape
    return rows[inverse.T.reshape(-1)].reshape(k, n, -1)


# Dispatch and combine are each other's transposes, so each is the other's cotangent: one row
# gather either way (and a sum over k), never a scatter, and neither keeps a row for its backward.
# ``sort = (tok, inverse, runs)``: each sorted row's token, the row of every token's j-th
# assignment, and where the combine reads its rows in runs their bounds (``_run_bounds``), else None.
@jax.custom_vjp
def _take_rows(x, tok, inverse, runs):
    """Dispatch: ``xs[m] = x[tok[m]]`` for the ``n * k`` sorted assignments (``tok = order // k``)."""
    return x[tok]


@jax.custom_vjp
def _sum_rows(ys, tok, inverse, runs):
    """Combine: ``y[n] = sum_j ys[inverse[n, j]]``, summed in float32. The router's weights are in
    the rows already (``_activate``), so nothing of ``ys`` is needed to pull a cotangent back.
    Two forms of the same sum. Without ``runs``: the compiled one, a gather by ``inverse`` and a
    sum over ``k`` (``_rows_of_the_tokens``): off the TPU, as ``grouped_matmul`` keeps
    ``lax.ragged_dot`` there, and wherever ``_run_bounds`` says. With ``runs``: the kernel
    ``ds_moe_rows_sum`` (``ops/pallas/rows_sum.py``), which never reads ``inverse``: the sort is
    stable, so a token tile's rows in a group are one run of ``ys``, streamed in whole chunks and
    added in fast memory through a one-hot product, each token's in float32 in sorted-row order
    (slot order in the compiled form: at most ``k`` float32 additions in another order, one
    rounding to the compute type either way). Alone on the chip, with the sum over ``k`` behind
    the gather: 2.53 -> 0.70 ms a call at Nemotron-H's (k 6, 8 groups, 2,688), 2.97 -> 0.98 at
    Mellum 2's (8, 16, 2,304), 1.35 -> 0.45 at GLM's and LFM2's (4, 8, 2,048), a balanced router
    or a leaning one; at OLMoE's 64 groups, runs of 32 rows, 2.68 -> 2.35 balanced and 2.65 -> 1.27
    at the load its cell's router has, +3.3 % of the cell's tokens a second (PERF.md, PR 53). As the
    dispatch's cotangent it serves the second slow gather of a layer: two calls an expert layer,
    neither made again by a recomputed layer. A row that is NOT finite: the gather confines it to
    its own token; the kernel's one-hot product meets it with a zero (``0 x inf``), so every token
    of a 256-token tile that visits the row's chunk of 128 comes out not finite, and no other
    tile (``test_olmoe.py -k not_finite``): the step's overflow check fires either way."""
    if runs is None:
        return jnp.sum(_rows_of_the_tokens(ys, inverse).astype(jnp.float32), axis=0).astype(ys.dtype)
    return rows_sum.rows_sum(ys, tok, runs, inverse.shape[0], interpret=jax.default_backend() != "tpu")


_take_rows.defvjp(lambda x, *sort: (_take_rows(x, *sort), sort), lambda sort, dxs: (_sum_rows(dxs, *sort), None, None, None))
_sum_rows.defvjp(lambda ys, *sort: (_sum_rows(ys, *sort), sort), lambda sort, dy: (_take_rows(dy, *sort), None, None, None))

# The grouped products' tiles: the rows of a tile where the contraction is cut, and the MOST a
# contraction and a column tile take there. The kernels (``ops/pallas/grouped_matmul.py``)
# round K and N up to whole tiles and compute every tile in full (a K remainder is masked
# besides), so a tile that does not divide its width is issued work nobody needs: (512, 1024,
# 1024) clipped with ``min`` padded 2304 and 2688 to 3072 and 1792 and 1856 to 2048, 1.26 to 1.52
# times the products (PERF.md, PR 47, ``tests/perf/gmm_sweep.py``). What bounds a tile from below
# is the operations a byte, ``tm tn / (tm + tn)`` (341 at a column tile of 1024, 284 at 640, 256
# at 512 against the chip's 240): no column tile goes under 512.
GMM_TILES = (512, 1024, 1024)
# What bounds them from above was read on the chip (PERF.md, PR 55, ``tests/perf/gmm_sweep.py``).
# A WHOLE contraction is 5-16 % faster a call than one cut in pieces (each piece a round trip of
# ``ds_gmm``'s float32 accumulator and a fetch of the weights' block a row tile), at every width
# read, up to 3,072: megablox's call could not state its fast memory, and its 16 MiB cut every
# contraction over 1,024; ``ops/pallas/grouped_matmul.py`` asks the compiler for what its blocks
# take. Beside a whole contraction ``ds_gmm`` reads level at any column tile (1.5 % between 640
# and the whole 1,856), and ``ds_tgmm`` falls off a cliff once its float32 accumulator ``[tk,
# tn]`` passes these elements (16 MiB: ``[2304, 1792]`` and ``[2048, 2048]`` level, ``[2688,
# 1856]`` 4.81 ms for 2.96, ``[2048, 3072]`` 3.93 for 2.45). So the one bound is ``ds_tgmm``'s,
# and the two products of a pair of widths share their tiles: K stays whole where a column tile
# of 512 beside it stays under the bound, and N takes the widest tile under it of those that pad
# it least. (A wide N beside a narrow K is bounded the same way by ``ds_gmm``'s float32 product
# ``[tm, tn]``.)
GMM_ACC = 2048 * 2048


def _width_tile(width, most):
    """The tile for a contraction or column ``width`` that ``most`` bounds: the whole width
    where ``most`` holds it; else the multiple of 128 from 512 up to ``most`` that pads the
    width least, of two that pad alike the larger (with 1,024: 2048 -> 1024, 2304 -> 768, 1792
    and 2688 -> 896, 1856 -> 640: 1920, where 1024 pads it to 2048)."""
    if width <= most:
        return width
    return min(range(most - most % 128, 512 - 1, -128), key=lambda tile: -(-width // tile) * tile)


# Where the contraction stays whole the ROW tile goes by the rows a group holds (PERF.md, PR 57).
# The kernels walk megablox's schedule: one grid step a (group, row tile) pair and column tile,
# and a row tile that a group's boundary cuts is visited once for EACH group in it and computed
# whole under a mask, so a call of ``M`` rows in ``G`` groups takes up to ``M / tm + G - 1``
# visits where ``M / tm`` are needed: 191 for 128 at OLMoE's 65,536 rows in 64 groups under a
# row tile of 512. With K whole the weights' block is fetched once a group whatever ``tm``, so a
# smaller row tile costs no traffic; what it costs is grid steps. Read on the chip at 128 | 256 |
# 512 | 1,024 rows (``tests/perf/gmm_sweep.py --grid picked``, ``chiprun_out/pr57a/``), a step
# takes a FIXED part, the same at every row tile (0.26-0.45 us in ``ds_gmm``, 0.5-0.55 in
# ``ds_tgmm``, which adds into its float32 accumulator), and its ``[tm, tk] x [tk, tn]`` product
# at the MXU's own rate (to 1 % in ``ds_gmm``; the masks' selects add 2.5 % in ``ds_tgmm``); and
# the step that OPENS a group is no shorter than the fetch of the group's weights (``ds_tgmm``:
# the write of its gradient), which the step before it hides: the bytes of ``tk tn`` values, as
# long as the product of ``GMM_FETCH`` rows (the chip's 240 operations a byte). That last part is
# why 128 is no faster than 256 at OLMoE's 1,024 rows a group, though it visits fewer rows.
# ``GMM_STEP`` is the fixed part BY KIND in the multiply-adds the MXU makes in that time, and the
# row tile is the one of ``GMM_ROW_TILES`` that makes the walk's most steps times a step, and the
# groups' openings, cost least.
GMM_ROW_TILES = (128, 256, 512)
GMM_STEP = {"gmm": 26e6, "gmm_t": 26e6, "tgmm": 49e6}
GMM_FETCH = 240


def _row_tile(kind, rows, groups, volume):
    """The row tile of a grouped product of ``kind`` whose ``rows`` lie in ``groups`` groups and
    whose grid step multiplies a row by ``volume = tk tn`` weights: the one of ``GMM_ROW_TILES``
    that divides the rows and makes ``(rows / tm + groups - 1) step + groups max(0, GMM_FETCH
    volume - step)`` least, ``step = GMM_STEP + tm volume``; of two alike the smaller. Fewer rows
    a group never pick a larger tile."""
    def cost(tm):
        step = GMM_STEP[kind] + tm * volume
        return (rows // tm + groups - 1) * step + groups * max(0, GMM_FETCH * volume - step)

    return min((tm for tm in GMM_ROW_TILES if rows % tm == 0), key=cost, default=min(GMM_TILES[0], rows))


def _tiles(kind, rows, groups, contraction, columns):
    """``(tm, tk, tn)`` for a grouped product of ``kind`` (``gmm``, its transposed form ``gmm_t``,
    ``tgmm``) over ``rows`` rows in ``groups`` groups at these widths, ``tgmm``'s ``tk`` and
    ``tn`` its output's two widths: a function of the kind and the shapes alone."""
    tm = min(GMM_TILES[0], rows)                    # the row tile beside a CUT contraction
    most = GMM_ACC // max(contraction, tm)          # the widest column tile beside the whole contraction
    if most < 512:                                  # past every cell's widths: cut, as under megablox
        return tm, _width_tile(contraction, GMM_TILES[1]), _width_tile(columns, GMM_TILES[2])
    tn = _width_tile(columns, most)
    return _row_tile(kind, rows, groups, contraction * tn), contraction, tn


def _run_bounds(n, k, G, H, group, tok):
    """``runs`` for ``_take_rows`` and ``_sum_rows``: where the combine reads its rows in runs
    (``ops/pallas/rows_sum.py``), the kernel's visits from each token tile's bounds in each of the
    ``G`` groups of the sorted rows (``group [n k]`` counts from 0 and ascends, ``tok`` ascends
    inside a group), made once a layer for the forward's call and the backward's; None
    where it gathers them by index: off the TPU and at shapes the kernel does not take. Static
    shapes alone."""
    if jax.default_backend() != "tpu" or not rows_sum.fits(n, n * k, H):
        return None
    return rows_sum.visits(rows_sum.run_bounds(group, tok, n, G), n * k)


def _ragged_sizes(rhs, group_sizes, first):
    """``lax.ragged_dot``'s operands for the groups ``first .. first + len(rhs) - 1`` alone:
    the rows before them become one leading group of zero weights, the rows after them
    belong to no group and come out zero."""
    if first is None:
        return rhs, group_sizes
    before = jnp.sum(jnp.where(jnp.arange(group_sizes.shape[0]) < first, group_sizes, 0))
    mine = jax.lax.dynamic_slice(group_sizes, (first,), (rhs.shape[0],))
    return (jnp.concatenate([jnp.zeros_like(rhs[:1]), rhs]),
            jnp.concatenate([before[None].astype(group_sizes.dtype), mine]))


def _count_product(kind, widths, rows, groups, tiles=None):
    """While a step program is traced, every grouped product leaves in the recorder how it will
    run: ``moe.<kind>.whole_k[<program>] <K>x<N> in <tm>x<tk>x<tn>, <M / G> rows a group, visits
    <= <(M / tm + G - 1) / (M / tm)>`` where its contraction stays ONE tile in fast memory
    (``tgmm``: its output's first width): what ``_tiles``' row tile saw and the most (group, row
    tile) pairs the walk can take over the row tiles the rows need. ``.cut_k`` where the
    contraction is cut in pieces, ``.ragged_dot`` off the TPU (no tiles); once a trace of the
    call (a layer traced once and run four times counts once). ``docs/telemetry.md``."""
    how = "ragged_dot" if tiles is None else "whole_k" if tiles[1] == widths[0] else "cut_k"
    text = " %dx%d" % widths
    if tiles:
        needed = -(-rows // tiles[0])
        text += " in %dx%dx%d, %d rows a group, visits <= %.2f" % (*tiles, rows // groups, (needed + groups - 1) / needed)
    spans.recorder().count_in_program(f"moe.{kind}.{how}", text)


def grouped_matmul(lhs, rhs, group_sizes, first=None, out=None, transpose_rhs=False):
    """``out[m] = lhs[m] @ rhs[g(m)]`` for rows sorted by group: ``lhs [M, K]``,
    ``rhs [G, K, N]`` (``[G, N, K]`` with ``transpose_rhs``), ``group_sizes [G]`` summing
    to ``M``. With ``first`` (an int32 scalar, traced or not) ``rhs`` holds only the groups
    ``first .. first + len(rhs) - 1`` of a longer ``group_sizes``: their rows are computed,
    every other row is ``out``'s (unspecified where ``out`` is None), so that a chain of
    calls over pieces of the experts fills one buffer in place. On the TPU this is the kernel
    ``ds_gmm`` (``ops/pallas/grouped_matmul.py``) at the tiles ``_tiles`` picks, elsewhere
    ``lax.ragged_dot``, which XLA's CPU backend runs and whose TPU lowering reached 55 % of
    megablox's rate on the chip."""
    kind, columns = "gmm_t" if transpose_rhs else "gmm", rhs.shape[1 if transpose_rhs else 2]
    rows, groups = lhs.shape[0], group_sizes.shape[0]
    tiles = _tiles(kind, rows, groups, lhs.shape[1], columns) if jax.default_backend() == "tpu" else None
    _count_product(kind, (lhs.shape[1], columns), rows, groups, tiles)
    if tiles is None:
        if transpose_rhs:
            rhs = rhs.swapaxes(1, 2)
        rhs, sizes = _ragged_sizes(rhs, group_sizes, first)
        y = jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=lhs.dtype)
        if out is None:
            return y
        rows = jnp.arange(lhs.shape[0])
        return jnp.where(((rows >= sizes[0]) & (rows < jnp.sum(sizes)))[:, None], y, out)
    if first is not None and out is None:
        # the kernel writes the tiles it visits and the pieces' calls together visit all:
        # the first starts from a buffer nothing has written
        out = jax.lax.empty((lhs.shape[0], columns), lhs.dtype)
    return grouped.gmm(lhs, rhs, group_sizes, lhs.dtype, tiles, first, out, transpose_rhs=transpose_rhs)


def grouped_matmul_weight_grad(lhs, grad, group_sizes, first, like):
    """The cotangent of ``grouped_matmul``'s ``rhs`` (shaped and typed ``like`` it):
    ``d_rhs[g] = lhs[rows of g].T @ grad[rows of g]`` for the groups ``first .. first +
    len(like) - 1`` (all of them where ``first`` is None). The kernel ``ds_tgmm`` on the TPU."""
    rows, groups = lhs.shape[0], group_sizes.shape[0]
    tiles = _tiles("tgmm", rows, groups, *like.shape[1:]) if jax.default_backend() == "tpu" else None
    _count_product("tgmm", like.shape[1:], rows, groups, tiles)
    if tiles is None:
        padded, sizes = _ragged_sizes(like, group_sizes, first)
        d_rhs, = jax.linear_transpose(
            lambda r: jax.lax.ragged_dot(lhs, r, sizes, preferred_element_type=grad.dtype),
            padded)(grad)
        return d_rhs if first is None else d_rhs[1:]
    return grouped.tgmm(lhs, grad, group_sizes, like.dtype, tiles, first, like.shape[0])


@jax.custom_vjp
def experts_matmul(lhs, pieces, firsts, group_sizes):
    """``grouped_matmul`` over experts that come in ``pieces`` (a tuple of ``[g, K, N]``;
    ``firsts[i]`` the group piece i starts at, None for one whole piece): one kernel call a
    piece, each writing its groups' rows into the same buffer, so that nothing copies the
    pieces together and the first piece's product can start before the last has arrived.
    Its cotangents are written the same way: one chained product for ``lhs``, one
    ``[g, K, N]`` gradient a piece, in the pieces' order."""
    out = None
    for rhs, first in zip(pieces, firsts):
        out = grouped_matmul(lhs, rhs, group_sizes, first, out)
    return out


def _experts_matmul_fwd(lhs, pieces, firsts, group_sizes):
    return experts_matmul(lhs, pieces, firsts, group_sizes), (lhs, pieces, firsts, group_sizes)


def _experts_matmul_bwd(res, grad):
    lhs, pieces, firsts, group_sizes = res
    d_lhs, d_pieces = None, []
    for rhs, first in zip(pieces, firsts):
        d_lhs = grouped_matmul(grad, rhs, group_sizes, first, d_lhs, transpose_rhs=True)
        d_pieces.append(grouped_matmul_weight_grad(lhs, grad, group_sizes, first, rhs))
    return d_lhs, tuple(d_pieces), tuple(None for _ in firsts), None


experts_matmul.defvjp(_experts_matmul_fwd, _experts_matmul_bwd)


# ------------------------------------------------------------- the experts' exchange
def _send(x, axis, places):
    """Every chip's ``x`` to the chip ``places`` further along ``axis`` (negative: back)."""
    n = jax.lax.axis_size(axis)
    return jax.lax.ppermute(x, axis, [(i, (i + places) % n) for i in range(n)])


def _nearest_first(n):
    """Where the chips of an axis of ``n`` sit from any one of them, itself first and then
    by their distance along the axis: 0, 1, -1, 2, -2, ... places back. Along the axis, not
    on the links: in ``jax.devices()`` order one place on a 2 x 2 host is a diagonal for
    two of the four chips (PERF.md, PR 27: the chips as a ring measured 1.6-1.8 % faster)."""
    return [0] + sorted((s if 2 * s <= n else s - n for s in range(1, n)),
                        key=lambda s: (abs(s), -s))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def gather_pieces(w, axis):
    """All-gather of ``w`` (this chip's experts, ``[E / n, ...]``) over ``axis`` as ``n - 1``
    transfers chip to chip, none waiting for another: ``n`` pieces, this chip's own first,
    then the others' in a fixed order (``piece_firsts`` has the expert each starts at). Each
    transfer is a ``collective-permute`` that the TPU compiler starts early and ends late,
    with the kernels that use the earlier pieces between; an ``all_gather`` of the same
    bytes it runs synchronously, and a ring of neighbour hops makes every piece wait for
    the hop before it (PERF.md, PR 27). The cotangent sends each piece's gradient straight
    to the experts' owner, which adds what the ``n`` chips computed in float32 and rounds
    once: the complete sum over the chips, not the mean."""
    return tuple(w if back == 0 else _send(w, axis, back)
                 for back in _nearest_first(jax.lax.axis_size(axis)))


def _gather_pieces_fwd(w, axis):
    return gather_pieces(w, axis), None


def _gather_pieces_bwd(axis, _, grads):
    with jax.named_scope("ds_moe_exchange"):
        total = grads[0].astype(jnp.float32)
        for back, grad in zip(_nearest_first(len(grads))[1:], grads[1:]):
            total += _send(grad, axis, -back).astype(jnp.float32)
        return (total.astype(grads[0].dtype),)


gather_pieces.defvjp(_gather_pieces_fwd, _gather_pieces_bwd)


def piece_firsts(axis, per_chip):
    """The expert each piece of ``gather_pieces`` starts at, ``per_chip`` experts a chip."""
    n, me = jax.lax.axis_size(axis), jax.lax.axis_index(axis)
    return tuple(((me - back) % n * per_chip).astype(jnp.int32) for back in _nearest_first(n))


# What a layer fetched for its forward, by name (``checkpoint_name``): beside ``ds_moe_gate_up``
# in the policy of a layer whose backward finds the pieces still there.
FETCHED = ("ds_moe_fetched_gate_up", "ds_moe_fetched_down")

_room = lambda: 0     # noqa: E731    (bytes; replaced while a program is traced under ``room_for_fetched_experts``)


@contextlib.contextmanager
def room_for_fetched_experts(room):
    """While a model is traced under this, its expert layers may keep ``room()`` bytes a chip
    of the experts they fetched for the forward (``fetches_kept``); asked only by a layer that
    fetches. The engine enters it around every trace of a model with what it knows of the chip
    (``DeepSpeedEngine._room_beside_state``); outside it nothing is kept."""
    global _room
    before, _room = _room, room
    try:
        yield
    finally:
        _room = before


def fetches_kept(layers, layer_bytes, room):
    """Whether ``layers`` stacked expert layers keep the experts they fetched from their forward
    for their backward: a pure function of a chip's bytes, a layer's ``n - 1`` fetched pieces of
    both arrays (``layer_bytes``) and the ``room`` for all layers'. Kept, a piece crosses the
    chips twice a step (fetched, its gradient sent home) and not three times. ALL OR NOTHING:
    with ``w_down``'s pieces alone kept (a third of the bytes, and the fetch a backward was seen
    waiting for) ``olmoe_d4_train_4chip``'s step read 323.6, 339.4 and 325.9 ms under three
    schedules where it reads 318.7 with nothing kept and 297.6 with everything: the second fetch
    that is left still stands between the gradients' sends, and what is kept costs the second
    step in flight all the same (PERF.md, PR 54)."""
    return layers * layer_bytes <= room


# ------------------------------------------------------------------ the experts' form
SILU_GATED, RELU2 = "silu_gated", "relu2"


def _activate(form, up, dt, weights=None):
    """What lies between an expert's products, from the first one's output ``up``: the gated
    SiLU of its two halves ``silu(gate) * up`` (``w_gate_up [.., H, 2F]``), or the squared
    ReLU of the whole (``w_up [.., H, F]``: an expert of two matrices); in float32, and there
    times the rows' ``weights`` ``[rows]`` where the router's go in BEFORE ``w_down``, which is
    linear: one pass, one rounding, and the weights' gradient is a row sum of its backward."""
    if form == RELU2:
        act = jnp.square(jax.nn.relu(up.astype(jnp.float32)))
    else:
        F = up.shape[1] // 2
        act = jax.nn.silu(up[:, :F].astype(jnp.float32)) * up[:, F:].astype(jnp.float32)
    return (act if weights is None else act * weights[:, None]).astype(dt)


# ------------------------------------------------------------ a held range's rows
def _held_pass(form, c, x2, weights, w_gate_up, w_down, sort):
    """The held-range form's pass ``c``: the sorted rows ``lo + c n .. lo + (c + 1) n - 1``,
    those of them that are held, gathered, multiplied and added into their tokens
    ``[n, H]`` float32. ``sort = (tok, order, lo, rows_here, ends)``: each sorted row's
    token and flat ``n * k + j``, the first held row, their count, and the held rows up to
    each held expert."""
    tok, order, lo, rows_here, ends = sort
    n = x2.shape[0]
    with jax.named_scope("ds_moe_dispatch"):
        at = c * n + jnp.arange(n, dtype=jnp.int32)
        live = (at < rows_here)[:, None]
        at = jnp.minimum(lo + at, tok.shape[0] - 1)
        mine, slot = tok[at], order[at]
        sizes = jnp.diff(jnp.clip(ends - c * n, 0, n), prepend=0).astype(jnp.int32)
        xs = jnp.where(live, x2[mine], 0)     # a row past the held ones: nothing in, nothing back
    with jax.named_scope("ds_moe_experts"):
        gate_up = experts_matmul(xs, (w_gate_up,), (None,), sizes)
        ys = experts_matmul(_activate(form, gate_up, xs.dtype), (w_down,), (None,), sizes)
    with jax.named_scope("ds_moe_combine"):
        # the products leave rows past their groups unwritten: taken as zero
        ys = jnp.where(live, ys.astype(jnp.float32), 0.0) * weights.reshape(-1)[slot][:, None]
        return jnp.zeros(x2.shape, jnp.float32).at[mine].add(ys)


def _passes(k, rows_here, n, one_pass, zero):
    """``sum_c one_pass(c)`` over the passes that hold a held row, of at most ``k``: a pass
    past the held rows costs a comparison, and leaves the sum where it is."""
    def add(total, c):
        more = lambda t: jax.tree_util.tree_map(jnp.add, t, one_pass(c))     # noqa: E731
        return jax.lax.cond(c * n < rows_here, more, lambda t: t, total), None
    return jax.lax.scan(add, zero, jnp.arange(k, dtype=jnp.int32))[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_rows(static, x2, weights, w_gate_up, w_down, sort):
    """The held experts' part of the layer's result, ``[n, H]``: ``_held_pass`` over as
    many passes as the held rows fill (``static = (k, the experts' form)``). The backward
    keeps the layer's input, the router's weights and the sort, and makes each pass again as
    it takes its cotangents (a gather and two small products): a row sent to an absent expert
    costs no buffer either way."""
    k, form = static
    n = x2.shape[0]
    y = _passes(k, sort[3], n, lambda c: _held_pass(form, c, x2, weights, w_gate_up, w_down, sort),
                jnp.zeros(x2.shape, jnp.float32))
    return y.astype(x2.dtype)


def _held_rows_fwd(static, x2, weights, w_gate_up, w_down, sort):
    return _held_rows(static, x2, weights, w_gate_up, w_down, sort), (x2, weights, w_gate_up, w_down, sort)


def _held_rows_bwd(static, res, dy):
    k, form = static
    *inputs, sort = res
    dy = dy.astype(jnp.float32)

    def one_pass(c):
        _, back = jax.vjp(lambda *a: _held_pass(form, c, *a, sort), *inputs)
        return tuple(g.astype(jnp.float32) for g in back(dy))

    zero = tuple(jnp.zeros(a.shape, jnp.float32) for a in inputs)
    grads = _passes(k, sort[3], inputs[0].shape[0], one_pass, zero)
    return tuple(g.astype(a.dtype) for g, a in zip(grads, inputs)) + (None,)


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


class DroplessMoE:
    """Top-k mixture of experts that drops nothing.

    ``apply(params, x [B, T, H]) -> (y, aux, stats)``. The router runs in float32 at
    full precision; the ``k`` largest probabilities weigh their experts as they are
    (``norm_topk_prob`` renormalises them). A chosen value is read out of ``[n, E]``, and its
    cotangent put back, by a compare and a select over ``E`` (``_chosen``), never by index: a
    gather or scatter of ``n k`` single floats took 0.3-0.7 ms a layer where the dense form
    takes microseconds (PERF.md, PR 51). Two things follow from a model's published keys
    and nothing else. ``router``: ``"softmax"`` of the logits, or ``("sigmoid_bias", factor[, eps])``:
    ``s = sigmoid(logits)``, the ``k`` largest of ``s + b`` chosen (``router_bias [E]``, a
    leaf no gradient reaches: a model moves it by a rule of its own, from ``stats["counts"]``),
    each weighted by its OWN ``s``, renormalised over the chosen where ``norm_topk_prob``
    (``s_e / (sum of the chosen s + eps)``, ``eps`` 1e-20 unless the family's code has another),
    times ``factor``; ``aux`` is then zero (such a router is balanced by its bias).
    ``experts``: ``"silu_gated"``, ``W_down(silu(W_gate x) * W_up x)`` with gate and up side by
    side in ``w_gate_up [.., H, 2F]``, or ``"relu2"``, ``W_down relu(W_up x)^2`` over
    ``w_up [.., H, F]``. Everything below is shared by both. A chip sorts its own tokens' assignments by
    expert and each expert's rows go through one grouped matmul (gate and up in one
    product, then down): the buffer holds exactly ``n * k`` rows however the router
    leans, so there is no capacity, no bound and nothing that could fail to fit. ``aux``
    is the load-balancing term ``E * sum_e f_e * P_e`` over the global batch, with
    ``f_e`` the share of assignments expert e received and ``P_e`` its mean probability.

    Across chips the experts are STORED split and GATHERED for use, as ZeRO-3 does with a
    parameter: where the context mesh's ``data`` axis (the engine traces the model under
    its own) has several devices that divide the experts, each chip owns ``E / ep`` of
    them, master copy and optimizer state with them; a layer fetches the other chips' bf16
    expert weights for the forward pass (``gather_pieces``: ``ep - 1`` chip-to-chip transfers
    the compiler runs under the kernels, each arrived piece going straight into its own
    grouped-matmul call) and, where the chip has no room to keep them (``fetches_kept``),
    again for the backward, every chip computes every
    expert on its own tokens, and each piece's gradient goes straight back to its owner,
    who sums them: complete there and not averaged. Tokens never cross the chips: this is
    not expert parallelism by a token all-to-all. That needs a static bound on what a
    chip may receive, and a router at initialisation sends most tokens to the same few
    experts (measured at OLMoE's widths, PERF.md PR 26: one chip had 2.19 times its even
    share), so a bound that never drops is the worst case, ``ep`` times the buffers. The
    price is wire traffic that grows with the parameters and not with the tokens
    (PERF.md section 7: the token exchange is still to be measured inside a step).

    The HELD-RANGE form (``held=(first, count)``) is one chip's share of a layer whose
    experts are divided over more chips than are here (Qwen3-Next's 512 over 16): the
    router keeps its ``num_experts`` outputs and its ``top_k``, every assignment is sorted
    as above, and the layer computes the part of the result that the experts ``first ..
    first + count - 1`` give: the rows sent to them, ``n`` sorted rows a pass (one pass
    unless more than ``n`` of the ``n * k`` assignments landed here), gathered, multiplied
    and added into their tokens. What the absent experts would add is left out, and a row
    sent to one costs no product, no gather and no buffer; nothing of the row path is kept
    for the backward, which makes it again. The expert arrays hold ``count`` experts. No
    token crosses a chip: this is the layer WITHOUT its exchange, and nothing stands in for
    the absent chips. Under a mesh every chip is a replica of the same share. With the
    whole range held (``held=None`` or ``(0, num_experts)``) it is the layer above.

    ``stand_in=True`` (held-range form only) lets the held experts stand in for the absent
    ones: expert ``e``'s rows go through the weights of held expert ``first + (e - first) %
    count``, as if this chip were each of the ``num_experts / count`` chips in turn. All
    ``n * k`` assignments are then computed here whatever the router does: the rows a
    deployment's exchange brings a chip at an even router, and the same work on every step,
    where the plain held range's rows follow the router's lean (a router that sends every
    token to one expert gives this chip ``n`` rows more or none, as that expert is held or
    not). The router, its choice, its weights and ``counts`` stay those of all
    ``num_experts``; ``rows_here`` is ``n * k``. Since the rows are ``n * k`` before anything
    is traced, the layer does what a chip does after its exchange and what the whole range
    does above: ONE sort by stand-in expert (the router's weights sorted with the rows), one
    gather of the rows, one grouped matmul a product over the ``count`` groups, the weights
    given to the activation's rows BEFORE ``w_down`` (it is linear), one gather back and a
    plain sum over ``k``. The first product's output is kept for the backward (``ds_moe_gate_up``:
    a name a recomputed layer around this one may keep too) and NOTHING of the second's: the
    cotangents are gathers, the sorted rows' from ``dy``, the tokens' from the sorted rows of the
    first product's, and the weights' is a row sum of the activation's backward pass. No passes,
    no branch, no scatter; the buffers are ``n * k`` rows, which the passes avoid for a held
    range that may see a sixteenth of them.

    ``stats``: ``load_max_over_mean`` (float32: the busiest expert's assignments over the
    mean, over all chips and all ``num_experts``); in the held-range form also
    ``rows_here`` (float32: the assignments that landed on held experts); with a
    ``sigmoid_bias`` router also ``counts`` (float32 ``[num_experts]``: the step's assignments
    to every expert, over all chips).
    """

    def __init__(self, hidden, ffn_dim, num_experts, top_k, norm_topk_prob=False, held=None,
                 router="softmax", experts=SILU_GATED, stand_in=False):
        self.hidden, self.ffn_dim = hidden, ffn_dim
        self.num_experts, self.top_k = num_experts, top_k
        self.norm_topk_prob = norm_topk_prob
        assert router == "softmax" or (len(router) in (2, 3) and router[0] == "sigmoid_bias"), router
        assert experts in (SILU_GATED, RELU2), experts
        self.scaling = None if router == "softmax" else float(router[1])
        # the sigmoid router's renormalisation adds it to the chosen scores' sum
        self.eps = float(router[2]) if router != "softmax" and len(router) == 3 else 1e-20
        self.form = experts
        self.w_in = "w_up" if experts == RELU2 else "w_gate_up"
        first, count = held or (0, num_experts)
        assert 0 <= first and count >= 1 and first + count <= num_experts, held
        self.held = None if count == num_experts else (first, count)
        assert not stand_in or (self.held is not None and num_experts % count == 0), (held, stand_in)
        self.stand_in = stand_in
        # every assignment is computed here, ``n * k`` rows known before tracing: the layer
        # sorts once and multiplies once; else the held rows' count follows the router
        self.every_row_here = self.held is None or stand_in

    # ------------------------------------------------------------------ params
    def init(self, rng, scale=0.02):
        kr, k1, k2 = jax.random.split(rng, 3)
        H, F, E = self.hidden, self.ffn_dim, self.num_experts
        held = E if self.held is None else self.held[1]
        wide = F if self.form == RELU2 else 2 * F
        params = {
            "router_w": jax.random.normal(kr, (H, E), jnp.float32) * scale,
            # experts stacked on a leading axis, gate and up side by side: three leaves
            self.w_in: jax.random.normal(k1, (held, H, wide), jnp.float32) * scale,
            "w_down": jax.random.normal(k2, (held, F, H), jnp.float32) * scale,
        }
        if self.scaling is not None:
            params["router_bias"] = jnp.zeros((E,), jnp.float32)
        return params

    def expert_specs(self, axis):
        """PartitionSpecs of the leaves: experts over ``axis``, the router whole."""
        specs = {"router_w": P(), self.w_in: P(axis), "w_down": P(axis)}
        return specs if self.scaling is None else dict(specs, router_bias=P())

    # ------------------------------------------------------------------- apply
    def _expert_axis(self, batch):
        """The mesh axis the experts are split over, or None: the context mesh's ``data``
        axis where it is automatic, larger than one, and divides experts and batch."""
        from .mesh import DATA_AXIS
        mesh = jax.sharding.get_abstract_mesh()
        if self.held is not None or mesh.empty or DATA_AXIS not in mesh.auto_axes:
            return None, None
        ep = mesh.shape[DATA_AXIS]
        if ep == 1 or self.num_experts % ep or batch % ep:
            return None, None
        return DATA_AXIS, mesh

    def fetches_kept(self, layers, x):
        """For a model of ``layers`` such layers in a row on inputs like ``x``: whether each keeps
        the experts it fetched (``apply``'s ``keep``), by the module's rule from the room the
        program is traced under. Never without an expert axis: nothing is fetched."""
        axis, mesh = self._expert_axis(x.shape[0])
        if axis is None:
            return False
        ep = mesh.shape[axis]
        one = self.ffn_dim * self.hidden * (3 if self.form == SILU_GATED else 2) * x.dtype.itemsize
        return fetches_kept(layers, (ep - 1) * (self.num_experts // ep) * one, _room())

    def apply(self, params, x, details=False, keep=False):
        """``details`` adds to ``stats`` what a comparison with a reference reads (never
        a step): ``experts``, each token's choices ``[B, T, k]`` in ascending order, and
        ``router_logits`` ``[B, T, E]`` float32. ``keep``: ``fetches_kept`` of the model that
        stacks the layers."""
        axis, mesh = self._expert_axis(x.shape[0])
        # the leaves in a fixed order; the selection bias, where the router has one, last
        leaves = [params["router_w"], params[self.w_in], params["w_down"]]
        if self.scaling is not None:
            leaves.append(params["router_bias"])
        if axis is None:
            return self._local(None, False, details, *leaves[:3], x, *leaves[3:])
        specs = self.expert_specs(axis)
        stats_specs = {"load_max_over_mean": P()}
        if self.scaling is not None:
            stats_specs["counts"] = P()
        if details:
            stats_specs.update(experts=P(axis), router_logits=P(axis))
        fn = jax.shard_map(
            lambda r, gu, d, xl, *b: self._local(axis, keep, details, r, gu, d, xl, *b),
            in_specs=(specs["router_w"], specs[self.w_in], specs["w_down"], P(axis))
            + (P(),) * (len(leaves) - 3),
            out_specs=(P(axis), P(), stats_specs),
            axis_names=frozenset(mesh.auto_axes), check_vma=False)
        return fn(*leaves[:3], x, *leaves[3:])

    def _choose(self, logits, bias):
        """``(weights [n, k], experts [n, k], what ``aux`` sums over the tokens [E])`` from
        the router's float32 logits ``[n, E]``. ``top_k`` only chooses: the chosen values are
        read by ``_chosen``, whose cotangent is a dense select where ``top_k``'s is a scatter."""
        k = self.top_k
        if self.scaling is None:
            probs = jax.nn.softmax(logits, axis=-1)                       # [n, E] f32
            _, experts = jax.lax.top_k(jax.lax.stop_gradient(probs), k)   # [n, k]
            weights = _chosen(probs, experts)
            if self.norm_topk_prob:
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
            return weights, experts, jnp.sum(probs, axis=0)
        scores = jax.nn.sigmoid(logits)
        # the bias chooses and never weighs; no gradient reaches it
        _, experts = jax.lax.top_k(scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
        weights = _chosen(scores, experts)
        if self.norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + self.eps)
        return weights * self.scaling, experts, jnp.zeros(logits.shape[1:], jnp.float32)

    def _local(self, axis, keep, details, router_w, w_gate_up, w_down, x, bias=None):
        """One chip's part: ``x`` its tokens, the expert arrays the experts it owns
        (``w_gate_up``: the first product's, whatever the experts' form); ``keep``: whether
        the backward finds the pieces it fetched still there (``FETCHED``)."""
        H, E, k = self.hidden, self.num_experts, self.top_k
        form = self.form
        shape = x.shape
        x2 = x.reshape(-1, H)
        n = x2.shape[0]
        first, count = self.held or (0, E)

        with jax.named_scope("ds_moe_router"):
            logits = jnp.dot(x2.astype(jnp.float32), router_w.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            weights, experts, prob_sum = self._choose(logits, bias)

        with jax.named_scope("ds_moe_dispatch"):
            slots = jnp.arange(n * k, dtype=jnp.int32)
            sent_to = experts.reshape(-1).astype(jnp.int32)
            if self.stand_in:
                # every row to the held expert that stands in for its own: sorted by that one
                every = jnp.sum(sent_to[:, None] == jnp.arange(E, dtype=jnp.int32), axis=0)
                sent_to = first + (sent_to - first) % count
            if self.every_row_here:
                by_expert, order, inverse, w_sorted = _sort_rows(sent_to, slots, weights)
            else:
                by_expert, order = jax.lax.sort((sent_to, slots), num_keys=1, is_stable=True)
            starts = jnp.searchsorted(by_expert, jnp.arange(E + 1, dtype=jnp.int32))
            group_sizes = jnp.diff(starts).astype(jnp.int32)              # [E]
            tok = order // k
            # on the TPU, where the kernel takes the shapes, the bounds of the combine's runs (else None)
            runs = _run_bounds(n, k, count, H, by_expert - first, tok) if self.every_row_here else None
        # the groups the whole range's products run over: standing in, the held ones hold every row
        sizes = group_sizes[first:first + count] if self.stand_in else group_sizes
        def routed(x2, w_sorted, w_gate_up, w_down):
            dt = x2.dtype
            w_gate_up, w_down = w_gate_up.astype(dt), w_down.astype(dt)
            with jax.named_scope("ds_moe_dispatch"):
                xs = _take_rows(x2, tok, inverse, runs)                   # [n * k, H]
            if axis is None:
                gate_up_pieces, down_pieces, firsts = (w_gate_up,), (w_down,), (None,)
            else:
                if keep:
                    # One barrier for the first product's rows and this chip's ``w_down``. Its
                    # transpose holds their cotangents together: ``w_down``'s gradients are home
                    # when the first product's backward is through, eight kernels to hide under,
                    # and the compiler spreads ``w_gate_up``'s over the next layer's backward.
                    # Alone, once no second fetch stands between them, it leaves all 24 sends to
                    # the end of the program (21 ms a step of waits after the last kernel). Forward,
                    # ``w_down``'s fetch starts with the layer and not, all four layers' at once,
                    # before the first kernel of the step (PERF.md, PR 54: 321.6 ms a step without
                    # it, 309.2 as a tie of the cotangents alone, 297.6 so; the parent 318.7)
                    xs, w_down = jax.lax.optimization_barrier((xs, w_down))
                with jax.named_scope("ds_moe_exchange"):
                    # this chip's own piece is the parameter it holds: the others' have a name
                    fetched = gather_pieces(w_gate_up, axis), gather_pieces(w_down, axis)
                    gate_up_pieces, down_pieces = (
                        pieces[:1] + tuple(checkpoint_name(piece, name) for piece in pieces[1:])
                        for name, pieces in zip(FETCHED, fetched))
                    firsts = piece_firsts(axis, w_down.shape[0])
            with jax.named_scope("ds_moe_experts"):
                gate_up = checkpoint_name(
                    experts_matmul(xs, gate_up_pieces, firsts, sizes), "ds_moe_gate_up")
                ys = experts_matmul(_activate(form, gate_up, dt, w_sorted), down_pieces, firsts, sizes)
            with jax.named_scope("ds_moe_combine"):
                return _sum_rows(ys, tok, inverse, runs)                  # [n, H]

        if self.held is not None:
            # the held range's rows are one run of the sorted order: its start and its length
            lo, rows_here = starts[first], starts[first + count] - starts[first]
        # The backward keeps the first product's output and nothing of the second's. It makes
        # the gathered rows and the weighted activation again (a gather and an elementwise
        # pass). The experts' weights it fetches again unless ``keep``: what a chip fetched for
        # OLMoE's four layers is 2.42 GB (604 MB a layer), kept where the chip has the room.
        if self.every_row_here:
            y = jax.checkpoint(routed, policy=jax.checkpoint_policies.save_only_these_names(
                "ds_moe_gate_up", *(FETCHED if keep else ())))(x2, w_sorted, w_gate_up, w_down)
        else:
            # as many rows as the router sends: in passes, with the held rows up to each held expert
            sort = (tok, order, lo, rows_here, jnp.cumsum(group_sizes[first:first + count]))
            y = _held_rows((k, form), x2, weights, w_gate_up.astype(x2.dtype),
                           w_down.astype(x2.dtype), sort)

        counts = (every if self.stand_in else group_sizes).astype(jnp.float32)
        tokens = n
        if axis is not None:
            counts, prob_sum = jax.lax.psum((counts, prob_sum), axis)
            tokens = n * jax.lax.axis_size(axis)
        aux = E * jnp.sum(jax.lax.stop_gradient(counts) / (tokens * k) * prob_sum / tokens)
        stats = {"load_max_over_mean": jnp.max(counts) / (tokens * k / E)}
        if self.scaling is not None:
            stats["counts"] = counts
        if self.held is not None:
            stats["rows_here"] = rows_here.astype(jnp.float32)
        if details:
            stats["experts"] = jnp.sort(experts, axis=-1).reshape(shape[:-1] + (k,))
            stats["router_logits"] = logits.reshape(shape[:-1] + (E,))
        return y.reshape(shape), aux, stats
