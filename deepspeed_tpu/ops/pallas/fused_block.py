"""Fused transformer-block attention half (LN + qkv + attention + residual).

Pallas counterpart of the reference's fused CUDA transformer op
(``csrc/transformer/transform_kernels.cu`` + the fused softmax path): one kernel
computes ``x + proj(attn(qkv(layernorm(x))))`` per q-tile, so the normalized
hidden states, the qkv activations, the [T, T] score matrix and the pre-residual
attention output never round-trip through HBM. This path is HBM-bound: at
GPT-2 shapes the unfused forward writes ~7 intermediate [B, T, E]-class tensors
per block; the fused kernel writes one.

Design:
- grid ``(B, T // block_q)``; the second dimension is sequential, so the kernel
  primes whole-row K and V into VMEM scratch once per batch row (at q-block 0:
  full-row LN + the k/v thirds of the fused qkv matmul) and every q-tile
  iteration reads them back from VMEM — the sequential-grid analog of flash
  attention's streamed k/v, with the projection fused in front.
- per-head attention runs over the resident K/V with an fp32 softmax; the
  [block_q, T] score tile lives only in registers/VMEM.
- the whole block's weights (w_qkv [E, 3E], w_proj [E, E]) are VMEM-resident
  beside the two [T, E] K/V scratch buffers. Compiled for a v5e (PR 21) that is
  too much: the chip's compiler refuses the kernel with VMEM exhausted at
  [2,1024,768]/12 heads, [2,1024,1024]/16 heads and [2,1024,1600]/25 heads, so it
  has run only in the interpreter. ROADMAP C6: re-tile or delete.
- backward: ``custom_vjp`` whose bwd differentiates the pure-jnp reference
  (``fused_block_reference``) at the saved primals — fused forward, XLA
  backward. Gradients are exactly the reference's; the forward values differ
  from the reference only by kernel rounding (one fewer dtype round-trip).
- ``interpret=True`` (auto on CPU) keeps the parity tests honest off-TPU.

Constraints: no attention dropout (route ``config.dropout > 0`` through the
unfused path), self-attention only, E divisible by n_head, T divisible by the
resolved block_q. On real TPUs prefer E a multiple of 128 (lane alignment).
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASK_VALUE = -1e9  # matches the model's dense causal mask (python scalar:
# a jnp constant would be captured by the kernel closure, which pallas rejects)


def fused_block_reference(x, ln_scale, ln_bias, w_qkv, b_qkv, w_proj, b_proj,
                          n_head: int, causal: bool = True,
                          sm_scale: Optional[float] = None, eps: float = 1e-5):
    """Pure-jnp oracle, mirroring ``GPT2Model._layer_norm`` + ``_attention``'s
    dense path + the residual add (models/gpt2.py). Differentiable; the fused
    kernel's custom_vjp backward runs ``jax.vjp`` of this function."""
    B, T, E = x.shape
    D = E // n_head
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    h = ((xf - mean) * jax.lax.rsqrt(var + eps)
         * ln_scale + ln_bias).astype(x.dtype)
    qkv = (jnp.dot(h, w_qkv.astype(x.dtype), preferred_element_type=jnp.float32)
           .astype(x.dtype) + b_qkv.astype(x.dtype))
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, n_head, D).transpose(0, 2, 1, 3)
    k = k.reshape(B, T, n_head, D).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, n_head, D).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), jnp.bool_))
        s = jnp.where(mask, s, _MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    y = jnp.einsum("bhqk,bhkd->bhqd", p, v,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    y = y.transpose(0, 2, 1, 3).reshape(B, T, E)
    out = jnp.dot(y, w_proj.astype(x.dtype), preferred_element_type=jnp.float32)
    return x + (out.astype(x.dtype) + b_proj.astype(x.dtype))


def _fused_block_kernel(x_full_ref, x_tile_ref, scale_ref, bias_ref, wqkv_ref,
                        bqkv_ref, wproj_ref, bproj_ref, o_ref, k_s, v_s, *,
                        n_head, sm_scale, eps, causal, block_q):
    E = x_tile_ref.shape[-1]
    D = E // n_head
    T = x_full_ref.shape[0]
    qb = pl.program_id(1)

    def ln(xf):  # fp32 in, fp32 out
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        return ((xf - mean) * jax.lax.rsqrt(var + eps)
                * scale_ref[0, :] + bias_ref[0, :])

    # prime whole-row K/V once per batch row: the grid's second dimension is
    # sequential, so the scratch persists across this row's q-tiles
    @pl.when(qb == 0)
    def _prime_kv():
        h = ln(x_full_ref[...].astype(jnp.float32)).astype(x_full_ref.dtype)
        k_s[...] = (jnp.dot(h, wqkv_ref[:, E:2 * E],
                            preferred_element_type=jnp.float32)
                    + bqkv_ref[0, E:2 * E]).astype(k_s.dtype)
        v_s[...] = (jnp.dot(h, wqkv_ref[:, 2 * E:],
                            preferred_element_type=jnp.float32)
                    + bqkv_ref[0, 2 * E:]).astype(v_s.dtype)

    xt = x_tile_ref[...]                                        # [bq, E]
    hq = ln(xt.astype(jnp.float32)).astype(xt.dtype)
    q_all = (jnp.dot(hq, wqkv_ref[:, :E], preferred_element_type=jnp.float32)
             + bqkv_ref[0, :E]).astype(xt.dtype)                # [bq, E]

    if causal:
        q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, T), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (block_q, T), 1)
        keep = q_pos >= k_pos
    heads = []
    for hd in range(n_head):
        qh = q_all[:, hd * D:(hd + 1) * D]
        kh = k_s[:, hd * D:(hd + 1) * D]
        vh = v_s[:, hd * D:(hd + 1) * D]
        s = jnp.dot(qh, kh.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = jnp.where(keep, s, _MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1).astype(vh.dtype)
        heads.append(jnp.dot(p, vh, preferred_element_type=jnp.float32)
                     .astype(xt.dtype))
    y = jnp.concatenate(heads, axis=-1)                         # [bq, E]
    out = jnp.dot(y, wproj_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = (xt.astype(jnp.float32) + out.astype(jnp.float32)
                  + bproj_ref[0, :]).astype(o_ref.dtype)


def _fused_block_impl(x, ln_scale, ln_bias, w_qkv, b_qkv, w_proj, b_proj,
                      n_head, causal, sm_scale, eps, block_q, interpret):
    B, T, E = x.shape
    grid = (B, T // block_q)
    kernel = functools.partial(_fused_block_kernel, n_head=n_head,
                               sm_scale=sm_scale, eps=eps, causal=causal,
                               block_q=block_q)
    dt = x.dtype
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, T, E), lambda b, i: (b, 0, 0)),        # full row
            pl.BlockSpec((None, block_q, E), lambda b, i: (b, i, 0)),  # q tile
            pl.BlockSpec((1, E), lambda b, i: (0, 0)),
            pl.BlockSpec((1, E), lambda b, i: (0, 0)),
            pl.BlockSpec((E, 3 * E), lambda b, i: (0, 0)),
            pl.BlockSpec((1, 3 * E), lambda b, i: (0, 0)),
            pl.BlockSpec((E, E), lambda b, i: (0, 0)),
            pl.BlockSpec((1, E), lambda b, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, E), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, E), dt),
        scratch_shapes=[pltpu.VMEM((T, E), dt), pltpu.VMEM((T, E), dt)],
        interpret=interpret,
    )(x, x,
      jnp.asarray(ln_scale, jnp.float32).reshape(1, E),
      jnp.asarray(ln_bias, jnp.float32).reshape(1, E),
      w_qkv.astype(dt), jnp.asarray(b_qkv, jnp.float32).reshape(1, 3 * E),
      w_proj.astype(dt), jnp.asarray(b_proj, jnp.float32).reshape(1, E))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _fused_block_core(x, ln_scale, ln_bias, w_qkv, b_qkv, w_proj, b_proj,
                      n_head, causal, sm_scale, eps, block_q, interpret):
    return _fused_block_impl(x, ln_scale, ln_bias, w_qkv, b_qkv, w_proj, b_proj,
                             n_head, causal, sm_scale, eps, block_q, interpret)


def _core_fwd(x, ln_scale, ln_bias, w_qkv, b_qkv, w_proj, b_proj,
              n_head, causal, sm_scale, eps, block_q, interpret):
    out = _fused_block_impl(x, ln_scale, ln_bias, w_qkv, b_qkv, w_proj, b_proj,
                            n_head, causal, sm_scale, eps, block_q, interpret)
    return out, (x, ln_scale, ln_bias, w_qkv, b_qkv, w_proj, b_proj)


def _core_bwd(n_head, causal, sm_scale, eps, block_q, interpret, res, g):
    # fused forward, reference backward: differentiate the jnp oracle at the
    # saved primals — XLA fuses this fine, and the gradients are exactly the
    # unfused block's (the kernel only reorders forward rounding)
    ref = functools.partial(fused_block_reference, n_head=n_head, causal=causal,
                            sm_scale=sm_scale, eps=eps)
    _, vjp = jax.vjp(ref, *res)
    return vjp(g)


_fused_block_core.defvjp(_core_fwd, _core_bwd)


def fused_transformer_block(x, ln_scale, ln_bias, w_qkv, b_qkv, w_proj, b_proj,
                            n_head: int, causal: bool = True,
                            sm_scale: Optional[float] = None, eps: float = 1e-5,
                            block_q: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """``x + proj(attention(qkv(layernorm(x))))`` in one Pallas kernel.

    Inputs: ``x`` [B, T, E]; ``ln_scale``/``ln_bias`` [E]; ``w_qkv`` [E, 3E]
    (fused ``[q | k | v]`` layout, the GPT-2 ``c_attn_w``); ``b_qkv`` [3E];
    ``w_proj`` [E, E]; ``b_proj`` [E]. Differentiable in all array arguments
    (see module docstring for the fused-fwd/reference-bwd contract). No
    attention dropout — keep such configs on the unfused path.
    """
    B, T, E = x.shape
    assert E % n_head == 0, f"n_embd {E} must divide by n_head {n_head}"
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(E // n_head)
    if block_q is None:
        block_q = 256
    # largest power-of-two reduction of block_q that divides T
    block_q = min(block_q, T)
    while T % block_q != 0:
        block_q //= 2
    block_q = max(block_q, 1)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _fused_block_core(x, ln_scale, ln_bias, w_qkv, b_qkv, w_proj, b_proj,
                             int(n_head), bool(causal), float(sm_scale),
                             float(eps), int(block_q), bool(interpret))
