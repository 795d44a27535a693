"""Real-TPU kernel parity smoke: compiled Pallas kernels vs dense XLA oracles.

The unit suite runs the kernels in interpret mode on a virtual CPU platform
(tests/conftest.py); this script validates the COMPILED TPU numerics. Run it on
the chip, alone (one process per chip):

    python tests/tpu_parity.py

Exits non-zero on any parity failure, and when there is no TPU. Tolerances are set
for the TPU's default fp32 matmul precision (bf16-pass dots), not CPU-exact fp32.
``chip_smoke.py`` imports ``flash_parity`` for its own check at GPT-2 XL shapes.
"""

import math
import sys

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

FAILURES = []


def check(name, got, want, tol):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want))) or 1.0
    rel = err / scale
    ok = rel < tol
    print(f"{'PASS' if ok else 'FAIL'} {name}: max_abs_err={err:.3e} rel={rel:.3e} "
          f"(tol {tol})")
    if not ok:
        FAILURES.append(name)
    return rel


def flash_parity(shape, dtype, causal, tol, seed=0):
    """Flash kernel forward and backward against the dense f32 oracle at
    ``shape`` = (B, H, T, D); returns {check name: relative error} and records
    failures like every other check here."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention, dense_attention
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=shape), dtype) for _ in range(3))

    def dense(q, k, v):
        return dense_attention(*(a.astype(jnp.float32) for a in (q, k, v)), causal=causal)

    def sumsq(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    tag = f"{list(shape)} {jnp.dtype(dtype).name} causal={causal}"
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal))(q, k, v)
    errs = {"fwd": check(f"flash fwd {tag}", out, jax.jit(dense)(q, k, v), tol)}
    gf = jax.jit(jax.grad(sumsq(lambda q, k, v: flash_attention(q, k, v, causal)),
                          argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(sumsq(dense), argnums=(0, 1, 2)))(q, k, v)
    for a, b, n in zip(gf, gd, "qkv"):
        errs[f"d{n}"] = check(f"flash d{n} {tag}", a, b, tol)
    return errs


def flash_checks():
    from deepspeed_tpu.ops.pallas.flash_attention import (
        flash_attention, dense_attention, dropout_keep_reference)
    B, H, T, D = 2, 4, 512, 64
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32) for _ in range(3))

    for causal in (False, True):
        flash_parity((B, H, T, D), jnp.float32, causal, 2e-2)

    bias = np.zeros((B, 1, T), np.float32)
    bias[0, :, -100:] = -1e9
    bias = jnp.asarray(bias)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, bias=bias))(q, k, v)
    ref = dense_attention(q, k, v, bias=bias)
    check("flash fwd bias", out, ref, 2e-2)

    rate, seed = 0.1, 77
    keep = dropout_keep_reference(seed, B, H, T, T, rate)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, True, dropout_rate=rate, dropout_seed=seed))(q, k, v)
    ref = dense_attention(q, k, v, causal=True, dropout_keep=keep)
    check("flash fwd dropout", out, ref, 2e-2)
    gf = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, True, dropout_rate=rate, dropout_seed=seed) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(
        dense_attention(q, k, v, causal=True, dropout_keep=keep) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gd, "qkv"):
        check(f"flash d{n} dropout", a, b, 3e-2)


def block_sparse_checks():
    from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig,
                                                    FixedSparsityConfig)
    from deepspeed_tpu.ops.pallas.block_sparse_attention import block_sparse_attention
    from deepspeed_tpu.ops.pallas.flash_attention import dense_attention, DEFAULT_MASK_VALUE
    B, H, T, D = 1, 4, 2048, 64
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32) for _ in range(3))
    for name, cfg in (("fixed", FixedSparsityConfig(num_heads=H, block=128)),
                      ("bigbird", BigBirdSparsityConfig(num_heads=H, block=128))):
        layout = np.asarray(cfg.make_layout(T))
        # the layout is static (LUTs are built at trace time) — close over it
        out = jax.jit(lambda q, k, v, lay=layout, blk=cfg.block: block_sparse_attention(
            q, k, v, lay, block=blk))(q, k, v)
        # dense oracle with the same block mask
        blk = cfg.block
        mask = np.kron(layout, np.ones((blk, blk), np.float32))  # [H, T, T]
        scores = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) / math.sqrt(D)
        scores = np.where(mask[None] > 0, scores, DEFAULT_MASK_VALUE)
        probs = jax.nn.softmax(jnp.asarray(scores), axis=-1)
        ref = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        check(f"block-sparse fwd {name}", out, ref, 2e-2)


def gpt2_sparse_check():
    """The sparse kernel wired INTO the GPT-2 model (GPT2Config.sparse_attention)
    on compiled TPU vs per-layer dense attention masked by the same layout."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.ops.pallas.block_sparse_attention import \
        dense_blocksparse_attention
    from deepspeed_tpu.ops.sparse_attention import BigBirdSparsityConfig

    V, T, E, NH, BLK = 512, 2048, 128, 4, 128
    sc = BigBirdSparsityConfig(num_heads=NH, block=BLK)
    model = GPT2Model(GPT2Config(vocab_size=V, n_positions=T, n_embd=E, n_layer=2,
                                 n_head=NH, compute_dtype=jnp.float32,
                                 sparse_attention=sc))
    params = model.init(jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.default_rng(4).integers(0, V, (1, T)), jnp.int32)
    got = jax.jit(model.logits)(params, toks)

    layout = np.asarray(sc.make_layout(T))
    oracle = GPT2Model(GPT2Config(vocab_size=V, n_positions=T, n_embd=E, n_layer=2,
                                  n_head=NH, compute_dtype=jnp.float32))

    def masked_attention(self, x, p, dropout_rng=None):
        B_, T_, _ = x.shape
        qkv = jnp.dot(x, p["c_attn_w"].astype(x.dtype)) + p["c_attn_b"].astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (a.reshape(B_, T_, NH, E // NH).transpose(0, 2, 1, 3)
                   for a in (q, k, v))
        # the maintained dense-masked oracle (same layout, causal)
        y = dense_blocksparse_attention(q, k, v, layout, BLK, causal=True)
        y = y.transpose(0, 2, 1, 3).reshape(B_, T_, E)
        return jnp.dot(y, p["c_proj_w"].astype(x.dtype)) + p["c_proj_b"].astype(x.dtype)

    oracle._attention = masked_attention.__get__(oracle)
    ref = jax.jit(oracle.logits)(params, toks)
    check("gpt2 sparse_attention logits", got, ref, 2e-2)


def long_context_checks():
    """Chunked long-context flash WITH global-coordinate dropout at T=16384 (past the
    resident kernel's VMEM ceiling) vs the dense oracle."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        flash_attention, dense_attention, dropout_keep_reference)
    B, H, T, D = 1, 1, 16384, 64
    rate, seed = 0.1, 321
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
               for _ in range(3))
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, dropout_rate=rate, dropout_seed=seed))(q, k, v)
    keep = dropout_keep_reference(seed, B, H, T, T, rate)
    ref = jax.jit(lambda q, k, v, keep: dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True, dropout_keep=keep))(q, k, v, keep)
    check("chunked long-context dropout T=16384", out, ref, 3e-2)


def main():
    print(f"backend: {jax.default_backend()}, devices: {jax.devices()}")
    if jax.default_backend() != "tpu":
        sys.exit("no TPU: this script checks the compiled kernels and does not "
                 "fall back to the interpreter")
    flash_checks()
    block_sparse_checks()
    gpt2_sparse_check()
    long_context_checks()
    if FAILURES:
        print(f"\n{len(FAILURES)} parity failures: {FAILURES}")
        sys.exit(1)
    print("\nall TPU parity checks passed")


if __name__ == "__main__":
    main()
