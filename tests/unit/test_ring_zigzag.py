"""Zigzag causal ring attention (PR 2 tentpole).

The zigzag schedule re-shards the sequence so rank i of an n-ring holds global
chunks (i, 2n-1-i): every (rank, rotation) pair contains useful work and the
~2x masked-compute tax of the contiguous causal ring disappears. These tests pin
the contract from the ISSUE's acceptance criteria:

- exact parity (existing ring tolerances) with the dense single-chip oracle AND
  with the masked-schedule ring, forward and gradients, with and without dropout;
- identical ``collective-permute`` count and bytes per step vs the masked ring
  (HLO probe over the shard_map'ped LOCAL ring — the sharded wrapper's layout
  gathers are kept out of the program on purpose);
- the per-rotation work-balance accounting (``ring_work_schedule``) that PERF.md
  reports: zigzag computes 3 + 2(n-1) C x C blocks per rank vs the masked ring's
  3 + 4(n-1), every rotation balanced across ranks;
- the kernel-level segmented operand (global-coordinate causal mask + dropout)
  against a hand-built dense reference.
"""

import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops.pallas.flash_attention import (DEFAULT_MASK_VALUE,
                                                      dense_attention,
                                                      dropout_keep_reference,
                                                      flash_attention_with_lse)
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.parallel.ring_attention import (ring_attention,
                                                   ring_attention_sharded,
                                                   ring_work_schedule,
                                                   zigzag_shard, zigzag_unshard)
from deepspeed_tpu.utils.hlo import (collective_bytes, collective_counts,
                                     optimized_hlo)

# B/H are broadcast dims for every parity check here — keep them minimal so the
# 8-rank interpret-mode ring compiles stay affordable inside the tier-1 budget
B, H, T, D = 1, 2, 256, 32
N_RING = 8


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(data=N_RING, model=1, pipe=1)


def qkv(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, H, T, D), jnp.float32) for k in ks)


# ------------------------------------------------------------------ layout helpers
def test_zigzag_shard_roundtrip():
    x = jnp.arange(2 * 3 * 32 * 4, dtype=jnp.float32).reshape(2, 3, 32, 4)
    for n in (1, 2, 4, 8):
        y = zigzag_shard(x, n, axis=2)
        np.testing.assert_array_equal(np.asarray(zigzag_unshard(y, n, axis=2)),
                                      np.asarray(x))


def test_zigzag_shard_layout():
    """Rank i's slice of the sharded layout is [chunk i, chunk 2n-1-i]."""
    n = 4
    Tl = 32
    c = Tl // (2 * n)
    x = jnp.arange(Tl)[None, None, :, None]
    y = np.asarray(zigzag_shard(x, n, axis=2))[0, 0, :, 0]
    for i in range(n):
        local = y[i * 2 * c:(i + 1) * 2 * c]
        np.testing.assert_array_equal(local[:c], np.arange(i * c, (i + 1) * c))
        j = 2 * n - 1 - i
        np.testing.assert_array_equal(local[c:], np.arange(j * c, (j + 1) * c))


def test_work_schedule_accounting():
    """The analytic per-rotation table: zigzag does 2 balanced units per rotation
    (3 at the diagonal), masked does 4 with rank-dependent usefulness; both cover
    the same useful work; n=8 compute ratio is 31/17 ~ 1.82."""
    for n in (2, 4, 8):
        zz = ring_work_schedule(n, "zigzag")
        mk = ring_work_schedule(n, "masked")
        assert zz["total_computed"] == 3 + 2 * (n - 1)
        assert mk["total_computed"] == 3 + 4 * (n - 1)
        assert zz["total_useful"] == mk["total_useful"]
        # zigzag is balanced: min == max useful on every rotation; no wasted
        # compute anywhere (computed == useful except the half-masked diagonal)
        for row in zz["rotations"]:
            assert row["useful_min"] == row["useful_max"]
            if row["r"] > 0:
                assert row["computed_per_rank"] == row["useful_min"]
        # the masked ring wastes whole visits (useful_min == 0 past the diagonal)
        assert any(row["useful_min"] == 0.0 for row in mk["rotations"][1:])
    r8 = ring_work_schedule(8, "masked")["total_computed"] / \
        ring_work_schedule(8, "zigzag")["total_computed"]
    assert r8 > 1.8


# ------------------------------------------------------------------ kernel: segments
def _dense_segmented(q, k, v, q_pos, k_pos, keep=None):
    """Dense oracle for a segmented call: causal in GLOBAL coordinates."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
    mask = q_pos[:, None] >= k_pos[None, :]
    scores = jnp.where(mask, scores, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1)
    if keep is not None:
        probs = probs * keep
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def test_segmented_causal_kernel_matches_dense():
    """flash_attention_with_lse(q_segments=k_segments=(off0, off1)) applies the
    causal mask in global coordinates: the interleaved [chunk lo, chunk hi]
    layout must equal a dense reference over the same global positions."""
    C, G = 64, 512  # half-chunk and pretend-global lengths
    off0, off1 = 2 * C, 6 * C  # zigzag-style: rank 2 of n=4
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (jax.random.normal(kk, (B, H, 2 * C, D), jnp.float32) for kk in ks)
    pos = jnp.concatenate([off0 + jnp.arange(C), off1 + jnp.arange(C)])

    out, _ = flash_attention_with_lse(q, k, v, causal=True, interpret=True,
                                      q_segments=(off0, off1),
                                      k_segments=(off0, off1))
    ref = _dense_segmented(q, k, v, pos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    # gradients through the segmented mask
    g = jax.random.normal(jax.random.PRNGKey(3), (B, H, 2 * C, D), jnp.float32)
    gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention_with_lse(
        q, k, v, causal=True, interpret=True, q_segments=(off0, off1),
        k_segments=(off0, off1))[0] * g), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(_dense_segmented(q, k, v, pos, pos) * g),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                                   atol=5e-5, err_msg=f"d{name}")


def test_segmented_dropout_hashes_global_coordinates():
    """Segmented dropout must sample exactly the whole-sequence oracle's bits at
    the interleaved global coordinates (the zigzag ring's exactness guarantee)."""
    C = 64
    off0, off1 = C, 5 * C
    rate, seed = 0.25, 77
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q, k, v = (jax.random.normal(kk, (B, H, 2 * C, D), jnp.float32) for kk in ks)
    pos = np.concatenate([off0 + np.arange(C), off1 + np.arange(C)])
    keep_full = dropout_keep_reference(seed, B, H, 8 * C, 8 * C, rate)
    keep = jnp.asarray(np.asarray(keep_full)[:, :, pos][:, :, :, pos])

    out, _ = flash_attention_with_lse(q, k, v, causal=True, interpret=True,
                                      dropout_rate=rate, dropout_seed=seed,
                                      q_segments=(off0, off1),
                                      k_segments=(off0, off1))
    ref = _dense_segmented(q, k, v, jnp.asarray(pos), jnp.asarray(pos), keep=keep)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------ ring parity
# The 8-rank interpret-mode parity tests below are compile-bound (18-56s each).
def test_zigzag_matches_dense_and_masked(mesh):
    """schedule='zigzag' (the default causal path) vs the dense oracle AND the
    schedule='masked' ring, at the existing ring tolerances."""
    q, k, v = qkv(21)
    out_zz = ring_attention_sharded(q, k, v, mesh, causal=True, interpret=True,
                                    schedule="zigzag")      # eagerly: its layout is the caller's
    # the ring it is compared with as ONE compiled program: called eagerly, a ``shard_map`` compiles
    # every primitive of its body as an 8-device program of its own
    out_mk = jax.jit(lambda q, k, v: ring_attention_sharded(q, k, v, mesh, causal=True, interpret=True,
                                                            schedule="masked"))(q, k, v)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_zz), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(out_zz), np.asarray(out_mk), rtol=2e-5,
                               atol=2e-5)
    assert not out_zz.sharding.is_fully_replicated


def test_zigzag_grads_match_dense(mesh):
    q, k, v = qkv(22)
    g = jax.device_put(jax.random.normal(jax.random.PRNGKey(7), (B, H, T, D),
                                         jnp.float32),
                       NamedSharding(mesh, P(None, None, "data", None)))

    def loss_zz(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=True,
                                              interpret=True,
                                              schedule="zigzag") * g)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) * g)

    gz = jax.jit(jax.grad(loss_zz, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gz, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                                   atol=5e-5, err_msg=f"d{name}")


def test_zigzag_dropout_matches_global_oracle(mesh):
    """Attention dropout under the zigzag ring: the interleaved layout hashes
    global coordinates through the segment operand, so the 8-shard zigzag must
    equal dense attention with the whole-sequence oracle mask — fwd and grads."""
    rate, seed = 0.2, 4321
    q, k, v = qkv(23)
    keep = dropout_keep_reference(seed, B, H, T, T, rate)

    def loss_zz(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=True,
                                              interpret=True, dropout_rate=rate,
                                              dropout_seed=seed,
                                              schedule="zigzag") ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True,
                                       dropout_keep=keep) ** 2)

    np.testing.assert_allclose(float(jax.jit(loss_zz)(q, k, v)),
                               float(loss_dense(q, k, v)), rtol=2e-5)
    gz = jax.jit(jax.grad(loss_zz, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gz, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                                   atol=5e-5, err_msg=f"d{name}")


# ------------------------------------------------------------------ collectives
def _local_ring_fn(mesh, schedule):
    spec = P(None, None, "data", None)
    return jax.shard_map(
        functools.partial(ring_attention, axis_name="data", causal=True,
                          interpret=True, schedule=schedule),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)


def test_zigzag_ppermute_count_and_bytes_match_masked(mesh):
    """Acceptance criterion: identical ppermute count AND bytes per step. Both
    schedules rotate the same [B, H, T/n, D] k/v blocks around the same ring —
    the zigzag only changes which half-blocks the flash calls compute. Lower the
    shard_map'ped LOCAL ring (layout conversion excluded — it is a one-off static
    gather outside the step) and compare compiled collectives, fwd and bwd."""
    q = jnp.zeros((1, 1, 128, 16), jnp.float32)
    stats = {}
    for schedule in ("masked", "zigzag"):
        fn = _local_ring_fn(mesh, schedule)
        txt_f = optimized_hlo(jax.jit(fn), q, q, q)
        grad_fn = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(_local_ring_fn(mesh, schedule)(q, k, v) ** 2),
            argnums=(0, 1, 2)))
        txt_b = optimized_hlo(grad_fn, q, q, q)
        stats[schedule] = {
            "fwd_count": collective_counts(txt_f).get("collective-permute", 0),
            "fwd_bytes": collective_bytes(txt_f),
            "bwd_count": collective_counts(txt_b).get("collective-permute", 0),
            "bwd_bytes": collective_bytes(txt_b),
        }
    # the ring must actually ride collective-permute
    assert stats["zigzag"]["fwd_count"] >= N_RING - 1, stats
    assert stats["zigzag"]["bwd_count"] >= N_RING - 1, stats
    assert stats["zigzag"] == stats["masked"], stats


# ------------------------------------------------------------------ engine config
def test_engine_sequence_parallel_config_block(mesh):
    """The ``sequence_parallel`` config block wires the model's sequence-parallel
    loss build into the engine: pass the MODEL OBJECT (not a pre-built model_fn)
    plus the block, and ``engine.model_fn`` becomes the zigzag-ring loss —
    numerically equal to the dense ``model.apply`` on natural-order inputs.
    (Training THROUGH this exact loss build is already exercised by
    test_gpt2_sequence_parallel_trains_through_engine; recompiling a second
    fused engine step here would double tier-1's slowest compile for no new
    coverage, so this test stops at the wiring + loss parity.)"""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32, n_layer=1, n_head=2,
                     compute_dtype=jnp.float32)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    engine = DeepSpeedEngine(
        model=model, model_parameters=params, mesh=mesh,
        config_params={"train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
                       "gradient_accumulation_steps": 1, "steps_per_print": 100,
                       "sequence_parallel": {"enabled": True, "schedule": "zigzag"},
                       "optimizer": {"type": "Adam", "params": {"lr": 3e-3}}})
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, 64, size=(2, 64)).astype(np.int32))
    labels = jnp.roll(toks, -1, axis=1)
    # the block must have swapped model_fn for the RING loss: its program rides
    # collective-permute (plain model.apply has no collectives at all), while
    # the loss value still equals the dense model on natural-order inputs
    lowered = jax.jit(engine.model_fn).lower(params, toks, labels)
    assert "collective_permute" in lowered.as_text()  # stablehlo spelling
    l_sp = float(lowered.compile()(params, toks, labels))
    l_ref = float(model.apply(params, toks, labels))
    np.testing.assert_allclose(l_sp, l_ref, rtol=2e-5)


def test_engine_sequence_parallel_requires_capable_model():
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    with pytest.raises(TypeError, match="sequence_parallel"):
        DeepSpeedEngine(
            model=lambda p, x: jnp.sum(p * x), model_parameters=jnp.ones((4,)),
            config_params={"train_batch_size": 8,
                           "sequence_parallel": {"enabled": True},
                           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
