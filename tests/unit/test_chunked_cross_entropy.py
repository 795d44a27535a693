"""The head's fused cross-entropy (``models/layers.chunked_cross_entropy``) and its own
backward against ``jax.grad`` of the plain full-logits float32 cross-entropy: value, the
hidden states' gradient and the table's; the compiled programs' products and collectives."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.models import layers
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.utils import hlo

B, T, H, V = 4, 48, 32, 160
NARROW_SLACK = 1.5          # of the old function's error in a narrow dtype


@pytest.fixture
def tile_of(monkeypatch):
    """Set the byte budget so that a tile takes ``positions`` of ``rows`` rows."""
    def set_budget(positions, rows=B):
        monkeypatch.setattr(layers, "LOGITS_TILE_BYTES", rows * positions * V * 4)
    return set_budget


def plain(x, head, labels):
    """Full logits, float32 throughout, differentiated by JAX."""
    logits = jnp.einsum("bth,vh->btv", x.astype(jnp.float32), head.astype(jnp.float32),
                        precision="highest")
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = labels >= 0
    ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, ll, 0.0)) / jnp.maximum(jnp.sum(valid), 1)


def rematted_scan(x, head, labels, chunk):
    """What the function was until PR 29: a rematted scan over chunks that JAX
    differentiates. Kept here as the yardstick of the narrow dtypes' tolerance."""
    n = x.shape[1] // chunk
    xs = x.reshape(x.shape[0], n, chunk, -1).swapaxes(0, 1)
    ls = labels.reshape(x.shape[0], n, chunk).swapaxes(0, 1)
    w = head.astype(x.dtype)

    def body(tot, xc_lc):
        xc, lc = xc_lc
        logits = jnp.einsum("bch,vh->bcv", xc, w, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = (lc >= 0).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        return (tot[0] + jnp.sum((lse - gold) * valid), tot[1] + jnp.sum(valid)), None

    zero = jnp.zeros((), jnp.float32)
    (total, count), _ = jax.lax.scan(jax.checkpoint(body), (zero, zero), (xs, ls))
    return total / jnp.maximum(count, 1.0)


def inputs(dtype, labels="all", seed=0, t=T):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, t, H)), dtype)
    head = jnp.asarray(rng.normal(size=(V, H)) * 0.3, dtype)
    lab = rng.integers(0, V, (B, t))
    if labels == "some":
        lab[:, -1] = -100
        lab[1, 3:11] = -100
    elif labels == "none":
        lab[:] = -100
    return x, head, jnp.asarray(lab, jnp.int32)


def value_and_grads(loss, x, head, labels, tied=False, cotangent=1.0):
    """``(value, d_x, d_head)``; ``tied`` feeds the table into the hidden states too, as
    an embedding does, so that its gradient arrives twice."""
    def fn(x, head):
        hidden = x + head[jnp.maximum(labels, 0) % 7].astype(x.dtype) if tied else x
        return loss(hidden, head, labels) * cotangent
    value, grads = jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))(x, head)
    return (value, *grads)


def rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


CASES = {
    "float32": dict(),
    "float32-some-labels-ignored": dict(labels="some"),
    "float32-every-label-ignored": dict(labels="none"),
    "float32-tied-table": dict(tied=True, labels="some"),
    "float32-one-tile": dict(positions=T),
    "float32-budget-does-not-divide-T": dict(positions=20),      # 3 tiles of 16
    "float32-T-is-prime": dict(positions=16, t=47, labels="some"),   # padded to 48
    "bfloat16": dict(dtype=jnp.bfloat16),
    "bfloat16-tied-some-ignored": dict(dtype=jnp.bfloat16, tied=True, labels="some"),
    "float16": dict(dtype=jnp.float16, labels="some"),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_value_and_both_gradients_match_the_plain_float32_loss(case, tile_of):
    spec = dict(dict(dtype=jnp.float32, labels="all", tied=False, positions=16, t=T), **CASES[case])
    tile_of(spec["positions"])
    x, head, labels = inputs(spec["dtype"], spec["labels"], t=spec["t"])
    want = value_and_grads(plain, x.astype(jnp.float32), head.astype(jnp.float32), labels,
                           spec["tied"])
    got = value_and_grads(layers.chunked_cross_entropy, x, head, labels, spec["tied"])
    assert got[1].dtype == x.dtype and got[2].dtype == head.dtype
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in got)
    if spec["labels"] == "none":
        assert float(got[0]) == 0.0
        assert not np.asarray(got[1], np.float32).any() and not np.asarray(got[2], np.float32).any()
        return
    if spec["dtype"] == jnp.float32:
        limits = (1e-6, 1e-5, 1e-5)
    else:
        # the narrow dtype's own error, measured: what JAX's derivative of the rematted
        # scan gives on the same inputs, with half as much again to spare (here the old
        # products take the tile's float32 gradient as it is; on the chip they round it
        # to bfloat16, coarser than the float16 this one rounds it to)
        old = value_and_grads(lambda *a: rematted_scan(*a, 16), x, head, labels, spec["tied"])
        limits = tuple(NARROW_SLACK * rel(o, w) + 1e-6 for o, w in zip(old, want))
    for name, g, w, limit in zip(("value", "d_x", "d_head"), got, want, limits):
        assert rel(g, w) <= limit, (name, rel(g, w), limit)


def test_a_loss_scale_in_the_cotangent_underflows_nothing_in_float16(tile_of):
    """fp16 with a loss scale: the scale arrives in the cotangent and multiplies float32
    sums; the tile's gradient is rounded unscaled. No element that JAX's derivative of the
    rematted scan gave is lost, and none overflows."""
    tile_of(16)
    x, head, labels = inputs(jnp.float16, "some")
    scale = 2.0 ** 16
    _, dx, dh = value_and_grads(layers.chunked_cross_entropy, x, head, labels, cotangent=scale)
    _, dx_old, dh_old = value_and_grads(lambda *a: rematted_scan(*a, 16), x, head, labels,
                                        cotangent=scale)
    _, dx_ref, dh_ref = value_and_grads(plain, x.astype(jnp.float32), head.astype(jnp.float32),
                                        labels, cotangent=scale)
    for got, old, ref in ((dx, dx_old, dx_ref), (dh, dh_old, dh_ref)):
        got, old = np.asarray(got, np.float32), np.asarray(old, np.float32)
        assert np.isfinite(got).all()
        assert not ((got == 0) & (old != 0)).any()
        assert rel(got, ref) <= NARROW_SLACK * rel(old, ref) + 1e-6


def products(text):
    """The result dims of every product of a compiled program."""
    found = []
    for line in hlo.instructions(text):
        m = re.search(r"= \w+\[([0-9,]*)\]\S* dot\(", line)
        if m:
            found.append(tuple(int(d) for d in m.group(1).split(",")))
    return found


def test_the_primal_makes_no_gradient(tile_of):
    tile_of(16)
    x, head, labels = inputs(jnp.float32)
    primal = jax.jit(layers.chunked_cross_entropy)
    assert products(hlo.optimized_hlo(primal, x, head, labels)) == [(B * 16, V)]
    assert rel(primal(x, head, labels), plain(x, head, labels)) < 1e-6


def test_one_product_a_tile_and_two_whole_ones_in_the_backward(tile_of):
    """The compiled grad program: the scan's body holds the logits' product, once; the
    backward rule holds the hidden states' gradient and the table's, each one product over
    every position. Three products against the table, none a second ``[tile, V]`` (JAX's
    derivative of the rematted scan holds four: it makes every tile's logits twice)."""
    tile_of(16)
    x, head, labels = inputs(jnp.float32)

    def grads(loss):
        # with the value, as a step takes it: alone, the old forward scan is dead code
        return jax.jit(jax.value_and_grad(lambda x, h: loss(x, h, labels), argnums=(0, 1)))

    new = products(hlo.optimized_hlo(grads(layers.chunked_cross_entropy), x, head))
    assert sorted(new) == sorted([(B * 16, V), (B * T, H), (V, H)])
    old = products(hlo.optimized_hlo(grads(lambda *a: rematted_scan(*a, 16)), x, head))
    assert sorted(old) == sorted([(B * 16, V)] * 2 + [(B * 16, H), (V, H)])


def test_under_a_mesh_the_batch_is_split_and_the_sums_cross_the_chips_once(tile_of):
    """Four devices, the batch split over ``data`` as the engine splits it: a tile is a
    chip's rows, the scan's body holds no collective, the table's gradient is reduced once
    and so is the loss's sum, after the scan, and nothing is gathered."""
    rows = 2
    tile_of(16, rows)
    mesh = build_mesh(data=4, devices=jax.devices()[:4])
    split, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4 * rows, T, H)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(V, H)) * 0.3, jnp.float32)
    labels = rng.integers(0, V, (4 * rows, T))
    labels[0, :9] = -100
    labels = jnp.asarray(labels, jnp.int32)

    def grad(x, head, labels):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jax.value_and_grad(
                lambda x, h: layers.chunked_cross_entropy(x, h, labels), argnums=(0, 1))(x, head)

    jitted = jax.jit(grad, out_shardings=(whole, (split, whole)))
    args = (jax.device_put(x, split), jax.device_put(head, whole), jax.device_put(labels, split))
    text = hlo.optimized_hlo(jitted, *args)
    counts = hlo.collective_counts(text)
    assert set(counts) == {"all-reduce"}                      # no all-gather, no permute
    reduced = [dims for _, _, dims in hlo.collective_results(text, "all-reduce")]
    assert reduced.count((V, H)) == 1 and all(d in ((), (V, H)) for d in reduced)
    # a chip's tile is its own two rows, 16 positions; its backward takes all its positions
    assert sorted(products(text)) == sorted([(rows * 16, V), (rows * T, H), (V, H)])
    # the computation that holds the tile's product is the scan's body: no collective in it
    bodies = [c for c in text.split("\n\n") if re.search(rf"\[{rows * 16},{V}\]\S* dot\(", c)]
    assert len(bodies) == 1 and not hlo.collective_counts(bodies[0])
    # the function names its own scope, forward and backward: every reduction is under it
    assert all("ds_loss" in line for line in text.splitlines() if " all-reduce(" in line)
    value, (dx, dh) = jitted(*args)
    want = value_and_grads(plain, x, head, labels)
    for g, w in zip((value, dx, dh), want):
        assert rel(g, w) < 1e-5
