"""The state-space scan's Pallas kernels (``ops/pallas/ssd.py``), interpreted: at the
benchmark's head widths against the reference's recurrence, the bfloat16 call against the
float32 call on the same values, a head that forgets slowly and one that forgets within a
token against a float64 recurrence, and what the gradient's program holds and under which
scopes. ``test_ssd.py`` has the scan at toy widths and lengths the tile does not divide."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite_hybrid_reference as ref
from benchmarks.reference import nemotron_h_reference as grouped_ref
from deepspeed_tpu.ops import ssd
from deepspeed_tpu.ops.pallas import ssd as kernels
from deepspeed_tpu.ops.ssd import ssd_scan
from test_delta_rule_kernel import _calls, rel

ARGNUMS = tuple(range(6))
NAMES = ("x", "dt", "A", "B", "C", "D")


def inputs(T, H=4, P=64, N=128, seed=0, rates=None, dtype=jnp.float32, groups=None):
    """x, B, C holding bfloat16 values (as the convolution leaves them), dt log-uniform in
    [0.001, 0.1], A from slow to fast, D, and a cotangent of bfloat16 values. ``groups``: B and
    C ``[1, T, groups, N]``, one for each group of heads."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    low = lambda key, *shape: jax.nn.silu(jax.random.normal(key, shape)).astype(jnp.bfloat16).astype(dtype)  # noqa: E731
    dt = jnp.exp(jax.random.uniform(ks[1], (1, T, H), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    A = -jnp.asarray(rates if rates is not None else np.geomspace(1.0, 64.0, H), jnp.float32)
    bc = (1, T, N) if groups is None else (1, T, groups, N)
    return ((low(ks[0], 1, T, H, P), dt, A, low(ks[2], *bc), low(ks[3], *bc),
             1.0 + 0.1 * jax.random.normal(ks[4], (H,))), low(ks[5], 1, T, H, P))


def grads(fn, args, cot):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot.astype(jnp.float32)),
                    argnums=ARGNUMS)(*args)


@pytest.mark.parametrize("T, heads, H, groups", [(100, 8, 4, None), (300, 2, 4, None), (300, 8, 4, 2),
                                                 (150, 2, 16, 8), (140, 1, 4, 2)],
                         ids=["under-a-tile", "over-a-tile-two-groups", "two-B-C-groups",
                              "eight-B-C-groups", "two-steps-a-B-C-group"])
def test_the_kernels_at_the_cells_head_widths_are_the_recurrence(T, heads, H, groups, monkeypatch):
    """Heads of 64 with a state of 128, two sharing a register's lanes; one group of four heads
    a grid step, then two groups of two over three tiles: forward and all six gradients. Then
    heads that come in groups with a B and C each (Nemotron-H's eight): a grid step one
    group's heads, and two grid steps a group (``HEADS`` 1 divides a group's two)."""
    monkeypatch.setattr(ssd, "HEADS", heads)
    args, cot = inputs(T, H=H, seed=T, groups=groups)
    recurrence = ref.ssm_recurrent if groups is None else grouped_ref.ssm_recurrent
    with jax.default_matmul_precision("highest"):
        want, want_grads = recurrence(*args), grads(recurrence, args, cot)
    got = ssd_scan(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert rel(got, want) < 2e-6
    for name, g, w in zip(NAMES, grads(ssd_scan, args, cot), want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert rel(g, w) < 1e-5, name


def test_one_group_of_b_and_c_is_the_ungrouped_call_bit_for_bit():
    args, cot = inputs(200, seed=9)
    one = tuple(a[:, :, None] if i in (3, 4) else a for i, a in enumerate(args))
    assert np.array_equal(ssd_scan(*args), ssd_scan(*one))
    for name, g, w in zip(NAMES, grads(ssd_scan, one, cot), grads(ssd_scan, args, cot)):
        assert np.array_equal(np.asarray(g).reshape(w.shape), w), name


def test_the_bfloat16_call_is_the_float32_call_on_the_same_values():
    """The step hands the scan bfloat16 arrays, the benchmark's check float32 arrays that hold
    bfloat16 values: one kernel, whose further terms are then exactly zero. What differs is
    what the bfloat16 call rounds once for a product (the decay matrix, the state ``C`` reads,
    the scaled x) and its output."""
    wide, cot = inputs(200, seed=5)
    narrow = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a for i, a in enumerate(wide))
    y_wide, y_narrow = ssd_scan(*wide), ssd_scan(*narrow)
    assert y_narrow.dtype == jnp.bfloat16 and y_wide.dtype == jnp.float32
    assert rel(y_narrow.astype(jnp.float32), y_wide) < 6e-3
    g_wide, g_narrow = grads(ssd_scan, wide, cot), grads(ssd_scan, narrow, cot.astype(jnp.bfloat16))
    for name, w, n in zip(NAMES, g_wide, g_narrow):
        assert n.dtype == (jnp.bfloat16 if name in "xBC" else jnp.float32), name
        assert rel(n.astype(jnp.float32), w) < 1e-2, name


def recurrence64(x, dt, A, B, C, D):
    """The recurrence a token at a time in float64, one row; ``B``, ``C`` ``[1, T, N]`` or, a
    group of heads each, ``[1, T, G, N]``."""
    x, dt, A, B, C, D = (np.asarray(a, np.float64) for a in (x, dt, A, B, C, D))
    if B.ndim == 4:          # every head its group's
        B, C = (np.repeat(a, x.shape[2] // a.shape[2], axis=2) for a in (B, C))
    else:
        B, C = (np.repeat(a[:, :, None], x.shape[2], axis=2) for a in (B, C))
    S = np.zeros((x.shape[2], x.shape[3], B.shape[-1]))
    y = np.zeros(x.shape[1:])
    for t in range(x.shape[1]):
        S = np.exp(dt[0, t] * A)[:, None, None] * S + (dt[0, t, :, None] * x[0, t])[:, :, None] * B[0, t][:, None, :]
        y[t] = np.einsum("hpn,hn->hp", S, C[0, t]) + D[:, None] * x[0, t]
    return y


@pytest.mark.parametrize("groups", [None, 2], ids=["one-B-C", "a-B-C-a-head"])
def test_a_head_that_forgets_slowly_keeps_its_state_over_two_thousand_tokens(groups):
    """``A dt`` = 1e-3 a token: the state carries a thousand tokens of memory through sixteen
    tiles nearly whole. Against the recurrence in float64 (with groups too: the grouped scan's
    limit in the benchmark is under 1e-4, so it is checked against float64 once, here)."""
    (x, dt, A, B, C, D), _ = inputs(2048, H=2, P=8, N=16, rates=[1.0, 40.0], groups=groups)
    dt = dt.at[:, :, 0].set(1e-3)
    got = np.asarray(ssd_scan(x, dt, A, B, C, D), np.float64)[0]
    want = recurrence64(x, dt, A, B, C, D)
    assert rel(got, want) < 2e-6
    assert rel(got[-256:, 0], want[-256:, 0]) < 2e-6          # the slow head, at the end
    assert np.abs(want[-256:, 0] - np.asarray(D)[0] * np.asarray(x, np.float64)[0, -256:, 0]).max() > 0.1


def test_a_head_that_forgets_within_a_token_rounds_at_the_segments_own_size():
    """``A dt`` = 6.4 a token, the last step 1e-3: a tile's whole decay is 820 and the
    difference of two float32 cumulative sums would carry its rounding (3e-5) into the last
    token's decay, which matters: the state before it passes nearly whole."""
    (x, dt, A, B, C, D), _ = inputs(256, H=2, P=8, N=16, rates=[64.0, 64.0])
    dt = jnp.full_like(dt, 0.1).at[:, 127].set(1e-3 / 64).at[:, 255].set(1e-3 / 64)
    got = np.asarray(ssd_scan(x, dt, A, B, C, D), np.float64)[0]
    want = recurrence64(x, dt, A, B, C, D)
    assert rel(got, want) < 1e-6
    for t in (127, 255):                       # where the previous token's state passes whole
        through = want[t] - np.asarray(D)[:, None] * np.asarray(x, np.float64)[0, t]
        assert np.abs(through).max() > 1e-3 and rel(got[t], want[t]) < 1e-6


def test_the_gradients_program_is_the_two_kernels_and_no_other_form_of_the_scan():
    args, cot = inputs(130, P=8, N=16)
    jaxpr = jax.make_jaxpr(lambda *a: grads(lambda *b: ssd_scan(*b, 64), a, cot))(*args)
    found = _calls(jaxpr.jaxpr, {"kernels": [], "others": set()})
    assert sorted(name for name, _ in found["kernels"]) == ["ds_ssd_scan_bwd", "ds_ssd_scan_fwd"]
    # what is left outside the kernels lays operands out and sums a head's cotangents: no
    # product, no loop, no scan, no decay
    assert not found["others"] & {"dot_general", "scan", "while", "cond", "exp", "cumsum"}, found["others"]
    for gone in ("HEADS_AT_ONCE", "segment_sums", "_within_chunks", "_mm"):
        assert not hasattr(ssd, gone), gone


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "under-checkpoint"])
def test_both_kernels_run_under_the_mixers_scopes(remat):
    """``benchmarks/ssm_spans.py`` counts an operation under ``ds_ssm`` and ``ds_ssd_scan`` by
    its scope path, and a recomputed forward by JAX's ``rematted_computation``: the backward
    kernel, which a transpose traces, has to carry both scopes as the forward does."""
    from test_granite_hybrid import build
    _, model, params = build()
    mp = params["layers"][0]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 32))
    loss = lambda x, mp: jnp.sum(model.mamba_mixer(x, mp) ** 2)      # noqa: E731
    loss = jax.checkpoint(loss) if remat else loss
    found = _calls(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, mp).jaxpr, {"kernels": [], "others": set()})
    names = [name for name, _ in found["kernels"]]
    assert names.count("ds_ssd_scan_bwd") == 1 and names.count("ds_ssd_scan_fwd") == 1 + remat
    for name, path in found["kernels"]:
        assert "ds_ssm" in path and "ds_ssd_scan/" in path and path.endswith(name), (name, path)
    if remat:
        again = [path for name, path in found["kernels"] if "rematted_computation" in path]
        assert len(again) == 1 and again[0].endswith("ds_ssd_scan_fwd")


def test_the_compiled_kernels_refuse_widths_the_lanes_do_not_divide():
    args, _ = inputs(64, P=8, N=16)
    with pytest.raises(AssertionError, match="whole registers of 128 lanes"):
        jax.eval_shape(lambda *a: ssd_scan(*a, interpret=False), *args)
    assert kernels.heads_together(8, 64) == 2 and kernels.heads_together(8, 128) == 1
    assert kernels.heads_together(3, 64) == 1 and kernels.heads_together(4, 8) == 1
