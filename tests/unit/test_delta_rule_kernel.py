"""The delta rule's Pallas kernels (``ops/pallas/delta_rule.py``), interpreted: at the
benchmark's head widths against the reference's recurrence, the bfloat16 call against the
float32 call on the same values, a head that forgets slowly against a float64 recurrence, and
what the gradient's program holds and under which scopes. ``test_qwen3_next.py`` has the rule
at toy widths, lengths the chunk does not divide, and the run of equal keys."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next_reference as ref
from deepspeed_tpu.ops import delta_rule
from deepspeed_tpu.ops.delta_rule import gated_delta_rule
from deepspeed_tpu.ops.pallas import delta_rule as kernels

ARGNUMS = (0, 1, 2, 3, 4)


def inputs(T, Hk=2, Hv=4, Dk=128, Dv=128, seed=0, rates=None, dtype=jnp.float32):
    """q, k, v holding bfloat16 values (as the convolution leaves them), g, beta, and a
    cotangent of bfloat16 values."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    if rates is None:
        rates = jax.random.uniform(ks[6], (Hv,), minval=0.001, maxval=16.0)
    g = -jnp.asarray(rates) * jax.nn.softplus(jax.random.normal(ks[3], (1, T, Hv)) + 1)
    low = lambda key, *shape: jax.nn.silu(jax.random.normal(key, shape)).astype(jnp.bfloat16).astype(dtype)  # noqa: E731
    return ((low(ks[0], 1, T, Hk, Dk), low(ks[1], 1, T, Hk, Dk), low(ks[2], 1, T, Hv, Dv), g,
             jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, Hv)))), low(ks[5], 1, T, Hv, Dv))


def recurrence(q, k, v, g, beta):
    r = v.shape[2] // k.shape[2]
    return ref.delta_rule_recurrent(jnp.repeat(ref.unit_scaled(q, True), r, axis=2),
                                    jnp.repeat(ref.unit_scaled(k, False), r, axis=2), v, g, beta)


def grads(fn, args, cot):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot.astype(jnp.float32)),
                    argnums=ARGNUMS)(*args)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("T", [192, 257])
def test_the_kernels_at_the_cells_head_widths_are_the_recurrence(T):
    """Dk = Dv = 128, two key heads serving four value heads: forward and all five gradients."""
    args, cot = inputs(T, seed=T)
    with jax.default_matmul_precision("highest"):
        want, want_grads = recurrence(*args), grads(recurrence, args, cot)
    got = gated_delta_rule(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert rel(got, want) < 2e-6
    for name, g, w in zip("q k v g beta".split(), grads(gated_delta_rule, args, cot), want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert rel(g, w) < 1e-5, name


def test_the_bfloat16_call_is_the_float32_call_on_the_same_values():
    """The step hands the rule bfloat16 arrays, the benchmark's check float32 arrays that hold
    bfloat16 values: one kernel, whose further terms are then exactly zero. They agree to
    float32 rounding: o to the last bfloat16 place, the float32 cotangents of g and beta to
    1e-5, those of q, k and v to the last bfloat16 place."""
    wide, cot = inputs(192, seed=5)
    narrow = tuple(a.astype(jnp.bfloat16) for a in wide[:3]) + wide[3:]
    o_wide, o_narrow = gated_delta_rule(*wide), gated_delta_rule(*narrow)
    assert o_narrow.dtype == jnp.bfloat16 and o_wide.dtype == jnp.float32
    near = dict(rtol=2.0 ** -7, atol=1e-6)          # a bfloat16 place either way
    np.testing.assert_allclose(o_narrow.astype(jnp.float32), o_wide, **near)
    assert rel(o_narrow.astype(jnp.float32), o_wide.astype(jnp.bfloat16).astype(jnp.float32)) < 1e-3
    g_wide, g_narrow = grads(gated_delta_rule, wide, cot), grads(gated_delta_rule, narrow, cot.astype(jnp.bfloat16))
    for w, n in zip(g_wide[:3], g_narrow[:3]):
        assert n.dtype == jnp.bfloat16
        assert rel(n.astype(jnp.float32), w) < 3e-3
    for w, n in zip(g_wide[3:], g_narrow[3:]):
        assert n.dtype == jnp.float32
        assert rel(n, w) < 1e-5


def test_a_head_that_forgets_slowly_keeps_its_state_over_two_thousand_tokens():
    """A decay of 0.0015 a token (Qwen3-Next draws ``A`` down to 0.001): the state carries
    670 tokens of memory through 32 chunks and four blocks. Against the recurrence in float64."""
    (q, k, v, g, beta), _ = inputs(2048, Hk=1, Hv=2, Dk=16, Dv=8, rates=[0.0015, 0.4])
    got = np.asarray(gated_delta_rule(q, k, v, g, beta), np.float64)
    unit = lambda a: a / np.sqrt(np.sum(a * a, axis=-1, keepdims=True) + kernels.L2_EPS)     # noqa: E731
    q, k, v, g, beta = (np.asarray(a, np.float64)[0] for a in (q, k, v, g, beta))
    q, k = unit(q) * q.shape[-1] ** -0.5, unit(k)
    want = np.zeros_like(v)
    S = np.zeros((2, 16, 8))
    for t in range(2048):
        for h in range(2):
            S[h] *= np.exp(g[t, h])
            S[h] += np.outer(k[t, 0], beta[t, h] * (v[t, h] - S[h].T @ k[t, 0]))
            want[t, h] = S[h].T @ q[t, 0]
    assert rel(got[0], want) < 2e-6
    assert rel(got[0, -256:, 0], want[-256:, 0]) < 2e-6          # the slow head, at the end


def _calls(jaxpr, found):
    """Every ``pallas_call`` of a jaxpr and its sub-jaxprs as (name, scope path), and the
    names of every other primitive outside them."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found["kernels"].append((eqn.params["name"], str(eqn.source_info.name_stack)))
            continue
        found["others"].add(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _calls(sub, found)
    return found


def test_the_gradients_program_is_the_two_kernels_and_no_other_form_of_the_rule():
    args, cot = inputs(130, Dk=16, Dv=8)
    jaxpr = jax.make_jaxpr(lambda *a: grads(gated_delta_rule, a, cot))(*args)
    found = _calls(jaxpr.jaxpr, {"kernels": [], "others": set()})
    assert sorted(name for name, _ in found["kernels"]) == ["ds_delta_rule_bwd", "ds_delta_rule_fwd"]
    # what is left outside the kernels lays operands out: no product, no loop, no scan
    assert not found["others"] & {"dot_general", "scan", "while", "cond", "exp", "cumsum"}, found["others"]
    assert not hasattr(delta_rule, "_block") and not hasattr(delta_rule, "_inverse_unit_lower")


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "under-checkpoint"])
def test_both_kernels_run_under_the_mixers_scopes(remat):
    """``benchmarks/hybrid_spans.py`` counts an operation under ``ds_lin_attn`` and
    ``ds_delta_rule`` by its scope path: the backward kernel, which a transpose traces, has to
    carry both as the forward does."""
    from test_qwen3_next import build
    _, model, params = build()
    mp = params["layers"][0]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 70, 32))
    loss = lambda x, mp: jnp.sum(model.linear_mixer(x, mp) ** 2)      # noqa: E731
    loss = jax.checkpoint(loss) if remat else loss
    found = _calls(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, mp).jaxpr, {"kernels": [], "others": set()})
    names = [name for name, _ in found["kernels"]]
    assert names.count("ds_delta_rule_bwd") == 1 and names.count("ds_delta_rule_fwd") == 1 + remat
    for name, path in found["kernels"]:
        assert "ds_lin_attn" in path and "ds_delta_rule/" in path and path.endswith(name), (name, path)


def test_the_compiled_kernels_refuse_a_head_width_the_lanes_do_not_divide():
    args, _ = inputs(64, Dk=16, Dv=8)
    with pytest.raises(AssertionError, match="multiples of 128"):
        jax.eval_shape(lambda *a: gated_delta_rule(*a, interpret=False), *args)
    assert kernels.heads_together(2) == 2 and kernels.heads_together(3) == 1
