"""OLMoE on the normal path against its plain float32 reference
(``benchmarks/reference/olmoe_reference.py``) on seeded weights at a tiny size: one device
and meshes of two and four devices with the experts split, top-2 and top-8; a router that
sends every token to one expert; the grouped matmul, whole and in pieces, against a
per-expert loop; the experts' exchange against the collectives it stands for."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu
from benchmarks.reference import olmoe_reference as ref
from deepspeed_tpu.models.olmoe import OlmoeConfig, OlmoeModel
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.ops.pallas import rows_sum
from deepspeed_tpu.parallel import moe
from deepspeed_tpu.parallel.moe import (DroplessMoE, _sort_rows, _sum_rows, _take_rows, experts_matmul, gather_pieces,
                                        piece_firsts)
from deepspeed_tpu.utils import spans
from test_ouro import equations_by_path

AUX = 0.01
CASES = [(k, d) for k in (2, 8) for d in (1, 2, 4)]
IDS = [f"top{k}-{d}dev" for k, d in CASES]


def published(top_k):
    return dict(hidden_size=64, intermediate_size=32, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4, num_experts=8,
                num_experts_per_tok=top_k, vocab_size=256, rms_norm_eps=1e-5, rope_theta=10000,
                norm_topk_prob=False, max_position_embeddings=64)


def build(top_k, **more):
    keys = published(top_k)
    more = dict(dict(compute_dtype=jnp.float32, initializer_range=0.2,
                     router_aux_loss_coef=AUX), **more)
    model = OlmoeModel(OlmoeConfig.from_published(keys, **more))
    return keys, model, model.init(jax.random.PRNGKey(top_k))


def batch(seed=1, rows=4, T=32):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 256, (rows, T)).astype(np.int32),
            rng.integers(0, 256, (rows, T)).astype(np.int32))


def on_mesh(model, params, tokens, labels, devices):
    """Everything placed as the engine places it, and a wrapper that traces under the mesh."""
    if devices == 1:
        return params, tokens, labels, lambda fn: fn
    mesh = build_mesh(data=devices, devices=jax.devices()[:devices])
    rows = NamedSharding(mesh, P("data"))

    def under(fn):
        def traced(*args):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return fn(*args)
        return traced
    return (jax.device_put(params, model.engine_shardings(mesh)),
            jax.device_put(tokens, rows), jax.device_put(labels, rows), under)


@pytest.mark.parametrize("top_k, devices", CASES, ids=IDS)
def test_loss_logits_and_expert_choices_match_the_reference(top_k, devices):
    keys, model, params = build(top_k)
    tokens, labels = batch()
    want = jax.jit(lambda p: ref.forward(p, tokens, labels, keys, AUX, last=8))(params)
    params, tok, lab, under = on_mesh(model, params, tokens, labels, devices)
    got = jax.jit(under(lambda p, t, l: model.forward_details(p, t, l, 8)))(params, tok, lab)
    for name in ("loss", "ce", "aux"):
        assert float(got[name]) == pytest.approx(float(want[name]), rel=1e-5), name
    np.testing.assert_allclose(got["logits"], want["logits"], atol=5e-5)
    assert np.array_equal(got["experts"], want["experts"])
    loss, stats = jax.jit(under(model.apply))(params, tok, lab)
    assert float(loss) == pytest.approx(float(want["loss"]), rel=1e-5)
    assert set(stats) == set(model.device_scalars) == {"moe_load_max_over_mean"}
    assert stats["moe_load_max_over_mean"].shape == (2,) and np.all(stats["moe_load_max_over_mean"] >= 1)


@pytest.mark.parametrize("top_k, devices", CASES, ids=IDS)
def test_gradients_of_every_parameter_group_match_the_reference(top_k, devices):
    keys, model, params = build(top_k)
    tokens, labels = batch(seed=2)
    want = jax.jit(jax.grad(lambda p: ref.loss(p, tokens, labels, keys, AUX)))(params)
    params, tok, lab, under = on_mesh(model, params, tokens, labels, devices)
    got = jax.jit(under(jax.grad(lambda p, t, l: model.apply(p, t, l)[0])))(params, tok, lab)
    flat_got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        g = flat_got[path]
        assert float(jnp.abs(g - w).max()) <= 2e-5 * float(jnp.abs(w).max()) + 1e-9, path
    if devices > 1:      # an owner's gradient stays with it, split as its experts are
        moe = got["layers"][0]["moe"]
        assert moe["w_gate_up"].sharding.spec == P("data") and moe["w_down"].sharding.spec == P("data")


@pytest.mark.parametrize("top_k, devices", CASES, ids=IDS)
def test_every_token_to_the_same_experts_is_computed_whole(top_k, devices):
    """The skewed router: every token is sent to the same ``k`` experts, so each of their
    groups holds every token and the others nothing. Nothing is dropped (the output is
    the reference's plain sum) and the load reads E / k."""
    E, H, F = 8, 64, 32
    layer = DroplessMoE(H, F, E, top_k)
    params = layer.init(jax.random.PRNGKey(0), 0.2)
    bias = jnp.where(jnp.arange(E) < top_k, 50.0, 0.0)       # experts 0..k-1, always
    params["router_w"] = jnp.zeros((H, E)).at[0].set(bias)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, H)).at[..., 0].set(1.0)
    keys = dict(num_experts=E, num_experts_per_tok=top_k, intermediate_size=F, norm_topk_prob=False)
    want, chosen, _, _ = ref.expert_layer(x.reshape(-1, H), params, keys)
    assert np.array_equal(np.sort(chosen, -1), np.tile(np.arange(top_k), (64, 1)))
    if devices > 1:
        mesh = build_mesh(data=devices, devices=jax.devices()[:devices])
        specs = layer.expert_specs("data")
        params = {k: jax.device_put(v, NamedSharding(mesh, specs[k])) for k, v in params.items()}
        x = jax.device_put(x, NamedSharding(mesh, P("data")))
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            y, _, stats = jax.jit(layer.apply)(params, x)
    else:
        y, _, stats = jax.jit(layer.apply)(params, x)
    assert float(stats["load_max_over_mean"]) == pytest.approx(E / top_k)
    np.testing.assert_allclose(y.reshape(-1, H), want, atol=1e-4)


@pytest.mark.parametrize("devices", [1, 4], ids=["1dev", "4dev"])
@pytest.mark.parametrize("router, experts", [("softmax", "relu2"), (("sigmoid_bias", 2.5), "silu_gated"),
                                             (("sigmoid_bias", 2.5), "relu2")],
                         ids=["softmax-relu2", "sigmoid_bias-silu_gated", "sigmoid_bias-relu2"])
def test_the_routers_rule_and_the_experts_form_follow_the_constructor(router, experts, devices):
    """The two things a model's published keys decide, against a plain loop over the experts:
    a sigmoid router chooses by ``s + b`` and weighs by ``s`` alone (experts 6 and 7 carry a
    bias that gets them chosen whatever they score), renormalised and scaled; no gradient
    reaches ``b``; ``relu2`` experts are two matrices. The counts are every chip's, summed."""
    E, H, F, k = 8, 64, 32, 3
    layer = DroplessMoE(H, F, E, k, norm_topk_prob=True, router=router, experts=experts)
    params = layer.init(jax.random.PRNGKey(0), 0.2)
    w_in = "w_up" if experts == "relu2" else "w_gate_up"
    assert params[w_in].shape == (E, H, F if experts == "relu2" else 2 * F)
    assert ("router_bias" in params) == (router != "softmax") and set(layer.expert_specs("data")) == set(params)
    if router != "softmax":
        params["router_bias"] = jnp.where(jnp.arange(E) >= 6, 5.0, 0.0) + 0.05 * jnp.arange(E)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, H))
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def plain(params, x):
        x2 = x.reshape(-1, H)
        logits = jnp.dot(x2, params["router_w"], precision="highest")
        if router == "softmax":
            top, chosen = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
            top = top / jnp.sum(top, -1, keepdims=True)
        else:
            scores = jax.nn.sigmoid(logits)
            _, chosen = jax.lax.top_k(scores + params["router_bias"], k)
            top = jnp.take_along_axis(scores, chosen, -1)
            top = router[1] * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
        weight = jnp.sum(jax.nn.one_hot(chosen, E) * top[..., None], axis=1)              # [n, E]
        y = 0.0
        for e in range(E):
            up = jnp.dot(x2, params[w_in][e], precision="highest")
            hidden = jnp.square(jax.nn.relu(up)) if experts == "relu2" else jax.nn.silu(up[:, :F]) * up[:, F:]
            y = y + weight[:, e:e + 1] * jnp.dot(hidden, params["w_down"][e], precision="highest")
        return y.reshape(x.shape), chosen

    want, chosen = plain(params, x)
    want_grads = jax.grad(lambda p, x: jnp.sum(plain(p, x)[0] * cot), argnums=(0, 1))(params, x)
    if router != "softmax":         # the biased experts are chosen every time, and weigh what they score
        assert np.all(np.sum(np.asarray(chosen) >= 6, axis=-1) == 2)

    def run(params, x):
        y, aux, stats = layer.apply(params, x)
        return jnp.sum(y.astype(jnp.float32) * cot), (y, aux, stats)

    if devices > 1:
        mesh = build_mesh(data=devices, devices=jax.devices()[:devices])
        specs = layer.expert_specs("data")
        params = {name: jax.device_put(v, NamedSharding(mesh, specs[name])) for name, v in params.items()}
        x = jax.device_put(x, NamedSharding(mesh, P("data")))
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            (_, (y, aux, stats)), grads = jax.jit(jax.value_and_grad(run, argnums=(0, 1), has_aux=True))(params, x)
    else:
        (_, (y, aux, stats)), grads = jax.jit(jax.value_and_grad(run, argnums=(0, 1), has_aux=True))(params, x)
    np.testing.assert_allclose(y, want, atol=2e-4)
    for name in params:
        np.testing.assert_allclose(grads[0][name], want_grads[0][name], atol=3e-4, err_msg=name)
    np.testing.assert_allclose(grads[1], want_grads[1], atol=3e-4)
    if router == "softmax":
        assert "counts" not in stats and float(aux) > 0
    else:
        assert not np.any(grads[0]["router_bias"]) and float(aux) == 0.0
        assert np.array_equal(stats["counts"], np.bincount(np.asarray(chosen).reshape(-1), minlength=E))
        assert float(stats["load_max_over_mean"]) == pytest.approx(64 / (64 * k / E))


@pytest.mark.parametrize("norm", [False, True], ids=["as-they-are", "renormalised"])
@pytest.mark.parametrize("k, E", [(1, 8), (4, 8), (1, 64), (4, 64), (10, 64), (1, 512), (4, 512), (10, 512)])
@pytest.mark.parametrize("router", ["softmax", ("sigmoid_bias", 2.5)], ids=["softmax", "sigmoid_bias"])
def test_the_chosen_scores_are_read_and_pulled_back_as_they_were_by_index(router, k, E, norm):
    """``_choose`` reads the ``k`` chosen of ``[n, E]`` by a compare, a select and a sum (``_chosen``)
    and leaves ``top_k`` the choice alone: the experts, their order and the float32 weights are, bit
    for bit, what ``top_k``'s values (softmax) and ``take_along_axis`` (sigmoid) gave, a row whose
    scores all tie and a row with one pair tied among them, and the logits' gradient is what the
    scatter back gave (one non-zero term a place: a token's experts are distinct). Operation by
    operation, as both forms are written: a compiler may fuse the two differently."""
    n = 12
    layer = DroplessMoE(16, 8, E, k, norm_topk_prob=norm, router=router)
    rng = np.random.default_rng(E + k)
    logits = rng.normal(size=(n, E)).astype(np.float32) * 2
    logits[0] = 0.25
    logits[1, 2] = logits[1, 5] = logits[1].max() + 1
    logits = jnp.asarray(logits)
    bias = jnp.asarray(0.25 * rng.integers(-1, 2, size=E), jnp.float32)       # the tied row stays tied under it
    cot = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)

    def by_index(logits):
        if router == "softmax":
            weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
            return (weights / jnp.sum(weights, axis=-1, keepdims=True) if norm else weights), experts
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(scores + bias, k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        return (weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20) if norm else weights) * router[1], experts

    def pulled(choose):
        weights, pull, experts = jax.vjp(choose, logits, has_aux=True)
        return weights, experts, pull(cot)[0]

    weights, experts, grad = pulled(lambda l: layer._choose(l, bias)[:2])
    want_weights, want_experts, want_grad = pulled(by_index)
    assert np.array_equal(experts, want_experts)
    assert router != "softmax" or np.array_equal(experts[0], np.arange(k))      # a tie goes to the lower index
    assert weights.dtype == jnp.float32 and np.array_equal(weights, want_weights)
    assert np.any(np.asarray(grad)) and np.array_equal(grad, want_grad)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("pieces", [1, 2, 4], ids=["whole", "2-pieces", "4-pieces"])
@pytest.mark.parametrize("sizes", [[16, 0, 40, 8], [64, 0, 0, 0], [0, 0, 0, 64], [16, 16, 16, 16]],
                         ids=["uneven", "first-group", "last-group", "even"])
def test_grouped_matmul_matches_a_per_expert_loop(sizes, pieces, backward):
    """``pieces`` > 1: the experts handed over in pieces out of order, as the other chips'
    arrive, each piece's rows written into the one output and its gradient returned alone."""
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(64, 24)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, 24, 12)), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    per = 4 // pieces
    order = [(1 - s) % pieces for s in range(pieces)]           # chip 1's arrivals
    firsts = (None,) if pieces == 1 else tuple(jnp.int32(o * per) for o in order)

    def loop(lhs, rhs):
        return jnp.concatenate([lhs[bounds[g]:bounds[g + 1]] @ rhs[g] for g in range(4)])

    def grouped(lhs, rhs):
        return experts_matmul(lhs, tuple(rhs[o * per:(o + 1) * per] for o in order), firsts,
                              group_sizes)

    if not backward:
        np.testing.assert_allclose(grouped(lhs, rhs), loop(lhs, rhs), rtol=1e-5, atol=1e-5)
        return
    cot = jnp.asarray(rng.normal(size=(64, 12)), jnp.float32)
    for a, b in zip(jax.grad(lambda l, r: jnp.sum(grouped(l, r) * cot), (0, 1))(lhs, rhs),
                    jax.grad(lambda l, r: jnp.sum(loop(l, r) * cot), (0, 1))(lhs, rhs)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# ``(k, groups, tokens, tokens a tile, how the rows are sent)`` of the combine's kernel cases: the four stand-in cells'
# ``(k, G)`` at toy sizes, then the runs a router can make of them
_IN_RUNS = {"nemotronh-k6-G8": (6, 8, 64, 16, "even"), "mellum2-k8-G16": (8, 16, 64, 16, "even"),
            "glm47flash-lfm2-k4-G8": (4, 8, 64, 32, "even"),
            "a-token-has-several-rows-in-a-group": (6, 2, 64, 16, "even"),     # the stand-in's fold
            "an-empty-group": (4, 8, 64, 16, "none-to-group-3"),
            "one-group-holds-every-row": (4, 8, 64, 16, "all-to-group-5"),
            "runs-cross-the-chunks-and-start-off-the-sublane-tile": (3, 2, 128, 32, "even"),
            "one-row-a-token": (1, 4, 128, 16, "even")}


def _sorted(k, E, n, sent, rng):
    """``(sent_to, slots, weights)`` for ``_sort_rows``: ``n k`` assignments over ``E`` groups."""
    sent_to = rng.integers(0, E, size=n * k)
    if sent == "none-to-group-3":
        sent_to = np.where(sent_to == 3, 4, sent_to)
    elif sent == "all-to-group-5":
        sent_to = np.full(n * k, 5)
    return (jnp.asarray(sent_to, jnp.int32), jnp.arange(n * k, dtype=jnp.int32),
            jnp.asarray(rng.random(size=(n, k)), jnp.float32))


@pytest.mark.parametrize("case, dtype", [
    pytest.param(case, dtype, id=f"{case}-{name}") for case, name, dtype in
    [(k, name, dtype) for name, dtype in (("f32", jnp.float32), ("bf16", jnp.bfloat16)) for k in (6, 8)]
    + [(case, "bf16", jnp.bfloat16) for case in _IN_RUNS]
    + [(case, "f32", jnp.float32) for case in ("nemotronh-k6-G8", "a-token-has-several-rows-in-a-group")]])
def test_dispatch_and_combine_are_each_others_transposes(case, dtype):
    """``_take_rows`` against ``x[tok]`` and ``_sum_rows`` against ``sum_j ys[inverse[:, j]]``, and
    under ``jax.vjp`` each one's cotangent IS the other, bit for bit: the combine pulls ``dy`` back
    as one gather by ``tok`` and keeps no row for it (the router's weights are in the rows before
    ``w_down``, PR 49); the dispatch pulls its rows' cotangents back as the combine's sum. The
    sorted weights come out of the rows' own sort, and their cotangent comes back as an operand of a
    sort by ``order`` (a permutation of the slots): ``d_ws[inverse]``, bit for bit. A case of
    ``_IN_RUNS`` takes the combine through its kernel (``ops/pallas/rows_sum.py``, interpreted here:
    ``runs`` given), which reads the rows in runs: the float32 sum of the same rows in another order
    (one float32 rounding before the cast), the same bits where a token has one row."""
    k, E, n, T, sent = _IN_RUNS.get(case, (case, 5, 40, None, "even"))
    H = 24 if T is None else 128
    rng = np.random.default_rng(k)
    sent_to, slots, weights = _sorted(k, E, n, sent, rng)
    (by_expert, order, inverse, w_sorted), pull = jax.vjp(lambda w: _sort_rows(sent_to, slots, w), weights)
    tok = order // k
    assert np.array_equal(by_expert, np.sort(sent_to)) and np.array_equal(sent_to[order], by_expert)
    assert np.array_equal(order[inverse.reshape(-1)], slots) and np.array_equal(w_sorted, weights.reshape(-1)[order])
    d_ws = jnp.asarray(rng.normal(size=n * k), jnp.float32)
    zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)      # noqa: E731
    d_weights = pull((zero(by_expert), zero(order), zero(inverse), d_ws))[0]
    assert np.array_equal(d_weights, d_ws[inverse]) and np.array_equal(d_weights.reshape(-1)[order], d_ws)
    x = jnp.asarray(rng.normal(size=(n, H)), dtype)
    ys = jnp.asarray(rng.normal(size=(n * k, H)), dtype)
    runs = None
    if T is not None:
        runs = rows_sum.run_bounds(by_expert, tok, n, E, T)
        bounds = np.asarray(runs)
        group_of, tok_of = np.asarray(by_expert), np.asarray(tok)
        for i, g in np.ndindex(n // T, E):        # a tile's run in a group: exactly its tokens' rows there
            mine = np.flatnonzero((group_of == g) & (tok_of // T == i))
            assert np.array_equal(mine, np.arange(bounds[i, g], bounds[i + 1, g])), (i, g)
        if "cross" in case:
            assert np.any(bounds[1:-1] % 16 != 0) and np.any(bounds[1:] // 128 > bounds[:-1] // 128)
        runs = rows_sum.visits(runs, n * k)
        assert int(runs[0][-1]) == rows_sum.chunks_visited(bounds.tolist()) <= runs[1].shape[0]

    def both_ways(x, ys):
        xs, pull_xs = jax.vjp(lambda x: _take_rows(x, tok, inverse, runs), x)
        y, pull_y = jax.vjp(lambda ys: _sum_rows(ys, tok, inverse, runs), ys)
        return xs, pull_xs(ys)[0], y, pull_y(x)[0], _sum_rows(ys, tok, inverse, runs)

    # the interpreted kernel as one program (a compile a case); the compiled form call by call, as it was
    xs, d_x, y, d_ys, summed = (both_ways if T is None else jax.jit(both_ways))(x, ys)
    assert np.array_equal(xs, x[tok]) and np.array_equal(d_x, summed)
    assert y.dtype == dtype and y.shape == (n, H) and np.array_equal(d_ys, xs) and np.array_equal(y, summed)
    # a token's k rows are added in float32 and rounded once; the plain scatter adds in the compute dtype
    tolerance = 1e-5 if dtype == jnp.float32 else 3e-2
    want = jnp.sum(ys[inverse].astype(jnp.float32), axis=1)
    if T is None or k == 1:
        assert np.array_equal(y, want.astype(dtype))
    else:           # the same rows in sorted order: apart by the float32 sum's own roundings, before the one cast
        ulp = (k - 1) * np.spacing(np.sum(np.abs(np.asarray(ys[inverse], np.float32)), axis=1))
        assert np.all(np.abs(np.asarray(y, np.float32) - np.asarray(want.astype(dtype), np.float32))
                      <= (ulp if dtype == jnp.float32 else np.abs(np.asarray(want)) * 2.0 ** -7 + ulp))
    np.testing.assert_allclose(y.astype(jnp.float32), jax.vjp(lambda x: x[tok], x)[1](ys)[0].astype(jnp.float32),
                               rtol=tolerance, atol=tolerance)


def test_the_combines_kernel_refuses_what_is_no_whole_tile():
    """``rows_sum.fits`` names the shapes the kernel takes (whole token tiles, whole chunks of rows, whole
    registers of lanes), the entry point refuses the others, and ``_run_bounds`` keeps the compiled gather
    for them and off the TPU; on it every cell's ``(k, G)`` that calls the helpers reads its rows in runs."""
    assert rows_sum.fits(8192, 8192 * 6, 2688) and not rows_sum.fits(8200, 8200 * 6, 2688)
    assert not rows_sum.fits(8192, 8192 * 6, 2688 + 64) and not rows_sum.fits(256, 256 * 3 + 64, 128)
    with pytest.raises(AssertionError):
        rows_sum.rows_sum(jnp.zeros((40 * 2, 128)), jnp.zeros(80, jnp.int32),
                          rows_sum.visits(jnp.zeros((3, 2), jnp.int32), 80), 40, interpret=True)
    group, tok = jnp.zeros(8192 * 8, jnp.int32), jnp.zeros(8192 * 8, jnp.int32)
    assert moe._run_bounds(8192, 8, 16, 2304, group, tok) is None          # off the TPU
    with pytest.MonkeyPatch.context() as monkey:
        monkey.setattr(jax, "default_backend", lambda: "tpu")
        took = {cell: moe._run_bounds(8192, k, G, H, group[:8192 * k], tok[:8192 * k]) is not None
                for cell, (k, G, H) in dict(nemotronh=(6, 8, 2688), mellum2=(8, 16, 2304), glm47flash=(4, 8, 2048),
                                            lfm2=(4, 8, 2048), olmoe=(8, 64, 2048)).items()}
        assert all(took.values()), took
        assert moe._run_bounds(8200, 8, 16, 2304, group[:8200 * 8], tok[:8200 * 8]) is None
    # what a balanced router's runs visit over what the rows fill: the count a trace and a test can both state
    assert rows_sum.rows_sum_chunks(8192, 8, 16) == (512, 512) and rows_sum.rows_sum_chunks(8192, 6, 8) == (512, 384)
    assert rows_sum.rows_sum_chunks(8192, 4, 8) == (256, 256) and rows_sum.rows_sum_chunks(8192, 8, 64) == (2048, 512)


@pytest.mark.parametrize("bad", [np.inf, np.nan], ids=["inf", "nan"])
def test_a_row_that_is_not_finite_stays_in_the_tiles_that_visit_its_chunk(bad):
    """What the kernel promises of a row that is not finite: its own token's sum is not finite (the
    step's overflow check sees it as it saw the gather's), and every token tile that visits no chunk
    holding the row keeps its bits. A tile that does visit the chunk meets ``0 x inf`` in the one-hot
    product and is not finite as a whole: wider than the compiled form, which confines it to the token."""
    k, G, n, T, H = 3, 2, 256, 32, 128
    rng = np.random.default_rng(7)
    by_expert, order, inverse, _ = _sort_rows(*_sorted(k, G, n, "even", rng))
    tok = order // k
    runs = rows_sum.visits(rows_sum.run_bounds(by_expert, tok, n, G, T), n * k)
    ys = jnp.asarray(rng.normal(size=(n * k, H)), jnp.bfloat16)
    row = 10
    combine = jax.jit(lambda ys: _sum_rows(ys, tok, inverse, runs))
    clean, spoilt = np.asarray(combine(ys), np.float32), np.asarray(combine(ys.at[row, 5].set(bad)), np.float32)
    first, chunks = np.asarray(runs[0]), np.asarray(runs[1])
    visiting = [i for i in range(n // T) if row // rows_sum.CHUNK in chunks[first[i]:first[i + 1]]]
    assert 0 < len(visiting) < n // T and int(tok[row]) // T in visiting
    assert not np.isfinite(spoilt[int(tok[row]), 5])
    finite = np.setdiff1d(np.arange(n // T), visiting)
    assert np.array_equal(spoilt.reshape(n // T, T, H)[finite], clean.reshape(n // T, T, H)[finite])
    assert np.all(np.isfinite(spoilt[:, np.arange(H) != 5]))               # a column of its own, too


@pytest.mark.parametrize("held, stand_in", [(None, False), ((4, 4), True)], ids=["whole-range", "held-stand-in"])
@pytest.mark.parametrize("router, k", [("softmax", 8), (("sigmoid_bias", 2.5), 6)], ids=["softmax-k8", "sigmoid_bias-k6"])
@pytest.mark.parametrize("experts", ["silu_gated", "relu2"])
def test_the_weights_before_w_down_give_what_they_give_after_it(experts, router, k, held, stand_in):
    """The layer in float32, whose rows take the router's weights BEFORE ``w_down``
    (``_activate``), against the plain form that weighs each token's ``k`` expert OUTPUTS
    (``einsum("nkh,nk->nh")`` after it): ``w_down`` is linear, so the result and every gradient
    (the input's, the router's, both expert arrays') agree to 1e-6 of their norms."""
    E, H, F = 16, 32, 24
    layer = DroplessMoE(H, F, E, k, norm_topk_prob=True, held=held, router=router, experts=experts, stand_in=stand_in)
    params = layer.init(jax.random.PRNGKey(k), 0.3)
    bias = 0.05 * jnp.arange(E, dtype=jnp.float32)
    if router != "softmax":
        params["router_bias"] = bias
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, H))
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    first, count = held or (0, E)

    def plain(params, x):
        x2 = x.reshape(-1, H)
        logits = jnp.dot(x2, params["router_w"], precision="highest")
        scores = jax.nn.softmax(logits, axis=-1) if router == "softmax" else jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + (0.0 if router == "softmax" else bias), k)
        top = jnp.take_along_axis(scores, chosen, -1)
        top = top / (jnp.sum(top, -1, keepdims=True) + (0.0 if router == "softmax" else 1e-20))
        top = top if router == "softmax" else 2.5 * top
        mine = (chosen - first) % count                      # the held expert that computes the row
        up = jnp.einsum("nh,nkhf->nkf", x2, params[layer.w_in][mine], precision="highest")
        act = jnp.square(jax.nn.relu(up)) if experts == "relu2" else jax.nn.silu(up[..., :F]) * up[..., F:]
        out = jnp.einsum("nkf,nkfh->nkh", act, params["w_down"][mine], precision="highest")
        return jnp.einsum("nkh,nk->nh", out, top).reshape(x.shape)

    loss = lambda f: (lambda p, x: jnp.sum(f(p, x) * cot))      # noqa: E731
    want, want_grads = jax.jit(plain)(params, x), jax.jit(jax.grad(loss(plain), argnums=(0, 1)))(params, x)
    got = jax.jit(lambda p, x: layer.apply(p, x)[0])(params, x)
    grads = jax.jit(jax.grad(loss(lambda p, x: layer.apply(p, x)[0]), argnums=(0, 1)))(params, x)
    apart = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))      # noqa: E731
    assert apart(got, want) < 1e-6
    assert apart(grads[1], want_grads[1]) < 1e-6
    for name in ("router_w", layer.w_in, "w_down"):
        assert apart(grads[0][name], want_grads[0][name]) < 1e-6, name


@pytest.mark.parametrize("devices", [2, 4], ids=["2dev", "4dev"])
def test_the_exchange_moves_what_the_collectives_moved(devices):
    """``gather_pieces`` against ``all_gather`` and its cotangent against ``psum_scatter``, in
    bf16 as the step runs them: the pieces, put where ``piece_firsts`` says, are the tiled
    all-gather bit for bit; the owners end with the SUM over the chips of the gradients of
    their experts, within bf16 roundings of the exact sum."""
    per, tail = 3, (5, 8)
    mesh = build_mesh(data=devices, devices=jax.devices()[:devices])
    rng = np.random.default_rng(devices)
    w = jnp.asarray(rng.normal(size=(devices * per,) + tail), jnp.bfloat16)
    cots = jnp.asarray(rng.normal(size=(devices, devices, per) + tail), jnp.bfloat16)

    def local(w, cots):                    # a chip: its experts; a cotangent a piece
        firsts = piece_firsts("data", per)

        def in_expert_order(pieces):
            whole = jnp.zeros((devices * per,) + tail, pieces[0].dtype)
            for piece, first in zip(pieces, firsts):
                whole = jax.lax.dynamic_update_slice(whole, piece, (first, 0, 0))
            return whole

        pieces, back = jax.vjp(lambda w: gather_pieces(w, "data"), w)
        cot = tuple(cots[0, s] for s in range(devices))
        whole_cot = in_expert_order(cot)
        return (in_expert_order(pieces), jax.lax.all_gather(w, "data", tiled=True),
                back(cot)[0],
                jax.lax.psum_scatter(whole_cot.astype(jnp.float32), "data", tiled=True),
                jax.lax.psum_scatter(jnp.abs(whole_cot).astype(jnp.float32), "data", tiled=True),
                jax.lax.psum_scatter(whole_cot, "data", tiled=True))

    data = P("data")
    pieces, gathered, summed, exact, size, collective = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(data, data), out_specs=(data,) * 6, check_vma=False))(w, cots)
    assert pieces.dtype == jnp.bfloat16 and summed.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(pieces, np.float32), np.asarray(gathered, np.float32))
    assert np.array_equal(np.asarray(pieces, np.float32),
                          np.tile(np.asarray(w, np.float32), (devices, 1, 1)))
    rounding = (devices - 1) * 2.0 ** -8 * np.asarray(size)      # a rounding a hop at most
    assert np.all(np.abs(np.asarray(summed, np.float32) - np.asarray(exact)) <= rounding)
    assert np.all(np.abs(np.asarray(summed, np.float32) - np.asarray(collective, np.float32))
                  <= 2 * rounding)


@pytest.mark.parametrize("room, kept", [(10 ** 12, True), (4 * 604, True), (4 * 604 - 1, False), (4 * 201, False), (0, False)],
                         ids=["everything", "to-the-byte", "a-byte-short", "w-down-alone-would-fit", "no-room"])
def test_what_a_backward_keeps_of_its_fetches_is_a_function_of_the_sizes(room, kept):
    """All or nothing: a part kept (``w_down``'s third) measured slower than nothing kept."""
    assert moe.fetches_kept(4, 604, room) is kept


@pytest.mark.parametrize("limit, classes, room", [
    (16_000, {"params": 1_000, "master": 2_000, "optimizer": 4_000, "grads": 500}, 16_000 - 7_000 - 500),
    (16_000, {"params": 1_000}, 15_000),                     # a fused step holds no gradients between programs
    (7_400, {"params": 1_000, "master": 2_000, "optimizer": 4_000, "grads": 500}, 0),       # never negative
], ids=["state-and-a-set-of-gradients", "no-gradients-held", "no-room"])
def test_the_room_beside_the_state_is_a_function_of_the_sizes(limit, classes, room, monkeypatch):
    from deepspeed_tpu.utils import hbm
    assert hbm.room_beside_state(limit + hbm.TEMPORARIES_MARGIN, classes) == room
    monkeypatch.setattr(hbm, "TEMPORARIES_MARGIN", 0)
    assert hbm.room_beside_state(limit, classes) == room


def test_olmoe_on_four_v5e_chips_has_the_room_for_all_it_fetches():
    """The sizes the margin was set from (PERF.md, PR 54): the four layers' fetched experts are
    2.416 GB a chip and fit; a fifth layer's would not."""
    from deepspeed_tpu.utils import hbm
    room = hbm.room_beside_state(16_909_334_528, {"params": 1_352_798_208, "master": 1_884_329_984, "optimizer": 3_768_659_968,
                                                  "grads": 942_163_968})
    layer = DroplessMoE(2048, 1024, 64, 8)
    fetched = 3 * 16 * 3 * 1024 * 2048 * 2
    assert fetched == 603_979_776 and moe.fetches_kept(4, fetched, room) and not moe.fetches_kept(5, fetched, room)
    assert layer.fetches_kept(4, jnp.zeros((8, 16, 2048), jnp.bfloat16)) is False      # no mesh in context: nothing is fetched


def two_layers_on_four_devices(top_k=2, dtype=jnp.float32):
    """The toy of two layers placed on four devices as the engine places it, ``gradient(room)``:
    its loss and gradients as a program traced with ``room`` bytes for the fetched experts (a
    new ``jit`` a call: the room is read when a program is traced), its arguments, and the bytes
    a layer fetches."""
    _, model, params = build(top_k, compute_dtype=dtype)
    params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
    tokens, labels = batch()
    params, tokens, labels, under = on_mesh(model, params, tokens, labels, 4)

    def gradient(room):
        def traced(*args):
            with moe.room_for_fetched_experts(lambda: room):
                return jax.value_and_grad(lambda p, t, l: model.apply(p, t, l)[0])(*args)
        return jax.jit(under(traced))
    # a layer's three fetched pieces of both arrays (the toy: E 8, F 32, H 64)
    return gradient, (params, tokens, labels), 3 * 2 * 3 * 32 * 64 * jnp.dtype(dtype).itemsize


FORWARD, AGAIN, HOME = (("jit", "shard_map") + rest for rest in (("custom_vjp_call",), ("remat2", "custom_vjp_call"), ("remat2",)))


@pytest.mark.parametrize("layers_that_fit", [0, 1, 2], ids=["no-room", "room-for-one-layer", "room-for-both"])
def test_a_kept_piece_crosses_the_chips_twice_a_step_and_not_three_times(layers_that_fit):
    """Two stacked expert layers on four devices: a layer's experts move as six transfers
    forward (three fetched pieces of two arrays), six gradients sent home, and six more in the
    backward's second forward where they were NOT kept: 36 in all as the parent has them, two
    thirds of that with the pieces kept, and nothing kept where not all fit. Counted where they
    stand in the gradient's jaxpr and again in the lowered program."""
    gradient, args, layer = two_layers_on_four_devices()
    kept = layers_that_fit == 2
    equations = list(equations_by_path(jax.make_jaxpr(gradient(layers_that_fit * layer))(*args).jaxpr))
    moved = collections.Counter(path for path, eqn in equations if eqn.primitive.name == "ppermute")
    assert moved == collections.Counter({FORWARD: 12, HOME: 12, **({} if kept else {AGAIN: 12})})
    # a layer that keeps its pieces passes the first product's rows and ``w_down`` through one barrier,
    # forward and (its transpose) backward, and again in the second forward; one that keeps nothing is the parent's
    barriers = collections.Counter(path for path, eqn in equations if eqn.primitive.name == "optimization_barrier")
    assert barriers == (collections.Counter({FORWARD[:2]: 2, HOME: 4}) if kept else {})
    assert gradient(layers_that_fit * layer).lower(*args).as_text().count("collective_permute") == (24 if kept else 36)


@functools.lru_cache(maxsize=None)
def not_kept(top_k, dtype):
    gradient, args, _ = two_layers_on_four_devices(top_k, dtype)
    return jax.device_get(gradient(0)(*args))


@pytest.mark.parametrize("top_k", [2, 8], ids=["top2", "top8"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_kept_pieces_change_no_bit_of_the_loss_or_of_any_gradient(dtype, top_k):
    """What is kept is what would be fetched again: the loss and every parameter's gradient are
    the not-kept program's, compared with ``==`` and no tolerance, on seeded weights."""
    gradient, args, layer = two_layers_on_four_devices(top_k, dtype)
    (loss, grads), (want_loss, want_grads) = jax.device_get(gradient(2 * layer)(*args)), not_kept(top_k, dtype)
    assert np.isfinite(loss) and loss == want_loss
    same = jax.tree_util.tree_map(np.array_equal, grads, want_grads)
    assert all(jax.tree_util.tree_leaves(same)), same
    assert np.abs(np.asarray(grads["layers"][0]["moe"]["w_down"], np.float32)).max() > 0


@pytest.mark.parametrize("limit, transfers", [(None, 36), (10 ** 12, 24)], ids=["no-limit-known", "a-chip-with-room"])
def test_the_engine_gives_its_gradient_program_the_room_its_device_reports(limit, transfers, monkeypatch):
    """The engine reads the room when it traces the model: the device's limit less its own
    state, by ``utils/hbm.room_beside_state``. The CPU reports no limit and keeps nothing."""
    from deepspeed_tpu.runtime import engine as engine_module
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    if limit is not None:
        monkeypatch.setattr(engine_module, "device_memory_stats", lambda device=None: {"bytes_limit": limit})
    _, model, params = build(2, compute_dtype=jnp.bfloat16, initializer_range=0.02)
    engine = DeepSpeedEngine(model=model, model_parameters=params, mesh=build_mesh(data=4, devices=jax.devices()[:4]),
                             config_params={"train_batch_size": 4, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
                                            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, "steps_per_print": 10 ** 9})
    assert (engine._room_beside_state() > 0) == (limit is not None)
    tokens, _ = batch(seed=4)
    lowered = engine._jit_loss_and_grad.lower(engine.params, engine.scaler_state.cur_scale,
                                              *map(engine.shard_batch, (tokens, np.roll(tokens, -1, 1))))
    assert lowered.as_text().count("collective_permute") == transfers


@pytest.mark.parametrize("fused_step", [False, True], ids=["two-programs", "fused_step"])
@pytest.mark.parametrize("devices", [1, 4], ids=["1dev", "4dev"])
def test_the_engine_trains_it_and_keeps_the_device_scalars(devices, fused_step):
    _, model, params = build(2, compute_dtype=jnp.bfloat16, initializer_range=0.02)
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    mesh = build_mesh(data=devices, devices=jax.devices()[:devices])
    engine = DeepSpeedEngine(model=model, model_parameters=params, mesh=mesh, config_params={
        "train_batch_size": 4, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, "steps_per_print": 10 ** 9,
        "fused_step": fused_step})
    assert (engine._run_fused_step is not None) == fused_step
    moe = engine.master_params["layers"][0]["moe"]
    split = P("data", None, None) if devices > 1 else P(None, None, None)
    assert moe["w_gate_up"].sharding.spec == split and moe["w_down"].sharding.spec == split
    assert engine.opt_state.exp_avg["layers"][1]["moe"]["w_down"].sharding.spec == split
    assert engine.params["layers"][0]["moe"]["w_gate_up"].sharding.spec == (
        P("data") if devices > 1 else P())
    tokens, _ = batch(seed=4)
    losses = []
    for _ in range(4):
        loss = engine(tokens, np.roll(tokens, -1, 1))
        engine.backward(loss)
        engine.step()
        assert loss.shape == ()          # the scalars never reach the caller's loss
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    kept = spans.recorder().device_scalars(engine._span_engine)
    assert all(isinstance(v, jax.Array) for _, s in kept for v in s.values())   # unfetched
    kept = jax.device_get(kept)
    assert [step for step, _ in kept] == [0, 1, 2, 3]
    assert all(set(s) == {"moe_load_max_over_mean"} for _, s in kept)
    assert kept[-1][1]["moe_load_max_over_mean"].shape == (2,)


def test_a_dict_beside_the_loss_that_the_model_does_not_declare_is_dropped():
    """``(loss, aux)`` from a model without ``device_scalars``: ``aux`` never leaves the grad
    program (a model that returns its logits there must not have them kept a step)."""
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    def model_fn(params, x, y):
        out = x @ params["w"]
        return jnp.mean((out - y) ** 2), {"logits": out}

    engine = DeepSpeedEngine(
        model=model_fn, model_parameters={"w": jnp.ones((8, 8), jnp.float32)},
        mesh=build_mesh(data=1, devices=jax.devices()[:1]), config_params={
            "train_batch_size": 4, "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "steps_per_print": 10 ** 9})
    x = np.ones((4, 8), np.float32)
    loss = engine(x, 2 * x)
    engine.backward(loss)
    engine.step()
    assert loss.shape == () and spans.recorder().device_scalars(engine._span_engine) == []
    outputs = jax.tree_util.tree_leaves(jax.eval_shape(
        engine._loss_and_grad_fn, engine.params, engine.scaler_state.cur_scale, x, 2 * x))
    assert sorted(o.shape for o in outputs) == [(), (8, 8)]


def test_initialize_takes_the_model_as_it_takes_gpt2():
    _, model, params = build(2, compute_dtype=jnp.bfloat16)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, "steps_per_print": 10 ** 9})
    assert engine.dp_size == 8
    assert engine.master_params["layers"][0]["moe"]["w_down"].sharding.spec == P("data", None, None)
    tokens, _ = batch(seed=5, rows=8)
    loss = engine(tokens, np.roll(tokens, -1, 1))
    engine.backward(loss)
    engine.step()
    assert np.isfinite(float(loss))
    # every grouped product left in the recorder how it runs, while the gradient program was traced:
    # off the TPU as ``lax.ragged_dot`` (on the chip ``.whole_k`` or ``.cut_k`` and its tiles)
    products = {name: n for name, n in spans.recorder().counters(engine._span_engine).items() if name.startswith("moe.")}
    assert products and all(".ragged_dot[loss_and_grad] " in name for name in products)
    assert {name.split(".")[1] for name in products} == {"gmm", "gmm_t", "tgmm"}


HELD = dict(E=16, H=64, F=32, k=3, first=4, count=4)


def held_range_against_a_plain_loop(stand_in, router_bias):
    """Experts 4..7 of 16 held, a sigmoid router over all 16 with three a token, the layer under
    ``jit`` against a plain loop over the experts: ``(the chosen experts [n, k], stats)`` once
    the result and every gradient have been compared at the test's limits."""
    E, H, F, k, first, count = (HELD[name] for name in ("E", "H", "F", "k", "first", "count"))
    layer = DroplessMoE(H, F, E, k, norm_topk_prob=True, held=(first, count),
                        router=("sigmoid_bias", 2.5), experts="relu2", stand_in=stand_in)
    params = layer.init(jax.random.PRNGKey(0), 0.2)
    assert params["w_up"].shape == (count, H, F) and params["router_w"].shape == (H, E)
    params["router_bias"] = jnp.asarray(router_bias, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, H))
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def plain(params, x):
        x2 = x.reshape(-1, H)
        scores = jax.nn.sigmoid(jnp.dot(x2, params["router_w"], precision="highest"))
        _, chosen = jax.lax.top_k(scores + params["router_bias"], k)
        top = jnp.take_along_axis(scores, chosen, -1)
        top = 2.5 * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
        weight = jnp.sum(jax.nn.one_hot(chosen, E) * top[..., None], axis=1)              # [n, E]
        y = 0.0
        for e in range(E) if stand_in else range(first, first + count):
            held = (e - first) % count
            hidden = jnp.square(jax.nn.relu(jnp.dot(x2, params["w_up"][held], precision="highest")))
            y = y + weight[:, e:e + 1] * jnp.dot(hidden, params["w_down"][held], precision="highest")
        return y.reshape(x.shape), chosen

    want, chosen = plain(params, x)
    want_grads = jax.grad(lambda p, x: jnp.sum(plain(p, x)[0] * cot), argnums=(0, 1))(params, x)

    def run(params, x):
        y, aux, stats = layer.apply(params, x)
        return jnp.sum(y.astype(jnp.float32) * cot), (y, stats)

    (_, (y, stats)), grads = jax.jit(jax.value_and_grad(run, argnums=(0, 1), has_aux=True))(params, x)
    np.testing.assert_allclose(y, want, atol=2e-4)
    for name in params:
        np.testing.assert_allclose(grads[0][name], want_grads[0][name], atol=3e-4, err_msg=name)
    np.testing.assert_allclose(grads[1], want_grads[1], atol=3e-4)
    assert not np.any(grads[0]["router_bias"])
    chosen = np.asarray(chosen)
    assert np.array_equal(stats["counts"], np.bincount(chosen.reshape(-1), minlength=E))
    return chosen, stats


@pytest.mark.parametrize("stand_in", [False, True], ids=["absent-left-out", "held-stand-in"])
def test_a_held_range_leaves_the_absent_experts_out_or_stands_in_for_them(stand_in):
    """Experts 4..7 of 16 held, a sigmoid router over all 16 with three a token, against a plain
    loop. Left out: only the rows sent to 4..7 add their part, and ``rows_here`` counts them.
    Standing in: expert ``e``'s rows go through held expert ``4 + (e - 4) % 4``, every
    assignment is computed (``rows_here = n k`` whatever the router does), and choice, weights
    and ``counts`` stay those of all 16."""
    E, H, F, k, first, count = (HELD[name] for name in ("E", "H", "F", "k", "first", "count"))
    chosen, stats = held_range_against_a_plain_loop(stand_in, 0.05 * np.arange(E))
    here = chosen.size if stand_in else np.sum((chosen >= first) & (chosen < first + count))
    assert float(stats["rows_here"]) == here and (stand_in or here < chosen.size)
    with pytest.raises(AssertionError):      # nothing to stand in for where every expert is held
        DroplessMoE(H, F, E, k, router=("sigmoid_bias", 2.5), experts="relu2", stand_in=True)


@pytest.mark.parametrize("stand_in", [False, True], ids=["absent-left-out", "held-stand-in"])
def test_a_router_that_sends_every_token_to_one_held_expert_changes_no_shape(stand_in):
    """A selection bias that gives every token the experts 5, 9 and 13. Standing in, all three
    are held expert 5's rows: ONE group of ``n k`` rows and three empty ones, the same static
    buffers as at an even router, the plain loop's result and gradients. Left out, expert 5's
    ``n`` rows alone are here, one full pass."""
    E, first, count = HELD["E"], HELD["first"], HELD["count"]
    lean = np.where(np.isin(np.arange(E), (5, 9, 13)), 10.0, 0.0)
    chosen, stats = held_range_against_a_plain_loop(stand_in, lean)
    assert np.array_equal(np.sort(chosen, axis=-1), np.broadcast_to([5, 9, 13], chosen.shape))
    assert set(first + (chosen.reshape(-1) - first) % count) == {5}
    assert float(stats["rows_here"]) == (chosen.size if stand_in else chosen.shape[0])
    assert float(stats["load_max_over_mean"]) == pytest.approx(E / 3)


def shapes_of(jaxpr, primitive):
    """``[(enclosing primitives, the first output's shape)]`` of every ``primitive`` of a jaxpr."""
    return [(path, eqn.outvars[0].aval.shape) for path, eqn in equations_by_path(jaxpr)
            if eqn.primitive.name == primitive]


@pytest.mark.parametrize("stand_in", [False, True], ids=["absent-left-out", "held-stand-in"])
def test_where_every_row_is_computed_here_the_layer_sorts_once_and_multiplies_once(stand_in):
    """The gradient's jaxpr of a held range. Standing in, the rows are ``n k`` whatever the
    router does, and the layer is the whole range's: no loop, no branch and no scatter (the one
    ``scan`` is ``searchsorted``'s; the chosen scores' cotangent is a select since PR 51, and the
    sorted weights' a third sort), each product once forward, and in the backward, under the layer's own
    ``checkpoint``, each product's two cotangents once, ``[n k, .]`` for the rows and
    ``[count, ., .]`` for the weights. A plain held range's rows follow the router: it stays in
    passes of ``n`` rows, a loop and a branch forward and backward, and the backward makes each
    pass again before its cotangents."""
    E, H, F, k, first, count = (HELD[name] for name in ("E", "H", "F", "k", "first", "count"))
    layer = DroplessMoE(H, F, E, k, norm_topk_prob=True, held=(first, count),
                        router=("sigmoid_bias", 2.5), experts="relu2", stand_in=stand_in)
    params = layer.init(jax.random.PRNGKey(0), 0.2)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, H))
    n = 2 * 48
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(layer.apply(p, x)[0]), argnums=(0, 1)))(params, x).jaxpr
    count_of = lambda name: len(shapes_of(jaxpr, name))      # noqa: E731
    products = shapes_of(jaxpr, "ragged_dot_general")
    if stand_in:
        assert (count_of("scan"), count_of("while"), count_of("cond"), count_of("scatter-add")) == (1, 0, 0, 0)
        assert sorted(products) == sorted([
            (("custom_vjp_call",), (n * k, F)), (("custom_vjp_call",), (n * k, H)),
            (("remat2",), (n * k, F)), (("remat2",), (n * k, H)),
            (("remat2",), (count, H, F)), (("remat2",), (count, F, H))])
        assert count_of("remat2") == 1 and count_of("sort") == 3
    else:
        # searchsorted's, the forward's passes, the backward's; a branch a loop
        assert (count_of("scan"), count_of("while"), count_of("cond"), count_of("scatter-add")) == (3, 0, 2, 4)
        in_passes = [(path, shape) for path, shape in products if path[:2] in (("scan", "cond"), ("custom_vjp_call", "scan"))]
        assert len(products) == len(in_passes) == 8
        # a pass of n rows: two products forward, the two made again, and four cotangents
        assert sorted(shape for _, shape in products) == sorted(
            [(n, F), (n, H)] * 2 + [(n, F), (n, H), (count, H, F), (count, F, H)])
        assert count_of("remat2") == 0 and count_of("sort") == 1


def test_no_token_major_rows_are_laid_out_at_a_k_the_tile_does_not_divide():
    """The gradient's jaxpr of a layer whose held experts stand in, three experts a token: no
    value ``[n, 3, H]`` exists (on the chip a relayout of all the rows, to and from ``[n k, H]``,
    where eight sublanes do not divide ``k``; PERF.md, PR 46), and none padded to eight slots
    either (``[n, 8, H]``, the combine's cotangent ``dy x weights`` until PR 49: the weights are
    in the rows before ``w_down``). A token's expert outputs and the dispatch's gathered cotangent
    are ``[k, n, H]``, slot by slot, ``[rows, H]`` as it lies. Of the gathers of rows ``H`` wide,
    the forward's two read the ``n`` tokens (dispatch) and the ``n k`` sorted rows (combine); the
    backward, under the layer's own ``checkpoint``, reads the tokens twice (the dispatch made
    again, and the combine's cotangent: ``dy`` as it is) and the ``n k`` sorted rows (the
    dispatch's), and never the second product's output."""
    E, H, F, k, first, count = (HELD[name] for name in ("E", "H", "F", "k", "first", "count"))
    layer = DroplessMoE(H, F, E, k, norm_topk_prob=True, held=(first, count),
                        router=("sigmoid_bias", 2.5), experts="relu2", stand_in=True)
    params = layer.init(jax.random.PRNGKey(0), 0.2)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, H)).astype(jnp.bfloat16)
    n = 2 * 48
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(layer.apply(p, x)[0].astype(jnp.float32)),
                                    argnums=(0, 1)))(params, x).jaxpr
    wide = collections.Counter(var.aval.shape for _, eqn in equations_by_path(jaxpr) for var in eqn.outvars
                               if len(var.aval.shape) == 3 and var.aval.shape[-1] == H)
    assert (n, k, H) not in wide and (n, 8, H) not in wide and wide[(k, n, H)], wide
    row_gathers = collections.Counter(     # by the checkpoint they are under, whichever of the pair's rules holds them
        (tuple(p for p in path if p != "custom_vjp_call"), eqn.invars[0].aval.shape[0]) for path, eqn in equations_by_path(jaxpr)
        if eqn.primitive.name == "gather" and eqn.invars[0].aval.shape[1:] == (H,))
    assert row_gathers == {((), n): 1, ((), n * k): 1, (("remat2",), n): 2, (("remat2",), n * k): 1}, row_gathers


# ---- ``engine_shardings``: the one model-side layout rule the engine reads (``runtime/engine.py``
# takes it where the caller names no ``param_shardings``). Shapes and specs alone: nothing is placed.
LEAF_KINDS = {
    # kind: (paths into the tree, the spec on a mesh whose ``data`` divides the experts)
    "experts": ((("layers", 0, "moe", "w_gate_up"), ("layers", 1, "moe", "w_down")), P("data")),
    "router": ((("layers", 0, "moe", "router_w"), ("layers", 1, "moe", "router_w")), P()),
    "norms": ((("layers", 0, "norm_1"), ("layers", 1, "norm_2"), ("layers", 0, "q_norm"),
               ("layers", 1, "k_norm"), ("norm_f",)), P()),
    "attention": ((("layers", 0, "wqkv"), ("layers", 1, "wo")), P()),
    "embedding": ((("embed",),), P()),
    "head": ((("head",),), P()),
}


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


@functools.lru_cache(maxsize=None)
def _layout(data, experts=8):
    model = OlmoeModel(OlmoeConfig.from_published(dict(published(2), num_experts=experts)))
    mesh = build_mesh(data=data, devices=jax.devices()[:data])
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0)), model.engine_shardings(mesh)


def test_the_engine_s_layout_has_the_tree_init_makes():
    model, shapes, layout = _layout(4)
    assert model.moe.w_in == "w_gate_up"
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(layout)
    named = {path for paths, _ in LEAF_KINDS.values() for path in paths}
    every = {tuple(getattr(k, "key", getattr(k, "idx", None)) for k in path)
             for path, _ in jax.tree_util.tree_leaves_with_path(layout)}
    # every kind of leaf of a layer is named above, in one layer or the other
    assert {p[2:] for p in every if p[0] == "layers"} == {p[2:] for p in named if p[0] == "layers"}
    assert {p for p in every if p[0] != "layers"} == {p for p in named if p[0] != "layers"}


@pytest.mark.parametrize("kind", sorted(LEAF_KINDS))
def test_the_engine_s_layout_on_four_devices(kind):
    """Experts over ``data`` on their leading axis, a quarter a device; everything else whole on
    every device (the engine's ZeRO layout then claims what is free)."""
    _, shapes, layout = _layout(4)
    paths, spec = LEAF_KINDS[kind]
    for path in paths:
        sharding, shape = _leaf(layout, path), _leaf(shapes, path).shape
        assert sharding.spec == spec and sharding.mesh.shape["data"] == 4, path
        a_device = sharding.shard_shape(shape)
        assert a_device == ((shape[0] // 4, *shape[1:]) if kind == "experts" else shape), path


@pytest.mark.parametrize("data, experts", [(1, 8), (4, 6), (2, 6)], ids=["one-device", "4-over-6-experts", "2-over-6-experts"])
def test_experts_that_data_does_not_divide_stay_whole(data, experts):
    _, shapes, layout = _layout(data, experts)
    split = data > 1 and experts % data == 0
    for path in LEAF_KINDS["experts"][0]:
        sharding, shape = _leaf(layout, path), _leaf(shapes, path).shape
        assert shape[0] == experts and sharding.spec == (P("data") if split else P())
        assert sharding.shard_shape(shape)[0] == (experts // data if split else experts)
    assert all(_leaf(layout, path).spec == P() for kind, (paths, _) in LEAF_KINDS.items() if kind != "experts" for path in paths)
