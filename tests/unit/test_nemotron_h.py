"""Nemotron-H (``deepspeed_tpu/models/nemotron_h.py``) against its plain float32 reference
(``benchmarks/reference/nemotron_h_reference.py``) at toy sizes: the whole model's loss, logits,
expert choices and counts; every leaf's gradient and the rule's update through
``deepspeed_tpu.initialize``; the layers' pieces (the grouped norm, the position-free attention
at head_dim x heads wider than the model); the sixteen held ranges' parts adding up to the
uncut layer; what a recomputed layer keeps by name; the scopes the benchmark reads, pinned in
the compiled programs."""

import collections
import contextlib
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.reference import nemotron_h_reference as ref
from deepspeed_tpu.models import nemotron_h
from deepspeed_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel
from deepspeed_tpu.parallel.moe import RELU2, DroplessMoE
from deepspeed_tpu.utils import spans
from test_ouro import equations_by_path, kernels_in_the_backward, primitives_by_path, residuals_by_shape

PATTERN = "MEM*EMEM*E"          # the first seven run: three mixers, three expert layers, an attention
# One layer of each kind, for the cases that read what a layer keeps, makes again or is named, and
# not how deep the model is: a gradient program of three layers compiles in under half the time of
# seven (ROADMAP.md C10). The whole model's comparisons keep the seven.
SHALLOW = dict(hybrid_override_pattern="ME*", num_hidden_layers=3)


def published(**more):
    return dict(dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=7, hybrid_override_pattern=PATTERN,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=8,
        ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=16, n_routed_experts=4,
        router_width=16, first_expert=4, num_experts_per_tok=3, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=40, norm_topk_prob=True, routed_scaling_factor=2.5,
        layer_norm_epsilon=1e-5, mlp_hidden_act="relu2", mamba_hidden_act="silu", n_group=1,
        topk_group=1, n_shared_experts=1, use_bias=False, use_conv_bias=True,
        tie_word_embeddings=False, time_step_limit=[0, None], model_type="nemotron_h"), **more)


def build(keys=None, **more):
    keys = keys or published()
    more = dict(dict(compute_dtype=jnp.float32, initializer_range=0.1), **more)
    model = NemotronHModel(NemotronHConfig.from_published(keys, **more))
    params = model.init(jax.random.PRNGKey(3))
    # norm weights, D and the biases (the selection bias too) off their initial values, so that
    # a dropped one shows
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape) if p.ndim == 1 else p,
        params)
    return keys, model, params


def batch(seed=1, rows=8, T=40):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 96, (rows, T)).astype(np.int32),
            rng.integers(0, 96, (rows, T)).astype(np.int32))


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------------------------------------ the whole model
STAND_IN = pytest.mark.parametrize("stand_in", [False, True], ids=["absent-left-out", "held-stand-in"])


@STAND_IN
def test_loss_logits_choices_and_counts_match_the_reference(highest, stand_in):
    keys, model, params = build(published(stand_in=stand_in))
    tokens, labels = batch(rows=2)
    want = jax.jit(lambda p: ref.forward(p, tokens, labels, keys, last=16))(params)
    got = jax.jit(lambda p: model.forward_details(p, tokens, labels, 16))(params)
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), rel=2e-5)
    np.testing.assert_allclose(got["logits"], want["logits"], atol=2e-4)
    assert np.array_equal(got["experts"], want["experts"]) and got["experts"].shape == (3, 2, 40, 3)
    assert np.array_equal(got["counts"], want["counts"]) and got["counts"].shape == (3, 16)
    np.testing.assert_allclose(got["layer_in"], want["layer_in"], atol=2e-4)
    np.testing.assert_allclose(jax.nn.sigmoid(got["router_logits"]), want["scores"], atol=1e-5)
    loss, stats = jax.jit(model.apply)(params, tokens, labels)
    assert set(stats) == set(model.device_scalars) | set(model.rule_sums)
    assert stats["moe_rows_here"].shape == stats["moe_bias_abs_max"].shape == (3,)
    # the held experts are 4..7 of 16: what landed here is what the reference's choices say;
    # where they stand in for the absent ones, every assignment
    here = np.sum((want["experts"] >= 4) & (want["experts"] < 8) | stand_in, axis=(1, 2, 3))
    assert np.array_equal(stats["moe_rows_here"], here)
    assert np.array_equal(stats["moe_counts"], want["counts"])
    assert float(stats["moe_counts"].sum()) == 3 * 2 * 40 * 3
    biases = [lp["moe"]["router_bias"] for lp in params["layers"] if "moe" in lp]
    np.testing.assert_allclose(stats["moe_bias_abs_max"], [np.abs(b).max() for b in biases], rtol=1e-6)
    # without labels: every position's logits
    np.testing.assert_allclose(jax.jit(model.apply)(params, tokens)[:, -16:], want["logits"], atol=2e-4)


@STAND_IN
def test_the_engine_computes_the_reference_loss_every_gradient_and_the_rules_update(highest, stand_in):
    """Through ``deepspeed_tpu.initialize`` in float32 with plain SGD: the step's loss is the
    reference's; what one step took off every parameter, over the rate, is its gradient; and
    every selection bias is the reference's ``b + u sign(mean(c) - c)`` from the reference's own
    counts, moved by no gradient and no rate."""
    keys, model, params = build(published(stand_in=stand_in))
    tokens, labels = batch(seed=2)
    def loss_and_counts(p):
        out = ref.forward(p, tokens, labels, keys, last=1)
        return out["loss"], out["counts"]
    (want_loss, counts), want = jax.jit(jax.value_and_grad(loss_and_counts, has_aux=True))(params)
    rate = 0.5
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": False},
        "optimizer": {"type": "SGD", "params": {"lr": rate}}, "steps_per_print": 10 ** 9})
    assert engine.compute_dtype == jnp.float32
    before = jax.device_get(engine.master_params)
    loss = engine(tokens, labels)
    engine.backward(loss)
    engine.step()
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
    after = jax.device_get(engine.master_params)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    biases = 0
    for path, b in jax.tree_util.tree_flatten_with_path(before)[0]:
        a = dict(jax.tree_util.tree_flatten_with_path(after)[0])[path]
        if jax.tree_util.keystr(path).endswith("['router_bias']"):
            assert not np.any(flat_want[path]), "the reference's gradient of a selection bias is zero"
            biases += 1
            continue
        got, w = (np.asarray(b) - np.asarray(a)) / rate, np.asarray(flat_want[path])
        # what a step took off a float32 leaf is known to the leaf's own spacing, over the rate
        coarse = np.sqrt(b.size) * np.spacing(np.abs(np.asarray(b)).max()) / rate
        assert np.linalg.norm(got - w) <= 2e-3 * np.linalg.norm(w) + coarse, jax.tree_util.keystr(path)
    assert biases == 3
    moved = ref.updated_biases(before, counts, keys, model.config.bias_update_rate)
    mine = [lp["moe"]["router_bias"] for lp in after["layers"] if "moe" in lp]
    for got, want_b, was in zip(mine, moved, [lp["moe"]["router_bias"] for lp in before["layers"] if "moe" in lp]):
        np.testing.assert_allclose(got, want_b, rtol=0, atol=1e-7)
        assert np.abs(np.abs(got - was) - 1e-3 * (got != was)).max() < 1e-7 and np.any(got != was)
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    assert set(kept[-1][1]) == set(model.device_scalars)


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "layers-recomputed"])
def test_it_trains_in_bfloat16_through_initialize(remat):
    _, model, params = build(published(**SHALLOW), compute_dtype=jnp.bfloat16, initializer_range=0.02, remat=remat)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 3e-3}}, "steps_per_print": 10 ** 9})
    tokens, _ = batch(seed=4)
    losses = []
    for _ in range(4):
        loss = engine(tokens, np.roll(tokens, -1, 1))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    kept = jax.device_get(spans.recorder().device_scalars(engine._span_engine))
    # the forward reads the selection bias as the master holds it (float32 in the compute copy
    # too: bf16 values lie 1e-3 apart at 0.2, as far as the rule's step): the largest |b| a
    # step's forward saw moves by exactly u a step
    for lp, mp in zip(engine.params["layers"], engine.master_params["layers"]):
        if "moe" in lp:
            assert lp["moe"]["router_bias"].dtype == jnp.float32 and lp["moe"]["router_w"].dtype == jnp.bfloat16
            np.testing.assert_array_equal(np.asarray(lp["moe"]["router_bias"]), np.asarray(mp["moe"]["router_bias"]))
    sizes = [float(np.max(s["moe_bias_abs_max"])) for _, s in kept[-4:]]
    np.testing.assert_allclose(np.abs(np.diff(sizes)), 1e-3, rtol=0, atol=1e-7)


def test_recomputed_layers_give_the_same_loss_and_gradients():
    _, kept, params = build(published(**SHALLOW))
    _, again, _ = build(published(**SHALLOW), remat=True)
    tokens, labels = batch(seed=5, rows=2)
    loss = lambda m: (lambda p, t, l: m.apply(p, t, l)[0])      # noqa: E731
    (l0, g0), (l1, g1) = (jax.jit(jax.value_and_grad(loss(m)))(params, tokens, labels) for m in (kept, again))
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


# ------------------------------------------------------------------ what a recomputed layer keeps
MIXERS, EXPERT_LAYERS, ATTENTIONS = (SHALLOW["hybrid_override_pattern"].count(kind) for kind in "ME*")
ONLY_THE_INPUT = "only-the-input-kept"
ROWS_MADE_AGAIN = "the-experts-rows-made-again"      # the kept set less ``parallel/moe.py``'s name (PR 41's)
STANDING_IN = {False: "absent-left-out", True: "held-stand-in"}


@contextlib.contextmanager
def keeping(what):
    """The model's kept set, ``policy=None`` in its place (``ONLY_THE_INPUT``), or the set without
    the name of an expert layer's row path (``ROWS_MADE_AGAIN``)."""
    with pytest.MonkeyPatch.context() as patch:
        if what == ONLY_THE_INPUT:
            patch.setattr(nemotron_h, "KEPT_BY_A_LAYER", None)
        elif what == ROWS_MADE_AGAIN:
            patch.setattr(nemotron_h, "KEPT_BY_A_LAYER", jax.checkpoint_policies.save_only_these_names(
                "attn_out", "attn_lse", "ssm_in", "ssm_dt", "shared_up"))
        yield


@functools.lru_cache(maxsize=None)
def loss_and_gradients(dtype, what, stand_in=False):
    """The loss and every leaf's gradient, with whole layers recomputed under the kept set or
    under ``policy=None``, or with nothing recomputed (``"layers-kept"``), compiled so that a
    value is the same bits wherever it is made: no rounding to bfloat16 dropped between two
    operations that happen to be fused (``xla_allow_excess_precision``). ``stand_in``: every
    assignment computed by the held experts, as in the cell, where an expert layer's rows go
    through the whole range's form and the kept set names one of its tensors."""
    _, model, params = build(published(stand_in=stand_in, **SHALLOW), remat=what != "layers-kept",
                             compute_dtype=getattr(jnp, dtype))
    tokens, labels = batch(seed=6, rows=2)
    with keeping(what):
        compiled = jax.jit(jax.value_and_grad(lambda p: model.apply(p, tokens, labels)[0])).lower(params).compile(
            compiler_options={"xla_allow_excess_precision": False})
    return jax.device_get(compiled(params))


@pytest.mark.parametrize("stand_in", [False, True], ids=STANDING_IN.values())
@pytest.mark.parametrize("dtype, other", [("float32", ONLY_THE_INPUT), ("bfloat16", ONLY_THE_INPUT),
                                          ("float32", "layers-kept")])
def test_what_a_layer_keeps_changes_no_bit_of_the_loss_or_of_a_gradient(dtype, other, stand_in):
    """The kept tensors are the values the second forward would have made again, in the dtype
    the forward made them in (the shared expert's first product in float32, before its
    activation; where the held experts stand in, the first grouped product's output in the
    compute dtype): the loss and every leaf's gradient are the same bits as under
    ``policy=None``, in float32 and in bfloat16, and as with nothing recomputed; the selection
    bias gets none."""
    (loss, got), (want_loss, want) = (loss_and_gradients(dtype, "the-kept-set", stand_in),
                                      loss_and_gradients(dtype, other, stand_in))
    assert float(loss) == float(want_loss) and np.isfinite(float(loss))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), jax.tree_util.keystr(path)
        assert np.any(np.asarray(a, np.float32)) != ("router_bias" in jax.tree_util.keystr(path))


def rows_in_the_backward(jaxpr):
    """``({primitive: count} of the second forward, {primitive: count} of the layers' own backward)``
    over the grouped products and the gathers of ROWS (an operand of two axes) of the recomputed
    expert layers: what the gradient's top-level ``remat2`` equations hold directly, and what the
    ``jax.checkpoint`` of the whole range's form holds inside them (its remake of the gathered
    rows, and the cotangents)."""
    second, own = collections.Counter(), collections.Counter()
    for outer in jaxpr.eqns:
        if outer.primitive.name == "remat2" and outer.params["differentiated"]:
            for path, eqn in equations_by_path(outer.params["jaxpr"]):
                name = eqn.primitive.name
                if "jit" not in path and (name == "ragged_dot_general"
                                          or (name == "gather" and eqn.invars[0].aval.ndim == 2)):
                    (own if "remat2" in path else second)[name] += 1
    return second, own


@pytest.mark.parametrize("what, stand_in", [
    ("the-kept-set", False), (ONLY_THE_INPUT, False),
    ("the-kept-set", True), (ROWS_MADE_AGAIN, True), (ONLY_THE_INPUT, True)],
    ids=lambda v: STANDING_IN.get(v, v))
def test_the_second_forward_runs_no_flash_kernel_and_a_product_fewer_a_mixer_and_an_expert_layer(what, stand_in):
    """What the backward makes again, by layer: a mixer's second forward runs NO product where
    ``policy=None`` runs one (``w_in``), an expert layer's the router's alone where it runs two
    (the shared expert's first besides), the attention's two (``wq``, ``wkv``) either way and no
    flash forward kernel. A layer's LAST product (``w_out``, ``wo``, the shared ``w_down``) is in
    neither: every layer ends ``x + f(norm(x))`` and nothing in its backward reads that output
    (PERF.md, PR 41). A product's backward is two products; the held experts' are their own.
    Where the held experts stand in (the cell), an expert layer's rows go through the whole
    range's form under its own ``jax.checkpoint``, which keeps the first grouped product's output
    and NOTHING of the second's: the router's weights are in the rows before ``w_down``, so the
    combine is a plain sum whose cotangent is a gather of ``dy`` (PERF.md, PR 49). The layer's
    second forward makes the first product again (a gather, a grouped product) unless the LAYER
    keeps it by name too, as the kept set does (PERF.md, PR 42): then it runs NONE of the row
    path, and never ``w_down``'s product or the gather back, whatever is kept. So an expert layer
    is six grouped products in all under the kept set: two forward and, in the form's own
    backward, each product's two cotangents, beside the remake of the gathered rows and the two
    gathers of cotangents."""
    _, model, params = build(published(stand_in=stand_in, **SHALLOW), remat=True)
    tokens, labels = batch(seed=5, rows=2)
    with keeping(what):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.apply(p, tokens, labels)[0]))(params).jaxpr
    found, kernels = primitives_by_path(jaxpr), kernels_in_the_backward(jaxpr)
    # mixer, expert layer, flash; grouped products, row gathers of an expert layer's second forward
    again = {"the-kept-set": (0, 1, 0, 0, 0), ROWS_MADE_AGAIN: (0, 1, 0, 1, 1), ONLY_THE_INPUT: (1, 2, 1, 1, 1)}[what]
    assert found[("remat2",), "dot_general"] == (MIXERS * (again[0] + 2 * 2) + ATTENTIONS * (2 + 2 * 3)
                                                 + EXPERT_LAYERS * (again[1] + 2 * 3))
    assert +kernels == +collections.Counter({
        "ds_flash_fwd": ATTENTIONS * again[2], "ds_flash_bwd_dkv": ATTENTIONS,
        "ds_ssd_scan_fwd": MIXERS, "ds_ssd_scan_bwd": MIXERS})
    # the first forward is the same either way: every kernel once
    assert found[(), "pallas_call"] == MIXERS + ATTENTIONS
    if stand_in:
        second, own = rows_in_the_backward(jaxpr)
        assert +second == +collections.Counter({"ragged_dot_general": EXPERT_LAYERS * again[3],
                                                "gather": EXPERT_LAYERS * again[4]})
        assert own == {"ragged_dot_general": EXPERT_LAYERS * 4, "gather": EXPERT_LAYERS * 3}
        grouped = sum(n for (_, name), n in found.items() if name == "ragged_dot_general")
        assert grouped == EXPERT_LAYERS * (2 + again[3] + 4)
        assert not any(name in ("scan", "cond", "while") for path, name in found
                       if "remat2" in path and "jit" not in path and "pallas_call" not in path)


@functools.lru_cache(maxsize=None)
def kept_by_the_layers(what, stand_in=False):
    """``{shape: count}`` of the activations that the layers of two sequences keep for their
    backward, an expert layer's flat rows among them (80 tokens, 240 assignments)."""
    _, model, params = build(published(stand_in=stand_in, **SHALLOW), remat=True)
    tokens, _ = batch(seed=5, rows=2)
    with keeping(what):
        return residuals_by_shape(lambda p: model._backbone(p, tokens)[0], params, rows=(3, 240))


@pytest.mark.parametrize("shape, count", [
    ((2, 4, 40, 16), ATTENTIONS), ((2, 4, 40), ATTENTIONS), ((2, 40, 2 * 32 + 2 * 2 * 16 + 4), MIXERS),
    ((2, 40, 4), MIXERS), ((2, 40, 40), EXPERT_LAYERS), ((2, 40, 32), MIXERS + EXPERT_LAYERS + ATTENTIONS + 1),
    ((240, 24), EXPERT_LAYERS), ((3, 80, 32), 0), ((240, 32), 0)],
    ids=["attn_out", "attn_lse", "ssm_in", "ssm_dt", "shared_up", "input", "ds_moe_gate_up",
         "no-tokens-expert-outputs", "no-second-products-rows"])
def test_a_layer_keeps_each_named_tensor_once(shape, count):
    """The residuals of the layers by shape: the attention keeps ONE kernel output (no second
    ``attn_out`` at the call) and ONE set of row sums, a mixer its first product's output once
    in the compute dtype and the ``dt`` columns once, an expert layer the shared expert's first
    product's output once, every layer its input, and nothing else (the last layer's output is
    ``norm_f``'s to keep); under ``policy=None`` the inputs alone. Where the held experts stand
    in, an expert layer keeps the first grouped product's output ``[n k, F]`` besides, once, and
    nothing of the second's: neither each token's ``k`` expert outputs ``[k, n, H]`` nor the sorted
    rows ``[n k, H]`` (no backward reads them since PR 49, and no name is there to keep them by);
    where they do not, the passes name nothing and keep nothing."""
    rows = shape == (240, 24)
    found = kept_by_the_layers("the-kept-set", True)
    assert found[shape] == count, found
    assert sum(found.values()) == 3 * MIXERS + 3 * EXPERT_LAYERS + 3 * ATTENTIONS + 1, found
    left_out = kept_by_the_layers("the-kept-set")
    assert left_out[shape] == (0 if rows else count), left_out
    assert sum(left_out.values()) == 3 * MIXERS + 2 * EXPERT_LAYERS + 3 * ATTENTIONS + 1, left_out
    assert kept_by_the_layers(ROWS_MADE_AGAIN, True) == left_out
    assert kept_by_the_layers(ONLY_THE_INPUT, stand_in=rows) == {(2, 40, 32): MIXERS + EXPERT_LAYERS + ATTENTIONS + 1}


def test_the_names_are_nothing_where_no_layer_is_recomputed(monkeypatch):
    """With ``remat=False`` (the reference comparison's path) a name lowers to nothing: the
    gradient program is the same text with every name of this file taken out (but for the
    numbers JAX gives its private functions, which count the traces before)."""
    _, model, params = build(published(**SHALLOW), compute_dtype=jnp.bfloat16)
    tokens, labels = batch(seed=5, rows=2)
    lowered = lambda: re.sub(r"@(\w+?)_\d+\b", r"@\1", jax.jit(jax.value_and_grad(      # noqa: E731
        lambda p: model.apply(p, tokens, labels)[0])).lower(params).as_text())
    named = lowered()
    monkeypatch.setattr(nemotron_h, "checkpoint_name", lambda x, name: x)
    assert lowered() == named and "stablehlo.dot_general" in named


def test_from_published_parses_the_pattern_and_refuses_what_the_model_cannot_do():
    c = NemotronHConfig.from_published(published())
    assert c.kinds == "MEM*EME" and c.hybrid_override_pattern == PATTERN
    whole = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    c = NemotronHConfig.from_published(published(hybrid_override_pattern=whole, num_hidden_layers=9))
    assert c.kinds == "MEMEM*EME" and (c.kinds.count("M"), c.kinds.count("E"), c.kinds.count("*")) == (4, 4, 1)
    with pytest.raises(AssertionError, match="unknown layer kinds"):
        NemotronHConfig.from_published(published(hybrid_override_pattern="MEM-EME"))       # a dense MLP layer
    with pytest.raises(AssertionError, match="a pattern of 3 for 7 layers"):
        NemotronHConfig.from_published(published(hybrid_override_pattern="MEM"))
    for wrong in (dict(mlp_hidden_act="silu"), dict(tie_word_embeddings=True), dict(n_group=2),
                  dict(use_bias=True), dict(use_conv_bias=False), dict(n_shared_experts=2),
                  dict(time_step_limit=[0, 0.5])):
        with pytest.raises(AssertionError):
            NemotronHConfig.from_published(published(**wrong))


# ------------------------------------------------------------------ the layers' pieces
def test_the_mixers_norm_is_over_each_group_and_the_gate_comes_first(highest):
    keys, model, params = build()
    mp = params["layers"][0]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, 32))
    got, want = jax.jit(model.mamba_mixer)(x, mp), ref.mamba_mixer(x, mp, keys)
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)
    # a norm over all channels (Granite's, one group) is another result; so is another group's B
    assert np.linalg.norm(ref.mamba_mixer(x, mp, keys, norm_groups=1) - want) > 1e-2 * np.linalg.norm(want)
    assert np.linalg.norm(ref.mamba_mixer(x, mp, keys, shift_groups=1) - want) > 1e-2 * np.linalg.norm(want)
    xs, dt, B, C, z = model.mamba_inputs(x, mp)
    assert B.shape == C.shape == (2, 40, 2, 16) and xs.shape == (2, 40, 4, 8) and z.shape == (2, 40, 32)


def test_the_attention_is_position_free_and_wider_than_the_model(highest):
    keys, model, params = build()
    mp = params["layers"][3]["mixer"]
    assert mp["wq"].shape == (32, 4 * 16) and mp["wkv"].shape == (32, 2 * 2 * 16) and mp["wo"].shape == (64, 32)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, 32))
    got, want = jax.jit(model.attention)(x, mp), ref.attention(x, mp, keys)
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)
    # the earliest token attends to itself alone: its output is its value through Wo
    v = jnp.split((x @ mp["wkv"]).reshape(2, 40, 4, 16), 2, axis=2)[1]
    np.testing.assert_allclose(got[:, 0], jnp.repeat(v[:, 0], 2, axis=1).reshape(2, 64) @ mp["wo"], atol=1e-5)


# ------------------------------------------------------------------ the share
def test_the_sixteen_held_ranges_add_up_to_the_uncut_layer(highest):
    """128 experts as sixteen ranges of 8 is the deployment; here 32 as sixteen of 2: the
    sixteen parts of the routed result, with the shared expert counted once, add up to what the
    uncut reference gives for the whole expert layer, and so do the gradients; every assignment
    lands on exactly one range, and every range returns the same counts."""
    H, F, S, E, k = 32, 24, 40, 32, 6
    keys = dict(n_routed_experts=E, num_experts_per_tok=k, norm_topk_prob=True, routed_scaling_factor=2.5)
    whole = DroplessMoE(H, F, E, k, norm_topk_prob=True, router=("sigmoid_bias", 2.5), experts=RELU2)
    params = whole.init(jax.random.PRNGKey(0), 0.4)
    params["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (E,))
    shared = {"w_up": jax.random.normal(jax.random.PRNGKey(5), (H, S)) * 0.3,
              "w_down": jax.random.normal(jax.random.PRNGKey(6), (S, H)) * 0.3}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, H))
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    flat = lambda a: a.reshape(-1, H)                              # noqa: E731

    def uncut(x, moe):
        return ref.expert_layer(flat(x), {"moe": moe, "shared": shared}, keys)[0].reshape(x.shape)

    def shared_alone(x):
        return (ref.relu2(flat(x) @ shared["w_up"]) @ shared["w_down"]).reshape(x.shape)

    want = uncut(x, params)
    want_dx, want_dp = jax.grad(lambda x, p: jnp.sum(uncut(x, p) * cot), argnums=(0, 1))(x, params)
    want_counts = ref.assignments(ref.router(flat(x), params, keys)[0], E)
    total, rows, dx, d_router = shared_alone(x), 0.0, jax.grad(lambda x: jnp.sum(shared_alone(x) * cot))(x), 0.0
    for first in range(0, E, 2):
        part = DroplessMoE(H, F, E, k, norm_topk_prob=True, held=(first, 2),
                           router=("sigmoid_bias", 2.5), experts=RELU2)
        mine = dict(params, w_up=params["w_up"][first:first + 2], w_down=params["w_down"][first:first + 2])
        # one forward and its pull-back a range (an ``apply`` and a ``grad`` were two forwards)
        y, pull, (aux, stats) = jax.vjp(lambda x, p: (lambda out: (out[0], out[1:]))(part.apply(p, x)),
                                        x, mine, has_aux=True)
        g_x, g_p = pull(cot)
        total, rows, dx = total + y, rows + float(stats["rows_here"]), dx + g_x
        d_router = d_router + g_p["router_w"]
        for name in ("w_up", "w_down"):
            np.testing.assert_allclose(g_p[name], want_dp[name][first:first + 2], atol=2e-4)
        assert float(aux) == 0.0 and not np.any(g_p["router_bias"])
        assert np.array_equal(stats["counts"], want_counts)
    assert rows == 2 * 24 * k                          # every assignment landed somewhere, once
    np.testing.assert_allclose(total, want, atol=3e-4)
    np.testing.assert_allclose(dx, want_dx, atol=3e-4)
    np.testing.assert_allclose(d_router, want_dp["router_w"], atol=3e-4)


# ------------------------------------------------------------------ the scopes
def test_the_scopes_the_benchmark_reads_are_in_the_compiled_programs():
    _, model, params = build(published(**SHALLOW), remat=True)
    tokens, labels = batch(seed=6, rows=2)
    text = jax.jit(jax.grad(lambda p, t, l: model.apply(p, t, l)[0])).lower(
        params, tokens, labels).compile().as_text()
    # a mixer is its layer's attention part, an expert layer its MLP part (the compiled
    # program's op names carry the whole path, through the passes' loop and its branch too)
    for path in ("ds_attn/ds_ssm/ds_conv", "ds_attn/ds_ssm/ds_ssd_scan", r"ds_mlp/\S*ds_moe_router",
                 r"ds_mlp/\S*ds_moe_dispatch", r"ds_mlp/\S*ds_moe_experts", r"ds_mlp/\S*ds_moe_combine",
                 "ds_mlp/ds_moe_shared", "ds_embed", "ds_loss", r"rematted_computation/ds_attn/ds_ssm/ds_ssd_scan",
                 "rematted_computation/ds_mlp/ds_moe_shared"):
        assert re.search(path, text), path
    assert not re.search(r"ds_mlp/\S*ds_ssm", text) and not re.search(r"ds_attn/\S*ds_moe", text)
    # the held experts' passes are made again by their own backward, never by the layer's
    # second forward: nothing needs that forward's result
    assert not re.search(r"rematted_computation/ds_mlp/\S*ds_moe_experts", text)
    # standing in (the cell), the rows go through the whole range's form under its own checkpoint:
    # the same scopes; its backward gathers the rows again and makes the activation again under
    # ITS ``rematted_computation``, which ``recompute_time_share`` reads; the layer's second
    # forward runs the router and the sort and nothing of the rows: no gather and no ``w_up`` (the
    # layer keeps that product's output by name), no ``w_down`` and no gather back (no backward
    # reads the second product's output: the router's weights are in the rows before it, PR 49)
    _, stands_in, its_params = build(published(stand_in=True, **SHALLOW), remat=True)
    text = jax.jit(jax.grad(lambda p, t, l: stands_in.apply(p, t, l)[0])).lower(
        its_params, tokens, labels).compile().as_text()
    for path in (r"ds_mlp/ds_moe_router", r"ds_mlp/ds_moe_dispatch", r"ds_mlp/checkpoint/ds_moe_dispatch",
                 r"ds_mlp/checkpoint/ds_moe_experts", r"ds_mlp/checkpoint/ds_moe_combine",
                 r"ds_mlp/checkpoint/rematted_computation/ds_moe_dispatch",
                 r"ds_mlp/checkpoint/rematted_computation/ds_moe_experts",
                 r"rematted_computation/ds_mlp/ds_moe_router", r"rematted_computation/ds_mlp/ds_moe_dispatch"):
        assert re.search(path, text), path
    assert not re.search(r"rematted_computation/ds_mlp/ds_moe_(experts|combine)", text)
    # the rule runs inside the update program, under the optimizer's scope and its own
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, "steps_per_print": 10 ** 9})
    update = [(name, jitted, args) for name, jitted, args, _ in engine.lint_programs(batch(seed=6))
              if name == "apply_update"]
    (_, jitted, args), = update
    text = jitted.lower(*args).as_text(debug_info=True)
    assert "ds_apply_update/" in text and "ds_moe_bias_update" in text
    assert text.index("ds_apply_update") < text.index("ds_moe_bias_update")
    assert "ds_apply_update/cond/branch_1_fun/ds_moe_bias_update" in text or \
        "ds_apply_update/cond/branch_0_fun/ds_moe_bias_update" in text
