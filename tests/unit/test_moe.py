"""Mixture-of-Experts + expert parallelism on the 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.parallel.moe import MoELayer, moe_apply_sharded

H, F, E = 16, 32, 8


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(data=1, model=8, pipe=1)


def oracle(layer, params, x2):
    """Per-token reference: each token through its argmax expert's MLP, weighted
    by the gate prob (assumes capacity large enough that nothing drops)."""
    logits = x2.astype(np.float32) @ np.asarray(params["gate_w"])
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    idx = np.argmax(np.asarray(probs), axis=-1)
    out = np.zeros_like(np.asarray(x2, np.float32))
    for n, e in enumerate(idx):
        h = np.asarray(x2[n], np.float32) @ np.asarray(params["w_in"][e]) + \
            np.asarray(params["b_in"][e])
        h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
        y = h @ np.asarray(params["w_out"][e]) + np.asarray(params["b_out"][e])
        out[n] = float(np.asarray(probs)[n, e]) * y
    return out


def sharded(layer, mesh):
    """``moe_apply_sharded`` as ONE compiled program, as the engine runs it. Called eagerly, a
    ``shard_map`` compiles every primitive of its body as an 8-device program of its own (some
    four hundred for the layer's value and gradient)."""
    return jax.jit(lambda params, x: moe_apply_sharded(layer, mesh, params, x))


def test_dense_dispatch_matches_per_token_oracle():
    layer = MoELayer(H, F, E, capacity_factor=8.0)  # no drops
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, H), jnp.float32)
    y, aux = layer.apply(params, x)
    np.testing.assert_allclose(np.asarray(y), oracle(layer, params, x),
                               rtol=1e-4, atol=1e-5)
    assert float(aux) > 0  # E * sum f*p >= 1 by Cauchy-Schwarz, > 0 always


def test_capacity_drops_overflow_tokens():
    """With capacity 1 per expert, later tokens routed to a full expert must
    produce ZERO output (they ride the residual in a real block)."""
    layer = MoELayer(H, F, E, capacity_factor=1e-9)  # capacity clamps to 1
    params = layer.init(jax.random.PRNGKey(0))
    # two identical tokens route to the same expert; the second must drop
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(2), (1, H)), (2, 1))
    y, _ = layer.apply(params, x)
    assert not np.allclose(np.asarray(y[0]), 0.0)
    np.testing.assert_allclose(np.asarray(y[1]), 0.0, atol=1e-7)


def test_expert_parallel_matches_dense_dispatch(mesh):
    """8-way expert-parallel (all_to_all dispatch) must equal the single-program
    dense dispatch bit-for-bit at fp32 — fwd AND grads."""
    dense = MoELayer(H, F, E, capacity_factor=8.0)
    ep = MoELayer(H, F, E, capacity_factor=8.0, expert_axis="model")
    params = dense.init(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 8, H), jnp.float32)

    y_d, aux_d = dense.apply(params, x)
    y_p, aux_p = sharded(ep, mesh)(params, x)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_d), rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(float(aux_p), float(aux_d), rtol=1e-5)

    def loss_d(p):
        y, aux = dense.apply(p, x)
        return jnp.sum(y ** 2) + 0.01 * aux

    def loss_p(p):
        y, aux = moe_apply_sharded(ep, mesh, p, x)
        return jnp.sum(y ** 2) + 0.01 * aux

    g_d = jax.grad(loss_d)(params)
    g_p = jax.jit(jax.grad(loss_p))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=5e-4, atol=1e-5),
        g_p, g_d)


def test_expert_parallel_emits_all_to_all(mesh):
    from deepspeed_tpu.utils.hlo import collective_counts, optimized_hlo

    ep = MoELayer(H, F, E, capacity_factor=2.0, expert_axis="model")
    params = ep.init(jax.random.PRNGKey(5))
    x = jnp.zeros((4, 8, H), jnp.float32)
    j = jax.jit(lambda p, x: moe_apply_sharded(ep, mesh, p, x)[0])
    counts = collective_counts(optimized_hlo(j, params, x))
    assert counts.get("all-to-all", 0) >= 2, \
        f"EP dispatch+return should be two all_to_alls: {counts}"


def test_moe_trains_through_engine(mesh):
    """A 2-layer MoE MLP regression model trains through DeepSpeedEngine with the
    aux loss added — loss decreases (experts + gate learn)."""
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    ep = MoELayer(H, F, E, capacity_factor=2.0, expert_axis="model")

    class Model:
        def init(self, rng):
            k1, k2 = jax.random.split(rng)
            return {"moe": ep.init(k1),
                    "head": jax.random.normal(k2, (H, H), jnp.float32) * 0.3}

        def apply(self, params, x, y):
            h, aux = moe_apply_sharded(ep, mesh, params["moe"], x)
            pred = jnp.tanh(h) @ params["head"]
            return jnp.mean((pred - y) ** 2) + 0.01 * aux

    model = Model()
    engine = DeepSpeedEngine(
        model=model, model_parameters=model.init(jax.random.PRNGKey(6)), mesh=mesh,
        config_params={"train_batch_size": 32, "train_micro_batch_size_per_gpu": 32,
                       "gradient_accumulation_steps": 1, "steps_per_print": 100,
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(H, H)).astype(np.float32) * 0.4
    losses = []
    for _ in range(50):
        x = rng.normal(size=(32, H)).astype(np.float32)
        y = np.tanh(x @ w_true)
        loss = engine(jnp.asarray(x), jnp.asarray(y))
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])


def test_gpt2_moe_trains_through_engine():
    """GPT2Config(moe_experts=..) alternates switch-MoE FFN blocks; the model
    trains through DeepSpeedEngine with ZeRO-2 and the aux loss folded in."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=128, n_positions=64, n_embd=32, n_layer=4, n_head=2,
                     compute_dtype=jnp.float32, moe_experts=4, moe_every=2,
                     moe_capacity_factor=2.0)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert "moe" in params["blocks"][1] and "mlp" in params["blocks"][0]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config_params={"train_batch_size": 16, "steps_per_print": 100,
                       "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
                       "zero_optimization": {"stage": 2}})
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 128, size=(16, 64)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    losses = []
    for _ in range(25):
        loss = engine(toks, labels)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])


def test_gpt2_moe_gspmd_expert_sharding_matches_replicated(mesh):
    """GSPMD expert parallelism: expert weights sharded over 'model' must give the
    same loss/grads as fully replicated params (XLA partitions the batched expert
    einsums; the math is identical)."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32, moe_experts=8, moe_every=1,
                     moe_capacity_factor=4.0)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 64, (4, 32)), jnp.int32)
    labels = jnp.roll(toks, -1, axis=1)

    l_repl = float(jax.jit(model.apply)(params, toks, labels))
    sh = model.param_shardings(mesh)
    assert not sh["blocks"][0]["moe"]["w_in"].is_fully_replicated
    params_sh = jax.device_put(params, sh)
    l_shard = float(jax.jit(model.apply)(params_sh, toks, labels))
    np.testing.assert_allclose(l_shard, l_repl, rtol=2e-5)

    g_r = jax.jit(jax.grad(model.apply))(params, toks, labels)
    g_s = jax.jit(jax.grad(model.apply))(params_sh, toks, labels)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=5e-4, atol=1e-5),
        g_s, g_r)


def test_gpt2_moe_composes_with_sequence_parallelism():
    """MoE + ring-attention sequence parallelism: dense dispatch routes each
    rank's local chunk (per-chunk capacity), aux folds into the pmean'd loss,
    and the sp loss matches the dense model when capacity is ample."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    sp_mesh = build_mesh(data=8, model=1, pipe=1)
    # aux weight 0 for the exact-parity check: the TASK loss is identical with
    # ample capacity; the aux term differs at second order (per-chunk E*sum(f·p)
    # means over ranks vs global statistics)
    cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=32, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32, moe_experts=4, moe_every=1,
                     moe_capacity_factor=8.0, moe_aux_weight=0.0)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    toks = jnp.asarray(np.random.default_rng(5).integers(0, 64, (2, 64)), jnp.int32)
    labels = jnp.roll(toks, -1, axis=1)
    sp_loss = model.sequence_parallel_loss_fn(sp_mesh, "data")
    l_sp = float(jax.jit(sp_loss)(params, toks, labels))
    l_ref = float(jax.jit(model.apply)(params, toks, labels))
    np.testing.assert_allclose(l_sp, l_ref, rtol=2e-5)

    # with the aux term on, sp and dense agree closely (the balancing statistics
    # are chunk-local) and grads stay finite
    cfg2 = GPT2Config(vocab_size=64, n_positions=64, n_embd=32, n_layer=2, n_head=2,
                      compute_dtype=jnp.float32, moe_experts=4, moe_every=1,
                      moe_capacity_factor=8.0)
    model2 = GPT2Model(cfg2)
    sp_loss2 = model2.sequence_parallel_loss_fn(sp_mesh, "data")
    l_sp2 = float(jax.jit(sp_loss2)(params, toks, labels))
    np.testing.assert_allclose(l_sp2, float(jax.jit(model2.apply)(params, toks, labels)),
                               rtol=1e-3)
    g = jax.jit(jax.grad(sp_loss2))(params, toks, labels)
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree_util.tree_leaves(g))


def test_grouped_routing_matches_ungrouped_outputs():
    """Grouped dispatch (the O(N*g) memory form) must produce the same outputs as
    one whole-batch group when capacity is ample — only the aux statistics are
    computed per group."""
    dense = MoELayer(H, F, E, capacity_factor=8.0)
    grouped = MoELayer(H, F, E, capacity_factor=8.0, group_size=8)
    params = dense.init(jax.random.PRNGKey(9))
    x = jax.random.normal(jax.random.PRNGKey(10), (32, H), jnp.float32)
    y_d, _ = dense.apply(params, x)
    y_g, aux_g = grouped.apply(params, x)
    np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_d), rtol=1e-5,
                               atol=1e-6)
    assert float(aux_g) > 0


def test_top2_gshard_matches_per_token_oracle():
    """top_k=2 (GShard): each token through its two highest-prob experts, gate
    weights normalized over the pair — per-token oracle parity with ample
    capacity; top-2 also runs through the expert-parallel path."""
    layer = MoELayer(H, F, E, capacity_factor=16.0, top_k=2)
    params = layer.init(jax.random.PRNGKey(11))
    x = jax.random.normal(jax.random.PRNGKey(12), (24, H), jnp.float32)
    y, aux = layer.apply(params, x)

    probs = np.asarray(jax.nn.softmax(
        jnp.asarray(np.asarray(x) @ np.asarray(params["gate_w"])), axis=-1))
    ref = np.zeros((24, H), np.float32)
    for n in range(24):
        order = np.argsort(-probs[n])
        e1, e2 = int(order[0]), int(order[1])
        denom = probs[n, e1] + probs[n, e2]
        for e, w in ((e1, probs[n, e1] / denom), (e2, probs[n, e2] / denom)):
            h = np.asarray(x[n]) @ np.asarray(params["w_in"][e]) + \
                np.asarray(params["b_in"][e])
            h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
            ref[n] += w * (h @ np.asarray(params["w_out"][e]) +
                           np.asarray(params["b_out"][e]))
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-5)
    assert float(aux) > 0


@pytest.mark.parametrize("top_k", [1, 2])
def test_scatter_dispatch_matches_einsum(mesh, top_k):
    """The scatter/gather dispatch (row scatter-add + row gather — flops-cheap,
    but slower than the default einsum on TPU, see PERF.md)
    must reproduce the dense one-hot einsum dispatch bit-for-bit in fp32 —
    dense apply, tight capacity (drops exercised), and the expert-parallel
    all_to_all path; gradients too."""
    cf = 0.6  # tight: forces capacity drops both modes must agree on
    kw = dict(hidden=H, ffn_dim=F, num_experts=E, capacity_factor=cf, top_k=top_k)
    l_sc = MoELayer(**kw, dispatch="scatter")
    l_ei = MoELayer(**kw, dispatch="einsum")
    params = l_sc.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, H), jnp.float32)

    y_sc, aux_sc = l_sc.apply(params, x)
    y_ei, aux_ei = l_ei.apply(params, x)
    np.testing.assert_allclose(np.asarray(y_sc), np.asarray(y_ei),
                               rtol=1e-5, atol=1e-5)
    assert float(aux_sc) == pytest.approx(float(aux_ei))

    g_sc = jax.grad(lambda p: jnp.sum(l_sc.apply(p, x)[0] ** 2))(params)
    g_ei = jax.grad(lambda p: jnp.sum(l_ei.apply(p, x)[0] ** 2))(params)
    for k in g_sc:
        np.testing.assert_allclose(np.asarray(g_sc[k]), np.asarray(g_ei[k]),
                                   rtol=1e-4, atol=1e-4)

    # expert-parallel: both modes through the all_to_all path
    l_sc_ep = MoELayer(**kw, dispatch="scatter", expert_axis="model")
    l_ei_ep = MoELayer(**kw, dispatch="einsum", expert_axis="model")
    y_sc_ep, _ = sharded(l_sc_ep, mesh)(params, x)
    y_ei_ep, _ = sharded(l_ei_ep, mesh)(params, x)
    np.testing.assert_allclose(np.asarray(y_sc_ep), np.asarray(y_ei_ep),
                               rtol=1e-5, atol=1e-5)


def test_top2_second_choice_queues_after_first(mesh):
    """Expert-parallel top-2 equals the dense-dispatch top-2 (the all_to_all path
    is routing-agnostic), and grads stay finite."""
    dense = MoELayer(H, F, E, capacity_factor=16.0, top_k=2)
    ep = MoELayer(H, F, E, capacity_factor=16.0, top_k=2, expert_axis="model")
    params = dense.init(jax.random.PRNGKey(13))
    x = jax.random.normal(jax.random.PRNGKey(14), (4, 8, H), jnp.float32)
    y_d, _ = dense.apply(params, x)
    y_p, _ = sharded(ep, mesh)(params, x)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_d), rtol=2e-5,
                               atol=2e-6)
    g = jax.jit(jax.grad(lambda p: jnp.sum(moe_apply_sharded(ep, mesh, p, x)[0] ** 2)))(params)
    assert all(bool(jnp.isfinite(v).all()) for v in jax.tree_util.tree_leaves(g))


def test_top2_drop_priority_under_tight_capacity():
    """Under contention the SECOND choice drops, never the first (GShard's
    two-pass assignment): with capacity 1 per expert and crossed preferences,
    each token keeps exactly its first-choice contribution."""
    layer = MoELayer(4, 8, 2, capacity_factor=1e-9, top_k=2)  # capacity clamps to 1
    params = layer.init(jax.random.PRNGKey(15))
    # gate logits chosen so x0 -> top1 expert0 / top2 expert1, x1 -> the reverse
    gate = np.zeros((4, 2), np.float32)
    gate[0] = [3.0, 1.0]
    gate[1] = [1.0, 3.0]
    params = dict(params, gate_w=jnp.asarray(gate))
    x = jnp.asarray(np.eye(2, 4, dtype=np.float32))  # x0 = e0, x1 = e1
    y, _ = layer.apply(params, x)

    probs = np.asarray(jax.nn.softmax(jnp.asarray(np.asarray(x) @ gate), axis=-1))

    def expert_out(e, xn):
        h = np.asarray(jax.nn.gelu(jnp.asarray(
            xn @ np.asarray(params["w_in"][e]) + np.asarray(params["b_in"][e]))))
        return h @ np.asarray(params["w_out"][e]) + np.asarray(params["b_out"][e])

    # each expert's single slot goes to its FIRST-choice token; the crossed
    # second choices (x0->e1, x1->e0) must both drop, leaving the normalized
    # first-choice contribution only
    for n, e1 in ((0, 0), (1, 1)):
        w1 = probs[n, e1] / (probs[n, 0] + probs[n, 1])
        np.testing.assert_allclose(np.asarray(y[n]),
                                   w1 * expert_out(e1, np.asarray(x[n])),
                                   rtol=1e-5, atol=1e-6)
