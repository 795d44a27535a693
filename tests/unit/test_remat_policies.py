"""Remat-policy classification (CPU guard for the TPU-side replay probe).

On the chip the attention policies compiled replay-free (earlier rig, not
re-measured); this suite pins the POLICY CALLABLES' decisions
per-equation in CI (the width-signature logic that distinguishes the fused-qkv
and square projections must not drift)."""

import jax
import jax.numpy as jnp
import pytest
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    checkpoint_wrapper, _flash_policy)

E = 8


def _eqns(fn, *args):
    return jax.make_jaxpr(fn)(*args).jaxpr.eqns


def _decide(policy, eqn):
    return bool(policy(eqn.primitive, *[v.aval for v in eqn.invars], **eqn.params))


def _dot_eqn(n_in, n_out):
    x = jnp.ones((4, n_in))
    w = jnp.ones((n_in, n_out))
    (eqn,) = [e for e in _eqns(lambda x, w: x @ w, x, w)
              if e.primitive.name == "dot_general"]
    return eqn


def test_flash_policy_saves_named_attention_residuals():
    pol = _flash_policy()
    (eqn,) = [e for e in _eqns(lambda x: checkpoint_name(x, "attn_out"), jnp.ones((2,)))
              if e.primitive.name == "name"]
    assert _decide(pol, eqn)
    (eqn,) = [e for e in _eqns(lambda x: checkpoint_name(x, "attn_lse"), jnp.ones((2,)))
              if e.primitive.name == "name"]
    assert _decide(pol, eqn)
    (eqn,) = [e for e in _eqns(lambda x: checkpoint_name(x, "other"), jnp.ones((2,)))
              if e.primitive.name == "name"]
    assert not _decide(pol, eqn)


@pytest.mark.parametrize("exclude,keep_qkv,qkv,square,fc,head", [
    # 'flash': drop the fused-qkv save, keep everything else
    ("qkv", False, False, True, True, True),
    # 'dots+attn-lean': keep qkv, drop the square attention projection
    ("square", True, True, False, True, True),
])
def test_flash_policy_width_signatures(exclude, keep_qkv, qkv, square, fc, head):
    pol = _flash_policy(exclude=exclude, keep_qkv=keep_qkv)
    assert _decide(pol, _dot_eqn(E, 3 * E)) == qkv        # fused qkv [E, 3E]
    assert _decide(pol, _dot_eqn(E, E)) == square          # attn proj [E, E]
    assert _decide(pol, _dot_eqn(E, 4 * E)) == fc          # mlp fc [E, 4E]
    assert _decide(pol, _dot_eqn(4 * E, E)) == head        # mlp proj [4E, E]


def test_flash_policy_refuses_colliding_qkv_widths():
    """Two DISTINCT shapes in the same exclusion class mean the width heuristic
    is ambiguous for this model — the policy must fail loudly, not silently
    drop one dot's save."""
    pol = _flash_policy(exclude="qkv", keep_qkv=False)
    assert not _decide(pol, _dot_eqn(E, 3 * E))
    with pytest.raises(ValueError, match="width-signature collision"):
        _decide(pol, _dot_eqn(2 * E, 6 * E))  # second, different fused-qkv width


def test_flash_policy_refuses_foreign_square_projection():
    """A square dot whose width disagrees with the qkv-implied embed width is
    NOT the attention output projection (e.g. an MoE/router square) and must
    not be silently excluded."""
    pol = _flash_policy(exclude="square", keep_qkv=True)
    assert _decide(pol, _dot_eqn(E, 3 * E))  # establishes embed width E
    with pytest.raises(ValueError, match="MoE/router square"):
        _decide(pol, _dot_eqn(2 * E, 2 * E))  # square, but at width 2E != E


def test_flash_policy_collision_raises_through_wrapper():
    """End-to-end: tracing a checkpointed block that contains a foreign square
    dot under 'dots+attn-lean' raises at trace time instead of mis-saving."""
    w_qkv = jnp.ones((E, 3 * E))
    w_moe = jnp.ones((2 * E, 2 * E))

    def block(x):
        h = x @ w_qkv                      # fused-qkv signature: embed width E
        r = jnp.ones((4, 2 * E)) @ w_moe   # square at 2E: not the attn out proj
        return h.sum() + r.sum()

    fn = checkpoint_wrapper(block, policy="dots+attn-lean")
    with pytest.raises(ValueError, match="width-signature collision"):
        jax.grad(lambda x: fn(x))(jnp.ones((4, E)))


def test_wrapper_rejects_unknown_policy():
    with pytest.raises(ValueError, match="unknown remat policy"):
        checkpoint_wrapper(lambda x: x, policy="not-a-policy")(jnp.ones((2,)))


@pytest.mark.parametrize("name", ["dots", "attn", "dots+attn", "flash",
                                  "dots+attn-lean", None])
def test_all_named_policies_differentiate(name):
    """Every named policy must produce a working checkpointed grad (numerics
    equal to the un-checkpointed oracle)."""
    w = jnp.ones((4, 4)) * 0.3

    def block(x):
        return jnp.tanh(x @ w).sum()

    x = jnp.arange(4.0).reshape(1, 4)
    g_ref = jax.grad(lambda x: block(x))(x)
    g = jax.grad(lambda x: checkpoint_wrapper(block, policy=name)(x))(x)
    assert jnp.allclose(g, g_ref)


# ---------------------------------------------------------------- tag gating
def _dot_decisions(pol, fn, *args):
    """Feed EVERY eqn to the policy in trace order (the announcements are
    stateful) and return the decisions for the dot_general eqns."""
    out = []
    for eqn in _eqns(fn, *args):
        d = _decide(pol, eqn)
        if eqn.primitive.name == "dot_general":
            out.append(d)
    return out


def test_flash_policy_tag_gated_qkv_exclusion():
    """Announced dots classify by tag, and the width heuristic is OFF in a
    tagged trace: an untagged dot with a colliding qkv width signature keeps its
    save and raises no collision error."""
    w_qkv = jnp.ones((E, 3 * E))
    w_other = jnp.ones((2 * E, 6 * E))  # same 3x signature, different width

    def block(x, y):
        t = checkpoint_name(x, "ds_dot:qkv")
        return (t @ w_qkv).sum() + (y @ w_other).sum()

    pol = _flash_policy(exclude="qkv", keep_qkv=False)
    decisions = _dot_decisions(pol, block, jnp.ones((4, E)), jnp.ones((4, 2 * E)))
    assert decisions == [False, True]  # tagged qkv dropped, untagged saved


def test_flash_policy_tag_gated_proj_exclusion():
    """'dots+attn-lean' under tags: the announced proj dot is excluded, the
    announced qkv dot is kept, and a foreign square dot neither loses its save
    nor trips the cross-validation error."""
    w_qkv = jnp.ones((E, 3 * E))
    w_proj = jnp.ones((E, E))
    w_moe = jnp.ones((2 * E, 2 * E))

    def block(x, y):
        t = checkpoint_name(x, "ds_dot:qkv")
        h = t @ w_qkv
        u = checkpoint_name(x, "ds_dot:proj")
        p = u @ w_proj
        return h.sum() + p.sum() + (y @ w_moe).sum()

    pol = _flash_policy(exclude="square", keep_qkv=True)
    decisions = _dot_decisions(pol, block, jnp.ones((4, E)), jnp.ones((4, 2 * E)))
    assert decisions == [True, False, True]


def test_tagged_block_with_foreign_square_differentiates():
    """End-to-end: the tagged-model analog of the collision scenario traces and
    differentiates cleanly under 'dots+attn-lean' (the untagged version raises —
    test_flash_policy_collision_raises_through_wrapper)."""
    w_qkv = jnp.ones((E, 3 * E)) * 0.1
    w_proj = jnp.ones((E, E)) * 0.1
    w_moe = jnp.ones((2 * E, 2 * E)) * 0.1

    def block(x):
        t = checkpoint_name(x, "ds_dot:qkv")
        h = jnp.tanh(t @ w_qkv)
        u = checkpoint_name(x, "ds_dot:proj")
        p = jnp.tanh(u @ w_proj)
        r = jnp.ones((4, 2 * E)) @ w_moe
        return h.sum() + p.sum() + r.sum()

    x = jnp.arange(4.0 * E).reshape(4, E) * 0.01
    g_ref = jax.grad(lambda x: block(x))(x)
    g = jax.grad(lambda x: checkpoint_wrapper(block, policy="dots+attn-lean")(x))(x)
    assert jnp.allclose(g, g_ref)


def test_gpt2_attention_emits_ds_dot_tags():
    """The gpt2 training forward announces its qkv and proj dots (the fused
    transformer kernel does the same — its tags are asserted by its own suite's
    policy compatibility, this pins the model-side contract)."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=32, n_positions=16, n_embd=16, n_layer=1,
                     n_head=2, compute_dtype=jnp.float32,
                     use_flash_attention=False)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.zeros((1, 16), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p: model.apply(p, toks, toks))(params)

    tags = []

    def walk(jxp):
        for e in jxp.eqns:
            if e.primitive.name == "name":
                tags.append(e.params["name"])
            for v in e.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)
    walk(jaxpr.jaxpr)
    assert "ds_dot:qkv" in tags, tags
    assert "ds_dot:proj" in tags, tags
