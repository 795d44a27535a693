"""Where each model's attention leaves its heads, at toy widths that take the kernels' row-major
branches: what a traced ``attention`` turns (``transpose`` equations of four axes in its jaxpr,
value and gradient) and which layout the flash entry says it took (``flash.<fwd|bwd>.<layout>``
through ``Recorder.count_in_program``).

The output always comes out ``[B, T, H * Dv]`` as the output projection reads it, and its
cotangent goes in so; ``v`` goes in as projected wherever nothing sits between its projection and
the kernel. A rotary turn or a norm a head between a projection and the kernel is a pass that
writes ``[B, heads, T, width]`` for nothing (the chip's compiler folds the turn of the axes into
it, and lays a ``[B, T, heads, width]`` intermediate out head-major anyway: PERF.md, PR 60), so q
and k stay head-major there: two turns forward, two back. Before PR 60 every model turned q, k, v
and the output: four forward and four back (three and three in the latent attention, whose keys
and values are split from one tensor).
"""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.utils import spans

B, T = 2, 128


def shapes_of(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def first_with(tree, key):
    """The first dict under ``tree`` that holds ``key``."""
    if isinstance(tree, dict):
        if key in tree:
            return tree
        tree = list(tree.values())
    if isinstance(tree, (list, tuple)):
        for sub in tree:
            found = first_with(sub, key)
            if found is not None:
                return found
    return None


def gpt2():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    model = GPT2Model(GPT2Config(n_embd=192, n_head=3, n_layer=1, vocab_size=64, n_positions=T,
                                 use_flash_attention=True))
    return 192, shapes_of(model)["blocks"][0]["attn"], lambda x, p: model._attention(x, p)


def glm():
    toy = importlib.import_module("glm_toy")
    from deepspeed_tpu.models.glm_moe import GlmMoeConfig, GlmMoeModel
    model = GlmMoeModel(GlmMoeConfig.from_published(
        toy.published(qk_nope_head_dim=96, qk_rope_head_dim=32, v_head_dim=128), mtp_loss_weight=0.3))
    return 32, shapes_of(model)["layers"][0]["attn"], model.attention


def xing():
    toy = importlib.import_module("xing_toy")
    from deepspeed_tpu.models.xing_moe import XingMoeConfig, XingMoeModel
    model = XingMoeModel(XingMoeConfig.from_published(
        toy.published(qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)))
    return 32, first_with(shapes_of(model), "wkv_b"), model.attention


def olmoe():
    toy = importlib.import_module("test_olmoe")
    from deepspeed_tpu.models.olmoe import OlmoeConfig, OlmoeModel
    model = OlmoeModel(OlmoeConfig.from_published(dict(toy.published(2), hidden_size=256, num_attention_heads=2,
                                                       num_key_value_heads=2)))
    return 256, shapes_of(model)["layers"][0], lambda x, p: model._attention(x, p, jnp.arange(T))


def ouro():
    toy = importlib.import_module("test_ouro")
    from deepspeed_tpu.models.ouro import OuroConfig, OuroModel
    model = OuroModel(OuroConfig.from_published(toy.published(head_dim=128, num_attention_heads=2, num_key_value_heads=2)))
    return 32, shapes_of(model)["layers"][0], lambda x, p: model.attention(x, p, jnp.arange(T))


def mellum():
    toy = importlib.import_module("mellum_toy")
    from deepspeed_tpu.models.mellum import MellumConfig, MellumModel
    model = MellumModel(MellumConfig.from_published(toy.published(head_dim=128)))
    return 32, first_with(shapes_of(model), "wkv"), lambda x, p: model.attention(x, p, "sliding_attention")


def granite():
    toy = importlib.import_module("test_granite_hybrid")
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig, GraniteHybridModel
    model = GraniteHybridModel(GraniteHybridConfig.from_published(toy.published(hidden_size=256, mamba_n_heads=64)))
    return 256, first_with(shapes_of(model), "wkv"), model.attention


def nemotron():
    toy = importlib.import_module("test_nemotron_h")
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel
    model = NemotronHModel(NemotronHConfig.from_published(toy.published(head_dim=128)))
    return 32, first_with(shapes_of(model), "wkv"), model.attention


def qwen():
    toy = importlib.import_module("test_qwen3_next")
    from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextModel
    model = Qwen3NextModel(Qwen3NextConfig.from_published(toy.published(head_dim=128)))
    return 32, first_with(shapes_of(model), "wkv"), lambda x, p: model.full_attention(x, p, jnp.arange(T))


def lfm2():
    toy = importlib.import_module("lfm2_toy")
    from deepspeed_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel
    model = Lfm2MoeModel(Lfm2MoeConfig.from_published(toy.published(hidden_size=256)))
    return 256, first_with(shapes_of(model), "wkv"), model.attention


# (the toy, the layout its call takes, heads as the counter spells them, turns of four axes forward and back)
MODELS = [(nemotron, "lanes", "4/2x128|128", 0, 0),       # nothing between the projections and the kernel
          (glm, "lanes", "4/4x128|128", 2, 2),            # q, and keys and values split from ONE tensor
          (ouro, "lanes", "2/2x128|128", 2, 2),
          (qwen, "lanes", "4/2x128|128", 2, 2),           # the gate is multiplied into the output where it lies
          # 192 | 128 is no lane block: the entry turns v and the output itself
          (xing, "heads_major", "4/4x192|128", 3, 3),
          # two heads of 64 share a lane block: the head-major call as before (their programs are the parent's)
          (gpt2, "heads_major", "3/3x64|64", 4, 4), (granite, "heads_major", "4/2x64|64", 4, 4),
          (lfm2, "heads_major", "4/2x64|64", 4, 4),
          # OLMoE and Mellum 2 keep the head-major call too, and the parent's programs: through the entry
          # ``olmoe_d4_train_4chip`` read 1.35 % SLOWER (the compiler laid the float32 passes round its
          # whole-width q and k norms out anew) and ``mellum2_ep4_d4_train_1chip`` 0.15 to 0.33 % (PERF.md, PR 60)
          (olmoe, "heads_major", "2/2x128|128", 4, 4), (mellum, "heads_major", "4/2x128|128", 4, 4)]


def turns(jaxpr):
    """``transpose`` equations on four axes anywhere under ``jaxpr``."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "transpose" and len(eqn.invars[0].aval.shape) == 4:
            n += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += turns(sub)
    return n


@pytest.mark.parametrize("toy,layout,heads,forward,backward", MODELS, ids=[m[0].__name__ for m in MODELS])
def test_a_models_attention_turns_no_operand_the_kernel_finds_in_place(toy, layout, heads, forward, backward):
    hidden, shapes, attention = toy()
    params = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, jnp.float32), shapes)
    x = jnp.zeros((B, T, hidden), jnp.float32)
    recorder = spans.recorder()
    with recorder.span("train.grad_program", engine=recorder.new_engine(), program="loss_and_grad") as call:
        value = jax.make_jaxpr(lambda x, p: attention(x, p))(x, params)
        grad = jax.make_jaxpr(jax.grad(lambda x, p: jnp.sum(attention(x, p)), argnums=(0, 1)))(x, params)
    counted = {name for name in recorder.counters(call.engine) if name.startswith("flash.")}
    assert counted == {f"flash.{way}.{layout}[loss_and_grad] {heads} at {T}" for way in ("fwd", "bwd")}, counted
    assert "pallas_call" in str(value) and re.search(r"ds_flash_bwd_dkv", str(grad))
    assert turns(value.jaxpr) == forward, str(value)
    assert turns(grad.jaxpr) - forward == backward, str(grad)
