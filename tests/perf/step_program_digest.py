"""sha256 of the ENGINE's lowered step programs (gradient and update program, and the fused step)
for the accepted models (GPT-2 and Nemotron-H among them) at the tests' small sizes, source locations taken out:
run it on two trees and compare the lines (a PR that says "the programs of the accepted cells are
unchanged" shows it so): python tests/perf/step_program_digest.py <root of a checkout>"""
import hashlib
import os
import re
import sys

root = os.path.abspath(sys.argv[1])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, root)
sys.path.insert(0, os.path.join(root, "tests/unit"))
os.chdir(root)

import numpy as np  # noqa: E402

import deepspeed_tpu  # noqa: E402
import test_granite_hybrid as granite, test_olmoe as olmoe, test_ouro as ouro, test_qwen3_next as qwen  # noqa: E401,E402
import test_nemotron_h as nemotron  # noqa: E402


def digest(jitted, *args):
    text = re.sub(r"#loc.*", "", re.sub(r"loc\(.*?\)", "", jitted.lower(*args).as_text()))
    return hashlib.sha256(text.encode()).hexdigest()[:16], len(text)


def models():
    for name, mod, kw in (("granite_remat", granite, {"remat": True}), ("qwen3next", qwen, {}), ("ouro", ouro, {})):
        _, model, params = mod.build(**kw)
        yield name, model, params, mod.batch()
    _, model, params = olmoe.build(2)
    yield "olmoe", model, params, olmoe.batch()
    _, model, params = nemotron.build(nemotron.published(stand_in=True), remat=True)
    yield "nemotronh_stand_in_remat", model, params, nemotron.batch()
    yield from toys()
    yield ("gpt2_flash",) + gpt2()


def toys():
    """The models whose toys live in a file of their own: Mellum 2, GLM-4.7-Flash (the other model on the
    sigmoid router) and LFM2 (absent from a tree older than PR 52), held experts standing in, layers recomputed."""
    import importlib
    cut = {"mellum": dict(num_experts=4, router_width=8, first_expert=4, stand_in=True),
           "glm": dict(n_routed_experts=4, router_width=8, first_expert=4, stand_in=True),
           "lfm2": dict(num_experts=4, router_width=8, first_expert=4, stand_in=True)}
    for name in cut:
        try:
            toy = importlib.import_module(name + "_toy")
        except ImportError:
            continue
        _, model, params = toy.build(toy.published(**cut[name]), remat=True)
        yield name + "_stand_in_remat", model, params, toy.batch()


def gpt2():
    """GPT-2 at the cell benchmark's toy sizes (``tests/cellbench/tiny.py``), the flash kernel on."""
    import jax
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    model = GPT2Model(GPT2Config(vocab_size=256, n_positions=64, n_embd=32, n_layer=2, n_head=2,
                                 use_flash_attention=True, loss_chunk=16))
    tokens = np.random.default_rng(0).integers(0, 250, (8, 64)).astype(np.int32)
    return model, model.init(jax.random.PRNGKey(0)), (tokens, np.roll(tokens, -1, 1))


for fused in (False, True):
    for name, model, params, batch in models():
        tokens, labels = (np.concatenate([np.asarray(a)] * 8)[:8] for a in batch)
        engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
            "train_batch_size": 8, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-5}}, "steps_per_print": 10 ** 9,
            "fused_step": fused})
        for program, jitted, args, _ in engine.lint_programs((tokens, labels)):
            print(name, "fused" if fused else "two-program", program, *digest(jitted, *args), flush=True)
