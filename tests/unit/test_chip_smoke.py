"""chip_smoke.py rehearsed on the CPU mesh: every phase function at a tiny size, so
the control flow and the multi-device comparison run on every PR, and the entry
point's refusal to run without a chip.

Kernel-presence assertions are off here (``on_chip=False``): the interpreter emits
no custom call. Nothing below says anything about the chip; only a chip run does.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deepspeed_tpu.parallel.mesh import single_device_mesh  # noqa: E402
from deepspeed_tpu.utils import compile_cache  # noqa: E402

TINY_MODEL = dict(vocab_size=512, n_positions=128, n_embd=64, n_head=4, n_layer=1)
TINY_TRAIN = dict(model=TINY_MODEL, batch=8, seq=64)
TINY_DEEPER = dict(model=dict(TINY_MODEL, n_layer=2), batch=8, seq=64)
TINY_SERVE = dict(
    model=TINY_MODEL,
    serving=dict(max_seqs=4, block_size=8, num_blocks=65, max_model_len=128,
                 prefill_chunk=16),
    prompt_lens=(8, 20, 33, 8), new_tokens=6)


@pytest.fixture(scope="module")
def log():
    return chip_smoke.CompileLog()


def test_phase_train_tiny(log):
    out = chip_smoke.phase_train(TINY_TRAIN, log, mesh=single_device_mesh(jax.devices()[0]),
                                 on_chip=False)
    assert out["dp"] == 1 and len(out["losses"]) == 5
    assert out["losses"][-1] < out["losses"][0]
    assert out["compiles_per_step"][2:] == [0, 0, 0]
    assert out["hbm_forecast"]["fits"]


def test_phase_flash_parity_tiny():
    out = chip_smoke.phase_flash_parity((1, 2, 128, 64), on_chip=False)
    assert set(out["rel_err"]) == {"fwd", "dq", "dk", "dv"}
    assert max(out["rel_err"].values()) < chip_smoke.PARITY_TOL


def test_phase_serve_tiny(log):
    out = chip_smoke.phase_serve(TINY_SERVE, log, on_chip=False)
    for path in ("gather", "pallas"):
        # token identity with generate() is exact on the CPU
        assert out[path]["identical_streams"] == len(TINY_SERVE["prompt_lens"])
        assert out[path]["tokens"] == 4 * TINY_SERVE["new_tokens"]


def test_phase_multichip_tiny(log, eight_devices):
    """The ``--chips 4`` phase over the eight virtual devices: dp=1 against the
    default mesh under ZeRO 2 and 3, then the deeper model under ZeRO 3."""
    runs = list(chip_smoke.phase_multichip(TINY_TRAIN, TINY_DEEPER, log, on_chip=False))
    assert [r["run"] for r in runs] == ["reference dp=1", "zero2 dp=8", "zero3 dp=8",
                                        "full depth zero3 dp=8"]
    assert all(r["dp"] == 8 and r["sharded_fraction"] > 0.9 for r in runs[1:])
    assert all(r["max_rel_loss_diff"] <= chip_smoke.BF16_EPS for r in runs[1:3])
    assert runs[3]["n_layer"] == 2


def test_near_tie_admits_only_close_calls():
    """The one way a served stream may leave the reference, on fabricated logits."""
    dense = np.zeros(16, np.float32)
    dense[3], dense[5] = 2.0, 1.99
    paged = dense.copy()
    paged[5] = 2.005                                   # argmax flips inside the bound
    assert chip_smoke.near_tie(paged, dense, 5, 3)["ok"]
    assert not chip_smoke.near_tie(paged, dense, 7, 3)["ok"]     # token 7 is 2.0 away
    paged[9] = 0.5                                     # the two paths disagree elsewhere
    assert not chip_smoke.near_tie(paged, dense, 5, 3)["ok"]


def test_replayed_logits_agree_between_paths():
    """The two replays a divergence is judged on give the same next-token logits
    on the CPU, where the paged and dense-cache paths are the same arithmetic."""
    import deepspeed_tpu
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    model = GPT2Model(GPT2Config(**TINY_MODEL, compute_dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(
        model=model, model_parameters=params,
        config_params={"serving": dict(TINY_SERVE["serving"], enabled=True)})
    prefix = np.random.default_rng(0).integers(0, 512, size=37).tolist()
    paged = chip_smoke.paged_next_logits(engine, chip_smoke.serving_programs(engine), prefix)
    dense = chip_smoke.dense_next_logits(model, params, prefix)
    np.testing.assert_allclose(paged, dense, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_entry_points_refuse_the_cpu(script):
    """No CPU mode: the script exits non-zero before building anything and prints
    no result."""
    r = subprocess.run([sys.executable, os.path.join(REPO, script)],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "TPU" in r.stderr


def test_compile_cache_helper(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without it the
    cache sits at the fixed in-checkout path."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/some/dir")
    assert compile_cache.configure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(compile_cache.CACHE_ENV)
    try:
        path = compile_cache.configure_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_phase_experts_in_pieces_tiny():
    """The control flow and the bookkeeping of the pieces (which cotangent is whose);
    here both sides are ``lax.ragged_dot``, the kernel's chaining only the chip sees."""
    size = dict(rows=256, hidden=32, columns=48, experts=8, pieces=4)
    out = chip_smoke.phase_experts_in_pieces(size, on_chip=False)
    assert set(out["layouts"]) == {"spread", "collapsed"}
    for layout in out["layouts"].values():
        assert layout["empty_groups"] >= 2
        assert max(layout["rel_err"].values()) < chip_smoke.PARITY_TOL
    for layout in ("spread", "collapsed"):
        assert chip_smoke.expert_group_sizes(size, layout).sum() == size["rows"]
