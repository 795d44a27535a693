"""Decode cache programs must donate — the cache double-buffer regression pin.

The 1.5B batch-8 decode and 420M beam-4 failures fixed at PR 2 were a cache
double-buffer: the in-place ``dynamic_update_slice`` rewrite kept the caller's KV
caches live across the prefill and decode programs because nothing donated them,
so XLA materialized input AND output cache buffers (~5.7 GB each at 1.5B b8)
through the prompt-forward activation peak — over the 16 GB v5e cliff at
execution time, which is why compilation succeeded and the run died. The fix
donates the caches through prefill and both decode programs and returns them, so
XLA aliases one buffer input -> scan carry -> output.

These tests pin the fix on CPU via the lint donation pass: every decode-path
program's declared cache donation must actually alias in the compiled HLO
(``unusable-donation``), no cache-sized input may ride un-donated
(``undonated-aliasable``), and the beam program's caches arrive pre-expanded
to [nl, B*K, ...] — the in-jit ``jnp.repeat`` variant is exactly the shape
mismatch that turns a donation into a silent no-op.
"""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.lint.program_passes import ProgramArtifact, run_program_passes
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.utils import hlo

B, T0, L, K = 2, 4, 4, 2


@pytest.fixture(scope="module")
def artifacts():
    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=16, n_layer=2,
                     n_head=2, compute_dtype=jnp.float32, loss_chunk=0)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    progs = model.decode_lint_programs(params, batch=B, prompt_len=T0,
                                       max_new_tokens=L, num_beams=K)
    assert [n for n, _, _, _ in progs] == \
        ["gpt2_prefill", "gpt2_decode_greedy", "gpt2_decode_beam"]
    return {n: ProgramArtifact.capture(f"gpt2:{n}", jitted, args, manifest)
            for n, jitted, args, manifest in progs}


def test_every_decode_program_donates_exactly_its_caches(artifacts):
    for name, art in artifacts.items():
        donated = [i for i, (d, _, _) in enumerate(art.args_info) if d]
        assert len(donated) == 2, (name, donated)  # kcs, vcs and nothing else
        shapes = {art.args_info[i][1] for i in donated}
        assert len(shapes) == 1, (name, shapes)    # k and v caches match


def test_donated_caches_actually_alias_in_compiled_hlo(artifacts):
    """The donation must survive compilation as an input_output_alias entry —
    a declared-but-unaliased donation is the exact failure the crash had."""
    for name, art in artifacts.items():
        aliases = hlo.input_output_aliases(art.hlo_text)
        donated = [i for i, (d, _, _) in enumerate(art.args_info) if d]
        for i in donated:
            assert i in aliases, (name, i, sorted(aliases))
        assert not any("donated buffers were not usable" in w.lower()
                       for w in art.compile_warnings), (name, art.compile_warnings)


def test_beam_decode_caches_arrive_pre_expanded(artifacts):
    """Beam decode takes [nl, B*K, ...] caches (the eager repeat happens
    outside the jit); a [nl, B, ...] donated input cannot alias the
    [nl, B*K, ...] output and would be flagged unusable-donation."""
    art = artifacts["gpt2_decode_beam"]
    cache_shapes = [shape for d, shape, _ in art.args_info if d]
    assert all(s[1] == B * K for s in cache_shapes), cache_shapes


def test_decode_programs_pass_the_full_lint_suite(artifacts):
    """Donation clean, zero large collectives (single-host decode), and no
    dtype-promotion surprises — the same gate ds-tpu lint runs in CI."""
    violations = run_program_passes(artifacts.values())
    assert violations == [], [v.vid for v in violations]
