"""KV-cache autoregressive generation parity (greedy decode == full re-forward)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model


def _oracle_greedy(model, params, tokens, n_new):
    """Teacher-forcing oracle: re-run the FULL forward for every step and take
    argmax of the last position — what the cached decode must reproduce."""
    toks = np.asarray(tokens)
    forward = jax.jit(model.apply)      # a program a length; eagerly, some fifty a length
    for _ in range(n_new):
        logits = np.asarray(forward(params, jnp.asarray(toks)))
        nxt = np.argmax(logits[:, -1], axis=-1).astype(toks.dtype)
        toks = np.concatenate([toks, nxt[:, None]], axis=1)
    return toks


@pytest.mark.parametrize("moe", [False, True])
def test_greedy_generate_matches_full_forward(moe):
    cfg = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=3, n_head=2,
                     compute_dtype=jnp.float32,
                     **({"moe_experts": 4, "moe_every": 2,
                         "moe_capacity_factor": 8.0} if moe else {}))
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jnp.asarray(np.random.default_rng(1).integers(0, 97, (2, 11)), jnp.int32)
    got = np.asarray(model.generate(params, prompt, max_new_tokens=8))
    want = _oracle_greedy(model, params, prompt, 8)
    np.testing.assert_array_equal(got, want)


def test_generate_sampling_and_bounds():
    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    prompt = jnp.asarray(np.random.default_rng(3).integers(0, 64, (3, 5)), jnp.int32)
    out = model.generate(params, prompt, max_new_tokens=6, temperature=1.0,
                         rng=jax.random.PRNGKey(4))
    assert out.shape == (3, 11)
    o = np.asarray(out)
    assert ((o >= 0) & (o < 64)).all()
    np.testing.assert_array_equal(o[:, :5], np.asarray(prompt))
    # different rng -> (almost surely) different samples
    out2 = model.generate(params, prompt, max_new_tokens=6, temperature=1.0,
                          rng=jax.random.PRNGKey(5))
    assert not np.array_equal(np.asarray(out2), o)
    # single-token path
    one = model.generate(params, prompt, max_new_tokens=1)
    assert one.shape == (3, 6)


def test_top_k_samples_stay_in_the_top_k_set():
    """Teacher-forcing check: every sampled token must be among the top-k of the
    full-forward oracle logits for its prefix (and in the nucleus for top_p)."""
    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(8))
    prompt = jnp.asarray(np.random.default_rng(9).integers(0, 64, (2, 4)), jnp.int32)
    k = 5
    out = np.asarray(model.generate(params, prompt, max_new_tokens=8, temperature=1.0,
                                    top_k=k, rng=jax.random.PRNGKey(10)))
    forward = jax.jit(model.apply)      # a program a length; eagerly, some fifty a length
    for t in range(4, 12):
        logits = np.asarray(forward(params, jnp.asarray(out[:, :t])))[:, -1]
        topk = np.argsort(logits, axis=-1)[:, -k:]
        for b in range(out.shape[0]):
            assert out[b, t] in topk[b], (b, t, out[b, t], topk[b])


def test_top_p_tiny_nucleus_is_greedy():
    """top_p small enough that only the argmax survives -> sampling == greedy,
    regardless of temperature; same for top_k=1."""
    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(11))
    prompt = jnp.asarray(np.random.default_rng(12).integers(0, 64, (2, 4)), jnp.int32)
    greedy = np.asarray(model.generate(params, prompt, max_new_tokens=6))
    nucleus = np.asarray(model.generate(params, prompt, max_new_tokens=6,
                                        temperature=1.3, top_p=1e-6,
                                        rng=jax.random.PRNGKey(13)))
    np.testing.assert_array_equal(greedy, nucleus)
    topk1 = np.asarray(model.generate(params, prompt, max_new_tokens=6,
                                      temperature=0.7, top_k=1,
                                      rng=jax.random.PRNGKey(14)))
    np.testing.assert_array_equal(greedy, topk1)


def _teacher_forced_logprob(model, params, full, T0):
    """Sum of log p(token_t | prefix) over the generated suffix, fp32."""
    logits = np.asarray(model.logits(params, jnp.asarray(full[:, :-1]))
                        if hasattr(model, "logits") else
                        model.apply(params, jnp.asarray(full[:, :-1])))
    logp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
    tot = np.zeros(full.shape[0])
    for t in range(T0, full.shape[1]):
        for b in range(full.shape[0]):
            tot[b] += logp[b, t - 1, full[b, t]]
    return tot


def test_beam1_equals_greedy():
    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(15))
    prompt = jnp.asarray(np.random.default_rng(16).integers(0, 64, (2, 5)), jnp.int32)
    greedy = np.asarray(model.generate(params, prompt, max_new_tokens=7))
    beam1, _ = model.beam_search(params, prompt, max_new_tokens=7, num_beams=1)
    np.testing.assert_array_equal(greedy, np.asarray(beam1))


def test_beam_search_scores_are_self_consistent_and_beat_greedy():
    """The returned score must equal the teacher-forced log-prob of the returned
    sequence (length_penalty=1 -> score*L), and the beam-4 winner's total
    log-prob must be >= the greedy sequence's."""
    cfg = GPT2Config(vocab_size=37, n_positions=32, n_embd=32, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(17))
    prompt = jnp.asarray(np.random.default_rng(18).integers(0, 37, (3, 4)), jnp.int32)
    L = 6
    seqs, scores = model.beam_search(params, prompt, max_new_tokens=L, num_beams=4)
    seqs = np.asarray(seqs)
    want = _teacher_forced_logprob(model, params, seqs, 4)
    np.testing.assert_allclose(np.asarray(scores) * L, want, rtol=1e-4, atol=1e-4)
    greedy = np.asarray(model.generate(params, prompt, max_new_tokens=L))
    g_lp = _teacher_forced_logprob(model, params, greedy, 4)
    assert (want >= g_lp - 1e-4).all(), (want, g_lp)


def test_beam_search_eos_freezes_and_pads():
    cfg = GPT2Config(vocab_size=16, n_positions=32, n_embd=16, n_layer=1, n_head=2,
                     compute_dtype=jnp.float32)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(19))
    prompt = jnp.asarray(np.random.default_rng(20).integers(0, 16, (2, 3)), jnp.int32)
    seqs, scores = model.beam_search(params, prompt, max_new_tokens=8, num_beams=3,
                                     eos_token_id=5, length_penalty=0.8)
    seqs = np.asarray(seqs)
    assert seqs.shape == (2, 11) and np.isfinite(np.asarray(scores)).all()
    for b in range(2):
        gen = seqs[b, 3:]
        hits = np.where(gen == 5)[0]
        if hits.size:  # everything after the first EOS is EOS padding
            assert (gen[hits[0]:] == 5).all(), gen
    # normalized score self-consistency: raw log-prob accumulates only up to the
    # first EOS (frozen continuations are free), length counts it, clamped at L
    full_logits = np.asarray(model.logits(params, jnp.asarray(seqs[:, :-1])))
    logp = np.asarray(jax.nn.log_softmax(jnp.asarray(full_logits), axis=-1))
    for b in range(2):
        gen = seqs[b, 3:]
        hits = np.where(gen == 5)[0]
        n = min(int(hits[0]) + 1 if hits.size else 8, 8)
        raw = sum(logp[b, 3 - 1 + t, gen[t]] for t in range(n))
        want = raw / n ** 0.8
        np.testing.assert_allclose(float(scores[b]), want, rtol=1e-4, atol=1e-4)


def test_generate_reuses_compiled_programs():
    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=2,
                     compute_dtype=jnp.float32)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(6))
    prompt = jnp.asarray(np.random.default_rng(7).integers(0, 64, (2, 5)), jnp.int32)
    o1 = model.generate(params, prompt, max_new_tokens=4)
    assert len(model._gen_jit_cache) == 2  # shape-keyed prefill + decode
    o2 = model.generate(params, prompt, max_new_tokens=4)
    assert len(model._gen_jit_cache) == 2  # same signature -> same programs
    # a different sampling config compiles a new decode but REUSES the prefill
    model.generate(params, prompt, max_new_tokens=4, temperature=0.5,
                   rng=jax.random.PRNGKey(0))
    assert len(model._gen_jit_cache) == 3
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    with pytest.raises(AssertionError, match="max_new_tokens"):
        model.generate(params, prompt, max_new_tokens=0)
