"""Closed-loop chat traffic: a fixed multiset of (prompt, output) lengths, of which
the seed fixes the order and the tokens.

The multiset is drawn once from the traffic file's own ``lengths_seed``, so every
``--seed`` serves the same lengths in another order: the work of a run does not
depend on the seed. The list is cycled when a run outlasts it.
"""

import numpy as np

from benchmarks.harness import clipped_lognormal


def multiset(traffic):
    rng = np.random.default_rng(traffic["lengths_seed"])
    n = traffic["multiset_size"]
    prompts = clipped_lognormal(rng, traffic["prompt_len"], n)
    outputs = clipped_lognormal(rng, traffic["output_len"], n)
    outputs = np.minimum(outputs, traffic["max_total_len"] - prompts)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


def generate(traffic, seed, *, vocab, **_):
    """An endless iterator of (prompt tokens, output length), and the multiset."""
    pairs = multiset(traffic)
    rng = np.random.default_rng([seed, 0x63686174])

    def requests():
        while True:
            for i in rng.permutation(len(pairs)):
                p, o = pairs[i]
                yield rng.integers(0, vocab, size=p, dtype=np.int64).tolist(), o

    return requests(), {"multiset": pairs}
