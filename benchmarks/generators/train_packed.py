"""Pre-training batches: documents of heavy-tailed length, packed into full sequences.

Document lengths are log-normal and clipped; tokens inside a document follow a Zipf
law over the vocabulary (a permutation of the ranks, fixed by the seed, says which id
is frequent), so there is a unigram distribution to learn. Documents are joined by the
end-of-text id and the stream is cut into rows of ``seq_len + 1`` tokens: inputs are a
row without its last token and labels the row without its first. No padding, and the
same shapes whatever the seed.
"""

import numpy as np

from benchmarks.harness import clipped_lognormal


def _zipf_table(rng, vocab, exponent):
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -exponent)
    return cdf / cdf[-1], rng.permutation(vocab)


def generate(traffic, seed, *, vocab, batch, n_batches):
    """``n_batches`` pairs (tokens, labels) of int32 ``[batch, seq_len]``, and what
    was drawn: the document lengths, so that a test can compare their distribution."""
    rng = np.random.default_rng([seed, 0x7061636B])
    T = traffic["seq_len"]
    need = n_batches * batch * (T + 1)
    eot = traffic["eot_token"]
    usable = min(vocab, eot)                 # ids below the end-of-text id
    cdf, ids = _zipf_table(rng, usable, traffic["token_dist"]["exponent"])
    mean_len = traffic["doc_len"]["median"] * np.exp(traffic["doc_len"]["sigma"] ** 2 / 2)
    doc_lens = clipped_lognormal(rng, traffic["doc_len"], int(need / mean_len * 1.5) + 16)
    while doc_lens.sum() + len(doc_lens) < need:
        doc_lens = np.concatenate([doc_lens, clipped_lognormal(rng, traffic["doc_len"], len(doc_lens))])
    total = int(doc_lens.sum() + len(doc_lens))
    stream = ids[np.searchsorted(cdf, rng.random(total))].astype(np.int32)
    stream[np.cumsum(doc_lens + 1) - 1] = eot          # one end-of-text after each document
    rows = stream[:need].reshape(n_batches, batch, T + 1)
    batches = [(np.ascontiguousarray(r[:, :-1]), np.ascontiguousarray(r[:, 1:])) for r in rows]
    return batches, {"doc_lens": doc_lens}
