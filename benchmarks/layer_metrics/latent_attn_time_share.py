"""Device time of the latent attention's whole mixers (every operation whose scope path holds
``ds_attn_latent``: both bottlenecks and their norms, the rotary turn, the concatenations and the
shared key's broadcast, the flash kernel's calls, the output projection; all six blocks, forward,
recomputed forward and backward) over the traced window. None without a trace, a catalog or such
a scope."""

from benchmarks import mla_spans


def read(record):
    return mla_spans.share(record, mla_spans.LATENT)
