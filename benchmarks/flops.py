"""Operations and bytes the algorithm requires, from the configuration's sizes.

Arithmetic only: no operation count comes from a compiler or a profiler, and nothing
recomputed counts. A multiply-add is two operations.
"""


def _sizes(model):
    return model["n_embd"], model["n_layer"], model["n_head"]


def matmul_params(model, vocab):
    """Parameters that a token passes through a matrix multiplication: the four
    weight matrices of every block (12 E^2) and the tied output head (V E). Biases,
    layer norms and the embedding look-ups multiply nothing."""
    E, L, _ = _sizes(model)
    return 12 * L * E * E + vocab * E


def param_count(model, vocab):
    """All parameters of the GPT-2 model as the program holds them."""
    E, L, _ = _sizes(model)
    per_block = 12 * E * E + 13 * E          # 4 matrices, 4 biases (3E+E+4E+E), 2 layer norms (4E)
    return L * per_block + vocab * E + model["n_positions"] * E + 2 * E


def attention_flops_per_token_fwd(model, seq_len, causal=True):
    """QK^T and PV over one head dimension each: 2 * 2 * T * E a token and layer for
    full attention, half of that for causal attention, which needs only the lower
    triangle."""
    E, L, _ = _sizes(model)
    per_layer = 4 * seq_len * E
    return L * (per_layer / 2 if causal else per_layer)


def train_flops_per_token(model, vocab, seq_len):
    """Forward and backward: 6 operations a matrix parameter and token, plus causal
    attention forward once and backward twice (dQ, dK, dV are two passes' worth)."""
    return 6 * matmul_params(model, vocab) + 3 * attention_flops_per_token_fwd(model, seq_len)


def flash_required(model, batch, seq_len, training=True):
    """Required operations and HBM bytes of the flash-attention calls of one step over
    ``batch`` sequences, all layers: (flops, bytes). Forward reads q, k, v and writes o
    once; backward reads q, k, v, o, do and writes dq, dk, dv. bf16 throughout; the
    per-row statistics are a 64th of that and are left out."""
    E, L, _ = _sizes(model)
    tokens = batch * seq_len
    fwd_flops = attention_flops_per_token_fwd(model, seq_len) * tokens
    elem = tokens * E * 2                      # one [B, T, E] bf16 tensor, bytes
    fwd_bytes = L * 4 * elem
    if not training:
        return fwd_flops, fwd_bytes
    return 3 * fwd_flops, fwd_bytes + L * 8 * elem


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which peak bounds it."""
    t_compute, t_memory = flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
