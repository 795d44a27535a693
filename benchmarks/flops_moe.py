"""Operations and bytes an expert model (OLMoE's block) requires, from the published keys.

Arithmetic only, as ``flops.py`` is for GPT-2: no count comes from a compiler or a
profiler, nothing recomputed counts, a multiply-add is two operations.
"""


def is_expert_model(model):
    return "num_experts" in model and "num_experts_per_tok" in model


def matmul_params(model, vocab):
    """Parameters a token passes through a matrix multiplication: Wq, Wk, Wv, Wo (4 H^2),
    the router (H E) and the k experts a token is sent to (3 H F each) in every layer,
    and the untied head (V H). Norms and the embedding look-up multiply nothing."""
    H, F, L = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    E, k = model["num_experts"], model["num_experts_per_tok"]
    return L * (4 * H * H + H * E + k * 3 * H * F) + vocab * H


def param_count(model, vocab):
    """All parameters as the program holds them: every expert, both embeddings, norms."""
    H, F, L = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    E = model["num_experts"]
    per_layer = 4 * H * H + H * E + E * 3 * H * F + 4 * H      # two block norms, q and k norm
    return L * per_layer + 2 * vocab * H + H


def attention_flops_per_token_fwd(model, seq_len):
    """Causal QK^T and PV: half of 2 * 2 * T * H a token and layer."""
    return model["num_hidden_layers"] * 2 * seq_len * model["hidden_size"]


def train_flops_per_token(model, vocab, seq_len):
    """Forward and backward: 6 operations a matrix parameter and token, plus causal
    attention forward once and backward twice."""
    return 6 * matmul_params(model, vocab) + 3 * attention_flops_per_token_fwd(model, seq_len)


def expert_matmul_required(model, tokens, training=True):
    """Required operations and HBM bytes of the grouped expert matmuls of one step over
    ``tokens`` tokens on one chip, all layers: (flops, bytes). A product reads its rows
    and every expert's matrix once and writes its output once, in bf16; the backward of a
    product is two products (the rows' and the matrix's cotangent), each reading two of
    the three arrays and writing the third."""
    H, F, L = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    E, k = model["num_experts"], model["num_experts_per_tok"]
    rows = tokens * k
    fwd_flops = L * 2 * rows * 3 * H * F
    gate_up = (rows * H, E * H * 2 * F, rows * 2 * F)       # rows in, matrices, rows out
    down = (rows * F, E * F * H, rows * H)
    fwd_bytes = L * 2 * (sum(gate_up) + sum(down))
    if not training:
        return fwd_flops, fwd_bytes
    return 3 * fwd_flops, 3 * fwd_bytes
