"""Operations and bytes a model of latent-attention blocks INSIDE hyper-connections (Xing4.0's:
``n`` residual streams, a leading dense block, expert blocks with a shared expert, no prediction
module here) requires of THIS chip, from the configuration's keys and the window's measured
expert rows.

Arithmetic only, as ``flops.py`` and ``flops_mla_moe.py`` are (whose counts of a latent-attention
block, an expert and a router this file takes as they stand): no count comes from a compiler or a
profiler, a multiply-add is two operations, and NOTHING RECOMPUTED COUNTS in the step's operations
(``train_flops_per_token``). Two kernels' worth of required work are priced here too, whatever
implements them: a sub-layer's hyper-connection (``hc_required``: memory-bound, the streams read and
written once a mix) and the flash kernel's calls at scores of one width and values of another
(``latent_flash_required``; ``flops.flash_required`` assumes one width).
"""

from benchmarks import flops_mla_moe as mla

BF16 = 2


def is_hc_moe_model(model):
    return mla.is_mla_moe_model(model) and "hc_mult" in model


def sub_layers(model):
    return 2 * model["num_hidden_layers"]


def hc_params(model):
    """One sub-layer's hyper-connection: ``Phi_pre``, ``Phi_post`` ``[n C, n]``, ``Phi_res`` ``[n C,
    n n]``, the flattened norm's weight, ``b_pre``, ``b_post``, ``B_res`` and the three gates."""
    n, C = model["hc_mult"], model["hidden_size"]
    return n * C * n * (n + 2) + n * C + n * (n + 2) + 3


def param_count(model, vocab):
    """All parameters as the program holds them: ``flops_mla_moe``'s blocks (no module), two
    hyper-connections a block, embedding, head and the last norm."""
    assert model["num_nextn_predict_layers"] == 0, "no prediction module is built"
    return mla.param_count(model, vocab) + sub_layers(model) * hc_params(model)


def hc_flops_per_token(model):
    """One sub-layer's hyper-connection, forward, a token: the projection onto ``n (n + 2)``
    columns, and the three mixes (``n C`` multiply-adds to ``u``, ``n n C`` for the streams,
    ``n C`` for ``H_post f``)."""
    n, C = model["hc_mult"], model["hidden_size"]
    return 2 * n * C * n * (n + 2) + 2 * (n * C + n * n * C + n * C)


def forward_flops_by_part(model, vocab, seq_len, rows_per_token):
    """Operations of ONE SEQUENCE of ``seq_len`` tokens, forward, by part (``flops_mla_moe``'s
    parts at this model's widths, scores 192 deep and values 128 wide, and the hyper-connections')."""
    parts = mla.forward_flops_by_part(model, vocab, seq_len, rows_per_token)
    assert parts.pop("mtp_projection") == 0
    return dict(parts, hyper_connections=seq_len * sub_layers(model) * hc_flops_per_token(model))


def train_flops_per_token(model, vocab, seq_len, rows_per_token):
    """Forward and backward a token: the backward of every product is two products, of
    attention twice the forward. The recomputed forward is not counted."""
    return 3 * sum(forward_flops_by_part(model, vocab, seq_len, rows_per_token).values()) / seq_len


def hc_required(model, tokens, recomputed):
    """``(operations, HBM bytes)`` of every sub-layer's hyper-connection over one step of
    ``tokens`` tokens, the least whatever implements it. Forward: the ``n`` streams read once (for
    the coefficients and ``u``), ``u`` written, the ``n`` streams and ``f`` read, the ``n`` streams
    written: ``(3 n + 2) C`` elements a token, in bf16. The second forward (where blocks are
    recomputed) the same again; the backward twice that (every array read again, and a cotangent
    read or written for each). The projections' ``n C x n (n + 2)`` weights and the ``n (n + 2)``
    coefficients a token are left out (a 600th)."""
    n, C = model["hc_mult"], model["hidden_size"]
    passes = 1 + bool(recomputed) + 2
    elements = (3 * n + 2) * C * tokens * sub_layers(model)
    return passes * hc_flops_per_token(model) * tokens * sub_layers(model), passes * elements * BF16


def latent_flash_required(model, batch, seq_len, forward):
    """``(operations, HBM bytes)`` of the flash kernel's calls of one step over ``batch``
    sequences, a call a block, at ``num_attention_heads`` heads, scores ``qk_nope + qk_rope`` deep
    and values ``v_head_dim`` wide, over the causal triangle: the forward's two products (one of
    each width), or the backward's five (S, dK and dQ at the scores' width; dP and dV at the
    values'). Forward reads q, k, v and writes o; backward reads q, k, v, o, dO and writes dq, dk,
    dv; bf16, the per-row statistics left out. The NEEDED work: a kernel that pads the values to
    the keys' width does more and reads a lower share."""
    L, n = model["num_hidden_layers"], model["num_attention_heads"]
    deep, wide = model["qk_nope_head_dim"] + model["qk_rope_head_dim"], model["v_head_dim"]
    pairs = batch * seq_len * (seq_len + 1) // 2
    tokens = batch * seq_len
    if forward:
        return 2 * pairs * L * n * (deep + wide), L * tokens * n * (2 * deep + 2 * wide) * BF16
    return 2 * pairs * L * n * (3 * deep + 2 * wide), L * tokens * n * (4 * deep + 4 * wide) * BF16
