"""Operations a looped decoder (Ouro's: ``num_hidden_layers`` blocks of full rotary attention
and a gated MLP, run ``total_ut_steps`` times on one set of weights, with the last norm, the
untied head and an exit gate after every pass) requires, from the configuration's keys.

Arithmetic only, as ``flops.py``, ``flops_moe.py``, ``flops_hybrid.py`` and ``flops_ssm.py``
are: no count comes from a compiler or a profiler, a multiply-add is two operations, and
NOTHING RECOMPUTED COUNTS: a cell whose blocks are made again in the backward does a second
forward that is not here. A weight used in four passes multiplies four times; it is held once.
"""


def is_loop_model(model):
    return "total_ut_steps" in model


def passes(model):
    """Block passes a token: every layer once a pass."""
    return model["num_hidden_layers"] * model["total_ut_steps"]


def block_matmul_params(model):
    """q, k, v, o over ``heads x head_dim`` and the gated MLP's three matrices."""
    H, A = model["hidden_size"], model["num_attention_heads"] * model["head_dim"]
    return 4 * H * A + 3 * H * model["intermediate_size"]


def layer_params(model):
    """A layer whole: its seven matrices and its four norms."""
    return block_matmul_params(model) + 4 * model["hidden_size"]


def param_count(model, vocab):
    """All parameters as the program HOLDS them: a layer once however many passes use it,
    the embedding and the untied head, the last norm, the gate's weight and bias."""
    H = model["hidden_size"]
    return model["num_hidden_layers"] * layer_params(model) + 2 * vocab * H + H + H + 1


def forward_flops_by_part(model, vocab, seq_len):
    """Operations a token, forward, by part."""
    H, A = model["hidden_size"], model["num_attention_heads"] * model["head_dim"]
    T = model["total_ut_steps"]
    return {
        "blocks": passes(model) * 2 * block_matmul_params(model),
        # causal QK^T and PV: half of 2 * 2 * seq_len * (heads x head_dim)
        "attention": passes(model) * 2 * seq_len * A,
        "heads": T * 2 * vocab * H,
        "gate": (T - 1) * 2 * H,         # the last pass's gate is never asked
    }


def forward_flops_per_token(model, vocab, seq_len):
    return sum(forward_flops_by_part(model, vocab, seq_len).values())


def train_flops_per_token(model, vocab, seq_len):
    """Forward and backward: the backward of every product is two products, of attention
    twice the forward. The recomputed forward is not counted."""
    return 3 * forward_flops_per_token(model, vocab, seq_len)
