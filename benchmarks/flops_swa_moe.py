"""Operations and bytes a model of sliding-window and full attention layers with an expert
layer each (Mellum 2's) requires of THIS chip, from the configuration's keys and the window's
measured expert rows.

Arithmetic only, as ``flops.py`` and ``flops_ssm_moe.py`` are: no count comes from a compiler
or a profiler, a multiply-add is two operations, and NOTHING RECOMPUTED COUNTS. Attention is
counted by the query-key pairs a layer's mask ALLOWS: the triangle of a full layer, the band of
a sliding one (``band_pairs_required``), never the pairs a tile schedule visits; K and V are as
wide as the key/value heads. The chip holds a range of the router's experts: the experts'
operations follow the assignments that landed on held experts (the program's ``moe_rows_here``
counter; where the held experts stand in for the absent ones, every assignment), never
``num_experts_per_tok``; an expert is THREE matrices (gate, up, down).
"""

SLIDING = "sliding_attention"


def is_swa_moe_model(model):
    return "layer_types" in model and "moe_intermediate_size" in model


def layer_kinds(model):
    return list(model["layer_types"][:model["num_hidden_layers"]])


def window_of(model, kind):
    sliding = kind == SLIDING and model.get("use_sliding_window", True)
    return model["sliding_window"] if sliding else None


def band_pairs_required(seq_len, window=None):
    """The query-key pairs a causal mask allows over ``seq_len`` positions: query ``i`` sees
    ``min(i + 1, window)`` keys (the whole triangle where ``window`` is None)."""
    w = seq_len if window is None else min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def attention_matmul_params(model):
    """q, k, v and o of one layer."""
    H, D = model["hidden_size"], model["head_dim"]
    return 2 * H * model["num_attention_heads"] * D + 2 * H * model["num_key_value_heads"] * D


def expert_params(model):
    """One expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def router_params(model):
    return model["hidden_size"] * (model.get("router_width") or model["num_experts"])


def layer_params(model):
    """A layer as the program holds it: the projections, two norms, the per-head norms of q
    and k, the router over all its outputs and the HELD experts."""
    return (attention_matmul_params(model) + 2 * model["hidden_size"] + 2 * model["head_dim"]
            + router_params(model) + model["num_experts"] * expert_params(model))


def param_count(model, vocab):
    H = model["hidden_size"]
    return model["num_hidden_layers"] * layer_params(model) + 2 * vocab * H + H


def forward_flops_by_part(model, vocab, seq_len, rows_per_token):
    """Operations of ONE SEQUENCE of ``seq_len`` tokens, forward, by part; ``rows_per_token``
    is the measured number of a token's assignments computed here, the layers' mean."""
    kinds = layer_kinds(model)
    heads_wide = model["num_attention_heads"] * model["head_dim"]
    # QK^T and PV over the allowed pairs: 2 * 2 * pairs * heads * head_dim
    pairs = sum(band_pairs_required(seq_len, window_of(model, kind)) for kind in kinds)
    return {
        "projections": 2 * seq_len * len(kinds) * attention_matmul_params(model),
        "attention": 4 * pairs * heads_wide,
        "router": 2 * seq_len * len(kinds) * router_params(model),
        "experts": 2 * seq_len * len(kinds) * rows_per_token * expert_params(model),
        "head": 2 * seq_len * vocab * model["hidden_size"],
    }


def train_flops_per_token(model, vocab, seq_len, rows_per_token):
    """Forward and backward a token: the backward of every product is two products, of
    attention twice the forward. The recomputed forward is not counted."""
    return 3 * sum(forward_flops_by_part(model, vocab, seq_len, rows_per_token).values()) / seq_len


def flash_required(model, batch, seq_len, training=True):
    """Required operations and HBM bytes of the flash-attention calls of one step over
    ``batch`` sequences, all layers: (flops, bytes). The pairs INSIDE each layer's band;
    forward reads q, k, v and writes o once, backward reads q, k, v, o, do and writes dq, dk,
    dv; q, o and their cotangents at the query heads' width, k, v and theirs at the key/value
    heads'; bf16 throughout, the per-row statistics left out."""
    kinds = layer_kinds(model)
    D = model["head_dim"]
    pairs = sum(band_pairs_required(seq_len, window_of(model, kind)) for kind in kinds)
    fwd_flops = batch * 4 * pairs * model["num_attention_heads"] * D
    wide = batch * seq_len * D * 2 * (2 * model["num_attention_heads"] + 2 * model["num_key_value_heads"])
    fwd_bytes = len(kinds) * wide
    if not training:
        return fwd_flops, fwd_bytes
    return 3 * fwd_flops, 3 * fwd_bytes
