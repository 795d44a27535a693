"""Operations and bytes a hybrid state-space expert model (Nemotron-H's layers: a Mamba-2
mixer, an expert layer or a grouped-query attention, ONE a layer) requires of THIS chip, from
the configuration's keys and the window's measured expert rows.

Arithmetic only, as ``flops.py``, ``flops_ssm.py`` and ``flops_hybrid.py`` are: no count comes
from a compiler or a profiler, a multiply-add is two operations, and NOTHING RECOMPUTED
COUNTS: a cell whose layers are made again in the backward does a second forward that is not
here. The state-space scan is counted in its RECURRENT form (a head and token ``5 P N``:
decay the state, add the outer product, read it with C), whatever the number of B/C groups.
The chip holds a range of the router's experts: the routed experts' operations follow the
assignments that landed on held experts (the program's ``moe_rows_here`` counter), never
``num_experts_per_tok``; an expert is TWO matrices (``relu(W_up x)^2`` between them). Where
the held experts stand in for the absent ones (the configuration's ``stand_in``) the counter
reads every assignment, ``tokens x num_experts_per_tok``, and so do these counts.
"""

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def is_ssm_moe_model(model):
    return "hybrid_override_pattern" in model and "n_routed_experts" in model


def layer_kinds(model):
    """``(mamba layers, expert layers, attention layers)`` of the depth the configuration runs."""
    kinds = model["hybrid_override_pattern"][:model["num_hidden_layers"]]
    return kinds.count(MAMBA), kinds.count(EXPERTS), kinds.count(ATTENTION)


def _mamba_sizes(model):
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    return inner, inner + 2 * model["n_groups"] * model["ssm_state_size"]


def mamba_matmul_params(model):
    """``in_proj`` ([z | xBC | dt]) and ``out_proj`` of one mixer."""
    inner, conv = _mamba_sizes(model)
    return model["hidden_size"] * (inner + conv + model["mamba_num_heads"]) + inner * model["hidden_size"]


def mamba_layer_params(model):
    """One mixer whole and its layer's norm: the projections, the convolution and its bias,
    ``dt_bias``, ``A_log``, ``D`` and the gated norm."""
    inner, conv = _mamba_sizes(model)
    return (mamba_matmul_params(model) + conv * model["conv_kernel"] + conv
            + 3 * model["mamba_num_heads"] + inner + model["hidden_size"])


def attention_matmul_params(model):
    """q, k, v and o of one attention layer (``num_attention_heads`` heads of ``head_dim``)."""
    H, D = model["hidden_size"], model["head_dim"]
    return 2 * H * model["num_attention_heads"] * D + 2 * H * model["num_key_value_heads"] * D


def expert_params(model):
    """One routed expert: up and down."""
    return 2 * model["hidden_size"] * model["moe_intermediate_size"]


def dense_expert_layer_params(model):
    """What every token passes in an expert layer whatever its choice: the router over all
    its outputs and the shared expert."""
    H = model["hidden_size"]
    width = model.get("router_width") or model["n_routed_experts"]
    return H * width + 2 * H * model["moe_shared_expert_intermediate_size"]


def expert_layer_params(model):
    """An expert layer as the program holds it: router, its selection bias, the shared
    expert, the HELD experts and the layer's norm."""
    width = model.get("router_width") or model["n_routed_experts"]
    return (dense_expert_layer_params(model) + width
            + model["n_routed_experts"] * expert_params(model) + model["hidden_size"])


def param_count(model, vocab):
    """All parameters as the program holds them: the held experts, both embeddings, the
    convolutions, decay rates, biases and norms."""
    mamba, experts, attention = layer_kinds(model)
    H = model["hidden_size"]
    return (mamba * mamba_layer_params(model) + experts * expert_layer_params(model)
            + attention * (attention_matmul_params(model) + H) + 2 * vocab * H + H)


def scan_flops_per_token_fwd(model):
    """The recurrence of the mamba layers, a head and token: ``5 P N``."""
    mamba, _, _ = layer_kinds(model)
    return mamba * model["mamba_num_heads"] * 5 * model["mamba_head_dim"] * model["ssm_state_size"]


def forward_flops_by_part(model, vocab, seq_len, rows_per_token):
    """Operations a token, forward, by part; ``rows_per_token`` is the measured number of a
    token's assignments that landed on held experts, the expert layers' mean."""
    mamba, experts, attention = layer_kinds(model)
    _, conv = _mamba_sizes(model)
    return {
        "mixers": (2 * mamba * mamba_matmul_params(model) + mamba * 2 * model["conv_kernel"] * conv
                   + scan_flops_per_token_fwd(model)),
        "expert_layers_dense": 2 * experts * dense_expert_layer_params(model),
        "held_experts": 2 * experts * rows_per_token * expert_params(model),
        # the projections, and causal QK^T and PV: half of 2 * 2 * T * heads * head_dim
        "attention": attention * (2 * attention_matmul_params(model)
                                  + 2 * seq_len * model["num_attention_heads"] * model["head_dim"]),
        "head": 2 * vocab * model["hidden_size"],
    }


def forward_flops_per_token(model, vocab, seq_len, rows_per_token):
    return sum(forward_flops_by_part(model, vocab, seq_len, rows_per_token).values())


def train_flops_per_token(model, vocab, seq_len, rows_per_token):
    """Forward and backward: the backward of every product is two products, of attention and
    of the recurrence twice the forward. The recomputed forward is not counted."""
    return 3 * forward_flops_per_token(model, vocab, seq_len, rows_per_token)


def held_experts_required(model, rows_here, training=True):
    """Required operations and HBM bytes of the held experts' two products of one step, all
    expert layers, from ``rows_here`` (assignments on held experts, a layer's, the layers'
    mean): (flops, bytes). Two products over the rows; each held expert's two matrices are
    read once forward and twice backward (the rows' and the matrix's cotangent), in bf16; the
    rows come in and go out once a pass."""
    _, experts, _ = layer_kinds(model)
    H, F = model["hidden_size"], model["moe_intermediate_size"]
    held = model["n_routed_experts"]
    fwd_flops = experts * rows_here * 2 * expert_params(model)
    weights = held * expert_params(model) * 2
    rows = 2 * rows_here * H * 2                  # x read, y written
    fwd_bytes = experts * (weights + rows)
    if not training:
        return fwd_flops, fwd_bytes
    return 3 * fwd_flops, experts * 3 * (weights + rows)
