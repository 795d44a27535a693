"""Operations and bytes a hybrid linear-attention expert model (Qwen3-Next's block) requires
of THIS chip, from the configuration's keys and the window's measured expert rows.

Arithmetic only, as ``flops.py`` and ``flops_moe.py`` are: no count comes from a compiler or a
profiler, nothing recomputed counts, a multiply-add is two operations. Two things set this
model apart from those files' counts. Only one layer in ``full_attention_interval`` has
softmax attention; the others run the gated delta rule, counted in its RECURRENT form, so
that no choice of chunk can make the count stale. And the chip holds a range of the
router's experts: the routed experts' operations follow the assignments that landed on
held experts (the program's ``moe_rows_here`` counter), never ``num_experts_per_tok``.
"""


def is_hybrid_model(model):
    return "linear_num_value_heads" in model and "full_attention_interval" in model


def layer_kinds(model):
    """``(linear layers, full-attention layers)`` of the depth the configuration runs."""
    L, period = model["num_hidden_layers"], model["full_attention_interval"]
    full = sum((l + 1) % period == 0 for l in range(L))
    return L - full, full


def linear_mixer_params(model):
    """Wqkvz, Wba and Wout of one delta-rule mixer."""
    H = model["hidden_size"]
    qk = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    vz = model["linear_num_value_heads"] * model["linear_value_head_dim"]
    return H * (2 * qk + 2 * vz) + H * 2 * model["linear_num_value_heads"] + vz * H


def full_attention_params(model):
    """Wq (query and gate), Wk, Wv and Wo of one gated attention."""
    H, D = model["hidden_size"], model["head_dim"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return H * nq * 2 * D + 2 * H * nkv * D + nq * D * H


def dense_params_per_layer(model):
    """What every token passes in every layer whatever its mixer: the router over all its
    outputs, the shared expert and its gate."""
    H, S = model["hidden_size"], model["shared_expert_intermediate_size"]
    return H * (model.get("router_width") or model["num_experts"]) + 3 * H * S + H


def expert_params(model):
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def matmul_params(model, vocab, rows_per_token):
    """Parameters a token passes through a matrix multiplication on this chip, all layers:
    the mixers, router, shared expert, the untied head, and ``rows_per_token`` routed
    experts a layer (measured: assignments on held experts over tokens, the layers' mean)."""
    linear, full = layer_kinds(model)
    L = linear + full
    return (linear * linear_mixer_params(model) + full * full_attention_params(model)
            + L * dense_params_per_layer(model) + L * rows_per_token * expert_params(model)
            + vocab * model["hidden_size"])


def param_count(model, vocab):
    """All parameters as the program holds them: the held experts, both embeddings, the
    convolution, decay rates and norms."""
    H = model["hidden_size"]
    linear, full = layer_kinds(model)
    Hv = model["linear_num_value_heads"]
    conv = model["linear_conv_kernel_dim"] * (
        2 * model["linear_num_key_heads"] * model["linear_key_head_dim"]
        + Hv * model["linear_value_head_dim"])
    lin = linear_mixer_params(model) + conv + 2 * Hv + model["linear_value_head_dim"]
    att = full_attention_params(model) + 2 * model["head_dim"]
    each = dense_params_per_layer(model) + model["num_experts"] * expert_params(model) + 2 * H
    return linear * lin + full * att + (linear + full) * each + 2 * vocab * H + H


def attention_flops_per_token_fwd(model, seq_len):
    """Causal QK^T and PV of the full-attention layers: half of 2 * 2 * T * heads * D."""
    _, full = layer_kinds(model)
    return full * 2 * seq_len * model["num_attention_heads"] * model["head_dim"]


def delta_rule_flops_per_token_fwd(model):
    """The recurrence of the linear layers, a value head and token: decay the state (Dk Dv),
    read it with k (2), form the update (Dv, left out), add the outer product (2), read it
    with q (2): 7 Dk Dv."""
    linear, _ = layer_kinds(model)
    return linear * model["linear_num_value_heads"] * 7 * (
        model["linear_key_head_dim"] * model["linear_value_head_dim"])


def conv_flops_per_token_fwd(model):
    linear, _ = layer_kinds(model)
    channels = (2 * model["linear_num_key_heads"] * model["linear_key_head_dim"]
                + model["linear_num_value_heads"] * model["linear_value_head_dim"])
    return linear * 2 * model["linear_conv_kernel_dim"] * channels


def forward_flops_per_token(model, vocab, seq_len, rows_per_token):
    return (2 * matmul_params(model, vocab, rows_per_token)
            + attention_flops_per_token_fwd(model, seq_len)
            + delta_rule_flops_per_token_fwd(model) + conv_flops_per_token_fwd(model))


def train_flops_per_token(model, vocab, seq_len, rows_per_token):
    """Forward and backward: the backward of every product is two products, of attention
    and of the recurrence twice the forward."""
    return 3 * forward_flops_per_token(model, vocab, seq_len, rows_per_token)


def delta_rule_required(model, tokens, training=True):
    """Required operations and HBM bytes of the delta-rule calls of one step over ``tokens``
    tokens, all linear layers: (flops, bytes). Forward reads q and k (a key head), v (a
    value head) in bf16 and g, beta in float32 and writes o once (counted in bf16, the
    fewest bytes a caller could ask for); the backward reads those and o's cotangent and
    writes a cotangent for each input."""
    linear, _ = layer_kinds(model)
    qk = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    v = model["linear_num_value_heads"] * model["linear_value_head_dim"]
    gates = 2 * model["linear_num_value_heads"] * 4
    fwd_flops = delta_rule_flops_per_token_fwd(model) * tokens
    inputs = (2 * qk + v) * 2 + gates
    fwd_bytes = linear * tokens * (inputs + v * 2)
    if not training:
        return fwd_flops, fwd_bytes
    return 3 * fwd_flops, fwd_bytes + linear * tokens * (inputs + v * 2 + inputs)
