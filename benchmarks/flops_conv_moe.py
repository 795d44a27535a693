"""Operations a model of gated short-convolution and grouped-query attention layers, leading dense
layers and expert layers without a shared expert (LFM2-24B-A2B's) requires of THIS chip, from the
configuration's keys and the window's measured expert rows; and the bytes the gated convolution
between a short-conv operator's two products has to move.

Arithmetic only, as ``flops.py`` and ``flops_mla_moe.py`` are: no count comes from a compiler or
a profiler, a multiply-add is two operations, and NOTHING RECOMPUTED COUNTS in the operations. A
short-conv operator is two products (``hidden -> 3 hidden``, ``hidden -> hidden``) with two gates
and ``conv_L_cache`` taps a channel between them; an attention operator four projections and the
causal triangle of query-key pairs at ``num_attention_heads`` heads of ``hidden / heads``. The
chip holds a range of the router's experts: the routed experts' operations follow the assignments
that landed on held experts (the program's ``moe_rows_here`` counter; where the held experts stand
in for the absent ones, every assignment), never ``num_experts_per_tok``; an expert is THREE
matrices (gate, up, down). The head is the embedding table, counted once. The flash kernel's own
requirement is ``flops.flash_required`` under ``flash_sizes``.
"""

CONV, ATTENTION = "conv", "full_attention"


def is_conv_moe_model(model):
    return "conv_L_cache" in model and "num_dense_layers" in model


def kinds(model):
    return list(model["layer_types"][:model["num_hidden_layers"]])


def layers(model):
    """``(short-conv layers, attention layers, dense layers, expert layers)`` of what is run."""
    run = kinds(model)
    dense = min(model["num_dense_layers"], len(run))
    return run.count(CONV), run.count(ATTENTION), dense, len(run) - dense


def head_dim(model):
    return model["hidden_size"] // model["num_attention_heads"]


def short_conv_matmul_params(model):
    """W_in and W_out of one short-conv operator."""
    return 4 * model["hidden_size"] ** 2


def short_conv_params(model):
    return short_conv_matmul_params(model) + model["conv_L_cache"] * model["hidden_size"]


def attention_matmul_params(model):
    """W_q, W_k, W_v and W_o of one attention operator."""
    H, D = model["hidden_size"], head_dim(model)
    return 2 * H * model["num_attention_heads"] * D + 2 * H * model["num_key_value_heads"] * D


def attention_params(model):
    """The projections and the two per-head norms."""
    return attention_matmul_params(model) + 2 * head_dim(model)


def expert_params(model):
    """One expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def dense_mlp_params(model):
    return 3 * model["hidden_size"] * model["intermediate_size"]


def router_params(model):
    return model["hidden_size"] * (model.get("router_width") or model["num_experts"])


def layer_params(model, l):
    """Layer ``l`` as the program holds it: its operator, two norms, and the dense MLP or the
    router over all its outputs, its selection biases and the HELD experts."""
    operator = short_conv_params(model) if kinds(model)[l] == CONV else attention_params(model)
    if l < model["num_dense_layers"]:
        return operator + 2 * model["hidden_size"] + dense_mlp_params(model)
    width = model.get("router_width") or model["num_experts"]
    return (operator + 2 * model["hidden_size"] + router_params(model) + width
            + model["num_experts"] * expert_params(model))


def param_count(model, vocab):
    """One table (tied), the layers, the last norm."""
    H = model["hidden_size"]
    return vocab * H + sum(layer_params(model, l) for l in range(model["num_hidden_layers"])) + H


def forward_flops_by_part(model, vocab, seq_len, rows_per_token):
    """Operations of ONE SEQUENCE of ``seq_len`` tokens, forward, by part; ``rows_per_token`` is
    the measured number of a token's assignments computed here, the expert layers' mean."""
    conv, attention, dense, experts = layers(model)
    H, n, D = model["hidden_size"], model["num_attention_heads"], head_dim(model)
    pairs = seq_len * (seq_len + 1) // 2
    return {
        "short_conv_projections": 2 * seq_len * conv * short_conv_matmul_params(model),
        # two gates and a multiply-add a tap, a channel
        "short_conv_gates": seq_len * conv * (2 + 2 * model["conv_L_cache"]) * H,
        "attention_projections": 2 * seq_len * attention * attention_matmul_params(model),
        # QK^T and PV over the triangle: 2 * pairs * heads * (their two widths)
        "attention": 2 * pairs * attention * n * 2 * D,
        "dense_mlp": 2 * seq_len * dense * dense_mlp_params(model),
        "routers": 2 * seq_len * experts * router_params(model),
        "experts": 2 * seq_len * experts * rows_per_token * expert_params(model),
        "head": 2 * seq_len * vocab * H,
    }


def train_flops_per_token(model, vocab, seq_len, rows_per_token):
    """Forward and backward a token: the backward of every product is two products, of
    attention twice the forward. The recomputed forward is not counted."""
    return 3 * sum(forward_flops_by_part(model, vocab, seq_len, rows_per_token).values()) / seq_len


def flash_sizes(model):
    """The three GPT-2 names ``flops.flash_required`` reads, such that it counts exactly this
    model's kernel calls: a call an attention layer, ``heads x head`` wide (that function prices
    keys and values at the query heads' width; the eight key/value heads move a quarter of it)."""
    return {"n_embd": model["num_attention_heads"] * head_dim(model),
            "n_layer": layers(model)[1], "n_head": model["num_attention_heads"]}


def short_conv_gate_required(model, tokens, recomputed):
    """``(operations, bytes)`` a STEP over ``tokens`` tokens needs for the gated convolution
    BETWEEN a short-conv operator's two products, all its layers, whatever implements it. Forward
    (and the second forward where layers are ``recomputed``): ``[tokens, 3 hidden]`` read and
    ``[tokens, hidden]`` written once, bf16. Backward: ``[tokens, 3 hidden]`` and ``dy [tokens,
    hidden]`` read, ``[tokens, 3 hidden]`` of cotangent written. The taps' weights are nothing.
    Operations: two gates and a multiply-add a tap forward, twice that backward."""
    conv, H = layers(model)[0], model["hidden_size"]
    forwards = 2 if recomputed else 1
    elem = tokens * H * 2                                  # one [tokens, hidden] bf16 array, bytes
    per_forward = tokens * (2 + 2 * model["conv_L_cache"]) * H
    return (conv * (forwards + 2) * per_forward, conv * (forwards * 4 + 7) * elem)
