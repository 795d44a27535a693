"""Operations a model of latent-attention blocks, a leading dense block, expert blocks with a
shared expert and one multi-token-prediction module (GLM-4.7-Flash's) requires of THIS chip, from
the configuration's keys and the window's measured expert rows.

Arithmetic only, as ``flops.py`` and ``flops_swa_moe.py`` are: no count comes from a compiler or
a profiler, a multiply-add is two operations, and NOTHING RECOMPUTED COUNTS. Every block, the
module's too, has the latent attention: its five matrices, and the causal triangle of query-key
pairs at ``num_attention_heads`` heads of ``qk_nope + qk_rope`` (scores) and of ``v_head_dim``
(values). The chip holds a range of the router's experts: the routed experts' operations follow
the assignments that landed on held experts (the program's ``moe_rows_here`` counter; where the
held experts stand in for the absent ones, every assignment), never ``num_experts_per_tok``; an
expert is THREE matrices (gate, up, down). The head is counted at BOTH prediction depths, the
module's block and projection with the blocks. The flash kernel's own requirement is
``flops.flash_required`` under ``flash_sizes``: whole triangles, K and V at the query heads' width.
"""


def is_mla_moe_model(model):
    return "kv_lora_rank" in model and "n_routed_experts" in model


def blocks(model):
    """``(dense blocks, expert blocks)`` of what is run: the module's block is an expert block."""
    dense = min(model["first_k_dense_replace"], model["num_hidden_layers"])
    return dense, model["num_hidden_layers"] - dense + model["num_nextn_predict_layers"]


def attention_matmul_params(model):
    """W_qa, W_qb, W_kva, W_kvb and W_o of one block."""
    H, n = model["hidden_size"], model["num_attention_heads"]
    nope, rot, wide = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    return (H * model["q_lora_rank"] + model["q_lora_rank"] * n * (nope + rot)
            + H * (model["kv_lora_rank"] + rot) + model["kv_lora_rank"] * n * (nope + wide)
            + n * wide * H)


def attention_params(model):
    """The projections and the two latent norms."""
    return attention_matmul_params(model) + model["q_lora_rank"] + model["kv_lora_rank"]


def expert_params(model):
    """One expert, routed or shared: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def dense_mlp_params(model):
    return 3 * model["hidden_size"] * model["intermediate_size"]


def router_params(model):
    return model["hidden_size"] * (model.get("router_width") or model["n_routed_experts"])


def dense_block_params(model):
    return attention_params(model) + 2 * model["hidden_size"] + dense_mlp_params(model)


def expert_block_params(model):
    """An expert block as the program holds it: the attention, two norms, the router over all
    its outputs and its selection biases, the HELD experts and the shared one."""
    width = model.get("router_width") or model["n_routed_experts"]
    return (attention_params(model) + 2 * model["hidden_size"] + router_params(model) + width
            + (model["n_routed_experts"] + model["n_shared_experts"]) * expert_params(model))


def module_params(model):
    """A prediction module: two norms, its projection, a whole expert block, its last norm."""
    H = model["hidden_size"]
    return 2 * H + 2 * H * H + expert_block_params(model) + H


def param_count(model, vocab):
    H = model["hidden_size"]
    dense, experts = blocks(model)
    modules = model["num_nextn_predict_layers"]
    return (2 * vocab * H + dense * dense_block_params(model)
            + (experts - modules) * expert_block_params(model) + modules * module_params(model) + H)


def forward_flops_by_part(model, vocab, seq_len, rows_per_token):
    """Operations of ONE SEQUENCE of ``seq_len`` tokens, forward, by part; ``rows_per_token`` is
    the measured number of a token's assignments computed here, the expert layers' mean."""
    dense, experts = blocks(model)
    H, n = model["hidden_size"], model["num_attention_heads"]
    pairs = seq_len * (seq_len + 1) // 2
    scores, values = model["qk_nope_head_dim"] + model["qk_rope_head_dim"], model["v_head_dim"]
    return {
        "latent_projections": 2 * seq_len * (dense + experts) * attention_matmul_params(model),
        # QK^T and PV over the triangle: 2 * pairs * heads * (their two widths)
        "attention": 2 * pairs * (dense + experts) * n * (scores + values),
        "dense_mlp": 2 * seq_len * dense * dense_mlp_params(model),
        "routers": 2 * seq_len * experts * router_params(model),
        "experts": 2 * seq_len * experts * rows_per_token * expert_params(model),
        "shared_experts": 2 * seq_len * experts * model["n_shared_experts"] * expert_params(model),
        "mtp_projection": 2 * seq_len * model["num_nextn_predict_layers"] * 2 * H * H,
        "heads": 2 * seq_len * (1 + model["num_nextn_predict_layers"]) * vocab * H,
    }


def train_flops_per_token(model, vocab, seq_len, rows_per_token):
    """Forward and backward a token: the backward of every product is two products, of
    attention twice the forward. The recomputed forward is not counted."""
    return 3 * sum(forward_flops_by_part(model, vocab, seq_len, rows_per_token).values()) / seq_len


def flash_sizes(model):
    """The three GPT-2 names ``flops.flash_required`` reads, such that it counts exactly this
    model's kernel calls: a call a block (the module's too), ``heads x head`` wide, keys and
    values as many heads as the queries (which is what that function assumes)."""
    return {"n_embd": model["num_attention_heads"] * model["v_head_dim"],
            "n_layer": sum(blocks(model)), "n_head": model["num_attention_heads"]}
