"""Operations and bytes a hybrid state-space model (Granite 4.0-H's block: Mamba-2 mixers, a
few position-free attention layers, a gated MLP a layer, a tied head) requires, from the
configuration's keys.

Arithmetic only, as ``flops.py``, ``flops_moe.py`` and ``flops_hybrid.py`` are: no count comes
from a compiler or a profiler, a multiply-add is two operations, and NOTHING RECOMPUTED COUNTS:
a cell whose blocks are made again in the backward does a second forward that is not here. The
state-space scan is counted in its RECURRENT form (a head and token: decay the state ``P N``,
add the outer product ``2 P N``, read it with C ``2 P N``), so that no choice of chunk can make
the count stale; the chunked form that runs does about three times as many.
"""


def is_ssm_model(model):
    return "mamba_n_heads" in model and "layer_types" in model


def layer_kinds(model):
    """``(mamba layers, attention layers)`` of the depth the configuration runs."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    return sum(k == "mamba" for k in kinds), sum(k == "attention" for k in kinds)


def _mamba_sizes(model):
    inner = model["mamba_n_heads"] * model["mamba_d_head"]
    return inner, inner + 2 * model["mamba_n_groups"] * model["mamba_d_state"]


def mamba_matmul_params(model):
    """``in_proj`` ([z | xBC | dt]) and ``out_proj`` of one mixer."""
    inner, conv = _mamba_sizes(model)
    return model["hidden_size"] * (inner + conv + model["mamba_n_heads"]) + inner * model["hidden_size"]


def mamba_mixer_params(model):
    """One mixer whole: the projections, the convolution and its bias, ``dt_bias``, ``A_log``,
    ``D`` and the gated norm."""
    inner, conv = _mamba_sizes(model)
    return (mamba_matmul_params(model) + conv * model["mamba_d_conv"] + conv
            + 3 * model["mamba_n_heads"] + inner)


def attention_params(model):
    """q, k, v and o of one attention layer (heads of ``hidden_size / num_attention_heads``)."""
    H = model["hidden_size"]
    kv = H // model["num_attention_heads"] * model["num_key_value_heads"]
    return 2 * H * H + 2 * H * kv


def mlp_params(model):
    return 3 * model["hidden_size"] * model["shared_intermediate_size"]


def layer_params(model, kind):
    """A layer whole: its mixer, its MLP and its two norms."""
    mixer = mamba_mixer_params(model) if kind == "mamba" else attention_params(model)
    return mixer + mlp_params(model) + 2 * model["hidden_size"]


def param_count(model, vocab):
    """All parameters as the program holds them (the table is tied: once) and the last norm."""
    mamba, attention = layer_kinds(model)
    return (mamba * layer_params(model, "mamba") + attention * layer_params(model, "attention")
            + vocab * model["hidden_size"] + model["hidden_size"])


def scan_flops_per_token_fwd(model):
    """The recurrence of the mamba layers, a head and token: ``5 P N``."""
    mamba, _ = layer_kinds(model)
    return mamba * model["mamba_n_heads"] * 5 * model["mamba_d_head"] * model["mamba_d_state"]


def forward_flops_by_part(model, vocab, seq_len):
    """Operations a token, forward, by part."""
    mamba, attention = layer_kinds(model)
    _, conv = _mamba_sizes(model)
    return {
        "projections": 2 * (mamba * mamba_matmul_params(model) + attention * attention_params(model)),
        "convolution": mamba * 2 * model["mamba_d_conv"] * conv,
        "scan": scan_flops_per_token_fwd(model),
        # causal QK^T and PV: half of 2 * 2 * T * hidden
        "attention": attention * 2 * seq_len * model["hidden_size"],
        "mlp": 2 * (mamba + attention) * mlp_params(model),
        "head": 2 * vocab * model["hidden_size"],
    }


def forward_flops_per_token(model, vocab, seq_len):
    return sum(forward_flops_by_part(model, vocab, seq_len).values())


def train_flops_per_token(model, vocab, seq_len):
    """Forward and backward: the backward of every product is two products, of attention and
    of the recurrence twice the forward. The recomputed forward is not counted."""
    return 3 * forward_flops_per_token(model, vocab, seq_len)


def ssd_scan_required(model, tokens, training=True):
    """Required operations and HBM bytes of the scans of one step over ``tokens`` tokens, all
    mamba layers, whatever implements them: (flops, bytes). Forward reads x, B and C in bf16
    and dt in float32 and writes y once in bf16; the backward reads those and y's cotangent
    and writes a cotangent for each input. A, D and their gradients are a head's and left out."""
    mamba, _ = layer_kinds(model)
    inner, conv = _mamba_sizes(model)
    fwd_flops = scan_flops_per_token_fwd(model) * tokens
    inputs = conv * 2 + model["mamba_n_heads"] * 4
    fwd_bytes = mamba * tokens * (inputs + inner * 2)
    if not training:
        return fwd_flops, fwd_bytes
    return 3 * fwd_flops, fwd_bytes + mamba * tokens * (inputs + inner * 2 + inputs)
