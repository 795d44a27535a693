"""The chip's compiler, without the chip: every Pallas kernel of the main paths is
compiled at real widths for a described ``v5e:2x2`` device.

Interpret mode (the rest of the suite) cannot see what the TPU compiler refuses:
a slice off the tiling, too much VMEM, an API the installed JAX has dropped. Each
case passes ``interpret=False`` explicitly (``jax.default_backend()`` is the CPU
here) and asserts the compiled program holds a ``tpu_custom_call``. Nothing runs,
so these say nothing about results or times; ``chip_smoke.py`` does that on the chip.
"""

import collections
import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from deepspeed_tpu.ops.pallas.block_sparse_attention import block_sparse_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention, flash_attention_rows
from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention
from deepspeed_tpu.ops.sparse_attention import BSLongformerSparsityConfig
from deepspeed_tpu.utils import spans


@pytest.fixture(scope="module")
def topo():
    # tests/conftest.py has the persistent compile cache off: a compile for a
    # described device can be written to it but not read back without a chip
    assert not jax.config.jax_enable_compilation_cache
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def sumsq_grad(attn):
    return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2))


FLASH_SHAPES = [(3, 25, 1024, 64),    # GPT-2 XL heads, chip_smoke phase A
                (8, 16, 1024, 64),    # GPT-2 medium heads
                (1, 16, 8192, 64),    # long sequence
                (1, 4, 8192, 128),    # the most the resident forward holds: the backward's
                                      # dQ accumulator takes it past the default 16 MB of VMEM
                (2, 16, 4096, 128)]   # OLMoE's heads, one chip's rows of olmoe_d4_train_4chip


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_flash_attention_compiles_for_v5e(chip, shape, backward):
    def attn(q, k, v):
        return flash_attention(q, k, v, True, interpret=False)

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)
    text = compiled_text(sumsq_grad(attn) if backward else attn, x, x, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_at_keys_wider_than_the_values_compiles_for_v5e(chip, backward):
    """Xing4.0's latent attention as ``xing4_ep8_d5_train_1chip`` calls it: 32 heads, keys of
    128 + 64 beside values of 128, at 4,096 positions (192 is no multiple of the 128 lanes, and
    the kernels' blocks take two widths since PR 58); nothing is padded outside the kernel."""
    def attn(q, k, v):
        return flash_attention(q, k, v, True, 0.15, interpret=False)

    qk = jax.ShapeDtypeStruct((1, 32, 4096, 192), jnp.bfloat16, sharding=chip)
    v = jax.ShapeDtypeStruct((1, 32, 4096, 128), jnp.bfloat16, sharding=chip)
    text = compiled_text(sumsq_grad(attn) if backward else attn, qk, qk, v)
    assert "tpu_custom_call" in text and "bf16[32,4096,128]" in text and "pad(" not in text


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
def test_grouped_query_flash_attention_compiles_for_v5e(chip, backward):
    """Qwen3-Next's full-attention layer as ``qwen3next_ep16_train_1chip`` calls it: 16 query
    heads of 256 over 2 key/value heads at 8,192 positions, K and V tiles indexed by group."""
    def attn(q, k, v):
        return flash_attention(q, k, v, True, interpret=False)

    q = jax.ShapeDtypeStruct((1, 16, 8192, 256), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, 2, 8192, 256), jnp.bfloat16, sharding=chip)
    text = compiled_text(sumsq_grad(attn) if backward else attn, q, kv, kv)
    assert "tpu_custom_call" in text


def test_sliding_window_flash_attention_compiles_for_v5e(chip):
    """A sliding-window layer of ``mellum2_ep4_d4_train_1chip``: 32 query heads of 128 over 4
    key/value heads at 8,192 positions under a window of 1,024, forward and backward, at the
    tiles ``_resolve`` picks for a windowed call (the band's three loops a kernel)."""
    def attn(q, k, v):
        return flash_attention(q, k, v, True, interpret=False, window=1024)

    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16, sharding=chip)
    text = compiled_text(sumsq_grad(attn), q, kv, kv)
    assert "ds_flash_fwd" in text and "ds_flash_bwd_dkv" in text


# the row-major entry's lanes branch: (B, T, query heads, key/value heads, width, which of q, k and v
# arrive head-major). GLM-4.7-Flash's 20 heads of 256 with q from the pass that splits and turns it,
# and with all three head-major as the cell calls it; 32 over 4 of 128 with q and k from a rotary
# pass and v as projected (a group's dK summed head-major and its dV as lane blocks; no cell's call:
# Mellum 2's 32 over 4 are banded, and a banded call is turned head-major by the entry,
# ``test_sliding_window_flash_attention_compiles_for_v5e``); Nemotron-H's position-free 32 over 2
# (all three as projected)
ROW_MAJOR_CALLS = [(1, 8192, 20, 20, 256, "q"), (1, 8192, 20, 20, 256, "qkv"),
                   (1, 8192, 32, 4, 128, "qk"), (1, 8192, 32, 2, 128, "")]


@pytest.mark.parametrize("B,T,H,Hkv,D,head_major", ROW_MAJOR_CALLS,
                         ids=[f"{c[2]}over{c[3]}x{c[4]}{'-' + c[5] if c[5] else ''}" for c in ROW_MAJOR_CALLS])
def test_the_row_major_flash_kernels_compile_for_v5e(chip, B, T, H, Hkv, D, head_major):
    """The lanes branch of ``flash_attention_rows`` at the cells' shapes, value and gradient in one
    program (this file's cases stay few enough to go whole into one worker's first chunk:
    ``tests/conftest.py``): the kernels under their names, the row sums of ``o * dO`` as a kernel of
    their own, and no operand copied or turned on its way in or out."""
    def attn(q, k, v):
        return flash_attention_rows(q, k, v, H, Hkv, True, interpret=False)

    shape = lambda n, name: (B, n, T, D) if name in head_major else (B, T, n * D)      # noqa: E731
    q, k, v = (jax.ShapeDtypeStruct(shape(n, name), jnp.bfloat16, sharding=chip)
               for n, name in ((H, "q"), (Hkv, "k"), (Hkv, "v")))
    text = compiled_text(sumsq_grad(attn), q, k, v)
    assert "ds_flash_fwd" in text and "ds_flash_bwd_dkv" in text and "ds_flash_delta" in text
    assert f"bf16[{B},{T},{H * D}]{{2,1,0" in text             # the output, as a projection reads it
    assert not re.search(r"= bf16\[\S* (copy|transpose)\(", text)


def test_the_row_major_flash_kernels_compile_under_a_four_chip_mesh(topo):
    """The row-major entry under the engine's mesh, at ``olmoe_d4_train_4chip``'s shapes (the cell itself
    keeps the head-major call: PERF.md, PR 60): q and k head-major from a rotary pass, v as projected,
    the batch over ``data``: the kernels split themselves over the mesh in both layouts at once."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4, 1), ("pipe", "data", "model"))
    rows = NamedSharding(mesh, P("data"))
    qk = jax.ShapeDtypeStruct((8, 16, 4096, 128), jnp.bfloat16, sharding=rows)
    v = jax.ShapeDtypeStruct((8, 4096, 16 * 128), jnp.bfloat16, sharding=rows)

    def attn(q, k, v):
        return flash_attention_rows(q, k, v, 16, 16, True, interpret=False)

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        text = compiled_text(sumsq_grad(attn), qk, qk, v)
    assert "ds_flash_fwd" in text and "ds_flash_bwd_dkv" in text and "ds_flash_delta" in text
    assert "bf16[2,4096,2048]" in text and "bf16[32,4096,128]" in text        # one chip's rows, both ways


@functools.lru_cache(maxsize=None)
def latent_attention_block_text(chip):
    """One latent (MLA) mixer of ``glm47flash_ep8_d5_train_1chip`` at its published widths and 8,192
    positions, value and gradient, as the chip's compiler leaves it: compiled once a process."""
    from benchmarks.manifest import Manifest
    from benchmarks.runners.train_mla_moe import build_model
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")     # the kernel, not its interpreter
        model = build_model(Manifest().config("glm-4.7-flash-ep8-d5"))
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"][1]["attn"]
        assert shapes["wkv_b"].shape == (512, 20 * (192 + 256)) and shapes["wkv_a"].shape == (2048, 512 + 64)
        ap = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=chip), shapes)
        x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16, sharding=chip)
        grad = jax.grad(lambda x, p: jnp.sum(model.attention(x, p).astype(jnp.float32) ** 2), argnums=(0, 1))
        return compiled_text(grad, x, ap)


def test_a_latent_attention_block_compiles_for_v5e(chip):
    """Both bottlenecks, the one rotary key broadcast to 20 heads, and the flash kernel at 20 query
    over 20 key/value heads of 192 + 64 | 256 (no grouped-query case compiles 20 key/value heads
    of 256)."""
    text = latent_attention_block_text(chip)
    assert "ds_attn_latent" in text and re.search(r"bf16\[1,20,8192,256\]", text)
    for kernel in ("ds_flash_fwd", "ds_flash_bwd_dkv"):
        assert re.search(r'custom-call\(.*op_name="[^"]*ds_attn_latent[^"]*/%s/' % kernel, text), kernel
    # the output leaves the kernel [1, 8192, 20 * 256] as W_o reads it, and its cotangent goes in as
    # W_o's backward writes it: no copy turns either (PR 60; two of the four copies of
    # bf16[1,8192,20,256] this program had). The two left lay the q projection's output out head-major
    # for the pass that splits and turns it, and that pass's cotangent back
    assert re.search(r"= \(bf16\[1,8192,5120\]\{2,1,0\S*, f32\[20,1,8192\]\S*\) custom-call\(", text)
    assert len(re.findall(r"= bf16\[1,8192,20,256\]\S* copy\(", text)) == 2


def test_every_product_and_fusion_of_a_v5e_program_is_priced(chip):
    """``utils/hlo.instruction_costs`` over the same program: every ``fusion``, ``dot`` and
    ``convolution`` the entry computation runs (a kernel's wrapper aside) has a ``cost``, its bytes
    above zero unless all it writes stays in fast memory (``S(1)``: traffic the floor leaves out),
    and every one that holds a product is among ``products`` with its ``[M, K, N, types]`` and
    flops above zero. A JAX whose compiler prints its text another way is red here, where on the
    chip the only sign is a cell's ``unpriced`` share rising."""
    from deepspeed_tpu.utils import hlo
    text = latent_attention_block_text(chip)
    priced = hlo.instruction_costs(text)
    bodies = dict(re.findall(r"^%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.S | re.M))
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M).group(1)
    seen = collections.Counter()
    for match in map(INSTRUCTION_RE.match, entry.splitlines()):
        name, result, opcode, rest = match.groups() if match else (None,) * 4
        if opcode not in ("fusion", "dot", "convolution"):
            continue
        body = bodies[re.search(r"calls=%?([\w.\-]+)", rest).group(1)] if opcode == "fusion" else f" {opcode}("
        if "tpu_custom_call" in body:
            assert name not in priced["cost"]           # a kernel is priced by its own reckoning
            continue
        holds_a_product = bool(re.search(r" (dot|convolution)\(", body))
        stays_in_fast_memory = all("S(1)" in layout for layout in re.findall(r"\[[\d,]*\]\{([^}]*)\}", result))
        seen[holds_a_product, stays_in_fast_memory] += 1
        flops, nbytes = priced["cost"][name]
        assert nbytes > 0 or stays_in_fast_memory, name
        assert (name in priced["products"]) == holds_a_product == (flops > 0), name
        for m, k, n, types in priced["products"].get(name, {"mkn": []})["mkn"]:
            assert m > 0 and k > 0 and n > 0 and re.fullmatch(r"\w+x\w+->\w+", types), (name, types)
    # the block's projections, forward and backward, and the elementwise passes between them
    assert seen[True, False] >= 10 and seen[False, False] >= 10, seen
    assert priced["collectives"] == []


def test_a_gated_short_convolution_operator_compiles_for_v5e(chip, monkeypatch):
    """One gated short-convolution operator of ``lfm2_ep8_d7_train_1chip`` at its published widths and
    8,192 positions, value and gradient: the convolution's two kernels at THREE taps over 2,048
    channels with no bias and no SiLU, on an operand of their own (the product ``B * z``: no window
    of the projection's output), between the 2048 -> 6144 and 2048 -> 2048 products."""
    from benchmarks.manifest import Manifest
    from benchmarks.runners.train_conv_moe import build_model
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # the kernels, not their interpreter
    model = build_model(Manifest().config("lfm2-24b-a2b-ep8-d7"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"][0]["conv"]
    assert shapes["w_in"].shape == (2048, 6144) and shapes["conv_w"].shape == (3, 2048)
    cp = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=chip), shapes)
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16, sharding=chip)
    grad = jax.grad(lambda x, p: jnp.sum(model.short_conv(x, p).astype(jnp.float32) ** 2), argnums=(0, 1))
    text = compiled_text(grad, x, cp)
    assert "ds_causal_conv_fwd" in text and "ds_causal_conv_bwd" in text and "ds_short_conv_gate" in text
    assert re.search(r"bf16\[1,8192,6144\]", text)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("T", [8192, 1024])
def test_the_delta_rule_kernels_compile_for_v5e(chip, T, dtype):
    """The gated delta rule as ``qwen3next_ep16_train_1chip`` calls it: 16 key heads serving 32
    value heads of 128, forward and the hand-written backward, in bfloat16 (the step) and on
    float32 arrays (the set-up's check: 8,192 positions for o, 1,024 for the gradients)."""
    from deepspeed_tpu.ops.delta_rule import gated_delta_rule
    shape = lambda *s, dt=dtype: jax.ShapeDtypeStruct(s, dt, sharding=chip)      # noqa: E731
    args = (shape(1, T, 16, 128), shape(1, T, 16, 128), shape(1, T, 32, 128),
            shape(1, T, 32, dt=jnp.float32), shape(1, T, 32, dt=jnp.float32))
    loss = lambda *a: jnp.sum(gated_delta_rule(*a, interpret=False).astype(jnp.float32) ** 2)  # noqa: E731
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    assert "ds_delta_rule_fwd" in text and "ds_delta_rule_bwd" in text
    # what the backward is handed: a block's states, a chunk's inverses and updates (0.24 GB
    # at 8,192 positions), never a state a chunk (1.07 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9 * T / 8192


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
def test_grouped_query_flash_attention_at_width_64_compiles_for_v5e(chip, backward):
    """Granite 4.0-H's attention layer as ``granite4h_d10_train_1chip`` calls it: 32 query heads
    of 64 over 8 key/value heads at 8,192 positions, the published scale 1/64, no positions."""
    def attn(q, k, v):
        return flash_attention(q, k, v, True, sm_scale=1 / 64, interpret=False)

    q = jax.ShapeDtypeStruct((1, 32, 8192, 64), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, 8, 8192, 64), jnp.bfloat16, sharding=chip)
    text = compiled_text(sumsq_grad(attn) if backward else attn, q, kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("T", [8192, 1024])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_the_state_space_scan_compiles_for_v5e(chip, dtype, T):
    """The scan as ``granite4h_d10_train_1chip`` calls it: 64 heads of 64 with a state of 128,
    forward and the hand-written backward, in bfloat16 (the step) and on float32 arrays (the
    set-up's check: 8,192 positions for y, 1,024 for the gradients)."""
    from deepspeed_tpu.ops.ssd import ssd_scan
    shape = lambda *s, dt=dtype: jax.ShapeDtypeStruct(s, dt, sharding=chip)      # noqa: E731
    f32 = jnp.float32
    args = (shape(1, T, 64, 64), shape(1, T, 64, dt=f32), shape(64, dt=f32),
            shape(1, T, 128), shape(1, T, 128), shape(64, dt=f32))
    loss = lambda *a: jnp.sum(ssd_scan(*a, interpret=False).astype(jnp.float32) ** 2)  # noqa: E731
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(*args).compile()
    text = compiled.as_text()
    assert "ds_ssd_scan_fwd" in text and "ds_ssd_scan_bwd" in text
    # what the backward is handed: the states every tile starts from (0.13 GB at 8,192
    # positions) and the operands laid out; never a decay matrix (1.35 GB the plain form's)
    assert compiled.memory_analysis().temp_size_in_bytes < (0.3e9 if dtype == jnp.bfloat16 else 0.5e9) * T / 8192


@pytest.mark.parametrize("wide, columns, bias, dtype, T", [
    (8512, (4096, 8448), True, jnp.bfloat16, 8192),       # granite4h_d10_train_1chip's step
    (12288, (0, 8192), False, jnp.bfloat16, 8192),        # qwen3next_ep16_train_1chip's step
    (8512, (4096, 8448), True, jnp.float32, 1024),        # the set-up's check of a mixer's gradients
    (8512, (4096, 8448), True, jnp.bfloat16, 1000),       # a last block the sequence does not fill
], ids=["granite", "qwen3next", "granite-f32-check", "a-short-last-block"])
def test_the_causal_convolution_compiles_for_v5e(chip, wide, columns, bias, dtype, T):
    """The mixers' short convolution as the two hybrid cells call it: a window of the
    projection's output read where it lies, forward and the hand-written backward. Beside the
    operands the program holds the result and the window's cotangent, never a float32 copy of
    the shifted inputs (0.57 GB for Granite's four taps)."""
    from deepspeed_tpu.ops.delta_rule import causal_conv
    C = columns[1] - columns[0]
    shape = lambda *s, dt=dtype: jax.ShapeDtypeStruct(s, dt, sharding=chip)      # noqa: E731
    args = (shape(1, T, wide), shape(4, C)) + ((shape(C),) if bias else ())
    conv = lambda x, w, b=None: causal_conv(x, w, True, b, columns, interpret=False)      # noqa: E731
    loss = lambda *a: jnp.sum(conv(*a).astype(jnp.float32) ** 2)      # noqa: E731
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(len(args))))).lower(*args).compile()
    text = compiled.as_text()
    assert "ds_causal_conv_fwd" in text and "ds_causal_conv_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 5 * T * wide * jnp.dtype(dtype).itemsize


def assert_the_flash_forward_runs_once(text):
    """One attention layer's compiled gradient program calls ``ds_flash_fwd`` once, in the first
    forward: a recomputed layer keeps the kernel's output by name (PR 41)."""
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line and "ds_flash_fwd" in line]
    assert len(calls) == 1 and "rematted_computation" not in calls[0]


def test_a_recomputed_state_space_block_and_the_tied_head_compile_for_v5e(chip, monkeypatch):
    """The gradient program of ``granite4h_d10_train_1chip`` at its widths and 8,192 positions,
    cut to one Mamba-2 block and the attention block (whole blocks recomputed, the tied head's
    cross-entropy over 12,544 words): the flash kernel is in it, its forward ONCE (a block keeps
    the kernel's output by name since PR 41, and the second forward runs none), and what it needs
    beside its parameters and their gradients is a block's internals (2.43 GB here; the cell's
    ten layers compile to 2.28 GB of temporaries with what they keep; 12.35 GB of training state
    leave 3.6)."""
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig, GraniteHybridModel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # the flash kernel, not its interpreter
    model = GraniteHybridModel(GraniteHybridConfig(
        vocab_size=12544, num_hidden_layers=2, layer_types=("mamba", "attention"), remat=True))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=chip), shapes)
    assert sum(s.size for s in jax.tree_util.tree_leaves(shapes)) == 76_182_976 + 60_821_504 + 25_692_160
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=chip)
    compiled = jax.jit(jax.value_and_grad(model.apply)).lower(params, tokens, tokens).compile()
    assert_the_flash_forward_runs_once(compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 2.7e9


def test_the_grouped_state_space_scan_compiles_for_v5e(chip):
    """The scan as ``nemotronh_ep16_d9_train_1chip`` calls it: 64 heads of 64 in EIGHT groups
    with a B and C of 128 each, a grid step one group's eight heads, tiles of 128 tokens."""
    from deepspeed_tpu.ops.ssd import ssd_scan
    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=chip)      # noqa: E731
    f32 = jnp.float32
    args = (shape(1, 8192, 64, 64), shape(1, 8192, 64, dt=f32), shape(64, dt=f32),
            shape(1, 8192, 8, 128), shape(1, 8192, 8, 128), shape(64, dt=f32))
    loss = lambda *a: jnp.sum(ssd_scan(*a, 128, interpret=False).astype(jnp.float32) ** 2)  # noqa: E731
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(*args).compile()
    text = compiled.as_text()
    assert "ds_ssd_scan_fwd" in text and "ds_ssd_scan_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9


def expert_cells_gradient_program(chip, monkeypatch, build_model, config):
    """``(compiled, model, the leaves' shapes)``: a cell's whole gradient program at its
    configuration's widths and 1 x 8,192 positions, bf16 leaves, compiled for the described chip."""
    from benchmarks.manifest import Manifest
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # the kernels, not their interpreters
    model = build_model(Manifest().config(config))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=chip), shapes)
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=chip)
    # traced as the engine traces it, inside a step program's call: every grouped product leaves
    # in the recorder how it runs (``moe._count_product``)
    with spans.recorder().span("train.grad_program", engine=spans.recorder().new_engine(), program="loss_and_grad") as call:
        compiled = jax.jit(jax.value_and_grad(lambda *a: model.apply(*a)[0])).lower(params, tokens, tokens).compile()
    products = {name: n for name, n in spans.recorder().counters(call.engine).items() if name.startswith("moe.")}
    assert products and all(".whole_k[loss_and_grad] " in name for name in products), products     # no contraction is cut
    # and each says what its row tile saw and bought: the rows a group holds, the walk's most visits over those needed
    said = [re.fullmatch(r"moe\.\w+\.whole_k\[loss_and_grad\] \d+x\d+ in (\d+)x\d+x\d+, (\d+) rows a group, visits <= (\d\.\d\d)", name)
            for name in products]
    assert all(said), products
    assert all(int(m[1]) in (128, 256, 512) and 1.0 <= float(m[3]) <= 1.0 + 512 / int(m[2]) for m in said), products
    return compiled, model, shapes


def test_the_state_space_expert_cells_gradient_program_compiles_for_v5e(chip, monkeypatch):
    """The gradient program of ``nemotronh_ep16_d9_train_1chip`` WHOLE: the published widths, the
    nine layers MEMEM*EME with 8 of 128 experts held, 1 x 8,192 positions, whole layers
    recomputed, the untied head's cross-entropy over 16,384 words. The grouped scan, the
    convolution over 6,144 channels, the flash kernel at sixteen query heads a key/value head
    and the grouped products over experts 1,856 wide (no multiple of 128) are all in it, the
    flash forward ONCE (a layer keeps the kernel's output by name since PR 41), and what it needs
    beside its parameters and their gradients stays under the 5.3 GB that 10.67 GB of training
    state leave on the chip."""
    from benchmarks.runners.train_ssm_moe import build_model
    compiled, model, shapes = expert_cells_gradient_program(chip, monkeypatch, build_model, "nemotron-twotower-30b-a3b-ep16-d9")
    assert model.config.kinds == "MEMEM*EME" and model.config.remat
    assert sum(s.size for s in jax.tree_util.tree_leaves(shapes)) == 666_963_456
    text = compiled.as_text()
    for kernel in ("ds_ssd_scan_fwd", "ds_ssd_scan_bwd", "ds_causal_conv_fwd", "ds_flash_fwd", "ds_gmm", "ds_tgmm"):
        assert kernel in text, kernel
    assert_the_flash_forward_runs_once(text)
    # 3.08 GB as compiled here since the router's weights go to the rows before ``w_down`` and a
    # layer's second forward makes nothing of the second product again (PR 49; 3.37 at PR 46, 3.65
    # at PR 42, when the stand-in experts' rows took the whole range's form and a layer began to
    # keep the first grouped product's output, 0.73 GB over four layers). With the passes it was
    # 3.43 GB, their loops counted twice (PR 41; 1.39 GB under policy None, PR 40): the mixers'
    # first product's output is 0.68 GB of it, the shared expert's 0.49
    assert compiled.memory_analysis().temp_size_in_bytes < 3.08e9 * 1.05          # 3.01 GB since PR 53
    # six rows a token: no float32 copy of all the rows is laid out, broadcast or moved to another
    # layout between the tokens and the sorted rows (three passes a layer until PR 46), and none
    # padded to eight slots a token (the combine's cotangent ``dy x weights`` until PR 49)
    assert not re.search(r"= f32\[(8192,6,2688|49152,2688)\]\S* (broadcast|reshape|copy|transpose)\(", text)
    assert "f32[8192,8,2688]" not in text
    # ``w_down``'s product and its rows' cotangent once a layer, four layers, and NOT the product
    # made again in a layer's second forward (twelve such calls until PR 49)
    assert len(re.findall(r"= bf16\[49152,2688\]\S* custom-call\(", text)) == 8
    # the weights' gradient is a row sum inside the ONE pass that writes the first product's
    # cotangent (and the weighted activation made again), never a pass of its own over the rows
    assert len(re.findall(r"= \(f32\[49152\]\S*, bf16\[49152,1856\]\S*, bf16\[49152,1856\]\S*\) fusion\(", text)) == 4
    # the rows' products once a call over all 49,152 rows, never a pass of 8,192 under a loop
    assert "49152,1856" in text and not re.search(r"bf16\[8192,1856\]\S* custom-call", text)
    # the router's chosen scores and the sorted weights' cotangent travel by no index (PR 51). The
    # parent's text matched sixteen times: ``f32[8192,6] gather`` twelve (the chosen scores read in
    # both forwards of four layers, and ``d_ws[inverse]``) and ``f32[1048576] scatter`` four (the
    # chosen scores' cotangent into zeros ``[8192, 128]``, flat), 8 ns an element each
    assert not re.search(r"= f32\[(?:49152|8192,6|8192,128|1048576)\]\S* (?:gather|scatter)\(", text)
    assert_the_combine_reads_its_rows_in_runs(text, "49152,2688", "8192,2688", layers=4)


@pytest.mark.parametrize("k, G, H, dtype", [(6, 8, 2688, jnp.bfloat16), (8, 16, 2304, jnp.bfloat16), (4, 8, 2048, jnp.bfloat16),
                                            (8, 64, 2048, jnp.bfloat16), (4, 8, 2048, jnp.float32)],
                         ids=["nemotronh", "mellum2", "glm47flash-lfm2", "olmoe", "float32"])
def test_the_expert_cells_combine_kernel_compiles_for_v5e(chip, k, G, H, dtype):
    """``ops/pallas/rows_sum.py`` at the five expert cells' ``(k, G, H)`` and 8,192 tokens, with
    the bounds of its runs made beside it (a compare and a sum, no search's loop): one kernel
    call, its copies from rows left in HBM, the chunks' buffers and a tile's float32 sum in
    fast memory; float32 rows take six passes of the MXU each and more of that memory."""
    from deepspeed_tpu.ops.pallas import rows_sum
    n = 8192
    rows, index = ((jax.ShapeDtypeStruct(shape, dt, sharding=chip)) for shape, dt in (((n * k, H), dtype), ((n * k,), jnp.int32)))
    text = jax.jit(lambda ys, group, tok: rows_sum.rows_sum(
        ys, tok, rows_sum.visits(rows_sum.run_bounds(group, tok, n, G), n * k), n)).lower(rows, index, index).compile().as_text()
    assert len(re.findall(r"custom-call\(.*tpu_custom_call", text)) == 1 and "ds_moe_rows_sum" in text
    assert " while(" not in text and " gather(" not in text


def _grouped_products():
    """Every grouped product the expert cells make, once a shape, by the cells that make it and
    its kind: ``tests/perf/gmm_sweep.py: expert_calls`` reads them from the cells' files
    (arithmetic on JSON: nothing is described or compiled while this module is imported)."""
    import importlib.util
    from benchmarks.manifest import Manifest
    spec = importlib.util.spec_from_file_location("gmm_sweep", os.path.join(
        os.path.dirname(__file__), "..", "perf", "gmm_sweep.py"))
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    cells_of = collections.defaultdict(list)
    for key in sweep.CELLS:
        for call in sweep.expert_calls(Manifest(), key):
            cells_of[call[1:]].append(key)
    cases = collections.defaultdict(list)
    for shape, cells in cells_of.items():
        cases["+".join(cells), shape[0]].append(sweep.Call(cells[0], *shape))
    return [pytest.param(calls, id=f"{cells}-{kind}") for (cells, kind), calls in cases.items()]


@pytest.mark.parametrize("calls", _grouped_products())
def test_the_expert_cells_grouped_products_compile_for_v5e_at_the_tiles_picked(chip, calls):
    """``ops/pallas/grouped_matmul.py`` at every call shape of the six expert cells (two of a kind
    a cell), at the tiles ``parallel/moe._tiles`` picks and under the ``vmem_limit_bytes`` the
    kernel reckons from its blocks: a block set the chip's compiler refuses (fast memory, a slice
    off the tiling) fails here. Of a chain of pieces the call that writes into the buffer before it."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as grouped
    from deepspeed_tpu.parallel import moe
    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=chip)      # noqa: E731
    assert len(calls) == 2
    for call in calls:
        tiles = moe._tiles(call.kind, call.rows, call.groups, call.K, call.N)
        assert tiles[0] in moe.GMM_ROW_TILES and call.rows % tiles[0] == 0
        held, sizes = call.groups // call.pieces, shape(call.groups, dt=jnp.int32)
        first = jnp.int32(held) if call.pieces > 1 else None
        if call.kind == "tgmm":
            text = compiled_text(lambda lhs, grad, sizes: grouped.tgmm(lhs, grad, sizes, lhs.dtype, tiles, first, held),
                                 shape(call.rows, call.K), shape(call.rows, call.N), sizes)
        else:
            weights = shape(held, call.N, call.K) if call.kind == "gmm_t" else shape(held, call.K, call.N)
            out = (shape(call.rows, call.N),) if call.pieces > 1 else ()
            text = compiled_text(lambda lhs, rhs, sizes, *out: grouped.gmm(
                lhs, rhs, sizes, lhs.dtype, tiles, first, *(out or (None,)), transpose_rhs=call.kind == "gmm_t"),
                shape(call.rows, call.K), weights, sizes, *out)
        assert ("ds_tgmm" if call.kind == "tgmm" else "ds_gmm") in text, (call, tiles)
        assert len(re.findall(r"custom-call\(.*tpu_custom_call", text)) == 1, (call, tiles)


def assert_the_grouped_products_stay_in_hbm(text, products):
    """In a compiled program: ``products`` grouped products, none with an operand past its five
    scalars or a result that the compiler laid out in fast memory (``S(1)``)."""
    calls = [line for line in text.splitlines() if re.search(r"%ds_t?gmm\S* = ", line) and "custom-call(" in line]
    assert len(calls) == products, (len(calls), products)
    layouts = dict(re.findall(r"%(\S+) = (\w+\[[0-9,]*\]\S*) ", text))
    for line in calls:
        result, operands = re.search(r"= (\S+) custom-call\(([^)]*)\)", line).groups()
        big = [layouts[name] for name in re.findall(r"%([^\s,)]+)", operands)[5:]]
        assert "S(1)" not in result and not any("S(1)" in layout for layout in big), line[:300]


@pytest.mark.parametrize("runner, config, handed, products", [
    ("train_ssm_moe", "nemotron-twotower-30b-a3b-ep16-d9", ("moe", "shared"), 5),
    ("train_hybrid", "qwen3-next-80b-a3b-ep16-d4", ("moe", "shared"), 6),         # a held range: its pass makes the first product again
    ("train_swa_moe", "mellum2-12b-a2.5b-ep4-d4", ("moe",), 5), ("train_mla_moe", "glm-4.7-flash-ep8-d5", ("moe", "shared"), 5),
    ("train_conv_moe", "lfm2-24b-a2b-ep8-d7", None, 5)], ids=["nemotronh", "qwen3next", "mellum2", "glm47flash", "lfm2"])
def test_a_small_programs_grouped_products_stay_in_hbm_on_a_v5e(chip, monkeypatch, runner, config, handed, products):
    """A one-chip expert cell's SET-UP reads its expert layer ALONE against the reference, with
    float32 parameters (``compare_layers`` of its runner, through ``train_hybrid.Alone``; ``handed``
    the part of a layer's parameters it hands the layer): the gradients of ``sum(y * cot)`` on the
    last 1,024 positions (the first product, the two row cotangents, the two weight cotangents:
    nothing reads the second product's value) and the output on all 8,192. Programs small enough
    for the compiler to lay a kernel's operands and outputs out in fast memory (``S(1)``), an
    expert array's 80 MB among them. Beside the scoped region the grouped products ask for,
    Nemotron-H's gradients never ended on the chip (PERF.md, PR 55), so their operands and outputs
    are held to HBM (``grouped_matmul._in_hbm``); the combine's kernel, under 18 MB of scoped
    memory, keeps the compiler's choice."""
    import importlib
    from benchmarks.manifest import Manifest
    from benchmarks.runners.train_hybrid import Alone
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = Manifest().config(config)
    model = importlib.import_module("benchmarks.runners." + runner).build_model(config)
    layer = next(lp for lp in jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"] if "moe" in lp)
    params = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=chip),
                                    {name: layer[name] for name in handed} if handed else layer["moe"])
    x = lambda t, dt: jax.ShapeDtypeStruct((1, t, model.config.hidden_size), dt, sharding=chip)      # noqa: E731
    system = lambda p, x: model.expert_layer(x, p)[0]      # noqa: E731
    alone = Alone(system, system)
    rows = config["reference"]["grad_positions"]
    assert rows == 1024
    gradients = alone.grads[0].lower(params, x(rows, jnp.bfloat16), x(rows, jnp.float32)).compile().as_text()
    assert_the_grouped_products_stay_in_hbm(gradients, products)
    assert_the_grouped_products_stay_in_hbm(alone.fns[0].lower(params, x(8192, jnp.bfloat16)).compile().as_text(), 2)


def assert_the_combine_reads_its_rows_in_runs(text, rows, tokens, layers):
    """In an expert cell's whole gradient program: ``ds_moe_rows_sum`` twice an expert layer (the
    forward's combine and the dispatch's cotangent, neither made again by a recomputed layer), no
    gather that writes the sorted rows ``[n k, H]`` FROM the sorted rows (2.1-2.4 ms a call at a
    price a ROW, PERF.md, PR 53; the parent's text held two a layer), and the three gathers a layer
    that are left (the dispatch, its second forward, the combine's cotangent) read the ``n``
    tokens' rows from a source the compiler holds in fast memory (``S(1)``) beside the kernel."""
    calls = [line for line in text.splitlines() if "ds_moe_rows_sum" in line and "custom-call(" in line]
    assert len(calls) == 2 * layers and all("tpu_custom_call" in line for line in calls)
    layouts = dict(re.findall(r"%(\S+) = (\w+\[[0-9,]*\]\S*) parameter\(", text))
    sources = [layouts[operand] for operand in re.findall(r"= bf16\[%s\]\S* gather\(%%(\S+?)," % rows, text)]
    assert len(sources) == 3 * layers and all(source.startswith(f"bf16[{tokens}]") and "S(1)" in source
                                              for source in sources), sources


def test_the_sliding_window_expert_cells_gradient_program_compiles_for_v5e(chip, monkeypatch):
    """The gradient program of ``mellum2_ep4_d4_train_1chip`` WHOLE (the published widths, three
    sliding-window layers and a full one, 16 of 64 experts held and standing in, 1 x 8,192
    positions, whole layers recomputed but for ``mellum.KEPT_BY_A_LAYER``): the banded flash kernel
    and the grouped products are in it, the combine reads its 65,536 sorted rows in runs, and
    what it needs beside its parameters and their gradients is 1.81 GB as compiled here (2.19
    until the combine's ``[k n, H]`` intermediate went, PR 53; 3.13 before PR 49)."""
    from benchmarks.runners.train_swa_moe import build_model
    compiled, _, _ = expert_cells_gradient_program(chip, monkeypatch, build_model, "mellum2-12b-a2.5b-ep4-d4")
    text = compiled.as_text()
    for kernel in ("ds_flash_fwd", "ds_flash_bwd_dkv", "ds_gmm", "ds_tgmm"):
        assert kernel in text, kernel
    assert compiled.memory_analysis().temp_size_in_bytes < 1.81e9 * 1.05
    assert_the_combine_reads_its_rows_in_runs(text, "65536,2304", "8192,2304", layers=4)


def looped_gradient_program(chip, monkeypatch, layers, passes):
    """Ouro's gradient program at its published widths and 2 x 4,096 positions, whole blocks
    recomputed, compiled for the described chip; and the shapes of its leaves."""
    from deepspeed_tpu.models.ouro import OuroConfig, OuroModel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # the flash kernel, not its interpreter
    model = OuroModel(OuroConfig(num_hidden_layers=layers, total_ut_steps=passes, remat=True))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=chip), shapes)
    tokens = jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=chip)
    return jax.jit(jax.value_and_grad(lambda *a: model.apply(*a)[0])).lower(params, tokens, tokens).compile(), shapes


def test_a_looped_models_recomputed_passes_and_its_exits_compile_for_v5e(chip, monkeypatch):
    """The gradient program of ``ouro_d6_train_1chip`` at its widths and 2 x 4,096 positions, cut
    to ONE layer run TWICE on its leaves (whole blocks recomputed; the two exits' cross-entropy a
    position over the whole vocabulary of 49,152 as one call; the exit gate): the flash kernel is
    in it at OLMoE's shape, a layer's leaves are arguments once, and what it needs beside its
    parameters and their gradients is the exits' kept ``softmax - onehot`` (2 x 8192 x 49,152
    bf16 = 1.61 GB), the table's float32 gradient and a block's internals (4.22 GB here; the cell's
    six layers and four passes compile to 5.11 GB of temporaries; 8.15 GB of state leave 8.7)."""
    compiled, shapes = looped_gradient_program(chip, monkeypatch, layers=1, passes=2)
    assert sum(s.size for s in jax.tree_util.tree_leaves(shapes)) == 51_388_416 + 201_326_592 + 2048 + 2049
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4.5e9


def test_a_looped_models_block_passes_keep_each_named_tensor_once_on_a_v5e(chip, monkeypatch):
    """The gradient program of ``ouro_d6_train_1chip`` WHOLE (its widths, six layers, four passes,
    2 x 4,096 positions): a block pass keeps the flash kernel's output and ``w_down``'s beside its
    input, so the program holds six flash forward calls (twelve while the second forward ran the
    kernel again), the forward loop carries six stacked kernel outputs and not twelve, and a kept
    tensor costs what it weighs: 24 block passes x (2 x 33.5 MB + 0.5 MB) = 1.62 GB over the
    5.68 GB that policy None compiles to, in the compiler's own buffer assignment. What
    ``memory_analysis()`` calls ``temp`` counts every array a loop stacks a second time (7.05 GB
    under policy None, 10.35 here, PERF.md, PR 38): the pin is on that reading plus 5 %."""
    compiled, _ = looped_gradient_program(chip, monkeypatch, layers=6, passes=4)
    lines = compiled.as_text().splitlines()
    forward_calls = [line for line in lines if "tpu_custom_call" in line and "ds_flash_fwd" in line]
    assert len(forward_calls) == 6 and not any("rematted_computation" in line for line in forward_calls)
    forward_loop = next(line for line in lines if re.search(r"= \(.*\) while\(", line)
                        and "jvp()/while" in line and "transpose" not in line)
    # the kernel's output is [2, 4096, 16 * 128] as ``wo`` reads it since PR 60 (head-major before:
    # six of ``bf16[4,2,16,4096,128]``), its row sums stay [2, 16, 4096]
    assert "bf16[4,2,16,4096,128]" not in forward_loop and forward_loop.count("f32[4,2,16,4096]") == 6
    # a block's input, w_down's output and the kernel's; the exits
    assert forward_loop.count("bf16[4,2,4096,2048]") == 6 * 3 + 1
    assert compiled.memory_analysis().temp_size_in_bytes < 10.354e9 * 1.05


def test_the_held_range_expert_layer_compiles_for_v5e(chip, monkeypatch):
    """Qwen3-Next's expert layer at its published widths as one chip of sixteen holds it: a
    router over 512, 32 experts of 512 held, 8,192 tokens; the grouped products' kernels inside
    the passes' ``cond``, forward and the hand-written backward."""
    from deepspeed_tpu.parallel.moe import DroplessMoE
    layer = DroplessMoE(2048, 512, 512, 10, norm_topk_prob=True, held=(0, 32))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the grouped matmul's kernel
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0))
    params = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16, sharding=chip) for k, v in shapes.items()}
    assert params["w_gate_up"].shape == (32, 2048, 1024) and params["router_w"].shape == (2048, 512)
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16, sharding=chip)

    def loss(params, x):
        y, aux, _ = layer.apply(params, x)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # no buffer of n * k rows: the temporaries stay under the weights' gradients in float32
    # (0.4 GB) and a few passes of 8,192 rows
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_the_stand_in_experts_products_compile_for_v5e_at_widths_the_tiles_divide(chip, monkeypatch):
    """Mellum 2's expert layer between its gathers, at its published widths as one chip of four
    holds it: 16 gated experts of 896 standing in for 64, 8,192 tokens of 8 experts each, value
    and gradient. Six grouped products (``ops/pallas/grouped_matmul.py``) over 65,536 rows at
    2,304, 1,792 and 896, none of which 1,024 divides, at the tiles ``parallel/moe._tiles`` picks
    (every contraction and every width whole since PR 55; 768 and 896 wide under megablox). The layer's
    router and its two sorts of 65,536 keys are Nemotron-H's, in the whole program above (a
    sort alone compiles in 8 s and more)."""
    from deepspeed_tpu.parallel import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the grouped matmul's kernel
    for kind, widths in (("gmm", (2304, 1792)), ("gmm", (896, 2304)), ("gmm_t", (1792, 2304)), ("gmm_t", (2304, 896)),
                         ("tgmm", (2304, 1792)), ("tgmm", (896, 2304))):
        tiles = moe._tiles(kind, 65536, 16, *widths)         # 4,096 rows a group: no row tile of 512 since PR 57
        assert tiles[1:] == widths and tiles[0] in (128, 256), (kind, tiles)

    def loss(xs, w_gate_up, w_down, sizes):
        gate_up = moe.experts_matmul(xs, (w_gate_up,), (None,), sizes)
        ys = moe.experts_matmul(moe._activate(moe.SILU_GATED, gate_up, xs.dtype), (w_down,), (None,), sizes)
        return jnp.sum(ys.astype(jnp.float32) ** 2)

    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=chip)      # noqa: E731
    text = compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)), shape(65536, 2304),
                         shape(16, 2304, 1792), shape(16, 896, 2304), shape(16, dt=jnp.int32))
    # four products of the rows and two of the weights, each one kernel over all 65,536 rows
    assert len(re.findall(r"= bf16\[65536,(?:1792|2304|896)\]\S* custom-call\(.*tpu_custom_call", text)) == 4
    assert len(re.findall(r"= bf16\[16,(?:2304,1792|896,2304)\]\S* custom-call\(.*tpu_custom_call", text)) == 2


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
def test_block_sparse_attention_compiles_for_v5e(chip, backward):
    heads, seq, block = 16, 8192, 128
    layout = np.asarray(BSLongformerSparsityConfig(
        num_heads=heads, block=block, num_sliding_window_blocks=9,
        global_block_indices=[0]).make_layout(seq))

    def attn(q, k, v):
        return block_sparse_attention(q, k, v, layout, block, causal=True, interpret=False)

    x = jax.ShapeDtypeStruct((1, heads, seq, 64), jnp.bfloat16, sharding=chip)
    text = compiled_text(sumsq_grad(attn) if backward else attn, x, x, x)
    assert "tpu_custom_call" in text


def test_paged_decode_attention_compiles_for_v5e(chip):
    slots, heads, head_dim, block_size, max_blocks = 8, 16, 64, 16, 64
    pool = jax.ShapeDtypeStruct((24, 256, block_size, heads, head_dim), jnp.bfloat16,
                                sharding=chip)
    q = jax.ShapeDtypeStruct((slots, heads, 1, head_dim), jnp.bfloat16, sharding=chip)
    tables = jax.ShapeDtypeStruct((slots, max_blocks), jnp.int32, sharding=chip)
    lengths = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)

    def decode(q, k_pool, v_pool, tables, lengths):
        return paged_decode_attention(q, k_pool, v_pool, 3, tables, lengths,
                                      block_size=block_size, interpret=False)

    assert "tpu_custom_call" in compiled_text(decode, q, pool, pool, tables, lengths)


def test_flash_attention_compiles_under_a_four_chip_mesh(topo):
    """The data-parallel path: XLA refuses to partition a compiled Pallas kernel, so
    under a mesh the kernel splits itself (ops/pallas/partition.py). Compiled for all
    four chips of the described host with the batch sharded over ``data``."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4, 1), ("pipe", "data", "model"))
    x = jax.ShapeDtypeStruct((4, 25, 1024, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))

    def attn(q, k, v):
        return flash_attention(q, k, v, True, interpret=False)

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        text = compiled_text(sumsq_grad(attn), x, x, x)
    assert "tpu_custom_call" in text
    with pytest.raises(NotImplementedError, match="cannot be automatically partitioned"):
        compiled_text(sumsq_grad(attn), x, x, x)   # no mesh in context: XLA is asked


def expert_layer_on_four_chips(topo, monkeypatch):
    """OLMoE's expert layer at its published widths with its arguments as shapes on the
    described host: 2 x 4096 tokens a chip, the experts split over ``data``."""
    from deepspeed_tpu.parallel.moe import DroplessMoE
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4, 1), ("pipe", "data", "model"))
    layer = DroplessMoE(2048, 1024, 64, 8)
    # the grouped matmul picks its kernel by the backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0))
    params = {k: jax.ShapeDtypeStruct(shapes[k].shape, jnp.bfloat16,
                                      sharding=NamedSharding(mesh, spec))
              for k, spec in layer.expert_specs("data").items()}
    x = jax.ShapeDtypeStruct((8, 4096, 2048), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    return mesh, layer, params, x


def test_the_expert_layer_compiles_under_a_four_chip_mesh(topo, monkeypatch):
    """The grouped products inside the layer's own ``shard_map``, the experts'
    weights fetched chip to chip over ``data`` and their gradients sent back to the owners."""
    mesh, layer, params, x = expert_layer_on_four_chips(topo, monkeypatch)

    def loss(params, x):
        y, aux, _ = layer.apply(params, x)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        text = compiled_text(jax.grad(loss), params, x)
    assert "tpu_custom_call" in text and "collective-permute-start" in text


def test_the_four_chip_cells_small_programs_grouped_products_stay_in_hbm_on_a_v5e(topo, monkeypatch):
    """``olmoe_d4_train_4chip``'s SET-UP reads every expert layer ALONE under the mesh, a copy of the
    sequence a chip, with the engine's float32 parameters (``train_moe.system_layer_fn``: the same
    two programs): the output on 4,096 positions and the gradients of ``sum(y * cot)`` on the last
    ``GRAD_ROWS``, the experts crossing the chips in four pieces chained through one buffer. No
    grouped product's operand or result, the aliased buffer among them, lies in fast memory."""
    from benchmarks.manifest import Manifest
    from benchmarks.runners import train_moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    manifest = Manifest()
    cell = manifest.cell("olmoe_d4_train_4chip")
    model = train_moe.build_model(manifest.config(cell["config"]))
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4, 1), ("pipe", "data", "model"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"][0]["moe"]
    params = {name: jax.ShapeDtypeStruct(shapes[name].shape, jnp.float32, sharding=sharding)
              for name, sharding in model.engine_shardings(mesh)["layers"][0]["moe"].items()}
    x = lambda t, dt: jax.ShapeDtypeStruct((4, t, model.config.hidden_size), dt,      # noqa: E731
                                           sharding=NamedSharding(mesh, P("data")))

    def fwd(mp, x):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return model.moe.apply(mp, x, details=True)[::2]

    def grads(mp, x, cot):
        scalar = lambda x, *w: jnp.sum(fwd(dict(zip(train_moe.WEIGHTS, w)), x)[0].astype(jnp.float32) * cot)     # noqa: E731
        return jax.grad(scalar, argnums=(0, 1, 2, 3))(x, *(mp[n] for n in train_moe.WEIGHTS))

    seq_len = manifest.traffic(cell["traffic"])["seq_len"]
    assert_the_grouped_products_stay_in_hbm(compiled_text(fwd, params, x(seq_len, jnp.bfloat16)), 2 * 4)
    rows = train_moe.GRAD_ROWS
    assert_the_grouped_products_stay_in_hbm(compiled_text(grads, params, x(rows, jnp.bfloat16), x(rows, jnp.float32)), 5 * 4)


SHAPE_RE = re.compile(r"(pred|[a-z]+\d+)\[([\d,]*)\]")
INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)$")


def scheduled_entry(text):
    """The entry computation of a compiled (scheduled) module, in the order it runs:
    ``(name, opcode, the largest array among result and operands in bytes, the rest of the
    line)`` an instruction."""
    assert "is_scheduled=true" in text
    body = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M).group(1)
    out = []
    for line in body.splitlines():
        m = INSTRUCTION_RE.match(line)
        if m is None:
            continue
        name, result, opcode, rest = m.groups()
        sizes = [int(np.prod([int(d) for d in dims.split(",") if d], dtype=np.int64))
                 * (1 if dtype == "pred" else int(re.sub(r"\D", "", dtype)) // 8)
                 for dtype, dims in SHAPE_RE.findall(result + " " + rest.split("), ")[0])]
        out.append((name, opcode, max(sizes, default=0), rest))
    return out


def test_the_experts_exchange_is_scheduled_under_the_kernels(topo, monkeypatch):
    """The gradient of two stacked expert layers, as the chip's compiler orders it: the
    experts' weights and gradients move as asynchronous chip-to-chip transfers with the
    grouped-matmul kernels between a transfer's start and its end, forward and backward,
    and no large collective is left that the chip only waits for. (An ``all_gather`` of the
    same weights compiles to eight large synchronous collectives here: PERF.md, PR 27.)"""
    mesh, layer, one, x = expert_layer_on_four_chips(topo, monkeypatch)
    between = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16, sharding=NamedSharding(mesh, P()))

    def loss(params, between, x):
        y, aux_a, _ = layer.apply(params["a"], x)
        y, aux_b, _ = layer.apply(params["b"], jnp.dot(y, between))
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux_a + aux_b

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        entry = scheduled_entry(compiled_text(jax.grad(loss), {"a": one, "b": one}, between, x))
    synchronous = [(name, size) for name, opcode, size, _ in entry
                   if opcode in ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
                                 "collective-permute") and size > 2 ** 20]
    assert not synchronous, synchronous
    started, covered = {}, {"forward": 0, "backward": 0}
    kernels = 0
    for name, opcode, size, rest in entry:
        if opcode == "collective-permute-start":
            started[name] = kernels
        elif opcode == "collective-permute-done":
            start = re.match(r"[^%]*%?([\w.\-]+)", rest).group(1)
            assert start in started, f"{name} ends {start}, which has not started"
            if kernels > started.pop(start) and size > 2 ** 20:
                covered["backward" if "transpose(" in rest else "forward"] += 1
        elif opcode == "custom-call" and "tpu_custom_call" in rest:
            kernels += 1
    assert not started, f"never ended: {sorted(started)}"
    assert covered["forward"] and covered["backward"], covered


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "fetched-again"])
def test_the_four_chip_expert_cells_layers_fetch_no_kept_piece_twice_on_a_v5e(topo, monkeypatch, kept):
    """``olmoe_d4_train_4chip``'s expert layer twice in a row, its gradient compiled for the four
    described chips with the room to keep what it fetched (``moe.fetches_kept``), and without. A
    layer moves its experts as six transfers forward and six gradients home; kept, no weight is
    the operand of a second fetch over the same pairs of chips, and the chip's compiler leaves
    24 transfers where the not-kept program has 33 (PERF.md, PR 54)."""
    from deepspeed_tpu.parallel import moe
    mesh, layer, one, x = expert_layer_on_four_chips(topo, monkeypatch)
    between = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16, sharding=NamedSharding(mesh, P()))

    def loss(params, between, x):
        keep = layer.fetches_kept(2, x)
        y, aux_a, _ = layer.apply(params["a"], x, keep=keep)
        y, aux_b, _ = layer.apply(params["b"], jnp.dot(y, between), keep=keep)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux_a + aux_b

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh), moe.room_for_fetched_experts(lambda: 2 ** 40 * kept):
        entry = scheduled_entry(compiled_text(jax.grad(loss), {"a": one, "b": one}, between, x))
    starts = [rest for _, opcode, _, rest in entry if opcode == "collective-permute-start"]
    fetched = collections.Counter(
        (re.match(r"%?(param[\w.]*)\)", rest).group(1), re.search(r"source_target_pairs=\{[^ ]*\}", rest).group(0))
        for rest in starts if re.match(r"%?param", rest))
    # four weight arrays, each to the three other chips: once where kept; twice where not, but for the
    # first layer's ``w_gate_up``, which no backward reads here (nothing asks for its input's gradient)
    assert len(fetched) == 12 and sorted(fetched.values()) == ([1] * 12 if kept else [1] * 3 + [2] * 9), fetched
    assert len(starts) == (24 if kept else 33)


# ------------------------------------------------------------- the hyper-connection's kernels (PR 59)
HC_KERNELS = ("ds_hc_read", "ds_hc_write", "ds_hc_write_bwd", "ds_hc_read_bwd")


@pytest.mark.parametrize("tokens", [4096, 1024], ids=["a_step", "the_set_up"])
@pytest.mark.parametrize("kernel", HC_KERNELS)
def test_the_hyper_connections_kernels_compile_for_v5e(chip, kernel, tokens):
    """``ops/pallas/hyper_connection.py`` at ``xing4_ep8_d5_train_1chip``'s shapes: four bf16 streams
    of 3,584 (``[4096, 14336]`` a step, 1,024 tokens in the set-up's comparisons), 24 columns, 20
    rounds, at the tile the rule picks."""
    from deepspeed_tpu.ops.pallas import hyper_connection as hc
    n, C = 4, 3584
    tm = hc.tile(tokens, n, C, 2)
    assert tm == 256
    kw = dict(n=n, iters=20, eps=1e-6, clamp=(-30.0, 30.0), tm=tm)
    a = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)      # noqa: E731
    x, one, co = a((tokens, n * C)), a((tokens, C)), a((tokens, 128), jnp.float32)
    g, cols = a((8, n * C), jnp.float32), a((2, 128, 128), jnp.float32)
    fn, args = {
        "ds_hc_read": (lambda *o: hc.read(*o, norm_eps=1e-6, **kw), (x, g, a((n * C, 128)), cols)),
        "ds_hc_write": (lambda *o: hc.write(*o, n=n, tm=tm), (x, one, co)),
        "ds_hc_write_bwd": (lambda *o: hc.write_bwd(*o, n=n, tm=tm), (x, x, one, co)),
        "ds_hc_read_bwd": (lambda *o: hc.read_bwd(*o, **kw), (x, one, x, co, co, g, a((128, n * C)), cols)),
    }[kernel]
    text = compiled_text(fn, *args)
    assert "tpu_custom_call" in text and re.search(rf"%{kernel}(\.\d+)? = ", text)
    # the streams stay in HBM, the small program's too (PERF.md, PR 55): no operand of 4 x 3,584 columns in S(1)
    assert not re.search(r"bf16\[\d+,14336\]\{[^}]*S\(1\)", text)


def test_a_hyper_connected_block_pairs_gradient_program_runs_the_kernels_on_a_v5e(chip, monkeypatch):
    """The gradient program of ``xing4_ep8_d5_train_1chip`` at its widths and 1 x 4,096 positions,
    cut to one dense and one expert block, whole blocks recomputed: the mechanism's engagement
    counter is the program's ``ds_hc_*`` custom calls by name. Four sub-layers: a ``ds_hc_read``
    each in the forward and in the second forward, a ``ds_hc_write`` each in the forward and in
    the second forward of a block's FIRST sub-layer alone (its last one's ``X'`` is read by nothing
    there: dead, as the ``jnp`` form's was), one of each backward kernel; every one under ``ds_hc``
    and one of its two parts; and no float32 copy of the four streams under ``ds_hc``."""
    import collections
    from benchmarks.manifest import Manifest
    from benchmarks.runners.train_hc_moe import build_model
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # the kernels, not the jnp form
    config = Manifest().config("xing4.0-29b-a4b-ep8-d5")
    assert config["remat"] and config["model"]["first_k_dense_replace"] == 1
    model = build_model(dict(config, model=dict(config["model"], num_hidden_layers=2)))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map_with_path(lambda path, s: jax.ShapeDtypeStruct(
        s.shape, jnp.float32 if "router_bias" in jax.tree_util.keystr(path) else jnp.bfloat16, sharding=chip), shapes)
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=chip)
    text = jax.jit(jax.value_and_grad(lambda *a: model.apply(*a)[0])).lower(params, tokens, tokens).compile().as_text()
    calls = [line for line in text.splitlines() if re.search(r"%ds_hc_[\w.]+ = .*custom-call\(", line)]
    by_name = collections.Counter(re.search(r"%(ds_hc_\w+?)(\.\d+)? = ", line)[1] for line in calls)
    assert by_name == {"ds_hc_read": 8, "ds_hc_write": 6, "ds_hc_write_bwd": 4, "ds_hc_read_bwd": 4}
    part = {"ds_hc_read": "ds_hc_coef", "ds_hc_read_bwd": "ds_hc_coef", "ds_hc_write": "ds_hc_mix", "ds_hc_write_bwd": "ds_hc_mix"}
    for line in calls:
        name, path = re.search(r"%(ds_hc_\w+?)(\.\d+)? = ", line)[1], re.search(r'op_name="([^"]*)"', line)[1]
        assert re.search(rf"ds_(attn|mlp)\)?/ds_hc/{part[name]}/", path), path
        assert ("rematted_computation" in path) == (path.count("/checkpoint/") == 1 and "_bwd" not in name), path
    second_forward = collections.Counter(re.search(r"%(ds_hc_\w+?)(\.\d+)? = ", line)[1]
                                         for line in calls if "rematted_computation" in line)
    assert second_forward == {"ds_hc_read": 4, "ds_hc_write": 2}
    wide = [line for line in text.splitlines() if re.search(r"= f32\[(1,)?4096,14336\]", line) and "ds_hc" in line]
    assert not wide, wide[:3]
