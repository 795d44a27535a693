"""``hlo.instruction_costs``: what every compiled operation has to do, read from optimized
text alone. Text and arithmetic: no model, no engine, nothing compiled. The fixtures marked
"v5e" are cut from the optimized gradient program of ``mellum2_ep4_d4_train_1chip`` as the
chip's compiler wrote it (metadata and backend configuration taken out); the others are in
the form this JAX's CPU text has (operands by name, no type beside them)."""

import pytest

from deepspeed_tpu.utils import hlo

BF16, F32, S32 = 2, 4, 4


def module(*computations, entry):
    return "HloModule jit_step, is_scheduled=true\n\n" + "\n\n".join(computations) + \
        "\n\nENTRY %main.1 (p: f32[]) -> f32[] {\n" + entry.strip("\n") + "\n}\n"


DOT_FUSION = module("""
%fused_computation (param_0.1: bf16[64,128], param_1.1: bf16[128,32]) -> bf16[64,32] {
  %param_0.1 = bf16[64,128]{1,0} parameter(0)
  %param_1.1 = bf16[128,32]{1,0} parameter(1)
  %dot.1 = f32[64,32]{1,0} dot(%param_0.1, %param_1.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %convert.1 = bf16[64,32]{1,0} convert(%dot.1)
}""", entry="""
  %x = bf16[64,128]{1,0} parameter(0)
  %w = bf16[128,32]{1,0} parameter(1)
  ROOT %dot_fusion = bf16[64,32]{1,0} fusion(%x, %w), kind=kOutput, calls=%fused_computation
""")

# v5e: a matmul spelled ``convolution``, its operands behind nested bitcast fusions, the
# weight prefetched into on-chip memory (``S(1)``) by a copy-start / copy-done pair
CONVOLUTION_MATMUL = module("""
%bitcast_fusion.15 (bitcast_input.15: bf16[8192,4096]) -> bf16[8192,4096] {
  %bitcast_input.15 = bf16[8192,4096]{0,1:T(8,128)(2,1)} parameter(0)
  ROOT %bitcast.15 = bf16[8192,4096]{0,1:T(8,128)(2,1)} bitcast(%bitcast_input.15)
}""", """
%bitcast_fusion.61 (bitcast_input.61: bf16[2304,4096]) -> bf16[2304,4096] {
  %bitcast_input.61 = bf16[2304,4096]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  ROOT %bitcast.61 = bf16[2304,4096]{1,0:T(8,128)(2,1)} bitcast(%bitcast_input.61)
}""", """
%fused_computation.855 (param_0.2902: bf16[8192,4096], param_1.3480: bf16[2304,4096]) -> bf16[8192,2304] {
  %param_0.2902 = bf16[8192,4096]{0,1:T(8,128)(2,1)} parameter(0)
  %fusion.1193 = bf16[8192,4096]{0,1:T(8,128)(2,1)} fusion(%param_0.2902), kind=kLoop, calls=%bitcast_fusion.15
  %param_1.3480 = bf16[2304,4096]{1,0:T(8,128)(2,1)S(1)} parameter(1)
  %fusion.1239 = bf16[2304,4096]{1,0:T(8,128)(2,1)} fusion(%param_1.3480), kind=kLoop, calls=%bitcast_fusion.61
  %convolution.104 = f32[8192,2304]{1,0:T(8,128)} convolution(%fusion.1193, %fusion.1239), dim_labels=bf_oi->bf
  ROOT %convert_element_type.2035 = bf16[8192,2304]{1,0:T(8,128)(2,1)} convert(%convolution.104)
}""", entry="""
  %bitcast.1382 = bf16[8192,4096]{0,1:T(8,128)(2,1)} parameter(0)
  %w_out = bf16[2304,4096]{1,0:T(8,128)(2,1)} parameter(1)
  %copy-start.246 = (bf16[2304,4096]{1,0:T(8,128)(2,1)S(1)}, bf16[2304,4096]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%w_out)
  %copy-done.246 = bf16[2304,4096]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.246)
  ROOT %convolution_convert_fusion.24 = bf16[8192,2304]{1,0:T(8,128)(2,1)} fusion(%bitcast.1382, %copy-done.246), kind=kOutput, calls=%fused_computation.855
""")

# v5e: a batched matmul as a convolution over a padded window (one tap a position reads
# the input), with the next norm's sum of squares riding along as the tuple's FIRST element
BATCHED_AS_WINDOW = module("""
%region_162.246 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[]{:T(128)} parameter(0)
  %b = f32[]{:T(128)} parameter(1)
  ROOT %add.9 = f32[]{:T(128)} add(%a, %b)
}""", """
%fused_computation.602 (param_0.8391: bf16[32,128,2304], param_1.9814: bf16[8192,2304,1]) -> (f32[8192,32], bf16[8192,32,128]) {
  %param_1.9814 = bf16[8192,2304,1]{1,0,2:T(8,128)(2,1)} parameter(1)
  %param_0.8391 = bf16[32,128,2304]{2,1,0:T(8,128)(2,1)} parameter(0)
  %convolution.81 = f32[8192,32,128]{2,0,1:T(8,128)} convolution(%param_1.9814, %param_0.8391), window={size=32 pad=31_31 rhs_reversal=1}, dim_labels=bf0_0oi->b0f
  %convert_element_type.1760 = bf16[8192,32,128]{2,0,1:T(8,128)(2,1)} convert(%convolution.81)
  %convert.367 = f32[8192,32,128]{2,0,1:T(8,128)} convert(%convert_element_type.1760)
  %mul.836 = f32[8192,32,128]{2,0,1:T(8,128)} multiply(%convert.367, %convert.367)
  %constant.8318 = f32[]{:T(128)} constant(0)
  %reduce.226 = f32[8192,32]{0,1:T(8,128)S(1)} reduce(%mul.836, %constant.8318), dimensions={2}, to_apply=%region_162.246
  ROOT %tuple.494 = (f32[8192,32]{0,1:T(8,128)S(1)}, bf16[8192,32,128]{2,0,1:T(8,128)(2,1)}) tuple(%reduce.226, %convert_element_type.1760)
}""", entry="""
  %wk = bf16[32,128,2304]{2,1,0:T(8,128)(2,1)} parameter(0)
  %xs = bf16[8192,2304,1]{1,0,2:T(8,128)(2,1)} parameter(1)
  ROOT %fusion.476 = (f32[8192,32]{0,1:T(8,128)S(1)}, bf16[8192,32,128]{2,0,1:T(8,128)(2,1)}) fusion(%wk, %xs), kind=kOutput, calls=%fused_computation.602
""")

# v5e: the head's weight gradient, a convolution of its own in the entry computation
HEAD_GRADIENT = module(entry="""
  %fusion.440 = bf16[8,1024,24576]{2,1,0:T(8,128)(2,1)} parameter(0)
  %multiply_convert_fusion.5 = bf16[8,1024,2304]{2,1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.75 = f32[24576,2304,1]{1,0,2:T(8,128)} convolution(%fusion.440, %multiply_convert_fusion.5), window={size=8}, dim_labels=0fb_0io->bf0
""")

TWO_PRODUCTS = module("""
%fused_computation.7 (param_0.7: bf16[256,512], param_1.7: bf16[512,1024], param_2.7: bf16[512,128]) -> (bf16[256,128], bf16[256,1024]) {
  %param_0.7 = bf16[256,512]{1,0} parameter(0)
  %param_1.7 = bf16[512,1024]{1,0} parameter(1)
  %param_2.7 = bf16[512,128]{1,0} parameter(2)
  %dot.71 = bf16[256,1024]{1,0} dot(%param_0.7, %param_1.7), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %dot.72 = bf16[256,128]{1,0} dot(%param_0.7, %param_2.7), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %tuple.7 = (bf16[256,128]{1,0}, bf16[256,1024]{1,0}) tuple(%dot.72, %dot.71)
}""", entry="""
  %x = bf16[256,512]{1,0} parameter(0)
  %up = bf16[512,1024]{1,0} parameter(1)
  %gate = bf16[512,128]{1,0} parameter(2)
  ROOT %fusion.7 = (bf16[256,128]{1,0}, bf16[256,1024]{1,0}) fusion(%x, %up, %gate), kind=kOutput, calls=%fused_computation.7
""")

ELEMENTWISE = module("""
%fused_computation.3 (param_0.3: f32[1024,256], param_1.3: bf16[1024,256]) -> bf16[1024,256] {
  %param_0.3 = f32[1024,256]{1,0} parameter(0)
  %param_1.3 = bf16[1024,256]{1,0} parameter(1)
  %convert.31 = f32[1024,256]{1,0} convert(%param_1.3)
  %multiply.3 = f32[1024,256]{1,0} multiply(%param_0.3, %convert.31)
  %tanh.3 = f32[1024,256]{1,0} tanh(%multiply.3)
  ROOT %convert.32 = bf16[1024,256]{1,0} convert(%tanh.3)
}""", entry="""
  %a = f32[1024,256]{1,0} parameter(0)
  %b = bf16[1024,256]{1,0} parameter(1)
  ROOT %fusion.3 = bf16[1024,256]{1,0} fusion(%a, %b), kind=kLoop, calls=%fused_computation.3
""")

# a scanned layer reads ITS matrices out of the stacked weights, and writes its row of what
# the backward keeps into the stacked buffer the loop carries: the text of a ``lax.scan``
SCANNED_LAYER = module("""
%fused_computation.4 (param_0.15: f32[24,512,512], param_1.13: s32[]) -> bf16[512,512] {
  %param_0.15 = f32[24,512,512]{2,1,0} parameter(0)
  %param_1.13 = s32[] parameter(1)
  %constant.53 = s32[] constant(0)
  %dynamic_slice.28 = f32[1,512,512]{2,1,0} dynamic-slice(%param_0.15, %param_1.13, %constant.53, %constant.53), dynamic_slice_sizes={1,512,512}
  %convert.327 = bf16[1,512,512]{2,1,0} convert(%dynamic_slice.28)
  ROOT %bitcast.12 = bf16[512,512]{1,0} bitcast(%convert.327)
}""", """
%fused_computation.2 (param_0.8: s32[], param_1.12: bf16[24,64,512], param_2.14: f32[64,512]) -> bf16[24,64,512] {
  %param_1.12 = bf16[24,64,512]{2,1,0} parameter(1)
  %param_2.14 = f32[64,512]{1,0} parameter(2)
  %convert.323 = bf16[64,512]{1,0} convert(%param_2.14)
  %bitcast.11 = bf16[1,64,512]{2,1,0} bitcast(%convert.323)
  %param_0.8 = s32[] parameter(0)
  %constant.52 = s32[] constant(0)
  ROOT %dynamic_update_slice.20 = bf16[24,64,512]{2,1,0} dynamic-update-slice(%param_1.12, %bitcast.11, %param_0.8, %constant.52, %constant.52)
}""", """
%wrapped_tanh_computation (param_0.20: bf16[64,512]) -> bf16[64,512] {
  %param_0.20 = bf16[64,512]{1,0} parameter(0)
  ROOT %tanh.20 = bf16[64,512]{1,0} tanh(%param_0.20)
}""", """
%region_body.5 (arg_tuple.5: (s32[], f32[24,512,512], bf16[24,64,512], f32[64,512])) -> (s32[], f32[24,512,512], bf16[24,64,512], f32[64,512]) {
  %arg_tuple.5 = (s32[], f32[24,512,512]{2,1,0}, bf16[24,64,512]{2,1,0}, f32[64,512]{1,0}) parameter(0)
  %get-tuple-element.50 = s32[] get-tuple-element(%arg_tuple.5), index=0
  %get-tuple-element.51 = f32[24,512,512]{2,1,0} get-tuple-element(%arg_tuple.5), index=1
  %get-tuple-element.52 = bf16[24,64,512]{2,1,0} get-tuple-element(%arg_tuple.5), index=2
  %get-tuple-element.53 = f32[64,512]{1,0} get-tuple-element(%arg_tuple.5), index=3
  %layer_weights = bf16[512,512]{1,0} fusion(%get-tuple-element.51, %get-tuple-element.50), kind=kLoop, calls=%fused_computation.4
  %kept_row = bf16[24,64,512]{2,1,0} fusion(%get-tuple-element.50, %get-tuple-element.52, %get-tuple-element.53), kind=kLoop, calls=%fused_computation.2
  ROOT %tuple.50 = (s32[], f32[24,512,512]{2,1,0}, bf16[24,64,512]{2,1,0}, f32[64,512]{1,0}) tuple(%get-tuple-element.50, %get-tuple-element.51, %kept_row, %get-tuple-element.53)
}""", """
%region_cond.6 (arg_tuple.6: (s32[], f32[24,512,512], bf16[24,64,512], f32[64,512])) -> pred[] {
  %arg_tuple.6 = (s32[], f32[24,512,512]{2,1,0}, bf16[24,64,512]{2,1,0}, f32[64,512]{1,0}) parameter(0)
  %get-tuple-element.60 = s32[] get-tuple-element(%arg_tuple.6), index=0
  %constant.60 = s32[] constant(24)
  ROOT %compare.60 = pred[] compare(%get-tuple-element.60, %constant.60), direction=LT
}""", entry="""
  %init = (s32[], f32[24,512,512]{2,1,0}, bf16[24,64,512]{2,1,0}, f32[64,512]{1,0}) parameter(0)
  %while.7 = (s32[], f32[24,512,512]{2,1,0}, bf16[24,64,512]{2,1,0}, f32[64,512]{1,0}) while(%init), condition=%region_cond.6, body=%region_body.5
  %get-tuple-element.70 = f32[64,512]{1,0} get-tuple-element(%while.7), index=3
  ROOT %reduce_sum = f32[64,512]{1,0} negate(%get-tuple-element.70)
""")

# v5e: the expert layer's gather back (the custom call only tells the compiler that the
# indices are in range), a Pallas kernel, and two collectives beside them
KERNEL_AND_COLLECTIVES = module("""
%fused_computation.59 (param_0.179: bf16[65536,2304], param_1.655: s32[65536]) -> bf16[65536,2304] {
  %param_0.179 = bf16[65536,2304]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1.655 = s32[65536]{0:T(1024)S(1)} parameter(1)
  %custom-call.89 = s32[65536]{0:T(1024)} custom-call(%param_1.655), custom_call_target="AssumeGatherIndicesInBound", operand_layout_constraints={s32[65536]{0:T(1024)}}
  %gather.256 = bf16[65536,2304]{1,0:T(8,128)(2,1)} gather(%param_0.179, %custom-call.89), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,2304}
  ROOT %reshape.3418 = bf16[65536,2304]{1,0:T(8,128)(2,1)} reshape(%gather.256)
}""", """
%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b)
}""", entry="""
  %rows = bf16[65536,2304]{1,0:T(8,128)(2,1)} parameter(0)
  %order = s32[65536]{0:T(1024)S(1)} parameter(1)
  %gmm.1 = bf16[65536,2304]{1,0:T(8,128)(2,1)} custom-call(%rows), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[65536,2304]{1,0}}
  %fusion.59 = bf16[65536,2304]{1,0:T(8,128)(2,1)} fusion(%gmm.1, %order), kind=kCustom, calls=%fused_computation.59
  %all-gather.3 = bf16[262144,2304]{1,0} all-gather(%fusion.59), channel_id=1, replica_groups=[1,4]<=[4], dimensions={0}
  %all-reduce-start.4 = f32[2304]{0} all-reduce-start(%rows), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%region_add
  %all-reduce-done.4 = f32[2304]{0} all-reduce-done(%all-reduce-start.4)
  ROOT %tuple.9 = (bf16[262144,2304]{1,0}, f32[2304]{0}) tuple(%all-gather.3, %all-reduce-done.4)
""")

OPERAND_TWICE = module("""
%fused_computation.8 (param_0.8: f32[512,512], param_1.8: f32[512,512]) -> f32[512,512] {
  %param_0.8 = f32[512,512]{1,0} parameter(0)
  %param_1.8 = f32[512,512]{1,0} parameter(1)
  ROOT %multiply.8 = f32[512,512]{1,0} multiply(%param_0.8, %param_1.8)
}""", entry="""
  %x = f32[512,512]{1,0} parameter(0)
  ROOT %square = f32[512,512]{1,0} fusion(%x, %x), kind=kLoop, calls=%fused_computation.8
""")

UNFUSED = module(entry="""
  %stacked = bf16[24,64,512]{2,1,0} parameter(0)
  %row = bf16[1,64,512]{2,1,0} parameter(1)
  %at = s32[] parameter(2)
  %zero = s32[] constant(0)
  %copy.1 = bf16[24,64,512]{1,2,0} copy(%stacked)
  %slice.1 = bf16[2,64,512]{2,1,0} slice(%stacked), slice={[0:2], [0:64], [0:512]}
  %dynamic-update-slice.1 = bf16[24,64,512]{2,1,0} dynamic-update-slice(%stacked, %row, %at, %zero, %zero)
  %bitcast.1 = bf16[24,32768]{1,0} bitcast(%stacked)
  %copy-start.1 = (bf16[24,64,512]{2,1,0:S(1)}, bf16[24,64,512]{2,1,0}, u32[]{:S(2)}) copy-start(%stacked)
  %copy-done.1 = bf16[24,64,512]{2,1,0:S(1)} copy-done(%copy-start.1)
  %negate.1 = bf16[24,64,512]{2,1,0} negate(%copy-done.1)
  ROOT %tuple.1 = (bf16[24,64,512]{1,2,0}, bf16[2,64,512]{2,1,0}, bf16[24,64,512]{2,1,0}, bf16[24,32768]{1,0}, bf16[24,64,512]{2,1,0}) tuple(%copy.1, %slice.1, %dynamic-update-slice.1, %bitcast.1, %negate.1)
""")

# v5e, four chips (``olmoe_d4_train_4chip``): the queries sliced out of the fused QKV product's
# result BY A FUSION NESTED in the fusion that lays the heads out; a gradient's reduce-scatter
# that the compiler wrote as a fusion; a product with a small all-gather riding along
FOUR_CHIPS = module("""
%slice_bitcast_fusion.11 (slice_input.11: bf16[2,4096,6144]) -> bf16[2,512,16,8,128] {
  %slice_input.11 = bf16[2,4096,6144]{2,1,0:T(8,128)(2,1)} parameter(0)
  %slice.311 = bf16[2,4096,2048]{2,1,0:T(8,128)(2,1)} slice(%slice_input.11), slice={[0:2], [0:4096], [0:2048]}
  ROOT %bitcast.527 = bf16[2,512,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} bitcast(%slice.311)
}""", """
%fused_computation.650 (param_0.6462: bf16[2,4096,6144]) -> bf16[2,4096,16,128] {
  %param_0.6462 = bf16[2,4096,6144]{2,1,0:T(8,128)(2,1)} parameter(0)
  %slice_bitcast_fusion.11 = bf16[2,512,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} fusion(%param_0.6462), kind=kLoop, calls=%slice_bitcast_fusion.11
  %copy.650 = bf16[2,512,16,8,128]{4,3,1,2,0:T(8,128)(2,1)} copy(%slice_bitcast_fusion.11)
  ROOT %bitcast.528 = bf16[2,4096,16,128]{3,1,2,0:T(8,128)(2,1)} bitcast(%copy.650)
}""", """
%add.21.clone (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.21 = f32[] add(%x, %y)
}""", """
%all-reduce-scatter (input: f32[50304,2048]) -> f32[12768,2048] {
  %input = f32[50304,2048]{1,0:T(8,128)} parameter(0)
  %constant.11510 = f32[]{:T(128)} constant(0)
  %pad.720 = f32[51072,2048]{1,0:T(8,128)} pad(%input, %constant.11510), padding=0_768x0_0
  %all-reduce.57 = f32[51072,2048]{1,0:T(8,128)} all-reduce(%pad.720), channel_id=66, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add.21.clone
  %partition-id.53 = u32[] partition-id()
  %constant.11512 = u32[]{:T(128)} constant(0)
  ROOT %dynamic-slice.186 = f32[12768,2048]{1,0:T(8,128)} dynamic-slice(%all-reduce.57, %partition-id.53, %constant.11512), dynamic_slice_sizes={12768,2048}
}""", """
%fused_computation.901 (param_0.6621: s32[1,2,4096], param_1.118: bf16[2048,6144,1], param_2.144: bf16[2,4096,2048]) -> (bf16[2,4096,6144], s32[4,2,4096]) {
  %param_2.144 = bf16[2,4096,2048]{2,1,0:T(8,128)(2,1)} parameter(2)
  %param_1.118 = bf16[2048,6144,1]{1,0,2:T(8,128)(2,1)} parameter(1)
  %convolution.135 = f32[2,4096,6144]{2,1,0:T(8,128)} convolution(%param_2.144, %param_1.118), window={size=1}, dim_labels=0bf_io0->0bf
  %convert_element_type.3944 = bf16[2,4096,6144]{2,1,0:T(8,128)(2,1)} convert(%convolution.135)
  %param_0.6621 = s32[1,2,4096]{2,1,0:T(2,128)} parameter(0)
  %all-gather.15 = s32[4,2,4096]{2,1,0:T(2,128)} all-gather(%param_0.6621), channel_id=65, replica_groups=[1,4]<=[4], dimensions={0}, use_global_device_ids=true
  ROOT %tuple.1121 = (bf16[2,4096,6144]{2,1,0:T(8,128)(2,1)}, s32[4,2,4096]{2,1,0:T(2,128)}) tuple(%convert_element_type.3944, %all-gather.15)
}""", """
%wrapped_gather (p: bf16[512,2048]) -> bf16[2048,2048] {
  %p = bf16[512,2048]{1,0} parameter(0)
  ROOT %all-gather.16 = bf16[2048,2048]{1,0} all-gather(%p), channel_id=70, replica_groups=[1,4]<=[4], dimensions={0}
}""", entry="""
  %qkv = bf16[2,4096,6144]{2,1,0:T(8,128)(2,1)} parameter(0)
  %grad = f32[50304,2048]{1,0:T(8,128)} parameter(1)
  %ids = s32[1,2,4096]{2,1,0:T(2,128)} parameter(2)
  %w = bf16[2048,6144,1]{1,0,2:T(8,128)(2,1)} parameter(3)
  %x = bf16[2,4096,2048]{2,1,0:T(8,128)(2,1)} parameter(4)
  %piece = bf16[512,2048]{1,0} parameter(5)
  %copy_bitcast_fusion.7 = bf16[2,4096,16,128]{3,1,2,0:T(8,128)(2,1)} fusion(%qkv), kind=kLoop, calls=%fused_computation.650
  %fusion.132 = f32[12768,2048]{1,0:T(8,128)} fusion(%grad), kind=kCustom, calls=%all-reduce-scatter
  %fusion.901 = (bf16[2,4096,6144]{2,1,0:T(8,128)(2,1)}, s32[4,2,4096]{2,1,0:T(2,128)}) fusion(%ids, %w, %x), kind=kOutput, calls=%fused_computation.901
  %all-gather-start.3 = ((bf16[512,2048]{1,0}), bf16[2048,2048]{1,0}) async-start(%piece), calls=%wrapped_gather
  %all-gather-done.3 = bf16[2048,2048]{1,0} async-done(%all-gather-start.3)
  %collective-permute-start.60 = (bf16[512,2048]{1,0}, bf16[512,2048]{1,0}, u32[], u32[]) collective-permute-start(%piece), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %collective-permute-done.60 = bf16[512,2048]{1,0} collective-permute-done(%collective-permute-start.60)
  ROOT %tuple.2 = (bf16[2,4096,16,128]{3,1,2,0:T(8,128)(2,1)}, f32[12768,2048]{1,0:T(8,128)}) tuple(%copy_bitcast_fusion.7, %fusion.132)
""")

STACK = 24 * 64 * 512 * BF16
CASES = {
    "a dot fusion, operands by name": (
        DOT_FUSION, {"dot_fusion": [2 * 64 * 32 * 128, (64 * 128 + 128 * 32 + 64 * 32) * BF16]},
        (), {"dot_fusion": [[64, 128, 32, "bf16xbf16->f32"]]}),
    "v5e: a matmul spelled convolution, its weight in on-chip memory": (
        CONVOLUTION_MATMUL,
        {"convolution_convert_fusion.24": [2 * 8192 * 2304 * 4096, (8192 * 4096 + 8192 * 2304) * BF16],
         "copy-start.246": [0, 0], "copy-done.246": [0, 0]},
        ("convolution.104", "fusion.1193"),
        {"convolution_convert_fusion.24": [[8192, 4096, 2304, "bf16xbf16->f32"]]}),
    "v5e: a batched matmul as a padded window, a reduction as the tuple's first element": (
        BATCHED_AS_WINDOW,
        {"fusion.476": [2 * 8192 * 32 * 128 * 2304,
                        (32 * 128 * 2304 + 8192 * 2304 + 8192 * 32 * 128) * BF16]},
        ("convolution.81", "reduce.226"),
        {"fusion.476": [[8192 * 32, 2304, 128, "bf16xbf16->f32"]]}),
    "v5e: the head's weight gradient, a convolution in the entry computation": (
        HEAD_GRADIENT,
        {"convolution.75": [2 * 24576 * 2304 * 8192,
                            (8192 * 24576 + 8192 * 2304) * BF16 + 24576 * 2304 * F32]},
        (), {"convolution.75": [[24576, 8192, 2304, "bf16xbf16->f32"]]}),
    "a tuple-valued fusion with two products": (
        TWO_PRODUCTS,
        {"fusion.7": [2 * 256 * 512 * (1024 + 128),
                      (256 * 512 + 512 * 1024 + 512 * 128 + 256 * 128 + 256 * 1024) * BF16]},
        ("dot.71", "dot.72"),
        {"fusion.7": [[256, 512, 1024, "bf16xbf16->bf16"], [256, 512, 128, "bf16xbf16->bf16"]]}),
    "an elementwise fusion: no operations, each array once": (
        ELEMENTWISE, {"fusion.3": [0, 1024 * 256 * (F32 + BF16 + BF16)]}, ("multiply.3", "tanh.3"), {}),
    "a scanned layer: the stacked weights at one layer's slice, the kept row at the update": (
        SCANNED_LAYER,
        {"layer_weights": [0, 512 * 512 * F32 + 512 * 512 * BF16 + S32],
         "kept_row": [0, 64 * 512 * F32 + 64 * 512 * BF16 + S32],
         "get-tuple-element.51": [0, 0], "compare.60": [0, S32 + S32 + 1],
         "reduce_sum": [0, 2 * 64 * 512 * F32]},
        ("while.7", "dynamic_slice.28", "dynamic_update_slice.20", "tanh.20"), {}),
    "v5e: a gather priced at its rows; the kernel and the collectives absent": (
        KERNEL_AND_COLLECTIVES, {"fusion.59": [0, 2 * 65536 * 2304 * BF16]},
        ("gmm.1", "all-gather.3", "all-reduce-start.4", "all-reduce-done.4", "gather.256"), {}),
    "an operand passed twice is read once": (
        OPERAND_TWICE, {"square": [0, 2 * 512 * 512 * F32]}, (), {}),
    "unfused: a copy both ways, a slice at what it takes, an update at its size, views at nothing": (
        UNFUSED,
        {"copy.1": [0, 2 * STACK], "slice.1": [0, 2 * 2 * 64 * 512 * BF16],
         "dynamic-update-slice.1": [0, 2 * 64 * 512 * BF16 + 2 * S32], "bitcast.1": [0, 0],
         "copy-start.1": [0, 0], "copy-done.1": [0, 0], "negate.1": [0, STACK], "tuple.1": [0, 0]},
        (), {}),
    "v5e, four chips: a slice in a nested fusion; a product beside a small all-gather": (
        FOUR_CHIPS,
        {"copy_bitcast_fusion.7": [0, 2 * 2 * 4096 * 2048 * BF16],
         "fusion.901": [2 * 8192 * 2048 * 6144,
                        (8192 * 2048 + 2048 * 6144 + 8192 * 6144) * BF16 + (1 + 4) * 2 * 4096 * S32]},
        ("fusion.132", "all-gather-start.3", "all-gather-done.3", "collective-permute-start.60",
         "collective-permute-done.60", "slice.311"),
        {"fusion.901": [[8192, 2048, 6144, "bf16xbf16->f32"]]}),
}


@pytest.mark.parametrize("text, priced, absent, products", CASES.values(), ids=list(CASES))
def test_an_instruction_is_priced_at_what_it_has_to_do(text, priced, absent, products):
    got = hlo.instruction_costs(text)
    cost, found = got["cost"], got["products"]
    for name, expect in priced.items():
        assert cost[name] == expect, name
    for name in absent:
        assert name not in cost, name
    assert {name: p["mkn"] for name, p in found.items()} == products
    assert all(isinstance(v, int) and v >= 0 for pair in cost.values() for v in pair)


def test_what_is_a_collective_or_only_wraps_one_is_named_so():
    """A trace calls a reduce-scatter the compiler wrote as a fusion ``fusion.132 f32[..] fusion``:
    the program says what it is. A fusion that holds a product is priced as the product."""
    got = hlo.instruction_costs(FOUR_CHIPS)
    assert sorted(got["collectives"]) == ["all-gather-done.3", "all-gather-start.3",
                                          "collective-permute-done.60", "collective-permute-start.60",
                                          "fusion.132"]
    assert not set(got["collectives"]) & set(got["cost"])
    assert hlo.instruction_costs(KERNEL_AND_COLLECTIVES)["collectives"] == [
        "all-gather.3", "all-reduce-start.4", "all-reduce-done.4"]
    assert hlo.instruction_costs(UNFUSED)["collectives"] == []       # an asynchronous COPY is none


def test_a_tuple_valued_product_is_named_by_the_element_it_fills():
    found = hlo.instruction_costs(BATCHED_AS_WINDOW)["products"]
    assert found["fusion.476"]["as"] == "bf16[8192,32,128]"      # not the f32[8192,32] a trace prints
    found = hlo.instruction_costs(TWO_PRODUCTS)["products"]
    assert found["fusion.7"]["as"] == "bf16[256,1024]"           # the larger product's
    found = hlo.instruction_costs(DOT_FUSION)["products"]
    assert found["dot_fusion"]["as"] is None


@pytest.mark.parametrize("n, out, size, stride, lo, lhs_dilate, rhs_dilate, pairs", [
    (16, 14, 3, 1, 0, 1, 1, 14 * 3),            # no padding: every tap of every position
    (16, 16, 3, 1, 1, 1, 1, 16 * 3 - 2),        # padded by one each side: the edges lose a tap
    (1, 32, 32, 1, 31, 1, 1, 32),               # a batch dimension as a window: one tap a position
    (16, 8, 2, 2, 0, 1, 1, 16),                 # stride two
    (8, 9, 4, 1, 3, 1, 2, 6 + 8 + 7 + 5),       # a dilated filter over a causal pad, tap by tap
])
def test_a_window_counts_the_taps_that_read_the_input(n, out, size, stride, lo, lhs_dilate,
                                                      rhs_dilate, pairs):
    assert hlo._window_pairs(n, out, size, stride, lo, lhs_dilate, rhs_dilate) == pairs


def test_text_that_is_no_program_prices_nothing():
    nothing = {"cost": {}, "products": {}, "collectives": []}
    assert hlo.instruction_costs("") == nothing
    assert hlo.instruction_costs("HloModule m\n\n%f (p: f32[]) -> f32[] {\n  ROOT %p = f32[] parameter(0)\n}\n") == nothing
