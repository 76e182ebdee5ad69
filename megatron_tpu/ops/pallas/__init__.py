"""Pallas TPU kernels for the hot ops.

These are the TPU-native equivalents of the reference's CUDA kernel zoo
(megatron/fused_kernels/: the three scaled-masked-softmax kernels, fused
layernorm) and its FlashAttention-2 dependency (transformer.py:9,524-553).
Everything else the CUDA kernels fuse by hand, XLA fuses on TPU; attention
is the one op where a hand-written blockwise kernel beats the compiler.

Attention is ONE kernel family (flash_template.py, mask/block-skip
predicates in masks.py): training/prefill fwd + custom-vjp recompute bwd,
decode as the Sq-small specialization, page-table indirection / sliding
window / kv_lengths masking / multi-query tiling as template knobs;
callers import the instantiations from flash_template itself. The MoE
experts' grouped matmuls are grouped_matmul.py.
"""
