"""Pallas TPU kernels — the hot-op corpus.

Parity: the reference's fused CUDA ops (/root/reference/paddle/fluid/operators/
fused/: fused_attention_op.cu, fmha_ref.h, fused_feedforward) re-designed as
Pallas TPU kernels instead of hand-written CUDA.

- :mod:`.flash_attention` — FlashAttention-2 fwd+bwd (MQA/GQA, ragged
  pad-to-block, and causal **query offsets**: ``q_offset`` places query
  row i at absolute position ``q_offset + i``, so causal ``sk != sq`` —
  cached decode, chunked prefill — runs the kernel instead of falling
  back to XLA).
- :mod:`.paged_attention` — ragged paged-attention single-token decode
  over a block KV-cache pool (page-table gather via scalar prefetch;
  the serving engine's attention core).
- :mod:`.ring_attention` — sequence-parallel ring attention.
- :mod:`.moe_dispatch` — fused MoE dispatch/combine: ONE kernel for
  top-k gate + capacity-clamped scatter into per-expert buffers, one
  for the weighted combine (scalar-prefetch row gather); gather-based
  reference + recompute VJPs, so fused training is trajectory-
  equivalent to the unfused path.
- :mod:`.grouped_matmul` — grouped matmul over rows sorted by group
  against a flat stack of expert weights read at a layer's index (the
  dropless expert product of ``models.sdar``; row tile from the shapes).
- :mod:`.int8_matmul` — weight-only-int8 dequant-matmul.
- :mod:`._mosaic` — what the kernel files share (x64 off around a
  ``pallas_call`` traced for the chip).
"""
