"""Pallas TPU kernels for the compute hot-spots (validated in
interpret=True mode on CPU; see tests/test_kernels.py):

  int4_matmul — fused HQQ-INT4 dequant matmul (quantized resident experts)
  moe_gmm     — grouped per-expert FFN matmul (expert-parallel MoE)
  ssd_scan    — Mamba2 SSD chunked scan with VMEM-carried state
  flash_attn  — causal GQA flash attention fwd (prefill; VMEM-resident KV)
  mla_decode  — latent-attention decode over a latent cache (one token per row)

``dispatch`` owns backend selection (ref | pallas | auto), platform
autodetection (interpret off-TPU) and the ``kernel_dispatch_total``
counter of what each call site ran.
"""
from . import dispatch, flash_attn, int4_matmul, mla_decode, moe_gmm, ssd_scan

__all__ = ["dispatch", "flash_attn", "int4_matmul", "mla_decode", "moe_gmm",
           "ssd_scan"]
