"""Pallas TPU kernels (checked against their oracles in interpret mode, and
compiled for a described v5e chip at real widths by the tests):

  * flash_attention — blocked causal GQA attention (train/prefill hot spot)
  * head_select     — FACADE step-2c fused k-head cross-entropy
  * rwkv6           — wkv recurrence with VMEM-resident state
"""
from . import flash_attention, head_select, rwkv6  # noqa: F401
