"""Pallas TPU kernels for the compute hot-spots, with pure-jnp oracles.

  flash_attention  — fused online-softmax attention (GQA-aware)
  ssd_scan         — Mamba2 SSD chunk scan (state carried in VMEM scratch)
  gmm              — grouped (per-expert) matmul for MoE EP
  ibn_conv         — pointwise (1x1) conv + activation fusion for IBN layers
  wstream          — few-row matmul reading fp32 weights in place (decode)

Each kernel ships as <name>.py (pl.pallas_call + BlockSpec), with ops.py
providing the jit'd dispatch wrappers (TPU kernel when available, interpret
mode for CPU validation, jnp reference otherwise) and ref.py the oracles.
"""
