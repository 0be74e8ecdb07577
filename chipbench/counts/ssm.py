"""Operations that a Mamba2 model's training step requires, from the
published widths. Recomputation (remat) is not counted."""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """In-projections (z, x, B and C, dt), out-projection and the output head
    (the tied table counts once, as the head); the embedding lookup is a
    gather and is left out."""
    d = m["d_model"]
    di = m["ssm_expand"] * d
    h = di // m["ssm_head_dim"]
    bc = 2 * m["ssm_ngroups"] * m["ssm_state"]
    return m["num_layers"] * (d * (2 * di + bc + h) + di * d) + d * m["vocab_size"]


def ssd_flops_per_token(m: dict) -> float:
    """The chunked SSD scan's contractions per token and layer, forward, at
    chunk Q: C B^T within a chunk (2 Q N per group), the masked scores times
    x (2 Q P per head), each chunk's state B^T x (2 N P per head) and the
    output from the carried state C h (2 N P per head); plus the depthwise
    convolution (2 W channels)."""
    d = m["d_model"]
    di = m["ssm_expand"] * d
    h, p = di // m["ssm_head_dim"], m["ssm_head_dim"]
    q, n, g = m["ssm_chunk"], m["ssm_state"], m["ssm_ngroups"]
    conv = 2 * m["ssm_conv_width"] * (di + 2 * g * n)
    return 2 * q * n * g + h * (2 * q * p + 4 * n * p) + conv


def train_flops_per_token(m: dict, seq: int) -> float:
    return 6 * matmul_params(m) + 3 * m["num_layers"] * ssd_flops_per_token(m)
