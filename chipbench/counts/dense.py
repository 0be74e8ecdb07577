"""Operations and bytes that a dense decoder's steps require, from the
published widths. Recomputation (remat) is not counted, and neither is
anything an implementation could avoid."""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters that take part in matmuls: every layer's projections and
    the output head (the tied table counts once, as the head); the embedding
    lookup is a gather and is left out."""
    d, hd, f = m["d_model"], m["head_dim"], m["d_ff"]
    attn = d * m["num_heads"] * hd * 2 + d * m["num_kv_heads"] * hd * 2
    return m["num_layers"] * (attn + 3 * d * f) + d * m["vocab_size"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """6 N plus causal attention: QK^T and PV at 2 (S/2) H hd operations each
    per token per layer forward, times 3 for forward and backward."""
    attn = 4 * (seq / 2) * m["num_heads"] * m["head_dim"] * m["num_layers"]
    return 6 * matmul_params(m) + 3 * attn


def decode_step(m: dict, batch: int, positions: float, kv_bytes: int) -> dict:
    """One greedy decode step of ``batch`` rows, each attending to
    ``positions`` cached positions (the new one included).

    Bytes: the weights at the compute dtype (bf16) read once, the cache read
    at its own dtype for the positions in use, and the new K and V written.
    Operations: 2 N per row for the matmuls, 4 positions H hd per row and
    layer for attention."""
    n = matmul_params(m)
    per_pos = 2 * m["num_layers"] * m["num_kv_heads"] * m["head_dim"] * kv_bytes
    norms = (2 * m["num_layers"] + 1) * m["d_model"] + 2 * m["num_layers"] * m["head_dim"]
    flops = batch * (2 * n + 4 * positions * m["num_heads"] * m["head_dim"]
                     * m["num_layers"])
    read = 2 * (n + norms) + batch * positions * per_pos
    return {"flops": float(flops), "bytes": float(read + batch * per_pos)}
