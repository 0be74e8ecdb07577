"""Flash attention Pallas kernel (TPU): fused online-softmax attention.

TPU adaptation (DESIGN.md §2): instead of a CUDA thread-block tiling, the
kernel is expressed over a sequential-minor Pallas grid
    (batch, kv_head, q_group, q_block, kv_block)
with the running (m, l, acc) state held in VMEM scratch across the kv_block
(minor, "arbitrary") dimension — the standard TPU flash layout. Block shapes
are MXU-aligned: q/kv blocks default to 128 rows, head_dim is the lane dim.

The GQA grouping is expressed in the grid (kv_head × q_group), so K/V blocks
are fetched from HBM once per kv head and reused by all of its query heads —
the HBM-traffic win that matters for the assigned GQA archs (kv ≤ 8).

VMEM working set per step: q(block_q×hd) + k,v(block_k×hd each) +
acc(block_q×hd f32) + m,l — e.g. 128×128 blocks in bf16: ~33+66+66+131 KB,
comfortably under the ~16 MB v5e VMEM budget, leaving room for double
buffering of the k/v streams.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, causal: bool, scale: float, block_q: int, block_k: int, kv_len: int,
):
    qi = pl.program_id(3)
    ki = pl.program_id(4)
    nk = pl.num_programs(4)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, 0]  # (block_q, hd)
    k = k_ref[0, 0]     # (block_k, hd)
    v = v_ref[0, 0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (block_q, block_k)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < kv_len
    if causal:
        mask = mask & (k_pos <= q_pos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.max(s, axis=1)[:, None]
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)[:, None]
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, H, Sq, hd)
    k: jax.Array,  # (B, KV, Skv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, hd = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, sq, hd)

    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq = -(-sq // block_q)
    nk = -(-skv // block_k)
    pad_q = nq * block_q - sq
    pad_k = nk * block_k - skv
    if pad_q:
        qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    grid = (b, kvh, g, nq, nk)
    kernel = functools.partial(
        _attn_kernel,
        causal=causal,
        scale=1.0 / math.sqrt(hd),
        block_q=block_q,
        block_k=block_k,
        kv_len=skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, 1, block_q, hd),
                lambda bi, ki_, gi, qi, kj: (bi, ki_, gi, qi, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda bi, ki_, gi, qi, kj: (bi, ki_, kj, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda bi, ki_, gi, qi, kj: (bi, ki_, kj, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, block_q, hd),
            lambda bi, ki_, gi, qi, kj: (bi, ki_, gi, qi, 0),
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qg, k, v)
    out = out.reshape(b, h, sq + pad_q, hd)
    return out[:, :, :sq]
