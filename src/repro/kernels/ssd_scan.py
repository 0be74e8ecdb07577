"""Mamba2 SSD chunk-scan Pallas kernel (TPU).

Grid: (batch, head, chunk) with the chunk axis sequential ("arbitrary") and
the (P, N) inter-chunk state carried in VMEM scratch — the TPU analogue of
the CUDA ssd_combined kernel: no HBM round-trip for the state, intra-chunk
work expressed as three MXU matmuls:

    cumsum(dA)          as  tril_ones(Q,Q) @ dA        (matmul-based cumsum)
    scores = (C Bᵀ) ∘ L then  y_intra = scores @ (x·dt)
    y_inter = C @ stateᵀ · decay_in
    state'  = state·exp(tot) + (x·dt)ᵀ @ (B·decay_out)

Block shapes: Q (chunk length, default 128) rows × P/N lanes — MXU-aligned
for the assigned configs (P=64, N∈{64,128}).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_scr,
                *, q: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)      # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)    # (Q, 1)
    a = a_ref[pl.program_id(1)]                 # scalar per head (SMEM)
    bm = b_ref[0, 0, 0].astype(jnp.float32)     # (Q, N)
    cm = c_ref[0, 0, 0].astype(jnp.float32)     # (Q, N)

    dA = dt * a  # (Q,1), <= 0
    # matmul-based inclusive cumsum (MXU-friendly; no lax.cumsum in mosaic)
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril = (cols <= rows).astype(jnp.float32)
    cs = jax.lax.dot_general(
        tril, dA, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q,1) inclusive cumsum

    seg = cs - cs.T  # (Q,Q): cs[i] - cs[j]
    lmat = jnp.where(cols <= rows, jnp.exp(seg), 0.0)

    xdt = x * dt  # (Q,P)
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * lmat  # (Q,Q)
    y = jax.lax.dot_general(
        scores, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q,P)

    state = state_scr[...]  # (P,N) f32
    decay_in = jnp.exp(cs)  # (Q,1)
    y = y + jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * decay_in  # (Q,P)

    tot = cs[q - 1, 0]
    decay_out = jnp.exp(tot - cs)  # (Q,1)
    state_scr[...] = state * jnp.exp(tot) + jax.lax.dot_general(
        xdt, bm * decay_out, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (P,N)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _final():
        st_ref[0, 0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,   # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) fp32 (post-softplus)
    A: jax.Array,   # (H,) negative
    B: jax.Array,   # (B, S, G, N)
    C: jax.Array,   # (B, S, G, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hg = h // g
    q = min(chunk, s)
    pad = (-s) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // q

    # (B, H, nc, Q, ...) layouts; G broadcast to H
    xk = x.transpose(0, 2, 1, 3).reshape(b, h, nc, q, p)
    dtk = dt.transpose(0, 2, 1).reshape(b, h, nc, q, 1)
    bk = jnp.repeat(B, hg, axis=2).transpose(0, 2, 1, 3).reshape(b, h, nc, q, n)
    ck = jnp.repeat(C, hg, axis=2).transpose(0, 2, 1, 3).reshape(b, h, nc, q, n)

    y, st = pl.pallas_call(
        functools.partial(_ssd_kernel, q=q),
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, 1), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # all of A, per-head scalars
            pl.BlockSpec((1, 1, 1, q, n), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, q, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xk, dtk, A.astype(jnp.float32), bk, ck)
    y = y.reshape(b, h, sp, p).transpose(0, 2, 1, 3)[:, :s]
    return y, st
