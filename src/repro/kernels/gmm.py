"""Grouped (per-expert) matmul Pallas kernel for MoE expert parallelism.

Computes y[e] = x[e] @ w[e] for the (E_local, C, D) × (E_local, D, F) dispatch
buffers of repro.models.moe. Grid: (E, C/bc, F/bf, D/bd) with the contraction
dim minor/sequential and an (bc, bf) fp32 accumulator in VMEM scratch —
MegaBlocks' grouped GEMM rethought as a Pallas block-tiled loop (the TPU has
no warp-level tiles to specialize; the MXU wants 128-aligned (bc×bd)·(bd×bf)
tiles, which BlockSpec provides directly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, y_ref, acc_scr):
    di = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]  # (bc, bd)
    w = w_ref[0]  # (bd, bf)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(di == nd - 1)
    def _final():
        y_ref[0] = acc_scr[...].astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "block_d", "interpret")
)
def gmm(
    x: jax.Array,  # (E, C, D)
    w: jax.Array,  # (E, D, F)
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    e, c, d = x.shape
    f = w.shape[2]
    bc, bf, bd = min(block_c, c), min(block_f, f), min(block_d, d)

    def padto(v, b):
        return (-v) % b

    pc, pf, pd = padto(c, bc), padto(f, bf), padto(d, bd)
    if pc or pd:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, pd)))
    if pd or pf:
        w = jnp.pad(w, ((0, 0), (0, pd), (0, pf)))
    nc, nf, nd = (c + pc) // bc, (f + pf) // bf, (d + pd) // bd

    y = pl.pallas_call(
        _gmm_kernel,
        grid=(e, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda ei, ci, fi, di: (ei, ci, di)),
            pl.BlockSpec((1, bd, bf), lambda ei, ci, fi, di: (ei, di, fi)),
        ],
        out_specs=pl.BlockSpec(
            (1, bc, bf), lambda ei, ci, fi, di: (ei, ci, fi)
        ),
        out_shape=jax.ShapeDtypeStruct((e, c + pc, f + pf), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")
        ),
        interpret=interpret,
    )(x, w)
    return y[:, :c, :f]
