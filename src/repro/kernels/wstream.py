"""Weight-streaming matmul Pallas kernel (TPU) for few-row matmuls.

Computes y = x @ w[layer].astype(x.dtype) for a few rows of x, reading the
weight in place and in its stored dtype: one layer of a stacked (L, K, N)
parameter, picked by a scalar-prefetched layer index in the BlockSpec index
map, or a whole (K, N) matrix. With ``transpose`` the weight is stored
(L, N, K) or (N, K), as a tied embedding table is, and the kernel contracts
over its last axis.

Each weight tile is DMA'd from HBM into VMEM, cast to x's dtype there and
multiplied on the MXU with an f32 accumulator in VMEM scratch; the output is
x's dtype. So the values are those of ``x @ w.astype(x.dtype)`` with f32
accumulation, while HBM sees each weight byte once: XLA instead writes a cast
copy of the stored weight and reads that again.

Grid: (N / block_n, K / block_k), the contraction axis minor and sequential.
A tile reads long contiguous rows of the weight's minor axis (up to
``MAX_ROW`` elements) and is filled along the other axis to ``BLOCK_BYTES``,
so that each DMA moves a few MB. The scoped VMEM limit is set from every
buffer: the double-buffered weight tiles and their cast copy, which are fixed,
and the double-buffered x and output blocks, the accumulator and the dot's f32
result, which grow with the rows (about 26 MiB in all at 479 rows). N need not divide
into blocks: the last block's columns past N read what lies beyond the
weight, and they are never written out. K must divide into blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_BYTES = 4 << 20  # one weight tile in its stored dtype
MAX_ROW = 2048  # elements of the weight's minor axis in one tile row
LANE = 128
SUBLANES = 32  # rows pad to this at most (packed 8-bit), so never undercounted


def _fit(dim: int, cap: int) -> int:
    """The whole axis if it fits under cap, else the largest multiple of
    128 up to cap that divides it."""
    if dim <= cap:
        return dim
    for b in range(cap - cap % LANE, 0, -LANE):
        if dim % b == 0:
            return b
    raise ValueError(f"no block of at most {cap} divides {dim}")


def blocks(k: int, n: int, itemsize: int, transpose: bool) -> tuple[int, int]:
    """(block_k, block_n) for a (K, N) weight, or an (N, K) one with
    transpose. K must divide into blocks; N may be ragged."""
    if transpose:  # rows run along K
        bk = _fit(k, MAX_ROW)
        bn = min(n, max(LANE, BLOCK_BYTES // (bk * itemsize) // LANE * LANE))
    else:  # rows run along N
        bn = min(n, MAX_ROW)
        bk = _fit(k, max(LANE, BLOCK_BYTES // (bn * itemsize)))
    return bk, bn


def _wstream_kernel(layer_ref, x_ref, w_ref, y_ref, acc_ref, *, transpose):
    del layer_ref  # used by the index maps only
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...].astype(x.dtype)
    contract = ((1,), (1,)) if transpose else ((1,), (0,))
    acc_ref[...] += jax.lax.dot_general(
        x, w, (contract, ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _final():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("transpose", "block_k", "block_n", "interpret"))
def wstream_matmul(
    x: jax.Array,  # (M, K)
    w: jax.Array,  # (L, K, N) or (K, N); (L, N, K) or (N, K) with transpose
    layer=0,
    *,
    transpose: bool = False,
    block_k: int | None = None,
    block_n: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    if w.ndim == 2:
        w = w[None]  # a bitcast, not a copy
    m, k = x.shape
    n = w.shape[1] if transpose else w.shape[2]
    assert w.shape[2 if transpose else 1] == k, (x.shape, w.shape, transpose)
    bk, bn = blocks(k, n, w.dtype.itemsize, transpose)
    bk, bn = block_k or bk, block_n or bn
    assert k % bk == 0, (k, bk)
    if transpose:
        w_spec = pl.BlockSpec((None, bn, bk), lambda ni, ki, l: (l[0], ni, ki))
    else:
        w_spec = pl.BlockSpec((None, bk, bn), lambda ni, ki, l: (l[0], ki, ni))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(n, bn), k // bk),
        in_specs=[pl.BlockSpec((m, bk), lambda ni, ki, l: (0, ki)), w_spec],
        out_specs=pl.BlockSpec((m, bn), lambda ni, ki, l: (0, ni)),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
    )
    xs = x.dtype.itemsize
    rows = -(-m // SUBLANES) * SUBLANES  # as Mosaic pads the row axis
    vmem = (2 * bk * bn * w.dtype.itemsize  # weight tiles, double-buffered
            + bk * bn * xs                  # their cast copy
            + 2 * rows * bk * xs            # x blocks, double-buffered
            + 2 * rows * bn * xs            # out blocks, double-buffered
            + 2 * rows * bn * 4             # f32 accumulator and dot result
            + (4 << 20))                    # Mosaic's internal scratch
    return pl.pallas_call(
        functools.partial(_wstream_kernel, transpose=transpose),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem, 16 << 20)),
        interpret=interpret,
        name="wstream_matmul",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), x, w)
