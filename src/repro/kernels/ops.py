"""jit'd dispatch wrappers: Pallas kernel on TPU, interpret-mode on explicit
request (tests), pure-jnp reference otherwise. Model code calls these; it
never touches pallas_call directly."""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.gmm import gmm as _gmm_pallas
from repro.kernels.ibn_conv import ibn_pointwise as _ibn_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas
from repro.kernels.wstream import wstream_matmul as _wstream_pallas


def on_tpu() -> bool:
    """Whether JAX's default device is a TPU. A backend that fails to start
    raises here: a broken chip is never taken for a CPU."""
    return jax.devices()[0].platform == "tpu"


# A matmul over fp32 weights does 2*M*K*N flops per 4*K*N bytes: on a v5e
# (197e12 flop/s over 819e9 B/s, 240 flop/B) it is bandwidth-bound while
# M < 480, and there the weight-streaming kernel's one read of each weight
# pays. A v5e reckoning: timed there at 4 to 479 rows, the kernel read the
# stacked MLP weights 1.85x as fast as XLA's cast and matmul, and the table
# as fast. Other TPU generations would move the cut-off.
STREAM_ROWS = 480


def streams(rows: int) -> bool:
    """Whether a matmul of this many rows over stored fp32 weights reads them
    through the weight-streaming kernel: on a TPU, below STREAM_ROWS."""
    return rows < STREAM_ROWS and on_tpu()


def flash_attention(
    q, k, v, *, causal: bool = True, q_offset=0, kv_len=None,
    interpret: bool = False,
):
    """(B,S,H,hd)/(B,T,KV,hd) layout (model convention) -> (B,S,H,hd).

    The decode path (q_offset/kv_len masking against a preallocated cache) is
    served by the chunked-jnp flash-decoding path in models.layers; this entry
    point covers the training/prefill shapes.
    """
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if on_tpu() or interpret:
        out = _flash_pallas(qt, kt, vt, causal=causal, interpret=interpret)
    else:
        out = ref.flash_attention_ref(qt, kt, vt, causal=causal)
    return out.transpose(0, 2, 1, 3)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = False):
    if on_tpu() or interpret:
        return _ssd_pallas(x, dt, A, B, C, chunk=chunk, interpret=interpret)
    return ref.ssd_scan_ref(x, dt, A, B, C, chunk)


def gmm(x, w, *, interpret: bool = False):
    if on_tpu() or interpret:
        return _gmm_pallas(x, w, interpret=interpret)
    return ref.gmm_ref(x, w)


def ibn_pointwise(x, w, b, *, act: str = "relu", interpret: bool = False):
    if on_tpu() or interpret:
        return _ibn_pallas(x, w, b, act=act, interpret=interpret)
    return ref.ibn_pointwise_ref(x, w, b, act)


def wstream(x, w, layer=0, *, transpose: bool = False, interpret: bool = False):
    """x (M, K) @ w[layer].astype(x.dtype), the weight read in place in its
    stored dtype: w is (L, K, N) or (K, N), or (L, N, K) or (N, K) with
    transpose (a tied embedding table)."""
    if on_tpu() or interpret:
        return _wstream_pallas(x, w, layer, transpose=transpose,
                               interpret=interpret)
    return ref.wstream_ref(x, w, layer, transpose=transpose)
