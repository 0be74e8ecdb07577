"""Pure-jnp oracles for every kernel. These are the correctness ground truth
the Pallas kernels are swept against (tests/test_kernels.py)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True) -> jax.Array:
    """q: (B,H,Sq,hd), k/v: (B,KV,Skv,hd) -> (B,H,Sq,hd). fp32 softmax."""
    b, h, sq, hd = q.shape
    kv = k.shape[1]
    g = h // kv
    qg = q.reshape(b, kv, g, sq, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgsd,bktd->bkgst", qg, kf) / math.sqrt(hd)
    skv = k.shape[2]
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), skv - sq)
        scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", p, vf)
    return out.reshape(b, h, sq, hd).astype(q.dtype)


def ssd_scan_ref(x, dt, A, B, C, chunk: int):
    """Oracle = the sequential (non-chunked) SSD recurrence.
    x: (B,S,H,P), dt: (B,S,H), A: (H,), B/C: (B,S,G,N).
    Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hg = h // g
    f32 = jnp.float32
    bg = jnp.repeat(B, hg, axis=2).astype(f32)  # (B,S,H,N)
    cg = jnp.repeat(C, hg, axis=2).astype(f32)
    dtf = dt.astype(f32)
    xf = x.astype(f32)

    def step(state, i):
        dA = jnp.exp(dtf[:, i] * A.astype(f32))  # (B,H)
        xdt = xf[:, i] * dtf[:, i][..., None]  # (B,H,P)
        state = state * dA[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", xdt, bg[:, i])
        y = jnp.einsum("bhpn,bhn->bhp", state, cg[:, i])
        return state, y

    state0 = jnp.zeros((b, h, p, n), f32)
    state, ys = jax.lax.scan(step, state0, jnp.arange(s))
    return ys.swapaxes(0, 1).astype(x.dtype), state


def gmm_ref(x, w) -> jax.Array:
    """Grouped matmul. x: (E, C, D), w: (E, D, F) -> (E, C, F)."""
    return jnp.einsum(
        "ecd,edf->ecf", x.astype(jnp.float32), w.astype(jnp.float32)
    ).astype(x.dtype)


def ibn_pointwise_ref(x, w, b, act: str = "relu") -> jax.Array:
    """1x1 conv + bias + activation. x: (N, Cin), w: (Cin, Cout), b: (Cout,)."""
    y = x.astype(jnp.float32) @ w.astype(jnp.float32) + b.astype(jnp.float32)
    if act == "relu":
        y = jax.nn.relu(y)
    elif act == "silu":
        y = jax.nn.silu(y)
    return y.astype(x.dtype)


def wstream_ref(x, w, layer=0, *, transpose: bool = False) -> jax.Array:
    """x @ w[layer] with the weight rounded to x's dtype, f32 accumulation.
    x: (M, K); w: (L, K, N) or (K, N), or (L, N, K) or (N, K) with
    transpose -> (M, N) in x's dtype."""
    w = (w if w.ndim == 2 else w[layer]).astype(x.dtype)
    if transpose:
        w = w.T
    return jnp.matmul(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
