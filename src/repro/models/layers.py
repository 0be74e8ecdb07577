"""Core transformer layers: norms, RoPE, attention (naive / chunked-flash / pallas),
gated MLPs, embeddings. Everything is functional: ``init_*`` builds param pytrees,
``apply``-style functions are pure.

Shape conventions:
  x       : (B, S, D)
  q       : (B, S, H, hd)      k/v : (B, S, KV, hd)
  caches  : k/v (B, KV, S_max, hd)  (+ int8 scales (B, KV, S_max, 1) when quantized)
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.common import dtype_of
from repro.config import ModelConfig
from repro.kernels import ops as kops
from repro.obs import scopes

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(rng, shape, dtype, fan_in: Optional[int] = None):
    fan_in = fan_in or shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(rng, shape, jnp.float32) * std).astype(dtype)


def embed_init(rng, shape, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + params["scale"].astype(jnp.float32) - 1.0)).astype(dtype) * 1.0


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)  # (hd/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd), positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------


def _grouped_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,S,H,hd) k: (B,T,KV,hd) -> scores (B, KV, G, S, T) with H = KV*G."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, s, kv, g, hd)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32)


def _grouped_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: (B,KV,G,S,T) v: (B,T,KV,hd) -> (B,S,H,hd)."""
    b, kv, g, s, t = probs.shape
    hd = v.shape[-1]
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, kv * g, hd)


def naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    q_offset: jax.Array | int = 0,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Materializes the full score matrix. Reference / short-seq path.

    q_offset: position of q[0] within the kv sequence (decode: cur position).
    kv_len:   number of valid kv entries (decode with a preallocated cache).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = _grouped_scores(q, k) * scale  # (B,KV,G,S,T) fp32
    s, t = scores.shape[-2], scores.shape[-1]
    q_pos = jnp.arange(s)[:, None] + q_offset
    k_pos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), dtype=bool)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if kv_len is not None:
        mask = mask & (k_pos < kv_len)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return _grouped_out(probs, v)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    chunk: int = 1024,
    q_offset: jax.Array | int = 0,
    kv_len: Optional[jax.Array] = None,
    unroll: bool = False,
) -> jax.Array:
    """Flash-style online-softmax attention, scanning over KV chunks.

    Peak memory is O(S_q * chunk) per (batch, kv-head) instead of O(S_q * S_kv);
    this is the dry-run / CPU stand-in for the Pallas flash kernel and also the
    flash-decoding path (S_q == 1, long caches).
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    chunk = min(chunk, t)
    n_chunks = -(-t // chunk)
    pad = n_chunks * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(hd)
    qg = (q * scale).reshape(b, s, kvh, g, hd)
    q_pos = jnp.arange(s) + q_offset  # (S,)

    # reshape kv into chunks up front so scan slices are cheap
    kc = k.reshape(b, n_chunks, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)

    def body(carry, inputs):
        m, l, acc = carry  # m,l: (B,KV,G,S) ; acc: (B,S,KV,G,hd)
        idx, k_i, v_i = inputs  # k_i/v_i: (B,chunk,KV,hd)
        k_pos = idx * chunk + jnp.arange(chunk)
        scores = jnp.einsum(
            "bskgd,btkd->bkgst", qg, k_i, preferred_element_type=jnp.float32
        )  # (B,KV,G,S,chunk)
        mask = jnp.ones((s, chunk), dtype=bool)
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if kv_len is not None:
            mask = mask & (k_pos[None, :] < kv_len)
        else:
            mask = mask & (k_pos[None, :] < t)  # padding chunk tail
        scores = jnp.where(mask, scores, -1e30)
        m_i = jnp.max(scores, axis=-1)  # (B,KV,G,S)
        m_new = jnp.maximum(m, m_i)
        p = jnp.exp(scores - m_new[..., None])  # (B,KV,G,S,chunk)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkd->bskgd", p.astype(v_i.dtype), v_i)
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, s), jnp.float32)
    acc0 = jnp.zeros((b, s, kvh, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (jnp.arange(n_chunks), kc, vc),
        unroll=n_chunks if unroll else 1,
    )
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, s, h, hd).astype(q.dtype)


def chunked_attention_quantized(
    q: jax.Array,  # (B, S, H, hd)
    cache: dict,   # int8 k/v (B, KV, T, hd) + fp32 scales (B, KV, T, 1)
    *,
    chunk: int = 1024,
    q_offset: jax.Array | int = 0,
    kv_len: Optional[jax.Array] = None,
    unroll: bool = False,
) -> jax.Array:
    """Flash-decoding over an int8 KV cache with PER-CHUNK dequantization.

    §Perf optimization (cfg.lazy_kv_dequant): the baseline dequantizes the
    whole cache to bf16 up-front (2x the cache bytes materialized + read);
    here each scan step dequantizes only its (chunk × hd) tile, so HBM sees
    the int8 bytes once — this halves the decode memory-roofline term on top
    of the int8 storage win.
    """
    b, s, h, hd = q.shape
    kvh, t = cache["k"].shape[1], cache["k"].shape[2]
    g = h // kvh
    chunk = min(chunk, t)
    n_chunks = -(-t // chunk)
    assert t % chunk == 0, "cache length must be a multiple of the chunk"
    scale = 1.0 / math.sqrt(hd)
    qg = (q * scale).reshape(b, s, kvh, g, hd)
    q_pos = jnp.arange(s) + q_offset

    def chunks(x):  # (B,KV,T,d) -> (nc,B,KV,chunk,d)
        return x.reshape(b, kvh, n_chunks, chunk, -1).transpose(2, 0, 1, 3, 4)

    kc, vc = chunks(cache["k"]), chunks(cache["v"])
    ksc, vsc = chunks(cache["k_scale"]), chunks(cache["v_scale"])

    def body(carry, inputs):
        m, l, acc = carry
        idx, k_i, v_i, ks_i, vs_i = inputs  # k/v int8 (B,KV,chunk,hd)
        k_f = k_i.astype(jnp.float32) * ks_i  # dequant this tile only
        k_pos = idx * chunk + jnp.arange(chunk)
        scores = jnp.einsum("bskgd,bktd->bkgst", qg.astype(jnp.float32), k_f)
        mask = k_pos[None, :] <= q_pos[:, None]
        if kv_len is not None:
            mask = mask & (k_pos[None, :] < kv_len)
        scores = jnp.where(mask, scores, -1e30)
        m_i = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, m_i)
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        v_f = v_i.astype(jnp.float32) * vs_i
        pv = jnp.einsum("bkgst,bktd->bskgd", p, v_f)
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, s), jnp.float32)
    acc0 = jnp.zeros((b, s, kvh, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (jnp.arange(n_chunks), kc, vc, ksc, vsc),
        unroll=n_chunks if unroll else 1,
    )
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, s, h, hd).astype(q.dtype)


def attention_core(q, k, v, cfg: ModelConfig, **kw) -> jax.Array:
    impl = cfg.attn_impl
    if impl == "flash_pallas":
        # The Pallas kernel covers the train/prefill shapes on a TPU. Decode
        # and cache-masked calls (kv_len, traced q_offset) and non-TPU runs
        # take the numerically equivalent chunked path. A kernel that fails
        # to lower on the chip raises.
        no_cache = kw.get("kv_len") is None and isinstance(
            kw.get("q_offset", 0), int)
        if no_cache and kops.on_tpu():
            return kops.flash_attention(q, k, v,
                                        causal=kw.get("causal", True))
        impl = "chunked"
    if impl == "chunked":
        return chunked_attention(q, k, v, chunk=cfg.attn_chunk,
                                 unroll=cfg.unroll_scans, **kw)
    return naive_attention(q, k, v, **kw)


# ---------------------------------------------------------------------------
# Weight matmuls
# ---------------------------------------------------------------------------


class StreamedWeight(NamedTuple):
    """A weight that a few-row matmul reads in place, in its stored dtype,
    through the weight-streaming kernel: layer ``layer`` of a stacked
    (L, K, N) parameter, or a whole 2-D matrix. The decode step hands these
    to the layers in place of weight arrays."""

    w: jax.Array
    layer: Any = 0


def linear(x: jax.Array, w, cdt, *, transpose: bool = False) -> jax.Array:
    """x (..., K) @ w in the compute dtype cdt; with transpose w is stored
    (N, K). A StreamedWeight goes through the kernel, with the same
    rounding of the weight and f32 accumulation."""
    if isinstance(w, StreamedWeight):
        y = kops.wstream(x.reshape(-1, x.shape[-1]).astype(cdt), w.w, w.layer,
                         transpose=transpose)
        return y.reshape(x.shape[:-1] + y.shape[-1:])
    w = w.astype(cdt)
    return x @ (w.T if transpose else w)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + cache handling)
# ---------------------------------------------------------------------------


def init_attention(rng, cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dtype = dtype_of(cfg.param_dtype)
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype),
        "wk": dense_init(ks[1], (d, kv * hd), dtype),
        "wv": dense_init(ks[2], (d, kv * hd), dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype, fan_in=h * hd),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def attention_block(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    cache: Optional[dict] = None,
    cache_index: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[dict]]:
    """Full attention block. If ``cache`` is given, runs one decode step:
    x is (B, 1, D); k/v are appended at ``cache_index``.
    """
    b, s, d = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    cdt = dtype_of(cfg.compute_dtype)
    xc = x.astype(cdt)
    q = linear(xc, params["wq"], cdt).reshape(b, s, h, hd)
    k = linear(xc, params["wk"], cdt).reshape(b, s, kvh, hd)
    v = linear(xc, params["wv"], cdt).reshape(b, s, kvh, hd)
    if cfg.use_qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = attention_core(q, k, v, cfg, causal=cfg.causal)
        new_cache = None
    else:
        from repro.serving.kvcache import cache_update, cache_kv, quantized

        new_cache = cache_update(cache, k, v, cache_index)
        if cfg.lazy_kv_dequant and quantized(new_cache):
            out = chunked_attention_quantized(
                q, new_cache, chunk=cfg.attn_chunk,
                q_offset=cache_index, kv_len=cache_index + s,
                unroll=cfg.unroll_scans,
            )
        else:
            k_full, v_full = cache_kv(new_cache)
            out = attention_core(
                q,
                k_full,
                v_full,
                cfg,
                causal=True,
                q_offset=cache_index,
                kv_len=cache_index + s,
            )
    out = linear(out.reshape(b, s, h * hd), params["wo"], cdt)
    return out.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def init_mlp(rng, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dtype = dtype_of(cfg.param_dtype)
    ks = jax.random.split(rng, 3)
    return {
        "wi_gate": dense_init(ks[0], (d, f), dtype),
        "wi_up": dense_init(ks[1], (d, f), dtype),
        "wo": dense_init(ks[2], (f, d), dtype, fan_in=f),
    }


def mlp_block(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    cdt = dtype_of(cfg.compute_dtype)
    xc = x.astype(cdt)
    gate = linear(xc, params["wi_gate"], cdt)
    up = linear(xc, params["wi_up"], cdt)
    act = jax.nn.silu(gate) if cfg.act == "silu" else jax.nn.gelu(gate)
    return linear(act * up, params["wo"], cdt).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def init_embedding(rng, cfg: ModelConfig) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    ks = jax.random.split(rng, 2)
    p = {"embedding": embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dtype)
    return p


@jax.named_scope(scopes.EMBED)
def embed(params: dict, tokens: jax.Array, cfg: ModelConfig, pc=None) -> jax.Array:
    """Token embedding lookup. With a ParallelCtx the gather runs inside an
    explicit shard_map over the model axis (table sharded on d_model): XLA's
    SPMD gather partitioning mis-compiles this pattern under jvp+scan
    (dynamic-slice size mismatch), and manual sharding is also faster — the
    lookup is local per shard with zero collectives. A StreamedWeight table
    is gathered in its stored dtype and the rows cast: the same values,
    without a cast copy of the whole table."""
    cdt = dtype_of(cfg.compute_dtype)
    table = params["embedding"]
    if pc is not None and pc.tp and table.shape[1] % pc.model_size == 0:
        from jax.sharding import PartitionSpec as P

        bt = pc.batch_axes if len(pc.batch_axes) > 1 else pc.batch_axes[0]
        tok_spec = P(bt, None) if tokens.shape[0] % pc.batch_size == 0 else P(None, None)
        out_spec = P(tok_spec[0], None, pc.model_axis)

        def body(tok, tab):
            return tab.astype(cdt)[tok]

        x = jax.shard_map(
            body,
            mesh=pc.mesh,
            in_specs=(tok_spec, P(None, pc.model_axis)),
            out_specs=out_spec,
            check_vma=False,
        )(tokens, table)
    elif isinstance(table, StreamedWeight):
        x = table.w[tokens].astype(cdt)
    else:
        x = table.astype(cdt)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cdt)
    return x


def unembed(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    cdt = dtype_of(cfg.compute_dtype)
    if cfg.tie_embeddings:
        logits = linear(x.astype(cdt), params["embedding"], cdt, transpose=True)
    else:
        logits = linear(x.astype(cdt), params["unembed"], cdt)
    logits = logits.astype(dtype_of(cfg.logits_dtype))
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    return logits


@jax.named_scope(scopes.LM_HEAD)
def lm_head(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The final norm and the unembedding of a whole model's params:
    hidden states (B, S, D) -> logits (B, S, V)."""
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return unembed(params["embed"], x, cfg)
