"""Decoder / encoder transformer (dense, VLM-backbone, audio-encoder families).

Layers are stacked along a leading L dim and executed with ``jax.lax.scan`` so
the lowered HLO stays compact for the 512-device dry-runs; remat policy is
applied to the scanned body.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common import dtype_of, fold_rng
from repro.config import ModelConfig
from repro.models import layers as L
from repro.obs import scopes
from repro.parallel.ctx import constrain
from repro.serving import kvcache

# ---------------------------------------------------------------------------
# Remat policies
# ---------------------------------------------------------------------------


def remat_wrap(fn, remat: str):
    if remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    if remat == "dots":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False,
        )
    return fn


# ---------------------------------------------------------------------------
# One transformer block
# ---------------------------------------------------------------------------


def init_block(rng, cfg: ModelConfig) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    ks = jax.random.split(rng, 2)
    return {
        "attn_norm": L.init_rmsnorm(cfg.d_model, dtype),
        "attn": L.init_attention(ks[0], cfg),
        "mlp_norm": L.init_rmsnorm(cfg.d_model, dtype),
        "mlp": L.init_mlp(ks[1], cfg),
    }


def block_apply(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    cache: Optional[dict] = None,
    cache_index=None,
) -> tuple[jax.Array, Optional[dict]]:
    with jax.named_scope(scopes.ATTENTION):
        h, new_cache = L.attention_block(
            params["attn"],
            L.rmsnorm(params["attn_norm"], x, cfg.norm_eps),
            cfg,
            positions=positions,
            cache=cache,
            cache_index=cache_index,
        )
    x = x + h
    with jax.named_scope(scopes.MLP):
        xn = L.rmsnorm(params["mlp_norm"], x, cfg.norm_eps)
        h = L.mlp_block(params["mlp"], xn, cfg)
    return x + h, new_cache


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------


def init(rng, cfg: ModelConfig) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    layer_rngs = jax.random.split(fold_rng(rng, "layers"), cfg.num_layers)
    stacked = jax.vmap(lambda r: init_block(r, cfg))(layer_rngs)
    params = {
        "embed": L.init_embedding(fold_rng(rng, "embed"), cfg),
        "layers": stacked,
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if cfg.frontend == "vision_patches":
        params["connector"] = L.dense_init(
            fold_rng(rng, "connector"), (cfg.frontend_dim, cfg.d_model), dtype
        )
    if cfg.frontend == "audio_frames":
        params["in_proj"] = L.dense_init(
            fold_rng(rng, "in_proj"), (cfg.frontend_dim, cfg.d_model), dtype
        )
    return params


def _embed_inputs(params: dict, batch: dict, cfg: ModelConfig, pc=None) -> jax.Array:
    cdt = dtype_of(cfg.compute_dtype)
    if cfg.frontend == "audio_frames":
        return (batch["frames"].astype(cdt) @ params["in_proj"].astype(cdt))
    if cfg.frontend == "vision_patches":
        patches = batch["patches"].astype(cdt) @ params["connector"].astype(cdt)
        toks = L.embed(params["embed"], batch["tokens"], cfg, pc)
        patches = constrain(patches, pc, None, None,
                            pc.act_model_axis if pc and patches.shape[-1] % pc.model_size == 0
                            else None, batch_dim=0)
        return jnp.concatenate([patches, toks], axis=1)
    return L.embed(params["embed"], batch["tokens"], cfg, pc)


def forward(
    params: dict,
    batch: dict,
    cfg: ModelConfig,
    pc=None,
    *,
    remat: str = "none",
    return_cache: bool = False,
    kv_dtype=jnp.bfloat16,
):
    """Train / prefill forward. Returns logits (B, S, V); with return_cache also
    returns a stacked (L-leading) KV cache holding the prefilled keys/values."""
    x = _embed_inputs(params, batch, cfg, pc)
    x = constrain(x, pc, None, None,
                  pc.act_model_axis if pc and x.shape[-1] % pc.model_size == 0
                  else None, batch_dim=0)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def body(x, layer_params):
        y, _ = block_apply(layer_params, x, cfg, positions=positions)
        y = constrain(y, pc, None, None, None, batch_dim=0)
        if not return_cache:
            return y, None
        # re-project k/v for the cache (cheap relative to the block itself)
        cdt = dtype_of(cfg.compute_dtype)
        xin = L.rmsnorm(layer_params["attn_norm"], x, cfg.norm_eps).astype(cdt)
        kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        k = (xin @ layer_params["attn"]["wk"].astype(cdt)).reshape(b, s, kvh, hd)
        v = (xin @ layer_params["attn"]["wv"].astype(cdt)).reshape(b, s, kvh, hd)
        if cfg.use_qk_norm:
            k = L.rmsnorm(layer_params["attn"]["k_norm"], k, cfg.norm_eps)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        return y, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

    body = remat_wrap(body, remat)
    x, kv = jax.lax.scan(body, x, params["layers"],
                         unroll=cfg.num_layers if cfg.unroll_scans else 1)
    logits = L.lm_head(params, x, cfg)
    logits = constrain(logits, pc, None, None, pc.act_model_axis if pc else None,
                       batch_dim=0)
    if return_cache:
        ks, vs = kv  # (L, B, KV, S, hd)
        cache = {"k": ks.astype(kv_dtype), "v": vs.astype(kv_dtype)}
        return logits, cache
    return logits


def init_cache(cfg: ModelConfig, batch: int, max_len: int, kv_dtype="bfloat16") -> dict:
    one = kvcache.init_cache(
        batch, cfg.num_kv_heads, max_len, cfg.resolved_head_dim, kv_dtype
    )
    return jax.tree.map(
        lambda x: jnp.zeros((cfg.num_layers,) + x.shape, x.dtype), one
    )


def decode_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,
    cache_index: jax.Array,
    cfg: ModelConfig,
    pc=None,
) -> tuple[jax.Array, dict]:
    """One decode step. tokens: (B, 1). cache: stacked (L, ...) kv cache.
    Returns (logits (B, 1, V), new_cache)."""
    x = L.embed(params["embed"], tokens, cfg, pc)
    x = constrain(x, pc, None, None,
                  pc.act_model_axis if pc and x.shape[-1] % pc.model_size == 0
                  else None, batch_dim=0)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(
        cache_index + jnp.arange(s, dtype=jnp.int32), (b, s)
    ).astype(jnp.int32)

    def body(x, scanned):
        layer_params, layer_cache = scanned
        y, new_layer_cache = block_apply(
            layer_params,
            x,
            cfg,
            positions=positions,
            cache=layer_cache,
            cache_index=cache_index,
        )
        y = constrain(y, pc, None, None, None, batch_dim=0)
        return y, new_layer_cache

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache),
                                unroll=cfg.num_layers if cfg.unroll_scans else 1)
    logits = L.lm_head(params, x, cfg)
    logits = constrain(logits, pc, None, None, pc.act_model_axis if pc else None,
                       batch_dim=0)
    return logits, new_cache
