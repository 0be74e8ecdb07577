"""Zamba2-style hybrid: a Mamba2 backbone with a *shared* transformer block
(attention + MLP, one set of weights) applied every ``hybrid_attn_every``
layers. The shared block's KV caches are per-application (stacked over group),
the weights are not — that is Zamba2's parameter-sharing trick.

Layout: num_layers = n_groups * per + tail, all Mamba2 layers; the shared
attention block fires after each group. (Zamba2's per-application LoRA deltas
on the shared block are omitted; noted in DESIGN.md.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import dtype_of, fold_rng
from repro.config import ModelConfig
from repro.models import layers as L
from repro.models import ssm
from repro.models import transformer as T
from repro.parallel.ctx import constrain
from repro.serving import kvcache


def _plan(cfg: ModelConfig) -> tuple[int, int, int]:
    per = cfg.hybrid_attn_every
    n_groups = cfg.num_layers // per
    tail = cfg.num_layers - n_groups * per
    return n_groups, per, tail


def init(rng, cfg: ModelConfig) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    n_groups, per, tail = _plan(cfg)
    g_rngs = jax.random.split(fold_rng(rng, "groups"), n_groups * per).reshape(
        n_groups, per, 2
    )
    stacked = jax.vmap(jax.vmap(lambda r: ssm.init_mamba_block(r, cfg)))(g_rngs)
    params = {
        "embed": L.init_embedding(fold_rng(rng, "embed"), cfg),
        "groups": stacked,  # (n_groups, per, ...)
        "shared": T.init_block(fold_rng(rng, "shared"), cfg),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if tail:
        t_rngs = jax.random.split(fold_rng(rng, "tail"), tail)
        params["tail"] = jax.vmap(lambda r: ssm.init_mamba_block(r, cfg))(t_rngs)
    return params


def _group_apply(group_params, shared, x, cfg, positions, pc=None):
    def inner(x, lp):
        y, _ = ssm.mamba_mixer(lp, x, cfg)
        y = constrain(x + y, pc, None, None, None, batch_dim=0)
        return y, None

    x, _ = jax.lax.scan(inner, x, group_params,
                        unroll=cfg.hybrid_attn_every if cfg.unroll_scans else 1)
    x, _ = T.block_apply(shared, x, cfg, positions=positions)
    return constrain(x, pc, None, None, None, batch_dim=0)


def forward(params, batch, cfg: ModelConfig, pc=None, *, remat: str = "none"):
    x = L.embed(params["embed"], batch["tokens"], cfg, pc)
    x = constrain(x, pc, None, None,
                  pc.act_model_axis if pc and x.shape[-1] % pc.model_size == 0
                  else None, batch_dim=0)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    shared = params["shared"]

    def body(x, group_params):
        return _group_apply(group_params, shared, x, cfg, positions, pc), None

    body = T.remat_wrap(body, remat)
    n_groups, _, tail = _plan(cfg)
    x, _ = jax.lax.scan(body, x, params["groups"],
                        unroll=n_groups if cfg.unroll_scans else 1)
    if "tail" in params:
        def inner(x, lp):
            y, _ = ssm.mamba_mixer(lp, x, cfg)
            return x + y, None
        x, _ = jax.lax.scan(inner, x, params["tail"],
                            unroll=tail if cfg.unroll_scans else 1)
    logits = L.lm_head(params, x, cfg)
    return constrain(logits, pc, None, None, pc.act_model_axis if pc else None,
                     batch_dim=0)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, kv_dtype="bfloat16"):
    n_groups, per, tail = _plan(cfg)
    ssm_one = ssm.init_ssm_cache(cfg, batch)
    kv_one = kvcache.init_cache(
        batch, cfg.num_kv_heads, max_len, cfg.resolved_head_dim, kv_dtype
    )
    cache = {
        "groups_ssm": jax.tree.map(
            lambda x: jnp.zeros((n_groups, per) + x.shape, x.dtype), ssm_one
        ),
        "attn": jax.tree.map(
            lambda x: jnp.zeros((n_groups,) + x.shape, x.dtype), kv_one
        ),
    }
    if tail:
        cache["tail_ssm"] = jax.tree.map(
            lambda x: jnp.zeros((tail,) + x.shape, x.dtype), ssm_one
        )
    return cache


def decode_step(params, cache, tokens, cache_index, cfg: ModelConfig, pc=None):
    x = L.embed(params["embed"], tokens, cfg, pc)
    x = constrain(x, pc, None, None,
                  pc.act_model_axis if pc and x.shape[-1] % pc.model_size == 0
                  else None, batch_dim=0)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(
        cache_index + jnp.arange(s, dtype=jnp.int32), (b, s)
    ).astype(jnp.int32)
    shared = params["shared"]

    def group_body(x, scanned):
        gp, g_ssm_cache, g_kv_cache = scanned

        def inner(x, sc):
            lp, lc = sc
            y, nc = ssm.mamba_mixer(lp, x, cfg, cache=lc)
            return x + y, nc

        x, new_ssm = jax.lax.scan(inner, x, (gp, g_ssm_cache),
                                  unroll=cfg.hybrid_attn_every if cfg.unroll_scans else 1)
        x, new_kv = T.block_apply(
            shared, x, cfg, positions=positions, cache=g_kv_cache,
            cache_index=cache_index,
        )
        return x, (new_ssm, new_kv)

    n_groups, _, tail = _plan(cfg)
    x, (new_groups_ssm, new_attn) = jax.lax.scan(
        group_body, x, (params["groups"], cache["groups_ssm"], cache["attn"]),
        unroll=n_groups if cfg.unroll_scans else 1,
    )
    new_cache = {"groups_ssm": new_groups_ssm, "attn": new_attn}
    if "tail" in params:
        def inner(x, sc):
            lp, lc = sc
            y, nc = ssm.mamba_mixer(lp, x, cfg, cache=lc)
            return x + y, nc
        x, new_tail = jax.lax.scan(inner, x, (params["tail"], cache["tail_ssm"]),
                                   unroll=tail if cfg.unroll_scans else 1)
        new_cache["tail_ssm"] = new_tail
    logits = L.lm_head(params, x, cfg)
    logits = constrain(logits, pc, None, None, pc.act_model_axis if pc else None,
                       batch_dim=0)
    return logits, new_cache
