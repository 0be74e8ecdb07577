"""Family dispatcher + losses. The launcher, trainer and dry-run only talk to
this module."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import hybrid, moe, ssm, transformer
from repro.obs import scopes
from repro.parallel.ctx import ParallelCtx

_FAMS = {
    "dense": transformer,
    "vlm": transformer,
    "audio": transformer,
    "moe": moe,
    "ssm": ssm,
    "hybrid": hybrid,
}


def module_for(cfg: ModelConfig):
    return _FAMS[cfg.family]


def init(rng, cfg: ModelConfig):
    return module_for(cfg).init(rng, cfg)


def forward(
    params,
    batch: dict,
    cfg: ModelConfig,
    pc: Optional[ParallelCtx] = None,
    *,
    remat: str = "none",
):
    """Returns (logits, aux_loss_scalar)."""
    mod = module_for(cfg)
    if cfg.family == "moe":
        return mod.forward(params, batch, cfg, pc, remat=remat)
    return mod.forward(params, batch, cfg, pc, remat=remat), jnp.zeros(
        (), jnp.float32)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, kv_dtype="bfloat16"):
    if not cfg.decoder:
        raise ValueError(f"{cfg.name} is encoder-only: no decode cache")
    return module_for(cfg).init_cache(cfg, batch, max_len, kv_dtype)


def decode_step(
    params,
    cache,
    tokens,
    cache_index,
    cfg: ModelConfig,
    pc: Optional[ParallelCtx] = None,
):
    mod = module_for(cfg)
    return mod.decode_step(params, cache, tokens, cache_index, cfg, pc)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


@jax.named_scope(scopes.LOSS)
def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token CE. logits (B,S,V) any float dtype (reduction in fp32);
    labels (B,S) with -1 = ignore."""
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)


def loss_fn(
    params,
    batch: dict,
    cfg: ModelConfig,
    pc: Optional[ParallelCtx] = None,
    *,
    remat: str = "none",
):
    """Returns (loss, metrics dict). batch needs 'labels' (B,S)."""
    logits, aux = forward(params, batch, cfg, pc, remat=remat)
    ce = cross_entropy(logits, batch["labels"])
    loss = ce + (cfg.router_aux_coef * aux if cfg.family == "moe" else 0.0)
    return loss, {"ce": ce, "aux": aux, "loss": loss}
