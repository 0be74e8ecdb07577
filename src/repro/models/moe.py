"""Mixture-of-Experts transformer with explicit expert parallelism.

Two sharding schemes, chosen per-architecture:
  * EP  (num_experts %% model_axis == 0): experts sharded over the model axis,
    replicated routing, local dispatch buffers, psum-combine. One psum per MoE
    layer — same collective count as a Megatron TP MLP.
  * TPE (otherwise, e.g. qwen2-moe's 60 experts on a 16-way axis): every shard
    holds all experts with the per-expert hidden dim sharded over the model
    axis; combine is the standard TP psum.

FSDP: expert weights are additionally sharded over the data axis and gathered
(all-gather, tiled) inside the shard_map body right before use — weights this
size (qwen3-moe: 227B in experts) do not fit a chip otherwise.

The single-device reference path (no ParallelCtx) uses the same dispatch math
with all experts local — tests assert the sharded and reference paths agree.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common import dtype_of, fold_rng, round_up
from repro.config import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.parallel.ctx import ParallelCtx, constrain

# ---------------------------------------------------------------------------
# Routing + dispatch (pure math, shared by sharded + reference paths)
# ---------------------------------------------------------------------------


def capacity_for(n_tokens: int, cfg: ModelConfig) -> int:
    assignments = n_tokens * cfg.num_experts_per_tok
    if assignments <= 8192:
        return assignments  # decode / tiny batches: never drop
    c = math.ceil(assignments * cfg.capacity_factor / cfg.num_experts)
    return round_up(max(c, 8), 8)


def route(x2d: jax.Array, wr: jax.Array, cfg: ModelConfig):
    """Returns (top_w (N,k) fp32, top_e (N,k) int32, aux_loss scalar)."""
    logits = x2d.astype(jnp.float32) @ wr.astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss
    e = cfg.num_experts
    assign = jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32)  # primary expert
    f = jnp.mean(assign, axis=0)
    p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * p)
    return top_w, top_e, aux


def dispatch(x2d, top_e, num_experts: int, capacity: int, e_start: int, e_count: int):
    """Scatter tokens into per-expert capacity buckets.

    Returns (buf (e_count, C, D), dest (N*k,), keep (N*k,) bool).
    ``dest`` indexes the *flattened* local buffer; dropped / remote assignments
    point at the overflow row.
    """
    n, k = top_e.shape
    d = x2d.shape[-1]
    flat_e = top_e.reshape(-1)  # (N*k,), token-major
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)  # (N*k,)
    keep = (flat_e >= e_start) & (flat_e < e_start + e_count) & (pos < capacity)
    dest = jnp.where(keep, (flat_e - e_start) * capacity + pos, e_count * capacity)
    token_idx = jnp.repeat(jnp.arange(n), k)
    buf = jnp.zeros((e_count * capacity + 1, d), x2d.dtype)
    buf = buf.at[dest].set(x2d[token_idx], mode="drop")
    return buf[:-1].reshape(e_count, capacity, d), dest, keep


def combine(y_buf, dest, keep, top_w, n: int, k: int):
    """Gather expert outputs back per assignment and weighted-sum over k slots."""
    d = y_buf.shape[-1]
    flat = jnp.concatenate([y_buf.reshape(-1, d), jnp.zeros((1, d), y_buf.dtype)])
    y_assign = flat[dest]  # overflow row is zeros
    w = (top_w.reshape(-1) * keep.astype(jnp.float32))[:, None]
    out = jnp.sum((y_assign.astype(jnp.float32) * w).reshape(n, k, d), axis=1)
    return out


def expert_ffn(buf, wg, wu, wo, cfg: ModelConfig):
    """buf: (E_loc, C, D); weights (E_loc, D, F)/(E_loc, F, D)."""
    cdt = dtype_of(cfg.compute_dtype)
    x = buf.astype(cdt)
    gate = jnp.einsum("ecd,edf->ecf", x, wg.astype(cdt))
    up = jnp.einsum("ecd,edf->ecf", x, wu.astype(cdt))
    act = jax.nn.silu(gate) if cfg.act == "silu" else jax.nn.gelu(gate)
    return jnp.einsum("ecf,efd->ecd", act * up, wo.astype(cdt))


# ---------------------------------------------------------------------------
# The MoE FFN layer (sharded + reference)
# ---------------------------------------------------------------------------


def ep_scheme(cfg: ModelConfig, pc: Optional[ParallelCtx]) -> str:
    if pc is None or pc.model_size == 1:
        return "ref"
    if cfg.num_experts % pc.model_size == 0:
        return "ep"
    f = cfg.moe_d_ff or cfg.d_ff
    if f % pc.model_size == 0:
        return "tpe"
    return "ref"


def _moe_ffn_local(x3d, wr, wg, wu, wo, cfg, e_start, e_count, axis_name=None):
    """Per-shard MoE ffn on local tokens. x3d: (Bl, S, D)."""
    bl, s, d = x3d.shape
    n = bl * s
    x2d = x3d.reshape(n, d)
    top_w, top_e, aux = route(x2d, wr, cfg)
    cap = capacity_for(n, cfg)
    buf, dest, keep = dispatch(x2d, top_e, cfg.num_experts, cap, e_start, e_count)
    y_buf = expert_ffn(buf, wg, wu, wo, cfg)
    out = combine(y_buf, dest, keep, top_w, n, cfg.num_experts_per_tok)
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    return out.reshape(bl, s, d).astype(x3d.dtype), aux


def moe_ffn(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    pc: Optional[ParallelCtx],
) -> tuple[jax.Array, jax.Array]:
    """Routed-experts FFN. Returns (out (B,S,D), aux loss scalar)."""
    scheme = ep_scheme(cfg, pc)
    wr = params["router"]
    wg, wu, wo = params["wg"], params["wu"], params["wo"]

    if scheme == "ref":
        out, aux = _moe_ffn_local(x, wr, wg, wu, wo, cfg, 0, cfg.num_experts)
        return out, aux

    m_ax, d_ax = pc.model_axis, pc.data_axis
    msize = pc.model_size
    fsdp = pc.fsdp_params
    bspec = pc.batch_axes if len(pc.batch_axes) > 1 else pc.batch_axes[0]

    if scheme == "ep":
        e_count = cfg.num_experts // msize
        w_spec = P(m_ax, None, "data") if fsdp else P(m_ax, None, None)
        wo_spec = P(m_ax, "data", None) if fsdp else P(m_ax, None, None)

        def body(x3d, wr_, wg_, wu_, wo_):
            if fsdp:
                wg_ = jax.lax.all_gather(wg_, d_ax, axis=2, tiled=True)
                wu_ = jax.lax.all_gather(wu_, d_ax, axis=2, tiled=True)
                wo_ = jax.lax.all_gather(wo_, d_ax, axis=1, tiled=True)
            shard = jax.lax.axis_index(m_ax)
            e_start = shard * e_count
            out, aux = _moe_ffn_local(
                x3d, wr_, wg_, wu_, wo_, cfg, e_start, e_count, axis_name=m_ax
            )
            aux = jax.lax.pmean(aux, pc.all_axes)
            return out, aux

        out, aux = jax.shard_map(
            body,
            mesh=pc.mesh,
            in_specs=(P(bspec, None, None), P(None, None), w_spec, w_spec, wo_spec),
            out_specs=(P(bspec, None, None), P()),
            check_vma=False,
        )(x, wr, wg, wu, wo)
        return out, aux

    # TPE: hidden dim sharded over the model axis, all experts on every shard.
    w_spec = P(None, "data", m_ax) if fsdp else P(None, None, m_ax)
    wo_spec = P(None, m_ax, "data") if fsdp else P(None, m_ax, None)

    def body(x3d, wr_, wg_, wu_, wo_):
        if fsdp:
            wg_ = jax.lax.all_gather(wg_, d_ax, axis=1, tiled=True)
            wu_ = jax.lax.all_gather(wu_, d_ax, axis=1, tiled=True)
            wo_ = jax.lax.all_gather(wo_, d_ax, axis=2, tiled=True)
        out, aux = _moe_ffn_local(
            x3d, wr_, wg_, wu_, wo_, cfg, 0, cfg.num_experts, axis_name=m_ax
        )
        aux = jax.lax.pmean(aux, pc.all_axes)
        return out, aux

    out, aux = jax.shard_map(
        body,
        mesh=pc.mesh,
        in_specs=(P(bspec, None, None), P(None, None), w_spec, w_spec, wo_spec),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(x, wr, wg, wu, wo)
    return out, aux


# ---------------------------------------------------------------------------
# Blocks / model
# ---------------------------------------------------------------------------


def init_moe_ffn(rng, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    e = cfg.num_experts
    dtype = dtype_of(cfg.param_dtype)
    ks = jax.random.split(rng, 5)
    p = {
        "router": L.dense_init(ks[0], (d, e), jnp.float32),
        "wg": L.dense_init(ks[1], (e, d, f), dtype, fan_in=d),
        "wu": L.dense_init(ks[2], (e, d, f), dtype, fan_in=d),
        "wo": L.dense_init(ks[3], (e, f, d), dtype, fan_in=f),
    }
    if cfg.num_shared_experts:
        p["shared"] = L.init_mlp(ks[4], cfg, d_ff=cfg.num_shared_experts * f)
        p["shared_gate"] = L.dense_init(fold_rng(rng, "sg"), (d, 1), jnp.float32)
    return p


def init_block(rng, cfg: ModelConfig) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    ks = jax.random.split(rng, 2)
    return {
        "attn_norm": L.init_rmsnorm(cfg.d_model, dtype),
        "attn": L.init_attention(ks[0], cfg),
        "mlp_norm": L.init_rmsnorm(cfg.d_model, dtype),
        "moe": init_moe_ffn(ks[1], cfg),
    }


def block_apply(
    params,
    x,
    cfg: ModelConfig,
    pc: Optional[ParallelCtx],
    *,
    positions,
    cache=None,
    cache_index=None,
):
    h, new_cache = L.attention_block(
        params["attn"],
        L.rmsnorm(params["attn_norm"], x, cfg.norm_eps),
        cfg,
        positions=positions,
        cache=cache,
        cache_index=cache_index,
    )
    x = x + h
    xin = L.rmsnorm(params["mlp_norm"], x, cfg.norm_eps)
    ff, aux = moe_ffn(params["moe"], xin, cfg, pc)
    if cfg.num_shared_experts:
        gate = jax.nn.sigmoid(
            xin.astype(jnp.float32) @ params["moe"]["shared_gate"]
        ).astype(x.dtype)
        ff = ff + gate * L.mlp_block(params["moe"]["shared"], xin, cfg)
    x = x + ff
    return x, new_cache, aux


def init(rng, cfg: ModelConfig) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    layer_rngs = jax.random.split(fold_rng(rng, "layers"), cfg.num_layers)
    stacked = jax.vmap(lambda r: init_block(r, cfg))(layer_rngs)
    return {
        "embed": L.init_embedding(fold_rng(rng, "embed"), cfg),
        "layers": stacked,
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }


def forward(
    params,
    batch,
    cfg: ModelConfig,
    pc: Optional[ParallelCtx] = None,
    *,
    remat: str = "none",
):
    """Returns (logits, aux_loss)."""
    x = L.embed(params["embed"], batch["tokens"], cfg, pc)
    x = constrain(x, pc, None, None,
                  pc.act_model_axis if pc and x.shape[-1] % pc.model_size == 0
                  else None, batch_dim=0)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def body(carry, layer_params):
        x, aux_sum = carry
        y, _, aux = block_apply(layer_params, x, cfg, pc, positions=positions)
        y = constrain(y, pc, None, None, None, batch_dim=0)
        return (y, aux_sum + aux), None

    body = T.remat_wrap(body, remat)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["layers"],
                               unroll=cfg.num_layers if cfg.unroll_scans else 1)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg)
    logits = constrain(logits, pc, None, None, pc.act_model_axis if pc else None,
                       batch_dim=0)
    return logits, aux / cfg.num_layers


def init_cache(cfg: ModelConfig, batch: int, max_len: int, kv_dtype="bfloat16"):
    return T.init_cache(cfg, batch, max_len, kv_dtype)


def decode_step(params, cache, tokens, cache_index, cfg: ModelConfig, pc=None):
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
        y, new_cache, _ = block_apply(
            layer_params,
            x,
            cfg,
            pc,
            positions=positions,
            cache=layer_cache,
            cache_index=cache_index,
        )
        return y, new_cache

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache),
                                unroll=cfg.num_layers if cfg.unroll_scans else 1)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg)
    logits = constrain(logits, pc, None, None, pc.act_model_axis if pc else None,
                       batch_dim=0)
    return logits, new_cache
