"""Mamba2 (SSD — state-space duality) blocks and the pure-SSM LM.

The SSD computation follows the chunked algorithm of arXiv:2405.21060: within a
chunk the dual quadratic form is used; across chunks a (B, H, P, N) state is
carried through ``lax.scan``. All per-head ops shard cleanly over the model
axis (heads / d_inner), so the layer introduces no collectives beyond the
input/output projections.

Projections are kept *separate* (wz / wx / wbc / wdt) rather than one fused
in_proj so each output dim shards on the model axis without resharding.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.common import dtype_of, fold_rng
from repro.config import ModelConfig
from repro.models import layers as L
from repro.obs import scopes
from repro.parallel.ctx import constrain

# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------


def segsum(x: jax.Array) -> jax.Array:
    """x: (..., Q) -> (..., Q, Q) with out[i, j] = sum_{l=j+1..i} x[l] (i >= j)."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, diff, -jnp.inf)


@jax.named_scope(scopes.SSD)
def ssd_chunked(
    x: jax.Array,  # (B, S, H, P)  already includes nothing; dt applied inside
    dt: jax.Array,  # (B, S, H) fp32 (post-softplus)
    A: jax.Array,  # (H,) negative
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    chunk: int,
    initial_state: Optional[jax.Array] = None,
    unroll: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    hg = h // g
    q = min(chunk, s)
    pad = (-s) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // q

    def to_chunks(t):  # (B, S, ...) -> (nc, B, Q, ...)
        return t.reshape((b, nc, q) + t.shape[2:]).swapaxes(0, 1)

    xc, dtc, Bc, Cc = map(to_chunks, (x, dt, Bm, Cm))

    def body(state, inputs):
        x_c, dt_c, b_c, c_c = inputs  # (B,Q,H,P) (B,Q,H) (B,Q,G,N) (B,Q,G,N)
        f32 = jnp.float32
        dA = dt_c.astype(f32) * A.astype(f32)  # (B,Q,H) <= 0
        lmat = jnp.exp(segsum(dA.transpose(0, 2, 1)))  # (B,H,Q,Q)
        xdt = x_c.astype(f32) * dt_c.astype(f32)[..., None]  # (B,Q,H,P)
        bg = jnp.repeat(b_c, hg, axis=2).astype(f32)  # (B,Q,H,N)
        cg = jnp.repeat(c_c, hg, axis=2).astype(f32)
        scores = jnp.einsum("bihn,bjhn->bhij", cg, bg) * lmat
        y = jnp.einsum("bhij,bjhp->bihp", scores, xdt)
        cs = jnp.cumsum(dA, axis=1)  # (B,Q,H)
        decay_in = jnp.exp(cs)  # (B,Q,H)
        y = y + jnp.einsum("bihn,bhpn->bihp", cg, state) * decay_in[..., None]
        tot = cs[:, -1, :]  # (B,H)
        decay_out = jnp.exp(tot[:, None, :] - cs)  # (B,Q,H)
        new_state = state * jnp.exp(tot)[..., None, None] + jnp.einsum(
            "bjhn,bjhp->bhpn", bg * decay_out[..., None], xdt
        )
        return new_state, y

    state0 = (
        initial_state.astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((b, h, p, n), jnp.float32)
    )
    final_state, ys = jax.lax.scan(body, state0, (xc, dtc, Bc, Cc),
                                   unroll=nc if unroll else 1)
    y = ys.swapaxes(0, 1).reshape(b, sp, h, p)[:, :s]
    return y.astype(x.dtype), final_state


@jax.named_scope(scopes.SSD)
def ssd_decode(x, dt, A, Bm, Cm, state):
    """Single-token recurrence. x: (B,1,H,P), dt: (B,1,H), B/C: (B,1,G,N),
    state: (B,H,P,N) fp32. Returns (y (B,1,H,P), new_state)."""
    b, _, h, p = x.shape
    g = Bm.shape[2]
    hg = h // g
    f32 = jnp.float32
    dA = jnp.exp(dt[:, 0].astype(f32) * A.astype(f32))  # (B,H)
    bg = jnp.repeat(Bm[:, 0], hg, axis=1).astype(f32)  # (B,H,N)
    cg = jnp.repeat(Cm[:, 0], hg, axis=1).astype(f32)
    xdt = x[:, 0].astype(f32) * dt[:, 0].astype(f32)[..., None]  # (B,H,P)
    new_state = state * dA[..., None, None] + jnp.einsum("bhp,bhn->bhpn", xdt, bg)
    y = jnp.einsum("bhpn,bhn->bhp", new_state, cg)
    return y[:, None].astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Mamba2 mixer layer
# ---------------------------------------------------------------------------


def init_mamba_block(rng, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_inner = cfg.ssm_d_inner
    h = cfg.ssm_nheads
    g, n, w = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_conv_width
    dtype = dtype_of(cfg.param_dtype)
    ks = jax.random.split(rng, 6)
    # dt bias init so softplus(dt_bias) spans ~[1e-3, 1e-1] (mamba2 default)
    u = jax.random.uniform(ks[4], (h,), jnp.float32)
    dt0 = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))  # inverse softplus
    bc = 2 * g * n
    # the depthwise conv is split into x / BC parts so the d_inner channels
    # shard over the model axis without a concat across shard boundaries
    return {
        "norm": L.init_rmsnorm(d, dtype),
        "wz": L.dense_init(ks[0], (d, d_inner), dtype),
        "wx": L.dense_init(ks[1], (d, d_inner), dtype),
        "wbc": L.dense_init(ks[2], (d, bc), dtype),
        "wdt": L.dense_init(ks[3], (d, h), dtype),
        "dt_bias": dt_bias,
        "A_log": jnp.log(
            jax.random.uniform(ks[5], (h,), jnp.float32, minval=1.0, maxval=16.0)
        ),
        "D": jnp.ones((h,), jnp.float32),
        "conv_x_w": (jax.random.normal(fold_rng(rng, "convx"), (w, d_inner),
                                       jnp.float32) / math.sqrt(w)).astype(dtype),
        "conv_x_b": jnp.zeros((d_inner,), dtype),
        "conv_bc_w": (jax.random.normal(fold_rng(rng, "convbc"), (w, bc),
                                        jnp.float32) / math.sqrt(w)).astype(dtype),
        "conv_bc_b": jnp.zeros((bc,), dtype),
        "gate_norm": L.init_rmsnorm(d_inner, dtype),
        "wo": L.dense_init(fold_rng(rng, "wo"), (d_inner, d), dtype, fan_in=d_inner),
    }


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B,S,C), w: (W,C)."""
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    s = x.shape[1]
    out = jnp.zeros_like(x)
    for i in range(width):  # width is 4: unrolled adds, no gather
        out = out + pad[:, i : i + s, :] * w[i]
    return out + b


def init_ssm_cache(cfg: ModelConfig, batch: int) -> dict:
    w = cfg.ssm_conv_width - 1
    cdt = dtype_of(cfg.compute_dtype)
    return {
        "state": jnp.zeros(
            (batch, cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32
        ),
        "conv_x": jnp.zeros((batch, w, cfg.ssm_d_inner), cdt),
        "conv_bc": jnp.zeros((batch, w, 2 * cfg.ssm_ngroups * cfg.ssm_state), cdt),
    }


@jax.named_scope(scopes.MIXER)
def mamba_mixer(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    cache: Optional[dict] = None,
) -> tuple[jax.Array, Optional[dict]]:
    """x: (B,S,D) -> (B,S,D). With cache, S must be 1 (decode)."""
    b, s, d = x.shape
    cdt = dtype_of(cfg.compute_dtype)
    d_inner, h, p = cfg.ssm_d_inner, cfg.ssm_nheads, cfg.ssm_head_dim
    g, n, w = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_conv_width

    xn = L.rmsnorm(params["norm"], x, cfg.norm_eps).astype(cdt)
    z = xn @ params["wz"].astype(cdt)
    xc = xn @ params["wx"].astype(cdt)
    bc = xn @ params["wbc"].astype(cdt)
    dt = jax.nn.softplus(
        (xn @ params["wdt"].astype(cdt)).astype(jnp.float32) + params["dt_bias"]
    )  # (B,S,H)

    new_cache = None
    if cache is None:
        conv_x = causal_conv(xc, params["conv_x_w"].astype(cdt),
                             params["conv_x_b"].astype(cdt))
        conv_bc = causal_conv(bc, params["conv_bc_w"].astype(cdt),
                              params["conv_bc_b"].astype(cdt))
    else:
        win_x = jnp.concatenate([cache["conv_x"].astype(cdt), xc], axis=1)
        win_bc = jnp.concatenate([cache["conv_bc"].astype(cdt), bc], axis=1)
        conv_x = (
            jnp.einsum("bwc,wc->bc", win_x, params["conv_x_w"].astype(cdt))
            + params["conv_x_b"].astype(cdt)
        )[:, None]
        conv_bc = (
            jnp.einsum("bwc,wc->bc", win_bc, params["conv_bc_w"].astype(cdt))
            + params["conv_bc_b"].astype(cdt)
        )[:, None]
        new_conv_x, new_conv_bc = win_x[:, 1:], win_bc[:, 1:]
    conv_x = jax.nn.silu(conv_x)
    conv_bc = jax.nn.silu(conv_bc)

    xs = conv_x.reshape(b, s, h, p)
    bmat = conv_bc[..., : g * n].reshape(b, s, g, n)
    cmat = conv_bc[..., g * n :].reshape(b, s, g, n)
    A = -jnp.exp(params["A_log"])

    if cache is None:
        y, _ = ssd_chunked(xs, dt, A, bmat, cmat, cfg.ssm_chunk,
                           unroll=cfg.unroll_scans)
    else:
        y, new_state = ssd_decode(xs, dt, A, bmat, cmat, cache["state"])
        new_cache = {"state": new_state, "conv_x": new_conv_x, "conv_bc": new_conv_bc}

    y = y + params["D"].astype(cdt)[None, None, :, None] * xs
    y = y.reshape(b, s, d_inner)
    y = L.rmsnorm(params["gate_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ params["wo"].astype(cdt)
    return out.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# Pure-SSM LM (mamba2-370m)
# ---------------------------------------------------------------------------


def init(rng, cfg: ModelConfig) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    layer_rngs = jax.random.split(fold_rng(rng, "layers"), cfg.num_layers)
    stacked = jax.vmap(lambda r: init_mamba_block(r, cfg))(layer_rngs)
    return {
        "embed": L.init_embedding(fold_rng(rng, "embed"), cfg),
        "layers": stacked,
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }


def forward(params, batch, cfg: ModelConfig, pc=None, *, remat: str = "none"):
    from repro.models.transformer import remat_wrap

    x = L.embed(params["embed"], batch["tokens"], cfg, pc)
    x = constrain(x, pc, None, None,
                  pc.act_model_axis if pc and x.shape[-1] % pc.model_size == 0
                  else None, batch_dim=0)

    def body(x, layer_params):
        y, _ = mamba_mixer(layer_params, x, cfg)
        y = constrain(x + y, pc, None, None, None, batch_dim=0)
        return y, None

    body = remat_wrap(body, remat)
    x, _ = jax.lax.scan(body, x, params["layers"],
                        unroll=cfg.num_layers if cfg.unroll_scans else 1)
    logits = L.lm_head(params, x, cfg)
    return constrain(logits, pc, None, None, pc.act_model_axis if pc else None,
                     batch_dim=0)


def init_cache(cfg: ModelConfig, batch: int, max_len: int = 0, kv_dtype="bfloat16"):
    one = init_ssm_cache(cfg, batch)
    return jax.tree.map(lambda x: jnp.zeros((cfg.num_layers,) + x.shape, x.dtype), one)


def decode_step(params, cache, tokens, cache_index, cfg: ModelConfig, pc=None):
    x = L.embed(params["embed"], tokens, cfg, pc)
    x = constrain(x, pc, None, None,
                  pc.act_model_axis if pc and x.shape[-1] % pc.model_size == 0
                  else None, batch_dim=0)

    def body(x, scanned):
        layer_params, layer_cache = scanned
        y, new_cache = mamba_mixer(layer_params, x, cfg, cache=layer_cache)
        y = constrain(x + y, pc, None, None, None, batch_dim=0)
        return y, new_cache

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache),
                                unroll=cfg.num_layers if cfg.unroll_scans else 1)
    logits = L.lm_head(params, x, cfg)
    logits = constrain(logits, pc, None, None, pc.act_model_axis if pc else None,
                       batch_dim=0)
    return logits, new_cache
