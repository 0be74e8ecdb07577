"""Plain float32 reference of a Mamba2 language model (arXiv:2405.21060):
pre-norm residual blocks of the Mamba2 mixer, then a final RMSNorm and a
tied or separate output head.

The mixer: in-projections to z, x, B, C and dt; a depthwise causal
convolution with SiLU over x and over (B, C); the SSD recurrence
h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t, y_t = C_t h_t + D x_t, computed by
the paper's minimal chunked form ("ssd_minimal_discrete", Listing 1); then
RMSNorm(y * SiLU(z)) and the out-projection. The projections are the
program's split of Mamba2's in_proj (z, x, BC, dt), which computes the same
map.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from refs import common as C


def sizes(m: dict) -> dict:
    d_inner = m["ssm_expand"] * m["d_model"]
    return dict(d=m["d_model"], di=d_inner, h=d_inner // m["ssm_head_dim"],
                p=m["ssm_head_dim"], g=m["ssm_ngroups"], n=m["ssm_state"],
                w=m["ssm_conv_width"], L=m["num_layers"], v=m["vocab_size"])


def make_params(m: dict, key) -> dict:
    """Seeded weights in the program's parameter layout (float32)."""
    z = sizes(m)
    L, d, di, h, w, bc = z["L"], z["d"], z["di"], z["h"], z["w"], 2 * z["g"] * z["n"]
    u = jax.random.uniform(C.leaf_key(key, "dt"), (L, h))
    dt0 = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    p = {
        "embed": {"embedding": C.normal(key, "embed", (z["v"], d), 0.02)},
        "final_norm": {"scale": C.scale(key, "final_norm", (d,))},
        "layers": {
            "norm": {"scale": C.scale(key, "norm", (L, d))},
            "wz": C.matrix(key, "wz", L, d, di),
            "wx": C.matrix(key, "wx", L, d, di),
            "wbc": C.matrix(key, "wbc", L, d, bc),
            "wdt": C.matrix(key, "wdt", L, d, h),
            # softplus(dt_bias) spans [1e-3, 1e-1]; A = -exp(A_log), A_log
            # from log U(1, 16); D around 1 (Mamba2's initialisation)
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "A_log": jnp.log(jax.random.uniform(C.leaf_key(key, "A"), (L, h),
                                                minval=1.0, maxval=16.0)),
            "D": C.scale(key, "D", (L, h)),
            "conv_x_w": C.normal(key, "conv_x_w", (L, w, di), 1 / math.sqrt(w)),
            "conv_x_b": C.normal(key, "conv_x_b", (L, di), 0.02),
            "conv_bc_w": C.normal(key, "conv_bc_w", (L, w, bc), 1 / math.sqrt(w)),
            "conv_bc_b": C.normal(key, "conv_bc_b", (L, bc), 0.02),
            "gate_norm": {"scale": C.scale(key, "gate_norm", (L, di))},
            "wo": C.matrix(key, "ssm_wo", L, di, d),
        },
    }
    if not m.get("tie_embeddings"):
        p["embed"]["unembed"] = C.matrix(key, "unembed", 1, d, z["v"])[0]
    return p


def causal_conv(x, w, b):
    """Depthwise causal convolution: out_t = b + sum_i w_i x_{t-(W-1)+i}."""
    width, s = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return b + sum(w[i] * xp[i:i + s] for i in range(width))


def segsum(x):
    """x: (..., T) -> (..., T, T), out[i, j] = x[j+1] + ... + x[i] for i >= j,
    -inf above the diagonal (the paper's stable form)."""
    t = x.shape[-1]
    xx = jnp.broadcast_to(x[..., :, None], x.shape + (t,))  # xx[i, j] = x[i]
    xx = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), xx, 0.0)
    out = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def ssd(x, a, b, c, chunk: int, prec: str):
    """Minimal chunked SSD of one row. x: (S, H, P) already times dt;
    a: (S, H) = dt * A; b, c: (S, H, N). Returns y: (S, H, P)."""
    s, h, p = x.shape
    nc = s // chunk
    x = x.reshape(nc, chunk, h, p)
    b = b.reshape(nc, chunk, h, -1)
    c = c.reshape(nc, chunk, h, -1)
    a = a.reshape(nc, chunk, h).transpose(2, 0, 1)  # (H, nc, l)
    a_cum = jnp.cumsum(a, axis=-1)
    # 1. within each chunk (the quadratic, attention-like form)
    lmat = jnp.exp(segsum(a))  # (H, nc, l, l)
    scores = C.einsum("clhn,cshn->hcls", c, b, prec=prec) * lmat
    y_diag = C.einsum("hcls,cshp->clhp", scores, x, prec=prec)
    # 2. each chunk's final state, from its own inputs
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)  # (H, nc, l)
    states = C.einsum("clhn,hcl,clhp->chpn", b, decay_states, x, prec=prec)
    # 3. pass states across chunk boundaries
    states = jnp.concatenate([jnp.zeros_like(states[:1]), states])
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cum[..., -1], ((0, 0), (1, 0)))))
    states = jnp.einsum("hzc,chpn->zhpn", decay_chunk, states,
                        precision=C.HIGHEST)[:-1]
    # 4. each chunk's output from the state it starts with
    y_off = C.einsum("clhn,chpn,hcl->clhp", c, states, jnp.exp(a_cum), prec=prec)
    return (y_diag + y_off).reshape(s, h, p)


def mixer(x, p, m: dict, prec: str):
    """The Mamba2 mixer of one block on a single row x: (S, D)."""
    z = sizes(m)
    s, h, hp, g, n = x.shape[0], z["h"], z["p"], z["g"], z["n"]
    y = C.rmsnorm(x, p["norm"]["scale"], m["norm_eps"])
    gate = C.mm(y, p["wz"], prec)
    xs = jax.nn.silu(causal_conv(C.mm(y, p["wx"], prec), p["conv_x_w"], p["conv_x_b"]))
    bc = jax.nn.silu(causal_conv(C.mm(y, p["wbc"], prec), p["conv_bc_w"], p["conv_bc_b"]))
    dt = jax.nn.softplus(C.mm(y, p["wdt"], prec) + p["dt_bias"])  # (S, H)
    a = -jnp.exp(p["A_log"])
    xh = xs.reshape(s, h, hp)
    # group j's B and C serve heads j*h/g ... (j+1)*h/g - 1
    bm = jnp.repeat(bc[:, : g * n].reshape(s, g, n), h // g, axis=1)
    cm = jnp.repeat(bc[:, g * n:].reshape(s, g, n), h // g, axis=1)
    out = ssd(xh * dt[..., None], dt * a, bm, cm, m["ssm_chunk"], prec)
    out = out + p["D"][None, :, None] * xh
    out = C.rmsnorm(out.reshape(s, -1) * jax.nn.silu(gate), p["gate_norm"]["scale"],
                    m["norm_eps"])
    return C.mm(out, p["wo"], prec)


def hidden(params, tokens, m: dict, prec: str):
    """Final-normed hidden states of one row of tokens (S,) -> (S, D). The
    residual stream is float32 where ``residual_in_fp32`` holds; otherwise
    it is kept in bfloat16, as are each mixer's output and the embedding
    that start it."""
    keep = ((lambda v: v) if m.get("residual_in_fp32", True)
            else (lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)))
    x = keep(params["embed"]["embedding"][tokens])

    def block(x, p):
        return keep(x + keep(mixer(x, p, m, prec)))

    def body(x, p):
        return jax.checkpoint(block)(x, p), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return C.rmsnorm(x, params["final_norm"]["scale"], m["norm_eps"])


def head(params, x, m: dict, prec: str):
    w = (params["embed"]["embedding"].T if m.get("tie_embeddings")
         else params["embed"]["unembed"])
    return C.mm(x, w, prec)
