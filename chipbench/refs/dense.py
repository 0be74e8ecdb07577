"""Plain float32 reference of a dense decoder (Qwen3): pre-norm blocks with
GQA attention, RMSNorm on queries and keys, rotary positions (rotate-half),
SwiGLU MLP, tied or separate output head.

Written from the Qwen3 description, not from the program. Each layer is
checkpointed, so gradients of a 4096-token row fit beside the weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from refs import common as C


def make_params(m: dict, key) -> dict:
    """Seeded weights in the program's parameter layout (float32)."""
    L, d, h, kv = m["num_layers"], m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd, f, v = m["head_dim"], m["d_ff"], m["vocab_size"]
    p = {
        "embed": {"embedding": C.normal(key, "embed", (v, d), 0.02)},
        "final_norm": {"scale": C.scale(key, "final_norm", (d,))},
        "layers": {
            "attn_norm": {"scale": C.scale(key, "attn_norm", (L, d))},
            "mlp_norm": {"scale": C.scale(key, "mlp_norm", (L, d))},
            "attn": {
                "wq": C.matrix(key, "wq", L, d, h * hd),
                "wk": C.matrix(key, "wk", L, d, kv * hd),
                "wv": C.matrix(key, "wv", L, d, kv * hd),
                "wo": C.matrix(key, "wo", L, h * hd, d),
            },
            "mlp": {
                "wi_gate": C.matrix(key, "wi_gate", L, d, f),
                "wi_up": C.matrix(key, "wi_up", L, d, f),
                "wo": C.matrix(key, "mlp_wo", L, f, d),
            },
        },
    }
    if m.get("use_qk_norm"):
        p["layers"]["attn"]["q_norm"] = {"scale": C.scale(key, "q_norm", (L, hd))}
        p["layers"]["attn"]["k_norm"] = {"scale": C.scale(key, "k_norm", (L, hd))}
    if not m.get("tie_embeddings"):
        p["embed"]["unembed"] = C.matrix(key, "unembed", 1, d, v)[0]
    return p


def rope(x, theta: float):
    """x: (S, H, hd); position i rotates pair (j, j + hd/2) by i / theta^(2j/hd)."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def block(x, p, m: dict, prec: str):
    """One decoder layer on a single row x: (S, D)."""
    s = x.shape[0]
    h, kv, hd, eps = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["norm_eps"]
    a = p["attn"]
    y = C.rmsnorm(x, p["attn_norm"]["scale"], eps)
    q = C.mm(y, a["wq"], prec).reshape(s, h, hd)
    k = C.mm(y, a["wk"], prec).reshape(s, kv, hd)
    v = C.mm(y, a["wv"], prec).reshape(s, kv, hd)
    if m.get("use_qk_norm"):
        q = C.rmsnorm(q, a["q_norm"]["scale"], eps)
        k = C.rmsnorm(k, a["k_norm"]["scale"], eps)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    # query head i reads key/value head i // (h / kv)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    scores = C.einsum("shd,thd->hst", q, k, prec=prec) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = C.einsum("hst,thd->shd", probs, v, prec=prec).reshape(s, h * hd)
    x = x + C.mm(o, a["wo"], prec)
    y = C.rmsnorm(x, p["mlp_norm"]["scale"], eps)
    mp = p["mlp"]
    act = jax.nn.silu(C.mm(y, mp["wi_gate"], prec)) * C.mm(y, mp["wi_up"], prec)
    return x + C.mm(act, mp["wo"], prec)


def hidden(params, tokens, m: dict, prec: str):
    """Final-normed hidden states of one row of tokens (S,) -> (S, D)."""
    x = params["embed"]["embedding"][tokens]

    def body(x, p):
        return jax.checkpoint(block, static_argnums=(2, 3))(x, p, m, prec), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return C.rmsnorm(x, params["final_norm"]["scale"], m["norm_eps"])


def head(params, x, m: dict, prec: str):
    w = (params["embed"]["embedding"].T if m.get("tie_embeddings")
         else params["embed"]["unembed"])
    return C.mm(x, w, prec)
