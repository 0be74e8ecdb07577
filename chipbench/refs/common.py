"""Pieces shared by the plain references: matmuls at a stated precision,
RMSNorm, the loss, AdamW, and the seeded weights.

Nothing here imports the program. The weights are made here, from the seed,
in the layout of the program's parameter tree, and handed to the program and
to the reference alike.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn

# Precisions: "f32", float32 products at full precision (the reference);
# "fp8", matmul inputs rounded to float8 e4m3 with a per-tensor scale,
# products accumulated in float32, gradients passed through unrounded (the
# control: the precision below the configurations' bf16 compute).


@jax.custom_vjp
def fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(F8).max)
    return (x / s).astype(F8).astype(jnp.float32) * s


fp8.defvjp(lambda x: (fp8(x), None), lambda _, g: (g,))


def rnd(x, prec: str):
    return fp8(x) if prec == "fp8" else x


def mm(a, b, prec: str):
    return jnp.matmul(rnd(a, prec), rnd(b, prec), precision=HIGHEST)


def einsum(spec: str, *xs, prec: str):
    return jnp.einsum(spec, *(rnd(x, prec) for x in xs), precision=HIGHEST)


def rmsnorm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def nll_sum(logits, labels):
    """Sum of token negative log-likelihoods over labels >= 0, and the count."""
    valid = labels >= 0
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    return jnp.sum((logz - gold) * valid), jnp.sum(valid)


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def normal(key, path: str, shape, std: float):
    return jax.random.normal(leaf_key(key, path), shape, jnp.float32) * std


def matrix(key, path: str, layers: int, fan_in: int, fan_out: int):
    return normal(key, path, (layers, fan_in, fan_out), 1.0 / math.sqrt(fan_in))


def scale(key, path: str, shape):
    return 1.0 + normal(key, path, shape, 0.1)


# ---------------------------------------------------------------------------
# leaves: each layer's slice of a stacked parameter is a leaf of its own
# ---------------------------------------------------------------------------


def leaves(tree) -> dict:
    """{name: array} with stacked ("layers") parameters split per layer."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if name.startswith("layers/"):
            for i in range(leaf.shape[0]):
                out[f"{name}#{i}"] = leaf[i]
        else:
            out[name] = leaf
    return out


def leaf_norms(tree) -> dict:
    """{leaf name: float32 L2 norm}, computed on the device in one call."""
    def norms(t):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in leaves(t).items()}
    return {k: float(v) for k, v in jax.jit(norms)(tree).items()}


# ---------------------------------------------------------------------------
# AdamW with global-norm clipping and linear warm-up
# ---------------------------------------------------------------------------


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"t": jnp.zeros((), jnp.int32), "mu": zeros,
            "nu": jax.tree.map(jnp.zeros_like, params)}


def adamw_step(params, grads, opt, hp: dict):
    """One AdamW step as the training configuration states it, inside its
    linear warm-up: decoupled weight decay on every parameter but the groups
    that ``no_weight_decay`` names. Returns (params, opt, the clipped
    gradients)."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * clip, grads)
    t = opt["t"] + 1
    tf = t.astype(jnp.float32)
    lr = hp["learning_rate"] * jnp.minimum(tf / max(hp["warmup_steps"], 1), 1.0)
    b1, b2 = hp["beta1"], hp["beta2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"], grads)
    c1, c2 = 1 - b1 ** tf, 1 - b2 ** tf

    def upd(path, p, m, v):
        u = (m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
        exempt = str(getattr(path[0], "key", path[0])) in hp["no_weight_decay"]
        wd = 0.0 if exempt else hp["weight_decay"] * p
        return p - lr * (u + wd)

    new = jax.tree_util.tree_map_with_path(upd, params, mu, nu)
    return new, {"t": t, "mu": mu, "nu": nu}, grads
