"""Optimizers in pure JAX (no optax dependency): AdamW, Adafactor (factored
second moments — the memory-frugal choice at 100B+ scale), RMSProp (the paper's
proxy-task optimizer) and SGD+momentum. Plus warmup+cosine LR schedule and
global-norm clipping.

API:
    opt = make_optimizer(train_cfg)
    state = opt.init(params)
    params, state, metrics = opt.step(params, grads, state)
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.common import global_norm
from repro.config import TrainConfig
from repro.obs import scopes


class Optimizer(NamedTuple):
    init: Callable
    step: Callable


def lr_schedule(cfg: TrainConfig):
    def f(step):
        step = step.astype(jnp.float32)
        warm = cfg.learning_rate * step / jnp.maximum(cfg.warmup_steps, 1)
        prog = (step - cfg.warmup_steps) / jnp.maximum(
            cfg.total_steps - cfg.warmup_steps, 1
        )
        prog = jnp.clip(prog, 0.0, 1.0)
        cos = cfg.learning_rate * 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
        return jnp.where(step < cfg.warmup_steps, warm, cos)

    return f


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), norm


def _is_matrix(x) -> bool:
    return x.ndim >= 2 and x.shape[-1] >= 128 and x.shape[-2] >= 128


def make_optimizer(cfg: TrainConfig) -> Optimizer:
    """The optimizer ``cfg.optimizer`` names; its whole step, clipping
    included, runs in the ``optimizer`` scope."""
    opt = _optimizer(cfg)

    def step(params, grads, state):
        with jax.named_scope(scopes.OPTIMIZER):
            return opt.step(params, grads, state)

    return Optimizer(opt.init, step)


def _optimizer(cfg: TrainConfig) -> Optimizer:
    sched = lr_schedule(cfg)

    if cfg.optimizer == "adamw":

        def init(params):
            return {
                "step": jnp.zeros((), jnp.int32),
                "mu": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
                "nu": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            }

        def step(params, grads, state):
            grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
            t = state["step"] + 1
            lr = sched(t)
            b1, b2 = cfg.beta1, cfg.beta2
            mu = jax.tree.map(
                lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                state["mu"], grads)
            nu = jax.tree.map(
                lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
                state["nu"], grads)
            bc1 = 1 - b1 ** t.astype(jnp.float32)
            bc2 = 1 - b2 ** t.astype(jnp.float32)

            def upd(p, m, v):
                u = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
                wd = cfg.weight_decay * p.astype(jnp.float32) if p.ndim >= 2 else 0.0
                return (p.astype(jnp.float32) - lr * (u + wd)).astype(p.dtype)

            new_params = jax.tree.map(upd, params, mu, nu)
            return new_params, {"step": t, "mu": mu, "nu": nu}, {
                "grad_norm": gnorm, "lr": lr}

        return Optimizer(init, step)

    if cfg.optimizer == "adafactor":

        def init(params):
            def factored(p):
                if _is_matrix(p):
                    return {
                        "vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32),
                    }
                return {"v": jnp.zeros(p.shape, jnp.float32)}

            return {
                "step": jnp.zeros((), jnp.int32),
                "v": jax.tree.map(factored, params,
                                  is_leaf=lambda x: hasattr(x, "ndim")),
            }

        def step(params, grads, state):
            grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
            t = state["step"] + 1
            lr = sched(t)
            decay = 1.0 - t.astype(jnp.float32) ** -0.8

            def upd(p, g, v):
                g = g.astype(jnp.float32)
                g2 = jnp.square(g) + 1e-30
                if "vr" in v:
                    vr = decay * v["vr"] + (1 - decay) * jnp.mean(g2, axis=-1)
                    vc = decay * v["vc"] + (1 - decay) * jnp.mean(g2, axis=-2)
                    r = vr / jnp.mean(vr, axis=-1, keepdims=True)
                    u = g / (jnp.sqrt(r[..., None]) * jnp.sqrt(vc[..., None, :]))
                    newv = {"vr": vr, "vc": vc}
                else:
                    vv = decay * v["v"] + (1 - decay) * g2
                    u = g / jnp.sqrt(vv + 1e-30)
                    newv = {"v": vv}
                # update clipping (Adafactor's RMS-1 rule)
                rms = jnp.sqrt(jnp.mean(jnp.square(u)) + 1e-30)
                u = u / jnp.maximum(1.0, rms)
                wd = cfg.weight_decay * p.astype(jnp.float32) if p.ndim >= 2 else 0.0
                newp = (p.astype(jnp.float32) - lr * (u + wd)).astype(p.dtype)
                return newp, newv

            flat_p, tdef = jax.tree.flatten(params)
            flat_g = tdef.flatten_up_to(grads)
            flat_v = tdef.flatten_up_to(state["v"])
            out = [upd(p, g, v) for p, g, v in zip(flat_p, flat_g, flat_v)]
            new_params = jax.tree.unflatten(tdef, [o[0] for o in out])
            new_v = jax.tree.unflatten(tdef, [o[1] for o in out])
            return new_params, {"step": t, "v": new_v}, {"grad_norm": gnorm, "lr": lr}

        return Optimizer(init, step)

    if cfg.optimizer == "rmsprop":

        def init(params):
            return {
                "step": jnp.zeros((), jnp.int32),
                "v": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            }

        def step(params, grads, state):
            grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
            t = state["step"] + 1
            lr = sched(t)
            v = jax.tree.map(
                lambda v_, g: 0.9 * v_ + 0.1 * jnp.square(g.astype(jnp.float32)),
                state["v"], grads)
            new_params = jax.tree.map(
                lambda p, g, v_: (p.astype(jnp.float32)
                                  - lr * g.astype(jnp.float32)
                                  / (jnp.sqrt(v_) + 1e-8)).astype(p.dtype),
                params, grads, v)
            return new_params, {"step": t, "v": v}, {"grad_norm": gnorm, "lr": lr}

        return Optimizer(init, step)

    if cfg.optimizer == "sgd":

        def init(params):
            return {
                "step": jnp.zeros((), jnp.int32),
                "m": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            }

        def step(params, grads, state):
            grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
            t = state["step"] + 1
            lr = sched(t)
            m = jax.tree.map(
                lambda m_, g: 0.9 * m_ + g.astype(jnp.float32), state["m"], grads)
            new_params = jax.tree.map(
                lambda p, m_: (p.astype(jnp.float32) - lr * m_).astype(p.dtype),
                params, m)
            return new_params, {"step": t, "m": m}, {"grad_norm": gnorm, "lr": lr}

        return Optimizer(init, step)

    raise ValueError(f"unknown optimizer {cfg.optimizer}")
