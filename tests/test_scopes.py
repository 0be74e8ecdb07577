"""Layer scopes in the step programs: every scope of ``repro.obs.scopes``
that a family uses reaches the compiled program's op metadata, in the
forward ops and, for training, in the backward ops too."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.config import RunConfig, ShapeConfig, TrainConfig
from repro.obs.scopes import SCOPES
from repro.train.optim import make_optimizer
from repro.train.steps import (abstract_cache, abstract_params, make_decode_step,
                               make_train_step)

OP_NAME = re.compile(r'op_name="([^"]*)"')
FAMILY_SCOPES = {
    "ssm": {"embed", "mixer", "ssd", "lm_head", "loss", "optimizer"},
    "dense": {"embed", "attention", "mlp", "lm_head", "loss", "optimizer"},
}


def scopes_on(op_name):
    """The scope names among an ``op_name`` path's entries, transform
    wrappers such as ``transpose(jvp(...))`` taken apart."""
    return set(re.split(r"[/()]", op_name)) & set(SCOPES)


def test_the_families_open_every_scope():
    assert FAMILY_SCOPES["ssm"] | FAMILY_SCOPES["dense"] == set(SCOPES)


def _train_hlo(cfg):
    run = RunConfig(model=cfg, shape=ShapeConfig("smoke", 32, 2, "train"),
                    train=TrainConfig(total_steps=10, warmup_steps=1, remat="full"))
    step, _, _ = make_train_step(run, None)
    params = abstract_params(cfg)
    state = {"params": params,
             "opt": jax.eval_shape(make_optimizer(run.train).init, params)}
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32) for k in ("tokens", "labels")}
    return jax.jit(step).lower(state, batch).compile().as_text()


def _decode_hlo(cfg):
    run = RunConfig(model=cfg, shape=ShapeConfig("smoke", 32, 2, "decode"))
    step, _, _, _ = make_decode_step(run, None)
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    idx = jax.ShapeDtypeStruct((), jnp.int32)
    cache = abstract_cache(cfg, run.shape, "bfloat16")
    lowered = jax.jit(step).lower(abstract_params(cfg), cache, tok, idx)
    return lowered.compile().as_text()


@pytest.mark.parametrize(
    "arch,mode",
    [("mamba2_370m", "train"), ("qwen3_1_7b", "train"), ("qwen3_1_7b", "decode")],
)
def test_step_program_ops_carry_the_family_scopes(arch, mode):
    cfg = configs.smoke(arch)
    text = _train_hlo(cfg) if mode == "train" else _decode_hlo(cfg)
    forward, backward = set(), set()
    for name in OP_NAME.findall(text):
        (backward if "transpose(" in name else forward).update(scopes_on(name))
    want = FAMILY_SCOPES[cfg.family]
    if mode == "decode":
        want = want - {"loss", "optimizer"}
        assert not backward
    else:
        # the optimizer has no backward pass
        assert want - {"optimizer"} <= backward
    assert want <= forward
    assert forward | backward == want
