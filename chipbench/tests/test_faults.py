"""A run with the timed path broken underneath comes out not correct, once
for each fault a cell can have; and the control (the reference at fp8 in the
program's place) fails a limit. SMOKE sizes on the CPU; the look for a chip
is skipped, everything else is a run."""
import time

import jax.numpy as jnp
import pytest

import run
import smoke
from repro.models import api
from repro.train import optim, steps


def execute(kind, calibrate=False):
    return run.execute("smoke", 31337, 0.3, False, t0=time.perf_counter(),
                       spec=smoke.spec(kind, "dense"), calibrate=calibrate)


def test_sound_runs_are_correct():
    assert execute("train")["correct"]
    assert execute("decode")["correct"]


def test_step_that_returns_its_state_unchanged(monkeypatch):
    real = optim.make_optimizer

    def frozen(cfg):
        opt = real(cfg)
        return optim.Optimizer(opt.init, lambda p, g, s: (p, s, {"grad_norm": 0.0, "lr": 0.0}))

    monkeypatch.setattr(steps, "make_optimizer", frozen)
    out = execute("train")
    assert not out["correct"]
    assert out["checks"]["update_gap"]["value"] == pytest.approx(1.0)
    assert out["checks"]["grad_gap_p90"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(monkeypatch):
    real = api.loss_fn

    def half(params, batch, cfg, pc=None, *, remat="none"):
        rows = batch["tokens"].shape[0] // 2
        return real(params, {k: v[:rows] for k, v in batch.items()}, cfg, pc, remat=remat)

    monkeypatch.setattr(api, "loss_fn", half)
    out = execute("train")
    assert not out["correct"]
    assert out["checks"]["loss_gap"]["value"] > smoke.LIMITS["train"]["loss_gap"]


def test_token_altered_where_produced(monkeypatch):
    real = api.decode_step

    def altered(params, cache, tokens, cache_index, cfg, pc=None):
        logits, cache = real(params, cache, tokens, cache_index, cfg, pc)
        # at one position of every request, another token wins the argmax
        bump = jnp.where(cache_index == 53, 1e4, 0.0)
        return logits.at[..., 7].add(bump), cache

    monkeypatch.setattr(api, "decode_step", altered)
    out = execute("decode")
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > smoke.LIMITS["decode"]["logit_gap"]


@pytest.mark.parametrize("kind", ["train", "decode"])
def test_control_fails_a_limit(kind):
    cal = execute(kind, calibrate=True)["calibration"]["control_fp8"]
    assert any(v > smoke.LIMITS[kind][k] for k, v in cal.items()), cal
