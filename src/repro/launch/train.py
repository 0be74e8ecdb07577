"""Training launcher: compose (arch × shape × mesh) into a sharded training
run. On the CPU container this runs REDUCED configs (--smoke) on the single
device; on a real pod the same entry point drives the full mesh.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
      --steps 50

``--trace DIR`` records the loop's ``repro.data.batch`` and
``repro.train.*`` spans as Chrome-trace JSONL (read with
``scripts/obs_report.py DIR``); ``--profile DIR`` takes a ``jax.profiler``
trace of the run with the same spans on the device profile's clock.
"""
from __future__ import annotations

import argparse
import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro import configs
from repro.common import enable_compile_cache
from repro.config import RunConfig, ShapeConfig, TrainConfig
from repro.data.synthetic import LMStream
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.obs import trace
from repro.runtime import cli as runtime_cli
from repro.train.loop import LoopConfig, run_training
from repro.train.optim import make_optimizer
from repro.train.steps import make_train_step
from repro.parallel import ctx as pctx


def init_train(run: RunConfig, pc: Optional[pctx.ParallelCtx]):
    """The jitted train step and its initial state.

    With a ParallelCtx (called under ``jax.set_mesh(pc.mesh)``) the state is
    created inside a jit whose outputs carry the step's shardings, so it never
    lands whole on one device: a model whose fp32 params and AdamW moments
    exceed one chip still starts.
    """
    step, sspecs, bspecs = make_train_step(run, pc)
    opt = make_optimizer(run.train)

    def init_state(rng):
        params = api.init(rng, run.model)
        return {"params": params, "opt": opt.init(params)}

    if pc is None:
        step = jax.jit(step, donate_argnums=(0,))
        init = jax.jit(init_state)
    else:
        step = jax.jit(step, donate_argnums=(0,), in_shardings=(sspecs, bspecs),
                       out_shardings=(sspecs, None))
        init = jax.jit(init_state, out_shardings=sspecs)
    return step, init(jax.random.PRNGKey(run.train.seed))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ckpt", type=str, default="/tmp/repro_launch_train")
    ap.add_argument("--mesh", type=str, default=None,
                    help="e.g. 2x4 -> (data=2, model=4); default single device")
    ap.add_argument("--trace", type=str, default=None, metavar="DIR",
                    help="record the loop's spans as Chrome-trace JSONL in DIR, "
                         "plus metrics.json (scripts/obs_report.py DIR)")
    ap.add_argument("--profile", type=str, default=None, metavar="DIR",
                    help="write a jax.profiler trace of the run to DIR, with the "
                         "loop's spans beside the device's ops")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    if cfg.family in ("audio", "vlm"):
        raise SystemExit("train launcher example covers token-LM families; "
                         "audio/vlm train via the dry-run cells")
    run = RunConfig(
        model=cfg,
        shape=ShapeConfig("cli", args.seq, args.batch, "train"),
        train=TrainConfig(total_steps=args.steps, warmup_steps=5,
                          learning_rate=1e-3),
    )
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    stream = LMStream(cfg.vocab_size, args.seq, args.batch, seed=0)
    batch_at = lambda i: {k: jnp.asarray(v)
                          for k, v in stream.batch_at(i).items()}
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        step, state = init_train(run, pctx.from_mesh(mesh))
        tracer = runtime_cli.start_trace(args)
        if args.profile:
            trace.annotate(True)
            jax.profiler.start_trace(args.profile)
        try:
            res = run_training(
                step, state, batch_at,
                LoopConfig(total_steps=args.steps,
                           ckpt_every=max(args.steps // 2, 10),
                           ckpt_dir=f"{args.ckpt}_"
                                    f"{configs.ALIASES.get(args.arch, args.arch)}",
                           log_every=10),
            )
        finally:
            if args.profile:
                jax.profiler.stop_trace()
                trace.annotate(False)
            runtime_cli.finish_trace(args, tracer)
    print(f"done: {res.final_step} steps, last loss "
          f"{res.metrics_history[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
