"""Compile each cell's programs for a described TPU v5e (no chip attached)
and print the compiler's memory plan per device: arguments + temporaries.

  JAX_PLATFORMS=cpu python chipbench/plan.py [<config>.<traffic> ...]

With no argument it plans every cell of BENCHMARK.json.

A program that does not fit, or that the chip's compiler refuses, fails here
without chip time. Nothing runs, so this says nothing about times.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def plan(compiled) -> str:
    mem = compiled.memory_analysis()
    a, t = mem.argument_size_in_bytes, mem.temp_size_in_bytes
    return f"{a / 1e9:.3f}e9 + {t / 1e9:.3f}e9 = {(a + t) / 1e9:.3f}e9 B per device"


def train_plan(spec, topo) -> list:
    from repro.config import ModelConfig, RunConfig, ShapeConfig, TrainConfig
    from repro.train.optim import make_optimizer
    from repro.train.steps import abstract_params, make_train_step

    tr, m = spec["traffic"], spec["config"]["model"]
    run = RunConfig(model=ModelConfig(**m),
                    shape=ShapeConfig("plan", tr["seq"], tr["batch"], "train"),
                    train=TrainConfig(**{k: v for k, v in tr["train"].items()
                                         if k != "no_weight_decay"}))
    aparams = abstract_params(run.model)
    astate = {"params": aparams, "opt": jax.eval_shape(make_optimizer(run.train).init, aparams)}
    batch = {k: jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), jnp.int32)
             for k in ("tokens", "labels")}
    one = SingleDeviceSharding(topo.devices[0])
    step, _, _ = make_train_step(run, None)
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)  # noqa: E731
    compiled = jax.jit(step, donate_argnums=(0,)).lower(put(astate), put(batch)).compile()
    return [f"train step: {plan(compiled)}"]


def decode_plan(spec, topo) -> list:
    from repro.config import ModelConfig, RunConfig, ServeConfig, ShapeConfig
    from repro.train.steps import abstract_cache, abstract_params, make_decode_step

    tr, m = spec["traffic"], spec["config"]["model"]
    cfg = ModelConfig(**m)
    run = RunConfig(model=cfg, shape=ShapeConfig("plan", tr["cache_len"], tr["batch"], "decode"),
                    serve=ServeConfig(kv_dtype=tr["kv_dtype"]))
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)  # noqa: E731
    step, _, _, _ = make_decode_step(run, None)
    params, cache = put(abstract_params(cfg)), put(abstract_cache(cfg, run.shape, tr["kv_dtype"]))
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    out = []
    for name, n in (("prefill chunk", tr["prefill_chunk"]), ("one-token step", 1)):
        toks = jax.ShapeDtypeStruct((tr["batch"], n), jnp.int32, sharding=one)
        compiled = jax.jit(step, donate_argnums=(1,)).lower(params, cache, toks, idx).compile()
        out.append(f"{name} ({tr['batch']} x {n}): {plan(compiled)}")
    return out


def main(argv) -> int:
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    spec_all = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    cells = argv or [w["name"] for w in spec_all["workloads"]]
    for cell in cells:
        conf, traffic = cell.split(".", 1)
        spec = {"config": json.loads((HERE / "configs" / f"{conf}.json").read_text()),
                "traffic": json.loads((HERE / "traffic" / f"{traffic}.json").read_text())}
        lines = (train_plan if spec["traffic"]["kind"] == "train" else decode_plan)(spec, topo)
        for line in lines:
            print(f"{cell}: {line}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
