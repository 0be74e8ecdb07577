"""Smoke run of the LM step programs on the chip, at full published widths.

One process drives the main path once through its normal entry points, with
weights, prompts and tokens made from ``--seed``:

  1. device   require a TPU (there is no CPU fallback); print kind and count
  2. kernels  flash_attention, gmm, ssd_scan and wstream through
              kernels/ops.py at qwen3-1.7b / MoE / mamba2-370m widths, each
              against kernels/ref.py
  3. serve    qwen3-1.7b (all 28 layers) through make_decode_step, batch 4 and
              a bf16 cache of 2048: the prompt goes through decode steps, then
              16 greedy tokens; decode logits at the prompt positions are
              checked against api.forward on the same prompt
  4. train    mamba2-370m (all 48 layers) through make_train_step, remat=full,
              batch 4 x 4096 from LMStream: a warm-up step and 5 steps; every
              loss finite and the parameters moved
  5. memory   peak device memory after phases 3 and 4

``--chips 4`` runs only the sharded path instead: launch/train.py's train
step on a 2x2 (data, model) mesh for qwen3-1.7b at global batch 4 x 4096,
and, as its comparison, a 2-layer full-width cut of qwen3-1.7b whose
first-step loss on the mesh must match the same cut run on device 0 alone.

  python chip_smoke.py [--seed 0]
  python chip_smoke.py --chips 4

Each phase prints one line. The last line of stdout is a JSON object,
{"ok": true, "device": {...}}, printed only when every phase passed; any
failed check or phase exits non-zero. Times are single samples from a smoke
run, not benchmark results.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.common import enable_compile_cache  # noqa: E402
from repro.config import RunConfig, ServeConfig, ShapeConfig, TrainConfig  # noqa: E402
from repro.data.synthetic import LMStream  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import init_train  # noqa: E402
from repro.models import api  # noqa: E402
from repro.parallel import ctx as pctx  # noqa: E402
from repro.train.steps import make_decode_step  # noqa: E402

# bf16 bounds of tests/test_kernels.py (outputs here are O(1) by construction)
FLASH_TOL = 2e-2
GMM_TOL = 1e-1
# ssd_scan is tested in f32 only; its bf16 bound is flash's, relative to the
# output's scale (the reference returns f32, the kernel rounds to bf16)
SSD_REL_TOL = 2e-2
# wstream rounds the f32-accumulated product to bf16: half a bf16 ulp,
# relative to the output's scale
WSTREAM_REL_TOL = 2.0**-8
# decode vs forward: both bf16 through 28 layers, in different attention
# paths (cache of 2048 with kv_len masking vs the prompt alone). Bound on
# max |decode - forward| relative to max |forward|.
DECODE_REL_TOL = 5e-2
# sharded vs unsharded first-step loss (tests/test_sharded.py's bound)
SHARDED_LOSS_TOL = 2e-2

SERVE = dict(arch="qwen3_1_7b", batch=4, cache_len=2048, prompt_len=32,
             gen_len=16)
TRAIN = dict(arch="mamba2_370m", batch=4, seq=4096, steps=5)
SHARDED = dict(arch="qwen3_1_7b", batch=4, seq=4096, steps=3)
# the comparison: a 2-layer cut at full width, small enough for one chip
SHARDED_CUT = dict(arch="qwen3_1_7b", layers=2, batch=4, seq=512)


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def timed(fn, *args):
    """(result, seconds) of one call, waited on with block_until_ready."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def compile_timed(jitted, *args):
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def planned_bytes(compiled) -> str:
    """Argument and temporary bytes as the chip's compiler planned them."""
    mem = compiled.memory_analysis()
    return f"{mem.argument_size_in_bytes}+{mem.temp_size_in_bytes}"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(chips: int):
    devs = jax.devices()
    d0 = devs[0]
    line = f"platform={d0.platform} kind={d0.device_kind} count={len(devs)}"
    check(d0.platform == "tpu", f"no TPU: {line}")
    check(len(devs) == chips, f"want {chips} chip(s): {line}")
    return f"{line} compile_cache={enable_compile_cache()}"


def phase_kernels(seed: int):
    """Each kernel at its dispatch precision; the oracles in full f32."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    bf16, f32 = jnp.bfloat16, jnp.float32
    highest = jax.default_matmul_precision("highest")
    out = []

    # flash attention at qwen3-1.7b widths, (B, S, H, hd) model layout
    b, s, h, kv, hd = 1, 4096, 16, 8, 128
    q = jax.random.normal(ks[0], (b, s, h, hd), bf16)
    k = jax.random.normal(ks[1], (b, s, kv, hd), bf16)
    v = jax.random.normal(ks[2], (b, s, kv, hd), bf16)
    got = ops.flash_attention(q, k, v, causal=True)
    with highest:
        want = ref.flash_attention_ref(
            *(x.transpose(0, 2, 1, 3) for x in (q, k, v)), causal=True
        ).transpose(0, 2, 1, 3)
    err = max_err(got, want)
    out.append(f"flash_attention err={err:.3g} tol={FLASH_TOL}")
    check(err < FLASH_TOL, out[-1])

    # grouped matmul over MoE dispatch buffers (E, C, D) x (E, D, F)
    e, c, d, f = 8, 512, 2048, 768
    x = jax.random.normal(ks[3], (e, c, d), bf16)
    w = (jax.random.normal(ks[4], (e, d, f), f32) / math.sqrt(d)).astype(bf16)
    got = ops.gmm(x, w)
    with highest:
        want = ref.gmm_ref(x, w)
    err = max_err(got, want)
    out.append(f"gmm err={err:.3g} tol={GMM_TOL}")
    check(err < GMM_TOL, out[-1])

    # SSD chunk scan at mamba2-370m widths, chunk 256
    b, s, h, p, g, n = 1, 4096, 32, 64, 1, 128
    x = jax.random.normal(ks[5], (b, s, h, p), bf16)
    dt = jax.nn.softplus(jax.random.normal(ks[6], (b, s, h), f32))
    a = -jnp.exp(jax.random.normal(ks[7], (h,), f32))
    bm = jax.random.normal(ks[8], (b, s, g, n), bf16)
    cm = (jax.random.normal(jax.random.fold_in(ks[8], 1), (b, s, g, n), f32)
          / math.sqrt(n)).astype(bf16)
    y, st = ops.ssd_scan(x, dt, a, bm, cm, chunk=256)
    with highest:
        yr, sr = ref.ssd_scan_ref(x.astype(f32), dt, a, bm, cm, 256)
    rel_y = max_err(y, yr) / float(jnp.max(jnp.abs(yr)))
    rel_s = max_err(st, sr) / float(jnp.max(jnp.abs(sr)))
    out.append(f"ssd_scan rel_err y={rel_y:.3g} state={rel_s:.3g} "
               f"tol={SSD_REL_TOL}")
    check(max(rel_y, rel_s) < SSD_REL_TOL, out[-1])

    # weight streaming at qwen3-1.7b decode widths: the last layer of a
    # stacked fp32 MLP weight, and a tied (V, D) table
    x = jax.random.normal(ks[0], (4, 2048), bf16)
    for name, shape, layer, tr in (("mlp", (28, 2048, 6144), 27, False),
                                   ("table", (151936, 2048), 0, True)):
        w = jax.random.normal(ks[1], shape, f32)
        got = ops.wstream(x, w, layer, transpose=tr)
        wl = (w if w.ndim == 2 else w[layer]).astype(bf16).astype(f32)
        with highest:
            want = x.astype(f32) @ (wl.T if tr else wl)
        rel = max_err(got, want) / float(jnp.max(jnp.abs(want)))
        out.append(f"wstream {name} rel_err={rel:.3g} tol={WSTREAM_REL_TOL:.3g}")
        check(rel < WSTREAM_REL_TOL, out[-1])
        del w
    return " | ".join(out)


def phase_serve(seed: int):
    cfg = configs.get(SERVE["arch"])
    batch, cache_len = SERVE["batch"], SERVE["cache_len"]
    prompt_len, gen_len = SERVE["prompt_len"], SERVE["gen_len"]
    run = RunConfig(model=cfg,
                    shape=ShapeConfig("smoke_decode", cache_len, batch, "decode"),
                    serve=ServeConfig(kv_dtype="bfloat16"))
    step, _, _, _ = make_decode_step(run, None)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(lambda k: api.init(k, cfg))(key)
    cache = jax.jit(lambda: api.init_cache(cfg, batch, cache_len, "bfloat16"))()
    prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                 (batch, prompt_len), 0, cfg.vocab_size)
    decode, compile_s = compile_timed(jax.jit(step, donate_argnums=(1,)), params,
                                      cache, prompts[:, :1], jnp.int32(0))

    # the prompt goes through decode steps (examples/serve_lm.py), then
    # greedy generation; only generation steps are timed
    tok = prompts[:, :1]
    prompt_logits, generated, step_s = [], [], []
    for t in range(prompt_len + gen_len - 1):
        (next_tok, logits, cache), dt = timed(decode, params, cache, tok,
                                              jnp.int32(t))
        if t < prompt_len:
            prompt_logits.append(logits[:, 0])
        if t + 1 < prompt_len:
            tok = prompts[:, t + 1:t + 2]
        else:
            tok = next_tok
            generated.append(next_tok)
            step_s.append(dt)
    dec = jnp.stack(prompt_logits, axis=1)
    full, _ = jax.jit(lambda p, t: api.forward(p, {"tokens": t}, cfg))(
        params, prompts)
    gen = np.asarray(jnp.concatenate(generated, axis=1))
    rel = max_err(dec, full) / float(jnp.max(jnp.abs(full)))
    agree = float(jnp.mean(jnp.argmax(dec, -1) == jnp.argmax(full, -1)))
    line = (f"{cfg.name} L={cfg.num_layers} batch={batch} cache={cache_len} "
            f"bf16 compile_s={compile_s:.2f} "
            f"planned_bytes={planned_bytes(decode)} "
            f"step_ms_median={1e3 * float(np.median(step_s)):.3f} "
            f"step_ms_min={1e3 * min(step_s):.3f} prompt={prompt_len} "
            f"generated={gen.shape[1]} decode_vs_forward rel_err={rel:.3g} "
            f"tol={DECODE_REL_TOL} argmax_agree={agree:.3f}")
    check(gen.shape == (batch, gen_len), f"generated {gen.shape}: {line}")
    check(bool(((gen >= 0) & (gen < cfg.vocab_size)).all()),
          f"token out of vocabulary: {line}")
    check(bool(jnp.isfinite(full).all() & jnp.isfinite(dec).all()),
          f"non-finite logits: {line}")
    check(rel < DECODE_REL_TOL, line)
    return line


def _train_run(cfg, seq: int, batch: int, seed: int) -> RunConfig:
    return RunConfig(
        model=cfg, shape=ShapeConfig("smoke_train", seq, batch, "train"),
        train=TrainConfig(remat="full", microbatches=1, warmup_steps=5,
                          learning_rate=1e-3, total_steps=100, seed=seed))


def _batches(cfg, seq: int, batch: int, n: int, seed: int) -> list:
    stream = LMStream(cfg.vocab_size, seq, batch, seed=seed)
    return [{k: jnp.asarray(v) for k, v in stream.batch_at(i).items()}
            for i in range(n)]


def _train(run: RunConfig, batches: list, mesh=None):
    """launch/train.py's path, on ``mesh`` if given: compile, a warm-up step
    on batches[0], then one timed step per further batch. Returns
    (state, losses, step seconds, compile seconds, state bytes per device
    before the first step, planned bytes per device)."""
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        step, state = init_train(run, pctx.from_mesh(mesh))
        placed = _bytes_per_device(state)
        step, compile_s = compile_timed(step, state, batches[0])
        losses, step_s = [], []
        for i, batch in enumerate(batches):
            (state, metrics), dt = timed(step, state, batch)
            losses.append(float(metrics["loss"]))
            if i:
                step_s.append(dt)
    return state, losses, step_s, compile_s, placed, planned_bytes(step)


def _bytes_per_device(tree) -> dict:
    out = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def _train_line(cfg, batch, seq, compile_s, planned, step_s, losses) -> str:
    med = float(np.median(step_s))
    return (f"{cfg.name} L={cfg.num_layers} batch={batch}x{seq} remat=full "
            f"compile_s={compile_s:.2f} planned_bytes={planned} "
            f"step_s_median={med:.4f} "
            f"step_s_min={min(step_s):.4f} "
            f"tokens_per_s={batch * seq / med:.1f} "
            f"losses={[round(x, 4) for x in losses]}")


def phase_train(seed: int):
    cfg = configs.get(TRAIN["arch"])
    batch, seq, steps = TRAIN["batch"], TRAIN["seq"], TRAIN["steps"]
    run = _train_run(cfg, seq, batch, seed)
    # the first leaves of the initial params, as init_train makes them
    watch = 4
    before = [np.asarray(x) for x in jax.jit(
        lambda k: jax.tree.leaves(api.init(k, cfg))[:watch])(
            jax.random.PRNGKey(seed))]
    state, losses, step_s, compile_s, _, planned = _train(
        run, _batches(cfg, seq, batch, steps + 1, seed))
    moved = max(float(np.max(np.abs(np.asarray(a) - b))) for a, b in zip(
        jax.tree.leaves(state["params"])[:watch], before))
    line = (_train_line(cfg, batch, seq, compile_s, planned, step_s, losses)
            + f" param_max_delta={moved:.3g}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {line}")
    check(moved > 0, f"parameters did not change: {line}")
    return line


def phase_sharded_cut(seed: int):
    """The sharded program on a 2-layer cut vs the same cut on device 0."""
    cfg = configs.get(SHARDED_CUT["arch"]).scaled(
        num_layers=SHARDED_CUT["layers"])
    batch, seq = SHARDED_CUT["batch"], SHARDED_CUT["seq"]
    run = _train_run(cfg, seq, batch, seed)
    batches = _batches(cfg, seq, batch, 1, seed)
    devs = jax.devices()
    mesh = make_mesh((2, 2), ("data", "model"))
    _, sharded, _, compile_s, placed, _ = _train(run, batches, mesh)
    with jax.default_device(devs[0]):
        _, single, _, _, placed_1, _ = _train(run, batches)
    # shares of the whole state, counted once (as it sits on device 0 alone)
    shares = [placed.get(d, 0) / sum(placed_1.values()) for d in devs]
    diff = abs(single[0] - sharded[0])
    line = (f"{cfg.name} cut L={cfg.num_layers} batch={batch}x{seq} "
            f"mesh=2x2(data,model) compile_s={compile_s:.2f} "
            f"loss sharded={sharded[0]:.6f} device0={single[0]:.6f} "
            f"diff={diff:.3g} tol={SHARDED_LOSS_TOL} "
            f"state_share_per_device={[round(x, 3) for x in shares]}")
    check(set(placed_1) == {devs[0]}, f"unsharded state left device 0: {line}")
    # every device holds part of the state, and none holds most of it
    check(min(shares) > 0.1 and max(shares) < 0.75,
          f"state not spread over the four devices: {line}")
    check(diff < SHARDED_LOSS_TOL, line)
    return line


def phase_sharded_train(seed: int):
    cfg = configs.get(SHARDED["arch"])
    batch, seq, steps = SHARDED["batch"], SHARDED["seq"], SHARDED["steps"]
    run = _train_run(cfg, seq, batch, seed)
    mesh = make_mesh((2, 2), ("data", "model"))
    _, losses, step_s, compile_s, placed, planned = _train(
        run, _batches(cfg, seq, batch, steps + 1, seed), mesh)
    devs = jax.devices()
    line = (_train_line(cfg, batch, seq, compile_s, planned, step_s, losses)
            + " mesh=2x2(data,model) state_bytes_per_device="
            + str([placed.get(d, 0) for d in devs])
            + f" peak_bytes_per_device={[peak_bytes(d) for d in devs]}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {line}")
    return line


def run_phase(name: str, fn, *args) -> bool:
    try:
        line = fn(*args)
    except Exception as e:  # reported; the run then exits non-zero
        traceback.print_exc()
        print(f"[{name}] FAIL {type(e).__name__}: {e}", flush=True)
        return False
    print(f"[{name}] PASS {line}", flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded train path and its comparison")
    args = ap.parse_args(argv)

    if not run_phase("device", phase_device, args.chips):
        return 1
    dev = jax.devices()[0]
    if args.chips == 4:
        ok = run_phase("sharded-cut", phase_sharded_cut, args.seed)
        ok &= run_phase("sharded-train", phase_sharded_train, args.seed)
    else:
        ok = run_phase("kernels", phase_kernels, args.seed)
        ok &= run_phase("serve", phase_serve, args.seed)
        after_serve = peak_bytes(dev)
        ok &= run_phase("train", phase_train, args.seed)
        print(f"[memory] peak_bytes_in_use after serve={after_serve} "
              f"after train={peak_bytes(dev)}", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
