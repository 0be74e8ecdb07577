import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape × mesh)
cell against the production mesh; record memory/cost analysis and the
collective schedule for the roofline table.

The two lines above MUST stay the first statements in this module (jax locks
the device count on first init). Run as ``python -m repro.launch.dryrun``.

Roofline reconstruction
-----------------------
XLA's cost_analysis counts a while-loop body ONCE, regardless of trip count
(verified empirically), so a layer-scanned model under-reports FLOPs/bytes and
the HLO-text collective parse under-reports in-loop collectives the same way.
We therefore compile small CALIBRATION variants with every scan fully unrolled
(cfg.unroll_scans) at (L=1,k=1), (L=2,k=1) and — for training — (L=1,k=2)
microbatches, and solve the linear system

    f(L, k) = base + k*per_step + k*L*per_layer

for per-layer / per-microbatch / one-off costs, then reconstruct the true
totals at the production (L, k). Hybrids get a 4-point system that separates
the Mamba-layer cost from the shared-attention cost. The REAL (scanned) cell
is still compiled first: that is the compile-proof and the memory_analysis
(loop buffers are reused, so memory numbers from the real artifact are the
correct ones).

Usage:
  python -m repro.launch.dryrun --arch mistral-nemo-12b --shape train_4k
  python -m repro.launch.dryrun --all --both-meshes [--out results/dryrun]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.config import SHAPES, RunConfig
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh
from repro.parallel import ctx as pctx
from repro.train import steps as steps_lib


def _mem_dict(mem) -> dict:
    keys = [
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ]
    out = {}
    for k in keys:
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    return out


def _lower(run: RunConfig, pc):
    """Build + lower the step for this run. Returns the lowered object."""
    mode = run.shape.mode
    if mode == "train":
        step, state_specs, bspecs = steps_lib.make_train_step(run, pc)
        aparams = steps_lib.abstract_params(run.model)
        from repro.train.optim import make_optimizer

        aopt = jax.eval_shape(make_optimizer(run.train).init, aparams)
        astate = {"params": aparams, "opt": aopt}
        abatch = steps_lib.input_specs(run.model, run.shape)
        jitted = jax.jit(
            step,
            in_shardings=(state_specs, bspecs),
            out_shardings=(state_specs, None),
            donate_argnums=(0,),
        )
        return jitted.lower(astate, abatch)
    if mode == "prefill":
        step, pspecs, bspecs = steps_lib.make_prefill_step(run, pc)
        aparams = steps_lib.abstract_params(run.model)
        abatch = steps_lib.input_specs(run.model, run.shape)
        return jax.jit(
            step, in_shardings=(pspecs, bspecs)
        ).lower(aparams, abatch)
    step, pspecs, cspecs, bspecs = steps_lib.make_decode_step(run, pc)
    aparams = steps_lib.abstract_params(run.model)
    acache = steps_lib.abstract_cache(run.model, run.shape, run.serve.kv_dtype)
    abatch = steps_lib.input_specs(run.model, run.shape)
    jitted = jax.jit(
        step,
        in_shardings=(pspecs, cspecs, bspecs["tokens"], P()),
        out_shardings=(None, None, cspecs),
        donate_argnums=(1,),
    )
    return jitted.lower(
        aparams, acache, abatch["tokens"], jax.ShapeDtypeStruct((), jnp.int32)
    )


def _measure(run: RunConfig, pc, want_mem: bool = False) -> dict:
    t0 = time.monotonic()
    lowered = _lower(run, pc)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0
    cost = dict(compiled.cost_analysis() or {})
    coll = roofline.parse_collectives(compiled.as_text())
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_wire": dict(coll.wire_bytes),
        "coll_counts": dict(coll.counts),
        "coll_result": dict(coll.result_bytes),
        "lower_s": t_lower,
        "compile_s": t_compile,
    }
    if want_mem:
        out["memory_analysis"] = _mem_dict(compiled.memory_analysis())
    return out


def _combine_dicts(ds: list[dict], coeffs: list[float]) -> dict:
    keys = set()
    for d in ds:
        keys |= set(d)
    out = {}
    for k in keys:
        # intermediate results may legitimately be negative (corrections);
        # the final totals are clamped in reconstruct()
        out[k] = sum(c * d.get(k, 0.0) for c, d in zip(coeffs, ds))
    return out


def _calib_run(run: RunConfig, layers: int, micro: int, every: int | None = None):
    """A reduced, fully-unrolled variant for cost calibration."""
    cfg = run.model
    kw = dict(num_layers=layers, unroll_scans=True)
    if every is not None:
        kw["hybrid_attn_every"] = every
    # cap unrolled chunk-scan lengths (keeps calibration compiles tractable;
    # FLOPs are unchanged — only the chunking granularity moves)
    s = run.shape.seq_len
    if s // cfg.attn_chunk > 128:
        kw["attn_chunk"] = -(-s // 128)
    if cfg.ssm_state and s // cfg.ssm_chunk > 128:
        kw["ssm_chunk"] = -(-s // 128)
    new_model = cfg.scaled(**kw)
    new_train = dataclasses.replace(run.train, microbatches=micro)
    return dataclasses.replace(run, model=new_model, train=new_train)


def _lc(ms: list[dict], coeffs: list[float]) -> dict:
    """Linear combination over measurement vectors (flops, bytes, wire)."""
    return {
        "flops": sum(c * m["flops"] for c, m in zip(coeffs, ms)),
        "bytes": sum(c * m["bytes"] for c, m in zip(coeffs, ms)),
        "coll_wire": _combine_dicts(
            [m["coll_wire"] for m in ms], coeffs
        ),
    }


def reconstruct(run: RunConfig, pc, verbose: bool = True) -> dict:
    """Calibrate + reconstruct true per-step totals (flops / bytes / wire).

    Cost structure (affine in L, k, and L*k):
        f(L, k) = base + k*mb + L*act + k*L*w
    where ``act`` is token-total-proportional per-layer work (invariant in k —
    microbatches split the same tokens) and ``w`` is per-layer per-microbatch
    fixed work (FSDP weight all-gathers, weight reads). Hybrids split the
    layer terms into mamba vs shared-attention components (6-point system).
    """
    cfg = run.model
    mode = run.shape.mode
    k = run.train.microbatches if mode == "train" else 1
    is_hybrid = cfg.family == "hybrid"

    def meas(layers, micro, every=None):
        r = _calib_run(run, layers, micro, every)
        m = _measure(r, pc)
        if verbose:
            print(
                f"  [calib] L={layers} k={micro} every={every}: "
                f"{m['flops']:.3e}F {m['bytes']:.3e}B ({m['compile_s']:.0f}s)",
                flush=True,
            )
        return m

    zero = {"flops": 0.0, "bytes": 0.0, "coll_wire": {}}
    if not is_hybrid:
        m11 = meas(1, 1)
        m21 = meas(2, 1)
        if mode == "train" and k > 1:
            m12 = meas(1, 2)
            m22 = meas(2, 2)
            w = _lc([m22, m12, m21, m11], [1, -1, -1, 1])
            act = _lc([m21, m11, w], [1, -1, -1])
            mb = _lc([m12, m11, w], [1, -1, -1])
            base = _lc([m11, mb, act, w], [1, -1, -1, -1])
        else:
            w = zero
            act = _lc([m21, m11], [1, -1])
            mb = zero
            base = _lc([m11, act], [1, -1])
        L = cfg.num_layers
        total = _lc([base, mb, act, w], [1, k, L, k * L])
    else:
        m111 = meas(1, 1, every=1)
        m221 = meas(2, 1, every=2)
        m211 = meas(2, 1, every=1)
        if mode == "train" and k > 1:
            m112 = meas(1, 2, every=1)
            m222 = meas(2, 2, every=2)
            m212 = meas(2, 2, every=1)
            a1 = _lc([m221, m111], [1, -1])       # am + wm
            a2 = _lc([m222, m112], [1, -1])       # am + 2wm
            wm = _lc([a2, a1], [1, -1])
            am = _lc([a1, wm], [1, -1])
            b1 = _lc([m211, m221], [1, -1])       # aa + wa
            b2 = _lc([m212, m222], [1, -1])       # aa + 2wa
            wa = _lc([b2, b1], [1, -1])
            aa = _lc([b1, wa], [1, -1])
            mb = _lc([m112, m111, wm, wa], [1, -1, -1, -1])
            base = _lc([m111, mb, am, wm, aa, wa], [1, -1, -1, -1, -1, -1])
        else:
            am = _lc([m221, m111], [1, -1])
            aa = _lc([m211, m221], [1, -1])
            wm = zero
            wa = zero
            mb = zero
            base = _lc([m111, am, aa], [1, -1, -1])
        n_m = cfg.num_layers
        n_a = cfg.num_layers // cfg.hybrid_attn_every
        total = _lc(
            [base, mb, am, wm, aa, wa],
            [1, k, n_m, k * n_m, n_a, k * n_a],
        )
    return {
        "flops": float(max(total["flops"], 0.0)),
        "bytes accessed": float(max(total["bytes"], 0.0)),
        "wire_bytes": {kk: float(max(v, 0.0))
                       for kk, v in total["coll_wire"].items()},
    }


def dryrun_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    extra_overrides: dict | None = None,
    calibrate: bool = True,
    verbose: bool = True,
) -> dict:
    """Lower + compile one cell (and its calibration variants)."""
    cfg = configs.get(arch)
    applicability = configs.applicable_shapes(cfg)[shape_name]
    base = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "num_devices": 512 if multi_pod else 256,
    }
    if applicability != "ok":
        return dict(base, status=applicability)

    run = configs.make_run(arch, shape_name, multi_pod=multi_pod,
                           **(extra_overrides or {}))
    mesh = make_production_mesh(multi_pod=multi_pod)
    pc = pctx.from_mesh(mesh, multi_pod=multi_pod, fsdp=run.mesh.fsdp_params,
                        tp=run.mesh.tp)

    with jax.set_mesh(mesh):
        real = _measure(run, pc, want_mem=True)
        record = dict(
            base,
            status="ok",
            lower_s=real["lower_s"],
            compile_s=real["compile_s"],
            memory_analysis=real["memory_analysis"],
            raw_cost={"flops": real["flops"], "bytes accessed": real["bytes"]},
            raw_collectives={
                "counts": real["coll_counts"],
                "result_bytes": real["coll_result"],
                "wire_bytes": real["coll_wire"],
            },
            config={
                "microbatches": run.train.microbatches,
                "remat": run.train.remat,
                "kv_dtype": run.serve.kv_dtype,
                "fsdp": run.mesh.fsdp_params,
                "optimizer": run.train.optimizer,
                "attn_impl": run.model.attn_impl,
                "shard_cache_seq": run.serve.shard_cache_seq,
            },
        )
        if calibrate and not multi_pod:
            rec = reconstruct(run, pc, verbose=verbose)
            record["cost_analysis"] = {
                "flops": rec["flops"],
                "bytes accessed": rec["bytes accessed"],
            }
            record["collectives"] = {
                "counts": real["coll_counts"],
                "result_bytes": real["coll_result"],
                "wire_bytes": rec["wire_bytes"],
            }
            record["roofline"] = roofline.analyze(record, run.model, run.shape)
    if verbose:
        mm = record["memory_analysis"]
        msg = (
            f"[dryrun] {arch} {shape_name} {record['mesh']}: "
            f"args={mm.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
            f"temp={mm.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
            f"(lower {record['lower_s']:.0f}s compile {record['compile_s']:.0f}s)"
        )
        if "roofline" in record:
            rl = record["roofline"]
            msg += (
                f" compute={rl['compute_s']*1e3:.2f}ms mem={rl['memory_s']*1e3:.2f}ms"
                f" coll={rl['collective_s']*1e3:.2f}ms dom={rl['dominant']}"
                f" roofline_frac={rl['roofline_fraction']:.3f}"
            )
        print(msg, flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default="results/dryrun")
    ap.add_argument("--no-calibrate", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = configs.ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{configs.ALIASES.get(arch, arch)}_{shape}_" + (
                    "multi" if mp else "single")
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[dryrun] skip existing {tag}", flush=True)
                    continue
                try:
                    rec = dryrun_cell(arch, shape, multi_pod=mp,
                                      calibrate=not args.no_calibrate)
                except Exception as e:  # a failure here is a bug in the system
                    traceback.print_exc()
                    rec = {
                        "arch": arch, "shape": shape,
                        "mesh": "2x16x16" if mp else "16x16",
                        "status": f"FAILED: {type(e).__name__}: {e}",
                    }
                    failures.append(tag)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
    if failures:
        print(f"[dryrun] FAILURES: {failures}", flush=True)
        raise SystemExit(1)
    print("[dryrun] all cells OK", flush=True)


if __name__ == "__main__":
    main()
