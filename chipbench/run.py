"""Run one cell of the on-chip benchmark once.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json. It names a
configuration (``configs/<name>.json``), a traffic mix
(``traffic/<name>.json``, whose ``kind`` picks ``loops/<kind>.py``) and its
chips; the limits of its comparison are in ``limits/<cell>.json``, the
family's counts in ``counts/<family>.py``, its plain reference in
``refs/<family>.py``, each per-layer metric's reader in
``metrics/<metric>.py`` and the chip's peaks in ``peaks.json``.

With ``--trace 0`` the run reports the cell's end-to-end metrics over a
window of ``--seconds``; with ``--trace 1`` it traces ``trace_steps`` steps
with the profiler and reports the cell's per-layer metrics. It refuses to
run without a TPU, on fewer chips than the cell asks for, or on a chip that
``peaks.json`` does not list. The last line of standard output is one JSON
object; the numbers compared, each beside its limit, end it and are the last
lines of standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))


def resolve(workload: str) -> dict:
    """The cell's entries of BENCHMARK.json and the files they name."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    limits = HERE / "limits" / f"{workload}.json"

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": json.loads((ROOT / conf["file"]).read_text()),
        "traffic": json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads(limits.read_text()) if limits.exists() else {},
        "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
        "per_layer": [m for m in spec["per_layer"] if mine(m)],
    }


def enable_compile_cache() -> None:
    """JAX's persistent cache at $JAX_COMPILATION_CACHE_DIR, else at a fixed
    directory in the checkout; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def chip(chips: int, peaks: dict):
    """The devices of the run: TPUs the peaks table lists, as many as asked."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    line = f"platform={d0.platform} kind={d0.device_kind} count={len(devs)}"
    print(f"device {line}", flush=True)
    if d0.platform != "tpu":
        raise SystemExit(f"no TPU: {line}")
    if d0.device_kind not in peaks:
        raise SystemExit(f"{d0.device_kind!r} is not in peaks.json")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips: {line}")
    return devs


class Ctx:
    """What a per-layer metric's reader may read."""

    def __init__(self, res, peak: dict, chips: int):
        self.kind, self.trace, self.steps = res.kind, res.trace, res.steps
        self.counts, self.host, self.peak, self.chips = res.counts, res.host, peak, chips


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            devices=None, calibrate: bool = False, t0: float = T0, spec=None,
            peaks=None) -> dict:
    """Run the cell and return its result line as a dict. ``devices`` is
    what ``chip`` returned; tests pass the host's own and a spec of their
    own."""
    import jax

    import harness as H

    spec = spec or resolve(workload)
    peaks = peaks or H.load_json("peaks.json")
    devices = devices or jax.devices()
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    job = H.Job(workload, config, traffic, spec["limits"], seed, seconds, trace,
                cell["chips"], t0, calibrate)
    fam = H.load_module(f"refs/{config['family']}.py")
    counts = H.load_module(f"counts/{config['family']}.py")
    res = H.load_module(f"loops/{traffic['kind']}.py").run(job, fam, counts)
    res.check("window_compiles", res.window_compiles, {"window_compiles": 0})
    inside = H.compiles().names[-res.window_compiles:] if res.window_compiles else []
    print(f"window_compiles={res.window_compiles} (must be 0) {' '.join(inside)}".rstrip(),
          flush=True)

    kind = devices[0].device_kind
    metrics = {}
    if trace:
        ctx = Ctx(res, peaks.get(kind, {}), cell["chips"])
        for m in spec["per_layer"]:
            v = H.load_module(f"metrics/{m['name']}.py").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": res.e2e[m["name"]], "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": res.memory_peak_bytes}
    out = {"correct": res.correct, "attempted": res.attempted, "failed": res.failed,
           "metrics": metrics, "device": device}
    if trace and res.trace is not None:
        device["busy_s"] = res.trace.busy_s
        device["window_s"] = res.trace.window_s
        out["breakdown"] = {"device_ops": res.trace.top_ops(), "idle_gaps": res.trace.gaps}
    if calibrate:
        out["calibration"] = res.calibration
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in res.checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--calibrate", action="store_true",
                    help="also read the control and the planted faults")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative integer")

    import harness as H

    spec = resolve(args.workload)
    enable_compile_cache()
    devices = chip(spec["cell"]["chips"], H.load_json("peaks.json"))
    devices = devices[:spec["cell"]["chips"]]
    out = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                  devices=devices, calibrate=args.calibrate, spec=spec)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
