"""§Roofline: read the dry-run artifacts (results/dryrun/*.json) and emit the
per-(arch × shape) three-term roofline table for the single-pod mesh.

The artifacts come from ``python -m repro.launch.dryrun``. ``setup`` (called
by ``benchmarks/run.py`` before timing) generates one cell when none exist —
in a subprocess, because the dryrun module must own jax initialization
(``XLA_FLAGS`` host-device count is locked at first import). The child runs
with ``JAX_PLATFORMS=cpu``: its 512 devices are host devices, and the parent
may hold the chip. A run with no
artifacts is a FAILURE, not an empty table: the old behavior of silently
emitting ``n_evals: 0`` hid a completely broken pipeline (dryrun did not
even import against this container's jax before the setup-hook fix).
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

# the cheapest (arch × shape) cell: smallest model, fully scanned
_SETUP_CELL = ("mamba2-370m", "train_4k")
_SETUP_TIMEOUT_S = 1800


def setup(fast: bool = True, out_dir: str = "results/dryrun") -> None:
    """Ensure at least one dry-run artifact exists (see module docstring)."""
    if glob.glob(os.path.join(out_dir, "*_single.json")):
        return
    arch, shape = _SETUP_CELL
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", arch, "--shape", shape, "--out", out_dir]
    print(f"[roofline] no dry-run artifacts in {out_dir} — generating "
          f"{arch}/{shape} (takes a few minutes)", flush=True)
    proc = subprocess.run(cmd, timeout=_SETUP_TIMEOUT_S,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"dry-run artifact generation failed (exit {proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )


def run(fast: bool = True, out_dir: str = "results/dryrun") -> dict:
    rows = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*_single.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            rows.append({"arch": rec.get("arch"), "shape": rec.get("shape"),
                         "status": rec.get("status")})
            continue
        rl = rec.get("roofline")
        if not rl:
            continue
        rows.append({
            "arch": rec["arch"], "shape": rec["shape"], "status": "ok",
            "compute_ms": rl["compute_s"] * 1e3,
            "memory_ms": rl["memory_s"] * 1e3,
            "collective_ms": rl["collective_s"] * 1e3,
            "dominant": rl["dominant"],
            "useful_flops_ratio": rl["useful_flops_ratio"],
            "roofline_fraction": rl["roofline_fraction"],
        })
    ok = [r for r in rows if r.get("status") == "ok"]
    if not ok:
        # no silently-empty result: the bench contract is that at least one
        # analysed cell exists (setup() generates one when missing)
        raise RuntimeError(
            f"no usable dry-run artifacts in {out_dir} — "
            f"run python -m repro.launch.dryrun (or let setup() do it)"
        )
    worst = min(ok, key=lambda r: r["roofline_fraction"])
    best = max(ok, key=lambda r: r["roofline_fraction"])
    derived = (f"{len(ok)} cells analysed; roofline fraction "
               f"{worst['roofline_fraction']:.3f} "
               f"({worst['arch']}/{worst['shape']}) .. "
               f"{best['roofline_fraction']:.3f} "
               f"({best['arch']}/{best['shape']})")
    return {"rows": rows, "n_evals": len(rows), "derived": derived}


if __name__ == "__main__":
    setup()
    print(run()["derived"])
