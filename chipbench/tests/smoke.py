"""Cells of the benchmark at the program's SMOKE sizes, for runs on the CPU.

Each spec is what ``run.resolve`` returns for a cell, with the same traffic
kinds, loops, references and metrics, but widths and lengths small enough
for a test."""
from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

SSM = {"name": "mamba2-smoke", "family": "ssm", "num_layers": 2, "d_model": 64,
       "num_heads": 1, "num_kv_heads": 1, "d_ff": 0, "vocab_size": 512, "norm_eps": 1e-5,
       "tie_embeddings": True, "ssm_state": 16, "ssm_head_dim": 16, "ssm_expand": 2,
       "ssm_conv_width": 4, "ssm_chunk": 16, "ssm_ngroups": 1,
       "param_dtype": "float32", "compute_dtype": "bfloat16"}
DENSE = {"name": "qwen3-smoke", "family": "dense", "num_layers": 2, "d_model": 64,
         "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "d_ff": 192, "vocab_size": 512,
         "act": "silu", "norm_eps": 1e-6, "use_qk_norm": True, "rope_theta": 1e6,
         "tie_embeddings": True, "param_dtype": "float32", "compute_dtype": "bfloat16"}


# Limits for the smoke cells, set from their readings on the CPU the same
# way as a cell's (PERF.md): above what sound runs read over seeds, below the
# control and the faults. Dense train, six seeds: loss_gap <= 3.2e-5,
# grad_gap_p90 <= 2.3e-3, update_gap <= 9.5e-3; the fp8 control reads
# grad_gap_p90 >= 1.6e-2; half of the batch reads loss_gap >= 9.7e-4 and
# grad_gap_p90 >= 4.6e-2; a state left unchanged reads 1. Dense decode:
# logit_gap <= 2.4e-3; control >= 3.9e-2; an altered token >= 0.44.
LIMITS = {
    "train": {"data_mismatch": 0.0, "loss_gap": 1.5e-4, "grad_gap_p90": 6e-3, "update_gap": 3e-2},
    "decode": {"logit_gap": 1.5e-2},
}


def _json(rel):
    return json.loads((BENCH / rel).read_text())


def spec(kind: str, family: str, *, limits=None) -> dict:
    """A cell at SMOKE size; ``limits`` defaults to LIMITS[kind]."""
    model = copy.deepcopy(SSM if family == "ssm" else DENSE)
    if kind == "train":
        traffic = _json("traffic/train_b4_s4096.json")
        traffic.update(batch=4, seq=64, trace_steps=2)
    else:
        traffic = _json("traffic/decode_b4_ctx2048.json")
        traffic.update(batch=2, cache_len=64, prompt_len=48, gen_len=16,
                       prefill_chunk=16, trace_steps=16, check_requests=2)
    name = f"smoke.{family}.{kind}"
    spec_ = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    e2e = [m for m in spec_["end_to_end"] if m["name"] == "setup_s" or
           (kind == "train") == m["name"].startswith("train")]
    per = [m for m in spec_["per_layer"] if m["name"].endswith("." + kind)]
    # the SSM cell's configuration keeps a bf16 residual, as the program does
    config = {"family": family, "model": model, "residual_in_fp32": family != "ssm"}
    return {"cell": {"name": name, "chips": 1}, "config": config,
            "traffic": traffic, "limits": dict(LIMITS[kind] if limits is None else limits), "end_to_end": e2e,
            "per_layer": per}
