"""The operation and byte counts against hand counts at the published
widths, and the decode roofline share's ceiling."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness as H
from counts import dense, ssm

BENCH = Path(__file__).resolve().parents[1]
QWEN = json.loads((BENCH / "configs/qwen3_1_7b.json").read_text())["model"]
MAMBA = json.loads((BENCH / "configs/mamba2_370m.json").read_text())["model"]
PEAK = H.load_json("peaks.json")["TPU v5 lite"]


def test_qwen3_params_and_train_flops():
    # per layer: q and o 2048 x 2048 each, k and v 2048 x 1024 each,
    # MLP 3 x 2048 x 6144; head (tied table) 151936 x 2048
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144
    assert layer == 50_331_648
    n = 28 * layer + 151_936 * 2048
    assert dense.matmul_params(QWEN) == n == 1_720_451_072
    # attention per token: 4 x (4096 / 2) x 16 x 128 x 28 layers, x 3
    attn = 3 * 4 * 2048 * 16 * 128 * 28
    assert dense.train_flops_per_token(QWEN, 4096) == 6 * n + attn
    assert dense.train_flops_per_token(QWEN, 4096) * 4 * 4096 == pytest.approx(1.922e14, rel=1e-3)


def test_mamba2_params_and_train_flops():
    # per layer: z and x 1024 x 2048, BC 1024 x 256, dt 1024 x 32, out 2048 x 1024
    layer = 2 * 1024 * 2048 + 1024 * 256 + 1024 * 32 + 2048 * 1024
    assert layer == 6_586_368
    n = 48 * layer + 50_280 * 1024
    assert ssm.matmul_params(MAMBA) == n == 367_632_384
    # SSD per token and layer at Q = 256, N = 128, P = 64, H = 32, G = 1:
    # C B^T 2 Q N = 65,536; scores x 2 Q P H = 1,048,576; states and
    # outputs 4 N P H = 1,048,576; conv 2 x 4 x (2048 + 256) = 18,432
    per = 65_536 + 1_048_576 + 1_048_576 + 18_432
    assert ssm.ssd_flops_per_token(MAMBA) == per
    assert ssm.train_flops_per_token(MAMBA, 4096) == 6 * n + 3 * 48 * per
    assert ssm.train_flops_per_token(MAMBA, 4096) * 4 * 4096 == pytest.approx(4.129e13, rel=1e-3)


def test_qwen3_decode_bytes():
    # bf16 weights: 2 x (N + norms), norms 57 x 2048 + 56 x 128 entries;
    # cache: 2 (K, V) x 28 x 8 x 128 x 2 bytes = 114,688 per position and row
    need = dense.decode_step(QWEN, 4, 1920, 2)
    weights = 2 * (1_720_451_072 + 57 * 2048 + 56 * 128)
    assert need["bytes"] == weights + 4 * 1920 * 114_688 + 4 * 114_688
    assert need["flops"] == 4 * (2 * 1_720_451_072 + 4 * 1920 * 16 * 128 * 28)


def mfu_decode(counts, step_s):
    ctx = SimpleNamespace(kind="decode", trace=SimpleNamespace(window_s=step_s * 10),
                          steps=10, counts=counts, peak=PEAK, chips=1)
    return H.load_module("metrics/mfu.decode.py").read(ctx)


@pytest.mark.parametrize("positions", [1, 1793, 1920.5, 2048])
def test_mfu_decode_at_most_100_for_bf16_weights_read_once(positions):
    """An implementation that reads the weights in bf16 once and the cache
    once takes at least bytes / 819 GB/s; at that time the share is 100%,
    and any slower step reads less."""
    need = dense.decode_step(QWEN, 4, positions, 2)
    c = {"flops_per_step": need["flops"], "bytes_per_step": need["bytes"]}
    fastest = need["bytes"] / PEAK["hbm_bytes_per_s"]
    assert need["flops"] / PEAK["bf16_flops_per_s"] < fastest  # bytes bound it
    assert mfu_decode(c, fastest) == pytest.approx(100.0)
    assert mfu_decode(c, 1.2 * fastest) < 100.0


def test_mfu_train_reader():
    ctx = SimpleNamespace(kind="train", trace=SimpleNamespace(window_s=3.0), steps=3,
                          counts={"flops_per_step": 197e12 * 0.5}, peak=PEAK, chips=1)
    assert H.load_module("metrics/mfu.train.py").read(ctx) == pytest.approx(50.0)
    ctx.kind = "decode"
    assert H.load_module("metrics/mfu.train.py").read(ctx) is None
