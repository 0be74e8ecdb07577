"""Each loop kind driven end to end on the CPU at SMOKE sizes, through the
same harness, loops, references and comparisons as a chip run (only the
look for a chip is skipped)."""
import math
import time

import jax
import pytest

import run
import smoke


def execute(kind, family, **kw):
    return run.execute("smoke", 2**33 + 5, 0.5, False, t0=time.perf_counter(),
                       spec=smoke.spec(kind, family, **kw))


def test_refuses_without_a_tpu():
    with pytest.raises(SystemExit, match="no TPU"):
        run.chip(1, {"TPU v5 lite": {}})


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_train_loop(family):
    out = execute("train", family)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    checks = out["checks"]
    assert list(checks)[-1] == "window_compiles" and checks["window_compiles"]["value"] == 0
    assert checks["data_mismatch"]["value"] == 0
    assert all(math.isfinite(c["value"]) for c in checks.values())
    assert out["correct"], checks


def test_decode_loop():
    out = execute("decode", "dense")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "decode_tokens_per_s", "decode_token_p95_ms"}
    assert out["checks"]["window_compiles"]["value"] == 0


def test_result_line_keys():
    out = execute("decode", "dense")
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == jax.devices()[0].platform
