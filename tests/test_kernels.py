"""Pallas kernels swept over shapes/dtypes vs the pure-jnp oracles
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gmm import gmm
from repro.kernels.ibn_conv import ibn_pointwise
from repro.kernels.ssd_scan import ssd_scan

RNG = jax.random.PRNGKey(0)


@pytest.mark.parametrize("b,h,kv,sq,skv,hd,causal", [
    (1, 4, 4, 64, 64, 32, True),
    (2, 4, 2, 64, 64, 32, True),
    (1, 8, 1, 128, 128, 64, True),   # MQA
    (2, 4, 1, 96, 160, 32, False),   # cross/unaligned
    (1, 2, 2, 200, 200, 16, True),   # ragged blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, h, kv, sq, skv, hd, causal, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, h, sq, hd), dtype)
    k = jax.random.normal(ks[1], (b, kv, skv, hd), dtype)
    v = jax.random.normal(ks[2], (b, kv, skv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert jnp.max(jnp.abs(out.astype(jnp.float32)
                           - want.astype(jnp.float32))) < tol


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 64, 4, 16, 1, 32, 16),
    (1, 128, 4, 8, 2, 16, 32),
    (2, 96, 2, 32, 1, 64, 32),   # padded tail chunk
    (1, 48, 8, 16, 4, 8, 48),    # single chunk
])
def test_ssd_scan(b, s, h, p, g, n, chunk):
    ks = jax.random.split(RNG, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))
    y, st = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, sr = ref.ssd_scan_ref(x, dt, A, B, C, chunk)
    assert jnp.max(jnp.abs(y - yr)) < 2e-3
    assert jnp.max(jnp.abs(st - sr)) < 2e-3


def test_ssd_scan_matches_model_chunked_path():
    """Kernel vs the model's lax.scan chunked implementation."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(RNG, 5)
    b, s, h, p, g, n = 2, 64, 4, 16, 1, 32
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))
    y1, s1 = ssd_scan(x, dt, A, B, C, chunk=16, interpret=True)
    y2, s2 = ssd_chunked(x, dt, A, B, C, 16)
    assert jnp.max(jnp.abs(y1 - y2)) < 2e-3
    assert jnp.max(jnp.abs(s1 - s2)) < 2e-3


@pytest.mark.parametrize("e,c,d,f", [
    (4, 64, 32, 48), (2, 100, 70, 30), (8, 128, 256, 128), (1, 8, 8, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm(e, c, d, f, dtype):
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (e, c, d), dtype)
    w = jax.random.normal(ks[1], (e, d, f), dtype)
    y = gmm(x, w, block_c=32, block_f=32, block_d=32, interpret=True)
    want = ref.gmm_ref(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    assert jnp.max(jnp.abs(y.astype(jnp.float32)
                           - want.astype(jnp.float32))) < tol


@pytest.mark.parametrize("n,ci,co,act", [
    (256, 32, 64, "relu"), (100, 48, 40, "silu"), (512, 128, 96, "none"),
    (64, 16, 8, "relu"),
])
def test_ibn_pointwise(n, ci, co, act):
    ks = jax.random.split(RNG, 3)
    x = jax.random.normal(ks[0], (n, ci), jnp.float32)
    w = jax.random.normal(ks[1], (ci, co), jnp.float32)
    b = jax.random.normal(ks[2], (co,), jnp.float32)
    y = ibn_pointwise(x, w, b, act=act, block_n=64, block_f=32, block_k=32,
                      interpret=True)
    assert jnp.max(jnp.abs(y - ref.ibn_pointwise_ref(x, w, b, act))) < 1e-4


def test_flash_pallas_routes_cache_calls_to_chunked(monkeypatch):
    """attn_impl="flash_pallas": decode-shaped calls (kv_len, traced
    q_offset) take the chunked path by condition, even on a TPU; cache-free
    calls there go to the kernel, and its errors propagate."""
    from repro.config import ModelConfig
    from repro.kernels import ops
    from repro.models import layers as L

    cfg = ModelConfig(attn_impl="flash_pallas", attn_chunk=8)
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 16))
    k = jax.random.normal(ks[1], (2, 24, 2, 16))
    v = jax.random.normal(ks[2], (2, 24, 2, 16))
    kw = dict(causal=True, q_offset=jnp.int32(5), kv_len=jnp.int32(6))
    want = L.chunked_attention(q, k, v, chunk=8, **kw)

    def kernel(*a, **k_):
        raise AssertionError("call reached the Pallas kernel")

    monkeypatch.setattr(ops, "flash_attention", kernel)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert jnp.array_equal(L.attention_core(q, k, v, cfg, **kw), want)

    sentinel = jnp.zeros_like(q)
    monkeypatch.setattr(ops, "flash_attention", lambda *a, **k_: sentinel)
    assert L.attention_core(q, k, v, cfg, causal=True) is sentinel

    # a kernel that fails on the chip raises; nothing falls back in silence
    monkeypatch.setattr(ops, "flash_attention", kernel)
    with pytest.raises(AssertionError, match="Pallas kernel"):
        L.attention_core(q, k, v, cfg, causal=True)


def test_on_tpu_does_not_hide_backend_errors(monkeypatch):
    from repro.kernels import ops

    def broken():
        raise RuntimeError("backend failed to initialize")

    monkeypatch.setattr(ops.jax, "devices", broken)
    with pytest.raises(RuntimeError, match="backend failed"):
        ops.on_tpu()
