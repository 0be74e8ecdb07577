"""Pallas kernels swept over shapes/dtypes vs the pure-jnp oracles
(interpret=True executes the kernel body on CPU)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gmm import gmm
from repro.kernels.ibn_conv import ibn_pointwise
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.wstream import wstream_matmul

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


@pytest.mark.parametrize("m,w_shape,layer,transpose,block_k,block_n", [
    (1, (3, 256, 384), 2, False, 128, 128),  # two K tiles, last layer
    (4, (2, 256, 256), 1, False, None, None),  # default blocks
    (8, (256, 300), 0, False, 128, 128),  # one (K, N) matrix, ragged N
    (4, (1000, 256), 0, True, 128, 256),  # tied (V, D) table, ragged V
    (37, (2, 256, 384), 1, False, 128, 128),  # rows off the sublane tiling
])
def test_wstream(m, w_shape, layer, transpose, block_k, block_n):
    """x @ w[layer].astype(bf16) from the fp32 weight read in place: within
    bf16's rounding of the f32-accumulated product."""
    ks = jax.random.split(RNG, 2)
    k = w_shape[-1] if transpose else w_shape[-2]
    x = jax.random.normal(ks[0], (m, k), jnp.bfloat16)
    w = jax.random.normal(ks[1], w_shape, jnp.float32)
    y = wstream_matmul(x, w, layer, transpose=transpose, block_k=block_k,
                       block_n=block_n, interpret=True)
    wl = (w if w.ndim == 2 else w[layer]).astype(jnp.bfloat16)
    want = jnp.matmul(x, wl.T if transpose else wl,
                      preferred_element_type=jnp.float32)
    assert y.dtype == jnp.bfloat16 and y.shape == want.shape
    err = jnp.abs(y.astype(jnp.float32) - want)
    assert bool(jnp.all(err <= 2.0**-8 * jnp.abs(want) + 1e-5)), float(err.max())


def _smoke_decode(monkeypatch, stream: bool, steps: int = 3):
    """Greedy one-token decode steps at SMOKE size after a 4-token prompt,
    on the weight-streaming path (TPU dispatch patched in, the kernel in
    interpret mode) or on XLA's. Returns each step's tokens and logits, and
    the weight shape of each kernel call traced."""
    from repro import configs
    from repro.kernels import ops
    from repro.models import api, transformer

    cfg = dataclasses.replace(configs.smoke("qwen3_1_7b"), tie_embeddings=True)
    params = api.init(RNG, cfg)
    calls = []

    def counted(*a, **k):
        calls.append(a[1].shape)
        return wstream_matmul(*a, **k, interpret=True)

    monkeypatch.setattr(ops, "wstream", counted)
    monkeypatch.setattr(ops, "on_tpu", lambda: stream)
    step = jax.jit(functools.partial(transformer.decode_step, cfg=cfg))
    cache = transformer.init_cache(cfg, 2, 16)
    prompt = jax.random.randint(RNG, (2, 4), 0, cfg.vocab_size, jnp.int32)
    tok, out = prompt, []
    for t in range(steps):
        index = jnp.int32(0 if t == 0 else 3 + t)
        logits, cache = step(params, cache, tok, index)
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        out.append((tok, logits[:, -1]))
    return out, calls


def test_decode_step_streams_weights_through_the_kernel(monkeypatch):
    """On a TPU a few-row decode step reads each weight matrix through the
    kernel (7 per layer body, 1 for the tied head) with the tokens of XLA's
    path and logits within one bf16 ulp of the largest."""
    from repro import configs

    want, none = _smoke_decode(monkeypatch, stream=False)
    got, calls = _smoke_decode(monkeypatch, stream=True)
    assert none == []
    c = configs.smoke("qwen3_1_7b")
    n, d, q, kv, f = c.num_layers, c.d_model, c.num_heads * c.head_dim, \
        c.num_kv_heads * c.head_dim, c.d_ff
    per_trace = [(n, d, q), (n, d, kv), (n, d, kv), (n, q, d),  # q k v o
                 (n, d, f), (n, d, f), (n, f, d),  # gate up down
                 (c.vocab_size, d)]  # the tied head
    # traced twice: the prompt's 8 rows, then the one-token steps' 2
    assert calls == per_trace * 2
    for (tok_w, lg_w), (tok_g, lg_g) in zip(want, got):
        assert jnp.array_equal(tok_w, tok_g)
        top = float(jnp.max(jnp.abs(lg_w)))
        ulp = 2.0 ** (jnp.floor(jnp.log2(top)) - 7)
        assert float(jnp.max(jnp.abs(lg_w - lg_g))) <= ulp


def test_wide_calls_and_training_keep_xla_matmuls(monkeypatch):
    """A decode call of 1,024 rows and a training forward never take the
    kernel, even on a TPU."""
    from repro import configs
    from repro.kernels import ops
    from repro.models import api, transformer

    def kernel(*a, **k):
        raise AssertionError("call reached the weight-streaming kernel")

    monkeypatch.setattr(ops, "wstream", kernel)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(configs.smoke("qwen3_1_7b"), tie_embeddings=True)
    params = api.init(RNG, cfg)
    tokens = jnp.zeros((4, 256), jnp.int32)
    cache = transformer.init_cache(cfg, 4, 256)
    jax.eval_shape(functools.partial(transformer.decode_step, cfg=cfg),
                   params, cache, tokens, jnp.int32(0))
    jax.eval_shape(lambda p, b: api.loss_fn(p, b, cfg)[0], params,
                   {"tokens": tokens[:1, :8], "labels": tokens[:1, :8]})


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
