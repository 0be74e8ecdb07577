"""Compiles for a described TPU v5e, with no chip attached: the Pallas kernels
of the main path at real widths, and one qwen3-1.7b decode step, which must
fit the chip's HBM and read its weights through the weight-streaming kernel.
A compile that passes says nothing about results or times; it catches what
the chip's compiler refuses (block shapes off the tiling, programs over HBM)
without chip time.

The topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and test workers each import this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# v5e HBM capacity as the TPU compiler states it ("... of 15.75G hbm")
HBM_BYTES = 15.75 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_call(name):
    """(function, argument shapes) at the widths the kernels serve."""
    from repro.kernels import flash_attention, gmm, ssd_scan, wstream

    bf16, f32 = jnp.bfloat16, jnp.float32
    # qwen3-1.7b weights at the most rows kernels.ops.streams admits (479):
    # the stacked fp32 MLP up-projection, layer 27, and the tied (V, D) table
    if name == "wstream_mlp":
        return (lambda x, w: wstream.wstream_matmul(x, w, 27)), [
            ((479, 2048), bf16), ((28, 2048, 6144), f32)]
    if name == "wstream_table":
        return (lambda x, w: wstream.wstream_matmul(x, w, transpose=True)), [
            ((479, 2048), bf16), ((151936, 2048), f32)]
    if name == "flash_attention":  # qwen3-1.7b: H16, KV8, S4096, hd128
        return flash_attention.flash_attention, [
            ((1, 16, 4096, 128), bf16), ((1, 8, 4096, 128), bf16),
            ((1, 8, 4096, 128), bf16)]
    if name == "gmm":  # MoE dispatch buffers (E8, C512, D2048) x (D2048, F768)
        return gmm.gmm, [((8, 512, 2048), bf16), ((8, 2048, 768), bf16)]
    # mamba2-370m: H32, P64, N128, chunk 256
    b, s, h, p, n = 1, 4096, 32, 64, 128
    return (lambda *a: ssd_scan.ssd_scan(*a, chunk=256)), [
        ((b, s, h, p), bf16), ((b, s, h), f32), ((h,), f32),
        ((b, s, 1, n), bf16), ((b, s, 1, n), bf16)]


@pytest.mark.parametrize("name", ["flash_attention", "gmm", "ssd_scan",
                                  "wstream_mlp", "wstream_table"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_call(name)
    args = [_spec(shape, dtype, one_chip) for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _calls_per_computation(hlo: str, kernel: str) -> dict:
    """{computation header: number of calls of the named kernel}"""
    out = {}
    for comp in re.split(r"\n(?=\S)", hlo):
        n = len(re.findall(rf"^\s*(?:ROOT )?%{kernel}[.\d]* = .* custom-call\(",
                           comp, flags=re.M))
        if n:
            out[comp.split(" ", 1)[0]] = n
    return out


@pytest.mark.parametrize("tied", [True, False])
def test_qwen3_decode_step_fits_v5e_hbm(one_chip, monkeypatch, tied):
    """The one-token step of qwen3-1.7b on the TPU path, with tied embeddings
    (published tie_word_embeddings, as the benchmark runs it) and untied (the
    registry's, with a (2048, 151936) head): every weight matrix read in
    place by the weight-streaming kernel, 7 calls in the layer body and 1 for
    the head, no bf16 copy of a stacked weight, the table or the head, and
    arguments + temporaries under 9.3e9 B plus the untied head's fp32 bytes."""
    import dataclasses

    from repro import configs
    from repro.config import RunConfig, ServeConfig, ShapeConfig
    from repro.kernels import ops
    from repro.train import steps

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(configs.get("qwen3_1_7b"), tie_embeddings=tied)
    run = RunConfig(model=cfg, shape=ShapeConfig("decode", 2048, 4, "decode"),
                    serve=ServeConfig(kv_dtype="bfloat16"))
    step, _, _, _ = steps.make_decode_step(run, None)
    on_chip = lambda tree: jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, one_chip), tree)
    aparams = steps.abstract_params(cfg)
    args = (on_chip(aparams),
            on_chip(steps.abstract_cache(cfg, run.shape, "bfloat16")),
            _spec((4, 1), jnp.int32, one_chip), _spec((), jnp.int32, one_chip))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(*args).compile()
    hlo = compiled.as_text()
    table = aparams["embed"]["embedding"].shape
    weights = [x.shape for x in jax.tree.leaves(aparams["layers"])
               if x.ndim == 3] + [table, table[::-1]]
    copies = [s for s in weights if f"bf16[{','.join(map(str, s))}]" in hlo]
    assert len(weights) == 9 and not copies, copies
    calls = _calls_per_computation(hlo, "wstream_matmul")
    assert sorted(calls.values()) == [1, 7], calls
    assert calls.get("ENTRY") == 1, calls
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB of {HBM_BYTES / 2**30} GiB"
    head = 0 if tied else 4 * table[0] * table[1]
    assert used < 9.3e9 + head, f"{used / 1e9:.3f}e9 B"
