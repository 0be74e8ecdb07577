"""Compiles for a described TPU v5e, with no chip attached: the Pallas kernels
of the main path at real widths, and one qwen3-1.7b decode step, which must
fit the chip's HBM. A compile that passes says nothing about results or
times; it catches what the chip's compiler refuses (block shapes off the
tiling, programs over HBM) without chip time.

The topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and test workers each import this file.
"""
import os

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
    from repro.kernels import flash_attention, gmm, ssd_scan

    bf16, f32 = jnp.bfloat16, jnp.float32
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


@pytest.mark.parametrize("name", ["flash_attention", "gmm", "ssd_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_call(name)
    args = [_spec(shape, dtype, one_chip) for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_decode_step_fits_v5e_hbm(one_chip):
    from repro import configs
    from repro.config import RunConfig, ServeConfig, ShapeConfig
    from repro.train import steps

    cfg = configs.get("qwen3_1_7b")
    run = RunConfig(model=cfg, shape=ShapeConfig("decode", 2048, 4, "decode"),
                    serve=ServeConfig(kv_dtype="bfloat16"))
    step, _, _, _ = steps.make_decode_step(run, None)
    on_chip = lambda tree: jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, one_chip), tree)
    args = (on_chip(steps.abstract_params(cfg)),
            on_chip(steps.abstract_cache(cfg, run.shape, "bfloat16")),
            _spec((4, 1), jnp.int32, one_chip), _spec((), jnp.int32, one_chip))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB of {HBM_BYTES / 2**30} GiB"
