"""The main path's Pallas kernels compile for a TPU v5e at qwen2-0.5b widths.

Each test compiles one kernel for a *described* v5e chip (no chip attached)
at the shape the fused masked update and the compressed upload give it in
the FL round at published widths: the stacked ``wq`` LoRA leaf of
qwen2-0.5b, (layers, d_model, rank) = (24, 896, 8), after the wrappers'
``_tile2d`` flattening. The compiled text must hold a ``tpu_custom_call``:
the kernel went through Mosaic, not the Pallas interpreter.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU compiler library at a time, and a module
that loaded it while being collected would give parallel test workers
different test lists.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels import compress, masked_update
from repro.kernels import ops

QWEN = ARCHS["qwen2-0.5b"]
WQ_LEAF = (QWEN.num_layers, QWEN.d_model, QWEN.lora_rank)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: entries
    compiled for a described device cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _tile(dtype, sharding):
    """The (rows, 128) tile grid ``ops._tile2d`` makes of the wq LoRA leaf."""
    shape = jax.eval_shape(ops._tile2d, jax.ShapeDtypeStruct(WQ_LEAF, dtype)).shape
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _scal(sharding):
    return jax.ShapeDtypeStruct((1, masked_update.SCAL_WIDTH), jnp.float32,
                                sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_masked_sgd_update_compiles_for_v5e(one_chip, dtype):
    x = _tile(dtype, one_chip)
    mask = _tile(jnp.float32, one_chip)
    assert x.shape == (1536, 128)  # 172032 values, rows padded to 256s

    def step(p, g, mu, mk, scal):
        return masked_update.masked_sgd_update_2d(
            p, g, mu, mk, scal, momentum=0.9, interpret=False
        )

    assert "tpu_custom_call" in _compiled_text(step, x, x, x, mask, _scal(one_chip))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_masked_adamw_update_compiles_for_v5e(one_chip, dtype):
    x = _tile(dtype, one_chip)
    mask = _tile(jnp.float32, one_chip)

    def step(p, g, m, v, mk, scal):
        return masked_update.masked_adamw_update_2d(
            p, g, m, v, mk, scal, interpret=False
        )

    assert "tpu_custom_call" in _compiled_text(step, x, x, x, x, mask, _scal(one_chip))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_fake_compress_int8_compiles_for_v5e(one_chip, dtype):
    def channel(x, scal):
        return compress.fake_compress_2d(x, scal, qmax=127, interpret=False)

    text = _compiled_text(channel, _tile(dtype, one_chip), _scal(one_chip))
    assert "tpu_custom_call" in text
