"""Compile-only checks of the main-path Pallas kernels for a described TPU
v5e, at real shapes, with no chip attached.

Interpret mode cannot show what Mosaic refuses (operand types, shape casts
across sublanes, tiling); the TPU compiler installed beside JAX can, for a
topology that is described rather than attached.  This is the only test
file that describes the chip.  The description happens in the ``topo``
fixture -- never at import, in a ``skipif`` or in ``parametrize`` -- so every
test worker collects the same tests and only the worker that runs this file
loads the TPU library.  Each test asserts that the compiled program holds the
kernel (``tpu_custom_call``).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.csd_matvec import csd_matvec_kernel, csd_qsweep_kernel
from repro.kernels.paged_attention import paged_attention_kernel
from repro.kernels.paged_gather import paged_gather_kernel

# pendigits 16-10-10 at 8992 validation rows, padded to the 128-row tile
M, D_PLANES, N_PAD, Q = 9088, 8, 128, 4
# qwen2-0.5b decode: 14 query heads over 2 KV heads (G = 7), head_dim 64
B, HQ, HKV, HD, BS, CTX = 8, 14, 2, 64, 128, 2048
NBLK = CTX // BS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    """``shape(dims, dtype)``: an argument placed on the described chip."""
    return lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("k_in", [16, 10])
def test_csd_matvec_kernel_compiles(shape, no_persistent_cache, k_in):
    text = _compile(csd_matvec_kernel, shape((M, k_in), jnp.int32),
                    shape((D_PLANES, k_in, N_PAD), jnp.int8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k_in", [16, 10])
def test_csd_qsweep_kernel_compiles(shape, no_persistent_cache, k_in):
    text = _compile(csd_qsweep_kernel, shape((Q, M, k_in), jnp.int32),
                    shape((Q, D_PLANES, k_in, N_PAD), jnp.int8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_attention_kernel_compiles(shape, no_persistent_cache, dtype):
    pool = shape((B * NBLK, BS, HKV, HD), dtype)
    text = _compile(paged_attention_kernel, shape((B, 1, HQ, HD), dtype), pool,
                    pool, shape((B, NBLK), jnp.int32), shape((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_gather_kernel_compiles(shape, no_persistent_cache):
    text = _compile(paged_gather_kernel,
                    shape((B * NBLK, BS, HKV, HD), jnp.bfloat16),
                    shape((B, NBLK), jnp.int32))
    assert "tpu_custom_call" in text
