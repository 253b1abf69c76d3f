"""Compile the device paths for one described TPU v5e chip (no chip needed).

Each test compiles one kernel (or the epoch pass) at the size the chip runs
it and checks that the TPU compiler accepts it: block shapes that break the
(sublane, lane) tiling rule, primitives Mosaic cannot lower and programs
that do not fit the chip all fail here, where interpret mode passes them.
Nothing runs, so these tests say nothing about results or times.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.burst_gather import burst_gather_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.epoch_fastpath import _gather, _scan_i32
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_burst_gather_compiles(sds):
    hlo = _compile(lambda a, s, n: burst_gather_pallas(a, s, n, 1518),
                   sds((4096, 2048), jnp.uint8), sds((256,), jnp.int32),
                   sds((256,), jnp.int32))
    assert "tpu_custom_call" in hlo


def _qkv(sds):
    return (sds((1, 2048, 16, 128), jnp.bfloat16),
            sds((1, 2048, 8, 128), jnp.bfloat16),
            sds((1, 2048, 8, 128), jnp.bfloat16))


def test_flash_attention_forward_compiles(sds):
    hlo = _compile(lambda q, k, v: ops.flash_attention(q, k, v, impl="pallas"),
                   *_qkv(sds))
    assert "tpu_custom_call" in hlo


def test_flash_attention_backward_compiles(sds):
    """The training rule: Pallas forward, XLA backward (custom_vjp)."""
    def loss(q, k, v):
        out = ops.flash_attention(q, k, v, impl="pallas")
        return out.astype(jnp.float32).sum()

    # value and grad, as a train step takes them: the loss needs the
    # forward kernel's output
    hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), *_qkv(sds))
    assert "tpu_custom_call" in hlo


def test_decode_attention_compiles(sds):
    hlo = _compile(decode_attention_pallas, sds((1, 16, 128), jnp.bfloat16),
                   sds((1, 2048, 8, 128), jnp.bfloat16),
                   sds((1, 2048, 8, 128), jnp.bfloat16),
                   sds((1,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_rglru_scan_compiles(sds):
    hlo = _compile(rglru_scan_pallas, sds((2, 2048, 2560), jnp.bfloat16),
                   sds((2, 2048, 2560), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_ssd_scan_compiles(sds):
    hlo = _compile(lambda x, dt, a, b, c: ssd_scan_pallas(x, dt, a, b, c,
                                                          chunk=128),
                   sds((1, 2048, 64, 64), jnp.bfloat16),
                   sds((1, 2048, 64), jnp.float32), sds((64,), jnp.float32),
                   sds((1, 2048, 128), jnp.bfloat16),
                   sds((1, 2048, 128), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_epoch_pass_compiles_in_int32(sds):
    """The wire scan and steer gather at a 65,536-element slice, as the
    device runs them: int32 throughout (no emulated int64)."""
    n = 1 << 16
    scan = _compile(_scan_i32, sds((n,), jnp.int32), sds((n,), jnp.int32))
    gather = _compile(_gather, sds((1024,), jnp.int32), sds((n,), jnp.int32))
    assert "s64" not in scan and "s64" not in gather
