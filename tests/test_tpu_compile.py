"""The Pallas kernels of the model paths compile for a TPU v5e.

Each kernel is lowered and compiled for a described (not attached)
``v5e:2x2`` chip at published widths — mixtral-8x22b for attention (48
query heads, 8 KV heads, head_dim 128, window 4096), recurrentgemma-9b for
the RG-LRU scan (width 4096) — and its program must hold the kernel as a
``tpu_custom_call``. This catches what interpret mode cannot: block shapes
the TPU tiling refuses and primitives Mosaic cannot lower. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so under several test workers
only the worker given this file does so, and every worker still collects
the same tests. The persistent compilation cache is off around these
compiles: an entry written for a described chip cannot be read back
without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import decode_attention, paged_decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rglru_scan.ops import rglru_scan

# mixtral-8x22b attention and recurrentgemma-9b RG-LRU widths
H, HKV, HD, WINDOW = 48, 8, 128, 4096
LRU_WIDTH = 4096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _flash(sds):
    q = sds((1, 512, H, HD), jnp.bfloat16)
    kv = sds((1, 512, HKV, HD), jnp.bfloat16)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True, window=WINDOW, interpret=False)
    return fn, (q, kv, kv)


def _decode(sds):
    q = sds((4, H, HD), jnp.bfloat16)
    cache = sds((4, WINDOW, HKV, HD), jnp.bfloat16)
    kv_len = sds((4,), jnp.int32)
    fn = lambda q, k, v, n: decode_attention(q, k, v, n, rolling=True, interpret=False)
    return fn, (q, cache, cache, kv_len)


def _paged_decode(sds):
    B, P, PS, NP = 4, 64, 16, 16
    q = sds((B, H, HD), jnp.bfloat16)
    pages = sds((P, PS, HKV, HD), jnp.bfloat16)
    table = sds((B, NP), jnp.int32)
    kv_len = sds((B,), jnp.int32)
    fn = lambda q, k, v, t, n: paged_decode_attention(q, k, v, t, n, interpret=False)
    return fn, (q, pages, pages, table, kv_len)


def _rglru(sds):
    a = sds((2, 1024, LRU_WIDTH), jnp.float32)
    fn = lambda a, b: rglru_scan(a, b, interpret=False)
    return fn, (a, a)


@pytest.mark.parametrize("kernel", [_flash, _decode, _paged_decode, _rglru],
                         ids=["flash_attention", "decode_attention",
                              "paged_decode_attention", "rglru_scan"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = kernel(sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
