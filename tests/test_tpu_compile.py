"""The Pallas kernels compile for a described TPU v5e at granite-3-2b's
head widths (32 query heads, 8 KV heads, head dim 64, bf16).

Nothing runs: the TPU compiler, installed alongside JAX, compiles for a chip
that is described and not attached. That catches what interpret mode cannot
— block shapes Mosaic refuses to tile, unsupported primitives, VMEM
overruns — at no chip time. The topology is described inside a fixture, so
collecting this file never loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, H, KVH, HD = 2, 32, 8, 64           # granite-3-2b attention widths
PAGES, PAGE, MP = 64, 16, 8            # pool pages, tokens a page, pages a row
C, L = 128, 1024                       # chunk width, cache / prompt length
BF, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernels():
    """name -> (fn, [(shape, dtype), ...])."""
    paged = [((B, H, HD), BF), ((PAGES, PAGE, KVH, HD), BF),
             ((PAGES, PAGE, KVH, HD), BF), ((B, MP), I32), ((B,), I32)]
    paged8 = [paged[0], ((PAGES, PAGE, KVH, HD), I8),
              ((PAGES, PAGE, KVH, HD), I8), paged[3], paged[4],
              ((PAGES, PAGE, KVH), F32), ((PAGES, PAGE, KVH), F32)]
    chunk = [((B, C, H, HD), BF), ((B, L, KVH, HD), BF), ((B, L, KVH, HD), BF),
             ((B, L), I32), ((B,), I32), ((B,), I32)]
    chunk8 = [chunk[0], ((B, L, KVH, HD), I8), ((B, L, KVH, HD), I8),
              *chunk[3:], ((B, L, KVH), F32), ((B, L, KVH), F32)]
    flash = [((B, L, H, HD), BF), ((B, L, KVH, HD), BF), ((B, L, KVH, HD), BF)]
    flash8 = [flash[0], ((B, L, KVH, HD), I8), ((B, L, KVH, HD), I8),
              ((B,), I32), ((B, L, KVH), F32), ((B, L, KVH), F32)]
    decode = [((B, H, HD), BF), ((B, L, KVH, HD), BF), ((B, L, KVH, HD), BF),
              ((B, L), I32), ((B,), I32)]
    return {
        "paged": (lambda *a: ops.paged_decode_attention(*a, impl="pallas"),
                  paged),
        "paged_int8": (lambda q, k, v, bt, pos, ks, vs:
                       ops.paged_decode_attention(q, k, v, bt, pos, k_scale=ks,
                                                  v_scale=vs, impl="pallas"),
                       paged8),
        "chunk": (lambda *a: ops.chunk_attention(*a, impl="pallas"), chunk),
        "chunk_int8": (lambda q, k, v, sp, p0, nv, ks, vs:
                       ops.chunk_attention(q, k, v, sp, p0, nv, k_scale=ks,
                                           v_scale=vs, impl="pallas"), chunk8),
        "flash": (lambda *a: ops.flash_attention(*a, impl="pallas"), flash),
        "flash_ragged": (lambda q, k, v, lens: ops.flash_attention(
            q, k, v, lens, impl="pallas"), flash[:3] + [((B,), I32)]),
        "flash_ragged_int8": (lambda q, k, v, lens, ks, vs:
                              ops.flash_attention(q, k, v, lens, k_scale=ks,
                                                  v_scale=vs, impl="pallas"),
                              flash8),
        "decode": (lambda *a: ops.decode_attention(*a, impl="pallas"), decode),
    }


@pytest.mark.parametrize("name", sorted(_kernels()))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, specs = _kernels()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
