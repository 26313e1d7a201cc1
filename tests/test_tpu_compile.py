"""The Pallas kernels compile for a described TPU v5e at granite-3-2b's
head widths (32 query heads, 8 KV heads, head dim 64, bf16), and the paged
engine's two step programs, compiled there at granite-3-2b's widths, update
the layer-stacked page pools in place.

Nothing runs: the TPU compiler, installed alongside JAX, compiles for a chip
that is described and not attached. That catches what interpret mode cannot
— block shapes Mosaic refuses to tile, unsupported primitives, VMEM
overruns — at no chip time. The topology is described inside a fixture, so
collecting this file never loads the TPU library.
"""
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import init_params
from repro.models import model as M
from repro.models import transformer as T
from repro.runtime import engine as E

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


# ------------------------------------------- step programs: pools in place
# granite-3-2b at its widths, cut to 4 layers and 128 pages, at the served
# cell's engine geometry: 16 rows, pages of 16, chunks of 256, 2 decode
# steps a dispatch, 2560-token block tables (a 2048-token staging buffer
# under a lossy precision).
LAYERS, POOL_PAGES, ROWS, CHUNK, STEPS, CACHE = 4, 128, 16, 256, 2, 2560
# decode-only temporaries at this size when each layer's pool slab went
# through the layer scan's xs and ys (sliced out, written back, and the
# scan's new pools copied into the decode loop's carry)
SLAB_SCAN_DECODE_TEMP = 65.3 * 2 ** 20

_INST = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([0-9,]*)\]\S* ([\w-]+)\(")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.-]+)")


def _hlo_ops(text):
    """(name, shape, opcode, in_loop) of every instruction in optimized HLO
    text; ``in_loop``: its computation is reachable from a while body."""
    insts, calls, bodies, comp = {}, {}, set(), None
    for line in text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            insts[comp], calls[comp] = [], set()
            continue
        if comp is None:
            continue
        calls[comp].update(_CALLED.findall(line))
        bodies.update(re.findall(r"body=%([\w.-]+)", line))
        m = _INST.match(line)
        if m:
            name, dims, op = m.groups()
            insts[comp].append((name, tuple(int(d) for d in dims.split(",") if d), op))
    loop, todo = set(), list(bodies)
    while todo:
        c = todo.pop()
        if c not in loop:
            loop.add(c)
            todo.extend(calls.get(c, ()))
    return [(n, d, op, c in loop) for c, rows in insts.items() for n, d, op in rows]


def _step_programs(one_chip, kv_precision):
    cfg = get_config("granite-3-2b").replace(n_layers=LAYERS,
                                             kv_precision=kv_precision)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    on = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    params = on(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    pools = on(jax.eval_shape(lambda: T.paged_pools_init(
        cfg, POOL_PAGES, PAGE, stage_rows=ROWS, stage_len=2048)))
    state = M.PagedDecodeState(pools=pools,
                               block_tables=sds((ROWS, CACHE // PAGE), I32),
                               pos=sds((ROWS,), I32), last_tok=sds((ROWS,), I32))
    sync = on(jax.eval_shape(lambda: E.sync_state_init(ROWS, CACHE)))
    sig, row = E._DecodeSig(), sds((ROWS,), I32)
    mixed = E._chunk_decode_sync_paged.lower(
        params, state, sync, sds((ROWS, CHUNK), I32), row, row, row,
        sds((ROWS,), jnp.bool_), row, None, n=STEPS, cfg=cfg, sig=sig)
    decode = E._decode_n_sync_paged.lower(params, state, sync, None,
                                          n=STEPS, cfg=cfg, sig=sig)
    return pools, {"mixed": mixed.compile(), "decode": decode.compile()}


@pytest.mark.parametrize("kv_precision", ["", "int8"])
def test_step_programs_update_pools_in_place(kv_precision, one_chip,
                                             no_persistent_cache):
    """No dynamic-slice or dynamic-update-slice of a pool leaf or of one
    layer's slab of it, and no copy of a stacked leaf inside a loop: only
    the layout copies at the programs' entry and exit, two per leaf."""
    pools, programs = _step_programs(one_chip, kv_precision)
    leaves = Counter(x.shape for x in jax.tree.leaves(pools))
    slabs = {s[1:] for s in leaves} | {(1, *s[1:]) for s in leaves}
    for name, compiled in programs.items():
        ops_ = _hlo_ops(compiled.as_text())
        moved = [(n, d, op) for n, d, op, _ in ops_
                 if (d in leaves or d in slabs)
                 and ("dynamic" in op or "dynamic" in n.replace("_", "-"))]
        assert not moved, (name, moved)
        copies = [(n, d) for n, d, op, in_loop in ops_
                  if op == "copy" and d in leaves and in_loop]
        assert not copies, (name, copies)
        edge = Counter(d for n, d, op, _ in ops_ if op == "copy" and d in leaves)
        assert all(edge[d] <= 2 * k for d, k in leaves.items()), (name, edge)
    if not kv_precision:
        temp = programs["decode"].memory_analysis().temp_size_in_bytes
        assert temp < SLAB_SCAN_DECODE_TEMP, temp
