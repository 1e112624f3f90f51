"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode runs the kernels' logic on the CPU but not Mosaic's layout
rules: a dynamic offset on a tiled axis, or a block over the VMEM limit,
passes every interpret test and is refused only by the TPU compiler.  These
tests compile, without a chip, for a described ``v5e:2x2`` topology and
check that each program holds the kernel (``tpu_custom_call``).

Geometries: ``qwen1.5-0.5b`` (Hq = Hkv = 16, D = 64) and a GQA stack
(Hq = 32, Hkv = 8, D = 128), with the unified step's two ragged sub-batches
(8 decode slots at ``max_q = 1``; 2 prefill rows of 128-token chunks) and
paged decode, over 128 pages of 16 tokens per request (``max_seq`` 2048).
The benchmark's own geometry, minitron-8b (Hq = 48, Hkv = 8, D = 128, so
G = 6), compiles at its cells' shapes: 24 or 32 decode slots at
``max_q = 1`` and 4 prefill rows of 256-token chunks, over 256 pages of 16.
So does one shard of deepseek-7b at tp=4 (Hq = Hkv = 8, D = 128, so G = 1:
one query row per kv head and decode segment), at its cell's 16 decode
slots and 2 prefill rows of 256, over its per-chip pool.

The topology is described only inside the module fixture (one process may
load the TPU library at a time), and the persistent compile cache is off
around the compiles: a program compiled for a described chip cannot be
read back without one.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import pallas_paged_decode_attention
from repro.kernels.ragged_attention import pallas_ragged_paged_attention

PAGE, MAX_PAGES, POOL = 16, 128, 8 * 128 + 1
SLOTS, ROWS, CHUNK = 8, 2, 128
GEOMETRIES = {"qwen1.5-0.5b": (16, 16, 64), "gqa": (32, 8, 128)}
# (Hq, Hkv, D), max_pages, pool pages: bench/configs/minitron-8b-l8.json
# and the engine of bench/traffic/*.json
BENCH_GEOMETRY, BENCH_MAX_PAGES, BENCH_POOL = (48, 8, 128), 256, 6145
# one tp=4 shard of bench/configs/deepseek-7b-tp4.json; the pool of
# bench/traffic/chat.json
SHARD_GEOMETRY, SHARD_POOL = (8, 8, 128), 3176


@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


def _assert_kernel(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("sub_batch", ["prefill", "decode"])
def test_ragged_kernel_compiles(one_chip, geometry, dtype, sub_batch):
    hq, hkv, d = GEOMETRIES[geometry]
    max_q, segs = (CHUNK, ROWS) if sub_batch == "prefill" else (1, SLOTS)
    t = segs * max_q
    pool = ((POOL, hkv, PAGE, d), dtype)
    args = _shapes(one_chip, ((t, hq, d), dtype), pool, pool,
                   ((segs, MAX_PAGES), jnp.int32), ((segs,), jnp.int32),
                   ((segs,), jnp.int32), ((segs,), jnp.int32))
    _assert_kernel(lambda q, k, v, pt, qs, ql, kl:
                   pallas_ragged_paged_attention(q, k, v, pt, qs, ql, kl,
                                                 max_q=max_q), args)


@pytest.mark.parametrize("geometry,n_pages,segs,max_q", [
    (BENCH_GEOMETRY, BENCH_POOL, 24, 1), (BENCH_GEOMETRY, BENCH_POOL, 32, 1),
    (BENCH_GEOMETRY, BENCH_POOL, 4, 256), (SHARD_GEOMETRY, SHARD_POOL, 16, 1),
    (SHARD_GEOMETRY, SHARD_POOL, 2, 256)],
    ids=["decode24", "decode32", "prefill4x256", "tp4-decode16",
         "tp4-prefill2x256"])
def test_ragged_kernel_compiles_at_bench_geometry(one_chip, geometry, n_pages,
                                                  segs, max_q):
    hq, hkv, d = geometry
    dt = jnp.bfloat16
    pool = ((n_pages, hkv, PAGE, d), dt)
    args = _shapes(one_chip, ((segs * max_q, hq, d), dt), pool, pool,
                   ((segs, BENCH_MAX_PAGES), jnp.int32),
                   ((segs,), jnp.int32), ((segs,), jnp.int32),
                   ((segs,), jnp.int32))
    _assert_kernel(lambda q, k, v, pt, qs, ql, kl:
                   pallas_ragged_paged_attention(q, k, v, pt, qs, ql, kl,
                                                 max_q=max_q), args)


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_paged_decode_kernel_compiles(one_chip, geometry):
    hq, hkv, d = GEOMETRIES[geometry]
    dt = jnp.bfloat16
    args = _shapes(one_chip, ((SLOTS, 1, hq, d), dt),
                   ((POOL, hkv, PAGE, d), dt), ((POOL, hkv, PAGE, d), dt),
                   ((SLOTS, MAX_PAGES), jnp.int32), ((SLOTS,), jnp.int32))
    _assert_kernel(pallas_paged_decode_attention, args)
