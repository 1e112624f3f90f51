"""Each one-chip cell's two step programs (mixed and decode-only) compile
for a TPU v5e at the cell's real shapes: the configuration's widths, the
mix's slots, chunk, prefill rows and KV pool.  Nothing runs; the TPU
compiler refuses here what it would refuse on the chip (a kernel layout,
a program that does not fit the chip's memory).

The topology is described only inside the module fixture (one process
may load the TPU library at a time), and the persistent compile cache is
off around the compiles: a program compiled for a described chip cannot
be read back without one."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench import model as bm
from bench.tests.tiny import REPO


def one_chip_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]
                if w["chips"] == 1]


@pytest.fixture(scope="module")
def one_chip():
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


@pytest.mark.parametrize("profile", ["mixed", "decode"])
@pytest.mark.parametrize("name", one_chip_cells())
def test_step_compiles_for_v5e(one_chip, monkeypatch, name, profile):
    from repro.models import build_model
    from repro.models.common import ModelContext
    from repro.serving import ServeEngine
    # code that asks for the backend sees the CPU here: take the kernel
    monkeypatch.setattr(ModelContext, "paged_kernel",
                        lambda self: ("pallas", False))
    cell = harness.load_cell(REPO, name)
    eng_cfg = cell["mix"]["engine"]
    model = build_model(bm.model_spec(cell["config"], name),
                        param_dtype=jnp.bfloat16,
                        compute_dtype=jnp.bfloat16)
    model = dataclasses.replace(model, ctx=model.ctx.with_(
        cache_layout="paged", kv_page_size=eng_cfg["page_size"]))
    slots, chunk = eng_cfg["max_slots"], eng_cfg["chunk_size"]
    rows, max_seq = eng_cfg["prefill_rows"], eng_cfg["max_seq"]
    # the engine's own step function, on a bare instance (no device state)
    eng = ServeEngine.__new__(ServeEngine)
    eng.model = model
    eng.cfg = type("Cfg", (), {"max_slots": slots})()
    if profile == "mixed":
        fn = functools.partial(eng._unified_and_sample, max_q=chunk,
                               n_decode=slots)
        n, t = slots + rows, slots + rows * chunk
    else:
        fn = functools.partial(eng._unified_and_sample, max_q=1, n_decode=0)
        n, t = slots, slots

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(model.init, jax.random.key(0)))
    cache = jax.tree.map(sds, jax.eval_shape(functools.partial(
        model.init_cache, slots, max_seq, layout="paged",
        n_pages=eng_cfg["n_pages"])))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    mp = max_seq // eng_cfg["page_size"]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, i32(t), i32(t), i32(n), i32(n), i32(n), i32(n, mp),
        key, f32(n), i32(n), f32(n)).compile()
    assert "tpu_custom_call" in compiled.as_text()
