"""Each four-chip cell's two sharded step programs (mixed and decode-only)
compile for a TPU v5e-4 host at the cell's real shapes: the
configuration's widths and tensor-parallel degree, the mix's slots, chunk,
prefill rows and per-chip KV pool.  Nothing runs; the TPU compiler
refuses here what it would refuse on the chips (a kernel layout at the
shard's geometry, a program that does not fit one chip's memory), and
the compiled step holds the ragged kernel (``tests/test_tp_serving.py``
counts the step's collectives).

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
import numpy as np
import pytest

from bench import harness
from bench import model as bm
from bench.tests.tiny import REPO


def four_chip_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]
                if w["chips"] == 4]


@pytest.fixture(scope="module")
def host():
    """The four chips of a described v5e:2x2 host."""
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
    yield topo.devices[:4]
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("profile", ["mixed", "decode"])
@pytest.mark.parametrize("name", four_chip_cells())
def test_sharded_step_compiles_for_v5e_host(host, monkeypatch, name,
                                            profile):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.models import build_model
    from repro.models.common import ModelContext
    from repro.serving import sharded as shard
    # code that asks for the backend sees the CPU here: take the kernel
    monkeypatch.setattr(ModelContext, "paged_kernel",
                        lambda self: ("pallas", False))
    cell = harness.load_cell(REPO, name)
    cfg, eng_cfg = cell["config"], cell["mix"]["engine"]
    tp = int(cfg["serving"]["tp"])
    assert tp * int(cfg["serving"].get("pp", 1)) == len(host)
    model = build_model(bm.model_spec(cfg, name), param_dtype=jnp.bfloat16,
                        compute_dtype=jnp.bfloat16)
    model = dataclasses.replace(model, ctx=model.ctx.with_(
        cache_layout="paged", kv_page_size=eng_cfg["page_size"]))
    mesh = Mesh(np.array(host).reshape(1, tp), (shard.PP_AXIS,
                                                shard.TP_AXIS))
    slots, chunk = eng_cfg["max_slots"], eng_cfg["chunk_size"]
    rows, max_seq = eng_cfg["prefill_rows"], eng_cfg["max_seq"]
    # the engine's own step builder, as ServeEngine calls it
    if profile == "mixed":
        fn = shard.build_sharded_step(model, mesh, tp, 1, max_slots=slots,
                                      max_q=chunk, n_decode=slots)
        n, t = slots + rows, slots + rows * chunk
    else:
        fn = shard.build_sharded_step(model, mesh, tp, 1, max_slots=slots,
                                      max_q=1, n_decode=0)
        n, t = slots, slots

    def placed(shapes, pspecs):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            shapes, pspecs)
    params = placed(jax.eval_shape(model.init, jax.random.key(0)),
                    shard.param_pspecs(model, tp, 1))
    cache = placed(jax.eval_shape(functools.partial(
        model.init_cache, slots, max_seq, layout="paged",
        n_pages=eng_cfg["n_pages"])), shard.cache_pspecs(model, tp, 1))
    rep = NamedSharding(mesh, P())
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep)
    mp = max_seq // eng_cfg["page_size"]
    text = fn.lower(params, cache, i32(t), i32(t), i32(n), i32(n), i32(n),
                    i32(n, mp), key, f32(n), i32(n), f32(n)).compile() \
        .as_text()
    assert "tpu_custom_call" in text
