"""Mesh-sharded serving: refusal surfaces, per-shard kernel bounds, and
the sharded engine's invariants on a forced multi-device CPU mesh.

The main pytest process sees ONE device (no XLA_FLAGS), so everything
that needs a real mesh runs in a subprocess via ``run_with_devices`` —
the same pattern as tests/test_distributed.py.  In-process tests cover
the validation/refusal paths (which must fail identically on any host:
shape divisibility before device count), the analytic collective
accounting, and the concrete kernel-bounds pass at per-shard shapes.
"""

import re
import textwrap
from pathlib import Path

import pytest

from conftest import tiny_dense_spec
from repro.analysis.kernel_bounds import (KernelCase, check_kernel_bounds,
                                          default_cases, sharded_cases)
from repro.serving import EngineConfig
from repro.serving.sharded import collective_stats, validate_engine_sharding
from test_distributed import run_with_devices

FIXDIR = Path(__file__).resolve().parent / "fixtures" / "lint"


# ---------------------------------------------------------------------------
# refusal surfaces — must fail the same way on any host
# ---------------------------------------------------------------------------

def _cfg(**kw):
    base = dict(max_slots=4, max_seq=64, chunk_size=4, prefill_rows=2,
                cache_layout="paged", page_size=8, unified=True)
    base.update(kw)
    return EngineConfig(**base)


def test_refuses_non_unified():
    with pytest.raises(ValueError, match="unified"):
        validate_engine_sharding(tiny_dense_spec(), _cfg(tp=2, unified=False))


def test_refuses_indivisible_heads():
    # tiny spec has n_kv_heads=2: tp=4 cannot give every rank a kv head
    with pytest.raises(ValueError, match="n_kv_heads"):
        validate_engine_sharding(tiny_dense_spec(), _cfg(tp=4))


def test_refuses_indivisible_vocab_untied():
    with pytest.raises(ValueError, match="vocab"):
        validate_engine_sharding(
            tiny_dense_spec(vocab=255, tied_embeddings=False), _cfg(tp=2))


def test_refuses_indivisible_layer_repeats():
    with pytest.raises(ValueError, match="repeats"):
        validate_engine_sharding(tiny_dense_spec(n_layers=3), _cfg(pp=2))


def test_refuses_too_few_devices_with_recipe():
    """Device-count check comes last and names the XLA_FLAGS recipe —
    the main pytest process has exactly one visible device."""
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        validate_engine_sharding(tiny_dense_spec(), _cfg(tp=2))


def test_engine_backend_refuses_unsupported_axes():
    """A ParallelismConfig the live engine cannot lower (ep>1) surfaces
    as an error Report naming the unsupported axis and the supported
    ones."""
    from repro.core.parallelism import ParallelismConfig
    from repro.core.stages import Workload
    from repro.scenario import Scenario, run

    sc = Scenario(model=tiny_dense_spec(),
                  workload=Workload(batch=2, tau_p=8, tau_d=4),
                  parallelism=ParallelismConfig(ep=2))
    rep = run([sc], backend="engine")[0]
    assert rep.status == "error"
    assert "ep=2" in rep.error
    assert "tp" in rep.error and "pp" in rep.error


@pytest.mark.parametrize("mode", ["disaggregated", "speculative"])
def test_engine_backend_refuses_parallel_disagg_and_spec(mode):
    """Only the unified chunked path is threaded through shard_map; the
    other engine lowerings refuse sharded scenarios instead of silently
    running tp=pp=1."""
    from repro.core.parallelism import ParallelismConfig
    from repro.core.stages import Workload
    from repro.scenario import Scenario, SpeculativeSpec, run

    kw = {}
    if mode == "speculative":
        kw["speculative"] = SpeculativeSpec(
            draft=tiny_dense_spec(n_layers=1), n=2)
    sc = Scenario(model=tiny_dense_spec(), mode=mode,
                  workload=Workload(batch=2, tau_p=8, tau_d=4),
                  parallelism=ParallelismConfig(tp=2), **kw)
    rep = run([sc], backend="engine")[0]
    assert rep.status == "error"
    assert mode in rep.error and "TP=2" in rep.error


# ---------------------------------------------------------------------------
# analytic collective accounting
# ---------------------------------------------------------------------------

def test_collective_stats_counts():
    spec = tiny_dense_spec(n_heads=8, n_kv_heads=4)  # untied, 2 layers
    t_pack, n_segs = 12, 4
    coll, nbytes = collective_stats(spec, 2, 1, t_pack, n_segs, 4)
    # 2 psums per layer + 1 logits all_gather for the untied lm_head
    assert coll == 2 * spec.n_layers + 1
    # each psum moves 2(tp-1)/tp x payload; payload = t_pack*d_model*4
    assert nbytes > 2 * spec.n_layers * t_pack * spec.d_model * 4 // 2
    coll_pp, _ = collective_stats(spec, 1, 2, t_pack, n_segs, 4)
    assert coll_pp == 2 + 1  # pp ppermutes + broadcast psum
    assert collective_stats(spec, 1, 1, t_pack, n_segs, 4) == (0, 0)


# ---------------------------------------------------------------------------
# kernel bounds at per-shard shapes
# ---------------------------------------------------------------------------

def test_sharded_kernel_cases_registered_and_clean():
    """The default registry now re-checks the kernels at the local
    geometry shard_map workers see (kv heads / tp), and they pass."""
    names = [c.name for c in default_cases()]
    assert any("tp2" in n for n in names)
    assert any("tp4" in n for n in names)
    findings = check_kernel_bounds(sharded_cases())
    assert findings == [], [(f.code, f.message) for f in findings]


def test_seeded_global_head_walk_caught_at_marker():
    """The seeded fixture walks the GLOBAL kv-head axis over a per-shard
    pool; the concrete pass must flag RPL301 exactly on the marked
    ``pallas_call`` line.  (The fixture name deliberately misses the
    ``rpl*.py`` glob: AST linting cannot see value-dependent bounds.)"""
    import importlib.util

    fix = FIXDIR / "sharded_rpl301_kv_head_walk.py"
    source = fix.read_text()
    golden = {(i, code)
              for i, line in enumerate(source.splitlines(), 1)
              for m in [re.search(r"#\s*EXPECT:\s*(RPL\d+)", line)] if m
              for code in [m.group(1)]}
    assert golden, "fixture lost its EXPECT markers"

    mspec = importlib.util.spec_from_file_location("sharded_fix", fix)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    findings = check_kernel_bounds(
        [KernelCase("sharded_kv_head_walk", mod.local_shard_case)])
    got = {(f.line, f.code) for f in findings}
    assert got == golden, [(f.code, f.line, f.message) for f in findings]


# ---------------------------------------------------------------------------
# the sharded engine itself — forced multi-device subprocesses
# ---------------------------------------------------------------------------

_PRELUDE = """\
import jax, jax.numpy as jnp
from repro.core.modelspec import AttnSpec, ModelSpec
from repro.models import build_model
from repro.serving import EngineConfig, Request, ServeEngine

spec = ModelSpec(name="t8", d_model=64, n_layers=2, n_heads=8,
                 n_kv_heads=4, d_head=16, d_ff=128, vocab=256,
                 attn=AttnSpec(kind="full", causal=True))
model = build_model(spec, mesh=None, param_dtype=jnp.float32,
                    compute_dtype=jnp.float32)
params = model.init(jax.random.key(0))

def run(tp, pp, n_pages=None, prefix=False, prompts=None, guards=True):
    eng = ServeEngine(model, params,
                      EngineConfig(max_slots=4, max_seq=64, chunk_size=4,
                                   prefill_rows=2, cache_layout="paged",
                                   page_size=8, unified=True, tp=tp,
                                   pp=pp, n_pages=n_pages,
                                   prefix_cache=prefix,
                                   debug_guards=guards))
    if prompts is None:
        prompts = [[7, 8, 9] + list(range(1, 10 + i)) for i in range(6)]
    reqs = [Request(prompt=list(p), max_new_tokens=6) for p in prompts]
    eng.serve(reqs)
    m = eng.metrics
    if prefix:  # CoW page copies are admission-time device dispatches
        assert m.dispatches >= m.steps, (m.dispatches, m.steps)
    else:
        assert m.dispatches == m.steps, (m.dispatches, m.steps)
    assert m.transfers_d2h == m.steps, (m.transfers_d2h, m.steps)
    return [r.output for r in reqs], m, eng
"""


def _mesh_run(n_devices: int, body: str) -> str:
    """Compose the zero-indent prelude with a dedented test body so
    ``run_with_devices``'s dedent is a no-op and the body really
    executes at module level (an indented body would silently become
    part of the prelude's last function)."""
    code = _PRELUDE + textwrap.dedent(body)
    out = run_with_devices(n_devices, code)
    assert "OK" in out, f"subprocess body did not run to its print: {out!r}"
    return out


def test_token_identity_counters_and_collectives_across_meshes():
    """tp=4, tp=2 x pp=2 and pp=2 all decode the exact tokens of the
    single-device engine, keep one dispatch + one d2h pull per step,
    and report the analytically-predicted collective count per step
    (2 psums/layer + 1 logits gather under tp; pp hops + broadcast
    under pp) — all with debug_guards trapping implicit transfers."""
    _mesh_run(8, """
        base, _, _ = run(1, 1)
        want = {(4, 1): 5.0, (2, 2): 8.0, (1, 2): 3.0}
        for (tp, pp), coll_per_step in want.items():
            out, m, _ = run(tp, pp)
            assert out == base, (tp, pp)
            assert m.collectives / m.steps == coll_per_step, \\
                (tp, pp, m.collectives, m.steps)
            assert m.collective_bytes > 0
        print("OK")
    """)


def test_preemption_recompute_identical_under_tp():
    """A starved page pool forces preemption + recompute; the sharded
    engine must preempt the same way and still match tp=1 greedy
    outputs token for token."""
    _mesh_run(2, """
        o1, m1, _ = run(1, 1, n_pages=9)
        o2, m2, _ = run(2, 1, n_pages=9)
        assert m2.preemptions > 0, m2
        assert m1.preemptions == m2.preemptions
        assert o1 == o2
        print("OK", m2.preemptions)
    """)


def test_prefix_cache_cow_fork_identical_under_tp():
    """Identical two-full-page prompts make every later request a full
    hit that forks its tail page copy-on-write; under tp=2 the forks
    happen in the sharded pools and outputs stay token-identical."""
    _mesh_run(2, """
        prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]] * 4
        o1, m1, _ = run(1, 1, prefix=True, prompts=prompts)
        o2, m2, _ = run(2, 1, prefix=True, prompts=prompts)
        assert m2.prefix_hits > 0 and m2.prefix_cow_forks > 0, m2
        assert (m1.prefix_hits, m1.prefix_cow_forks) == \\
               (m2.prefix_hits, m2.prefix_cow_forks)
        assert o1 == o2 and len(set(map(tuple, o1))) == 1
        print("OK", m2.prefix_cow_forks)
    """)


def test_page_table_bounds_and_shard_geometry():
    """Every device holds exactly its (repeats/pp, kv_heads/tp) slice of
    the pools, and every page-table entry indexes inside the local pool
    (the table is replicated; pools shard on non-page axes, so ids are
    valid on all ranks)."""
    _mesh_run(4, """
        import numpy as np
        _, _, eng = run(2, 2, prompts=[list(range(1, 12))] * 3)
        ptab = np.asarray(eng.cache.page_table)
        assert ptab.min() >= 0 and ptab.max() < eng.pager.n_pages
        k = eng.cache.layers["pos0"].k
        assert len(k.addressable_shards) == 4
        for sh in k.addressable_shards:
            assert sh.data.shape[0] == k.shape[0] // 2  # repeats / pp
            assert sh.data.shape[1] == k.shape[1]       # full page pool
            assert sh.data.shape[2] == k.shape[2] // 2  # kv heads / tp
        print("OK", k.shape, "->", tuple(sh.data.shape))
    """)


def test_sharded_forward_logits_match_one_device():
    """Params and pools created already split over the mesh hold the same
    values as the one-device ones, and the sharded packed forward's
    logits match ``Model.unified_step`` at tp=2 and tp=2 x pp=2."""
    _mesh_run(4, """
        import numpy as np
        from repro.models.attention import PackedSegs
        from repro.serving import sharded as shard

        fmodel = build_model(spec, param_dtype=jnp.float32,
                             compute_dtype=jnp.float32,
                             cache_layout="paged", kv_page_size=8)

        def init_cache():
            return fmodel.init_cache(2, 32, layout="paged", n_pages=9)

        # two idle decode slots, then prefill rows of 4 and 3 tokens
        i32 = lambda x: jnp.asarray(x, jnp.int32)
        args = (i32([0, 0, 5, 6, 7, 8, 9, 10, 11, 0]),
                i32([0, 0, 0, 1, 2, 3, 0, 1, 2, 0]),
                i32([0, 1, 2, 6]), i32([0, 0, 4, 3]), i32([0, 0, 4, 3]),
                i32([[0] * 4, [0] * 4, [1, 0, 0, 0], [2, 0, 0, 0]]))

        def one(p, c, tok, pos, qs, ql, kl, pt):
            packed = PackedSegs(q_start=qs, q_len=ql, kv_len=kl,
                                page_table=pt, max_q=4, n_decode=2)
            return fmodel.unified_step(p, c, tok, pos, packed)
        want, _ = jax.jit(one)(params, init_cache(), *args)
        for tp, pp in ((2, 1), (2, 2)):
            mesh = shard.make_engine_mesh(tp, pp)
            p = shard.init_sharded(lambda: fmodel.init(jax.random.key(0)),
                                   shard.param_pspecs(fmodel, tp, pp), mesh)
            for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(params)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            c = shard.init_sharded(init_cache,
                                   shard.cache_pspecs(fmodel, tp, pp), mesh)
            assert len(c.layers["pos0"].k.addressable_shards) == tp * pp
            fwd = shard.build_sharded_forward(fmodel, mesh, tp, pp, max_q=4,
                                              n_decode=2)
            got, _ = fwd(p, c, *args)
            np.testing.assert_allclose(np.asarray(got)[2:],
                                       np.asarray(want)[2:],
                                       atol=1e-5, rtol=1e-5)
        print("OK")
    """)


def test_compiled_step_holds_the_counted_collectives():
    """The compiled tp=4 step (both packed profiles) executes exactly
    ``collective_stats(...)[0]`` all-reduces and all-gathers: 2 per layer
    inside the layer scan (counted once per trip) and the logits
    gather."""
    _mesh_run(4, """
        import functools, re
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.serving import sharded as shard
        from repro.serving.sharded import collective_stats

        def executed(text):
            comps, cur, entry = {}, None, None
            for line in text.splitlines():
                head = re.match(r"^(ENTRY )?%([\\w.\\-]+) .*\\{$", line)
                if head:
                    cur = head.group(2)
                    comps[cur] = [0, []]
                    entry = cur if head.group(1) else entry
                    continue
                if cur is None:
                    continue
                if re.search(r" (all-reduce|all-gather)(-start)?\\(", line):
                    comps[cur][0] += 1
                for kind, callee in re.findall(
                        r"(body|calls|to_apply)=%([\\w.\\-]+)", line):
                    trips = re.search(r'"known_trip_count":\\{"n":"(\\d+)"',
                                      line) if kind == "body" else None
                    comps[cur][1].append(
                        (callee, int(trips.group(1)) if trips else 1))

            def count(name):
                own, calls = comps[name]
                return own + sum(n * count(c) for c, n in calls)
            return count(entry)

        tspec = ModelSpec(name="mha", d_model=128, n_layers=3, n_heads=4,
                          n_kv_heads=4, d_head=32, d_ff=344, vocab=512,
                          attn=AttnSpec(kind="full", causal=True),
                          act="swiglu")
        m = build_model(tspec, param_dtype=jnp.float32,
                        compute_dtype=jnp.float32, cache_layout="paged",
                        kv_page_size=8)
        mesh = shard.make_engine_mesh(4, 1)
        sds = lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s))
        p = jax.tree.map(sds, jax.eval_shape(m.init, jax.random.key(0)),
                         shard.param_pspecs(m, 4, 1))
        c = jax.tree.map(sds, jax.eval_shape(functools.partial(
            m.init_cache, 2, 32, layout="paged", n_pages=9)),
            shard.cache_pspecs(m, 4, 1))
        rep = NamedSharding(mesh, P())
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep)
        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=rep)
        for max_q, n_decode, t, n in ((4, 2, 2 + 2 * 4, 4), (1, 0, 2, 2)):
            fn = shard.build_sharded_step(m, mesh, 4, 1, max_slots=2,
                                          max_q=max_q, n_decode=n_decode)
            text = fn.lower(p, c, i32(t), i32(t), i32(n), i32(n), i32(n),
                            i32(n, 4), key, f32(n), i32(n),
                            f32(n)).compile().as_text()
            want = collective_stats(tspec, 4, 1, t, n)[0]
            assert want == 2 * tspec.n_layers + 1
            assert executed(text) == want, (max_q, executed(text), want)
        print("OK")
    """)
