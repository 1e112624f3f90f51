"""Prefix cache: refcounted page sharing (allocator), the radix tree vs a
brute-force longest-common-page-prefix oracle (property-based), the engine's
hit / copy-on-write / eviction behavior with greedy outputs held
token-identical, and the analytical prefix discount + compare() loop."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Optimizations, Workload
from repro.core.stages import concurrency_from_kv_budget
from repro.models import build_model
from repro.models.model import ModelCache
from repro.serving import (EngineConfig, PageAllocator, PrefixCache, Request,
                           ServeEngine)
from repro.serving.prefix_cache import CACHE_OWNER

from conftest import tiny_dense_spec

PS = 4  # page size for the host-only radix/allocator tests


# ---------------------------------------------------------------------------
# allocator refcounting
# ---------------------------------------------------------------------------

def test_allocator_refcount_sharing():
    a = PageAllocator(n_pages=6, page_size=PS)
    assert a.ensure(1, 10)  # 3 pages
    pages = a.owned(1)
    a.acquire(2, pages[:2])
    assert a.refcount(pages[0]) == 2
    assert a.shared_pages == 2
    a.check()
    # owner 1 lets go: only its unshared third page returns to the pool
    assert a.release(1) == 1
    assert a.refcount(pages[0]) == 1
    assert a.refcount(pages[2]) == 0
    a.check()
    assert a.release_one(2, pages[0]) is True  # last holder -> freed
    assert a.release(2) == 1
    assert a.free_pages == a.usable_pages
    with pytest.raises(ValueError):
        a.acquire(3, [pages[0]])  # page is free again: not acquirable
    with pytest.raises(ValueError):
        a.acquire(3, [0])  # the null page is never live


def test_allocator_check_catches_refcount_drift():
    a = PageAllocator(n_pages=6, page_size=PS)
    a.ensure(1, 5)
    a.check()
    page = a.owned(1)[0]
    a._refs[page] += 1  # simulate a lost decref
    with pytest.raises(AssertionError, match="refcount drift"):
        a.check()


# ---------------------------------------------------------------------------
# radix tree units
# ---------------------------------------------------------------------------

def _mk(n_pages=64):
    pager = PageAllocator(n_pages=n_pages, page_size=PS)
    return pager, PrefixCache(pager)


def _put(pager, cache, owner, tokens):
    """Insert like the engine does: owner prefills into its own pages, the
    cache registers the full ones, the owner finishes and releases."""
    assert pager.ensure(owner, len(tokens))
    new = cache.insert(tokens, pager.owned(owner))
    pager.release(owner)
    return new


def test_insert_lookup_page_granular():
    pager, cache = _mk()
    toks = list(range(10))  # 2 full pages + a 2-token tail
    assert _put(pager, cache, 1, toks) == 2
    assert cache.cached_pages == 2
    pages, n = cache.lookup(toks)
    assert n == 8 and len(pages) == 2  # the partial tail is never cached
    _, n = cache.lookup(toks[:6])  # mid-page query matches 1 page
    assert n == 4
    _, n = cache.lookup([99] + toks)  # shifted by one token: no block match
    assert n == 0
    cache.check()
    pager.check()


def test_first_writer_wins():
    pager, cache = _mk()
    toks = [7] * PS
    assert _put(pager, cache, 1, toks) == 1
    page0 = cache.lookup(toks)[0][0]
    assert _put(pager, cache, 2, toks) == 0  # latecomer caches nothing new
    assert cache.lookup(toks)[0][0] == page0
    assert cache.cached_pages == 1


def test_lru_eviction_order_and_pinning():
    pager, cache = _mk()
    a, b, c = [0] * PS, [1] * PS, [2] * PS
    for i, t in enumerate((a, b, c)):
        _put(pager, cache, i + 1, t)
    cache.acquire(9, a)  # refreshes a's LRU *and* pins its page
    assert cache.evict(1) == 1  # b is the LRU refcount-1 leaf
    assert cache.lookup(b)[1] == 0
    assert cache.lookup(a)[1] == PS and cache.lookup(c)[1] == PS
    assert cache.evict(10) == 1  # c goes; a stays pinned by owner 9
    assert cache.lookup(a)[1] == PS
    pager.release(9)
    assert cache.evict(10) == 1  # unpinned: a is reclaimable now
    assert cache.cached_pages == 0
    assert pager.free_pages == pager.usable_pages


def test_evict_peels_cold_branch():
    pager, cache = _mk()
    chain = list(range(3 * PS))  # one 3-node path
    assert _put(pager, cache, 1, chain) == 3
    # only the leaf is evictable at first; evicting it exposes its parent
    assert len(cache._evictable()) == 1
    assert cache.evict(3) == 3
    assert cache.cached_pages == 0
    cache.check()
    pager.check()


# ---------------------------------------------------------------------------
# property test: radix insert/match/evict vs a brute-force oracle
# ---------------------------------------------------------------------------

class _Oracle:
    """Brute-force mirror: the cache IS the set of block-path prefixes of
    every insert, matching is longest-common-page-prefix over that set, and
    (full) eviction removes unpinned leaves to a fixpoint."""

    def __init__(self):
        self.paths: set[tuple] = set()
        self.pins: dict[int, tuple] = {}

    @staticmethod
    def blocks(tokens):
        return tuple(tuple(tokens[i:i + PS])
                     for i in range(0, len(tokens) - PS + 1, PS))

    def match(self, tokens):
        bs = self.blocks(tokens)
        for k in range(len(bs), 0, -1):
            if bs[:k] in self.paths:
                return k
        return 0

    def insert(self, tokens):
        bs, new = self.blocks(tokens), 0
        for k in range(1, len(bs) + 1):
            if bs[:k] not in self.paths:
                self.paths.add(bs[:k])
                new += 1
        return new

    def acquire(self, owner, tokens):
        k = self.match(tokens)
        self.pins[owner] = self.blocks(tokens)[:k]
        return k

    def release(self, owner):
        self.pins.pop(owner, None)

    def evict_all(self):
        pinned = {p[:k] for p in self.pins.values()
                  for k in range(1, len(p) + 1)}
        freed, changed = 0, True
        while changed:
            changed = False
            for p in sorted(self.paths, key=len, reverse=True):
                if p in pinned:
                    continue
                if any(q != p and q[:len(p)] == p for q in self.paths):
                    continue  # interior node: some longer path needs it
                self.paths.remove(p)
                freed += 1
                changed = True
        return freed


def _random_tokens(rng, history):
    if history and rng.random() < 0.5:  # extend a known stem: forces shares
        stem = history[int(rng.integers(len(history)))]
        stem = stem[:int(rng.integers(len(stem) + 1))]
    else:
        stem = []
    fresh = rng.integers(0, 2, size=int(rng.integers(0, 13))).tolist()
    return (stem + fresh)[:20]


def _run_ops(seed, n_ops=120):
    rng = np.random.default_rng(seed)
    pager, cache = _mk(n_pages=257)
    oracle = _Oracle()
    history, owners, next_owner = [], [], 1
    for _ in range(n_ops):
        op = rng.choice(["insert", "lookup", "acquire", "release", "evict"],
                        p=[0.35, 0.25, 0.15, 0.15, 0.10])
        toks = _random_tokens(rng, history)
        if op == "insert":
            if pager.pages_for(len(toks)) <= pager.free_pages:
                history.append(toks)
                assert _put(pager, cache, next_owner, toks) \
                    == oracle.insert(toks)
                next_owner += 1
        elif op == "lookup":
            pages, n = cache.lookup(toks)
            assert n == oracle.match(toks) * PS
            assert len(pages) == n // PS
        elif op == "acquire":
            got = cache.acquire(next_owner, toks)
            assert len(got) == oracle.acquire(next_owner, toks)
            if got:
                owners.append(next_owner)
            else:
                oracle.release(next_owner)
            next_owner += 1
        elif op == "release" and owners:
            victim = owners.pop(int(rng.integers(len(owners))))
            pager.release(victim)
            oracle.release(victim)
        elif op == "evict":
            assert cache.evict(10 ** 9) == oracle.evict_all()
        cache.check()
        pager.check()
    # drain: release every owner, evict everything, pool must be whole again
    for o in owners:
        pager.release(o)
        oracle.release(o)
    assert cache.evict(10 ** 9) == oracle.evict_all()
    assert cache.cached_pages == 0
    assert pager.free_pages == pager.usable_pages


@pytest.mark.parametrize("seed", range(8))
def test_radix_matches_oracle(seed):
    _run_ops(seed)


try:  # hypothesis drives the same property when the host has it installed
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    pass
else:
    @given(st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_radix_matches_oracle_hypothesis(seed):
        _run_ops(seed)


# ---------------------------------------------------------------------------
# engine: hits, copy-on-write isolation, eviction under pressure
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    spec = tiny_dense_spec()
    model = build_model(spec, mesh=None, param_dtype=jnp.float32,
                        compute_dtype=jnp.float32)
    params = model.init(jax.random.key(7))
    return spec, model, params


def _greedy_reference(model, params, prompt, n, max_seq=128):
    cache = model.init_cache(1, max_seq)
    logits, cache = model.prefill(
        params, jnp.asarray([prompt], jnp.int32), cache=cache)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n - 1):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
    return out


def _prefix_cfg(**kw):
    base = dict(max_slots=2, max_seq=64, chunk_size=8, prefill_rows=2,
                cache_layout="paged", page_size=8, unified=True,
                prefix_cache=True, debug_guards=True)
    base.update(kw)
    return EngineConfig(**base)


def test_prefix_cache_requires_unified(served):
    spec, model, params = served
    with pytest.raises(ValueError, match="prefix"):
        ServeEngine(model, params,
                    EngineConfig(max_slots=2, max_seq=64, chunk_size=8,
                                 cache_layout="paged", page_size=8,
                                 prefix_cache=True))


def test_multi_tenant_hits_keep_greedy_outputs(served):
    """Two tenants, each with a page-aligned shared template: later
    requests hit the cache, are charged only their uncached suffix, and
    still decode exactly the reference tokens."""
    spec, model, params = served
    rng = np.random.default_rng(11)
    tmpl = {t: [int(x) for x in rng.integers(1, spec.vocab, size=16)]
            for t in ("tA", "tB")}
    reqs = [Request(prompt=tmpl[t] + [int(x) for x in
                                      rng.integers(1, spec.vocab, size=5)],
                    max_new_tokens=4, tenant=t, template_id=f"{t}/0")
            for t in ("tA", "tB") for _ in range(3)]
    eng = ServeEngine(model, params, _prefix_cfg(max_slots=3),
                      rng=jax.random.key(1))
    eng.serve(reqs)
    assert all(r.state == "done" for r in reqs)
    for r in reqs:
        assert r.output == _greedy_reference(model, params, r.prompt, 4)
    m = eng.metrics
    assert m.prefix_hit_rate > 0.0
    assert m.prefix_shared_pages_peak >= 1
    assert set(m.prefix_by_tenant) == {"tA", "tB"}
    # later same-template requests mapped both template pages for free
    assert any(r.n_cached >= 16 for r in reqs)


def test_cow_fork_isolation(served):
    """A full hit forks its tail page copy-on-write; corrupting the shared
    original afterwards must not change the forked request's output."""
    spec, model, params = served
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]  # 2 pages
    eng = ServeEngine(model, params, _prefix_cfg(), rng=jax.random.key(1))
    r1 = Request(prompt=prompt, max_new_tokens=6)
    eng.serve([r1])
    shared, n_cached = eng.prefix.lookup(prompt)
    assert n_cached == 16 and len(shared) == 2

    free0 = eng.pager.free_pages
    r2 = Request(prompt=prompt, max_new_tokens=6)
    eng.submit(r2)
    eng.step()  # admission: attach the hit + copy-on-write fork
    assert eng.metrics.prefix_cow_forks == 1
    assert r2.n_cached == len(prompt) - 1  # only the tail token recomputes
    held = eng.pager.owned(r2.rid)
    assert shared[0] in held  # read-only shared head page
    assert shared[1] not in held  # tail was forked out of the shared page
    # charged only the fork page + the decode-token page; a cache miss
    # would have paid pages_for(17 tokens) = 3 fresh pages
    assert free0 - eng.pager.free_pages == 2

    # corrupt the shared tail page on device; r2 only reads its fork
    poison = dataclasses.replace(
        eng.cache,
        layers=jax.tree.map(lambda a: a.at[:, shared[1]].set(1e9),
                            eng.cache.layers))
    assert isinstance(poison, ModelCache)
    eng.cache = poison
    while r2.state != "done":
        eng.step()
    assert r2.output == r1.output


def test_eviction_under_pressure(served):
    """A pool too small to cache every distinct prompt forces LRU eviction
    of cold refcount-1 leaves; every request still finishes with reference
    outputs and the allocator balances."""
    spec, model, params = served
    rng = np.random.default_rng(5)
    prompts = [[int(x) for x in rng.integers(1, spec.vocab, size=16)]
               for _ in range(6)]
    eng = ServeEngine(model, params, _prefix_cfg(n_pages=14),
                      rng=jax.random.key(1))
    reqs = [Request(prompt=p, max_new_tokens=4) for p in prompts]
    eng.serve(reqs)
    assert all(r.state == "done" for r in reqs)
    for p, r in zip(prompts, reqs):
        assert r.output == _greedy_reference(model, params, p, 4)
    assert eng.metrics.prefix_evicted_pages > 0
    # all request pages released; only cache-held nodes keep pages pinned
    assert eng.pager.holders() in ([], [CACHE_OWNER])
    eng.pager.check()
    eng.prefix.check()


# ---------------------------------------------------------------------------
# analytical: prefill discount, capacity raise, compare() loop
# ---------------------------------------------------------------------------

def _ttft(**opt_kw):
    from repro.core.stages import prefill
    from repro.scenario import Scenario

    sc = Scenario.make("llama3-8b", use_case="chat", batch=8,
                       platform="hgx-h100x8", parallelism=dict(tp=8),
                       opt=Optimizations(**opt_kw))
    return prefill(sc.resolve_model(), sc.resolve_platform(),
                   sc.parallelism, sc.opt, sc.workload).meta["ttft"]


def test_prefix_hit_discounts_prefill_ttft():
    ttft = {hit: _ttft(paged_kv=True, prefix_hit_rate=hit)
            for hit in (0.0, 0.5, 0.9)}
    assert ttft[0.9] < ttft[0.5] < ttft[0.0]
    # pages are the sharing unit: without paged_kv the rate is inert
    assert _ttft(prefix_hit_rate=0.9) == _ttft()


def test_prefix_hit_raises_kv_concurrency():
    spec = tiny_dense_spec()
    wl = Workload(batch=8, tau_p=256, tau_d=64, name="t")
    budget = 64 * 1024 * 1024
    base = concurrency_from_kv_budget(spec, Optimizations(paged_kv=True),
                                      wl, budget)
    shared = concurrency_from_kv_budget(
        spec, Optimizations(paged_kv=True, prefix_hit_rate=0.5), wl, budget)
    assert shared > base > 0
    # hit rates are clamped to [0, 1]; each request keeps >= one page of
    # private KV (the copy-on-write fork floor), so capacity stays finite
    full = concurrency_from_kv_budget(
        spec, Optimizations(paged_kv=True, prefix_hit_rate=1.0), wl, budget)
    over = concurrency_from_kv_budget(
        spec, Optimizations(paged_kv=True, prefix_hit_rate=1.5), wl, budget)
    assert over == full >= shared
    # dense engines can't share pages: the rate is inert without paged_kv
    assert concurrency_from_kv_budget(
        spec, Optimizations(prefix_hit_rate=0.5), wl, budget,
        reserved_ctx=512) == concurrency_from_kv_budget(
        spec, Optimizations(), wl, budget, reserved_ctx=512)


def test_engine_backend_closes_prefix_compare_loop():
    """Scenario -> prefix-cache engine run -> measured hit rate -> the
    analytical prediction at that hit rate -> compare() errors for TTFT
    and max concurrency (the bench's artifact path, in miniature)."""
    from repro.scenario import Scenario, compare, run

    wl = Workload(batch=6, tau_p=24, tau_d=4, name="prefix-loop")
    sc = Scenario.make(tiny_dense_spec(), workload=wl, batch=6,
                       platform="hgx-h100x8", mode="monolithic",
                       opt=Optimizations(paged_kv=True, kv_page_size=8))
    meas = run([sc], backend="engine",
               engine_kw=dict(prefix_cache=True, max_slots=4, max_seq=64,
                              page_size=8, n_requests=6, max_new=4))[0]
    assert meas.status == "ok"
    eng = meas.extra["engine"]
    hit = eng["prefix_hit_rate"]
    assert 0.0 < hit < 1.0
    assert meas.extra["engine_config"]["prefix_cache"] is True
    pred = run([sc.replace(opt=dataclasses.replace(
        sc.opt, prefix_hit_rate=hit))], backend="analytical")[0]
    errs = compare(pred, meas)
    assert "ttft_s" in errs and "max_concurrency" in errs
    # the discount moves predictions the right way: cheaper prefill, more
    # concurrent requests out of the same KV budget
    pred0 = run([sc], backend="analytical")[0]
    assert pred.ttft_s < pred0.ttft_s
    assert pred.max_concurrency > pred0.max_concurrency
