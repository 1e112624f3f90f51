"""Disaggregated prefill/decode cluster: token identity vs the unified
engine (device + simulated-link transports, mid-migration preemption, a
poisoned-page corruption probe), migration accounting, and property tests
of the KvMigrationChannel's page-content/refcount protocol against a
brute-force oracle under random interleavings."""

import itertools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import build_model
from repro.models.model import ModelCache
from repro.serving import (DisaggCluster, DisaggClusterConfig, EngineConfig,
                           KvMigrationChannel, MigrationLink, PageAllocator,
                           Request, ServeEngine, pool_split_from_plan)

from conftest import tiny_dense_spec

PROMPTS = [[1 + i, 5, 9, 2 + i, 7, 11, (3 * i) % 50, 4][: 4 + i % 4]
           for i in range(6)]
MAX_NEW = 8


def _requests():
    return [Request(prompt=list(p), max_new_tokens=MAX_NEW) for p in PROMPTS]


@pytest.fixture(scope="module")
def served():
    spec = tiny_dense_spec()
    model = build_model(spec, mesh=None, param_dtype=jnp.float32,
                        compute_dtype=jnp.float32)
    params = model.init(jax.random.key(7))
    # the head-to-head baseline: the unified chunked engine on the same
    # workload (greedy outputs are scheduler-independent, so every
    # cluster variant below must reproduce these exact tokens)
    eng = ServeEngine(model, params, EngineConfig(
        max_slots=4, max_seq=64, chunk_size=8, prefill_rows=2,
        cache_layout="paged", page_size=8, unified=True))
    baseline = [r.output for r in eng.serve(_requests())]
    return spec, model, params, baseline


def _cluster(model, params, **kw):
    cfg = DisaggClusterConfig(max_seq=64, page_size=8, chunk_size=8,
                              prefill_rows=2, decode_slots=4,
                              debug_guards=True, **kw)
    return DisaggCluster(model, params, cfg)


# -- token identity -----------------------------------------------------------

def test_disagg_token_identity_device_transport(served):
    spec, model, params, baseline = served
    cl = _cluster(model, params)
    reqs = cl.serve(_requests())
    assert all(r.state == "done" for r in reqs)
    assert [r.output for r in reqs] == baseline
    s = cl.summary(reqs)
    assert s["migrations"] == len(PROMPTS)
    assert s["migrated_bytes"] > 0 and s["migrated_pages"] > 0
    # the prefill engine never decoded, the decode engine never prefilled
    # from the queue (its only prefills would be preemption recomputes)
    assert cl.prefill_eng.metrics.exports == len(PROMPTS)
    assert cl.decode_eng.metrics.imports == len(PROMPTS)
    assert cl.prefill_eng.metrics.decode_steps == 0
    # hand-off left both pools clean
    cl.prefill_eng.pager.check()
    cl.decode_eng.pager.check()
    assert cl.prefill_eng.pager.pages_in_use == 0
    assert cl.decode_eng.pager.pages_in_use == 0


def test_disagg_token_identity_simulated_link(served):
    """The bandwidth/latency link prices every transfer and charges it
    to TTFT, without changing a single output token."""
    spec, model, params, baseline = served
    cl = _cluster(model, params,
                  link=MigrationLink(bandwidth=50e9, latency_s=1e-4))
    reqs = cl.serve(_requests())
    assert [r.output for r in reqs] == baseline
    s = cl.summary(reqs)
    assert s["migration_transfer_s_mean"] > 1e-4  # latency + bytes/bw
    for r in reqs:
        assert cl.ttft_incl_migration_s(r) > r.ttft_s
    assert abs(s["ttft_incl_migration_s_mean"] - s["ttft_s_mean"]
               - s["migration_transfer_s_mean"]) < 1e-9


def test_disagg_identity_under_mid_migration_preemption(served):
    """A starved decode pool preempts mid-stream while later migrations
    are still in flight; recompute-style resume keeps greedy outputs
    exactly the baseline's."""
    spec, model, params, baseline = served
    cl = _cluster(model, params, decode_pages=7)
    reqs = [Request(prompt=list(p), max_new_tokens=10) for p in PROMPTS]
    eng = ServeEngine(model, params, EngineConfig(
        max_slots=4, max_seq=64, chunk_size=8, prefill_rows=2,
        cache_layout="paged", page_size=8, unified=True))
    want = [r.output for r in eng.serve(
        [Request(prompt=list(p), max_new_tokens=10) for p in PROMPTS])]
    cl.serve(reqs)
    assert cl.decode_eng.metrics.preemptions > 0
    assert [r.output for r in reqs] == want


def test_disagg_identity_two_dispatch_decode_pool(served):
    """decode_unified=False routes the decode pool through the
    two-dispatch paged path — install_imported is page-table stitching
    either way, so outputs cannot move."""
    spec, model, params, baseline = served
    cl = _cluster(model, params, decode_unified=False)
    reqs = cl.serve(_requests())
    assert [r.output for r in reqs] == baseline


def test_poisoned_page_corruption_probe(served):
    """After each migration lands, scribble the *source* pages in the
    prefill pool.  If the decode engine read anything but its own copy,
    outputs would change; they must not."""
    spec, model, params, baseline = served
    cl = _cluster(model, params)
    poisoned = []
    orig_install = cl._install

    def install_and_poison(mig):
        orig_install(mig)
        pre = cl.prefill_eng
        ids = jnp.asarray(np.asarray(mig.src_pages, np.int32))

        def scribble(a):
            return a.at[:, ids].set(jnp.asarray(1e3, a.dtype))

        pre.cache = ModelCache(layers=jax.tree.map(scribble,
                                                   pre.cache.layers),
                               lengths=pre.cache.lengths,
                               page_table=pre.cache.page_table)
        poisoned.append(mig.req.rid)

    cl._install = install_and_poison
    reqs = cl.serve(_requests())
    assert len(poisoned) == len(PROMPTS)
    assert [r.output for r in reqs] == baseline


def test_prefill_finishes_short_requests_without_migration(served):
    """max_new_tokens=1 finishes at prefill: the first token is the
    whole answer, so nothing crosses the channel."""
    spec, model, params, baseline = served
    cl = _cluster(model, params)
    reqs = cl.serve([Request(prompt=list(p), max_new_tokens=1)
                     for p in PROMPTS])
    assert all(r.state == "done" for r in reqs)
    assert [r.output for r in reqs] == [o[:1] for o in baseline]
    assert cl.summary(reqs)["migrations"] == 0
    assert cl.metrics.prefill_finished == len(PROMPTS)
    assert cl.prefill_eng.pager.pages_in_use == 0


def test_submit_guards_decode_capacity(served):
    spec, model, params, _ = served
    cl = _cluster(model, params, decode_pages=3)  # 2 usable = 16 tokens
    with pytest.raises(ValueError, match="decode_pages"):
        cl.submit(Request(prompt=list(range(1, 30)), max_new_tokens=4))


# -- ratio planner ------------------------------------------------------------

def test_pool_split_from_plan():
    from repro.core.disagg import DisaggPlan

    def plan(xp_tp, xp_groups, yp_tp, yp_groups):
        return DisaggPlan(tp_prefill=xp_tp, tp_decode=yp_tp,
                          n_prefill_groups=xp_groups,
                          n_decode_groups=yp_groups, goodput_rps=1.0,
                          ttft=0.1, tpot=0.01, decode_batch=8,
                          kv_transfer_s=0.0, meets_slo=True)

    assert pool_split_from_plan(None, 8) == (4, 4)  # even fallback
    # 1:3 NPU ratio onto 8 units -> 2 prefill, 6 decode
    assert pool_split_from_plan(plan(1, 1, 1, 3), 8) == (2, 6)
    # extreme ratios still leave every pool >= 1 unit
    assert pool_split_from_plan(plan(8, 4, 1, 1), 4) == (3, 1)
    assert pool_split_from_plan(plan(1, 1, 8, 8), 4) == (1, 3)
    with pytest.raises(ValueError, match="budget"):
        pool_split_from_plan(None, 1)


def test_plan_with_baseline_returns_both():
    from repro.core import Workload
    from repro.core.disagg import plan_with_baseline
    from repro.scenario.platforms import resolve_platform

    spec = tiny_dense_spec()
    wl = Workload(batch=1, tau_p=64, tau_d=32)
    plans, co = plan_with_baseline(spec, resolve_platform("hgx-h100x8"), wl,
                                   tp_options=(1, 2))
    assert plans and plans[0].goodput_rps > 0
    assert co["goodput_rps"] > 0  # the colocated baseline rides along


# -- channel property tests ---------------------------------------------------

class _Oracle:
    """Brute-force model of the hand-off: host dicts for both pools'
    page contents, plus the expected token payload per request."""

    def __init__(self):
        self.src_store = {}  # src page id -> token tuple
        self.dst_store = {}
        self.expected = {}  # rid -> payload tokens
        self.installed = {}

    def copy_fn(self, src_pages, dst_pages):
        assert len(src_pages) == len(dst_pages)
        for s, d in zip(src_pages, dst_pages):
            self.dst_store[d] = self.src_store[s]


def _write_payload(store, pages, payload, page_size):
    for pi, page in enumerate(pages):
        store[page] = tuple(payload[pi * page_size:(pi + 1) * page_size])


def _read_payload(store, pages, n_tokens, page_size):
    out = []
    for page in pages:
        out.extend(store[page])
    return out[:n_tokens]


def test_channel_preserves_contents_and_refcounts_random():
    """Random interleavings of submit / (randomly refused) pump /
    release against the oracle: every installed request reads back its
    exact payload from the destination pool, source refs drop to zero
    at hand-off, and both allocators' invariants hold after every op."""
    for trial in range(8):
        rng = random.Random(100 + trial)
        ps = rng.choice([2, 4])
        src = PageAllocator(n_pages=rng.randint(8, 16), page_size=ps)
        dst = PageAllocator(n_pages=rng.randint(8, 16), page_size=ps)
        oracle = _Oracle()
        ch = KvMigrationChannel(src, dst, oracle.copy_fn,
                                page_bytes=ps * 4, clock=lambda: 0.0)
        cap = (min(src.usable_pages, dst.usable_pages)) * ps - 1
        ids = itertools.count()
        slot_free = True

        def reserve(rid, n_tokens):
            return slot_free and dst.ensure(rid, n_tokens)

        def install(mig):
            rid = mig.req.rid
            got = _read_payload(oracle.dst_store, dst.owned(rid),
                                mig.kv_len, ps)
            assert got == oracle.expected[rid], "payload corrupted in flight"
            # source refs handed off, destination holds exactly one ref
            assert src.owned(rid) == []
            for page in dst.owned(rid):
                assert dst.refcount(page) == 1
            oracle.installed[rid] = got

        for _ in range(60):
            op = rng.choice(("submit", "pump", "pump", "release"))
            if op == "submit":
                n = rng.randint(1, max(cap, 1))
                rid = next(ids)
                if not src.ensure(rid, n + 1):
                    continue  # source pool full right now: skip
                payload = [rng.randrange(1000) for _ in range(n)]
                _write_payload(oracle.src_store, src.owned(rid), payload, ps)
                oracle.expected[rid] = payload
                req = Request(prompt=[0], max_new_tokens=1)
                req.rid = rid
                ch.submit(req, n)
            elif op == "pump":
                slot_free = rng.random() < 0.7
                before = ch.pending
                ch.pump(reserve, install)
                if not slot_free:  # a refused head blocks the whole FIFO
                    assert ch.pending == before
            else:
                if oracle.installed:
                    rid = rng.choice(sorted(oracle.installed))
                    dst.release(rid)
                    del oracle.installed[rid]
            src.check()
            dst.check()
        # drain: release everything installed, then land the backlog
        slot_free = True
        while ch.pending:
            for rid in list(oracle.installed):
                dst.release(rid)
                del oracle.installed[rid]
            if not ch.pump(reserve, install):
                break
        for rid in list(oracle.installed):
            dst.release(rid)
        src.check()
        dst.check()
        assert ch.pending == 0, "backlog failed to drain"
        assert src.pages_in_use == 0 and dst.pages_in_use == 0
        assert ch.migrations == len(oracle.expected)


def test_channel_fifo_blocking_is_all_or_nothing():
    """A refused reservation leaves the head migration fully intact:
    source refs still held, nothing copied, nothing installed."""
    src = PageAllocator(n_pages=8, page_size=4)
    dst = PageAllocator(n_pages=8, page_size=4)
    oracle = _Oracle()
    ch = KvMigrationChannel(src, dst, oracle.copy_fn, page_bytes=16,
                            clock=lambda: 0.0)
    assert src.ensure(7, 6)
    _write_payload(oracle.src_store, src.owned(7), list(range(5)), 4)
    oracle.expected[7] = list(range(5))
    req = Request(prompt=[0], max_new_tokens=1)
    req.rid = 7
    ch.submit(req, 5)
    installed = ch.pump(lambda rid, n: False, lambda mig: None)
    assert installed == 0 and ch.pending == 1
    assert len(src.owned(7)) == 2 and ch.migrations == 0
    # and the same pump succeeds once the destination says yes
    ch.pump(lambda rid, n: dst.ensure(rid, n),
            lambda mig: oracle.installed.setdefault(mig.req.rid, True))
    assert ch.pending == 0 and src.owned(7) == []
    assert len(dst.owned(7)) == 2


def test_channel_rejects_mismatched_page_sizes():
    with pytest.raises(ValueError, match="page size"):
        KvMigrationChannel(PageAllocator(8, 4), PageAllocator(8, 8),
                           lambda s, d: None, page_bytes=1)


def test_simulated_link_time_scale_gates_landing():
    """time_scale > 0 turns simulated seconds into wall-clock gating:
    a pump before ready_t lands nothing."""
    src = PageAllocator(n_pages=8, page_size=4)
    dst = PageAllocator(n_pages=8, page_size=4)
    now = [0.0]
    ch = KvMigrationChannel(
        src, dst, lambda s, d: None, page_bytes=100,
        link=MigrationLink(bandwidth=100.0, latency_s=0.0, time_scale=1.0),
        clock=lambda: now[0])
    assert src.ensure(1, 4)
    req = Request(prompt=[0], max_new_tokens=1)
    req.rid = 1
    mig = ch.submit(req, 3)
    assert mig.transfer_s == 1.0  # 1 page x 100 bytes / 100 B/s
    assert ch.pump(lambda r, n: dst.ensure(r, n), lambda m: None) == 0
    now[0] = 1.5  # the link has drained: same pump now lands it
    assert ch.pump(lambda r, n: dst.ensure(r, n), lambda m: None) == 1
