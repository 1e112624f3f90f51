"""Continuous-batching serving engine (the end-to-end inference driver).

Slot-based continuous batching in the JetStream style: a fixed pool of
decode slots shares one device-resident KV cache; prompts are prefilled in
``chunk_size`` pieces (chunked prefill, paper §IV-A — bounds the decode
stall between chunks) and inserted into a free slot; every engine step
advances all active slots by one token.  Finished requests free their slot
immediately, so new prompts join without draining the batch (Orca-style
iteration-level scheduling).

Hot-path design (the batched rebuild):

  * **One jitted decode+sample per step.**  ``decode_step`` and the per-slot
    sampler are fused into a single jitted call that advances *all* slots
    and samples them on device; the engine performs exactly one
    device->host transfer per decode step (the (B,) sampled-token vector) —
    logits never leave the device.  Per-slot sampling parameters ride along
    as (B,) arrays, so mixed greedy/stochastic batches share one trace.
  * **Active-slot mask, no retracing.**  Slot occupancy is tracked on the
    host; freed slots keep decoding garbage rows (their outputs are simply
    ignored), so shapes are static and nothing retraces as requests come
    and go.  Sequence lengths are mirrored on the host, so stop conditions
    need no device sync.
  * **Concurrent chunked prefills.**  The scratch cache has
    ``prefill_rows`` rows; every in-flight prompt owns a row and all rows
    at the same chunk width advance through one batched ``prefill_chunk``
    call.  A row mask selects, per row, between the advanced and previous
    scratch state, so rows at different widths (e.g. a final partial
    chunk) never corrupt each other and the batched call's shapes depend
    only on the chunk width — exactly the trace profile of the
    single-prefill engine.  First tokens for completing prompts are
    sampled on device in one batched call.
  * **Greedy admission under decode_priority.**  The scheduler admits
    queued prompts into free prefill rows whenever a decode slot is
    guaranteed at completion; ``decode_priority`` orders decode before
    prefill chunks within a step (SLO order).

Wall-clock and step-level metrics (TTFT, TPOT, tokens/s, slot occupancy)
accumulate in ``engine.metrics``; see ``EngineMetrics.summary``.

**Paged KV cache** (``cache_layout="paged"``): instead of reserving
``max_slots x max_seq`` KV tokens per layer, the device keeps a flat pool
of ``page_size``-token pages plus a per-slot page table
(:mod:`repro.serving.paging`).  Admission switches from "free slot" to
"free pages for the prompt + headroom"; pages are allocated as sequences
grow (allocate-on-append) and returned the moment a request finishes
(free-on-finish).  When the pool runs dry mid-decode, the youngest active
request is preempted back to the queue (recompute-style: its prompt +
generated tokens re-prefill on re-admission, so greedy outputs are
unchanged).  Prefill still runs on the dense scratch rows; a completed
prompt is scattered into its pages at insert time.  The one-device->host-
transfer-per-decode-step and no-retrace invariants hold in both layouts
(the page table is a fixed-shape device array, re-uploaded host->device
only when it changes).

**Unified token-packed step** (``unified=True``, requires the paged
layout and an attention-only stack): instead of one jitted decode
dispatch plus one jitted prefill dispatch *per chunk-width group*, every
engine step packs all decode tokens and all in-flight prefill chunks into
one fixed-shape ragged batch — segments at fixed offsets (slot s's decode
token at s; prefill row r's chunk at ``max_slots + r * chunk_size``),
partial chunks padded and masked by the per-segment ``q_len`` — and
drives it through one jitted ``unified_step`` + on-device sampling call.
Prefill K/V are written **directly into their pages** inside that same
forward pass, so the dense scratch cache and the insert-time scatter
disappear entirely; a completed prompt "moves" into its decode slot by
pure host bookkeeping (the pages already hold its KV).  The invariant
strengthens to exactly **one jitted dispatch and one device->host
transfer per step** regardless of how many prefill width-groups are in
flight, and nothing retraces as widths vary (the packed shapes depend
only on the engine geometry).  Greedy outputs stay token-identical to the
two-dispatch path (asserted in tests).  ``EngineMetrics`` counts
``dispatches`` / ``transfers_d2h`` so the collapse is measurable.

The scheduler itself stays pure Python and therefore easy to fault-inject
and test.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..models.attention import (PackedSegs, PagedAttnCache,
                                paged_insert_rows)
from ..models.model import Model, ModelCache
from . import sharded as shard
from .paging import PageAllocator
from .prefix_cache import PrefixCache
from .sampling import SamplingConfig, sample_slots
from .speculative import PackedSpeculator


@dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int = 32
    eos_id: int | None = None
    sampling: SamplingConfig = field(default_factory=SamplingConfig)
    rid: int = -1
    #: optional multi-tenant trace metadata (workload generator / metrics
    #: attribution only — the scheduler never reads these)
    tenant: str | None = None
    template_id: str | None = None
    # filled by the engine:
    output: list[int] = field(default_factory=list)
    state: str = "queued"  # queued | prefill | decode | done
    slot: int = -1
    n_cached: int = 0  # prompt tokens served from shared prefix-cache pages
    ttft_steps: int = 0  # engine steps until first token (TTFT proxy)
    submit_t: float = 0.0  # wall-clock timestamps (perf_counter)
    admit_t: float = 0.0  # left the queue (first admission only)
    first_token_t: float = 0.0
    finish_t: float = 0.0

    @property
    def ttft_s(self) -> float:
        return max(self.first_token_t - self.submit_t, 0.0)

    @property
    def tpot_s(self) -> float:
        n = len(self.output) - 1
        if n <= 0 or self.finish_t <= self.first_token_t:
            return 0.0
        return (self.finish_t - self.first_token_t) / n


@dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 8
    max_seq: int = 512
    chunk_size: int = 128
    decode_priority: bool = True  # decode before prefill chunks (SLO order)
    prefill_rows: int = 2  # concurrent chunked prefills (scratch rows)
    record_step_log: bool = False  # keep a StepRecord per step
    #: KV-cache layout: "dense" reserves max_slots x max_seq tokens per
    #: layer; "paged" keeps an n_pages pool + page-table indirection
    cache_layout: str = "dense"
    page_size: int = 16  # tokens per KV page (paged layout)
    #: total pool pages including the reserved null page; None sizes the
    #: pool capacity-equivalent to the dense reservation (the interesting
    #: configurations set it *lower* — that is the whole point)
    n_pages: int | None = None
    #: unified token-packed step: decode tokens + prefill chunks of every
    #: in-flight prompt ride ONE jitted dispatch per step, with prefill
    #: K/V written directly into their pages (requires cache_layout=
    #: "paged" and an attention-only stack)
    unified: bool = False
    #: radix-tree prefix cache over KV pages: requests whose prompt shares
    #: a page-aligned prefix with an earlier request map those pages
    #: read-only into their page table and prefill only the uncached
    #: suffix (requires ``unified=True`` — the packed step's ragged
    #: attention reads shared pages in place; greedy outputs stay
    #: token-identical to a cache-off engine)
    prefix_cache: bool = False
    #: runtime enforcement of the hot-path invariants: every engine step
    #: runs under ``jax.transfer_guard("disallow")`` (any *implicit*
    #: host<->device transfer — e.g. a numpy array slipped straight into
    #: a jitted call — raises; the engine's own uploads/pulls are explicit
    #: ``jax.device_put``/``jax.device_get`` and stay legal) and the jit
    #: caches of the steady-state dispatches are asserted flat across slot
    #: churn (a growing cache is a retrace).  In the paged layout every
    #: step also runs ``PageAllocator.check()`` (refcount / free-list
    #: audit) and, with the prefix cache on, the radix-tree audit.
    #: Greedy outputs are identical with the guards on or off — this mode
    #: only *observes*.
    debug_guards: bool = False
    #: tensor-parallel degree: the unified step runs under ``shard_map``
    #: on a (pp, tp) device mesh with heads/FFN column-row sharded and
    #: the paged KV pools split on their kv-head axis (requires
    #: ``unified=True`` and tp*pp visible devices; on CPU export
    #: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)
    tp: int = 1
    #: pipeline-parallel degree: shards the stacked layer ``repeats`` axis
    #: of params and KV pools; the step runs a masked ppermute ring
    pp: int = 1
    #: speculative decoding: every decode slot contributes a K+1-token
    #: verify segment (its committed token + K draft proposals, causal
    #: within the segment) to the packed batch, with the draft model's
    #: propose loop, the target verify and device-side accept/reject all
    #: fused into the step's ONE dispatch (requires ``unified=True`` and
    #: ``draft_model``/``draft_params`` at engine construction; tp/pp
    #: meshes are refused).  0 disables speculation.
    n_spec: int = 0


@dataclass
class StepRecord:
    """What one ``step()`` did, kept in ``EngineMetrics.step_log`` when
    ``record_step_log`` is on.  Times are ``perf_counter``.  The packing
    fields are filled by the token-packed steps (unified, speculative);
    the two-dispatch path leaves them empty."""

    step: int
    t0: float
    t1: float = 0.0
    mixed: bool = False  # the mixed decode+prefill profile ran
    rows_packed: int = 0  # token rows of the profile that ran
    rows_live: int = 0  # of those, rows holding a live token (sum q_len)
    decode: list = field(default_factory=list)  # live decode kv_lens
    prefill: list = field(default_factory=list)  # [(q_len, kv_len)]
    sampled: int = 0  # segments whose sampled token is used
    #: of those, segments whose temperature is > 0; a unified step ran
    #: ``sample_slots``' filter branch exactly when this is > 0
    stochastic: int = 0
    pages_in_use: int = 0  # after the step (paged layout)
    preempted: int = 0  # preemptions during the step
    admitted: list = field(default_factory=list)  # rids admitted


@dataclass
class EngineMetrics:
    """Wall-clock + step-level serving metrics."""

    decode_steps: int = 0
    prefill_calls: int = 0
    prefill_tokens: int = 0
    generated_tokens: int = 0
    # -- dispatch accounting --------------------------------------------------
    #: jitted device dispatches issued (decode, prefill groups, inserts,
    #: row resets, first-token samples — or exactly one per step when the
    #: unified token-packed path is on)
    dispatches: int = 0
    #: device->host transfers (sampled-token pulls)
    transfers_d2h: int = 0
    start_t: float = 0.0
    end_t: float = 0.0
    occupancy_sum: float = 0.0  # sum over steps of active/max_slots
    steps: int = 0
    step_log: list = field(default_factory=list)  # StepRecord per step
    # -- KV capacity counters (both layouts) --------------------------------
    peak_active: int = 0  # max concurrent decode slots (measured concurrency)
    peak_inflight: int = 0  # max active + in-flight prefills
    kv_util_sum: float = 0.0  # per-step live-KV fraction of the reservation
    kv_used_tokens_peak: int = 0  # dense layout: peak live cache tokens
    # -- paged-layout counters ----------------------------------------------
    preemptions: int = 0  # victims pushed back to the queue (pool ran dry)
    capacity_stops: int = 0  # requests force-finished (no victim available)
    pages_in_use_peak: int = 0
    # -- mesh-sharded counters (zero at tp=pp=1) ------------------------------
    collectives: int = 0  # psum/ppermute/all_gather ops issued per device
    collective_bytes: int = 0  # estimated all-reduce/ring bytes moved
    # -- P/D disaggregation counters (zero outside a DisaggCluster) ----------
    exports: int = 0  # prefill completions handed off to a decode pool
    imports: int = 0  # migrated requests installed into a decode slot
    # -- prefix-cache counters (mirrors of PrefixCacheStats + engine-side) --
    prefix_lookups: int = 0
    prefix_hits: int = 0  # submits whose prompt matched >= 1 cached page
    prefix_lookup_tokens: int = 0
    prefix_hit_tokens: int = 0  # tokens matched at submit-time lookup
    prefix_cached_tokens: int = 0  # prefill tokens actually skipped
    prefix_cow_forks: int = 0  # full-hit tail pages forked copy-on-write
    prefix_inserted_pages: int = 0
    prefix_evicted_pages: int = 0
    prefix_shared_pages_peak: int = 0  # peak pages mapped by > 1 holder
    #: tenant -> [hit_tokens, lookup_tokens] (per-tenant hit attribution)
    prefix_by_tenant: dict = field(default_factory=dict)
    # -- speculative-decoding counters (zero unless n_spec > 0) --------------
    spec_rounds: int = 0  # engine steps that ran a draft/verify round
    spec_slot_rounds: int = 0  # per-slot verify windows executed
    spec_proposed: int = 0  # draft tokens offered for verification
    spec_accepted: int = 0  # draft tokens the target accepted
    spec_bonus: int = 0  # fully-accepted windows (earned a bonus token)
    spec_emitted: int = 0  # tokens committed by speculative rounds
    #: slot -> [accepted, proposed] (per-slot acceptance attribution)
    spec_by_slot: dict = field(default_factory=dict)

    @property
    def prefix_hit_rate(self) -> float:
        """Token-weighted submit-time hit rate."""
        return (self.prefix_hit_tokens / self.prefix_lookup_tokens
                if self.prefix_lookup_tokens else 0.0)

    @property
    def spec_acceptance_rate(self) -> float:
        """Fraction of offered draft tokens the target accepted."""
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed else 0.0)

    @property
    def spec_tokens_per_round(self) -> float:
        """Effective tokens committed per per-slot verify window (1.0 is
        the non-speculative baseline; the fig-11 win is this number)."""
        return (self.spec_emitted / self.spec_slot_rounds
                if self.spec_slot_rounds else 0.0)

    @property
    def wall_s(self) -> float:
        return max(self.end_t - self.start_t, 0.0)

    @property
    def tokens_per_s(self) -> float:
        return self.generated_tokens / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.steps if self.steps else 0.0

    @property
    def mean_kv_utilization(self) -> float:
        return self.kv_util_sum / self.steps if self.steps else 0.0

    def summary(self, requests=None) -> dict:
        out = {
            "steps": self.steps,
            "decode_steps": self.decode_steps,
            "prefill_calls": self.prefill_calls,
            "prefill_tokens": self.prefill_tokens,
            "generated_tokens": self.generated_tokens,
            "dispatches": self.dispatches,
            "transfers_d2h": self.transfers_d2h,
            "dispatches_per_step": (self.dispatches / self.steps
                                    if self.steps else 0.0),
            "transfers_per_step": (self.transfers_d2h / self.steps
                                   if self.steps else 0.0),
            "wall_s": self.wall_s,
            "tokens_per_s": self.tokens_per_s,
            "mean_slot_occupancy": self.mean_occupancy,
            "peak_active": self.peak_active,
            "peak_inflight": self.peak_inflight,
            "kv_utilization_mean": self.mean_kv_utilization,
            "preemptions": self.preemptions,
            "capacity_stops": self.capacity_stops,
            "pages_in_use_peak": self.pages_in_use_peak,
            "kv_used_tokens_peak": self.kv_used_tokens_peak,
        }
        if self.exports or self.imports:  # only under P/D disaggregation
            out["exports"] = self.exports
            out["imports"] = self.imports
        if self.collectives:  # only on a >1-device mesh
            out["collectives"] = self.collectives
            out["collective_bytes"] = self.collective_bytes
            out["collectives_per_step"] = (self.collectives / self.steps
                                           if self.steps else 0.0)
            out["allreduce_bytes_per_step"] = (
                self.collective_bytes / self.steps if self.steps else 0.0)
        if self.spec_rounds:  # only with speculative decoding on
            out.update(
                spec_rounds=self.spec_rounds,
                spec_proposed=self.spec_proposed,
                spec_accepted=self.spec_accepted,
                spec_bonus=self.spec_bonus,
                spec_emitted=self.spec_emitted,
                spec_acceptance_rate=self.spec_acceptance_rate,
                spec_tokens_per_round=self.spec_tokens_per_round,
                tokens_per_dispatch=(self.generated_tokens / self.dispatches
                                     if self.dispatches else 0.0),
                spec_by_slot={s: {"accepted": a, "proposed": p,
                                  "acceptance_rate": a / p if p else 0.0}
                              for s, (a, p)
                              in sorted(self.spec_by_slot.items())})
        if self.prefix_lookups:  # keep cache-off summaries unchanged
            out.update(
                prefix_hit_rate=self.prefix_hit_rate,
                prefix_lookups=self.prefix_lookups,
                prefix_hits=self.prefix_hits,
                prefix_hit_tokens=self.prefix_hit_tokens,
                prefix_lookup_tokens=self.prefix_lookup_tokens,
                prefix_cached_tokens=self.prefix_cached_tokens,
                prefix_cow_forks=self.prefix_cow_forks,
                prefix_inserted_pages=self.prefix_inserted_pages,
                prefix_evicted_pages=self.prefix_evicted_pages,
                prefix_shared_pages_peak=self.prefix_shared_pages_peak,
                prefix_by_tenant={t: {"hit_tokens": h, "lookup_tokens": n,
                                      "hit_rate": h / n if n else 0.0}
                                  for t, (h, n)
                                  in sorted(self.prefix_by_tenant.items())})
        done = [r for r in (requests or []) if r.state == "done"]
        if done:
            ttfts = sorted(r.ttft_s for r in done)
            tpots = [r.tpot_s for r in done if r.tpot_s > 0]
            out["requests_done"] = len(done)
            out["ttft_s_mean"] = sum(ttfts) / len(ttfts)
            out["ttft_s_p50"] = ttfts[len(ttfts) // 2]
            out["ttft_s_p95"] = ttfts[min(int(len(ttfts) * 0.95),
                                          len(ttfts) - 1)]
            out["tpot_s_mean"] = (sum(tpots) / len(tpots)) if tpots else 0.0
        return out


class ServeEngine:
    def __init__(self, model: Model, params, config: EngineConfig,
                 rng: jax.Array | None = None, draft_model: Model | None = None,
                 draft_params=None):
        if config.max_slots < 1:
            raise ValueError("EngineConfig.max_slots must be >= 1")
        if config.prefill_rows < 1:
            raise ValueError("EngineConfig.prefill_rows must be >= 1")
        if config.chunk_size < 1:
            raise ValueError("EngineConfig.chunk_size must be >= 1")
        if config.cache_layout not in ("dense", "paged"):
            raise ValueError(f"unknown cache_layout "
                             f"{config.cache_layout!r}")
        if config.unified:
            if config.cache_layout != "paged":
                raise ValueError(
                    "unified=True needs cache_layout='paged': the packed "
                    "step writes prefill K/V directly into KV pages")
            if any(k == "ssm" for k in model.spec.layer_kinds()):
                raise ValueError(
                    "unified=True supports attention-only stacks; "
                    f"{model.spec.name!r} has SSM layers whose sequential "
                    "state has no packed-segment forward")
            if model.spec.attn.kind == "swa":
                raise ValueError("unified=True has no sliding-window "
                                 "masking in the ragged kernel yet")
        if config.prefix_cache and not config.unified:
            raise ValueError(
                "prefix_cache=True requires unified=True: shared pages are "
                "read in place by the packed step's ragged attention; the "
                "dense-scratch prefill path cannot map them")
        if config.n_spec < 0:
            raise ValueError("EngineConfig.n_spec must be >= 0")
        if config.n_spec:
            if not config.unified:
                raise ValueError(
                    "n_spec > 0 requires unified=True: speculative verify "
                    "segments ride the packed ragged dispatch")
            if draft_model is None or draft_params is None:
                raise ValueError(
                    "n_spec > 0 needs draft_model and draft_params: the "
                    "draft proposes the K tokens the target verifies")
        elif draft_model is not None:
            raise ValueError(
                "draft_model given but n_spec == 0: set EngineConfig."
                "n_spec=K to enable speculative decoding")
        shard.validate_engine_sharding(model.spec, config)
        self.unified = config.unified
        self.paged = config.cache_layout == "paged"
        if self.paged:
            if config.max_seq % config.page_size:
                raise ValueError("paged layout needs max_seq to be a "
                                 "multiple of page_size")
            # the model builds paged pools sized by its context knobs
            model = dataclasses.replace(
                model, ctx=model.ctx.with_(cache_layout="paged",
                                           kv_page_size=config.page_size))
        self.model = model
        self.params = params
        self.cfg = config
        self.rng = rng if rng is not None else jax.random.key(0)
        self._ids = itertools.count()
        self.queue: deque[Request] = deque()
        self.active: dict[int, Request] = {}  # slot -> request
        self.free_slots = list(range(config.max_slots))
        self.finished: list[Request] = []
        # P/D disaggregation hook (set post-construction by DisaggCluster):
        # called as export_fn(req, src_len, done, now) when a prefill
        # completes instead of promoting into a local decode slot.  The
        # request's pages stay owned by its rid until the migration
        # channel releases them after the cross-pool copy.
        self.export_fn = None
        self.steps = 0
        self.metrics = EngineMetrics()
        self._rec: StepRecord | None = None  # this step's, when recording

        # (pp, tp) device mesh of the sharded unified step (None: one
        # device); the paged pools are created already split over it
        self.tp, self.pp = config.tp, config.pp
        self.mesh = shard.make_engine_mesh(self.tp, self.pp) \
            if self.tp * self.pp > 1 else None

        self.max_pages = config.max_seq // config.page_size
        self.pager: PageAllocator | None = None
        self._ptab = None  # host mirror of the device page table
        self._ptab_dirty = False
        if self.paged:
            n_pages = config.n_pages
            if n_pages is None:  # capacity-equivalent to dense (+ null page)
                n_pages = config.max_slots * self.max_pages + 1
            self.pager = PageAllocator(n_pages=n_pages,
                                       page_size=config.page_size)
            self._ptab = np.zeros((config.max_slots, self.max_pages),
                                  np.int32)
            init = functools.partial(model.init_cache, config.max_slots,
                                     config.max_seq, layout="paged",
                                     n_pages=n_pages)
            self.cache = init() if self.mesh is None else shard.init_sharded(
                init, shard.cache_pspecs(model, self.tp, self.pp),
                self.mesh)
        else:
            self.cache = model.init_cache(config.max_slots, config.max_seq,
                                          layout="dense")
        # radix-tree prefix cache: shares pages across requests through the
        # refcounted allocator; `_attached` tracks which queued/admitted
        # rids already hold their shared-prefix references
        self.prefix = PrefixCache(self.pager) if config.prefix_cache \
            else None
        self._attached: set[int] = set()
        if self.unified:
            # the packed step writes prefill K/V straight into pages — no
            # dense scratch cache exists at all
            self.scratch = None
        else:
            # prefill runs on dense scratch rows; completed prompts are
            # scattered into their pages at insert time
            self.scratch = model.init_cache(config.prefill_rows,
                                            config.max_seq, layout="dense")
        # prefill bookkeeping: prefill row -> in-flight request / position
        self._prefills: dict[int, Request] = {}
        self._prefill_pos: dict[int, int] = {}
        self._free_rows = list(range(config.prefill_rows))

        # fixed packed layout of the unified step: decode slot s's token at
        # offset s, prefill row r's chunk at max_slots + r * chunk_size —
        # shapes depend only on the geometry, so nothing ever retraces
        self.n_segs = config.max_slots + config.prefill_rows
        self.t_pack = (config.max_slots
                       + config.prefill_rows * config.chunk_size)
        self._seg_start = np.concatenate([
            np.arange(config.max_slots, dtype=np.int32),
            config.max_slots + np.arange(config.prefill_rows,
                                         dtype=np.int32)
            * config.chunk_size])
        # the layouts are static: keep their device copies resident
        self._seg_start_dev = jnp.asarray(self._seg_start)
        self._seg_start_decode_dev = jnp.asarray(
            self._seg_start[:config.max_slots])

        # host mirrors (np, never synced from device): next-token feed,
        # per-slot sampling params, per-slot sequence lengths
        self._tokens = np.zeros((config.max_slots, 1), np.int32)
        self._temps = np.zeros((config.max_slots,), np.float32)
        self._topks = np.zeros((config.max_slots,), np.int32)
        self._topps = np.ones((config.max_slots,), np.float32)
        self._lengths = np.zeros((config.max_slots,), np.int64)
        # device copy of (temps, topks, topps): they only change on slot
        # churn, so cache the upload and invalidate on insert
        self._dev_sampling = None
        # device-resident next-token feed: the previous decode step's
        # sampled tokens never leave the device (the donated (B, 1) buffer
        # is updated in place); None = stale, re-upload from the host
        # mirror (slot churn wrote a first token)
        self._dev_tokens = None
        # unified-path analogues: the (B,) packed decode feed and the
        # (B, max_pages) slot page table, cached on device and invalidated
        # on slot churn / page-table change
        self._dev_utokens = None
        self._dev_ptab = None

        # -- mesh-sharded serving (tp/pp > 1) ---------------------------------
        # place params ONCE with their (pp, tp) NamedShardings (a no-op for
        # params made by ``shard.init_sharded``) so steady-state dispatches
        # reshard nothing; the per-profile collective counts are static
        # functions of the packed geometry, accumulated into metrics after
        # each dispatch
        self._coll_mixed = self._coll_decode = (0, 0)
        self._ptab_sharding = None
        if self.mesh is not None:
            self.params = shard.shard_tree(
                self.params, shard.param_pspecs(self.model, self.tp,
                                                self.pp), self.mesh)
            self._ptab_sharding = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec())
            # the static packed layouts live replicated on the mesh, like
            # every other per-step input (see _up)
            self._seg_start_dev = jax.device_put(self._seg_start,
                                                 self._ptab_sharding)
            self._seg_start_decode_dev = jax.device_put(
                self._seg_start[:config.max_slots], self._ptab_sharding)
            nbytes = np.dtype(self.model.ctx.compute_dtype).itemsize
            self._coll_mixed = shard.collective_stats(
                model.spec, self.tp, self.pp, self.t_pack, self.n_segs,
                nbytes)
            self._coll_decode = shard.collective_stats(
                model.spec, self.tp, self.pp, config.max_slots,
                config.max_slots, nbytes)

        self._jit_decode = jax.jit(self._decode_and_sample,
                                   donate_argnums=(1, 2))
        self._jit_prefill = jax.jit(self._prefill_masked,
                                    donate_argnums=(1,))
        self._jit_insert = jax.jit(self._insert, donate_argnums=(0,))
        self._jit_insert_paged = jax.jit(self._insert_paged,
                                         donate_argnums=(0,))
        self._jit_reset_row = jax.jit(self._reset_row, donate_argnums=(0,))
        self._jit_copy_page = jax.jit(self._copy_page, donate_argnums=(0,))
        self._jit_sample = jax.jit(sample_slots)
        # two fixed packed profiles, both one dispatch per step: the mixed
        # decode+prefill layout, and a decode-only layout (T = max_slots,
        # max_q = 1) so idle prefill rows cost nothing.  Shapes depend
        # only on the geometry — nothing retraces as widths vary.
        if self.mesh is not None:
            # same signatures, same two static profiles — but the packed
            # forward runs per-shard under shard_map on the mesh
            self._jit_unified = shard.build_sharded_step(
                self.model, self.mesh, self.tp, self.pp,
                max_slots=config.max_slots,
                max_q=max(config.chunk_size, 1),
                n_decode=config.max_slots)
            self._jit_unified_decode = shard.build_sharded_step(
                self.model, self.mesh, self.tp, self.pp,
                max_slots=config.max_slots, max_q=1, n_decode=0)
        else:
            self._jit_unified = jax.jit(
                functools.partial(self._unified_and_sample,
                                  max_q=max(config.chunk_size, 1),
                                  n_decode=config.max_slots),
                donate_argnums=(1,))
            self._jit_unified_decode = jax.jit(
                functools.partial(self._unified_and_sample, max_q=1,
                                  n_decode=0),
                donate_argnums=(1,))

        # speculative decoding: the PackedSpeculator owns the draft model,
        # its page-id-mirrored KV pool (same allocator, same n_pages — the
        # slot page-table rows address both pools), the draft-consumed
        # host mirror, and the fused draft/verify jit profiles
        self.speculator: PackedSpeculator | None = None
        if config.n_spec:
            self.speculator = PackedSpeculator(
                self.model, draft_model, draft_params,
                n_spec=config.n_spec, max_slots=config.max_slots,
                max_seq=config.max_seq, chunk_size=config.chunk_size,
                prefill_rows=config.prefill_rows,
                page_size=config.page_size, n_pages=self.pager.n_pages)

        # debug-guards bookkeeping: last observed jit cache size of each
        # steady-state dispatch (``_jit_prefill`` legitimately traces once
        # per chunk width and is excluded)
        self.debug_guards = config.debug_guards
        self._trace_sizes: dict[str, int] = {}

    # -- debug guards ---------------------------------------------------------
    def _step_guard(self):
        """``transfer_guard("disallow")`` for the whole step when
        ``debug_guards`` is on: implicit transfers (a numpy array passed
        straight into a jitted call) raise; the engine's explicit
        ``device_put``/``device_get``/``jnp.asarray`` traffic is exempt."""
        if self.debug_guards:
            return jax.transfer_guard("disallow")
        return contextlib.nullcontext()

    def _assert_no_retrace(self) -> None:
        """The steady-state dispatches each compile exactly one program
        (their shapes depend only on the engine geometry); a jit cache
        that grows after its first trace is a retrace regression.  Uses
        ``_cache_size`` where this jax version exposes it."""
        checks = (("_jit_decode", self._jit_decode),
                  ("_jit_unified", self._jit_unified),
                  ("_jit_unified_decode", self._jit_unified_decode))
        if self.speculator is not None:
            checks += (("_spec_mixed", self.speculator._jit_mixed),
                       ("_spec_decode", self.speculator._jit_decode))
        # repro-lint: disable=RPL204 — iterates jit wrappers, not arrays
        for name, fn in checks:
            size_of = getattr(fn, "_cache_size", None)
            if size_of is None:  # pragma: no cover - older/newer jax
                continue
            size = size_of()
            prev = self._trace_sizes.get(name, 0)
            if prev > 0 and size > prev:
                raise AssertionError(
                    f"debug_guards: {name} retraced (jit cache grew "
                    f"{prev} -> {size}); its shapes depend only on the "
                    "engine geometry, so slot churn must never retrace")
            # repro-lint: disable=RPL204 — cache sizes are host ints
            self._trace_sizes[name] = max(prev, size)

    def _up(self, x) -> jax.Array:
        """Host -> device upload of a packed-step input.  On a mesh the
        upload is an *explicit* ``device_put`` onto the replicated
        NamedSharding (transfer-guard-exempt, and the dispatch reshards
        nothing); single-device keeps the plain ``jnp.asarray``."""
        if self._ptab_sharding is not None:
            return jax.device_put(x, self._ptab_sharding)
        return jnp.asarray(x)

    @staticmethod
    def _dev_i32(val) -> jax.Array:
        """Python scalar -> device int32 via *explicit* device_put:
        ``jnp.int32(val)`` runs a convert primitive whose implicit
        host->device upload trips ``transfer_guard("disallow")``."""
        return jax.device_put(np.int32(val))

    # -- jitted device functions ---------------------------------------------
    def _decode_and_sample(self, params, cache: ModelCache, tokens, step_key,
                           temps, topks, topps):
        """All slots: one decode step + on-device per-slot sampling.  The
        (B,) token vector is the only thing the host ever pulls back; the
        (B, 1) next-step feed stays resident on device (reusing the
        donated input buffer), so steady-state decode re-uploads nothing."""
        logits, new_cache = self.model.decode_step(params, cache, tokens)
        with jax.named_scope("sample"):
            keys = jax.random.split(step_key, self.cfg.max_slots)
            toks = sample_slots(logits, keys, temps, topks, topps)
        return toks, toks[:, None], new_cache

    def _unified_and_sample(self, params, cache: ModelCache, tokens,
                            positions, q_start, q_len, kv_len, seg_ptab,
                            step_key, temps, topks, topps, *, max_q,
                            n_decode):
        """The whole engine step as ONE dispatch: packed mixed
        decode+prefill forward (K/V straight to pages) + per-segment
        on-device sampling.  The (S,) token vector — decode samples for
        the slot segments, first-token samples for completing prefill
        segments — is the step's single device->host transfer."""
        packed = PackedSegs(q_start=q_start, q_len=q_len, kv_len=kv_len,
                            page_table=seg_ptab, max_q=max_q,
                            n_decode=n_decode)
        logits, new_cache = self.model.unified_step(params, cache, tokens,
                                                    positions, packed)
        with jax.named_scope("sample"):
            keys = jax.random.split(step_key, q_len.shape[0])
            toks = sample_slots(logits, keys, temps, topks, topps)
        # the first max_slots samples are next step's decode feed: keep a
        # device-resident copy so steady-state decode re-uploads nothing
        return toks, toks[:self.cfg.max_slots], new_cache

    def _prefill_masked(self, params, scratch: ModelCache, tokens, mask):
        """Batched chunked prefill over all scratch rows; ``mask`` selects,
        per row, the advanced state — unmasked rows (idle, or mid-prefill at
        a different chunk width) keep their previous state untouched."""
        logits, new = self.model.prefill_chunk(params, scratch, tokens)

        def sel(n, o):
            m = mask.reshape((1, mask.shape[0]) + (1,) * (n.ndim - 2))
            return jnp.where(m, n, o)

        layers = jax.tree.map(sel, new.layers, scratch.layers)
        lengths = jnp.where(mask, new.lengths, scratch.lengths)
        return logits, ModelCache(layers=layers, lengths=lengths)

    @staticmethod
    def _insert(big: ModelCache, small: ModelCache, slot, row) -> ModelCache:
        """Copy scratch row ``row`` into decode-cache slot ``slot``.  Both
        indices are traced scalars, so every (slot, row) pair shares one
        compiled program."""
        def ins(b, s):
            # leaves: (L, B, ...) vs (L, R, ...); batch is dim 1
            col = jax.lax.dynamic_slice_in_dim(s, row, 1, axis=1)
            idx = (0, slot) + (0,) * (b.ndim - 2)
            return jax.lax.dynamic_update_slice(b, col.astype(b.dtype), idx)

        layers = jax.tree.map(ins, big.layers, small.layers)
        length = jax.lax.dynamic_slice_in_dim(small.lengths, row, 1, axis=0)
        lengths = jax.lax.dynamic_update_slice(big.lengths, length, (slot,))
        return ModelCache(layers=layers, lengths=lengths)

    @staticmethod
    def _insert_paged(big: ModelCache, small: ModelCache, slot, row,
                      pages) -> ModelCache:
        """Paged insert: scatter scratch row ``row`` into the pool pages
        named by ``pages`` (attention layers) and copy the row's SSM/conv
        states into batch slot ``slot`` (state layers are constant-size per
        request — paging never applies to them).  Also installs the slot's
        page-table row, so the device table needs no separate upload."""
        def dense_ins(b, s):
            col = jax.lax.dynamic_slice_in_dim(s, row, 1, axis=1)
            idx = (0, slot) + (0,) * (b.ndim - 2)
            return jax.lax.dynamic_update_slice(b, col.astype(b.dtype), idx)

        new_layers = {}
        for pos, leaf in big.layers.items():
            if isinstance(leaf, PagedAttnCache):
                # leaves carry the leading layer-repeats axis: vmap over it
                new_layers[pos] = jax.vmap(
                    paged_insert_rows, in_axes=(0, 0, None, None))(
                        leaf, small.layers[pos], row, pages)
            else:
                new_layers[pos] = jax.tree.map(dense_ins, leaf,
                                               small.layers[pos])
        length = jax.lax.dynamic_slice_in_dim(small.lengths, row, 1, axis=0)
        lengths = jax.lax.dynamic_update_slice(big.lengths, length, (slot,))
        ptab = jax.lax.dynamic_update_slice(
            big.page_table, pages[None].astype(big.page_table.dtype),
            (slot, 0))
        return ModelCache(layers=new_layers, lengths=lengths,
                          page_table=ptab)

    @staticmethod
    def _reset_row(scratch: ModelCache, row) -> ModelCache:
        """Zero one scratch row (claimed by a newly admitted prompt)."""
        def z(b):
            upd = jnp.zeros(b.shape[:1] + (1,) + b.shape[2:], b.dtype)
            idx = (0, row) + (0,) * (b.ndim - 2)
            return jax.lax.dynamic_update_slice(b, upd, idx)

        layers = jax.tree.map(z, scratch.layers)
        lengths = jax.lax.dynamic_update_slice(
            scratch.lengths, jnp.zeros((1,), scratch.lengths.dtype), (row,))
        return ModelCache(layers=layers, lengths=lengths)

    @staticmethod
    def _copy_page(cache: ModelCache, src, dst) -> ModelCache:
        """Copy-on-write fork: duplicate physical page ``src`` into ``dst``
        across every paged pool leaf (page axis is dim 1 behind the leading
        layer-repeats axis).  Both ids are traced scalars, so every
        (src, dst) pair shares one compiled program."""
        def cp(a):
            page = jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(a, page, dst, axis=1)

        return ModelCache(layers=jax.tree.map(cp, cache.layers),
                          lengths=cache.lengths,
                          page_table=cache.page_table)

    # -- public API --------------------------------------------------------------
    def submit(self, req: Request) -> int:
        req.rid = next(self._ids)
        if self.paged:
            need = self.pager.pages_for(len(req.prompt) + 1)
            # a slot's page-table row holds max_pages entries (= max_seq
            # tokens) and the pool can never lend more than usable_pages
            limit = min(self.max_pages, self.pager.usable_pages)
            if need > limit:
                cap = min(self.max_pages * self.cfg.page_size,
                          self.pager.usable_pages * self.cfg.page_size)
                raise ValueError(
                    f"request {req.rid}: prompt of {len(req.prompt)} tokens "
                    f"needs {need} KV pages but per-request capacity is "
                    f"{limit} pages = {cap} tokens (max_pages="
                    f"{self.max_pages} x page_size={self.cfg.page_size}, "
                    f"usable pool={self.pager.usable_pages})")
        if self.prefix is not None:
            # submit-time lookup: a read-only peek recorded in the cache's
            # own stats (what was cached *at arrival*).  The engine's
            # serving-time hit metrics are counted at admission, where
            # shared pages are actually mapped — under batched submission
            # the cache warms up between submit and admit.
            self.prefix.lookup(req.prompt)
        req.state = "queued"
        req.submit_t = time.perf_counter()
        self.queue.append(req)
        return req.rid

    @staticmethod
    def _src(req: Request) -> list[int]:
        """Prefill token source.  For a preempted request resuming after
        recompute-style eviction this is prompt + everything generated so
        far, so greedy outputs continue identically."""
        return req.prompt + req.output if req.output else req.prompt

    # -- scheduling ----------------------------------------------------------
    def _admit(self) -> None:
        """Greedily start prefills: every free scratch row takes a queued
        prompt, as long as a decode slot is guaranteed at completion and —
        in the paged layout — the pool has free pages for the prompt plus
        one token of headroom (reserved up front, so concurrent prefills
        never race for the same pages).  An exporting engine (P/D
        disaggregation) never promotes into a local decode slot, so the
        slot guarantee is waived and admission is bounded by prefill rows
        and pool pages alone."""
        while (self.queue and self._free_rows
               and (self.export_fn is not None
                    or len(self.active) + len(self._prefills)
                    < self.cfg.max_slots)):
            req = self.queue[0]
            if self.paged:
                if self.prefix is not None and req.rid not in self._attached:
                    self._prefix_attach(req)
                if not self._ensure_or_evict(req.rid,
                                             len(self._src(req)) + 1):
                    break  # pool dry: wait for frees (decode keeps running)
            self.queue.popleft()
            row = self._free_rows.pop()
            self._prefills[row] = req
            # cache-hit prefill starts past the shared prefix: only the
            # uncached suffix is ever computed
            self._prefill_pos[row] = req.n_cached
            req.state = "prefill"
            if not req.admit_t:  # a resumed request keeps its first
                req.admit_t = time.perf_counter()
            if self._rec is not None:
                self._rec.admitted.append(req.rid)
            if not self.unified:  # unified prefill has no scratch to reset
                self.scratch = self._jit_reset_row(self.scratch,
                                                   self._dev_i32(row))
                self.metrics.dispatches += 1

    # -- prefill --------------------------------------------------------------
    def _prefill_step(self) -> None:
        """Advance every in-flight prefill by one chunk.  Rows are grouped
        by this step's chunk width (the final chunk runs at its exact width
        — no padding — which keeps SSM states and token-shift caches exact
        for every architecture family); each group advances in one batched
        call."""
        if not self._prefills:
            return
        groups: dict[int, list[int]] = {}
        for row in sorted(self._prefills):
            req = self._prefills[row]
            w = min(self.cfg.chunk_size,
                    len(self._src(req)) - self._prefill_pos[row])
            groups.setdefault(w, []).append(row)
        for w in sorted(groups):
            self._prefill_chunk_group(w, groups[w])

    def _prefill_chunk_group(self, w: int, rows: list[int]) -> None:
        nrows = self.cfg.prefill_rows
        toks = np.zeros((nrows, w), np.int32)
        mask = np.zeros((nrows,), np.bool_)
        for row in rows:
            lo = self._prefill_pos[row]
            toks[row] = self._src(self._prefills[row])[lo:lo + w]
            mask[row] = True
        logits, self.scratch = self._jit_prefill(
            self.params, self.scratch, jnp.asarray(toks), jnp.asarray(mask))
        self.metrics.prefill_calls += 1
        self.metrics.prefill_tokens += w * len(rows)
        self.metrics.dispatches += 1
        finishing = []
        for row in rows:
            self._prefill_pos[row] += w
            if self._prefill_pos[row] >= len(self._src(self._prefills[row])):
                finishing.append(row)
        if finishing:
            self._finish_prefills(finishing, logits)

    def _finish_prefills(self, rows: list[int], logits) -> None:
        """Sample first tokens for the completing prompts (one batched
        on-device call, one transfer) and move them into decode slots."""
        nrows = self.cfg.prefill_rows
        temps = np.zeros((nrows,), np.float32)
        topks = np.zeros((nrows,), np.int32)
        topps = np.ones((nrows,), np.float32)
        for row in rows:
            s = self._prefills[row].sampling
            temps[row] = s.temperature
            topks[row] = s.top_k
            topps[row] = s.top_p
        self.rng, k = jax.random.split(self.rng)
        keys = jax.random.split(k, nrows)
        first = jax.device_get(self._jit_sample(
            logits, keys, jnp.asarray(temps), jnp.asarray(topks),
            jnp.asarray(topps)))
        self.metrics.dispatches += 1
        self.metrics.transfers_d2h += 1
        now = time.perf_counter()

        def install(req, slot, row):
            """Device insert: copy the scratch row into the decode cache
            (scattered into the request's pages in the paged layout)."""
            if self.paged:
                pages = self._ptab_row(req.rid)
                self._ptab[slot] = pages
                self._dev_ptab = None
                self.cache = self._jit_insert_paged(
                    self.cache, self.scratch, self._dev_i32(slot),
                    self._dev_i32(row), jnp.asarray(pages))
            else:
                self.cache = self._jit_insert(self.cache, self.scratch,
                                              self._dev_i32(slot),
                                              self._dev_i32(row))
            self.metrics.dispatches += 1

        for row in rows:
            self._promote_prefill(row, int(first[row]), now, install)

    # -- prefix cache ---------------------------------------------------------
    def _prefix_attach(self, req: Request) -> None:
        """Map the longest cached page-prefix of this request's source
        tokens read-only into its page list (one refcount per page, charged
        nothing else).  On a FULL hit the tail page would be written by the
        recomputed last token — the engine needs its logits to sample — so
        that one page is forked copy-on-write: a fresh page (charged to the
        request) gets a device copy of the shared page and replaces it in
        the request's table; the shared original is never written."""
        src = self._src(req)
        self._attached.add(req.rid)
        pages = self.prefix.acquire(req.rid, src)
        n_cached = len(pages) * self.cfg.page_size
        m = self.metrics
        m.prefix_lookups += 1
        m.prefix_hits += bool(pages)
        m.prefix_lookup_tokens += len(src)
        m.prefix_hit_tokens += min(n_cached, len(src))
        tally = m.prefix_by_tenant.setdefault(req.tenant or "-", [0, 0])
        tally[0] += min(n_cached, len(src))
        tally[1] += len(src)
        if pages and n_cached >= len(src):
            shared_tail = pages[-1]
            self.pager.release_one(req.rid, shared_tail)
            if self.pager.ensure(req.rid, n_cached):  # ONE fresh fork page
                fork = self.pager.owned(req.rid)[-1]
                if self.speculator is not None:
                    # mirrored pools: the shared page holds valid draft KV
                    # too, so the CoW fork copies it in BOTH pools (one
                    # fused dispatch keeps the accounting exact)
                    self.cache = self.speculator.fork_page(
                        self.cache, self._dev_i32(shared_tail),
                        self._dev_i32(fork))
                else:
                    self.cache = self._jit_copy_page(
                        self.cache, self._dev_i32(shared_tail),
                        self._dev_i32(fork))
                self.metrics.dispatches += 1
                self.metrics.prefix_cow_forks += 1
                n_cached = len(src) - 1
            else:  # pool too tight to fork: cache one page less instead
                n_cached -= self.cfg.page_size
        req.n_cached = min(n_cached, max(len(src) - 1, 0))
        self.metrics.prefix_cached_tokens += req.n_cached

    def _prefix_insert(self, req: Request, processed: int) -> None:
        """Register every *full* page of ``req``'s processed tokens in the
        radix tree (pages it matched at attach time are already there —
        first writer wins).  Called on prefill completion and again when a
        request leaves its slot (finish or preemption), so decoded turns
        become hittable history for multi-turn continuations."""
        ps = self.cfg.page_size
        n_full = (processed // ps) * ps
        if n_full:
            new = self.prefix.insert(self._src(req)[:n_full],
                                     self.pager.owned(req.rid))
            self.metrics.prefix_inserted_pages += new

    def _ensure_or_evict(self, rid: int, n_tokens: int) -> bool:
        """``pager.ensure`` that evicts cold prefix-cache entries (LRU
        refcount-1 leaves) before reporting shortage — clean frees beat
        preempting a live request."""
        if self.pager.ensure(rid, n_tokens):
            return True
        if self.prefix is not None:
            short = (self.pager.pages_for(n_tokens)
                     - len(self.pager.owned(rid)) - self.pager.free_pages)
            if short > 0:
                freed = self.prefix.evict(short)
                self.metrics.prefix_evicted_pages += freed
                if freed >= short:
                    return self.pager.ensure(rid, n_tokens)
        return False

    # -- paged bookkeeping ----------------------------------------------------
    def _ptab_row(self, rid: int) -> np.ndarray:
        """One (max_pages,) page-table row for ``rid``'s held pages, in
        token order, null-page-0 padded."""
        row = np.zeros((self.max_pages,), np.int32)
        held = self.pager.owned(rid)
        row[:len(held)] = held
        return row

    # -- P/D import hooks (decode side of a DisaggCluster) --------------------
    def reserve_imported(self, rid: int, n_tokens: int) -> bool:
        """Reserve admission for a request whose KV pages are arriving
        from another engine's pool: allocate pages for ``n_tokens`` under
        ``rid`` (evicting cold prefix entries if needed) and report
        whether a decode slot is free to install into.  Pure reservation
        — ``install_imported`` completes the hand-off after the
        cross-pool page copy has landed."""
        if not self.paged:
            raise ValueError(
                "imported-page installs need cache_layout='paged'")
        if self.speculator is not None:
            raise ValueError(
                "speculative decoding (n_spec > 0) cannot accept imported "
                "pages: the migration channel fills only the target pool, "
                "so the mirrored draft pool would read garbage")
        if not self.free_slots:
            return False
        return self._ensure_or_evict(rid, n_tokens)

    def install_imported(self, req: Request, kv_len: int) -> int:
        """Install a migrated request into a decode slot.  Its pages —
        already filled under ``req.rid`` by the cross-pool copy — become
        the slot's page-table row and decode resumes from the request's
        last sampled token.  Page-table stitching only: the ragged
        kernel reads migrated pages exactly like home-grown ones."""
        if not req.output:
            raise ValueError(f"request {req.rid}: importing with no "
                             "sampled first token (nothing to decode from)")
        slot = self.free_slots.pop()
        req.slot = slot
        req.state = "decode"
        self.active[slot] = req
        self._ptab[slot] = self._ptab_row(req.rid)
        self._ptab_dirty = True
        self._dev_ptab = None
        self._lengths[slot] = kv_len
        if not self.unified:
            # the two-dispatch decode reads its write position from the
            # device-side lengths (the unified path packs host lengths
            # every step); stitch the slot's length in with its pages
            cache = self.cache
            self.cache = ModelCache(
                layers=cache.layers,
                lengths=cache.lengths.at[slot].set(kv_len),
                page_table=cache.page_table)
        self._tokens[slot, 0] = req.output[-1]
        self._temps[slot] = req.sampling.temperature
        self._topks[slot] = req.sampling.top_k
        self._topps[slot] = req.sampling.top_p
        # slot churn: every cached device mirror is stale
        self._dev_sampling = None
        self._dev_tokens = None
        self._dev_utokens = None
        self.metrics.imports += 1
        return slot

    def _release_slot(self, slot: int, req: Request) -> None:
        """Free-on-finish: return the decode slot and (paged) every page
        the request holds; its page-table row falls back to the null page
        so the now-garbage decode row writes somewhere harmless."""
        self.free_slots.append(slot)
        if self._temps[slot] > 0.0:
            # an idle slot samples greedy, so a batch whose live rows are
            # all greedy skips the vocabulary filters (``sample_slots``)
            self._temps[slot] = 0.0
            self._dev_sampling = None
        if self.paged:
            if self.prefix is not None:
                # full pages of what this request actually processed stay
                # hittable (multi-turn history / cheap preemption resume):
                # the cache's refcounts keep them alive past the release
                self._prefix_insert(req, int(self._lengths[slot]))
                self._attached.discard(req.rid)
            self.pager.release(req.rid)
            self._ptab[slot] = 0
            self._ptab_dirty = True
            self._dev_ptab = None
        if self.speculator is not None:
            self.speculator.release_slot(slot)

    def _preempt(self, slot: int) -> None:
        """Victim preemption: push an active request back to the queue head
        and free its pages.  Recompute-style — on re-admission its prompt +
        generated tokens re-prefill, so greedy outputs are unchanged."""
        req = self.active.pop(slot)
        self._release_slot(slot, req)
        req.state = "queued"
        req.slot = -1
        self.queue.appendleft(req)
        self.metrics.preemptions += 1

    def _grow_pages(self) -> None:
        """Allocate-on-append: every active slot needs a page covering the
        position this step writes (its current length).  When the pool runs
        dry, evict the youngest other active request and retry.  With no
        victim left the request preempts *itself* (pages held by in-flight
        prefill reservations free up once those prompts reach decode, so
        retrying later preserves greedy token-identity); only a request
        whose full context can never fit the pool is force-finished."""
        for slot in sorted(self.active,
                           key=lambda s: self.active[s].rid):
            req = self.active.get(slot)
            if req is None:
                continue
            need = int(self._lengths[slot]) + 1
            if self.speculator is not None:
                # a verify window writes up to K positions past the
                # committed frontier: reserve the whole window up front so
                # rejected proposals never allocate mid-dispatch
                need = min(need + self.speculator.k, self.cfg.max_seq)
            while not self._ensure_or_evict(req.rid, need):
                victims = [s for s, r in self.active.items()
                           if r.rid != req.rid]
                if not victims:
                    if self.pager.pages_for(need) > self.pager.usable_pages:
                        # grew past the whole pool: a capacity stop is the
                        # only option (the dense analogue of max_seq exit)
                        req.state = "done"
                        req.finish_t = time.perf_counter()
                        del self.active[slot]
                        self._release_slot(slot, req)
                        self.finished.append(req)
                        self.metrics.capacity_stops += 1
                    else:
                        self._preempt(slot)
                    break
                self._preempt(max(victims,
                                  key=lambda s: self.active[s].rid))
            else:
                # ensure() only ever appends pages, so a length change is
                # the only way this slot's table row can differ
                held = len(self.pager.owned(req.rid))
                if held != int(np.count_nonzero(self._ptab[slot])):
                    self._ptab[slot] = self._ptab_row(req.rid)
                    self._ptab_dirty = True
                    self._dev_ptab = None

    def _sync_page_table(self) -> None:
        if self._ptab_dirty:
            # on a mesh the table is replicated: an explicit device_put
            # with its NamedSharding keeps the donated-buffer layout
            # stable (page ids are global — only the head axis shards)
            ptab = jnp.asarray(self._ptab) if self._ptab_sharding is None \
                else jax.device_put(self._ptab, self._ptab_sharding)
            self.cache = ModelCache(layers=self.cache.layers,
                                    lengths=self.cache.lengths,
                                    page_table=ptab)
            self._ptab_dirty = False

    # -- decode ---------------------------------------------------------------
    def _decode_step(self) -> None:
        if not self.active:
            return
        if self.paged:
            self._grow_pages()
            self._sync_page_table()
            if not self.active:
                return
        self.rng, step_key = jax.random.split(self.rng)
        if self._dev_sampling is None:
            self._dev_sampling = (jnp.asarray(self._temps),
                                  jnp.asarray(self._topks),
                                  jnp.asarray(self._topps))
        # steady-state decode feeds the device-resident buffer from the
        # previous step (donated in, so XLA updates it in place); only
        # slot churn forces a host re-upload
        feed = self._dev_tokens
        if feed is None:
            feed = jnp.asarray(self._tokens)
        sampled, self._dev_tokens, self.cache = self._jit_decode(
            self.params, self.cache, feed, step_key, *self._dev_sampling)
        # The one device->host transfer of the step: the sampled (B,)
        # token vector.  Everything below reads host numpy only.
        toks = jax.device_get(sampled)
        self.metrics.decode_steps += 1
        self.metrics.dispatches += 1
        self.metrics.transfers_d2h += 1
        self._finish_decode_slots(toks, time.perf_counter())

    def _finish_decode_slots(self, toks, now: float) -> None:
        """Shared decode bookkeeping (two-dispatch and unified paths must
        never drift): append each active slot's sampled token, advance
        lengths, exit on max_new / eos / max_seq, free on finish."""
        for slot, req in list(self.active.items()):
            tok = int(toks[slot])
            req.output.append(tok)
            self._lengths[slot] += 1
            self.metrics.generated_tokens += 1
            done = (len(req.output) >= req.max_new_tokens
                    or (req.eos_id is not None and tok == req.eos_id)
                    or self._lengths[slot] >= self.cfg.max_seq - 1)
            if done:
                req.state = "done"
                req.finish_t = now
                del self.active[slot]
                self._release_slot(slot, req)
                self.finished.append(req)
            else:
                self._tokens[slot, 0] = tok

    def _promote_prefill(self, row: int, tok: int, now: float,
                         install) -> None:
        """Shared prefill-completion bookkeeping: record the first token
        and move the request from its prefill row into a decode slot.
        ``install(req, slot, row)`` puts the request's KV where the slot
        will read it (device insert on the two-dispatch path; a host
        page-table row on the unified path, whose pages already hold it).
        """
        req = self._prefills.pop(row)
        del self._prefill_pos[row]
        src_len = len(self._src(req))  # tokens the prefill processed
        if not req.output:  # resumed requests keep their original TTFT
            req.ttft_steps = self.steps
            req.first_token_t = now
        req.output.append(tok)
        self.metrics.generated_tokens += 1
        if self.export_fn is not None:
            # P/D hand-off: the request leaves this engine at prefill
            # completion.  Its pages stay owned by its rid (the migration
            # channel copies them out and releases them); the prefill row
            # frees immediately so the next prompt can start.
            if not self.unified:
                raise ValueError(
                    "export_fn needs unified=True: only the packed step "
                    "writes prefill K/V directly into pages — the dense-"
                    "scratch path has nothing page-resident to migrate")
            self._free_rows.append(row)
            if self.prefix is not None:
                self._prefix_insert(req, src_len)
                self._attached.discard(req.rid)
            self.metrics.exports += 1
            done = (len(req.output) >= req.max_new_tokens
                    or (req.eos_id is not None and tok == req.eos_id))
            self.export_fn(req, src_len, done, now)
            return
        slot = self.free_slots.pop()
        req.slot = slot
        install(req, slot, row)
        self._free_rows.append(row)
        self._lengths[slot] = src_len
        if self.prefix is not None:
            # insert on prefill completion: every full page of the prompt
            # becomes hittable while this request is still decoding
            self._prefix_insert(req, src_len)
        if (len(req.output) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)):
            req.state = "done"
            req.finish_t = now
            self._release_slot(slot, req)
            self.finished.append(req)
            return
        req.state = "decode"
        self.active[slot] = req
        self._tokens[slot, 0] = tok
        self._temps[slot] = req.sampling.temperature
        self._topks[slot] = req.sampling.top_k
        self._topps[slot] = req.sampling.top_p
        # slot churn: every cached device mirror is stale
        self._dev_sampling = None
        self._dev_tokens = None
        self._dev_utokens = None

    # -- unified token-packed step --------------------------------------------
    def _pack_guard(self, req: Request, src_len: int) -> None:
        """A segment whose context can never fit its page-table row must
        fail loudly at pack time, not inside the kernel's index map."""
        cap = self.max_pages * self.cfg.page_size
        if src_len + 1 > cap:
            raise ValueError(
                f"request {req.rid}: packing a {src_len}-token context "
                f"exceeds the per-request KV capacity of {cap} tokens "
                f"(max_pages={self.max_pages} x page_size="
                f"{self.cfg.page_size})")

    def _unified_step(self):
        """The whole iteration in ONE jitted dispatch: all active slots'
        decode tokens and all in-flight prompts' current chunks packed
        into the fixed ragged layout, prefill K/V written directly to
        pages, every segment sampled on device.  The sampled (S,) vector
        is the step's single device->host transfer.  Returns the step's
        commit (the host bookkeeping on the pulled tokens), or None when
        no request is left to step."""
        span = jax.profiler.TraceAnnotation
        with span("engine.pages"):
            self._grow_pages()
        if not (self.active or self._prefills):
            return None
        nslots, csize = self.cfg.max_slots, self.cfg.chunk_size
        # two static packed profiles (one compiled program each): the
        # decode-only layout (T = max_slots) when no prefill is in flight,
        # else the full mixed layout — idle prefill rows never pad the
        # decode hot path, and the step stays ONE dispatch either way
        mixed = bool(self._prefills)
        n_segs, t_pack = (self.n_segs, self.t_pack) if mixed \
            else (nslots, nslots)
        widths: dict[int, int] = {}
        with span("engine.pack"):
            positions = np.zeros((t_pack,), np.int32)
            q_len = np.zeros((n_segs,), np.int32)
            kv_len = np.zeros((n_segs,), np.int32)
            # decode segments: slot s's next token at packed offset s
            for slot in self.active:
                positions[slot] = self._lengths[slot]
                q_len[slot] = 1
                kv_len[slot] = self._lengths[slot] + 1
            completing = 0
            if mixed:
                tokens = np.zeros((t_pack,), np.int32)
                tokens[:nslots] = self._tokens[:, 0]
                seg_ptab = np.zeros((n_segs, self.max_pages), np.int32)
                seg_ptab[:nslots] = self._ptab
                temps = np.zeros((n_segs,), np.float32)
                topks = np.zeros((n_segs,), np.int32)
                topps = np.ones((n_segs,), np.float32)
                temps[:nslots] = self._temps
                topks[:nslots] = self._topks
                topps[:nslots] = self._topps
                # prefill segments: row r's current chunk at
                # nslots + r * csize
                for row, req in self._prefills.items():
                    src = self._src(req)
                    self._pack_guard(req, len(src))
                    lo = self._prefill_pos[row]
                    w = min(csize, len(src) - lo)
                    seg, qs = nslots + row, nslots + row * csize
                    tokens[qs:qs + w] = src[lo:lo + w]
                    positions[qs:qs + w] = np.arange(lo, lo + w)
                    q_len[seg] = w
                    kv_len[seg] = lo + w
                    seg_ptab[seg] = self._ptab_row(req.rid)
                    widths[row] = w
                    if lo + w >= len(src):  # completes: its config samples
                        s = req.sampling
                        temps[seg] = s.temperature
                        topks[seg] = s.top_k
                        topps[seg] = s.top_p
                        completing += 1
            rec = self._rec
            if rec is not None:
                rec.mixed, rec.rows_packed = mixed, t_pack
                rec.rows_live = int(q_len.sum())
                rec.decode = [int(kv_len[s]) for s in self.active]
                rec.prefill = [(w, int(kv_len[nslots + r]))
                               for r, w in widths.items()]
                rec.sampled = len(self.active) + completing
                rec.stochastic = int(
                    ((temps if mixed else self._temps) > 0.0).sum())
        with span("engine.upload"):
            if mixed:
                fn, seg_start = self._jit_unified, self._seg_start_dev
                tokens_dev = self._up(tokens)
                ptab_dev = self._up(seg_ptab)
                sampling_dev = (self._up(temps), self._up(topks),
                                self._up(topps))
            else:
                # decode-only steady state: tokens, sampling params and
                # the slot page table all live on device already —
                # nothing but positions/lengths (which advance every
                # step) is uploaded
                fn, seg_start = self._jit_unified_decode, \
                    self._seg_start_decode_dev
                tokens_dev = self._dev_utokens
                if tokens_dev is None:
                    tokens_dev = self._up(self._tokens[:, 0])
                if self._dev_ptab is None:
                    self._dev_ptab = self._up(self._ptab)
                ptab_dev = self._dev_ptab
                if self._dev_sampling is None:
                    self._dev_sampling = (self._up(self._temps),
                                          self._up(self._topks),
                                          self._up(self._topps))
                sampling_dev = self._dev_sampling
            segs_dev = (self._up(positions), seg_start, self._up(q_len),
                        self._up(kv_len))
            self.rng, step_key = jax.random.split(self.rng)
            if self._ptab_sharding is not None:
                # the split key lives on device 0: replicate it
                # explicitly so the dispatch stays transfer-free under
                # the guard
                step_key = jax.device_put(step_key, self._ptab_sharding)
        with span("engine.dispatch"):
            sampled, self._dev_utokens, self.cache = fn(
                self.params, self.cache, tokens_dev, *segs_dev, ptab_dev,
                step_key, *sampling_dev)
        with span("engine.pull"):
            # the step's only device->host transfer: the (S,) tokens
            toks = jax.device_get(sampled)
        return functools.partial(self._unified_commit, toks, mixed, widths)

    def _unified_commit(self, toks, mixed: bool, widths: dict) -> None:
        """Host bookkeeping of a unified step on its pulled tokens: finish
        and advance decode slots, advance prefill rows, promote the
        completing ones."""
        nslots = self.cfg.max_slots
        self.metrics.dispatches += 1
        self.metrics.transfers_d2h += 1
        coll, coll_bytes = self._coll_mixed if mixed else self._coll_decode
        self.metrics.collectives += coll
        self.metrics.collective_bytes += coll_bytes
        now = time.perf_counter()
        if self.active:
            self.metrics.decode_steps += 1
        self._finish_decode_slots(toks, now)
        # -- prefill bookkeeping ----------------------------------------------
        if widths:
            self.metrics.prefill_calls += 1
            self.metrics.prefill_tokens += sum(widths.values())
        finishing = [row for row, w in widths.items()
                     if self._prefill_pos[row] + w
                     >= len(self._src(self._prefills[row]))]
        for row, w in widths.items():
            self._prefill_pos[row] += w

        def install(req, slot, row):
            """The pages already hold the prompt's KV — "inserting" into
            a decode slot is pure host bookkeeping."""
            self._ptab[slot] = self._ptab_row(req.rid)
            self._dev_ptab = None

        for row in finishing:
            self._promote_prefill(row, int(toks[nslots + row]), now,
                                  install)

    # -- speculative token-packed step ----------------------------------------
    def _spec_step(self):
        """The unified step with speculation: every active slot packs a
        K+1-token verify window (committed feed + the draft's K proposals,
        causal within the segment); the draft catch-up, the K-step propose
        loop, the target verify, device-side accept/reject and prefill
        chunks all ride ONE jitted dispatch, and the accepted tokens +
        per-slot counts come back in the step's ONE device->host transfer.
        Rollback of rejected tokens is pure length bookkeeping on both the
        host mirrors and the device ``cache.lengths`` (stale K/V past the
        accepted frontier is masked by kv_len until overwritten — the
        preemption-recompute invariant).  Returns the step's commit, or
        None when no request is left to step."""
        span = jax.profiler.TraceAnnotation
        with span("engine.pages"):
            self._grow_pages()
        if not (self.active or self._prefills):
            return None
        spec = self.speculator
        nslots, csize = self.cfg.max_slots, self.cfg.chunk_size
        rows = self.cfg.prefill_rows
        mixed = bool(self._prefills)
        widths: dict[int, int] = {}
        with span("engine.pack"):
            n_samp = nslots + rows if mixed else nslots
            feed = np.zeros((nslots,), np.int32)
            d_feed = np.zeros((nslots, 2), np.int32)
            lengths = np.zeros((nslots,), np.int32)
            gaps = np.zeros((nslots,), np.int32)
            win = np.zeros((nslots,), np.int32)
            temps = np.zeros((n_samp,), np.float32)
            topks = np.zeros((n_samp,), np.int32)
            topps = np.ones((n_samp,), np.float32)
            temps[:nslots] = self._temps
            topks[:nslots] = self._topks
            topps[:nslots] = self._topps
            for slot, req in self.active.items():
                src = self._src(req)
                sl = int(self._lengths[slot])
                g, tail = spec.catch_up(slot, src)
                if not 1 <= g <= 2:  # the draft frontier invariant
                    raise AssertionError(
                        f"slot {slot}: draft gap {g} outside {{1, 2}} "
                        f"(d_len={int(spec.d_lens[slot])}, len={sl})")
                feed[slot] = src[-1]
                d_feed[slot, :g] = tail
                lengths[slot] = sl
                gaps[slot] = g
                win[slot] = min(spec.k + 1, self.cfg.max_seq - sl)
            completing = 0
            pre = [None] * 5  # tokens, positions, q_len, kv_len, ptab
            if mixed:
                pre = [np.zeros((rows * csize,), np.int32),
                       np.zeros((rows * csize,), np.int32),
                       np.zeros((rows,), np.int32),
                       np.zeros((rows,), np.int32),
                       np.zeros((rows, self.max_pages), np.int32)]
                pre_tokens, pre_positions, pre_q_len, pre_kv_len, \
                    pre_ptab = pre
                for row, req in self._prefills.items():
                    src = self._src(req)
                    self._pack_guard(req, len(src))
                    lo = self._prefill_pos[row]
                    w = min(csize, len(src) - lo)
                    qs = row * csize
                    pre_tokens[qs:qs + w] = src[lo:lo + w]
                    pre_positions[qs:qs + w] = np.arange(lo, lo + w)
                    pre_q_len[row] = w
                    pre_kv_len[row] = lo + w
                    pre_ptab[row] = self._ptab_row(req.rid)
                    widths[row] = w
                    if lo + w >= len(src):  # completes: its config samples
                        s = req.sampling
                        temps[nslots + row] = s.temperature
                        topks[nslots + row] = s.top_k
                        topps[nslots + row] = s.top_p
                        completing += 1
            rec = self._rec
            if rec is not None:
                rec.mixed = mixed
                rec.rows_packed = nslots * (spec.k + 1) \
                    + (rows * csize if mixed else 0)
                rec.rows_live = int(win.sum()) + sum(widths.values())
                rec.decode = [int(lengths[s] + win[s]) for s in self.active]
                rec.prefill = [(w, int(pre[3][r]))
                               for r, w in widths.items()]
                rec.sampled = len(self.active) + completing
                rec.stochastic = int((temps > 0.0).sum())
        with span("engine.upload"):
            if self._dev_ptab is None:
                self._dev_ptab = self._up(self._ptab)
            slots_dev = [self._up(a) for a in (feed, d_feed, lengths, gaps,
                                                win)]
            pre_dev = [None if a is None else self._up(a) for a in pre]
            sampling_dev = [self._up(a) for a in (temps, topks, topps)]
            self.rng, step_key = jax.random.split(self.rng)
        with span("engine.dispatch"):
            self.cache, pulled = spec.dispatch(
                self.params, self.cache, *slots_dev, self._dev_ptab,
                *pre_dev, step_key, *sampling_dev, mixed=mixed)
        with span("engine.pull"):
            # the step's only device->host transfer: accepted tokens,
            # per-slot counts, and (mixed) the completing prefills' first
            # tokens
            out_toks, n_emit, pre_sampled = jax.device_get(pulled)
        return functools.partial(self._spec_commit, out_toks, n_emit,
                                 pre_sampled, win, widths)

    def _spec_commit(self, out_toks, n_emit, pre_sampled, win,
                     widths: dict) -> None:
        """Host bookkeeping of a speculative step on its pulled tokens."""
        spec = self.speculator
        self.metrics.dispatches += 1
        self.metrics.transfers_d2h += 1
        now = time.perf_counter()
        if self.active:
            self.metrics.decode_steps += 1
            self.metrics.spec_rounds += 1
        self._finish_spec_slots(out_toks, n_emit, win, now)
        # -- prefill bookkeeping (identical to the non-speculative step) ------
        if widths:
            self.metrics.prefill_calls += 1
            self.metrics.prefill_tokens += sum(widths.values())
        finishing = [row for row, w in widths.items()
                     if self._prefill_pos[row] + w
                     >= len(self._src(self._prefills[row]))]
        for row, w in widths.items():
            self._prefill_pos[row] += w

        def install(req, slot, row):
            """Pages already hold the prompt's KV in BOTH pools (the
            packed prefill chunks ran through target and draft): promote
            is host bookkeeping plus seeding the draft frontier."""
            self._ptab[slot] = self._ptab_row(req.rid)
            self._dev_ptab = None
            # _src already includes the just-sampled first token; the
            # pools hold everything before it
            spec.install_slot(slot, len(self._src(req)) - 1)

        for row in finishing:
            self._promote_prefill(row, int(pre_sampled[row]), now, install)

    def _finish_spec_slots(self, out_toks, n_emit, win, now: float) -> None:
        """Per-slot commit of a speculative round: append the accepted
        prefix + the resampled/bonus token one at a time under the SAME
        stop conditions as plain decode (max_new / eos / max_seq), so
        greedy outputs truncate identically to the non-speculative engine.
        A mid-window stop discards the tail and frees the slot — the
        device's overshoot in ``cache.lengths`` dies with the slot."""
        spec = self.speculator
        m = self.metrics
        for slot, req in list(self.active.items()):
            sl = int(self._lengths[slot])
            w = int(win[slot])
            emit = int(n_emit[slot])
            m.spec_slot_rounds += 1
            m.spec_proposed += w - 1
            m.spec_accepted += emit - 1
            m.spec_bonus += emit == w
            m.spec_emitted += emit
            tally = m.spec_by_slot.setdefault(slot, [0, 0])
            tally[0] += emit - 1
            tally[1] += w - 1
            done = False
            committed = 0
            for j in range(emit):
                tok = int(out_toks[slot, j])
                req.output.append(tok)
                self._lengths[slot] += 1
                committed += 1
                m.generated_tokens += 1
                done = (len(req.output) >= req.max_new_tokens
                        or (req.eos_id is not None and tok == req.eos_id)
                        or self._lengths[slot] >= self.cfg.max_seq - 1)
                if done:
                    break
            spec.commit_slot(slot, sl, committed,
                             spec.proposal_steps(sl))
            if done:
                req.state = "done"
                req.finish_t = now
                del self.active[slot]
                self._release_slot(slot, req)
                self.finished.append(req)
            else:
                self._tokens[slot, 0] = int(out_toks[slot, emit - 1])

    # -- main loop ------------------------------------------------------------
    @property
    def _prefilling(self) -> bool:
        return bool(self._prefills)

    def step(self) -> None:
        """One engine iteration: a decode step for all active slots plus a
        prefill chunk for every in-flight prompt (decode-priority order) —
        or, with ``unified=True``, both packed into one dispatch.

        The step's phases are ``jax.profiler.TraceAnnotation`` spans
        (``engine.step`` around all of it, ``engine.admit``; on the packed
        paths ``engine.pages``, ``engine.pack``, ``engine.upload``,
        ``engine.dispatch``, ``engine.pull`` and ``engine.commit``), so a
        profiler trace places the host's time beside the device's."""
        if self.metrics.start_t == 0.0:
            self.metrics.start_t = time.perf_counter()
        self.steps += 1
        self.metrics.steps += 1
        if self.cfg.record_step_log:
            self._rec = StepRecord(step=self.steps, t0=time.perf_counter())
        preempted = self.metrics.preemptions
        span = jax.profiler.TraceAnnotation
        with span("engine.step", step=self.steps):
            with span("engine.admit"):
                self._admit()
            with self._step_guard():
                commit = None
                if self.speculator is not None:
                    commit = self._spec_step()
                elif self.unified:
                    commit = self._unified_step()
                elif self.cfg.decode_priority:
                    self._decode_step()
                    self._prefill_step()
                else:
                    self._prefill_step()
                    self._decode_step()
                with (span("engine.commit") if self.unified
                      else contextlib.nullcontext()):
                    if commit is not None:
                        commit()
                    self._account_step(preempted)

    def _account_step(self, preempted: int) -> None:
        """End-of-step audits and metric updates (``preempted``: the
        preemption count when the step began)."""
        if self.debug_guards:
            self._assert_no_retrace()
            if self.paged:
                self.pager.check()  # refcount / free-list invariant audit
            if self.prefix is not None:
                self.prefix.check()
        self.metrics.end_t = time.perf_counter()
        self.metrics.occupancy_sum += len(self.active) / self.cfg.max_slots
        m = self.metrics
        m.peak_active = max(m.peak_active, len(self.active))
        m.peak_inflight = max(m.peak_inflight,
                              len(self.active) + len(self._prefills))
        # kv utilization = live KV tokens / reserved capacity tokens, with
        # the SAME numerator definition for both layouts so dense-vs-paged
        # utilization ratios measure packing, not accounting differences
        used = int(sum(self._lengths[s] for s in self.active))
        if self.paged:
            cap_tokens = self.pager.usable_pages * self.cfg.page_size
            m.pages_in_use_peak = max(m.pages_in_use_peak,
                                      self.pager.pages_in_use)
            if self.prefix is not None:
                m.prefix_shared_pages_peak = max(m.prefix_shared_pages_peak,
                                                 self.pager.shared_pages)
        else:
            cap_tokens = self.cfg.max_slots * self.cfg.max_seq
        m.kv_util_sum += used / cap_tokens
        m.kv_used_tokens_peak = max(m.kv_used_tokens_peak, used)
        rec, self._rec = self._rec, None
        if rec is not None:
            rec.preempted = m.preemptions - preempted
            if self.paged:
                rec.pages_in_use = self.pager.pages_in_use
            rec.t1 = time.perf_counter()
            m.step_log.append(rec)

    def kv_stats(self) -> dict:
        """Static + peak KV-capacity numbers for benchmarks: the decode
        cache's device reservation in bytes and the peak bytes actually
        holding live tokens (the dense layout's footprint *is* its
        reservation — that gap is what paging recovers)."""
        leaves = []
        for leaf in self.cache.layers.values():
            if isinstance(leaf, (PagedAttnCache,)) or hasattr(leaf, "k"):
                for f in ("k", "v", "k_scale", "v_scale"):
                    arr = getattr(leaf, f, None)
                    if arr is not None:
                        leaves.append(arr)
        reserved = int(sum(x.size * x.dtype.itemsize for x in leaves))
        out = {"cache_layout": self.cfg.cache_layout,
               "kv_reserved_bytes": reserved}
        if self.paged:
            per_page = reserved / self.pager.n_pages
            per_token = per_page / self.cfg.page_size
            out.update(
                page_size=self.cfg.page_size,
                n_pages=self.pager.n_pages,
                usable_pages=self.pager.usable_pages,
                kv_peak_bytes=int(self.pager.peak_in_use * per_page),
                kv_live_peak_bytes=int(self.metrics.kv_used_tokens_peak
                                       * per_token),
                pages_in_use=self.pager.pages_in_use)
        else:
            cap_tokens = self.cfg.max_slots * self.cfg.max_seq
            per_token = reserved / cap_tokens
            out.update(
                kv_peak_bytes=reserved,  # dense footprint == reservation
                kv_live_peak_bytes=int(self.metrics.kv_used_tokens_peak
                                       * per_token))
        return out

    @property
    def busy(self) -> bool:
        """Queued, prefilling, or decoding work pending."""
        return bool(self.queue or self.active or self._prefills)

    def run(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.busy:
                break
            self.step()

    def serve(self, requests: list[Request],
              max_steps: int = 10_000) -> list[Request]:
        for r in requests:
            self.submit(r)
        self.run(max_steps)
        return requests
