#!/usr/bin/env python
"""Serving benchmark: request-rate × prompt-length-mix sweep over the
rebuilt ServeEngine, emitting JSON so successive PRs have a serving perf
trajectory (tokens/s, TTFT, TPOT, slot occupancy per cell).

    PYTHONPATH=src python benchmarks/serving_bench.py            # full sweep
    PYTHONPATH=src python benchmarks/serving_bench.py --smoke    # CI smoke
    PYTHONPATH=src python benchmarks/serving_bench.py --out r.json
    PYTHONPATH=src python benchmarks/serving_bench.py --scenario sc.json
    PYTHONPATH=src python benchmarks/serving_bench.py --paged    # paged KV
    PYTHONPATH=src python benchmarks/serving_bench.py --unified  # packed step
    PYTHONPATH=src python benchmarks/serving_bench.py --compare-paged \
        --out artifacts/benchmarks/paged_kv.json   # dense-vs-paged capacity
    PYTHONPATH=src python benchmarks/serving_bench.py --compare-unified \
        --out artifacts/benchmarks/unified_step.json  # one-dispatch win
    PYTHONPATH=src python benchmarks/serving_bench.py --compare-spec \
        --out artifacts/benchmarks/speculative.json  # batched speculation
    PYTHONPATH=src python benchmarks/serving_bench.py --trace [trace.json] \
        # replay a (generated or loaded) bursty multi-tenant trace through
        # the prefix-cache engine AND a cache-off twin; token identity
        # asserted, SLO attainment + goodput reported for both
    PYTHONPATH=src python benchmarks/serving_bench.py --compare-prefix \
        --out artifacts/benchmarks/prefix_cache.json  # prefix-cache win
    PYTHONPATH=src python benchmarks/serving_bench.py --compare-disagg \
        --out artifacts/benchmarks/disagg.json  # P/D disaggregation
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python benchmarks/serving_bench.py --compare-tp \
        --out artifacts/benchmarks/tp_serving.json  # mesh-sharded tp/pp

Every cell reports peak KV bytes and cache utilization alongside
throughput/latency (``kv_reserved_bytes`` / ``kv_peak_bytes`` /
``kv_utilization_mean``), for the dense and the paged layout alike.
``--compare-paged`` runs the same workload through a dense engine and a
paged engine holding the *same HBM token budget* and records the
concurrency / utilization win (the paper's §V memory-capacity lever).
``--compare-unified`` runs the same rate x prompt-mix sweep through a
two-dispatch paged engine and the unified token-packed engine (one jitted
dispatch + one device->host transfer per step), asserts greedy outputs
stay token-identical, and records tokens/s, TTFT, TPOT and
dispatches/step per cell plus the predicted-vs-measured chunked-TPOT
error from ``repro.scenario.compare`` (the paper's validation loop for
the chunking optimization).

The engine under test is constructed by *lowering a Scenario*
(``repro.scenario``): either one loaded from ``--scenario`` (a
``Scenario.to_json()`` file; its model / mode / chunk spec drive the
engine) or one assembled from the CLI flags.  The open-loop driver then
sweeps offered rate × prompt mix around that scenario: arrivals are
Poisson at the offered rate; requests are submitted when wall-clock passes
their arrival time, and the engine steps whenever it has work.  One engine
instance is reused across cells (same jitted programs — only chunk widths
retrace), with metrics reset per cell.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import numpy as np

from repro.launch.runtime import use_compile_cache
from repro.serving import EngineConfig, EngineMetrics, Request, ServeEngine

MIXES = {
    "short": (4, 16),
    "mixed": (4, 48),
    "long": (48, 96),
}


def build_scenario(args):
    """CLI flags -> the Scenario the engine is lowered from."""
    from repro.core.modelspec import AttnSpec, ModelSpec
    from repro.core.stages import Workload
    from repro.scenario import ChunkedSpec, Scenario

    if args.scenario:
        return Scenario.from_json(Path(args.scenario).read_text())
    model = args.arch or ModelSpec(
        name="bench-tiny", d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_head=16, d_ff=128, vocab=256, attn=AttnSpec(kind="full",
                                                      causal=True))
    wl = Workload(batch=args.requests, tau_p=max(MIXES[m][1] for m in
                                                 args.mixes),
                  tau_d=args.max_new, name="serving-bench")
    return Scenario.make(model, workload=wl, batch=args.requests,
                         platform="hgx-h100x8", mode="chunked",
                         chunked=ChunkedSpec(chunk=args.chunk,
                                             decode_batch=args.slots))


def page_size(args, sc) -> int:
    """Effective KV page size: an explicit --page-size wins, then a paged
    Scenario's own kv_page_size, then the default."""
    if args.page_size is not None:
        return args.page_size
    return sc.opt.kv_page_size if sc.opt.paged_kv else 16


def build_engine(sc, args, layout=None, unified=None):
    """Lower the Scenario to a live engine (shared with the scenario
    engine backend, so bench and backend measure the same thing)."""
    from repro.scenario.engine_backend import lower_model

    if sc.mode not in ("monolithic", "chunked"):
        raise SystemExit(
            f"serving_bench drives a plain ServeEngine; scenario mode "
            f"{sc.mode!r} has no lowering here (use repro.scenario.run("
            f"sc, backend='engine') for speculative scenarios)")
    spec, model, params = lower_model(sc.model)
    chunk = (sc.chunked.chunk if sc.mode == "chunked" and sc.chunked
             else args.chunk)
    unified = args.unified if unified is None else unified
    layout = layout or ("paged" if (args.paged or sc.opt.paged_kv or unified)
                        else "dense")
    paging = {}
    if layout == "paged":
        paging = dict(cache_layout="paged", page_size=page_size(args, sc),
                      n_pages=args.n_pages)
    cfg = EngineConfig(max_slots=args.slots, max_seq=args.max_seq,
                       chunk_size=min(chunk, args.max_seq),
                       prefill_rows=args.prefill_rows, unified=unified,
                       **paging)
    return spec, ServeEngine(model, params, cfg, rng=jax.random.key(1))


def run_cell(eng: ServeEngine, vocab: int, rate: float, mix: str,
             n_requests: int, max_new: int, seed: int) -> dict:
    """One sweep cell: Poisson arrivals at ``rate`` req/s, prompt lengths
    uniform in MIXES[mix]."""
    rng = np.random.default_rng(seed)
    lo, hi = MIXES[mix]
    prompts = [[int(t) for t in rng.integers(0, vocab,
                                             size=int(rng.integers(lo, hi)))]
               for _ in range(n_requests)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests))
    reqs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]

    eng.metrics = EngineMetrics()  # per-cell metrics window
    if eng.paged:  # the allocator's peak is lifetime-monotonic: re-base it
        eng.pager.peak_in_use = eng.pager.pages_in_use
    t0 = time.perf_counter()
    i = 0
    while i < len(reqs) or eng.queue or eng.active or eng._prefilling:
        now = time.perf_counter() - t0
        while i < len(reqs) and arrivals[i] <= now:
            eng.submit(reqs[i])
            i += 1
        if not (eng.queue or eng.active or eng._prefilling):
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.05))
            continue
        eng.step()
    wall = time.perf_counter() - t0

    assert all(r.state == "done" for r in reqs)
    cell = {"rate_req_s": rate, "mix": mix, "n_requests": n_requests,
            "max_new_tokens": max_new, "cell_wall_s": wall,
            "prompt_tokens": sum(len(p) for p in prompts)}
    cell.update(eng.metrics.summary(reqs))
    cell.update(eng.kv_stats())  # peak KV bytes + reservation per layout
    return cell, reqs


def compare_paged(sc, args) -> dict:
    """Dense vs paged under the same HBM token budget (the tentpole's
    acceptance number): the dense engine reserves slots x max_seq tokens;
    the paged engine gets exactly that many tokens as pages plus a wide
    scheduling limit, and the win is how many more requests it keeps
    resident (peak_active) and how much less KV it touches at peak."""
    from repro.scenario.engine_backend import lower_model

    spec, model, params = lower_model(sc.model)
    budget_tokens = args.slots * args.max_seq
    ps = page_size(args, sc)
    rng = np.random.default_rng(args.seed)

    def workload():
        lo, hi = MIXES["mixed"]
        return [Request(prompt=[int(t) for t in rng.integers(
                    0, spec.vocab, size=int(r))],
                        max_new_tokens=args.max_new)
                for r in rng.integers(lo, hi, size=args.requests)]

    rng_state = rng.bit_generator.state
    out = {"budget_tokens": budget_tokens, "max_seq": args.max_seq,
           "page_size": ps, "n_requests": args.requests}
    outputs: dict[str, list] = {}
    for layout in ("dense", "paged"):
        rng.bit_generator.state = rng_state  # identical request sets
        if layout == "dense":
            cfg = EngineConfig(max_slots=args.slots, max_seq=args.max_seq,
                               chunk_size=args.chunk,
                               prefill_rows=args.prefill_rows)
        else:
            cfg = EngineConfig(
                max_slots=min(args.requests, 4 * args.slots),
                max_seq=args.max_seq, chunk_size=args.chunk,
                prefill_rows=args.prefill_rows, cache_layout="paged",
                page_size=ps, n_pages=budget_tokens // ps + 1)
        eng = ServeEngine(model, params, cfg, rng=jax.random.key(1))
        reqs = eng.serve(workload())
        assert all(r.state == "done" for r in reqs)
        cell = eng.metrics.summary(reqs)
        cell.update(eng.kv_stats())
        outputs[layout] = [list(r.output) for r in reqs]
        cell["outputs_sha1"] = hashlib.sha1(
            repr(outputs[layout]).encode()).hexdigest()
        out[layout] = cell
    # exact per-request token sequences must match, not just a digest
    assert outputs["dense"] == outputs["paged"], \
        "dense and paged engines diverged on the same workload"
    out["concurrency_win"] = (out["paged"]["peak_active"]
                              / max(out["dense"]["peak_active"], 1))
    out["utilization_win"] = (out["paged"]["kv_utilization_mean"]
                              / max(out["dense"]["kv_utilization_mean"],
                                    1e-12))
    return out


def compare_unified(sc, args) -> dict:
    """Two-dispatch paged engine vs the unified token-packed step on the
    same mixed rate x prompt sweep: identical requests through both,
    greedy outputs asserted token-identical, and the win reported as
    aggregate tokens/s plus per-cell TTFT/TPOT/dispatches-per-step.  The
    analytical chunked-TPOT prediction (one fused pass per iteration,
    ``core.stages.chunked``) is compared against the measured unified
    TPOT through ``repro.scenario.compare`` — the paper's
    predicted-vs-measured loop, now against a real fused implementation.
    """
    out = {"max_slots": args.slots, "max_seq": args.max_seq,
           "chunk_size": args.chunk, "prefill_rows": args.prefill_rows,
           "page_size": page_size(args, sc), "n_requests": args.requests,
           "rates": args.rates, "mixes": args.mixes}
    outputs: dict[str, list] = {}
    for mode in ("two_dispatch", "unified"):
        spec, eng = build_engine(sc, args, layout="paged",
                                 unified=(mode == "unified"))
        # warm the jitted programs so cell 0 isn't all compile time
        eng.serve([Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=2)])
        cells, outs = [], []
        for mix in args.mixes:
            for rate in args.rates:
                cell, reqs = run_cell(eng, spec.vocab, rate, mix,
                                      args.requests, args.max_new,
                                      args.seed)
                cells.append(cell)
                outs.append([list(r.output) for r in reqs])
        gen = sum(c["generated_tokens"] for c in cells)
        wall = sum(c["cell_wall_s"] for c in cells)
        outputs[mode] = outs
        out[mode] = {
            "cells": cells,
            "generated_tokens": gen,
            "sweep_wall_s": wall,
            "tokens_per_s": gen / wall if wall > 0 else 0.0,
            "ttft_s_mean": float(np.mean([c["ttft_s_mean"] for c in cells])),
            "tpot_s_mean": float(np.mean([c["tpot_s_mean"] for c in cells])),
            "dispatches_per_step": (sum(c["dispatches"] for c in cells)
                                    / max(sum(c["steps"] for c in cells), 1)),
            "transfers_per_step": (sum(c["transfers_d2h"] for c in cells)
                                   / max(sum(c["steps"] for c in cells), 1)),
            "outputs_sha1": hashlib.sha1(
                repr(outs).encode()).hexdigest(),
        }
    # greedy token-identity between the two implementations, per request
    assert outputs["two_dispatch"] == outputs["unified"], \
        "unified and two-dispatch engines diverged on the same workload"
    out["tokens_per_s_win"] = (out["unified"]["tokens_per_s"]
                               / max(out["two_dispatch"]["tokens_per_s"],
                                     1e-12))
    out["dispatch_collapse"] = (out["two_dispatch"]["dispatches_per_step"]
                                / max(out["unified"]["dispatches_per_step"],
                                      1e-12))

    # predicted-vs-measured chunked TPOT through the Scenario backends
    from repro.scenario import compare, run as run_scenarios
    pred = run_scenarios([sc], backend="analytical")[0]
    meas = run_scenarios(
        [sc], backend="engine",
        engine_kw=dict(unified=True, max_slots=args.slots,
                       max_seq=args.max_seq,
                       prefill_rows=args.prefill_rows,
                       page_size=page_size(args, sc),
                       n_requests=args.requests))[0]
    out["chunked_tpot"] = {
        "predicted_fused_s": pred.tpot_s,
        "predicted_two_dispatch_s":
            (pred.extra.get("chunked_two_dispatch") or {}).get("tpot"),
        "measured_unified_s": meas.tpot_s,
        "compare": compare(pred, meas),
    }
    return out


def compare_tp(sc, args) -> dict:
    """Mesh-sharded unified engine across {tp=1, tp=2, tp=4, pp=2} on the
    same rate x mix sweep: greedy outputs asserted token-identical to the
    tp=1 engine, the one-dispatch/one-transfer-per-step invariant asserted
    per host, and per-step collective count / estimated all-reduce bytes
    recorded next to tokens/s.  Each mesh shape also closes the
    predicted-vs-measured loop: the same ``Scenario`` with its
    ``ParallelismConfig`` runs through the analytical and the engine
    backends and ``compare()`` reports TTFT/TPOT/max-concurrency error —
    the paper's multi-NPU scaling claims (figs 13/16/17) against a live
    sharded run."""
    from repro.core.modelspec import AttnSpec, ModelSpec
    from repro.core.parallelism import ParallelismConfig
    from repro.scenario import compare, run as run_scenarios
    from repro.scenario.engine_backend import lower_model

    n_dev = jax.device_count()
    if n_dev < 2:
        raise SystemExit(
            "--compare-tp needs a >= 2-device mesh; on CPU export "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
            "running")
    # TP-friendly GQA geometry (8 q heads / 4 kv heads): tp=4 still
    # leaves every rank a kv head; bench-tiny's 4/2 cannot shard past 2
    tp_spec = ModelSpec(name="bench-tp", d_model=64, n_layers=2, n_heads=8,
                        n_kv_heads=4, d_head=16, d_ff=128, vocab=256,
                        attn=AttnSpec(kind="full", causal=True))
    sc = sc.replace(model=tp_spec)
    spec, model, params = lower_model(tp_spec)
    ps = page_size(args, sc)
    meshes = [(name, tp, pp) for name, tp, pp in
              [("tp1", 1, 1), ("tp2", 2, 1), ("tp4", 4, 1), ("pp2", 1, 2)]
              if tp * pp <= n_dev]
    out = {"devices": n_dev, "page_size": ps, "n_requests": args.requests,
           "rates": args.rates, "mixes": args.mixes, "meshes": {}}
    outputs: dict[str, list] = {}
    for name, tp, pp in meshes:
        cfg = EngineConfig(max_slots=args.slots, max_seq=args.max_seq,
                           chunk_size=min(args.chunk, args.max_seq),
                           prefill_rows=args.prefill_rows, unified=True,
                           cache_layout="paged", page_size=ps,
                           n_pages=args.n_pages, tp=tp, pp=pp)
        eng = ServeEngine(model, params, cfg, rng=jax.random.key(1))
        # warm the jitted programs so cell 0 isn't all compile time
        eng.serve([Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=2)])
        cells, outs = [], []
        for mix in args.mixes:
            for rate in args.rates:
                cell, reqs = run_cell(eng, spec.vocab, rate, mix,
                                      args.requests, args.max_new,
                                      args.seed)
                cells.append(cell)
                outs.append([list(r.output) for r in reqs])
        outputs[name] = outs
        steps = sum(c["steps"] for c in cells)
        disp = sum(c["dispatches"] for c in cells)
        tx = sum(c["transfers_d2h"] for c in cells)
        # per-host hot-path invariant, preserved on the mesh: exactly ONE
        # jitted dispatch and ONE device->host pull per engine step
        assert disp == steps, (name, disp, steps)
        assert tx == steps, (name, tx, steps)
        gen = sum(c["generated_tokens"] for c in cells)
        wall = sum(c["cell_wall_s"] for c in cells)
        agg = {
            "tp": tp, "pp": pp, "cells": cells,
            "generated_tokens": gen,
            "sweep_wall_s": wall,
            "tokens_per_s": gen / wall if wall > 0 else 0.0,
            "ttft_s_mean": float(np.mean([c["ttft_s_mean"]
                                          for c in cells])),
            "tpot_s_mean": float(np.mean([c["tpot_s_mean"]
                                          for c in cells])),
            "dispatches_per_step": disp / max(steps, 1),
            "transfers_per_step": tx / max(steps, 1),
            "collectives_per_step": (sum(c.get("collectives", 0)
                                         for c in cells) / max(steps, 1)),
            "allreduce_bytes_per_step": (sum(c.get("collective_bytes", 0)
                                             for c in cells)
                                         / max(steps, 1)),
            "outputs_sha1": hashlib.sha1(repr(outs).encode()).hexdigest(),
        }
        # predicted-vs-measured at this mesh shape (the Scenario carries
        # the ParallelismConfig; the engine backend lowers it to tp/pp)
        sc_m = sc.replace(parallelism=ParallelismConfig(tp=tp, pp=pp))
        pred = run_scenarios([sc_m], backend="analytical")[0]
        meas = run_scenarios(
            [sc_m], backend="engine",
            engine_kw=dict(unified=True, max_slots=args.slots,
                           max_seq=args.max_seq,
                           prefill_rows=args.prefill_rows, page_size=ps,
                           n_requests=args.requests))[0]
        agg["analytical"] = {
            "predicted_ttft_s": pred.ttft_s,
            "predicted_tpot_s": pred.tpot_s,
            "predicted_max_concurrency": pred.max_concurrency,
            "measured_ttft_s": meas.ttft_s,
            "measured_tpot_s": meas.tpot_s,
            "measured_max_concurrency": meas.max_concurrency,
            "status": meas.status,
            "compare": compare(pred, meas),
        }
        out["meshes"][name] = agg
    for name in outputs:  # greedy token identity across every mesh shape
        assert outputs[name] == outputs["tp1"], \
            f"{name} diverged from the tp=1 engine on the same workload"
    out["token_identical"] = sorted(outputs)
    return out


def run_trace(sc, args) -> dict:
    """Replay one bursty multi-tenant multi-turn trace through the
    prefix-cache engine and through an identical cache-off engine holding
    the SAME page budget, assert the greedy outputs are token-identical
    per request, and report SLO attainment / goodput / hit rate for both.

    ``args.trace`` is either ``True`` (generate a trace from the flags and
    seed) or a path to a ``trace_to_json`` file; ``--trace-out`` writes the
    trace used, so a generated trace can be replayed elsewhere.
    """
    import dataclasses

    from repro.scenario.engine_backend import lower_model
    from repro.serving import (TraceConfig, generate_trace, replay,
                               smoke_config, trace_from_json, trace_to_json)

    spec, model, params = lower_model(sc.model)
    tcfg = None
    if isinstance(args.trace, str):
        trace = trace_from_json(Path(args.trace).read_text())
    else:
        tcfg = TraceConfig(n_requests=args.requests, seed=args.seed,
                           vocab=spec.vocab)
        if args.smoke:
            tcfg = smoke_config(tcfg)
        trace = generate_trace(tcfg)
    if args.trace_out:
        Path(args.trace_out).write_text(trace_to_json(trace, tcfg))
        print(f"wrote {args.trace_out}", file=sys.stderr)

    ps = page_size(args, sc)
    out = {"n_trace_requests": len(trace),
           "n_turns": max((t.turn for t in trace), default=0) + 1,
           "tenants": sorted({t.tenant for t in trace}),
           "page_size": ps, "max_slots": args.slots,
           "max_seq": args.max_seq, "n_pages": args.n_pages,
           "ttft_slo_s": args.ttft_slo, "tpot_slo_s": args.tpot_slo,
           "trace_config": dataclasses.asdict(tcfg) if tcfg else None}
    outputs: dict[str, list] = {}
    for mode in ("prefix_off", "prefix_on"):
        cfg = EngineConfig(max_slots=args.slots, max_seq=args.max_seq,
                           chunk_size=min(args.chunk, args.max_seq),
                           prefill_rows=args.prefill_rows, unified=True,
                           cache_layout="paged", page_size=ps,
                           n_pages=args.n_pages,
                           prefix_cache=(mode == "prefix_on"))
        eng = ServeEngine(model, params, cfg, rng=jax.random.key(1))
        # warm the jitted programs so request 0 isn't all compile time
        eng.serve([Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=2)])
        eng.metrics = EngineMetrics()
        eng.pager.peak_in_use = eng.pager.pages_in_use
        summ, reqs = replay(eng, trace, ttft_slo_s=args.ttft_slo,
                            tpot_slo_s=args.tpot_slo,
                            time_scale=args.time_scale)
        assert all(r.state == "done" for r in reqs)
        outputs[mode] = [list(r.output) for r in reqs]
        out[mode] = dataclasses.asdict(summ)
    # the cache must never change what is decoded, only when: per-request
    # greedy outputs are compared exactly, not just digested
    assert outputs["prefix_off"] == outputs["prefix_on"], \
        "prefix-cache engine diverged from the cache-off engine"
    out["token_identity"] = True
    on, off = out["prefix_on"], out["prefix_off"]
    out["hit_rate"] = on["engine"].get("prefix_hit_rate", 0.0)
    out["ttft_win"] = off["ttft_mean_s"] / max(on["ttft_mean_s"], 1e-12)
    out["goodput_win"] = (on["goodput_tok_s"]
                          / max(off["goodput_tok_s"], 1e-12))
    out["slo_attainment_gain"] = (on["slo_attainment"]
                                  - off["slo_attainment"])
    return out


def compare_prefix(sc, args) -> dict:
    """The trace-replay cache-on-vs-off comparison (:func:`run_trace`)
    plus the analytical loop closed over the prefix cache: the Scenario is
    lowered to a prefix-cache engine run (multi-tenant shared templates),
    its MEASURED hit rate is fed back into
    ``Optimizations.prefix_hit_rate``, and ``repro.scenario.compare``
    reports the predicted-vs-measured TTFT and max-concurrency error —
    alongside the hit=0 prediction so the artifact shows how much of the
    prefill/capacity win the model attributes to the cache."""
    import dataclasses

    from repro.scenario import compare, run as run_scenarios

    out = run_trace(sc, args)
    ps = page_size(args, sc)
    # the analytical loop runs in monolithic mode: chunked reports call
    # out TPOT only, while the prefix cache's headline prediction is the
    # TTFT of the one prefill pass it discounts
    sc_run = sc.replace(mode="monolithic", opt=dataclasses.replace(
        sc.opt, paged_kv=True, kv_page_size=ps, prefix_hit_rate=0.0))
    meas = run_scenarios(
        [sc_run], backend="engine",
        engine_kw=dict(prefix_cache=True, max_slots=args.slots,
                       max_seq=args.max_seq,
                       prefill_rows=args.prefill_rows, page_size=ps,
                       n_requests=args.requests))[0]
    hit = float((meas.extra.get("engine") or {}).get("prefix_hit_rate", 0.0))
    pred = run_scenarios(
        [sc_run.replace(opt=dataclasses.replace(
            sc_run.opt, prefix_hit_rate=hit))],
        backend="analytical")[0]
    pred0 = run_scenarios([sc_run], backend="analytical")[0]
    errs = compare(pred, meas)
    out["analytical"] = {
        "status": meas.status,
        "measured_hit_rate": hit,
        "predicted_ttft_s": pred.ttft_s,
        "predicted_ttft_s_no_cache": pred0.ttft_s,
        "measured_ttft_s": meas.ttft_s,
        "predicted_max_concurrency": pred.max_concurrency,
        "predicted_max_concurrency_no_cache": pred0.max_concurrency,
        "measured_max_concurrency": meas.max_concurrency,
        "ttft_error": errs.get("ttft_s"),
        "max_concurrency_error": errs.get("max_concurrency"),
        "compare": errs,
    }
    return out


def compare_disagg(sc, args) -> dict:
    """Unified colocated engine vs the live two-pool ``DisaggCluster`` on
    an identical request set: the same prompts are served by one unified
    token-packed engine (prefill and decode share slots and pages) and by
    the disaggregated cluster (prefill pool -> page-granular KV migration
    -> decode pool), greedy outputs are asserted token-identical, and
    both sides report TTFT / TPOT / goodput.  The cluster runs over an
    accounting-only simulated link (``time_scale=0``), so the migration
    stats price the analytical inter-pool bandwidth term without gating
    wall-clock.  The closed loop then runs the *same* Scenario in
    ``mode="disaggregated"`` through the analytical backend and the
    engine backend and reports the ``repro.scenario.compare`` error,
    including the predicted-vs-measured KV-migration seconds."""
    import dataclasses

    from repro.scenario.engine_backend import lower_model
    from repro.serving import (ClusterMetrics, DisaggCluster,
                               DisaggClusterConfig, MigrationLink)

    spec, model, params = lower_model(sc.model)
    ps = page_size(args, sc)
    chunk = min(args.chunk, args.max_seq)
    rng = np.random.default_rng(args.seed)
    lo, hi = MIXES["mixed"]
    prompts = [[int(t) for t in rng.integers(0, spec.vocab, size=int(r))]
               for r in rng.integers(lo, hi, size=args.requests)]

    def requests():
        # engines mutate Request in place: each side gets fresh clones
        return [Request(prompt=list(p), max_new_tokens=args.max_new)
                for p in prompts]

    out = {"n_requests": args.requests, "max_new_tokens": args.max_new,
           "max_seq": args.max_seq, "page_size": ps, "chunk_size": chunk,
           "prefill_rows": args.prefill_rows, "decode_slots": args.slots,
           "link_bandwidth_B_s": args.link_bw}
    outputs: dict[str, list] = {}

    cfg = EngineConfig(max_slots=args.slots, max_seq=args.max_seq,
                       chunk_size=chunk, prefill_rows=args.prefill_rows,
                       unified=True, cache_layout="paged", page_size=ps,
                       n_pages=args.n_pages)
    eng = ServeEngine(model, params, cfg, rng=jax.random.key(1))
    eng.serve([Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=2)])
    eng.metrics = EngineMetrics()
    eng.pager.peak_in_use = eng.pager.pages_in_use
    reqs = eng.serve(requests())
    assert all(r.state == "done" for r in reqs)
    outputs["unified"] = [list(r.output) for r in reqs]
    cell = eng.metrics.summary(reqs)
    cell.update(eng.kv_stats())
    cell["goodput_tok_s"] = cell["tokens_per_s"]
    out["unified"] = cell

    ccfg = DisaggClusterConfig(
        max_seq=args.max_seq, page_size=ps, chunk_size=chunk,
        prefill_rows=args.prefill_rows, decode_slots=args.slots,
        link=MigrationLink(bandwidth=args.link_bw))
    cl = DisaggCluster(model, params, ccfg, rng=jax.random.key(1))
    cl.serve([Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=2)])
    # the warmup compiled both pools' programs and pushed one migration
    # through the link: re-base every lifetime counter so the measured
    # window covers only the benchmark requests
    cl.metrics = ClusterMetrics()
    for e in (cl.prefill_eng, cl.decode_eng):
        e.metrics = EngineMetrics()
        e.pager.peak_in_use = e.pager.pages_in_use
    ch = cl.channel
    ch.migrations = ch.migrated_pages = ch.migrated_bytes = 0
    ch.transfer_s_total = ch.wait_s_total = 0.0
    ch.pending_peak = 0
    cl.migration_s.clear()
    creqs = cl.serve(requests())
    assert all(r.state == "done" for r in creqs)
    outputs["disaggregated"] = [list(r.output) for r in creqs]
    dcell = cl.summary(creqs)
    dcell["kv"] = cl.kv_stats()
    out["disaggregated"] = dcell

    # greedy token identity: migration must never change what is decoded
    assert outputs["unified"] == outputs["disaggregated"], \
        "disaggregated cluster diverged from the unified engine"
    out["token_identity"] = True
    out["goodput_win"] = (dcell["goodput_tok_s"]
                          / max(out["unified"]["goodput_tok_s"], 1e-12))

    # predicted-vs-measured through the Scenario backends, including the
    # KV-migration term the disaggregated mode adds to TTFT
    from repro.scenario import compare, run as run_scenarios
    sc_d = sc.replace(mode="disaggregated", opt=dataclasses.replace(
        sc.opt, paged_kv=True, kv_page_size=ps))
    pred = run_scenarios([sc_d], backend="analytical")[0]
    meas = run_scenarios(
        [sc_d], backend="engine",
        engine_kw=dict(max_slots=args.slots, max_seq=args.max_seq,
                       page_size=ps, n_requests=args.requests))[0]
    errs = compare(pred, meas)
    ex = meas.extra or {}
    out["analytical"] = {
        "status": meas.status,
        "predicted_ttft_s": pred.ttft_s,
        "measured_ttft_s": meas.ttft_s,
        "predicted_tpot_s": pred.tpot_s,
        "measured_tpot_s": meas.tpot_s,
        "predicted_kv_transfer_s": ex.get("predicted_kv_transfer_s"),
        "measured_kv_transfer_s": ex.get("measured_kv_transfer_s"),
        "plan": ex.get("plan"),
        "colocated": ex.get("colocated"),
        "compare": errs,
    }
    return out


def compare_spec(sc, args) -> dict:
    """Batched speculative decoding inside the unified engine, measured
    three ways on identical prompts (self-draft, so greedy acceptance is
    ~1.0 and token identity is exact):

      * ``spec_off`` — the unified engine with ``n_spec=0`` (one target
        pass per decode token),
      * ``spec_on`` — the same engine with ``n_spec=K``: every decode slot
        runs a K+1-token verify segment and the whole draft/verify round
        is ONE jitted dispatch + ONE device->host transfer per step
        (asserted below, per engine),
      * ``batch1_decoder`` — the retained ``SpeculativeDecoder`` oracle,
        one request at a time (the pre-batching reference).

    Greedy outputs are asserted token-identical between spec_on and
    spec_off.  The fig-11 predicted-vs-measured loop then runs the same
    Scenario in ``mode='speculative'`` through the analytical backend —
    with ``gamma`` set to the MEASURED acceptance rate — and the engine
    backend, and ``repro.scenario.compare`` reports the TPOT error."""
    import dataclasses

    from repro.scenario.engine_backend import lower_model
    from repro.serving.speculative import SpeculativeDecoder

    spec, model, params = lower_model(sc.model)
    k = args.n_spec
    ps = page_size(args, sc)
    rng = np.random.default_rng(args.seed)
    lo, hi = MIXES["mixed"]
    prompts = [[int(t) for t in rng.integers(0, spec.vocab, size=int(r))]
               for r in rng.integers(lo, hi, size=args.requests)]

    def requests():
        # engines mutate Request in place: each side gets fresh clones
        return [Request(prompt=list(p), max_new_tokens=args.max_new)
                for p in prompts]

    out = {"n_spec": k, "draft": "self", "n_requests": args.requests,
           "max_new_tokens": args.max_new, "max_slots": args.slots,
           "max_seq": args.max_seq, "page_size": ps,
           "prefill_rows": args.prefill_rows}
    outputs: dict[str, list] = {}
    for mode in ("spec_off", "spec_on"):
        on = mode == "spec_on"
        cfg = EngineConfig(max_slots=args.slots, max_seq=args.max_seq,
                           chunk_size=min(args.chunk, args.max_seq),
                           prefill_rows=args.prefill_rows, unified=True,
                           cache_layout="paged", page_size=ps,
                           n_pages=args.n_pages, n_spec=k if on else 0)
        eng = ServeEngine(model, params, cfg, rng=jax.random.key(1),
                          draft_model=model if on else None,
                          draft_params=params if on else None)
        # warm the jitted programs so the timed window is steady-state
        eng.serve([Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=2)])
        eng.metrics = EngineMetrics()
        eng.pager.peak_in_use = eng.pager.pages_in_use
        t0 = time.perf_counter()
        reqs = eng.serve(requests())
        wall = time.perf_counter() - t0
        assert all(r.state == "done" for r in reqs)
        outputs[mode] = [list(r.output) for r in reqs]
        cell = eng.metrics.summary(reqs)
        cell.update(eng.kv_stats())
        # the hot-path contract, WITH speculation riding the packed batch:
        # exactly one jitted dispatch and one device->host pull per step
        assert cell["dispatches"] == cell["steps"] > 0, \
            (mode, cell["dispatches"], cell["steps"])
        assert cell["transfers_d2h"] == cell["steps"], \
            (mode, cell["transfers_d2h"], cell["steps"])
        out[mode] = {
            "wall_s": wall,
            "generated_tokens": cell["generated_tokens"],
            "tokens_per_s": cell["generated_tokens"] / wall if wall else 0.0,
            "tpot_s_mean": cell.get("tpot_s_mean"),
            "ttft_s_mean": cell.get("ttft_s_mean"),
            "steps": cell["steps"],
            "dispatches_per_step": cell["dispatches"] / cell["steps"],
            "transfers_per_step": cell["transfers_d2h"] / cell["steps"],
            "acceptance_rate": cell.get("spec_acceptance_rate", 0.0),
            "tokens_per_window": cell.get("spec_tokens_per_round", 0.0),
            "outputs_sha1": hashlib.sha1(
                repr(outputs[mode]).encode()).hexdigest(),
            "engine": cell,
        }
    # self-draft greedy speculation must not change a single token
    assert outputs["spec_off"] == outputs["spec_on"], \
        "speculative engine diverged from the non-speculative engine"
    out["token_identity"] = True
    out["tokens_per_s_win"] = (out["spec_on"]["tokens_per_s"]
                               / max(out["spec_off"]["tokens_per_s"], 1e-12))
    off_t, on_t = out["spec_off"]["tpot_s_mean"], out["spec_on"]["tpot_s_mean"]
    out["tpot_win"] = (off_t / on_t) if off_t and on_t else None

    # the batch-1 oracle: same K, same self-draft, one request at a time
    sd = SpeculativeDecoder(model, params, model, params, n_spec=k,
                            max_seq=args.max_seq, temperature=1e-3,
                            rng=jax.random.key(9))
    sd.generate(prompts[0], 4)  # warm
    gen = 0
    t0 = time.perf_counter()
    for p in prompts:
        d = SpeculativeDecoder(model, params, model, params, n_spec=k,
                               max_seq=args.max_seq, temperature=1e-3,
                               rng=jax.random.key(args.seed))
        gen += len(d.generate(p, args.max_new))
    wall = time.perf_counter() - t0
    out["batch1_decoder"] = {
        "generated_tokens": gen, "wall_s": wall,
        "tokens_per_s": gen / wall if wall else 0.0,
        "acceptance_rate": d.stats.acceptance_rate,
    }
    out["batch1_win"] = (out["spec_on"]["tokens_per_s"]
                         / max(out["batch1_decoder"]["tokens_per_s"], 1e-12))

    # fig-11 closed loop: the measured acceptance becomes the analytical
    # gamma, and the same Scenario runs through both backends
    from repro.scenario import SpeculativeSpec, compare, run as run_scenarios
    acc = out["spec_on"]["acceptance_rate"]
    sc_s = sc.replace(mode="speculative",
                      speculative=SpeculativeSpec(draft=sc.model, n=k,
                                                  gamma=acc),
                      opt=dataclasses.replace(sc.opt, paged_kv=True,
                                              kv_page_size=ps))
    pred = run_scenarios([sc_s], backend="analytical")[0]
    meas = run_scenarios(
        [sc_s], backend="engine",
        engine_kw=dict(max_slots=args.slots, max_seq=args.max_seq,
                       prefill_rows=args.prefill_rows, page_size=ps,
                       n_requests=args.requests, seed=args.seed))[0]
    errs = compare(pred, meas)
    out["fig11"] = {
        "gamma": acc,
        "status": meas.status,
        "predicted_tpot_s": pred.tpot_s,
        "measured_tpot_s": meas.tpot_s,
        "predicted_tokens_per_s": pred.throughput_tok_s,
        "measured_tokens_per_s": meas.throughput_tok_s,
        "measured_acceptance": (meas.extra or {}).get("acceptance_rate"),
        "measured_tokens_per_window": (meas.extra or {}).get(
            "tokens_per_pass"),
        "tpot_error": errs.get("tpot_s"),
        "compare": errs,
    }
    return out


def main() -> None:
    use_compile_cache(str(Path(__file__).resolve().parent.parent))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="registry arch (default: inline tiny model)")
    ap.add_argument("--scenario", default=None,
                    help="path to a Scenario JSON; overrides --arch and "
                         "drives the engine's mode/chunk config")
    ap.add_argument("--rates", type=float, nargs="+",
                    default=[2.0, 8.0, 32.0])
    ap.add_argument("--mixes", nargs="+", default=list(MIXES),
                    choices=list(MIXES))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prefill-rows", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged KV layout")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (default: the scenario's "
                         "kv_page_size, else 16)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="page-pool size (default: dense-equivalent)")
    ap.add_argument("--unified", action="store_true",
                    help="serve with the unified token-packed step (one "
                         "jitted dispatch per engine step; implies paged)")
    ap.add_argument("--compare-paged", action="store_true",
                    help="dense-vs-paged capacity comparison under the "
                         "same HBM token budget (skips the rate sweep)")
    ap.add_argument("--compare-unified", action="store_true",
                    help="two-dispatch vs unified-step comparison on the "
                         "rate x mix sweep (token-identity asserted; "
                         "records the tokens/s win and the "
                         "predicted-vs-measured chunked TPOT error)")
    ap.add_argument("--compare-disagg", action="store_true",
                    help="unified colocated engine vs the live two-pool "
                         "disaggregated cluster on identical prompts "
                         "(token-identity asserted; records migration "
                         "traffic, per-pool occupancy and the "
                         "predicted-vs-measured error incl. the "
                         "KV-migration term)")
    ap.add_argument("--link-bw", type=float, default=100e9,
                    help="simulated inter-pool link bandwidth (B/s) for "
                         "--compare-disagg migration accounting")
    ap.add_argument("--compare-spec", action="store_true",
                    help="speculative vs non-speculative unified engine on "
                         "identical prompts (self-draft; token-identity and "
                         "the one-dispatch/one-transfer-per-step invariant "
                         "asserted), plus the batch-1 decoder reference and "
                         "the fig-11 predicted-vs-measured TPOT loop with "
                         "gamma = measured acceptance; skips the rate sweep")
    ap.add_argument("--n-spec", type=int, default=4,
                    help="draft window K for --compare-spec")
    ap.add_argument("--trace", nargs="?", const=True, default=None,
                    metavar="PATH",
                    help="replay a trace (from PATH, or generated from the "
                         "flags+seed when bare) through the prefix-cache "
                         "engine and a cache-off twin on the same page "
                         "budget; greedy outputs are asserted "
                         "token-identical")
    ap.add_argument("--trace-out", default=None,
                    help="write the trace used by --trace/--compare-prefix "
                         "as JSON (round-trips via trace_from_json)")
    ap.add_argument("--compare-prefix", action="store_true",
                    help="--trace replay plus the closed analytical loop: "
                         "the measured hit rate is fed into "
                         "opt.prefix_hit_rate and compare() reports the "
                         "predicted-vs-measured TTFT / max-concurrency "
                         "error")
    ap.add_argument("--ttft-slo", type=float, default=5.0,
                    help="TTFT SLO (s) for trace-replay goodput")
    ap.add_argument("--tpot-slo", type=float, default=1.0,
                    help="TPOT SLO (s) for trace-replay goodput")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="compress (<1) or stretch (>1) trace arrival "
                         "times at replay")
    ap.add_argument("--compare-tp", action="store_true",
                    help="mesh-sharded unified engine across "
                         "{tp=1, tp=2, tp=4, pp=2}: greedy outputs asserted "
                         "token-identical to tp=1, per-step collectives and "
                         "all-reduce bytes recorded, and predicted-vs-"
                         "measured TTFT/TPOT/max-concurrency per mesh shape "
                         "(needs >= 2 devices; on CPU export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep for CI: one rate, two mixes")
    ap.add_argument("--out", default=None, help="write JSON here too")
    args = ap.parse_args()

    if args.smoke:
        args.rates = [16.0]
        args.mixes = ["short", "mixed"]
        args.requests = 6
        args.max_new = 8

    def scenario_for_run():
        """Keep the recorded scenario consistent with the engine: --paged
        (and --unified / --compare-unified, which imply the paged layout)
        promotes the scenario's opt so the JSON never claims a dense
        scenario next to a paged engine run."""
        import dataclasses
        sc = build_scenario(args)
        paged = (args.paged or args.unified or args.compare_unified
                 or args.compare_prefix or args.compare_disagg
                 or args.compare_tp or args.compare_spec
                 or args.trace is not None)
        if paged and not sc.opt.paged_kv:
            sc = sc.replace(opt=dataclasses.replace(
                sc.opt, paged_kv=True, kv_page_size=page_size(args, sc)))
        return sc

    if args.compare_spec:
        sc = scenario_for_run()
        res = compare_spec(sc, args)
        report = {"bench": "serving_bench/speculative",
                  "scenario": sc.to_dict(), "smoke": args.smoke,
                  "result": res}
        text = json.dumps(report, indent=2)
        print(text)
        on, off = res["spec_on"], res["spec_off"]
        print(f"speculative vs non-speculative unified engine "
              f"(token-identical): {res['tokens_per_s_win']:.2f}x tokens/s "
              f"({off['tokens_per_s']:.1f} -> {on['tokens_per_s']:.1f}), "
              f"acceptance {on['acceptance_rate']:.2f}, "
              f"{on['tokens_per_window']:.2f} tokens/window, "
              f"{on['dispatches_per_step']:.0f} dispatch + "
              f"{on['transfers_per_step']:.0f} transfer per step; "
              f"{res['batch1_win']:.1f}x over the batch-1 decoder",
              file=sys.stderr)
        f11 = res["fig11"]
        err = f11.get("tpot_error")
        print(f"fig-11 loop (gamma={f11['gamma']:.2f}): tpot predicted "
              f"{f11['predicted_tpot_s']:.3e} vs measured "
              f"{f11['measured_tpot_s']:.3e} s "
              f"(error {err if err is None else f'{err:.3f}'})",
              file=sys.stderr)
        if args.out:
            Path(args.out).write_text(text)
            print(f"wrote {args.out}", file=sys.stderr)
        return

    if args.compare_prefix or args.trace is not None:
        sc = scenario_for_run()
        res = (compare_prefix if args.compare_prefix else run_trace)(sc, args)
        report = {"bench": ("serving_bench/prefix_cache"
                            if args.compare_prefix
                            else "serving_bench/trace_replay"),
                  "scenario": sc.to_dict(), "smoke": args.smoke,
                  "result": res}
        text = json.dumps(report, indent=2)
        print(text)
        on, off = res["prefix_on"], res["prefix_off"]
        print(f"prefix cache on vs off (token-identical): "
              f"hit rate {res['hit_rate']:.2f}, "
              f"ttft {off['ttft_mean_s'] * 1e3:.1f} -> "
              f"{on['ttft_mean_s'] * 1e3:.1f} ms, "
              f"goodput {off['goodput_tok_s']:.1f} -> "
              f"{on['goodput_tok_s']:.1f} tok/s, "
              f"slo {off['slo_attainment']:.2f} -> "
              f"{on['slo_attainment']:.2f}", file=sys.stderr)
        if args.compare_prefix:
            a = res["analytical"]
            err = {k: (f"{a[k]:.3f}" if a[k] is not None else "n/a")
                   for k in ("ttft_error", "max_concurrency_error")}
            print(f"analytical loop: measured hit "
                  f"{a['measured_hit_rate']:.2f}, "
                  f"ttft error {err['ttft_error']}, "
                  f"max-concurrency error {err['max_concurrency_error']}",
                  file=sys.stderr)
        if args.out:
            Path(args.out).write_text(text)
            print(f"wrote {args.out}", file=sys.stderr)
        return

    if args.compare_disagg:
        sc = scenario_for_run()
        res = compare_disagg(sc, args)
        report = {"bench": "serving_bench/compare_disagg",
                  "scenario": sc.to_dict(), "smoke": args.smoke,
                  "result": res}
        text = json.dumps(report, indent=2)
        print(text)
        d, u, a = res["disaggregated"], res["unified"], res["analytical"]
        print(f"disaggregated vs unified (token-identical): "
              f"{d['migrations']} migrations, "
              f"{d['migrated_bytes']} B over the link, "
              f"ttft {u['ttft_s_mean'] * 1e3:.1f} -> "
              f"{d['ttft_incl_migration_s_mean'] * 1e3:.1f} ms incl. "
              f"migration, goodput {u['goodput_tok_s']:.1f} -> "
              f"{d['goodput_tok_s']:.1f} tok/s", file=sys.stderr)
        mkv = a["measured_kv_transfer_s"]
        pkv = a["predicted_kv_transfer_s"]
        print(f"analytical loop ({a['status']}): "
              f"kv transfer predicted "
              f"{pkv if pkv is None else f'{pkv:.3e}'} s vs measured "
              f"{mkv if mkv is None else f'{mkv:.3e}'} s, "
              f"ttft error {a['compare'].get('ttft_s')}", file=sys.stderr)
        if args.out:
            Path(args.out).write_text(text)
            print(f"wrote {args.out}", file=sys.stderr)
        return

    if args.compare_paged:
        sc = scenario_for_run()
        report = {"bench": "serving_bench/compare_paged",
                  "scenario": sc.to_dict(), "smoke": args.smoke,
                  "result": compare_paged(sc, args)}
        text = json.dumps(report, indent=2)
        print(text)
        if args.out:
            Path(args.out).write_text(text)
            print(f"wrote {args.out}", file=sys.stderr)
        return

    if args.compare_tp:
        sc = scenario_for_run()
        res = compare_tp(sc, args)
        report = {"bench": "serving_bench/compare_tp",
                  "scenario": sc.to_dict(), "smoke": args.smoke,
                  "result": res}
        text = json.dumps(report, indent=2)
        print(text)
        for name, m in res["meshes"].items():
            a = m["analytical"]
            print(f"{name}: {m['tokens_per_s']:.1f} tok/s, "
                  f"{m['collectives_per_step']:.1f} collectives/step, "
                  f"{m['allreduce_bytes_per_step'] / 1024:.1f} KiB "
                  f"all-reduce/step, tpot predicted "
                  f"{a['predicted_tpot_s']:.3e} vs measured "
                  f"{a['measured_tpot_s']:.3e} s", file=sys.stderr)
        print(f"token-identical across meshes: "
              f"{', '.join(res['token_identical'])}", file=sys.stderr)
        if args.out:
            Path(args.out).write_text(text)
            print(f"wrote {args.out}", file=sys.stderr)
        return

    if args.compare_unified:
        sc = scenario_for_run()
        res = compare_unified(sc, args)
        report = {"bench": "serving_bench/compare_unified",
                  "scenario": sc.to_dict(), "smoke": args.smoke,
                  "result": res}
        text = json.dumps(report, indent=2)
        print(text)
        print(f"unified vs two-dispatch: "
              f"{res['tokens_per_s_win']:.2f}x tokens/s, "
              f"{res['two_dispatch']['dispatches_per_step']:.2f} -> "
              f"{res['unified']['dispatches_per_step']:.2f} dispatches/step",
              file=sys.stderr)
        if args.out:
            Path(args.out).write_text(text)
            print(f"wrote {args.out}", file=sys.stderr)
        return

    sc = scenario_for_run()
    spec, eng = build_engine(sc, args)
    # warm the jitted programs so cell 0 isn't all compile time
    eng.serve([Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=2)])

    cells = []
    for mix in args.mixes:
        for rate in args.rates:
            cell, _ = run_cell(eng, spec.vocab, rate, mix, args.requests,
                               args.max_new, args.seed)
            cells.append(cell)
            print(f"  {mix:>6} @ {rate:6.1f} req/s: "
                  f"{cell['tokens_per_s']:8.1f} tok/s | "
                  f"ttft p50 {cell.get('ttft_s_p50', 0) * 1e3:7.1f} ms "
                  f"p95 {cell.get('ttft_s_p95', 0) * 1e3:7.1f} ms | "
                  f"tpot {cell.get('tpot_s_mean', 0) * 1e3:6.1f} ms | "
                  f"occ {cell['mean_slot_occupancy']:.2f}",
                  file=sys.stderr)

    report = {
        "bench": "serving_bench",
        "arch": spec.name,
        "scenario": sc.to_dict(),
        "engine": {"max_slots": eng.cfg.max_slots,
                   "chunk_size": eng.cfg.chunk_size,
                   "prefill_rows": eng.cfg.prefill_rows,
                   "max_seq": eng.cfg.max_seq,
                   "cache_layout": eng.cfg.cache_layout,
                   "unified": eng.cfg.unified,
                   "page_size": eng.cfg.page_size,
                   "n_pages": eng.pager.n_pages if eng.paged else None},
        "smoke": args.smoke,
        "cells": cells,
    }
    text = json.dumps(report, indent=2)
    print(text)
    if args.out:
        Path(args.out).write_text(text)
        print(f"wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
