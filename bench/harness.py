"""One run of one cell: set-up, the measured window, the metrics and the
comparison with the plain reference.

The path under test is ``ServeEngine.submit`` -> ``ServeEngine.step``
with the unified, token-packed, paged step.  The harness reads the
engine's state around each ``step()`` (requests, slot lengths, prefill
rows and positions, pages in use) and never changes it; its own calls
(the arrival wait, ``submit``, ``step`` and its bookkeeping) sit in
``jax.profiler.TraceAnnotation`` spans, so a traced run can say what the
host was doing while the device idled.

Times: a request is timed from when it was due (open loop: its arrival;
closed loop: the moment its client's previous request finished), and a
token at the end of the ``step()`` that returned it (the step pulls its
sampled tokens to the host, so the device work is done).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from . import model as bm
from .traffic import Mix

ROOT = os.path.dirname(bm.bench_root())


# ---------------------------------------------------------------------------
# the cell table
# ---------------------------------------------------------------------------

def load_cell(root: str, name: str) -> dict:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its config,
    mix and metric lists resolved by name."""
    bench = bm.load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = bm.load_json(os.path.join(root, conf["file"]))
    mix = bm.load_json(os.path.join(root, "bench", "traffic",
                                    cell["traffic"] + ".json"))

    def applies(m):
        return "workloads" not in m or name in m["workloads"]
    return {"cell": cell, "config": cfg, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def metric_reader(root: str, name: str):
    """``bench/metrics/<name>.py``'s ``read(run) -> float | None``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------

@dataclass
class Req:
    k: int
    due: float
    engine: object  # the submitted repro.serving.Request
    submit: float | None = None
    packed: float | None = None  # start of the first step that packed it
    token_t: list = field(default_factory=list)
    done_t: float | None = None


@dataclass
class Step:
    t0: float
    t1: float
    mixed: bool
    decode: list  # [(1, kv_len)] decode segments
    prefill: list  # [(q_len, kv_len)] prefill segments
    sampled: int  # segments whose sampled token is used
    pages: int  # pages in use after the step
    t_pack: int  # packed rows of the profile that ran
    preempted: int


@dataclass
class Run:
    """Everything a metric reader may read."""
    name: str
    config: dict
    mix: dict
    chips: int
    setup_s: float
    t0: float  # window start (perf_counter)
    t_end: float  # the window's nominal end
    window_s: float
    reqs: list
    steps: list  # steps of the window, in order
    usable_pages: int
    peak: dict
    itemsize: int = 2
    trace: object = None  # bench.trace.Summary of a traced run

    @property
    def window_end(self) -> float:
        return self.t0 + self.window_s


# ---------------------------------------------------------------------------
# driving the engine
# ---------------------------------------------------------------------------

class Driver:
    """Feeds one engine from one mix and records what it does."""

    def __init__(self, eng, mix: Mix, chunk: int):
        self.eng, self.mix, self.chunk = eng, mix, chunk
        self.reqs: list[Req] = []
        self.inflight: dict[int, Req] = {}  # engine rid -> record
        self.steps: list[Step] = []
        self.next_k = 0

    def submit(self, due: float, now: float) -> Req:
        from repro.serving import Request
        from repro.serving.sampling import SamplingConfig
        prompt, n_out = self.mix.request(self.next_k)
        r = Request(prompt=prompt, max_new_tokens=n_out,
                    sampling=SamplingConfig(temperature=0.0))
        with jax.profiler.TraceAnnotation("bench.submit"):
            self.eng.submit(r)
        rec = Req(k=self.next_k, due=due, engine=r, submit=now)
        self.next_k += 1
        self.reqs.append(rec)
        self.inflight[r.rid] = rec
        return rec

    def step(self, record: bool) -> list[Req]:
        """One ``engine.step()``; returns the requests it finished."""
        eng = self.eng
        active = {s: int(eng._lengths[s]) for s in eng.active}
        rows = [(len(r.prompt) + len(r.output), eng._prefill_pos[row])
                for row, r in eng._prefills.items()]
        queued = {r.rid: len(r.prompt) + len(r.output) for r in eng.queue}
        pre = eng.metrics.preemptions
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.observe"):
            done = []
            for rec in list(self.inflight.values()):
                r = rec.engine
                if rec.packed is None and r.state != "queued":
                    rec.packed = t0
                rec.token_t += [t1] * (len(r.output) - len(rec.token_t))
                if r.state == "done":
                    rec.done_t = t1
                    del self.inflight[r.rid]
                    done.append(rec)
            if record:
                admitted = [n for rid, n in queued.items()
                            if self._by_rid(rid).engine.state != "queued"]
                rows += [(n, 0) for n in admitted]
                prefill = [(min(self.chunk, n - lo), lo
                            + min(self.chunk, n - lo)) for n, lo in rows]
                decode = [(1, n + 1) for n in active.values()]
                completing = sum(kv >= n for (_, kv), (n, _)
                                 in zip(prefill, rows))
                mixed = bool(prefill)
                cfg = eng.cfg
                self.steps.append(Step(
                    t0=t0, t1=t1, mixed=mixed, decode=decode,
                    prefill=prefill, sampled=len(decode) + completing,
                    pages=eng.pager.pages_in_use,
                    t_pack=eng.t_pack if mixed else cfg.max_slots,
                    preempted=eng.metrics.preemptions - pre))
        return done

    def _by_rid(self, rid: int) -> Req:
        return self.inflight.get(rid) or next(
            q for q in self.reqs if q.engine.rid == rid)

    @property
    def busy(self) -> bool:
        return self.eng.busy


def warm_up(eng, vocab: int, chunk: int, seed: int) -> None:
    """Compile both step profiles: a prompt one chunk and a bit long (the
    mixed step, with a decode beside its second chunk) and a short one,
    each decoding a few tokens (the decode-only step)."""
    from repro.serving import Request
    from repro.serving.sampling import SamplingConfig
    rng = np.random.default_rng([int(w) for w in bm.seed_words(seed)]
                                + [1 << 29])
    for n, out in ((16, 4), (chunk + 16, 3)):
        eng.submit(Request(prompt=rng.integers(0, vocab, n).tolist(),
                           max_new_tokens=out,
                           sampling=SamplingConfig(temperature=0.0)))
    while eng.busy:
        eng.step()


class CompileCounter:
    """Counts jaxpr traces and backend compiles while ``on``."""

    def __init__(self):
        self.on, self.traces, self.compiles = False, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if not self.on:
            return
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def window(drv: Driver, mix: dict, t0: float, seconds: float,
           dues: list[float]) -> float:
    """Drive the engine for ``seconds`` from ``t0``; returns the window's
    length: the nominal end, or later where the last step started before
    it ran over.  Open loop: ``dues`` (relative to t0, ascending) are
    submitted as they come due; closed loop: every finished request's
    client sends its next one at once."""
    t_end = t0 + seconds
    closed = mix["loop"] == "closed"
    i = 0
    last = t0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        while i < len(dues) and t0 + dues[i] <= now:
            drv.submit(t0 + dues[i], now)
            i += 1
        if not drv.busy:
            nxt = t0 + dues[i] if i < len(dues) else t_end
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt, t_end) - now))
            continue
        done = drv.step(record=True)
        last = time.perf_counter()
        if closed:
            for rec in done:
                drv.submit(rec.done_t, last)
    return max(t_end, last) - t0


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def require_chips(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX found {devs[0].platform} "
                         "devices); the benchmark never runs on the CPU")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def log(msg: str) -> None:
    print(msg, flush=True)


def build(cell: dict, seed: int):
    """The engine under test with the seed's weights, and its Mix."""
    import jax.numpy as jnp
    from repro.models import build_model
    from repro.serving import EngineConfig, ServeEngine
    from repro.serving import sharded as shard
    cfg, mixd = cell["config"], cell["mix"]
    spec = bm.model_spec(cfg, cell["cell"]["config"])
    model = build_model(spec, param_dtype=jnp.bfloat16,
                        compute_dtype=jnp.bfloat16)
    tp = int(cfg.get("serving", {}).get("tp", 1))
    mesh = pspecs = None
    if tp > 1:
        mesh = shard.make_engine_mesh(tp, 1)
        pspecs = shard.param_pspecs(model, tp, 1)
    params = bm.program_params(cfg, model, seed, mesh, pspecs)
    eng = ServeEngine(model, params, EngineConfig(
        cache_layout="paged", unified=True, tp=tp, **mixd["engine"]),
        rng=jax.random.key(0))
    return eng, Mix(mixd, seed, cfg["vocab_size"])


def sample_for_check(drv: Driver, t0: float, n: int, seed: int) -> list:
    """Requests the window finished, drawn from the seed, the longest
    among them; where it finished fewer than ``n``, the longest of those
    still in flight fill the rest with the tokens they were served."""
    fin = [r for r in drv.reqs if r.done_t is not None and r.done_t >= t0]
    fin.sort(key=lambda r: (-(len(r.engine.prompt) + len(r.engine.output)),
                            r.k))
    picked = fin[:1]
    rest = fin[1:]
    rng = np.random.default_rng([int(w) for w in bm.seed_words(seed)]
                                + [1 << 28])
    for j in rng.permutation(len(rest))[:max(0, n - 1)]:
        picked.append(rest[int(j)])
    if len(picked) < n:
        live = [r for r in drv.reqs if r.done_t is None and r.engine.output]
        live.sort(key=lambda r: (-len(r.engine.output), r.k))
        picked += live[:n - len(picked)]
    return [(list(r.engine.prompt), list(r.engine.output)) for r in picked]


def free_engine(eng) -> None:
    """Delete the engine's device buffers: the reference runs after the
    window and must not share the chip with the program's state."""
    for leaf in jax.tree.leaves((eng.params, eng.cache)):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
    gc.collect()


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             t_process: float, devices=None, peak=None,
             control: bool = False) -> dict:
    """One run.  ``devices``/``peak`` stand in for the chip look and the
    peaks table only where a test drives a run without a chip."""
    cell = load_cell(root, name)
    chips = int(cell["cell"]["chips"])
    devs = devices if devices is not None else require_chips(chips)
    from repro.launch.runtime import use_compile_cache
    log(f"bench: compile cache {use_compile_cache(root)}")
    log(f"bench: device {devs[0].platform} {devs[0].device_kind} "
        f"x{len(devs)}")
    from .peaks import peaks
    peak = peak if peak is not None else peaks(devs[0].device_kind)
    counter = CompileCounter()
    mixd = cell["mix"]
    eng, mix = build(cell, seed)
    chunk = eng.cfg.chunk_size
    drv = Driver(eng, mix, chunk)
    t = time.perf_counter()
    warm_up(eng, cell["config"]["vocab_size"], chunk, seed)
    log(f"bench: weights+engine {t - t_process:.3f} s, warm-up "
        f"{time.perf_counter() - t:.3f} s")
    # pre-window traffic, part of set-up
    dues: list[float] = []
    if mixd["loop"] == "closed":
        now = time.perf_counter()
        for _ in range(int(mixd["clients"])):
            drv.submit(now, now)
        if mixd.get("fill_before_window"):
            while eng._prefills or eng.queue:
                for rec in drv.step(record=False):
                    drv.submit(rec.done_t, time.perf_counter())
            drv.step(record=False)  # and one decode-only step
    else:
        dues = mix.due_times(seconds)
        pre_s = float(mixd.get("preroll_s", 0.0))
        pre = [d + pre_s for d in dues if d < 0]
        dues = [d for d in dues if d >= 0]
        if pre:  # the pre-roll: the same traffic, unmeasured
            window(drv, mixd, time.perf_counter(), pre_s, pre)
            drv.steps.clear()
    tracer = None
    if trace:
        tracer = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tracer)
    counter.on = True
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    with jax.profiler.TraceAnnotation("bench.window"):
        window_s = window(drv, mixd, t0, seconds, dues)
    counter.on = False
    summary = None
    if trace:
        jax.profiler.stop_trace()
        from . import trace as btrace
        t = time.perf_counter()
        summary = btrace.reduce_dir(tracer, chips)
        shutil.rmtree(tracer, ignore_errors=True)
        log(f"bench: trace reduced in {time.perf_counter() - t:.3f} s")
    if counter.compiles or counter.traces:
        raise SystemExit(f"bench: {counter.traces} traces and "
                         f"{counter.compiles} compiles inside the window")
    run = Run(name=name, config=cell["config"], mix=mixd, chips=chips,
              setup_s=setup_s, t0=t0, t_end=t0 + seconds, window_s=window_s,
              reqs=drv.reqs, steps=drv.steps,
              usable_pages=eng.pager.usable_pages, peak=peak, trace=summary)
    mem = memory_peak(devs[:chips])
    report_lines(run)
    check = mixd["check"]
    samples = sample_for_check(drv, t0, int(check["requests"]), seed)
    free_engine(eng)
    del eng, drv.eng
    log_prediction(cell)
    from .reference import served_gaps
    t = time.perf_counter()
    got = served_gaps(cell["config"], seed, samples,
                      mixd["engine"]["max_seq"], int(check["requests"]),
                      int(mixd["output"]["hi"]), control=control)
    limit = float(cell["config"]["correct"]["max_logit_gap"])
    log(f"bench: reference over {len(samples)} requests, {got['tokens']} "
        f"served tokens, {time.perf_counter() - t:.3f} s")
    metrics = {}
    for m in (cell["per_layer"] if trace else cell["end_to_end"]):
        value = metric_reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": bool(got["tokens"] > 0 and got["gap"] <= limit),
           "attempted": attempted(run), "failed": failed(run),
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    if control:
        out["control_gap"] = got["control_gap"]
    out["check"] = {"max_logit_gap": {"value": got["gap"], "limit": limit},
                    "served_tokens_compared": {"value": got["tokens"],
                                               "limit": 1}}
    return out


def attempted(run: Run) -> int:
    """Requests the window had to serve: due in it, or in flight at its
    start."""
    return sum(1 for r in run.reqs
               if r.due < run.window_end and (r.done_t is None
                                              or r.done_t >= run.t0))


def failed(run: Run) -> int:
    """Requests the engine ended short of their tokens in the window."""
    return sum(1 for r in run.reqs
               if r.done_t is not None and r.done_t >= run.t0
               and len(r.engine.output) < r.engine.max_new_tokens
               and r.engine.eos_id is None)


def report_lines(run: Run) -> None:
    """Counts for the record, on lines before the result."""
    st = run.steps
    n_mixed = sum(s.mixed for s in st)
    done = [r for r in run.reqs if r.done_t is not None
            and r.done_t >= run.t0]
    first = [r.token_t[0] - r.due for r in run.reqs
             if r.token_t and r.token_t[0] >= run.t0]
    lags = [r.submit - r.due for r in run.reqs if r.due >= run.t0]
    log(f"bench: window {run.window_s:.3f} s, {len(st)} steps "
        f"({n_mixed} mixed), {sum(len(s.decode) for s in st)} decode "
        f"segments, {len(done)} requests finished, "
        f"{sum(s.preempted for s in st)} preemptions, "
        f"{sum(r.packed is None for r in run.reqs)} requests never packed")
    if first:
        log(f"bench: time to first token, every request whose first "
            f"token came in the window: n={len(first)} median "
            f"{1e3 * float(np.median(first)):.1f} ms max "
            f"{1e3 * max(first):.1f} ms")
    if lags:
        log(f"bench: generator lag: n={len(lags)} max "
            f"{1e3 * max(lags):.3f} ms")
    if st:  # a host stall inside one step shows here
        s = max(st, key=lambda s: s.t1 - s.t0)
        log(f"bench: slowest step {1e3 * (s.t1 - s.t0):.1f} ms "
            f"({'mixed' if s.mixed else 'decode'}), "
            f"{s.t0 - run.t0:.3f} s into the window")


def log_prediction(cell: dict) -> None:
    try:
        from .genz import predict
        log("bench: genz " + json.dumps(predict(cell)))
    except Exception as e:  # noqa: BLE001 - a prediction never fails a run
        log(f"bench: genz prediction failed: {type(e).__name__}: {e}")


def main(argv: list[str], t_process: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_process)
    for k, v in out["check"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
